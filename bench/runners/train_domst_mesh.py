"""Stacked (IP-D) Dom-ST training with the watershed axis sharded over
every chip of the host: ``train_domst.py``'s job, window and check, on a
(chips, 1) ("data", "model") mesh (``repro.launch.mesh.make_local_mesh``)
in place of its one-chip mesh.  Nothing else differs; the reference still
runs each watershed's replica on one device, and the gaps are maxima over
every watershed of every chip."""
from __future__ import annotations

import contextlib

from bench.runners import train_domst


@contextlib.contextmanager
def local_mesh():
    """``train_domst.Job`` builds its engine on ``make_host_mesh()``;
    inside this block that call returns the mesh over the host's chips."""
    from repro.launch import mesh
    one_chip = mesh.make_host_mesh
    mesh.make_host_mesh = mesh.make_local_mesh
    try:
        yield
    finally:
        mesh.make_host_mesh = one_chip


def run(**kw):
    with local_mesh():
        return train_domst.run(**kw)


def calibrate(*args, **kw):
    with local_mesh():
        return train_domst.calibrate(*args, **kw)
