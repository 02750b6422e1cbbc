"""Optional ``jax.profiler`` integration.

Two pieces, both inert unless a profile window is open:

  * :func:`start` / :func:`stop` / :func:`profile` — wrap
    ``jax.profiler.start_trace`` so ``--profile-dir`` on either launcher
    captures a device trace (open the run directory in TensorBoard's
    profile plugin or ui.perfetto.dev);
  * :func:`annotate` — a ``jax.profiler.TraceAnnotation`` scope the
    engines place around prefill/decode/verify/restore DISPATCH, the
    loader around batch assembly and its wait, and ``obs.trace.Tracer``
    around each scheduler span, so the host-side phase names line up
    with device timelines on real hardware.  When no window is active
    (the common case, and always in unit tests) it returns a null
    context and costs one attribute read — annotation can never perturb
    numerics or show up in the digest parity tests.
"""
from __future__ import annotations

from contextlib import contextmanager, nullcontext

_active = False


def active() -> bool:
    return _active


def start(profile_dir: str) -> None:
    """Open a ``jax.profiler`` trace window writing to ``profile_dir``:
    device operations and host annotations (host tracer level 2), with
    no per-call Python events (the Python tracer's cost lands on every
    host phase the trace is meant to time)."""
    global _active
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(profile_dir, profiler_options=opts)
    _active = True


def stop() -> None:
    global _active
    if not _active:
        return
    import jax

    jax.profiler.stop_trace()
    _active = False


@contextmanager
def profile(profile_dir=None):
    """Profile window for the duration of the block when ``profile_dir``
    is set; no-op otherwise — lets launchers write
    ``with profiler.profile(args.profile_dir): ...`` unconditionally."""
    if not profile_dir:
        yield
        return
    start(profile_dir)
    try:
        yield
    finally:
        stop()


def annotate(name: str, **args):
    """``jax.profiler.TraceAnnotation(name, **args)`` while a profile
    window is open, else a null context.  ``args`` become the host
    event's stats in the trace."""
    if not _active:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name, **args)
