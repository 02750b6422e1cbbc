"""Async sharded data loading — the paper's input pipeline ("I.P.", Fig. 2a).

The paper's 12.6x end-to-end speedup assumes the input pipeline keeps
every replica fed: watersheds are distributed to nodes, and each node's
device step must never wait on host-side windowing or H2D transfer.  The
seed reproduced the *distribution* (``InputPipeline.shard`` /
``stacked_batches``) but drove it with synchronous python loops, so every
``Engine.step`` paid host batch assembly + transfer on the critical path.

This module closes that gap with two pieces:

  * :class:`DataSource` — a *random-access* batch protocol:
    ``host_batch(step)`` returns the host (numpy) batch for a global step
    index.  Epoch shuffles are seeded deterministically from
    ``(seed, watershed, epoch)`` with ``epoch = step // steps_per_epoch``,
    so the global step doubles as a **resumable stream cursor**: restoring
    a checkpoint and restarting the source at ``step`` replays *exactly*
    the stream an uninterrupted run would have seen — mid-epoch included,
    identically for the Dom-ST and LM paths.

  * :class:`ShardedLoader` — wraps a DataSource and an
    ``Engine``: each host batch is placed onto the mesh with
    ``jax.device_put`` under the engine's ``NamedSharding``s (resolved
    from the same logical-axis rule tables the jitted step uses, so the
    arrays arrive already laid out for ``in_shardings``), and a
    background thread runs ``prefetch`` batches ahead of the consumer
    (depth >= 2 => double buffering).  The training loop collapses to
    ``for batch in loader: state, m = engine.step(state, batch)``.

``prefetch=0`` degrades to the synchronous path (same batches, same
placement, no thread) — the parity baseline for tests.
"""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Dict, Iterator, Optional, Protocol, runtime_checkable

import numpy as np

from repro.obs import profiler


@runtime_checkable
class DataSource(Protocol):
    """Random-access host-batch stream indexed by global step."""

    #: steps per epoch for epoch-shuffled sources; None for endless streams
    steps_per_epoch: Optional[int]

    def host_batch(self, step: int) -> Dict[str, np.ndarray]:
        """The host batch for global step ``step`` (deterministic)."""
        ...


class ShardedLoader:
    """Prefetching device-put iterator over a :class:`DataSource`.

    Args:
      source: the batch stream (``host_batch(step)``).
      engine: a ``repro.train.Engine`` — supplies ``place_batch``, which
        device_puts host arrays under the rule-table shardings (incl. the
        leading watershed axis in stacked mode).
      prefetch: background-queue depth; >= 2 double-buffers H2D transfer
        behind compute, 0 means fully synchronous (no thread).
      start_step: the stream cursor to (re)start from — pass the restored
        ``int(state.step)`` to resume a checkpointed run in place.
      num_steps: batches yielded per ``iter()`` (None = endless).

    ``loader.cursor`` always names the next step to be consumed, so after
    ``state, m = engine.step(state, batch)`` it equals ``int(state.step)``
    and can be checkpointed implicitly with the TrainState.
    """

    _DONE = object()
    _ERR = object()

    def __init__(self, source: DataSource, engine, *, prefetch: int = 2,
                 start_step: int = 0, num_steps: Optional[int] = None):
        self.source = source
        self.engine = engine
        self.prefetch = int(prefetch)
        self.cursor = int(start_step)
        self.num_steps = num_steps

    def _steps(self):
        if self.num_steps is None:
            return itertools.count(self.cursor)
        return range(self.cursor, self.cursor + int(self.num_steps))

    def _place(self, step: int):
        # the producer's work on either path: gather and stack the host
        # batch, then device_put it (a span only inside a profile window)
        with profiler.annotate("train.loader_produce"):
            return self.engine.place_batch(self.source.host_batch(step))

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        if self.prefetch <= 0:                 # synchronous reference path
            for s in self._steps():
                batch = self._place(s)
                self.cursor = s + 1
                yield batch
            return

        steps = self._steps()
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def put(item) -> bool:
            # bounded put that gives up once the consumer has left, so an
            # abandoned iterator never wedges the worker on a full queue
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def worker() -> None:
            try:
                for s in steps:
                    if stop.is_set() or not put((s, self._place(s))):
                        return
            except BaseException as e:         # re-raised on the consumer side
                put((self._ERR, e))
            else:
                put((self._DONE, None))

        t = threading.Thread(target=worker, name="sharded-loader-prefetch",
                             daemon=True)
        t.start()
        try:
            while True:
                with profiler.annotate("train.loader_wait"):
                    tag, item = q.get()
                if tag is self._DONE:
                    return
                if tag is self._ERR:
                    raise item
                self.cursor = tag + 1
                yield item
        finally:
            stop.set()
