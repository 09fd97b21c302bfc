"""Double-buffered host→device batch prefetch (DESIGN.md §6).

The partition-major batch is the ONLY bulk host→device transfer the
device-resident step loop makes (k·mb unique sequences — the fused step
computes only those; the spmd backend replicates them on device).
``DevicePrefetcher`` overlaps even that: batch t+1 is materialized (host
numpy) AND uploaded (``jax.device_put``) on a background thread while the
consumer runs step t, so the step never waits on batch generation or the
wire.  Host batch builders are numpy-bound
and the jitted step blocks in XLA — both release the GIL, so the overlap
is real even in-process.

``DevicePrefetcher`` is data-source agnostic: anything exposing
``batch(step) -> pytree`` (e.g. :class:`~repro.data.pipeline.SyntheticData`)
works, and the yielded leaves are committed device arrays the engine
consumes without further copies.

Failure propagation (DESIGN.md §11): a raising ``batch()`` on the worker
thread ships a poison pill through the queue and is re-raised on the
consumer thread with the ORIGINAL exception and traceback — never a hang,
never a silent early stop.  Conversely, a consumer that abandons the
iterator mid-run (break, exception, generator GC) signals the worker to
stop and joins it, so no thread outlives the loop.
"""

from __future__ import annotations

import queue
import sys
import threading
from typing import Any, Iterator, Protocol

import jax

from repro.obs.trace import NULL_TRACER, Tracer

__all__ = ["DevicePrefetcher"]

class _Poison:
    """Worker-thread failure shipped to the consumer for re-raising."""

    __slots__ = ("exc", "tb")

    def __init__(self, exc: BaseException, tb):
        self.exc = exc
        self.tb = tb


class BatchSource(Protocol):
    def batch(self, step: int) -> Any: ...


class DevicePrefetcher:
    """Iterate ``(step, device_batch)`` over ``[start, stop)`` with one
    batch of lookahead built on a worker thread: while the consumer runs
    step t, the thread generates and uploads batch t+1 (double buffering —
    one slot in flight keeps peak memory at ~2 batches, enforced by a
    semaphore the consumer releases as it takes each batch).

    With a tracer installed (DESIGN.md §10), each background
    generate+upload is a ``prefetch.upload`` span on its own wall-clock
    track (tid=1) — overlap with the ``step`` spans on tid=0 is the
    double-buffering working as designed — and the consumer's wait for its
    next batch is a ``prefetch.wait`` span (tid=0): a long one is a
    prefetch stall.
    """

    def __init__(
        self, data: BatchSource, start: int, stop: int, device=None,
        trace: Tracer | None = None,
    ):
        self.data = data
        self.start = start
        self.stop = stop
        self.device = device
        self.tracer = trace if trace is not None else NULL_TRACER

    def _load(self, step: int):
        with self.tracer.span("prefetch.upload", tid=1):
            batch = self.data.batch(step)
            return (
                jax.device_put(batch, self.device) if self.device is not None
                else jax.device_put(batch)
            )

    def _worker(self, q: queue.Queue, slots: threading.Semaphore,
                stop_ev: threading.Event) -> None:
        try:
            for step in range(self.start, self.stop):
                # bound the lookahead WITHOUT blocking forever: an abandoned
                # consumer sets stop_ev instead of draining the queue
                while not slots.acquire(timeout=0.1):
                    if stop_ev.is_set():
                        return
                if stop_ev.is_set():
                    return
                q.put((step, self._load(step)))
        except BaseException as exc:  # noqa: BLE001 - shipped to the consumer
            q.put(_Poison(exc, sys.exc_info()[2]))

    def __iter__(self) -> Iterator[tuple[int, Any]]:
        if self.start >= self.stop:
            return
        q: queue.Queue = queue.Queue()  # unbounded: worker puts never block
        slots = threading.Semaphore(2)  # current + one lookahead
        stop_ev = threading.Event()
        worker = threading.Thread(
            target=self._worker, args=(q, slots, stop_ev),
            name="prefetch", daemon=True,
        )
        worker.start()
        tr = self.tracer
        try:
            # exactly one item per step: the consumer never waits for an
            # end marker, so every ``prefetch.wait`` precedes a step
            for _ in range(self.start, self.stop):
                with tr.span("prefetch.wait"):
                    item = q.get()
                slots.release()  # the previous batch slot is free again
                if isinstance(item, _Poison):
                    # surface the worker's failure as the ORIGINAL exception
                    # with the worker-side traceback attached
                    raise item.exc.with_traceback(item.tb)
                yield item
        finally:
            stop_ev.set()
            worker.join(timeout=5.0)
