"""Background-thread double-buffered prefetch for the scanned train loop.

The scanned hot loop alternates two host-side costs: synthesizing the next
(unroll_k, ...) chunk in numpy and blocking on the in-flight scan's aux for
logging/checkpointing.  `Prefetcher` moves the synthesis (and optionally the
device placement) onto a daemon worker thread behind a bounded queue, so the
next chunk is already resident when the current dispatch retires — the loop
then runs at max(host, device) instead of host + device.

Placement: `make_placer(mesh)` resolves each leaf's NamedSharding through
`repro.dist.sharding.logical_spec` (TRAIN_RULES), so chunk leaves land
pre-sharded over the agent torus instead of being replicated by the first
jit invocation.  With ``mesh=None`` it degrades to `jnp.asarray` — the right
thing on a single-device CPU container, and still overlaps H2D with compute
because the transfer happens on the worker thread.

Host spans (`repro.trace`): the worker's synthesis is ``repro.data.produce``
and its placement ``repro.data.place``; the consumer's wait for the queue is
``repro.data.wait``.  Each carries the chunk's first step as ``step``, so a
profiler trace links the chunk a loop consumed to the work that made it.
"""
from __future__ import annotations

import queue
import threading
import weakref
from typing import Any, Callable, Iterable

import jax

from .. import trace as tr
from .pipeline import BATCH_LOGICAL, CHUNK_LOGICAL
from .worker import END as _END
from .worker import bounded_put as _bounded_put
from .worker import shutdown_worker as _shutdown_worker

__all__ = ["Prefetcher", "make_placer", "prefetch_chunks"]


class _Labelled:
    """The source, each item's synthesis inside a ``repro.data.produce``
    span; ``step`` is the label of the item last returned."""

    def __init__(self, source: Iterable, first_step: int, stride: int):
        self._it = iter(source)
        self._next, self._stride = first_step, stride
        self.step = None

    def __iter__(self):
        return self

    def __next__(self):
        with tr.span(tr.DATA_PRODUCE, step=self._next):
            item = next(self._it)
        self.step, self._next = self._next, self._next + self._stride
        return item


def _worker_loop(it: _Labelled, place: Callable | None,
                 stop: threading.Event, q: queue.Queue):
    # Module-level (no Prefetcher reference): the thread must not keep the
    # owning Prefetcher alive, or its GC finalizer could never run.
    end = (_END, None)  # clean end-of-stream
    try:
        for item in it:
            if stop.is_set():
                return
            if place is not None:
                with tr.span(tr.DATA_PLACE, step=it.step):
                    item = place(item)
            _bounded_put(stop, q, (item, None))
    except BaseException as e:  # re-raised by the consumer
        end = (_END, e)
    finally:
        _bounded_put(stop, q, end)


def make_placer(mesh=None, rules=None) -> Callable[[Any], Any]:
    """Build place(batch_or_chunk) -> device-resident pytree.

    Leaves of rank ``len(BATCH_LOGICAL)`` are treated as per-step batches,
    rank ``len(CHUNK_LOGICAL)`` as scanned chunks; anything else (and the
    ``mesh=None`` case) falls back to plain `jnp.asarray`.
    """
    if mesh is None:
        return lambda tree: jax.tree.map(jax.numpy.asarray, tree)

    from jax.sharding import NamedSharding

    from ..dist.sharding import TRAIN_RULES, logical_spec

    rules = TRAIN_RULES if rules is None else rules

    def place_leaf(x):
        ndim = getattr(x, "ndim", None)  # scalars/flags fall back too
        if ndim == len(CHUNK_LOGICAL):
            logical = CHUNK_LOGICAL
        elif ndim == len(BATCH_LOGICAL):
            logical = BATCH_LOGICAL
        else:
            return jax.numpy.asarray(x)
        spec = logical_spec(mesh, x.shape, logical, rules)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return lambda tree: jax.tree.map(place_leaf, tree)


class Prefetcher:
    """Iterate ``source`` on a daemon thread, ``depth`` items ahead.

    ``place`` (e.g. from `make_placer`) runs ON THE WORKER THREAD, so both
    batch synthesis and the host->device transfer overlap the consumer's
    device work.  Iteration ends when the source is exhausted; worker
    exceptions re-raise in the consumer.  `close()` (also via context
    manager / generator ``.close()`` protocol) stops the worker promptly
    even when the queue is full and joins it — no leaked threads.

    Item n is labelled step ``first_step + n * stride`` in the host spans
    (`prefetch_chunks` gives a chunk's first training step).
    """

    def __init__(self, source: Iterable, place: Callable | None = None,
                 depth: int = 2, first_step: int = 0, stride: int = 1):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._exhausted = False
        self._next_step, self._stride = first_step, stride
        self._thread = threading.Thread(
            target=_worker_loop,
            args=(_Labelled(source, first_step, stride), place, self._stop,
                  self._queue),
            name="repro-data-prefetch", daemon=True)
        self._thread.start()
        # Abandoned-iterator safety net: an un-close()d, un-exhausted
        # Prefetcher would leave the worker polling a full queue forever,
        # pinning depth+1 buffered chunks.  GC of the Prefetcher stops it.
        self._finalizer = weakref.finalize(
            self, _shutdown_worker, self._stop, self._queue, self._thread,
            0.2)

    def __iter__(self):
        return self

    # Between polls of the queue, check that the worker is still able to
    # ever satisfy the get: `_worker_loop` posts its END sentinel from a
    # finally, but a thread killed without unwinding (interpreter
    # teardown racing a daemon, an out-of-band kill) posts nothing, and
    # an untimed get() would then park the train loop forever.
    _POLL_S = 1.0

    def __next__(self):
        if self._exhausted or self._stop.is_set():
            raise StopIteration
        with tr.span(tr.DATA_WAIT, step=self._next_step):
            item, err = self._get()
        if err is not None:
            self._exhausted = True
            raise err
        if item is _END:
            self._exhausted = True
            raise StopIteration
        self._next_step += self._stride
        return item

    def _get(self):
        """The next (item, error) the worker posted."""
        while True:
            try:
                return self._queue.get(timeout=self._POLL_S)
            except queue.Empty:
                if self._thread.is_alive():
                    continue
            # Dead worker: drain once more without blocking — it may have
            # posted between the timeout and the liveness check.
            try:
                return self._queue.get_nowait()
            except queue.Empty:
                self._exhausted = True
                raise RuntimeError(
                    "prefetch worker thread died without posting "
                    "end-of-stream; the chunk stream is torn (not an "
                    "exhausted source — those end with a sentinel)"
                ) from None

    def close(self, join_timeout: float = 5.0):
        """Stop the worker and join it; idempotent.

        The stop event is polled between items, so a worker mid-synthesis
        finishes its current item first; if that outlives ``join_timeout``
        the leak is reported rather than silently ignored.
        """
        _shutdown_worker(self._stop, self._queue, self._thread, join_timeout)
        if self._thread.is_alive():
            import warnings
            warnings.warn(
                f"prefetch worker still synthesizing an item after "
                f"{join_timeout}s; it will exit after the current item "
                "(daemon thread, safe at interpreter shutdown)")
        self._exhausted = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def prefetch_chunks(pipeline, unroll_k: int, start_step: int = 0,
                    num_chunks: int | None = None, mesh=None,
                    place: Callable | None = None,
                    depth: int = 2,
                    agent_slice: tuple[int, int] | None = None) -> Prefetcher:
    """Prefetching iterator of device-resident (unroll_k, ...) chunks.

    ``place`` defaults to `make_placer(mesh)`.  ``agent_slice`` restricts
    synthesis to the rank's own agents (multi-controller deployments never
    build other hosts' batches).  Use as a context manager so an early
    exit (exception, KeyboardInterrupt) still joins the worker.
    """
    if place is None:
        place = make_placer(mesh)
    return Prefetcher(
        pipeline.chunks(unroll_k, start_step=start_step,
                        num_chunks=num_chunks, agent_slice=agent_slice),
        place=place, depth=depth, first_step=start_step, stride=unroll_k)
