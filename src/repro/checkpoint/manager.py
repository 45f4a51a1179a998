"""Crash-safe, non-blocking checkpoint manager for the train loop.

`save_checkpoint` costs the hot loop np.asarray + npz serialization every
time it fires (the ROADMAP's "Async checkpoint writes" item).
`CheckpointManager` splits a save at the only boundary that must stay on
the caller's thread:

  1. **snapshot** (caller thread): `io.snapshot_tree` stages device-side
     copies of the state's leaves — an async dispatch, so the hot loop's
     pipeline never drains, yet ordered before the next donated step can
     invalidate the source buffers;
  2. **commit** (daemon writer thread): npz write + tree.json, staged in
     ``step_<n>.tmp-<pid>`` and `os.rename`d into place, so readers only
     ever see complete steps (`io.commit_snapshot`);
  3. **retention** (writer thread): after each commit, superseded steps
     beyond ``keep_last`` are GC'd (``keep_every`` pins periodic steps
     forever, the newest complete step is never deleted) and
     ``manifest.json`` records the surviving completed steps.

The writer follows the `data.worker` daemon-thread pattern shared with
`data.prefetch.Prefetcher`: bounded queue (backpressure, never unbounded
memory), first exception parked and re-raised in the train loop on the
next `save()`/`wait()`/`close()`, `close()` drains in-flight writes, and a
`weakref.finalize` safety net stops an abandoned writer without keeping
the manager alive.

Single-writer assumption: one live manager owns a checkpoint directory
(stale ``*.tmp-*`` debris from crashed predecessors is swept on open).
"""
from __future__ import annotations

import multiprocessing as mp
import os
import queue
import shutil
import threading
import time as _time
import weakref
from typing import Any, Callable

import jax
import numpy as np

from ..data import worker as _w
from . import io

__all__ = ["CheckpointManager"]

MANIFEST = "manifest.json"


class _WriterState:
    """Mutable state shared with the writer thread (never holds the
    manager itself, so the finalizer can run)."""

    def __init__(self, completed: list[int]):
        self.lock = threading.Lock()
        self.error: BaseException | None = None
        self.completed: set[int] = set(completed)
        self.retries = 0  # transient commit OSErrors survived (cumulative)


def _retained(completed: set[int], keep_last: int | None,
              keep_every: int | None) -> set[int]:
    """Steps that survive GC.  ``keep_last=None`` disables GC entirely."""
    if keep_last is None or not completed:
        return set(completed)
    # The slice always contains max(completed) (keep_last >= 1 enforced in
    # __init__), so the newest complete step is never collected.
    keep = set(sorted(completed)[-keep_last:])
    if keep_every:
        keep |= {s for s in completed if s % keep_every == 0}
    return keep


def _remove_debris(path: str) -> None:
    # Debris can be a DIR or a plain FILE (manifest.json.tmp-<pid>) —
    # rmtree on a file is a silent no-op under ignore_errors, so branch.
    if os.path.isdir(path):
        shutil.rmtree(path, ignore_errors=True)
    else:
        try:
            os.remove(path)
        except OSError:
            pass


def _recover_or_sweep(directory: str) -> None:
    """Handle a crashed predecessor's leftovers.

    ``step_<n>.tmp-<pid>`` staging dirs and torn ``*.tmp-<pid>`` files are
    deleted.  A ``step_<n>.old-<pid>`` dir is the OLD copy parked by a
    re-save (`io.commit_snapshot`); if the process died between its two
    renames, that parked dir is the only durable copy of step n — rename
    it back into place rather than destroying it.  Only when the final
    dir exists (the re-save completed) is the parked copy superseded
    debris.
    """
    for name in os.listdir(directory):
        path = os.path.join(directory, name)
        if io._OLD_SUFFIX in name:
            base = name.split(io._OLD_SUFFIX)[0]
            final = os.path.join(directory, base)
            if (io._STEP_RE.fullmatch(base) and not os.path.exists(final)
                    and io.is_complete(path)):
                os.rename(path, final)
                continue
            _remove_debris(path)
        elif io._TMP_SUFFIX in name:
            _remove_debris(path)


def _abandon_writer(q: queue.Queue, thread: threading.Thread,
                    join_timeout: float) -> None:
    """Finalizer for a manager GC'd without close(): drop queued jobs and
    unblock the writer (it waits in an untimed q.get(), so a stop event
    alone could never reach it — only an END sentinel does)."""
    _w.drain_queue(q)
    try:
        q.put_nowait(_w.END)
    except queue.Full:
        pass  # writer is mid-job with a refilled queue; daemon dies at exit
    thread.join(timeout=join_timeout)


def _write_manifest(directory: str, state: _WriterState,
                    keep_last: int | None, keep_every: int | None) -> None:
    io._atomic_write_json(os.path.join(directory, MANIFEST), {
        "format": 1,
        "completed": sorted(state.completed),
        "policy": {"keep_last": keep_last, "keep_every": keep_every},
        "retries": state.retries,
    })


def _commit_and_gc(directory: str, step: int, arrays: dict, meta: dict,
                   state: _WriterState, keep_last: int | None,
                   keep_every: int | None) -> None:
    io.commit_snapshot(directory, step, arrays, meta)
    with state.lock:
        state.completed.add(step)
        drop = state.completed - _retained(state.completed, keep_last,
                                           keep_every)
        state.completed -= drop
        _write_manifest(directory, state, keep_last, keep_every)
    for s in sorted(drop):
        shutil.rmtree(os.path.join(directory, io.step_dirname(s)),
                      ignore_errors=True)


# Transient-OSError retry policy for commits.  NFS blips, ENOSPC races
# with a concurrent GC, EINTR-adjacent weirdness: parking the manager
# fatal on the FIRST such error turns a 100ms filesystem hiccup into a
# dead train run.  `io.commit_snapshot` cleans up its staging dir on any
# failure, so re-running it is safe; attempts are bounded and backed off
# so a genuinely broken disk still fails fast-ish, and the count of
# survived retries is surfaced in manifest.json for post-mortems.
COMMIT_RETRIES = 3        # total attempts = 1 + COMMIT_RETRIES
COMMIT_BACKOFF_S = 0.1    # doubles per retry: 0.1, 0.2, 0.4


def _commit_with_retry(directory: str, step: int, arrays: dict, meta: dict,
                       state: _WriterState, keep_last: int | None,
                       keep_every: int | None) -> None:
    for attempt in range(1 + COMMIT_RETRIES):
        try:
            _commit_and_gc(directory, step, arrays, meta, state,
                           keep_last, keep_every)
            return
        except OSError:
            if attempt == COMMIT_RETRIES:
                raise
            with state.lock:
                state.retries += 1
            _time.sleep(COMMIT_BACKOFF_S * (2 ** attempt))


def _writer_loop(directory: str, q: queue.Queue, state: _WriterState,
                 keep_last: int | None, keep_every: int | None,
                 commit: Callable | None = None,
                 shutdown: Callable | None = None) -> None:
    # Module-level (no CheckpointManager reference): the thread must not
    # keep the owning manager alive, or its GC finalizer could never run.
    # ``commit`` defaults to the in-thread commit; the subprocess writer
    # substitutes a round-trip through its child (see _spawn_commit_child).
    if commit is None:
        def commit(step, arrays, meta):
            _commit_with_retry(directory, step, arrays, meta, state,
                               keep_last, keep_every)
    while True:
        job = q.get()
        try:
            if job is _w.END:
                if shutdown is not None:
                    try:
                        shutdown()
                    except BaseException as e:
                        if state.error is None:
                            state.error = e
                return
            if state.error is not None:
                continue  # park the first error, drain the rest unwritten
            step, arrays, meta = job
            commit(step, arrays, meta)
        except BaseException as e:
            state.error = e
        finally:
            q.task_done()


# -- subprocess writer (the GIL-free commit path) -------------------------
#
# The thread writer's npz serialization and fsync-adjacent work hold the
# GIL while the train loop is dispatch-bound (ROADMAP "checkpoint
# free-threading").  ``writer="subprocess"`` keeps the exact queue/END/
# error plumbing of the thread writer, but the thread only converts the
# snapshot to numpy (releasing the GIL during the device->host copy) and
# round-trips the job through a spawned child process, which runs the very
# same `_commit_with_retry` + manifest + retention code — so the on-disk
# semantics are pinned identical by construction (and by tests).


def _subprocess_commit_loop(directory: str, keep_last: int | None,
                            keep_every: int | None, completed0: list[int],
                            jobq, ackq) -> None:
    """Child-process main: commit jobs until the None sentinel.  The jobs
    are plain numpy, so no JAX backend is ever initialised here; the
    CPU pin makes sure a future change cannot make this child claim the
    accelerator the parent process holds."""
    jax.config.update("jax_platforms", "cpu")
    state = _WriterState(completed0)
    while True:
        job = jobq.get()
        if job is None:
            ackq.put(("end", None, None))
            return
        step, arrays, meta = job
        try:
            _commit_with_retry(directory, step, arrays, meta, state,
                               keep_last, keep_every)
            with state.lock:
                ackq.put(("ok", sorted(state.completed), state.retries))
        except BaseException as e:  # surfaced as the writer error upstream
            ackq.put(("err", repr(e), None))


def _spawn_commit_child(directory: str, state: _WriterState,
                        keep_last: int | None, keep_every: int | None
                        ) -> tuple[Callable, Callable]:
    """Start the commit child; returns (commit, shutdown) for _writer_loop."""
    ctx = mp.get_context("spawn")  # never fork a live jax runtime
    jobq, ackq = ctx.Queue(), ctx.Queue()
    with state.lock:
        completed0 = sorted(state.completed)
    child = ctx.Process(
        target=_subprocess_commit_loop,
        args=(directory, keep_last, keep_every, completed0, jobq, ackq),
        name="repro-checkpoint-commit", daemon=True)
    child.start()

    def commit(step, arrays, meta):
        # Device->host here on the writer thread (np.asarray releases the
        # GIL for the copy); the child only ever sees plain numpy.
        jobq.put((step, {k: np.asarray(v) for k, v in arrays.items()},
                  meta))
        while True:
            try:
                kind, a, b = ackq.get(timeout=1.0)
                break
            except queue.Empty:
                if not child.is_alive():
                    raise RuntimeError(
                        "checkpoint commit subprocess died mid-write")
        if kind == "err":
            raise RuntimeError(f"checkpoint commit subprocess failed: {a}")
        with state.lock:  # mirror the child's authoritative view
            state.completed = set(a)
            state.retries = b

    def shutdown():
        try:
            jobq.put(None)
            deadline = _time.monotonic() + 60.0
            while _time.monotonic() < deadline:
                try:
                    if ackq.get(timeout=1.0)[0] == "end":
                        break
                except queue.Empty:
                    if not child.is_alive():
                        break
        finally:
            child.join(timeout=10.0)
            if child.is_alive():  # wedged: daemon child dies with us
                child.terminate()

    return commit, shutdown


class CheckpointManager:
    """Background-writing checkpoint store with retention.

    Parameters
    ----------
    directory:    checkpoint root (`<dir>/step_<n>/...` + manifest.json).
    keep_last:    retain this many newest complete steps (None = keep all).
    keep_every:   additionally pin every step divisible by this, forever
                  (e.g. ``keep_last=3, keep_every=1000`` keeps a rolling
                  window plus durable millennial checkpoints).
    async_writes: False serializes commits on the caller thread (same
                  atomicity/retention, no worker) — the tests' simple mode
                  and a fallback for single-shot tooling.
    writer:       "thread" (default), "subprocess", or "sync"; overrides
                  async_writes when given.  "subprocess" keeps the writer
                  thread as the queue conduit but runs the npz commit +
                  retention + manifest in a spawned child process, so the
                  serialization never competes with a dispatch-bound train
                  loop for the GIL; on-disk semantics are identical (the
                  child runs the same commit code).
    queue_depth:  bounded in-flight snapshots; a full queue back-pressures
                  `save()` rather than buffering unbounded host copies.
    fresh:        True CLEARS any existing steps/manifest on open (after
                  crash-debris recovery).  A fresh run reusing a directory
                  must not leave another trajectory's states behind: stale
                  higher-numbered steps would both poison retention GC
                  (the new run's saves look "oldest" and get collected)
                  and hand a later --resume the wrong trajectory.  The
                  default adopts what's on disk (the resume case).
    run_meta:     JSON-stable dict recorded under ``"run"`` in every
                  step's tree.json (e.g. the mixing-config fingerprint) —
                  read back via `io.read_run_meta` so a --resume under a
                  different configuration fails fast.
    """

    def __init__(self, directory: str, *, keep_last: int | None = None,
                 keep_every: int | None = None, async_writes: bool = True,
                 queue_depth: int = 2, fresh: bool = False,
                 run_meta: dict | None = None,
                 writer: str | None = None):
        if keep_last is not None and keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        if keep_every is not None and keep_every < 1:
            raise ValueError(f"keep_every must be >= 1, got {keep_every}")
        if queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
        if writer is None:
            writer = "thread" if async_writes else "sync"
        if writer not in ("thread", "subprocess", "sync"):
            raise ValueError(
                f"writer must be 'thread', 'subprocess' or 'sync', "
                f"got {writer!r}")
        self.writer = writer
        self.directory = directory
        self.keep_last = keep_last
        self.keep_every = keep_every
        self.run_meta = run_meta
        os.makedirs(directory, exist_ok=True)
        _recover_or_sweep(directory)  # a crashed predecessor's leftovers
        if fresh:
            for s in io.complete_steps(directory):
                shutil.rmtree(os.path.join(directory, io.step_dirname(s)),
                              ignore_errors=True)
            _remove_debris(os.path.join(directory, MANIFEST))
        self._state = _WriterState(io.complete_steps(directory))
        # Idempotence is scoped to THIS manager's lifetime (terminal +
        # boundary saves of one run dedupe) — steps already on disk from a
        # previous run are overwritten, not skipped: a fresh run reusing a
        # checkpoint dir must not silently keep a different trajectory's
        # states.
        self._submitted: set[int] = set()
        self._closed = False
        self._queue: queue.Queue | None = None
        self._thread = None
        if writer != "sync":
            commit = shutdown = None
            if writer == "subprocess":
                commit, shutdown = _spawn_commit_child(
                    directory, self._state, keep_last, keep_every)
            self._queue = queue.Queue(maxsize=queue_depth)
            self._thread = threading.Thread(
                target=_writer_loop,
                args=(directory, self._queue, self._state, keep_last,
                      keep_every, commit, shutdown),
                name="repro-checkpoint-writer", daemon=True)
            self._thread.start()
            # Abandoned-manager safety net: drops queued (not yet started)
            # writes, which is exactly what interpreter teardown would do —
            # call close() to guarantee queued saves land.
            self._finalizer = weakref.finalize(
                self, _abandon_writer, self._queue, self._thread, 1.0)

    # -- introspection ----------------------------------------------------
    @property
    def completed_steps(self) -> list[int]:
        """Sorted steps with committed on-disk payloads (post-GC)."""
        with self._state.lock:
            return sorted(self._state.completed)

    def latest_step(self) -> int | None:
        steps = self.completed_steps
        return steps[-1] if steps else None

    @property
    def retries(self) -> int:
        """Transient commit OSErrors survived so far (also in manifest)."""
        with self._state.lock:
            return self._state.retries

    # -- error plumbing ---------------------------------------------------
    def _raise_pending(self) -> None:
        err = self._state.error
        if err is not None:
            raise RuntimeError(
                f"checkpoint writer failed for {self.directory!r}; the "
                "train loop must not continue as if its state were "
                "durable") from err

    # -- the API ----------------------------------------------------------
    def save(self, step: int, tree: Any) -> bool:
        """Snapshot ``tree`` now; commit (a)synchronously.  Idempotent:
        a step already committed or in flight is skipped (returns False).
        Re-raises a prior writer failure into the caller."""
        self._raise_pending()
        if self._closed:
            raise RuntimeError("CheckpointManager is closed")
        step = int(step)
        if step in self._submitted:
            return False
        arrays, meta = io.snapshot_tree(step, tree, run_meta=self.run_meta)
        self._submitted.add(step)
        if self._queue is None:
            _commit_with_retry(self.directory, step, arrays, meta,
                               self._state, self.keep_last, self.keep_every)
            return True
        while True:  # bounded put that notices a dying writer
            self._raise_pending()
            try:
                self._queue.put((step, arrays, meta), timeout=0.05)
                return True
            except queue.Full:
                continue

    def wait(self) -> None:
        """Block until every submitted snapshot is on disk (or raise the
        writer's failure).  The manager stays usable."""
        if self._queue is not None:
            self._queue.join()
        self._raise_pending()

    def close(self, join_timeout: float = 300.0) -> None:
        """Drain in-flight writes, stop the writer, surface any failure.

        Unlike the prefetcher's close (which discards — data is
        re-synthesizable), a checkpoint close must LAND what was queued:
        an END sentinel follows the last job, and we join on it."""
        if self._closed:
            self._raise_pending()
            return
        self._closed = True
        if self._queue is not None:
            # Timed put: an untimed one on a full queue would block before
            # join_timeout could ever apply if the writer is wedged in a
            # stalled filesystem call.
            deadline = _time.monotonic() + join_timeout
            while True:
                try:
                    self._queue.put(_w.END, timeout=0.1)
                    break
                except queue.Full:
                    if _time.monotonic() >= deadline:
                        self._finalizer.detach()
                        raise TimeoutError(
                            f"checkpoint writer wedged (queue still full "
                            f"after {join_timeout}s)")
            self._thread.join(timeout=max(0.0,
                                          deadline - _time.monotonic()))
            self._finalizer.detach()
            if self._thread.is_alive():
                raise TimeoutError(
                    f"checkpoint writer still running after {join_timeout}s")
        self._raise_pending()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
