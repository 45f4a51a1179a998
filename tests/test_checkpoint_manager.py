"""CheckpointManager: atomic commits, background writer lifecycle,
retention GC, manifest, and crash-debris handling.

The paper's privacy argument makes checkpoint integrity load-bearing: a
resume that picks up a torn checkpoint (or silently restarts at step 0)
would re-issue `agent_key(key, step, agent)` draws for consumed steps.
These tests pin the guarantees the train loop leans on: a reader can
never observe a partial step, an in-flight write lands on `close()`, a
writer failure surfaces in the caller, and GC never eats the newest
complete step.
"""
import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import repro.checkpoint.manager as manager_mod
from repro.checkpoint import (CheckpointManager, complete_steps,
                              latest_step, load_checkpoint, save_checkpoint,
                              step_dirname)


def _tree(v=1.0):
    return {"w": jnp.full((2, 3), float(v)), "b": jnp.full((4,), float(v))}


def _read_w(directory, step):
    out = load_checkpoint(directory, step, _tree())
    return float(np.asarray(out["w"])[0, 0])


# -- atomicity / discovery ---------------------------------------------------

def test_save_checkpoint_leaves_no_tmp_debris(tmp_path):
    save_checkpoint(str(tmp_path), 7, _tree())
    names = os.listdir(tmp_path)
    assert names == [step_dirname(7)]
    assert latest_step(str(tmp_path)) == 7


def test_latest_step_skips_incomplete_dirs(tmp_path):
    """A directory missing tree.json/arrays.npz (pre-atomic writer killed
    mid-write) must never be selected."""
    save_checkpoint(str(tmp_path), 4, _tree())
    save_checkpoint(str(tmp_path), 8, _tree())
    os.remove(tmp_path / step_dirname(8) / "arrays.npz")
    assert latest_step(str(tmp_path)) == 4
    (tmp_path / step_dirname(12)).mkdir()  # empty dir, no payload at all
    assert latest_step(str(tmp_path)) == 4


def test_latest_step_ignores_tmp_staging_dirs(tmp_path):
    """Kill-mid-write simulation: debris staged by a writer that died
    before its rename is invisible to discovery and to --resume."""
    save_checkpoint(str(tmp_path), 3, _tree(3))
    stage = tmp_path / (step_dirname(9) + ".tmp-12345")
    stage.mkdir()
    # even a COMPLETE payload in the staging dir doesn't count: the rename
    # is the commit point
    np.savez(stage / "arrays.npz", a0=np.zeros(3))
    (stage / "tree.json").write_text("{}")
    assert latest_step(str(tmp_path)) == 3
    assert complete_steps(str(tmp_path)) == [3]


def test_latest_step_wide_step_numbers(tmp_path):
    """f"{step:08d}" widens past 8 digits at 10^8; the old \\d{8} regex
    silently dropped those steps."""
    save_checkpoint(str(tmp_path), 99_999_999, _tree(1))
    assert latest_step(str(tmp_path)) == 99_999_999
    save_checkpoint(str(tmp_path), 100_000_000, _tree(2))
    assert latest_step(str(tmp_path)) == 100_000_000
    assert complete_steps(str(tmp_path)) == [99_999_999, 100_000_000]
    assert _read_w(str(tmp_path), 100_000_000) == 2.0


def test_commit_failure_leaves_no_partial_step(tmp_path, monkeypatch):
    real_write = manager_mod.io._write_npz

    def dying_write(path, arrays):
        real_write(path, arrays)
        raise OSError("disk full")

    monkeypatch.setattr(manager_mod.io, "_write_npz", dying_write)
    with pytest.raises(OSError):
        save_checkpoint(str(tmp_path), 5, _tree())
    assert latest_step(str(tmp_path)) is None
    assert os.listdir(tmp_path) == []  # staging dir cleaned up too


# -- manager lifecycle -------------------------------------------------------

def test_async_write_lands_on_close(tmp_path):
    """An in-flight write completes on close() — close drains, it does not
    discard (unlike the prefetcher, whose items are re-synthesizable)."""
    m = CheckpointManager(str(tmp_path))
    m.save(1, _tree(1))
    m.save(2, _tree(2))
    m.close()
    assert complete_steps(str(tmp_path)) == [1, 2]
    assert _read_w(str(tmp_path), 2) == 2.0


def test_async_and_sync_writes_bit_identical(tmp_path):
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))}
    with CheckpointManager(str(tmp_path / "a")) as m:
        m.save(5, tree)
    save_checkpoint(str(tmp_path / "s"), 5, tree)
    a = load_checkpoint(str(tmp_path / "a"), 5, tree)
    s = load_checkpoint(str(tmp_path / "s"), 5, tree)
    np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(s["w"]))


def test_save_snapshots_before_caller_mutates(tmp_path):
    """The snapshot happens inside save(): overwriting the live tree after
    save() must not change what lands on disk (donation-safety stand-in)."""
    buf = np.ones((2, 2), np.float32)
    with CheckpointManager(str(tmp_path)) as m:
        m.save(1, {"w": buf})
        buf[:] = -1.0  # train loop marches on / donation invalidates
    out = load_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((2, 2))})
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.ones((2, 2), np.float32))


def test_worker_exception_surfaces_in_caller(tmp_path, monkeypatch):
    monkeypatch.setattr(
        manager_mod.io, "commit_snapshot",
        lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")))
    m = CheckpointManager(str(tmp_path))
    m.save(1, _tree())
    with pytest.raises(RuntimeError, match="checkpoint writer failed"):
        m.wait()
    # the original exception rides along as the cause, and close() keeps
    # raising rather than pretending the state is durable
    with pytest.raises(RuntimeError) as exc:
        m.close()
    assert isinstance(exc.value.__cause__, OSError)


def test_save_idempotent_within_run_but_overwrites_across_runs(tmp_path):
    with CheckpointManager(str(tmp_path)) as m:
        assert m.save(3, _tree(3)) is True
        m.wait()
        assert m.save(3, _tree(99)) is False  # same run: skipped
    assert _read_w(str(tmp_path), 3) == 3.0
    # a NEW manager over the same dir must overwrite, not skip: a fresh
    # run reusing a checkpoint dir cannot silently keep a different
    # trajectory's states for --resume to pick up
    with CheckpointManager(str(tmp_path)) as m:
        assert m.save(3, _tree(7)) is True
    assert _read_w(str(tmp_path), 3) == 7.0
    # and the re-save parked no .old debris behind
    assert sorted(os.listdir(tmp_path)) == ["manifest.json", step_dirname(3)]


def test_closed_manager_refuses_saves(tmp_path):
    m = CheckpointManager(str(tmp_path))
    m.close()
    with pytest.raises(RuntimeError, match="closed"):
        m.save(1, _tree())


def test_bounded_queue_backpressures_not_unbounded(tmp_path, monkeypatch):
    """With a slow writer, save() blocks on the bounded queue instead of
    buffering every snapshot in host memory — and everything still lands."""
    gate = threading.Event()
    real = manager_mod.io.commit_snapshot

    def slow_commit(*a, **k):
        gate.wait(timeout=10)
        return real(*a, **k)

    monkeypatch.setattr(manager_mod.io, "commit_snapshot", slow_commit)
    m = CheckpointManager(str(tmp_path), queue_depth=1)
    t0 = time.perf_counter()
    m.save(1, _tree(1))   # picked up by the worker, blocks on the gate
    m.save(2, _tree(2))   # fills the depth-1 queue
    done = threading.Event()

    def third():
        m.save(3, _tree(3))
        done.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert not done.wait(timeout=0.3)  # back-pressured while writer stalls
    gate.set()
    assert done.wait(timeout=10)
    t.join(timeout=10)
    m.close()
    assert complete_steps(str(tmp_path)) == [1, 2, 3]
    assert time.perf_counter() - t0 < 30


# -- retention / manifest ----------------------------------------------------

def test_retention_keeps_last_n_and_pinned(tmp_path):
    with CheckpointManager(str(tmp_path), keep_last=2, keep_every=4) as m:
        for s in range(1, 9):
            m.save(s, _tree(s))
    assert complete_steps(str(tmp_path)) == [4, 7, 8]  # {4} pinned, last 2


def test_retention_never_deletes_newest_complete_step(tmp_path):
    with CheckpointManager(str(tmp_path), keep_last=1) as m:
        for s in range(1, 6):
            m.save(s, _tree(s))
            m.wait()
            assert m.latest_step() == s  # newest survives every GC pass
    assert complete_steps(str(tmp_path)) == [5]
    assert _read_w(str(tmp_path), 5) == 5.0


def test_keep_last_none_keeps_everything(tmp_path):
    with CheckpointManager(str(tmp_path)) as m:
        for s in range(1, 5):
            m.save(s, _tree(s))
    assert complete_steps(str(tmp_path)) == [1, 2, 3, 4]


def test_manifest_records_completed_steps(tmp_path):
    with CheckpointManager(str(tmp_path), keep_last=3) as m:
        for s in range(1, 6):
            m.save(s, _tree(s))
    with open(tmp_path / "manifest.json") as f:
        manifest = json.load(f)
    assert manifest["completed"] == [3, 4, 5]
    assert manifest["completed"] == complete_steps(str(tmp_path))
    assert manifest["policy"] == {"keep_last": 3, "keep_every": None}


def test_writer_retries_transient_oserror(tmp_path, monkeypatch):
    """Two NFS-blip-style commit failures must not kill the run: the
    writer retries with backoff (commit_snapshot cleans its staging dir
    on failure, so a re-run is safe), the save lands, and the survived
    retry count is surfaced in manifest.json for post-mortems."""
    real = manager_mod.io.commit_snapshot
    fails = {"n": 2}

    def flaky(*a, **k):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient blip")
        return real(*a, **k)

    monkeypatch.setattr(manager_mod.io, "commit_snapshot", flaky)
    monkeypatch.setattr(manager_mod, "COMMIT_BACKOFF_S", 0.01)
    with CheckpointManager(str(tmp_path)) as m:
        m.save(1, _tree(1))
        m.wait()
        assert m.retries == 2
    assert complete_steps(str(tmp_path)) == [1]
    with open(tmp_path / "manifest.json") as f:
        assert json.load(f)["retries"] == 2


def test_writer_parks_fatal_after_retry_budget(tmp_path, monkeypatch):
    """A commit failing through every attempt still surfaces in the
    caller: retries are bounded, so a genuinely broken disk fails the
    run instead of spinning forever."""
    calls = {"n": 0}

    def broken(*a, **k):
        calls["n"] += 1
        raise OSError("disk gone")

    monkeypatch.setattr(manager_mod.io, "commit_snapshot", broken)
    monkeypatch.setattr(manager_mod, "COMMIT_BACKOFF_S", 0.01)
    m = CheckpointManager(str(tmp_path))
    m.save(1, _tree(1))
    with pytest.raises(RuntimeError, match="checkpoint writer failed"):
        m.wait()
    assert calls["n"] == 1 + manager_mod.COMMIT_RETRIES
    with pytest.raises(RuntimeError):
        m.close()


def test_sync_mode_retries_transient_oserror(tmp_path, monkeypatch):
    """async_writes=False takes the same retry path as the writer."""
    real = manager_mod.io.commit_snapshot
    fails = {"n": 1}

    def flaky(*a, **k):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient blip")
        return real(*a, **k)

    monkeypatch.setattr(manager_mod.io, "commit_snapshot", flaky)
    monkeypatch.setattr(manager_mod, "COMMIT_BACKOFF_S", 0.01)
    with CheckpointManager(str(tmp_path), async_writes=False) as m:
        m.save(2, _tree(2))
        assert m.retries == 1
    assert complete_steps(str(tmp_path)) == [2]


def test_manager_sweeps_stale_tmp_debris_on_open(tmp_path):
    stage = tmp_path / (step_dirname(9) + ".tmp-99999")
    stage.mkdir()
    (stage / "arrays.npz").write_text("torn")
    # debris can also be a plain FILE (a torn manifest tmp) or a parked
    # .old dir from a re-save killed mid-swap — both must go
    (tmp_path / "manifest.json.tmp-99999").write_text("{")
    parked = tmp_path / (step_dirname(2) + ".old-99999")
    parked.mkdir()
    with CheckpointManager(str(tmp_path)) as m:
        m.save(1, _tree())
    assert not stage.exists()
    assert not (tmp_path / "manifest.json.tmp-99999").exists()
    assert not parked.exists()
    assert complete_steps(str(tmp_path)) == [1]


def test_manager_recovers_step_parked_mid_reswap(tmp_path):
    """A crash between commit_snapshot's two renames leaves the only copy
    of a step as step_<n>.old-<pid>; the next open must rename it BACK,
    never sweep it — and --resume then sees it via latest_step."""
    save_checkpoint(str(tmp_path), 4, _tree(4))
    os.rename(tmp_path / step_dirname(4),
              tmp_path / (step_dirname(4) + ".old-31337"))
    assert latest_step(str(tmp_path)) is None
    with CheckpointManager(str(tmp_path)) as m:
        assert m.completed_steps == [4]
    assert latest_step(str(tmp_path)) == 4
    assert _read_w(str(tmp_path), 4) == 4.0


def test_fresh_manager_clears_stale_trajectory(tmp_path):
    """fresh=True (the driver's non --resume mode): stale higher-numbered
    steps from a previous run must not survive — they would poison
    retention GC (the new run's saves look oldest and get collected) and
    hand a later --resume the wrong trajectory."""
    save_checkpoint(str(tmp_path), 100, _tree(100))
    save_checkpoint(str(tmp_path), 200, _tree(200))
    with CheckpointManager(str(tmp_path), keep_last=2, fresh=True) as m:
        assert m.completed_steps == []
        m.save(2, _tree(2))
        m.wait()
        assert m.completed_steps == [2]  # NOT collected against stale 200
    assert complete_steps(str(tmp_path)) == [2]
    assert latest_step(str(tmp_path)) == 2


def test_manager_adopts_existing_checkpoints(tmp_path):
    save_checkpoint(str(tmp_path), 2, _tree(2))
    with CheckpointManager(str(tmp_path), keep_last=2) as m:
        assert m.completed_steps == [2]
        m.save(4, _tree(4))
        m.save(6, _tree(6))
    assert complete_steps(str(tmp_path)) == [4, 6]  # old step GC'd by policy


@pytest.mark.parametrize("kwargs", [
    {"keep_last": 0}, {"keep_every": 0}, {"queue_depth": 0},
])
def test_invalid_knobs_rejected(tmp_path, kwargs):
    with pytest.raises(ValueError):
        CheckpointManager(str(tmp_path), **kwargs)


# -- subprocess writer flavor ------------------------------------------------

def test_subprocess_writer_parity_with_thread(tmp_path):
    """writer="subprocess" must produce byte-identical on-disk semantics
    to writer="thread": same completed steps, same retention survivors,
    same manifest, same loaded values."""
    dirs = {}
    for flavor in ("thread", "subprocess"):
        d = str(tmp_path / flavor)
        m = CheckpointManager(d, keep_last=2, keep_every=4, writer=flavor)
        for s in (1, 2, 3, 4, 5, 6):
            m.save(s, _tree(s))
        m.close()
        dirs[flavor] = d
    ct = complete_steps(dirs["thread"])
    cs = complete_steps(dirs["subprocess"])
    assert ct == cs == [4, 5, 6]  # keep_last=2 + pinned step 4
    mt = json.load(open(os.path.join(dirs["thread"], "manifest.json")))
    ms = json.load(open(os.path.join(dirs["subprocess"], "manifest.json")))
    assert mt == ms
    for s in ct:
        a = load_checkpoint(dirs["thread"], s, _tree())
        b = load_checkpoint(dirs["subprocess"], s, _tree())
        assert np.array_equal(np.asarray(a["w"]), np.asarray(b["w"]))


def test_subprocess_writer_reopen_adopts(tmp_path):
    d = str(tmp_path)
    m = CheckpointManager(d, writer="subprocess", run_meta={"k": 1})
    m.save(3, _tree(3))
    m.close()
    assert latest_step(d) == 3
    m2 = CheckpointManager(d, writer="subprocess")  # adopt, not fresh
    m2.save(5, _tree(5))
    m2.close()
    assert complete_steps(d) == [3, 5]
    assert _read_w(d, 3) == 3.0 and _read_w(d, 5) == 5.0


def test_subprocess_writer_records_run_meta(tmp_path):
    from repro.checkpoint import read_run_meta
    d = str(tmp_path)
    m = CheckpointManager(d, writer="subprocess",
                          run_meta={"mixing": {"mode": "static"}})
    m.save(2, _tree(2))
    m.close()
    assert read_run_meta(d, 2) == {"mixing": {"mode": "static"}}


def test_subprocess_writer_child_never_initialises_a_backend(tmp_path):
    """The commit child runs in a fresh interpreter next to a parent that
    may hold the chip: its loop must commit without ever creating a JAX
    backend.  Run the child's main in a fresh process and ask JAX."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = f"""
import sys, queue
sys.path.insert(0, {os.path.abspath(src)!r})
import numpy as np
from jax._src import xla_bridge
from repro.checkpoint.io import snapshot_tree
from repro.checkpoint.manager import _subprocess_commit_loop
arrays, meta = snapshot_tree(3, {{"w": np.full((2, 3), 7.0, np.float32)}})
jobq, ackq = queue.Queue(), queue.Queue()
jobq.put((3, arrays, meta))
jobq.put(None)
_subprocess_commit_loop({str(tmp_path)!r}, None, None, [], jobq, ackq)
print(ackq.get()[0], ackq.get()[0], xla_bridge.backends_are_initialized())
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["ok", "end", "False"]
    assert complete_steps(str(tmp_path)) == [3]


def test_writer_choice_validated(tmp_path):
    with pytest.raises(ValueError, match="writer"):
        CheckpointManager(str(tmp_path), writer="fork")
