"""Checkpoint/resume correctness: a resumed run must replay the EXACT
trajectory of the uninterrupted one — same batches (random-access
`batch_at`), same per-step keys (fold_in on the absolute step), and a step
counter that keeps counting so `privacy.agent_key(key, step, agent)` never
re-issues Lambda draws for an already-consumed step."""
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.checkpoint.manager as manager_mod
from repro.checkpoint import (complete_steps, latest_step, load_checkpoint,
                              save_checkpoint, step_dirname)
from repro.core import (init_state, make_decentralized_step, make_topology)
from repro.core.schedules import harmonic
from repro.launch.train import build_parser, run_training

ARCH = "stablelm-3b-smoke"
# A small step size keeps the 8-step trajectory out of the chaotic regime
# (at the CLI default 0.4 the consensus error grows ~100x in 8 steps), so
# float32 rounding differences between two compiled programs stay at
# rounding scale instead of being amplified to O(update).
BASE = ["--arch", ARCH, "--agents", "4", "--steps", "8",
        "--per-agent-batch", "1", "--seq-len", "16", "--log-every", "1",
        "--lr", "0.01"]

# Eager and scanned drivers run the same step body, but XLA compiles it
# once as a top-level program and once inside the scan's while loop, with
# different fusion and reduction order: the per-step gradients already
# differ at ~1e-5 relative.  Over 8 steps at lr 0.01 the parameters agree
# to well inside this bound, while a wrong batch, key or W_k would move
# them by O(update) ~ 3e-3.
TRAJECTORY_ATOL = 1e-4


def _assert_same_trajectory(a_result, b_result):
    for a, b in zip(_params(a_result), _params(b_result)):
        np.testing.assert_allclose(a, b, rtol=0, atol=TRAJECTORY_ATOL)


def _run(extra):
    return run_training(build_parser().parse_args(BASE + extra))


def _params(result):
    return [np.asarray(x) for x in jax.tree.leaves(result["state"].params)]


@pytest.fixture(scope="module")
def uninterrupted():
    """One 8-step scanned run + one eager run, shared across tests."""
    return {"scanned": _run(["--unroll-k", "4"]), "eager": _run([])}


def test_eager_and_scanned_drivers_walk_identical_trajectory(uninterrupted):
    _assert_same_trajectory(uninterrupted["eager"], uninterrupted["scanned"])


def test_scanned_driver_reports_compile_apart_from_steps(uninterrupted):
    """The scanned loop compiles its step program before the first
    dispatch and times the steps after it; the eager loop reports none."""
    scanned, eager = uninterrupted["scanned"], uninterrupted["eager"]
    t = scanned["timing"]
    assert t["steps_timed"] == 8
    assert t["compile_s"] > 0 and t["steady_s"] > 0
    assert "while" in scanned["compiled"].as_text()
    assert scanned["step"].inner is not None
    losses = [x for h in scanned["history"] for x in h["step_losses"]]
    assert len(losses) == 8 and np.all(np.isfinite(losses))
    assert eager["timing"] is None and eager["compiled"] is None


def test_scanned_driver_times_terminal_checkpoint_apart(tmp_path):
    """With no periodic save inside the run, the terminal save is taken
    after the step clock stops and timed on its own, and is on disk when
    the run returns."""
    d = str(tmp_path)
    t = _run(["--unroll-k", "4", "--checkpoint-dir", d,
              "--checkpoint-every", "100"])["timing"]
    assert t["steps_timed"] == 8
    assert t["steady_s"] > 0 and t["checkpoint_s"] > 0
    assert latest_step(d) == 8


def test_scanned_resume_bit_identical(tmp_path, uninterrupted):
    d = str(tmp_path)
    _run(["--unroll-k", "4", "--steps", "4", "--checkpoint-dir", d,
          "--checkpoint-every", "4"])
    assert latest_step(d) == 4
    resumed = _run(["--unroll-k", "4", "--checkpoint-dir", d,
                    "--checkpoint-every", "4", "--resume"])
    assert resumed["resumed_from"] == 4
    assert int(resumed["state"].step) == 8
    for a, b in zip(_params(uninterrupted["scanned"]), _params(resumed)):
        np.testing.assert_array_equal(a, b)
    # the logged chunk reductions line up bit-for-bit too
    full_tail = [h["loss"] for h in uninterrupted["scanned"]["history"][-1:]]
    res_tail = [h["loss"] for h in resumed["history"][-1:]]
    assert full_tail == res_tail


def test_eager_resume_bit_identical(tmp_path, uninterrupted):
    d = str(tmp_path)
    _run(["--steps", "4", "--checkpoint-dir", d, "--checkpoint-every", "4"])
    resumed = _run(["--checkpoint-dir", d, "--checkpoint-every", "4",
                    "--resume"])
    assert resumed["resumed_from"] == 4
    for a, b in zip(_params(uninterrupted["eager"]), _params(resumed)):
        np.testing.assert_array_equal(a, b)
    full = {h["step"]: h["loss"] for h in uninterrupted["eager"]["history"]}
    for h in resumed["history"]:
        assert h["loss"] == full[h["step"]]


def test_resume_skips_truncated_checkpoint(tmp_path, uninterrupted):
    """Kill-mid-write regression: truncate the newest checkpoint and assert
    resume falls back to the previous COMPLETE step — and still reproduces
    the uninterrupted trajectory bit-for-bit from there."""
    d = str(tmp_path)
    _run(["--steps", "6", "--checkpoint-dir", d, "--checkpoint-every", "2"])
    assert latest_step(d) == 6
    os.remove(os.path.join(d, step_dirname(6), "arrays.npz"))
    assert latest_step(d) == 4
    resumed = _run(["--checkpoint-dir", d, "--checkpoint-every", "2",
                    "--resume"])
    assert resumed["resumed_from"] == 4
    for a, b in zip(_params(uninterrupted["eager"]), _params(resumed)):
        np.testing.assert_array_equal(a, b)


def test_terminal_checkpoint_saved_off_boundary(tmp_path):
    """--steps not crossing a --checkpoint-every boundary must still leave
    a terminal checkpoint: a finished run resumes from its END rather than
    replaying (and re-keying) work from an earlier boundary."""
    d = str(tmp_path)
    r = _run(["--steps", "6", "--checkpoint-dir", d,
              "--checkpoint-every", "4"])
    assert complete_steps(d) == [4, 6]
    restored = load_checkpoint(d, 6, like=r["state"])
    assert int(restored.step) == 6
    # resuming at the terminal step is a no-op that stays consistent
    resumed = _run(["--steps", "6", "--checkpoint-dir", d,
                    "--checkpoint-every", "4", "--resume"])
    assert resumed["resumed_from"] == 6
    assert complete_steps(d) == [4, 6]


def test_driver_keep_last_retention(tmp_path):
    d = str(tmp_path)
    _run(["--steps", "8", "--checkpoint-dir", d, "--checkpoint-every", "2",
          "--keep-last", "2"])
    assert complete_steps(d) == [6, 8]
    resumed = _run(["--checkpoint-dir", d, "--checkpoint-every", "2",
                    "--keep-last", "2", "--resume"])
    assert resumed["resumed_from"] == 8


def test_sync_and_async_driver_checkpoints_identical(tmp_path):
    da, ds = str(tmp_path / "async"), str(tmp_path / "sync")
    _run(["--steps", "4", "--checkpoint-dir", da, "--checkpoint-every", "4"])
    _run(["--steps", "4", "--checkpoint-dir", ds, "--checkpoint-every", "4",
          "--checkpoint-sync"])
    a = _run(["--checkpoint-dir", da, "--checkpoint-every", "4", "--resume"])
    s = _run(["--checkpoint-dir", ds, "--checkpoint-every", "4", "--resume"])
    for x, y in zip(_params(a), _params(s)):
        np.testing.assert_array_equal(x, y)


def test_writer_failure_surfaces_in_run_training(tmp_path, monkeypatch):
    """A dying background writer must fail the training run — the loop
    never reports success on checkpoints that never landed."""
    monkeypatch.setattr(
        manager_mod.io, "commit_snapshot",
        lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")))
    with pytest.raises(RuntimeError, match="checkpoint writer failed"):
        _run(["--steps", "4", "--checkpoint-dir", str(tmp_path),
              "--checkpoint-every", "2"])


def test_fresh_run_clears_stale_checkpoint_dir(tmp_path):
    """A non --resume run reusing a checkpoint dir must clear another
    trajectory's stale steps: a higher-numbered leftover would otherwise
    be what a later --resume restores."""
    d = str(tmp_path)
    save_checkpoint(d, 100, {"junk": jnp.ones((2,))})
    _run(["--steps", "4", "--checkpoint-dir", d, "--checkpoint-every", "2"])
    assert complete_steps(d) == [2, 4]
    resumed = _run(["--checkpoint-dir", d, "--checkpoint-every", "2",
                    "--resume"])
    assert resumed["resumed_from"] == 4


def test_resume_without_checkpoint_refuses(tmp_path):
    """--resume with an empty/mistyped checkpoint dir must NOT silently
    restart at step 0 (that would replay (key, step) pairs)."""
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        _run(["--checkpoint-dir", str(tmp_path), "--resume"])


def test_checkpoint_persists_full_state_with_step(tmp_path):
    """The checkpoint carries the WHOLE DecentralizedState — a restore
    without --resume-style re-derivation gets the step counter back."""
    state = init_state({"w": jnp.ones((3, 2))}, 4)
    state.step = jnp.asarray(17, jnp.int32)
    save_checkpoint(str(tmp_path), 17, state)
    like = init_state({"w": jnp.zeros((3, 2))}, 4)
    restored = load_checkpoint(str(tmp_path), 17, like)
    assert int(restored.step) == 17
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.ones((4, 3, 2), np.float32))


def test_load_checkpoint_rejects_dtype_mismatch(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((2, 2), jnp.float32)})
    with pytest.raises(ValueError, match="dtype mismatch"):
        load_checkpoint(str(tmp_path), 1,
                        {"w": jnp.zeros((2, 2), jnp.float16)})
    out = load_checkpoint(str(tmp_path), 1,
                          {"w": jnp.zeros((2, 2), jnp.float16)},
                          allow_cast=True)
    assert out["w"].dtype == np.float16


def test_dsgt_algorithm_reachable_and_converges():
    """`--algorithm dsgt` is a real choice now: the tracker pair rides in
    the state tuple, and the recursion tracks the global optimum on the
    paper's quadratic."""
    algo_action = next(a for a in build_parser()._actions
                       if a.dest == "algorithm")
    assert "dsgt" in algo_action.choices
    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))

    def loss(p, batch):
        return jnp.mean(jnp.sum((p - batch) ** 2, -1))

    step = make_decentralized_step(loss, top, harmonic(0.3),
                                   algorithm="dsgt")
    state = init_state(jnp.zeros((d,)), m, algorithm="dsgt")
    assert state.tracker is not None
    for k in range(400):
        state, aux = step(state, targets, jax.random.key(k))
    xbar = np.asarray(jax.tree.leaves(state.params)[0]).mean(0)
    np.testing.assert_allclose(xbar, np.asarray(targets).mean(0), atol=0.05)
    assert float(aux["consensus_error"]) < 1e-2


def test_dsgt_requires_tracker_state():
    top = make_topology("ring", 4)
    step = make_decentralized_step(lambda p, b: jnp.sum(p ** 2), top,
                                   harmonic(0.1), algorithm="dsgt")
    with pytest.raises(ValueError, match="tracker"):
        step(init_state(jnp.zeros((2,)), 4), None, jax.random.key(0))


def test_dsgt_state_checkpoints_with_tracker(tmp_path):
    state = init_state({"w": jnp.ones((2,))}, 3, algorithm="dsgt")
    save_checkpoint(str(tmp_path), 5, state)
    like = init_state({"w": jnp.zeros((2,))}, 3, algorithm="dsgt")
    restored = load_checkpoint(str(tmp_path), 5, like)
    assert int(restored.step) == 0
    y, g_prev = restored.tracker
    np.testing.assert_array_equal(np.asarray(y["w"]), np.zeros((3, 2)))
    np.testing.assert_array_equal(np.asarray(g_prev["w"]), np.zeros((3, 2)))


# -- fault-tolerant runs: resume stays on the SAME fault trajectory -----

FAULT = ["--fault-crash-rate", "0.2", "--fault-restart-rate", "0.5",
         "--nan-policy", "skip"]


@pytest.fixture(scope="module")
def fault_uninterrupted():
    """8-step chaos runs (markov crash churn + sentinels), both drivers."""
    return {"scanned": _run(FAULT + ["--unroll-k", "4"]),
            "eager": _run(FAULT)}


def test_fault_drivers_walk_identical_trajectory(fault_uninterrupted):
    e, s = fault_uninterrupted["eager"], fault_uninterrupted["scanned"]
    _assert_same_trajectory(e, s)
    assert e["fault_totals"] == s["fault_totals"]
    assert e["fault_totals"].get("fault_down", 0) > 0  # churn happened


def test_fault_resume_bit_identical(tmp_path, fault_uninterrupted):
    """The fault realization folds in from the ABSOLUTE step: a resumed
    run replays the same crash draws (and never re-issues Lambda keys
    for a survived step) — bit-for-bit the uninterrupted trajectory."""
    d = str(tmp_path)
    _run(FAULT + ["--unroll-k", "4", "--steps", "4",
                  "--checkpoint-dir", d, "--checkpoint-every", "4"])
    resumed = _run(FAULT + ["--unroll-k", "4", "--checkpoint-dir", d,
                            "--checkpoint-every", "4", "--resume"])
    assert resumed["resumed_from"] == 4
    for a, b in zip(_params(fault_uninterrupted["scanned"]),
                    _params(resumed)):
        np.testing.assert_array_equal(a, b)


def test_fault_resume_refuses_mismatched_fault_config(tmp_path):
    """The fault fingerprint rides in run_meta: resuming under a
    different fault scenario (or none) refuses instead of silently
    walking a different trajectory."""
    d = str(tmp_path)
    _run(FAULT + ["--steps", "4", "--checkpoint-dir", d,
                  "--checkpoint-every", "4"])
    with pytest.raises(ValueError, match="fault config"):
        _run(["--checkpoint-dir", d, "--checkpoint-every", "4",
              "--resume"])  # fault flags dropped
    with pytest.raises(ValueError, match="fault config"):
        _run(FAULT[:1] + ["0.3"] + FAULT[2:] +
             ["--checkpoint-dir", d, "--checkpoint-every", "4",
              "--resume"])  # different crash rate
    # and the inverse: a fault-free checkpoint refuses fault-flag resume
    d2 = str(tmp_path / "clean")
    _run(["--steps", "4", "--checkpoint-dir", d2, "--checkpoint-every", "4"])
    with pytest.raises(ValueError, match="fault config"):
        _run(FAULT + ["--checkpoint-dir", d2, "--checkpoint-every", "4",
                      "--resume"])


def test_sigkill_mid_chaos_run_resumes_bit_identical(tmp_path,
                                                     fault_uninterrupted):
    """The whole self-healing story end to end: a chaos run is hard-
    killed (SIGKILL — no finally blocks, no atexit) mid-training, then
    --resume from the surviving durable checkpoint reproduces the
    uninterrupted trajectory bit-for-bit."""
    import subprocess
    import sys
    import time

    d = str(tmp_path)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train"] + BASE + FAULT +
        ["--checkpoint-dir", d, "--checkpoint-every", "2"],
        env=env, cwd=root,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 180.0
        while time.time() < deadline and proc.poll() is None:
            if (latest_step(d) or 0) >= 2:
                break
            time.sleep(0.05)
        killed = proc.poll() is None
        proc.kill()
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.terminate()
    last = latest_step(d)
    assert last is not None and last >= 2  # a durable checkpoint survived
    if not killed:  # raced a fast finish: resume is then a pure no-op
        assert proc.returncode == 0
    resumed = _run(FAULT + ["--checkpoint-dir", d,
                            "--checkpoint-every", "2", "--resume"])
    assert resumed["resumed_from"] == last
    for a, b in zip(_params(fault_uninterrupted["eager"]),
                    _params(resumed)):
        np.testing.assert_array_equal(a, b)


class _FakeMesh:
    """Duck-typed mesh: the dense-gossip path of make_train_step only reads
    .shape (a dict), so no multi-device runtime is needed."""

    def __init__(self, **axes):
        self.shape = axes


def test_dsgt_mesh_path_parity_with_core():
    """ROADMAP "dsgt in launch.steps": the mesh path's gradient-tracking
    branch must walk the SAME trajectory as core.pdsgd's dsgt branch —
    same W (torus == ring for 1 x m), same 1/k lam, same phase convention
    for the (y, prev_grads) pair carried alongside params."""
    from repro.core.topology import Topology, metropolis_weights, torus2d
    from repro.launch.steps import dsgt_carry, make_train_step

    m, d = 4, 3
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))

    def loss(p, batch):
        return jnp.mean(jnp.sum((p - batch) ** 2, -1))

    adj = torus2d(1, m)
    top = Topology(name="torus", adjacency=adj,
                   weights=metropolis_weights(adj))
    core_step = make_decentralized_step(loss, top, harmonic(0.1),
                                        algorithm="dsgt", donate=False)
    bundle = types.SimpleNamespace(loss_fn=loss)
    mesh_step = jax.jit(make_train_step(bundle, _FakeMesh(data=m, model=1),
                                        algorithm="dsgt", lam_base=0.1))

    state = init_state(jnp.zeros((d,)), m, algorithm="dsgt")
    carry = dsgt_carry(jnp.zeros((m, d)))
    for k in range(10):
        state, aux = core_step(state, targets, jax.random.key(k))
        carry, mesh_loss = mesh_step(carry, targets, jnp.int32(0),
                                     jnp.int32(k))
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(state.params)[0]), np.asarray(carry[0]))
    # trackers agree too (phase convention matches, not just the params)
    for a, b in zip(jax.tree.leaves(state.tracker),
                    jax.tree.leaves(carry[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(mesh_loss) == pytest.approx(float(aux["loss"]), rel=1e-6)


def test_dsgt_mesh_path_rejects_ring_gossip():
    bundle = types.SimpleNamespace(loss_fn=lambda p, b: jnp.sum(p ** 2))
    from repro.launch.steps import make_train_step
    with pytest.raises(ValueError, match="dense"):
        make_train_step(bundle, _FakeMesh(data=4, model=1),
                        algorithm="dsgt", gossip="ring")
