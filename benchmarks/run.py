"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * fig2_convex      : estimation error, PDSGD vs conventional DSGD
  * fig3_nonconvex   : decentralized digits training accuracy parity
  * fig5_dlg         : DLG attacker MSE, conventional vs PDSGD
  * table1_dp        : DP-noise baseline accuracy/DLG-error trade-off
  * remark5_entropy  : Thm 5 privacy bound (numeric vs closed form)
  * kernel_*         : Pallas kernel (interpret) vs jnp-oracle timing
  * bench_step_path  : PDSGD hot-loop paths (eager-host vs device-resident
                       vs lax.scan) — also writes BENCH_pdsgd.json at the
                       repo root so later PRs can regress against it
                       (scripts/bench_gate.py enforces the regression gate)
  * bench_pipeline   : scanned-loop data pipeline — staged per-step loops
                       vs the chunked prefetched scan on an LM config
                       (merged into BENCH_pdsgd.json)
  * bench_checkpoint : checkpointing cost on the hot loop — off vs
                       blocking save_checkpoint vs the async
                       CheckpointManager (merged into BENCH_pdsgd.json)
  * bench_dynamic_topology : time-varying mixing — static W vs per-step
                       link dropout through the fused mask->reweight->
                       gossip kernel (merged into BENCH_pdsgd.json)
  * bench_privacy_audit : wire-tap observation capture — capture-off vs
                       the external-eavesdropper and full-auditor taps on
                       the scanned hot loop; reports the capture overhead
                       (merged into BENCH_pdsgd.json)
  * bench_multihost  : multi-controller deployment tax — the tiny-LM run
                       driven by launch.multihost as one process vs two
                       socket-coupled rank processes
                       (merged into BENCH_pdsgd.json)
  * bench_overlap    : overlapped gossip — the fused ring kernel
                       (obfuscate + staged shifts in one pallas_call) vs
                       the eager and jitted staged-ring programs, and the
                       pipelined socket transport vs the blocking one at
                       world=2 (merged into BENCH_pdsgd.json)
  * bench_sharded_lm : sharded big-model PDSGD — a >=100M-param/agent LM
                       on an agents x fsdp mesh (4 fake devices) vs a
                       pure-data-parallel mean-grad baseline; reports the
                       gossip+obfuscation overhead ratio
                       (merged into BENCH_pdsgd.json)
  * bench_serve      : continuous-batching serving — seed Python loop vs
                       the device-resident chunk loop, and the slot
                       engine continuous vs gang admission under the
                       same Poisson offered load
                       (merged into BENCH_pdsgd.json)

``--only NAME`` runs a single benchmark (substring match).
"""
from __future__ import annotations

import argparse
import json
import sys
import os
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

ROWS = []


def _cpu_child_env() -> dict:
    """Environment for a benchmark child that measures host-side work
    (fake-device meshes, socket transports): pinned to the CPU, so it
    never competes with this process for a chip this process may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def _write_bench_json(update: dict):
    """Merge ``update`` into BENCH_pdsgd.json (so bench_step_path and
    bench_pipeline each own their keys without clobbering the other)."""
    path = os.path.join(REPO_ROOT, "BENCH_pdsgd.json")
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload.update(update)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _timeit(fn, n=5):
    fn()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------

def fig2_convex(iters=1200, runs=3):
    from repro.core import init_state, make_decentralized_step, make_topology
    from repro.core.schedules import paper_experiment
    from repro.data import estimation_problem

    top = make_topology("paper_fig1", 5)
    prob = estimation_problem(5, d=2, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])

    def loss_fn(p, batch):
        z, Mi = batch
        return jnp.mean(jnp.sum((z - p @ Mi.T) ** 2, -1))

    def run(algo, seed):
        step = make_decentralized_step(loss_fn, top, paper_experiment(0.05),
                                       algorithm=algo)
        state = init_state(jnp.zeros((2,)), 5)
        key = jax.random.key(seed)
        t0 = time.perf_counter()
        for k in range(iters):
            key, sk, bk = jax.random.split(key, 3)
            idx = jax.random.randint(bk, (5, 8), 0, 100)
            state, aux = step(state, (Z[jnp.arange(5)[:, None], idx], M), sk)
        dt = (time.perf_counter() - t0) / iters * 1e6
        xbar = np.asarray(jax.tree.leaves(state.params)[0]).mean(0)
        return np.linalg.norm(xbar - prob["theta_opt"]), dt

    for algo in ("pdsgd", "dsgd"):
        errs, dts = zip(*[run(algo, s) for s in range(runs)])
        emit(f"fig2_convex_{algo}", float(np.mean(dts)),
             f"final_err={np.mean(errs):.5f}")


def fig3_nonconvex(steps=400):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "examples"))
    import decentralized_learning as DL
    from repro.core import init_state, make_decentralized_step, make_topology
    from repro.core.schedules import warmup_harmonic
    from repro.data import noniid_partition, synthetic_digits

    m = 5
    top = make_topology("paper_fig1", m)
    x, y = synthetic_digits(3000, seed=0, size=8, classes=10)
    xv, yv = synthetic_digits(600, seed=1, size=8, classes=10)
    parts = noniid_partition(y, m, alpha=1.0, seed=0)
    for algo in ("pdsgd", "dsgd"):
        step = make_decentralized_step(DL.loss_fn, top,
                                       warmup_harmonic(0.5, hold=100),
                                       algorithm=algo)
        state = init_state(DL.conv_net_init(jax.random.key(0)), m)
        key = jax.random.key(1)
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        for k in range(steps):
            key, sk = jax.random.split(key)
            idxs = [rng.choice(p_, 16) for p_ in parts]
            bx = np.stack([x[i] for i in idxs])
            by = np.stack([y[i] for i in idxs])
            state, aux = step(state, (jnp.asarray(bx), jnp.asarray(by)), sk)
        dt = (time.perf_counter() - t0) / steps * 1e6
        va = DL.accuracy(state.params, jnp.asarray(xv), jnp.asarray(yv))
        emit(f"fig3_nonconvex_{algo}", dt, f"val_acc={va:.3f}")


def _dlg_setup():
    from repro.data import synthetic_digits
    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32) * 0.2),
        "b1": jnp.zeros((32,)),
        "w2": jnp.asarray(rng.normal(size=(32, 10)).astype(np.float32) * 0.2),
        "b2": jnp.zeros((10,)),
    }

    def loss(params, x, soft):
        h = jnp.tanh(x.reshape(x.shape[0], -1) @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        return -jnp.mean(jnp.sum(soft * jax.nn.log_softmax(logits), -1))

    x, y = synthetic_digits(1, seed=7, size=8, classes=10)
    x = jnp.asarray(x)
    soft = jax.nn.one_hot(jnp.asarray(y), 10)
    g = jax.grad(loss)(params, x, soft)
    return params, loss, x, soft, g


def fig5_dlg(steps=500):
    from repro.core.attacks import dlg_attack
    from repro.core.privacy import obfuscated_gradient
    params, loss, x, soft, g = _dlg_setup()
    t0 = time.perf_counter()
    res = dlg_attack(loss, params, g, x.shape, 10, key=jax.random.key(0),
                     steps=steps, lr=0.1, true_x=x)
    dt = (time.perf_counter() - t0) / steps * 1e6
    mse_conv = float(jnp.mean((res.recon_x - x) ** 2))
    emit("fig5_dlg_conventional", dt, f"attacker_mse={mse_conv:.5f}")
    obs = obfuscated_gradient(jax.random.key(9), g, jnp.float32(0.05))
    res2 = dlg_attack(loss, params, obs, x.shape, 10, key=jax.random.key(0),
                      steps=steps, lr=0.1, true_x=x)
    mse_ours = float(jnp.mean((res2.recon_x - x) ** 2))
    emit("fig5_dlg_pdsgd", dt,
         f"attacker_mse={mse_ours:.5f};degradation={mse_ours/max(mse_conv,1e-9):.1f}x")


def table1_dp(steps=500):
    """DP baseline: additive Gaussian noise trades DLG error for gradient
    distortion (accuracy); PDSGD (fig3/fig5 rows) needs no such trade."""
    from repro.core.attacks import dlg_attack
    params, loss, x, soft, g = _dlg_setup()
    for sigma in (0.0, 1e-3, 1e-2, 1e-1):
        noisy = jax.tree.map(
            lambda a: a + sigma * jax.random.normal(jax.random.key(5),
                                                    a.shape), g)
        t0 = time.perf_counter()
        res = dlg_attack(loss, params, noisy, x.shape, 10,
                         key=jax.random.key(0), steps=steps, lr=0.1, true_x=x)
        dt = (time.perf_counter() - t0) / steps * 1e6
        mse = float(jnp.mean((res.recon_x - x) ** 2))
        gn = float(sum(jnp.sum(a ** 2) for a in jax.tree.leaves(g))) ** 0.5
        nn = float(sum(jnp.sum((a - b) ** 2) for a, b in
                       zip(jax.tree.leaves(noisy), jax.tree.leaves(g)))) ** 0.5
        emit(f"table1_dp_sigma{sigma:g}", dt,
             f"attacker_mse={mse:.5f};grad_distortion={nn/gn:.3f}")


def remark5_entropy():
    from repro.core import entropy as E
    for kappa in (1.0, 5.0, 20.0):
        t0 = time.perf_counter()
        th_num = E.theta_numeric(0.01, kappa)
        dt = (time.perf_counter() - t0) * 1e6
        th_cl = E.theta_closed(0.01, kappa)
        emit(f"remark5_entropy_k{kappa:g}", dt,
             f"theta_num={th_num:.4f};theta_closed={th_cl:.4f};"
             f"mse_bound={E.mse_lower_bound(th_cl):.4f}")


def comm_cost(iters=1200, runs=2):
    """Sec. I claim: gradient-tracking methods [49,50] must share TWO
    variables (x and the tracker y) per iteration; PDSGD shares ONE mixed
    v_ij.  Row reports bytes/edge/iteration (d floats each) + final error
    of DSGT on the fig2 estimation problem for accuracy context."""
    import numpy as np_
    from repro.core import make_topology
    from repro.core.pdsgd import dsgt_update
    from repro.data import estimation_problem

    top = make_topology("paper_fig1", 5)
    prob = estimation_problem(5, d=2, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])
    W = jnp.asarray(top.weights, jnp.float32)
    d = 2

    def grad(p, idx):  # stochastic gradient of the per-agent quadratic
        z = Z[jnp.arange(5)[:, None], idx]
        def g1(pi, zi, Mi):
            return jax.grad(lambda p_: jnp.mean(
                jnp.sum((zi - p_ @ Mi.T) ** 2, -1)))(pi)
        return jax.vmap(g1)(p, z, M)

    errs = []
    for seed in range(runs):
        rng = np_.random.default_rng(seed)
        x = jnp.zeros((5, d))
        idx = jnp.asarray(rng.integers(0, 100, (5, 8)))
        g = grad(x, idx)
        y = g
        t0 = time.perf_counter()
        for k in range(iters):
            lam = jnp.float32(0.05 / (k + 1.0))
            x_n, _ = dsgt_update(x, y, g, g, W=W, lam=lam)
            idx = jnp.asarray(rng.integers(0, 100, (5, 8)))
            g_n = grad(x_n, idx)
            _, y = dsgt_update(x, y, g_n, g, W=W, lam=lam)
            x, g = x_n, g_n
        dt = (time.perf_counter() - t0) / iters * 1e6
        xbar = np_.asarray(x).mean(0)
        errs.append(np_.linalg.norm(xbar - prob["theta_opt"]))
    emit("comm_cost_dsgt", dt,
         f"final_err={np_.mean(errs):.5f};bytes_per_edge_iter={2*d*4}")
    emit("comm_cost_pdsgd", 0.0,
         f"bytes_per_edge_iter={d*4};half_of_dsgt=True")


def remark7_lambda_ablation(steps=300):
    """Beyond-paper ablation (Remark 7): empirical DLG error vs lam_bar.
    Theory (our closed form, DESIGN.md §1): h(g|λg) = log κ − γ_EM is
    *independent* of lam_bar — the protection comes from the multiplicative
    structure, not the stepsize magnitude.  The DLG attacker's empirical
    error should therefore stay high across lam_bar scales."""
    from repro.core.attacks import dlg_attack
    from repro.core.privacy import obfuscated_gradient
    params, loss, x, soft, g = _dlg_setup()
    for lam in (0.005, 0.05, 0.5):
        obs = obfuscated_gradient(jax.random.key(9), g, jnp.float32(lam))
        t0 = time.perf_counter()
        res = dlg_attack(loss, params, obs, x.shape, 10,
                         key=jax.random.key(0), steps=steps, lr=0.1,
                         true_x=x)
        dt = (time.perf_counter() - t0) / steps * 1e6
        mse = float(jnp.mean((res.recon_x - x) ** 2))
        emit(f"remark7_lambda{lam:g}", dt, f"attacker_mse={mse:.5f}")


def bench_step_path(iters=600, unroll_k=100):
    """Fig. 2 estimation workload (d=2, m=5) through the three hot-loop
    paths:

      * eager   — the seed behavior: schedule evaluated on host each step
                  (`int(state.step)` device->host sync) + python dispatch
      * fused   — device-resident schedule, zero host syncs, python loop
      * scanned — `make_scanned_steps`: unroll_k iterations per lax.scan
                  dispatch

    The paper's claim is privacy at zero overhead; that is only visible
    when the loop is dispatch-bound-free, so this row set is the repo's
    canonical perf trajectory (written to BENCH_pdsgd.json).
    """
    from repro.core import (init_state, make_decentralized_step,
                            make_scanned_steps, make_topology)
    from repro.core.schedules import paper_experiment
    from repro.data import estimation_problem

    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    prob = estimation_problem(m, d=d, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])

    def loss_fn(p, batch):
        z, Mi = batch
        return jnp.mean(jnp.sum((z - p @ Mi.T) ** 2, -1))

    sched = paper_experiment(0.05)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 100, size=(iters, m, 8)))
    batches = (Z[jnp.arange(m)[None, :, None], idx],
               jnp.broadcast_to(M[None], (iters,) + M.shape))
    keys = jax.random.split(jax.random.key(0), iters)
    batch_at = lambda k: jax.tree.map(lambda x: x[k], batches)

    def time_python_loop(step):
        state = init_state(jnp.zeros((d,)), m)
        state, _ = step(state, batch_at(0), keys[0])  # warmup/compile
        state = init_state(jnp.zeros((d,)), m)
        t0 = time.perf_counter()
        for k in range(iters):
            state, aux = step(state, batch_at(k), keys[k])
        jax.block_until_ready(state.params)
        return (time.perf_counter() - t0) / iters * 1e6, state

    results = {}
    # 1. seed path: host schedule eval forces a device->host sync per step
    step_eager = make_decentralized_step(loss_fn, top, sched,
                                         force_host_schedule=True,
                                         donate=False)
    us, st_e = time_python_loop(step_eager)
    results["eager"] = us
    # 2. device-resident step (zero host syncs), still one dispatch/step
    step_fused = make_decentralized_step(loss_fn, top, sched, donate=False)
    us, st_f = time_python_loop(step_fused)
    results["fused"] = us
    # 3. scanned: unroll_k steps per dispatch
    assert iters % unroll_k == 0
    scanned = make_scanned_steps(step_fused, unroll_k, donate=False)
    chunk = lambda x, c: jax.tree.map(
        lambda l: l[c * unroll_k:(c + 1) * unroll_k], x)
    state = init_state(jnp.zeros((d,)), m)
    state, _ = scanned(state, chunk(batches, 0), chunk(keys, 0))  # warmup
    state = init_state(jnp.zeros((d,)), m)
    t0 = time.perf_counter()
    for c in range(iters // unroll_k):
        state, aux = scanned(state, chunk(batches, c), chunk(keys, c))
    jax.block_until_ready(state.params)
    results["scanned"] = (time.perf_counter() - t0) / iters * 1e6

    err = float(np.linalg.norm(
        np.asarray(jax.tree.leaves(state.params)[0]).mean(0)
        - prob["theta_opt"]))
    payload = {
        "workload": f"fig2_estimation d={d} m={m} iters={iters}",
        "unroll_k": unroll_k,
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "speedup_fused_vs_eager": round(results["eager"] / results["fused"], 2),
        "speedup_scanned_vs_eager": round(
            results["eager"] / results["scanned"], 2),
        "final_err_scanned": err,
        "backend": jax.default_backend(),
    }
    _write_bench_json(payload)
    for name, us in results.items():
        emit(f"bench_step_path_{name}", us,
             f"steps_per_s={1e6 / us:.1f}")
    emit("bench_step_path_speedup", 0.0,
         f"scanned_vs_eager={payload['speedup_scanned_vs_eager']}x;"
         f"fused_vs_eager={payload['speedup_fused_vs_eager']}x")


def bench_pipeline(steps=384, unroll_k=96):
    """Tentpole bench: the scanned-loop data pipeline (chunked super-batches
    + background-thread prefetcher) vs the staged per-step loop, training an
    LM end-to-end.

    Like bench_step_path, this measures the dispatch/pipeline-bound regime —
    a further-reduced 1-layer LM config ("lm-pipeline-smoke") — because the
    pipeline's benefit is per-step HOST cost (staging, dispatch, schedule
    sync, batch synthesis) and on this CPU container a full smoke model's
    fwd/bwd drowns those in model flops.  All four rows run the same PDSGD
    math over the same `batch_at`/fold_in streams:

      * staged_eager_host  : seed behavior — one host batch staged per step,
                             schedule evaluated on host (device->host sync
                             every iteration)
      * staged_eager       : PR1 driver — device-resident schedule, still
                             one staged batch + one dispatch per step
      * staged_scanned     : lax.scan hot loop, but chunks synthesized
                             synchronously between scan dispatches
      * prefetched_scanned : full pipeline — `data.prefetch.Prefetcher`
                             double-buffers device-placed chunks under the
                             in-flight scan

    Results merge into BENCH_pdsgd.json under "bench_pipeline".
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import (init_state, make_decentralized_step,
                            make_scanned_steps, make_topology)
    from repro.core.schedules import warmup_harmonic
    from repro.data import make_lm_pipeline, make_placer, prefetch_chunks
    from repro.launch.steps import per_step_keys
    from repro.models import build_model

    cfg = dataclasses.replace(
        get_config("stablelm-3b-smoke"), name="lm-pipeline-smoke",
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=128)
    m, pab, seq = 4, 1, 16
    assert steps % unroll_k == 0
    pl = make_lm_pipeline(cfg.vocab_size, m, pab, seq, seed=0)
    bundle = build_model(cfg)
    top = make_topology("ring", m)
    sched = warmup_harmonic(0.4, hold=200)
    params0 = bundle.init(jax.random.key(0))
    base_key = jax.random.key(1)
    stage = make_placer(None)  # same placement both paths: apples-to-apples

    step_host = make_decentralized_step(bundle.loss_fn, top, sched,
                                        force_host_schedule=True)
    step_dev = make_decentralized_step(bundle.loss_fn, top, sched)
    scanned = make_scanned_steps(step_dev, unroll_k)

    def eager_loop(step):
        state = init_state(params0, m)
        state, aux = step(state, stage(pl.batch_at(0)), base_key)  # compile
        state = init_state(params0, m)
        t0 = time.perf_counter()
        for k in range(steps):
            sk = jax.random.fold_in(base_key, k)
            state, aux = step(state, stage(pl.batch_at(k)), sk)
            if k % 10 == 0:  # seed driver's logging cadence
                float(aux["loss"])
        jax.block_until_ready(jax.tree.leaves(state.params)[0])
        return (time.perf_counter() - t0) / steps * 1e6, float(aux["loss"])

    def scanned_loop(prefetched):
        state = init_state(params0, m)
        state, aux = scanned(state, stage(pl.chunk_at(0, unroll_k)),
                             per_step_keys(base_key, 0, unroll_k))  # compile
        state = init_state(params0, m)
        n_chunks = steps // unroll_k
        t0 = time.perf_counter()
        if prefetched:
            with prefetch_chunks(pl, unroll_k, num_chunks=n_chunks,
                                 place=stage) as chunks:
                for c, chunk in enumerate(chunks):
                    state, aux = scanned(
                        state, chunk,
                        per_step_keys(base_key, c * unroll_k, unroll_k))
                    float(aux["loss"].mean())  # per-chunk log reduction
        else:
            for c in range(n_chunks):
                chunk = stage(pl.chunk_at(c * unroll_k, unroll_k))
                state, aux = scanned(
                    state, chunk,
                    per_step_keys(base_key, c * unroll_k, unroll_k))
                float(aux["loss"].mean())
        jax.block_until_ready(jax.tree.leaves(state.params)[0])
        return ((time.perf_counter() - t0) / steps * 1e6,
                float(aux["loss"].mean()))

    def best_of(fn, *args, n=5):
        # identical deterministic work per repeat; min discards load spikes
        runs = [fn(*args) for _ in range(n)]
        return min(runs, key=lambda r: r[0])

    results, losses = {}, {}
    results["staged_eager_host"], losses["staged_eager_host"] = \
        best_of(eager_loop, step_host)
    results["staged_eager"], losses["staged_eager"] = \
        best_of(eager_loop, step_dev)
    results["staged_scanned"], losses["staged_scanned"] = \
        best_of(scanned_loop, False)
    results["prefetched_scanned"], losses["prefetched_scanned"] = \
        best_of(scanned_loop, True)

    payload = {
        "workload": (f"lm-pipeline-smoke 1L d32 v128 m={m} "
                     f"per_agent_batch={pab} seq={seq} steps={steps}"),
        "unroll_k": unroll_k,
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "speedup_prefetched_vs_staged": round(
            results["staged_eager_host"] / results["prefetched_scanned"], 2),
        "speedup_prefetched_vs_staged_scanned": round(
            results["staged_scanned"] / results["prefetched_scanned"], 2),
        "final_loss_prefetched": losses["prefetched_scanned"],
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_pipeline": payload})
    for name, us in results.items():
        emit(f"bench_pipeline_{name}", us, f"steps_per_s={1e6 / us:.1f}")
    emit("bench_pipeline_speedup", 0.0,
         f"prefetched_vs_staged={payload['speedup_prefetched_vs_staged']}x")


def bench_checkpoint(iters=3000, unroll_k=50, checkpoint_every=500):
    """Checkpointing tax on the Fig. 2 scanned hot loop: off vs blocking
    `save_checkpoint` vs the async `CheckpointManager`, saving every
    ``checkpoint_every`` steps.

    The cadence is deliberately brutal for a ~55k steps/s dispatch-bound
    loop — one save per ~9ms of compute, orders of magnitude more frequent
    than any real run — because that is where checkpoint cost shows at
    all.  Two things keep the rows honest: (1) the blocking row uses the
    same fast commit path (`io._write_npz`) as the manager, so the async
    gain is the overlap, not a slower strawman serializer; (2) on this
    dispatch-bound workload the main thread holds the GIL almost
    continuously, so writer bytecode competes for GIL slices instead of
    hiding under device compute — the measured recovery is therefore a
    LOWER bound on what a model-bound workload sees.

    The blocking row is the seed behavior the ROADMAP's "Async checkpoint
    writes" item calls out: np.asarray + npz serialization inline in the
    loop.  The async row snapshots on the caller thread (`jax.device_get`
    only) and commits on the daemon writer; its timing INCLUDES the final
    `close()` drain, so hidden-but-unfinished work can't flatter it.  The
    acceptance bar is async recovering >= 90% of the checkpoint-off
    steps/s.
    """
    import shutil
    import tempfile

    from repro.checkpoint import CheckpointManager, save_checkpoint
    from repro.core import (init_state, make_decentralized_step,
                            make_scanned_steps, make_topology)
    from repro.core.schedules import paper_experiment
    from repro.data import estimation_problem

    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    prob = estimation_problem(m, d=d, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])

    def loss_fn(p, batch):
        z, Mi = batch
        return jnp.mean(jnp.sum((z - p @ Mi.T) ** 2, -1))

    step = make_decentralized_step(loss_fn, top, paper_experiment(0.05),
                                   donate=False)
    scanned = make_scanned_steps(step, unroll_k, donate=False)
    assert iters % unroll_k == 0
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 100, size=(iters, m, 8)))
    batches = (Z[jnp.arange(m)[None, :, None], idx],
               jnp.broadcast_to(M[None], (iters,) + M.shape))
    keys = jax.random.split(jax.random.key(0), iters)
    chunk = lambda x, c: jax.tree.map(
        lambda l: l[c * unroll_k:(c + 1) * unroll_k], x)

    def run(mode):
        ckpt_dir = tempfile.mkdtemp(prefix=f"bench_ckpt_{mode}_")
        try:
            state = init_state(jnp.zeros((d,)), m)
            state, _ = scanned(state, chunk(batches, 0), chunk(keys, 0))
            state = init_state(jnp.zeros((d,)), m)
            manager = None
            if mode == "async":
                manager = CheckpointManager(ckpt_dir, keep_last=3)
            t0 = time.perf_counter()
            for c in range(iters // unroll_k):
                state, aux = scanned(state, chunk(batches, c),
                                     chunk(keys, c))
                k_next = (c + 1) * unroll_k
                # No save on the terminal chunk: this measures STEADY-STATE
                # checkpointing, where every save has subsequent compute to
                # overlap (the drain an end-of-run save can't hide is the
                # driver's close(), one-off by construction).
                if k_next % checkpoint_every == 0 and k_next < iters:
                    if mode == "blocking":
                        save_checkpoint(ckpt_dir, k_next, state)
                    elif mode == "async":
                        manager.save(k_next, state)
            if manager is not None:
                manager.close()  # drain counts against the async row
            jax.block_until_ready(state.params)
            return (time.perf_counter() - t0) / iters * 1e6
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    results = {mode: min(run(mode) for _ in range(5))
               for mode in ("off", "blocking", "async")}
    recovery = results["off"] / results["async"]
    payload = {
        "workload": (f"fig2_estimation d={d} m={m} iters={iters} "
                     f"checkpoint_every={checkpoint_every}"),
        "unroll_k": unroll_k,
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "async_recovery_of_off": round(recovery, 3),
        "blocking_overhead_vs_off": round(
            results["blocking"] / results["off"], 2),
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_checkpoint": payload})
    for name, us in results.items():
        emit(f"bench_checkpoint_{name}", us, f"steps_per_s={1e6 / us:.1f}")
    emit("bench_checkpoint_recovery", 0.0,
         f"async_recovery_of_off={recovery:.3f};"
         f"blocking_overhead={payload['blocking_overhead_vs_off']}x")


def bench_dynamic_topology(iters=600, unroll_k=100, rate=0.1):
    """Time-varying mixing tax on the Fig. 2 scanned hot loop, fused-kernel
    path: static W vs per-step link dropout through the fused
    mask -> Metropolis-re-weight -> gossip kernel
    (`kernels.masked_gossip_update`).

    Both rows run `use_pallas=True` (the Pallas interpreter on this CPU
    container — same code that compiles on TPU) so the comparison isolates
    what dropout adds: one (m, m) Bernoulli mask draw + the in-VMEM
    re-weighting, with W_k never staged from HBM.  The acceptance bar is
    dropout within 15% of static steps/s.  The derived column carries the
    final estimation error of the dropout run — convergence evidence that
    unreliable links still solve the paper's problem.
    """
    from repro.core import (init_state, make_decentralized_step, make_mixing,
                            make_scanned_steps, make_topology)
    from repro.core.schedules import paper_experiment
    from repro.data import estimation_problem

    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    prob = estimation_problem(m, d=d, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])

    def loss_fn(p, batch):
        z, Mi = batch
        return jnp.mean(jnp.sum((z - p @ Mi.T) ** 2, -1))

    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 100, size=(iters, m, 8)))
    batches = (Z[jnp.arange(m)[None, :, None], idx],
               jnp.broadcast_to(M[None], (iters,) + M.shape))
    keys = jax.random.split(jax.random.key(0), iters)
    chunk = lambda x, c: jax.tree.map(
        lambda l: l[c * unroll_k:(c + 1) * unroll_k], x)
    assert iters % unroll_k == 0

    def run(scanned):
        state = init_state(jnp.zeros((d,)), m)
        state, _ = scanned(state, chunk(batches, 0), chunk(keys, 0))
        state = init_state(jnp.zeros((d,)), m)
        t0 = time.perf_counter()
        for c in range(iters // unroll_k):
            state, aux = scanned(state, chunk(batches, c), chunk(keys, c))
        jax.block_until_ready(state.params)
        elapsed = time.perf_counter() - t0  # before the err host transfer
        err = float(np.linalg.norm(
            np.asarray(jax.tree.leaves(state.params)[0]).mean(0)
            - prob["theta_opt"]))
        return elapsed / iters * 1e6, err

    # One build (one trace/compile) per mode, OUTSIDE the repeat loop; the
    # repeats are interleaved so a load spike inflates BOTH rows instead
    # of silently skewing the static/dropout ratio the gate watches.
    processes = {"static": make_mixing(top),
                 "dropout": make_mixing(top, rate=rate, seed=1)}
    scans = {
        name: make_scanned_steps(
            make_decentralized_step(loss_fn, process, paper_experiment(0.05),
                                    use_pallas=True, donate=False),
            unroll_k, donate=False)
        for name, process in processes.items()
    }
    runs = {name: [] for name in processes}
    for _ in range(4):
        for name in processes:
            runs[name].append(run(scans[name]))
    results = {name: min(rs)[0] for name, rs in runs.items()}
    errs = {name: rs[0][1] for name, rs in runs.items()}

    payload = {
        "workload": (f"fig2_estimation d={d} m={m} iters={iters} "
                     f"dropout={rate} use_pallas=True"),
        "unroll_k": unroll_k,
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "dropout_overhead_vs_static": round(
            results["dropout"] / results["static"], 3),
        "final_err_static": errs["static"],
        "final_err_dropout": errs["dropout"],
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_dynamic_topology": payload})
    for name, us in results.items():
        emit(f"bench_dynamic_topology_{name}", us,
             f"steps_per_s={1e6 / us:.1f};final_err={errs[name]:.5f}")
    emit("bench_dynamic_topology_overhead", 0.0,
         f"dropout_vs_static={payload['dropout_overhead_vs_static']}x")


def bench_privacy_audit(iters=600, unroll_k=100):
    """Wire-tap capture tax on the Fig. 2 scanned hot loop: capture-off vs
    the external-eavesdropper tap (the v_ij tensor riding the scan's aux)
    vs the full auditor record (v + x/u/g/W/B ground truth).

    The ROADMAP's scenario-diversity north star wants the adversary's
    view to be a FIRST-CLASS benchmarked scenario, so the overhead of
    observing must be a committed number, not a guess: each step's
    capture adds one (m, m, D) outer-product tensor + the scan's aux
    stacking (T copies on device).  Rows are interleaved across repeats
    so a load spike inflates all three rather than skewing the ratio;
    the derived column carries capture_overhead (capture-on us / off us)
    — the acceptance bar is the eavesdropper tap within 25% of
    capture-off steps/s on this dispatch-bound worst case (a model-bound
    workload hides it entirely).
    """
    from repro.core import (init_state, make_decentralized_step,
                            make_scanned_steps, make_topology)
    from repro.core.schedules import paper_experiment
    from repro.data import estimation_problem
    from repro.privacy import observe as O

    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    prob = estimation_problem(m, d=d, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])

    def loss_fn(p, batch):
        z, Mi = batch
        return jnp.mean(jnp.sum((z - p @ Mi.T) ** 2, -1))

    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 100, size=(iters, m, 8)))
    batches = (Z[jnp.arange(m)[None, :, None], idx],
               jnp.broadcast_to(M[None], (iters,) + M.shape))
    keys = jax.random.split(jax.random.key(0), iters)
    chunk = lambda x, c: jax.tree.map(
        lambda l: l[c * unroll_k:(c + 1) * unroll_k], x)
    assert iters % unroll_k == 0

    observers = {"capture_off": None,
                 "eavesdropper": O.external_eavesdropper(),
                 "auditor": O.auditor()}
    scans = {
        name: make_scanned_steps(
            make_decentralized_step(loss_fn, top, paper_experiment(0.05),
                                    donate=False, observer=obs),
            unroll_k, donate=False)
        for name, obs in observers.items()
    }

    def run(scanned):
        state = init_state(jnp.zeros((d,)), m)
        state, _ = scanned(state, chunk(batches, 0), chunk(keys, 0))
        state = init_state(jnp.zeros((d,)), m)
        t0 = time.perf_counter()
        for c in range(iters // unroll_k):
            state, aux = scanned(state, chunk(batches, c), chunk(keys, c))
        jax.block_until_ready(state.params)
        return (time.perf_counter() - t0) / iters * 1e6

    runs = {name: [] for name in observers}
    for _ in range(4):
        for name in observers:
            runs[name].append(run(scans[name]))
    results = {name: min(rs) for name, rs in runs.items()}

    payload = {
        "workload": (f"fig2_estimation d={d} m={m} iters={iters} "
                     f"adversary=external_eavesdropper/auditor"),
        "unroll_k": unroll_k,
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "eavesdropper_overhead_vs_off": round(
            results["eavesdropper"] / results["capture_off"], 3),
        "auditor_overhead_vs_off": round(
            results["auditor"] / results["capture_off"], 3),
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_privacy_audit": payload})
    for name, us in results.items():
        emit(f"bench_privacy_audit_{name}", us,
             f"steps_per_s={1e6 / us:.1f}")
    emit("bench_privacy_audit_overhead", 0.0,
         f"eavesdropper_vs_off={payload['eavesdropper_overhead_vs_off']}x;"
         f"auditor_vs_off={payload['auditor_overhead_vs_off']}x")


def bench_fault_injection(iters=600, unroll_k=100):
    """Fault-tolerance tax on the Fig. 2 scanned hot loop, fused-kernel
    path: fault-free vs nan-sentinels-only vs markov crash churn vs
    guarded corrupt links.

    Four rows, all `use_pallas=True` over the same workload so each
    ratio isolates one mechanism: ``sentinel`` adds the traced isfinite
    reduction over loss+params (nan_policy="skip", no faults);
    ``crash`` adds the per-step fault realization + in-trace Metropolis
    re-weighting over survivors + row freezing; ``corrupt_guarded``
    routes gossip through the per-link finite-guard kernel
    (`kernels.gossip.guarded_gossip_update`, the (m, m, bn) v tensor
    in VMEM).  Rows are interleaved across repeats so a load spike
    inflates all four rather than skewing the ratios.  The derived
    columns carry the final estimation error of the off and crash runs
    — convergence evidence that 5% per-step crash onsets still solve
    the paper's problem (the degraded-but-correct acceptance bar).
    """
    from repro.core import (init_state, make_decentralized_step,
                            make_scanned_steps, make_topology)
    from repro.core.schedules import paper_experiment
    from repro.data import estimation_problem
    from repro.faults import make_faults

    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    prob = estimation_problem(m, d=d, s=3, n_per_agent=100, seed=0)
    Z, M = jnp.asarray(prob["Z"]), jnp.asarray(prob["M"])

    def loss_fn(p, batch):
        z, Mi = batch
        return jnp.mean(jnp.sum((z - p @ Mi.T) ** 2, -1))

    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 100, size=(iters, m, 8)))
    batches = (Z[jnp.arange(m)[None, :, None], idx],
               jnp.broadcast_to(M[None], (iters,) + M.shape))
    keys = jax.random.split(jax.random.key(0), iters)
    chunk = lambda x, c: jax.tree.map(
        lambda l: l[c * unroll_k:(c + 1) * unroll_k], x)
    assert iters % unroll_k == 0

    modes = {
        "off": dict(faults=None, nan_policy="off"),
        "sentinel": dict(faults=None, nan_policy="skip"),
        "crash": dict(faults=make_faults(m, crash_rate=0.05,
                                         restart_rate=0.5, seed=1),
                      nan_policy="skip"),
        "corrupt_guarded": dict(
            faults=make_faults(m, corrupt_rate=0.1, corrupt_mode="nan",
                               guard_clip=1e3, seed=1),
            nan_policy="skip"),
    }
    scans = {
        name: make_scanned_steps(
            make_decentralized_step(loss_fn, top, paper_experiment(0.05),
                                    use_pallas=True, donate=False, **kw),
            unroll_k, donate=False)
        for name, kw in modes.items()
    }

    def run(scanned):
        state = init_state(jnp.zeros((d,)), m)
        state, _ = scanned(state, chunk(batches, 0), chunk(keys, 0))
        state = init_state(jnp.zeros((d,)), m)
        t0 = time.perf_counter()
        for c in range(iters // unroll_k):
            state, aux = scanned(state, chunk(batches, c), chunk(keys, c))
        jax.block_until_ready(state.params)
        elapsed = time.perf_counter() - t0
        err = float(np.linalg.norm(
            np.asarray(jax.tree.leaves(state.params)[0]).mean(0)
            - prob["theta_opt"]))
        return elapsed / iters * 1e6, err

    runs = {name: [] for name in modes}
    for _ in range(4):
        for name in modes:
            runs[name].append(run(scans[name]))
    results = {name: min(rs)[0] for name, rs in runs.items()}
    errs = {name: rs[0][1] for name, rs in runs.items()}

    payload = {
        "workload": (f"fig2_estimation d={d} m={m} iters={iters} "
                     f"crash=0.05/0.5 corrupt=0.1 use_pallas=True"),
        "unroll_k": unroll_k,
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "sentinel_overhead_vs_off": round(
            results["sentinel"] / results["off"], 3),
        "crash_overhead_vs_off": round(results["crash"] / results["off"], 3),
        "corrupt_guarded_overhead_vs_off": round(
            results["corrupt_guarded"] / results["off"], 3),
        "final_err_off": errs["off"],
        "final_err_crash": errs["crash"],
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_fault_injection": payload})
    for name, us in results.items():
        emit(f"bench_fault_injection_{name}", us,
             f"steps_per_s={1e6 / us:.1f};final_err={errs[name]:.5f}")
    emit("bench_fault_injection_overhead", 0.0,
         f"sentinel_vs_off={payload['sentinel_overhead_vs_off']}x;"
         f"crash_vs_off={payload['crash_overhead_vs_off']}x;"
         f"corrupt_guarded_vs_off="
         f"{payload['corrupt_guarded_overhead_vs_off']}x")


def bench_multihost(steps=8, agents=4):
    """Multi-controller deployment tax: the same tiny-LM PDSGD run driven
    by `launch.multihost` as ONE process (in-process dense transport) vs
    TWO rank processes exchanging framed v_ij over TCP sockets.

    Both runs walk bit-identical trajectories (pinned by
    tests/test_multihost.py); the rows therefore isolate pure deployment
    cost — rendezvous, per-step socket framing, and the per-rank
    checkpoint shards — as us/step from each rank's own wall clock.  The
    derived column carries the socket-vs-inproc overhead ratio; on this
    single CPU the two ranks also SHARE the core, so the ratio is an
    upper bound on what separate hosts see.
    """
    import shutil
    import subprocess
    import tempfile

    def launch(world):
        root = tempfile.mkdtemp(prefix=f"bench_mh_w{world}_")
        try:
            cmd = [sys.executable, "-m", "repro.launch.multihost",
                   "--arch", "stablelm-3b-tiny", "--agents", str(agents),
                   "--world", str(world), "--steps", str(steps),
                   "--per-agent-batch", "2", "--seq-len", "16",
                   "--seed", "0", "--checkpoint-dir", root,
                   "--checkpoint-every", str(steps), "--timeout", "120"]
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=600, env=_cpu_child_env())
            if out.returncode != 0:
                raise RuntimeError(f"multihost world={world} failed:\n"
                                   + out.stderr[-2000:])
            summary = json.loads(out.stdout.strip().splitlines()[-1])
            ranks = summary["multihost_summary"]["ranks"]
            return max(r["us_per_step"] for r in ranks.values()
                       if r is not None)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    results = {"inproc_world1": launch(1), "socket_world2": launch(2)}
    overhead = results["socket_world2"] / results["inproc_world1"]
    payload = {
        "workload": (f"stablelm-3b-tiny m={agents} steps={steps} "
                     f"per_agent_batch=2 seq=16 via launch.multihost"),
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 1)}
            for name, us in results.items()
        },
        "socket_overhead_vs_inproc": round(overhead, 3),
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_multihost": payload})
    for name, us in results.items():
        emit(f"bench_multihost_{name}", us, f"steps_per_s={1e6 / us:.1f}")
    emit("bench_multihost_overhead", 0.0,
         f"socket_vs_inproc={overhead:.3f}x")


_OVERLAP_RANK_SCRIPT = r'''
"""One rank of the bench_overlap socket family (spawned twice)."""
import hashlib, json, socket, sys, time
import numpy as np
sys.path.insert(0, sys.argv[1])
from repro.dist import transport as T

rank, mode, p0, p1, steps, agents, dim = (
    int(sys.argv[2]), sys.argv[3], int(sys.argv[4]), int(sys.argv[5]),
    int(sys.argv[6]), int(sys.argv[7]), int(sys.argv[8]))
world = 2
A = np.zeros((agents, agents), np.int64)
for i in range(agents):
    A[i, (i + 1) % agents] = A[(i + 1) % agents, i] = 1
deg = A.sum(1)
W = np.zeros((agents, agents), np.float32)
for i in range(agents):
    for j in range(agents):
        if A[i, j]:
            W[i, j] = 1.0 / (1 + max(deg[i], deg[j]))
    W[i, i] = 1 - W[i].sum()
rng = np.random.default_rng(0)
Bm = (W * rng.uniform(0.5, 1.5, W.shape).astype(np.float32)
      * A).astype(np.float32)
np.fill_diagonal(Bm, 0.2)
L = agents // world
endpoints = {0: ("127.0.0.1", p0), 1: ("127.0.0.1", p1)}
ls = socket.socket()
ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
ls.bind(endpoints[rank])
ls.listen(4)
if rank == 0:  # wait until rank 1's listener is up (poll-connect probe)
    for _ in range(200):
        try:
            socket.create_connection(endpoints[1], timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.2)
secret = T.derive_wire_secret(0, 0)
if mode == "blocking":
    tr = T.SocketTransport(A, rank, world, endpoints, ls, timeout=60.0,
                           secret=secret)
else:
    tr = T.PipelinedSocketTransport(A, rank, world, endpoints, ls,
                                    timeout=60.0, secret=secret,
                                    frames_ahead=1)
x = rng.standard_normal((L, dim)).astype(np.float32) + rank
t0 = time.monotonic()
for s in range(steps):
    u = x * 0.1  # trivial local "gradient": isolates the transport cost
    x = tr.exchange(x, u, W, Bm, step=s)
dt = time.monotonic() - t0
print(json.dumps({"rank": rank, "us_per_step": dt / steps * 1e6,
                  "sha": hashlib.sha256(x.tobytes()).hexdigest(),
                  "drops": tr.drops, "tag_failures": tr.tag_failures,
                  "comm_wait_s": round(tr.comm_wait_s, 4)}), flush=True)
tr.close()
'''


def bench_overlap(steps=30, ring_cols=65536, sock_steps=40,
                  sock_dim=262144, agents=8):
    """Overlapped gossip: the two headline rows of the PR.

    Ring family (in-process, m=8 torus): the Λ-draw + obfuscate + staged
    ring shifts of Eq. (4) as (a) the eager per-direction jnp loop the
    dense fallback runs, (b) the same program under ONE jit
    (`ref.ring_obfuscate_gossip_ref` — the bit-parity oracle), and (c)
    the fused `ring_obfuscate_gossip` pallas kernel that builds direction
    d+1's v tiles in the double-buffered VMEM slot while direction d's
    shift is consumed.  The fused kernel must match the jitted oracle
    BITWISE (asserted inline, dropout tables too); on this CPU the
    kernel runs in interpret mode, so (b) is the fastest row and the
    fused-vs-staged headline compares (c) against the EAGER staging it
    replaces — on TPU the kernel is the only row that overlaps the DMA.

    Socket family (two subprocess ranks, ring m=8, D=262k): the same
    multi-step exchange through the blocking `SocketTransport` vs the
    `PipelinedSocketTransport` (async send thread, eager receive thread,
    frames_ahead=1 runahead window).  Final params must agree EXACTLY
    (sha256 asserted) with zero drops; the win on one shared CPU core is
    eliminated serial framing work, so separate hosts see at least this.
    """
    import socket
    import subprocess
    import tempfile

    import jax.random as jrandom

    from repro.dist import collectives as C
    from repro.kernels import ref as kref
    from repro.kernels import ring_obfuscate_gossip

    # --- ring family ------------------------------------------------------
    n_data, n_pod, m = agents, 1, agents
    b_tab = C.sample_b_draws(jrandom.key(0), m, n_data, n_pod)
    ndirs = b_tab.shape[1] - 1
    wts = C.torus_weights(n_data, n_pod)
    w_tab = jnp.concatenate(
        [jnp.full((m, 1), wts["w_self"], jnp.float32),
         jnp.full((m, ndirs), wts["w_edge"], jnp.float32)], axis=1)
    perms = C.perm_stack(n_data, n_pod)
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((m, ring_cols)).astype(np.float32))
    G = jnp.asarray(rng.standard_normal((m, ring_cols)).astype(np.float32))
    bits = jrandom.bits(jrandom.key(2), (m, ring_cols), dtype=jnp.uint32)
    lam_bar = 0.05

    def staged_eager():
        lam = (2.0 * jnp.float32(lam_bar)) * kref.bits_to_uniform(bits)
        u = lam * G
        out = w_tab[:, 0:1] * X - b_tab[:, 0:1] * u
        for d in range(ndirs):
            v = w_tab[:, d + 1:d + 2] * X - b_tab[:, d + 1:d + 2] * u
            out = out + perms[d] @ v
        return out

    _staged_jit = jax.jit(kref.ring_obfuscate_gossip_ref)
    staged_jit = lambda: _staged_jit(w_tab, b_tab, perms, X, G, bits,
                                     lam_bar)[0]
    # one column tile per call: under CPU interpret the grid loop is pure
    # dispatch overhead, and the double-buffered staging it drives only
    # pays off on TPU where it overlaps a real DMA
    fused = lambda: ring_obfuscate_gossip(w_tab, b_tab, perms, X, G, bits,
                                          lam_bar, block_n=ring_cols)

    # parity is part of the bench contract, not just the test suite
    assert np.array_equal(np.asarray(fused()), np.asarray(staged_jit()))
    np.testing.assert_allclose(np.asarray(staged_eager()),
                               np.asarray(fused()), atol=2e-5, rtol=2e-5)
    keep = jnp.ones((m, ndirs), jnp.float32).at[::2, 0].set(0.0)
    b_m = C.mask_b_draws(b_tab, keep)
    w_m = (w_tab.at[:, 0].add(w_tab[:, 1] * (1 - keep[:, 0])))\
        .at[:, 1].set(w_tab[:, 1] * keep[:, 0])
    drop_fused = ring_obfuscate_gossip(w_m, b_m, perms, X, G, bits, lam_bar,
                                       block_n=ring_cols)
    drop_ref = jax.jit(kref.ring_obfuscate_gossip_ref)(
        w_m, b_m, perms, X, G, bits, lam_bar)[0]
    np.testing.assert_allclose(np.asarray(drop_fused), np.asarray(drop_ref),
                               atol=2e-6, rtol=2e-6)

    results = {
        "ring_staged_eager": _timeit(staged_eager, n=steps),
        "ring_staged_jit": _timeit(staged_jit, n=steps),
        "ring_fused": _timeit(fused, n=steps),
    }

    # --- socket family ----------------------------------------------------
    def _free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    import socket
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_OVERLAP_RANK_SCRIPT)
        script = f.name
    src_dir = os.path.join(REPO_ROOT, "src")
    sock_rows = {}
    try:
        for mode in ("blocking", "pipelined"):
            p0, p1 = _free_port(), _free_port()
            procs = []
            for r in range(2):
                procs.append(subprocess.Popen(
                    [sys.executable, script, src_dir, str(r), mode, str(p0),
                     str(p1), str(sock_steps), str(agents), str(sock_dim)],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, env=_cpu_child_env()))
                time.sleep(0.3)
            outs = []
            for p in procs:
                stdout, stderr = p.communicate(timeout=600)
                if p.returncode != 0:
                    raise RuntimeError(f"overlap rank ({mode}) failed:\n"
                                       + stderr[-2000:])
                outs.append(json.loads(stdout.strip().splitlines()[-1]))
            assert all(o["drops"] == 0 and o["tag_failures"] == 0
                       for o in outs), outs
            sock_rows[mode] = outs
    finally:
        os.unlink(script)
    assert all(sock_rows["blocking"][r]["sha"]
               == sock_rows["pipelined"][r]["sha"] for r in range(2)), \
        "pipelined transport diverged from the blocking trajectory"
    results["socket_blocking_world2"] = max(
        o["us_per_step"] for o in sock_rows["blocking"])
    results["socket_pipelined_world2"] = max(
        o["us_per_step"] for o in sock_rows["pipelined"])

    fused_x = results["ring_staged_eager"] / results["ring_fused"]
    pipe_x = (results["socket_blocking_world2"]
              / results["socket_pipelined_world2"])
    payload = {
        "workload": (f"ring m={agents} cols={ring_cols} (kernel family) / "
                     f"world=2 D={sock_dim} steps={sock_steps} "
                     f"(socket family)"),
        "paths": {
            name: {"us_per_step": round(us, 2)}
            for name, us in results.items()
        },
        "fused_vs_staged_eager": round(fused_x, 3),
        "pipelined_vs_blocking": round(pipe_x, 3),
        "comm_wait_s": {mode: [o["comm_wait_s"] for o in sock_rows[mode]]
                        for mode in sock_rows},
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_overlap": payload})
    for name, us in results.items():
        emit(f"bench_overlap_{name}", us, "")
    emit("bench_overlap_ratios", 0.0,
         f"fused_vs_staged={fused_x:.3f}x;pipelined_vs_blocking="
         f"{pipe_x:.3f}x")


_SHARDED_LM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, {src!r})
import dataclasses, json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import init_state, make_decentralized_step, make_topology
from repro.core.schedules import warmup_harmonic
from repro.data import make_lm_pipeline
from repro.dist.sharding import TRAIN_RULES, audit_rules, logical_spec
from repro.launch.mesh import make_sharded_mesh
from repro.launch.specs import with_agent_axis
from repro.models import build_model
from repro.optim import shard_like

m, pab, seq, steps, lam = {agents}, 1, 16, {steps}, 0.02
mesh = make_sharded_mesh(agents=m, fsdp={fsdp}, tensor=1)

# ~115M-param LM (>=100M/agent): 100.7M tied embedding (vocab 131072 x 768)
# + 2 dense layers of ~7.1M.  Kept to 2 layers so the bench isolates what
# the ISSUE asks for — the per-step UPDATE cost over a big param tree —
# rather than CPU fwd/bwd flops.
cfg = dataclasses.replace(
    get_config("stablelm-3b"), name="sharded-lm-bench",
    num_layers=2, d_model=768, num_heads=12, num_kv_heads=12, head_dim=64,
    d_ff=2048, vocab_size=131072, tie_embeddings=True, dtype="float32")
bundle = build_model(cfg, mesh=mesh)
assert [f for f in audit_rules(bundle.abstract(), bundle.logical_axes(),
                               mesh) if f["severity"] == "error"] == []
params_per_agent = int(sum(np.prod(l.shape)
                           for l in jax.tree.leaves(bundle.abstract())))
assert params_per_agent >= 100_000_000, params_per_agent

pipeline = make_lm_pipeline(cfg.vocab_size, m, pab, seq, seed=0)
base_key = jax.random.key(1)

# --- PDSGD: W-gossip + B/Lambda obfuscation over the sharded pytree -------
p_abs, p_log = with_agent_axis(bundle.abstract(), bundle.logical_axes(), m)
leaf_specs = jax.tree.map(
    lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
    p_abs, p_log)
params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), leaf_specs)
step = make_decentralized_step(
    bundle.loss_fn, make_topology("ring", m), warmup_harmonic(lam, hold=100),
    spmd_axis_name="data", kernel_layout="leafwise", mesh=mesh,
    leaf_specs=leaf_specs, donate=False)
params0 = bundle.init(jax.random.key(0))

def run_pdsgd():
    state = init_state(params0, m)
    state = jax.device_put(state, shard_like(
        state, state.params, params_sh,
        scalar_sharding=NamedSharding(mesh, P())))
    state, aux = step(state, pipeline.batch_at(0), base_key)  # compile
    t0 = time.perf_counter()
    for k in range(steps):
        state, aux = step(state, pipeline.batch_at(k),
                          jax.random.fold_in(base_key, k))
    jax.block_until_ready(state.params)
    n_sharded = sum(0 if l.sharding.is_fully_replicated else 1
                    for l in jax.tree.leaves(state.params))
    return ((time.perf_counter() - t0) / steps * 1e6,
            float(aux["loss"]), n_sharded)

# --- baseline: pure data parallelism (one param copy, mean-grad SGD) ------
# Same model, mesh, batches, and stepsize; the ONLY difference is the
# update rule — allreduce-mean gradient + broadcast SGD instead of the
# m-copy W-gossip + per-agent B/Lambda draws.  The ratio therefore prices
# exactly what decentralized privacy adds on top of conventional training.
dp_specs = jax.tree.map(
    lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
    bundle.abstract(), bundle.logical_axes())
dp_grad = jax.vmap(jax.value_and_grad(bundle.loss_fn), in_axes=(None, 0))

@jax.jit
def dp_step(p, batch):
    losses, grads = dp_grad(p, batch)
    p = jax.tree.map(lambda x, g: x - lam * g.mean(0), p, grads)
    return p, losses.mean()

def run_dp():
    p = jax.device_put(params0, jax.tree.map(
        lambda s: NamedSharding(mesh, s), dp_specs))
    p, loss = dp_step(p, pipeline.batch_at(0))  # compile
    t0 = time.perf_counter()
    for k in range(steps):
        p, loss = dp_step(p, pipeline.batch_at(k))
    jax.block_until_ready(p)
    return (time.perf_counter() - t0) / steps * 1e6, float(loss)

pdsgd_us, pdsgd_loss, n_sharded = run_pdsgd()
dp_us, dp_loss = run_dp()
assert n_sharded > 0, "params never left the replicated layout"
assert np.isfinite(pdsgd_loss) and np.isfinite(dp_loss)
print(json.dumps({{"params_per_agent": params_per_agent,
                   "mesh": dict(mesh.shape),
                   "pdsgd_us": pdsgd_us, "pure_dp_us": dp_us,
                   "loss_pdsgd": pdsgd_loss, "loss_dp": dp_loss,
                   "n_sharded": n_sharded}}))
"""


def bench_sharded_lm(steps=4, agents=2, fsdp=2):
    """Sharded big-model PDSGD vs pure data parallelism: a ~115M-param LM
    (>=100M params/agent — the tied 131072x768 embedding dominates) trained
    for a few steps on an agents=2 x fsdp=2 mesh of 4 fake host devices in
    a subprocess (the parent pinned jax to 1 device at import).

    Both rows share the model, mesh, batches, and stepsize; they differ
    only in the update — PDSGD's m param copies + W-gossip einsum +
    per-agent B/Lambda randomness vs one copy + mean-grad broadcast SGD.
    The derived ratio is the ISSUE's committed number: what Eq. (3)/(4)
    privacy costs over conventional data-parallel training at big-model
    scale.  On this 1-core container the 4 fake devices time-slice, so
    the ratio (same slicing both rows) is the signal; absolute us/step
    is not TPU-predictive.
    """
    import subprocess
    src = os.path.join(REPO_ROOT, "src")
    script = _SHARDED_LM_SCRIPT.format(src=src, agents=agents, fsdp=fsdp,
                                       steps=steps)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1200,
                         env=_cpu_child_env())
    if out.returncode != 0:
        raise RuntimeError("bench_sharded_lm subprocess failed:\n"
                           + out.stderr[-3000:])
    res = json.loads(out.stdout.strip().splitlines()[-1])
    results = {"pure_dp": res["pure_dp_us"], "pdsgd_sharded": res["pdsgd_us"]}
    overhead = results["pdsgd_sharded"] / results["pure_dp"]
    payload = {
        "workload": (f"sharded-lm-bench {res['params_per_agent']} "
                     f"params/agent m={agents} fsdp={fsdp} "
                     f"per_agent_batch=1 seq=16 steps={steps}"),
        "params_per_agent": res["params_per_agent"],
        "mesh": res["mesh"],
        "sharded_param_leaves": res["n_sharded"],
        "paths": {
            name: {"us_per_step": round(us, 2),
                   "steps_per_s": round(1e6 / us, 3)}
            for name, us in results.items()
        },
        "gossip_obfuscation_overhead_vs_pure_dp": round(overhead, 3),
        "final_loss_pdsgd": res["loss_pdsgd"],
        "final_loss_pure_dp": res["loss_dp"],
        "backend": jax.default_backend(),
    }
    _write_bench_json({"bench_sharded_lm": payload})
    for name, us in results.items():
        emit(f"bench_sharded_lm_{name}", us, f"steps_per_s={1e6 / us:.3f}")
    emit("bench_sharded_lm_overhead", 0.0,
         f"pdsgd_vs_pure_dp={overhead:.3f}x;"
         f"params_per_agent={res['params_per_agent']}")


def bench_serve(arch="stablelm-3b-tiny", slots=4, prompt_len=16,
                gen=32, chunk=8):
    """Continuous-batching serving subsystem (repro.serve).

    Four measured paths on the same tiny LM:
      * python_loop — the seed serving loop: one host dispatch + host-side
        sample per generated token (batch of ``slots`` rows);
      * device_loop — the lax.scan chunk loop (`serve.loop`): ``chunk``
        tokens per dispatch, sampling in-trace;
      * continuous / gang — the full `ServeEngine` under the SAME
        open-loop Poisson arrivals, continuous slot re-fill vs
        run-to-completion wave admission.

    us_per_step keys are microseconds per generated token (gate-
    comparable across runs); the engine rows add tokens/s, TTFT and
    latency percentiles.
    """
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import Request, ServeEngine, init_loop_state, \
        make_decode_loop
    from repro.models.common import pad_vocab

    cfg = get_config(arch)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    B = slots
    key = jax.random.key(1)
    batch = {"tokens": jax.random.randint(key, (B, prompt_len), 0,
                                          cfg.vocab_size)}
    prefill = jax.jit(bundle.prefill_fn)
    decode = jax.jit(bundle.decode_fn)
    out0 = jax.block_until_ready(prefill(params, batch))
    pos0 = int(out0["pos"])

    # -- seed-style Python loop: one dispatch per token -------------------
    def python_loop():
        logits, cache = out0["logits"], out0["cache"]
        for p in range(pos0, pos0 + gen):
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            o = decode(params, toks, cache, jnp.asarray(p, jnp.int32))
            logits, cache = o["logits"], o["cache"]
        return logits
    us_py = _timeit(python_loop, n=3) / (gen * B)
    emit("bench_serve_python_loop", us_py, f"batch={B};per=token")

    # -- device-resident chunk loop ---------------------------------------
    # The loop donates its state, so timing CHAINS states call-to-call
    # (pos keeps advancing around the ring; every slot stays active via an
    # unreachable token budget) — each timed call is a steady full batch.
    loop = make_decode_loop(bundle, chunk=chunk)
    state = init_loop_state(prefill(params, batch)["cache"], B,
                            pad_vocab(cfg.vocab_size), jax.random.key(0))
    state.update(logits=out0["logits"].astype(jnp.float32),
                 pos=jnp.full((B,), pos0, jnp.int32),
                 req_id=jnp.arange(B, dtype=jnp.int32),
                 active=jnp.ones((B,), bool),
                 remaining=jnp.full((B,), 1 << 30, jnp.int32))
    holder = {"s": state}

    def device_chunk():
        s, toks, _ = loop(params, holder["s"])
        holder["s"] = s
        return toks
    us_dev = _timeit(device_chunk, n=6) / (chunk * B)
    emit("bench_serve_device_loop", us_dev,
         f"chunk={chunk};speedup_vs_python={us_py / us_dev:.2f}x")

    # -- continuous vs gang at the same offered load ----------------------
    # Bimodal lengths: gang makes every short request in a wave wait for
    # the wave's longest; continuous re-fills the short request's slot as
    # soon as it retires.  Load sits near capacity so a queue exists.
    n_req = 4 * slots
    gens = np.where(np.arange(n_req) % 2 == 0, gen, max(gen // 4, 1))
    cap_tok_s = 1e6 / us_dev
    rate = 0.9 * cap_tok_s / float(gens.mean())   # req/s, ~90% of peak
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = rng.integers(0, cfg.vocab_size, (n_req, prompt_len),
                           dtype=np.int32)
    engines = {}
    for adm in ("continuous", "gang"):
        eng = ServeEngine(bundle, params, slots=slots,
                          max_seq_len=prompt_len + gen, decode_chunk=chunk,
                          admission=adm, seed=0)
        eng.warmup(prompt_len)
        comps = eng.run([Request(req_id=i, tokens=prompts[i],
                                 max_new_tokens=int(gens[i]),
                                 arrival_time=float(arrivals[i]))
                         for i in range(n_req)])
        lat = np.asarray([c.latency for c in comps]) * 1e3
        ttft = np.asarray([c.ttft for c in comps
                           if c.first_token_at is not None]) * 1e3
        toks = sum(len(c.tokens) for c in comps)
        span = max(c.finished_at for c in comps) - float(arrivals[0])
        engines[adm] = {
            "us_per_step": 1e6 * span / toks,
            "tokens_per_s": round(toks / span, 1),
            "completed": len(comps),
            "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 2),
            "latency_p50_ms": round(float(np.percentile(lat, 50)), 2),
            "latency_p99_ms": round(float(np.percentile(lat, 99)), 2),
        }
        emit(f"bench_serve_{adm}", engines[adm]["us_per_step"],
             f"p50_ms={engines[adm]['latency_p50_ms']};"
             f"tokens_per_s={engines[adm]['tokens_per_s']}")

    payload = {
        "arch": arch, "slots": slots, "prompt_len": prompt_len,
        "gen_tokens": gen, "decode_chunk": chunk,
        "offered_load_req_s": round(rate, 2),
        "python_loop": {"us_per_step": round(us_py, 2)},
        "device_loop": {"us_per_step": round(us_dev, 2),
                        "speedup_vs_python":
                            round(us_py / us_dev, 3)},
        "continuous": engines["continuous"],
        "gang": engines["gang"],
        "p50_continuous_vs_gang":
            round(engines["continuous"]["latency_p50_ms"]
                  / engines["gang"]["latency_p50_ms"], 3),
    }
    _write_bench_json({"bench_serve": payload})


def kernel_benches():
    from repro.kernels import (flash_attention, gossip_update,
                               obfuscate_update, ssd_intra_chunk)
    from repro.kernels import ref
    rng = np.random.default_rng(0)

    q = jnp.asarray(rng.normal(size=(2, 256, 4, 64)).astype(np.float32))
    us_k = _timeit(lambda: flash_attention(q, q, q, causal=True, bq=64,
                                           bk=64), n=3)
    us_r = _timeit(lambda: ref.flash_attention_ref(q, q, q, causal=True), n=3)
    emit("kernel_flash_attention", us_k, f"ref_us={us_r:.1f};interpret=True")

    W = jnp.asarray(rng.dirichlet(np.ones(16), 16).T.astype(np.float32))
    X = jnp.asarray(rng.normal(size=(16, 65536)).astype(np.float32))
    us_k = _timeit(lambda: gossip_update(W, W, X, X), n=3)
    us_r = _timeit(lambda: ref.gossip_ref(W, W, X, X), n=3)
    emit("kernel_gossip", us_k, f"ref_us={us_r:.1f}")

    x = jnp.asarray(rng.normal(size=(16, 4096)).astype(np.float32))
    bits = jax.random.bits(jax.random.key(0), x.shape, dtype=jnp.uint32)
    us_k = _timeit(lambda: obfuscate_update(x, x, bits, 0.1, 0.5, 0.3,
                                            block=(16, 512)), n=3)
    us_r = _timeit(lambda: ref.obfuscate_ref(x, x, bits, 0.1, 0.5, 0.3), n=3)
    emit("kernel_obfuscate", us_k, f"ref_us={us_r:.1f}")

    xs = jnp.asarray(rng.normal(size=(4, 64, 2, 8)).astype(np.float32))
    dt_ = jnp.abs(jnp.asarray(rng.normal(size=(4, 64, 2)).astype(np.float32)))
    acum = jnp.cumsum(dt_ * -0.5, axis=1)
    Bm = jnp.asarray(rng.normal(size=(4, 64, 16)).astype(np.float32))
    us_k = _timeit(lambda: ssd_intra_chunk(xs, dt_, acum, Bm, Bm), n=3)
    us_r = _timeit(lambda: ref.ssd_intra_chunk_ref(xs, dt_, acum, Bm, Bm), n=3)
    emit("kernel_ssd_chunk", us_k, f"ref_us={us_r:.1f}")


BENCHES = {
    "remark5_entropy": remark5_entropy,
    "fig2_convex": fig2_convex,
    "fig5_dlg": fig5_dlg,
    "table1_dp": table1_dp,
    "remark7_lambda_ablation": remark7_lambda_ablation,
    "comm_cost": comm_cost,
    "bench_step_path": bench_step_path,
    "bench_pipeline": bench_pipeline,
    "bench_checkpoint": bench_checkpoint,
    "bench_dynamic_topology": bench_dynamic_topology,
    "bench_privacy_audit": bench_privacy_audit,
    "bench_fault_injection": bench_fault_injection,
    "bench_multihost": bench_multihost,
    "bench_overlap": bench_overlap,
    "bench_sharded_lm": bench_sharded_lm,
    "bench_serve": bench_serve,
    "kernel_benches": kernel_benches,
    "fig3_nonconvex": fig3_nonconvex,
}


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="run a single benchmark (substring match on "
                        + ", ".join(BENCHES))
    args = p.parse_args(argv)
    use_compile_cache()
    if args.only:
        selected = {k: v for k, v in BENCHES.items() if args.only in k}
        if not selected:
            raise SystemExit(f"no benchmark matches {args.only!r}; "
                             f"have {sorted(BENCHES)}")
    else:
        selected = BENCHES
    print("name,us_per_call,derived")
    for fn in selected.values():
        fn()
    if not args.only:
        # Only a full sweep owns the canonical CSV — a --only spot check
        # must not clobber it with a partial row set.
        out = os.path.join(os.path.dirname(__file__), "results",
                           "bench_results.csv")
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            f.write("name,us_per_call,derived\n" + "\n".join(ROWS) + "\n")


if __name__ == '__main__':
    main()
