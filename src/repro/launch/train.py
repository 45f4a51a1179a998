"""Decentralized PDSGD training driver.

Runs the full stack end-to-end: config -> model -> streaming data pipeline
-> PDSGD step -> checkpoints.  On this CPU container use a smoke config; on
a TPU slice pass a full arch + mesh flags.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m-smoke \
      --agents 4 --steps 50 --per-agent-batch 2 --seq-len 64

``--unroll-k K`` (K > 1) selects the scanned hot loop: `make_scanned_steps`
fuses K iterations per dispatch and a background-thread prefetcher
(`data.prefetch`) synthesizes the next (K, agents, batch, seq) chunk while
the current scan is in flight.  ``--unroll-k 1`` keeps the eager
one-dispatch-per-step loop; both walk bit-identical trajectories because
batches come from the random-access `DataPipeline.batch_at` and per-step
keys are fold_in-derived from the absolute step index.

``--topology-dropout`` / ``--topology-resample-every`` make the coupling
time-varying (`core.mixing.MixingProcess`): W_k is realized on device each
step from the absolute step index, so both loops and ``--resume`` walk the
identical W_k sequence.  The mixing config is fingerprinted into each
checkpoint's metadata and a resume under different ``--topology*`` flags
fails fast.  ``--topology-p`` / ``--topology-seed`` parameterize the
``erdos`` base graph.

``--fault-*`` flags inject agent failures as a first-class traced
scenario (`repro.faults`): Markov crash/restart (or permanent failstop)
realized on device from the absolute step, corrupt links poisoning the
transmitted v_ij (NaN/Inf/scaled; ``--fault-guard-clip 0`` disables the
receive-side finite guard for the raw chaos scenario), and a rejoin
policy for recovering agents.  ``--nan-policy`` adds traced isfinite
sentinels: ``warn`` counts non-finite steps (``fault_nonfinite`` in the
log), ``skip`` additionally holds the last finite state.  When a
checkpoint manager is active, a streak of ``--rollback-patience``
non-finite observations triggers a wall-clock rollback to the newest
durable checkpoint with exponential backoff, bounded by
``--max-rollbacks`` before the run fails.  The fault config is
fingerprinted into checkpoint metadata like the mixing config, so a
``--resume`` under different fault flags fails fast.

Checkpoints persist the FULL `DecentralizedState` — params, the step
counter, and any algorithm tracker — so ``--resume`` continues schedules
and, critically, never re-derives `privacy.agent_key(key, step, agent)` for
an already-consumed step: replaying a (key, step) pair would re-issue the
same Lambda^k draws against new gradients, exactly the key reuse the
paper's information-theoretic privacy argument forbids.

Saves go through `checkpoint.CheckpointManager`: the loop only stages
async device-side copies of the leaves (no host sync — the dispatch
pipeline never drains); the device->host transfer, serialization, and the
atomic tmp-dir/rename commit happen on a daemon writer thread
(``--checkpoint-sync`` forces the blocking path).  ``--keep-last``/``--keep-every`` bound disk usage, and a
terminal checkpoint is always written when ``--checkpoint-dir`` is set —
a finished run resumes from its end, not from the last periodic boundary.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import (CheckpointManager, latest_step, load_checkpoint,
                          read_run_meta)
from ..configs import get_config
from ..core import (init_state, make_decentralized_step, make_mixing,
                    make_scanned_steps, make_topology)
from ..core.schedules import warmup_harmonic
from ..data import make_lm_pipeline, make_placer, prefetch_chunks
from ..models import build_model
from .compile_cache import use_compile_cache
from .steps import per_step_keys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="xlstm-125m-smoke")
    p.add_argument("--agents", type=int, default=4)
    p.add_argument("--topology", default="ring")
    p.add_argument("--topology-p", type=float, default=0.4,
                   help="edge probability for --topology erdos")
    p.add_argument("--topology-seed", type=int, default=None,
                   help="graph seed for --topology erdos and the "
                        "time-varying mixing draw stream "
                        "(default: --seed)")
    p.add_argument("--topology-dropout", type=float, default=0.0,
                   help="per-step probability that each link fails "
                        "(time-varying W_k with in-trace Metropolis "
                        "re-weighting; 0 = static)")
    p.add_argument("--topology-resample-every", type=int, default=0,
                   help="redraw the graph as Erdos-Renyi every N steps "
                        "(0 = never); exclusive with --topology-dropout")
    p.add_argument("--b-window", type=int, default=None,
                   help="B-connectivity diagnostic window: log whether the "
                        "union graph of the last N realized supports is "
                        "connected (default: 8 when the topology is "
                        "time-varying, off otherwise; 0 disables)")
    p.add_argument("--kernel-layout", default="auto",
                   choices=["auto", "concat", "leafwise", "ring"],
                   help="fused-kernel buffer layout: auto picks leafwise "
                        "when sharded else concat; 'ring' forces the "
                        "overlapped ring kernel (Lambda-draw + obfuscate "
                        "+ per-direction v staging fused in one "
                        "pallas_call; requires --topology ring)")
    p.add_argument("--algorithm", default="pdsgd",
                   choices=["pdsgd", "dsgd", "dsgt", "dp_dsgd"])
    p.add_argument("--grad-clip-kappa", type=float, default=None,
                   help="clip every gradient element to [-kappa, kappa] "
                        "before obfuscation — enforces the bounded-"
                        "gradient premise of Theorem 5's uniform analysis "
                        "(see privacy.clip_gradients / lambda_stats)")
    p.add_argument("--privacy-audit", action="store_true",
                   help="after training, run the repro.launch.audit "
                        "adversary suite (parity, Theorem-5 estimators, "
                        "inversion attacks) and write privacy_report.json "
                        "next to the checkpoints (or cwd); the audit "
                        "config is fingerprinted into checkpoint run_meta")
    p.add_argument("--fault-crash-rate", type=float, default=0.0,
                   help="per-step probability that each live agent "
                        "crashes (0 = no crash faults; the rate-0 path is "
                        "byte-identical to the fault-free step)")
    p.add_argument("--fault-restart-rate", type=float, default=0.0,
                   help="per-step recovery probability of a crashed agent "
                        "(geometric outage lengths); 0 with a crash rate "
                        "= permanent failstop")
    p.add_argument("--fault-corrupt-rate", type=float, default=0.0,
                   help="per-step probability that each live agent "
                        "poisons the v_ij it transmits (0 = off)")
    p.add_argument("--fault-corrupt-mode", default="nan",
                   choices=["nan", "inf", "scale"],
                   help="what a corrupt sender puts on the wire")
    p.add_argument("--fault-rejoin", default="hold",
                   choices=["hold", "neighbor-avg"],
                   help="warm-start policy for a recovering agent; "
                        "'neighbor-avg' broadcasts neighbor states in the "
                        "clear for that step (see README privacy caveat)")
    p.add_argument("--fault-guard-clip", type=float, default=1e3,
                   help="receive-side per-link finite-guard clip; 0 "
                        "DISABLES the guard (raw poison reaches "
                        "receivers — the scenario --nan-policy exists for)")
    p.add_argument("--fault-seed", type=int, default=None,
                   help="seed of the fault draw stream (default: --seed)")
    p.add_argument("--nan-policy", default="off",
                   choices=["off", "warn", "skip"],
                   help="traced isfinite sentinels on loss and updated "
                        "state: 'warn' counts non-finite steps, 'skip' "
                        "additionally holds the last finite state")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="checkpoint rollbacks attempted on a sustained "
                        "non-finite streak before the run fails")
    p.add_argument("--rollback-patience", type=int, default=2,
                   help="consecutive non-finite observations (chunks in "
                        "the scanned loop, steps in the eager loop) "
                        "before a rollback fires")
    p.add_argument("--rollback-backoff", type=float, default=0.5,
                   help="base rollback delay in seconds, doubling per "
                        "rollback")
    p.add_argument("--mesh-fsdp", type=int, default=1,
                   help="shard each agent's params/optimizer over this "
                        "many devices (FSDP within the agent; agents x "
                        "fsdp x tensor must divide the device count). "
                        ">1 turns on sharded big-model mode: the mesh is "
                        "built with launch.mesh.make_sharded_mesh, params "
                        "are placed by logical-axis rules, and the PDSGD "
                        "kernels run leafwise over the sharded pytree")
    p.add_argument("--mesh-tensor", type=int, default=1,
                   help="tensor-parallel ('model' axis) devices per agent; "
                        "composes with --mesh-fsdp")
    p.add_argument("--scan-layers", action="store_true",
                   help="roll the transformer stack into one lax.scan over "
                        "a stacked layer pytree (MaxText-style): constant "
                        "trace/compile size in depth, same loss bit-for-bit")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--per-agent-batch", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.4)
    p.add_argument("--warmup-hold", type=int, default=200)
    p.add_argument("--sigma-dp", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--unroll-k", type=int, default=1,
                   help="iterations fused per lax.scan dispatch; 1 = eager")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="chunks buffered ahead by the prefetch thread")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--checkpoint-sync", action="store_true",
                   help="commit checkpoints on the caller thread (blocks "
                        "the hot loop; default is the async writer)")
    p.add_argument("--checkpoint-writer", default=None,
                   choices=["thread", "subprocess"],
                   help="async writer flavor: 'thread' (default) commits "
                        "on a daemon thread; 'subprocess' ships the "
                        "serialization to a spawned child so it never "
                        "competes with the dispatch loop for the GIL "
                        "(identical manifest/retention semantics)")
    p.add_argument("--keep-last", type=int, default=None,
                   help="retain only this many newest checkpoints "
                        "(default: keep all)")
    p.add_argument("--keep-every", type=int, default=None,
                   help="additionally pin every step divisible by this, "
                        "exempt from --keep-last GC")
    p.add_argument("--resume", action="store_true",
                   help="restore the latest full state (incl. step counter) "
                        "from --checkpoint-dir and continue")
    p.add_argument("--log-every", type=int, default=10)
    return p


def build_mixing(args):
    """The run's `MixingProcess` from the CLI topology knobs.

    ``--topology-p`` / ``--topology-seed`` reach `make_topology` (the seed
    CLI used to drop them: every erdos run silently got p=0.4, seed=0);
    the same seed drives the time-varying draw stream so a run is fully
    reproducible from its flags.  Factored out of `run_training` so tests
    can pin the wiring without building a model.
    """
    topo_seed = args.topology_seed if args.topology_seed is not None \
        else args.seed
    top = make_topology(args.topology, args.agents, p=args.topology_p,
                        seed=topo_seed)
    return make_mixing(top, rate=args.topology_dropout,
                       resample_every=args.topology_resample_every,
                       seed=topo_seed)


def build_faults(args):
    """The run's `faults.FaultProcess` from the CLI fault knobs, or None
    when no injection is configured — None keeps the byte-identical
    fault-free code path (`make_decentralized_step` also normalizes an
    inert process away, so rate 0 can never perturb a trajectory).
    ``--fault-guard-clip 0`` maps to ``guard_clip=None`` (guard off).
    Factored out like `build_mixing` so tests can pin the wiring.
    """
    if args.fault_crash_rate <= 0.0 and args.fault_corrupt_rate <= 0.0:
        return None
    from ..faults import make_faults
    fault_seed = args.fault_seed if args.fault_seed is not None \
        else args.seed
    clip = args.fault_guard_clip if args.fault_guard_clip > 0 else None
    return make_faults(args.agents,
                       crash_rate=args.fault_crash_rate,
                       restart_rate=args.fault_restart_rate,
                       corrupt_rate=args.fault_corrupt_rate,
                       corrupt_mode=args.fault_corrupt_mode,
                       rejoin=args.fault_rejoin,
                       guard_clip=clip,
                       seed=fault_seed)


def run_training(args, mesh=None) -> dict:
    """Run the driver loop; returns {state, history, resumed_from, ...}.

    ``history`` is the list of emitted log records.  ``step`` is the built
    training step; with ``--unroll-k`` > 1, ``compiled`` is the scanned
    step program (compiled ahead of its first dispatch) and ``timing``
    holds its compile seconds apart from the steady wall seconds of the
    steps that followed, periodic checkpoint saves inside them included,
    and, with a checkpoint directory, the seconds until the terminal
    checkpoint is on disk (``compiled`` and ``timing`` are None for the
    eager loop).  Factored out of
    `main` so tests can drive resume round-trips in-process.
    """
    cfg = get_config(args.arch)
    if args.scan_layers:
        cfg = dataclasses.replace(cfg, scan_layers=True)
    sharded = args.mesh_fsdp > 1 or args.mesh_tensor > 1
    if not sharded and jax.device_count() > 1:
        print(json.dumps({
            "warning": f"{jax.device_count()} devices visible but no "
                       "--mesh-fsdp/--mesh-tensor: every agent runs on "
                       "the default device"}))
    if sharded and mesh is None:
        from .mesh import make_sharded_mesh
        mesh = make_sharded_mesh(agents=args.agents, fsdp=args.mesh_fsdp,
                                 tensor=args.mesh_tensor)
    bundle = build_model(cfg, mesh=mesh if sharded else None)

    leaf_specs = None
    place_state = lambda s: s
    if sharded:
        # Fail fast on sharding-rule gaps BEFORE any compile: a param
        # whose logical axes no rule covers would silently replicate,
        # defeating the FSDP memory budget the flags asked for.
        from ..dist.sharding import (TRAIN_RULES, audit_rules,
                                     logical_spec)
        findings = audit_rules(bundle.abstract(), bundle.logical_axes(),
                               mesh)
        errors = [f for f in findings if f["severity"] == "error"]
        if errors:
            raise ValueError(
                "sharding audit failed (unknown logical axes):\n"
                + "\n".join(f"  {f['path']}: {f['issue']}" for f in errors))
        print(json.dumps({"sharding_audit": "ok",
                          "mesh": dict(mesh.shape),
                          "replicated_leaves": len(findings)}))
        from jax.sharding import NamedSharding, PartitionSpec
        from .specs import with_agent_axis
        p_abs, p_log = with_agent_axis(bundle.abstract(),
                                       bundle.logical_axes(), args.agents)
        leaf_specs = jax.tree.map(
            lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
            p_abs, p_log)
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 leaf_specs)
        scalar_sh = NamedSharding(mesh, PartitionSpec())

        def place_state(s):
            # Optimizer/tracker subtrees shard exactly like params, the
            # step counter replicates — `optim.shard_like` finds the
            # params-congruent subtrees structurally.
            from ..optim import shard_like
            return jax.device_put(
                s, shard_like(s, s.params, params_sh,
                              scalar_sharding=scalar_sh))

    mixing = build_mixing(args)
    faults = build_faults(args)
    sched = warmup_harmonic(args.lr, hold=args.warmup_hold)
    kernel_layout = args.kernel_layout
    use_pallas = None
    if kernel_layout == "auto":
        kernel_layout = "leafwise" if sharded else "concat"
    elif kernel_layout == "ring":
        # The ring tables need the coupling support inside the (m, 1)
        # single-ring torus adjacency; other graphs keep the dense layouts.
        if args.topology != "ring":
            raise SystemExit("--kernel-layout ring requires "
                             "--topology ring")
        if sharded:
            raise SystemExit("--kernel-layout ring flattens each agent's "
                             "leaves; it does not compose with --mesh-fsdp"
                             "/--mesh-tensor sharding")
        use_pallas = True  # the ring layout only exists as a kernel path
    step = make_decentralized_step(bundle.loss_fn, mixing, sched,
                                   algorithm=args.algorithm,
                                   sigma_dp=args.sigma_dp,
                                   grad_clip=args.grad_clip_kappa,
                                   faults=faults,
                                   nan_policy=args.nan_policy,
                                   use_pallas=use_pallas,
                                   spmd_axis_name="data" if sharded
                                   else None,
                                   kernel_layout=kernel_layout,
                                   mesh=mesh if sharded else None,
                                   leaf_specs=leaf_specs)

    # B-connectivity window diagnostics (ROADMAP): a single disconnected
    # dropout realization is fine; a STREAK of disconnected unions is what
    # silently stalls consensus, so surface it in the step log.
    b_window = args.b_window
    if b_window is None:
        b_window = 8 if not mixing.is_static else 0
    monitor = mixing.window_monitor(b_window) if b_window > 0 else None
    pipeline = make_lm_pipeline(cfg.vocab_size, args.agents,
                                args.per_agent_batch, args.seq_len,
                                seed=args.seed)
    state = place_state(
        init_state(bundle.init(jax.random.key(args.seed)), args.agents,
                   algorithm=args.algorithm))
    key = jax.random.key(args.seed + 1)
    place = make_placer(mesh)

    if args.checkpoint_dir and args.checkpoint_every < 1:
        raise ValueError("--checkpoint-every must be >= 1 (omit "
                         "--checkpoint-dir to disable checkpoints)")
    if args.resume and not args.checkpoint_dir:
        raise ValueError("--resume requires --checkpoint-dir")

    # Built BEFORE resume selection: opening the manager recovers a
    # predecessor's crash debris (a step parked mid-re-save is renamed
    # back), so `latest_step` below sees everything recoverable.  A fresh
    # (non --resume) run CLEARS stale steps — another trajectory's
    # checkpoints must neither poison retention GC nor get handed to a
    # later --resume.
    manager = None
    mixing_fp = mixing.fingerprint()
    faults_fp = faults.fingerprint() if faults is not None else None
    audit_cfg = None
    run_meta = {"mixing": mixing_fp}
    if faults_fp is not None:
        run_meta["faults"] = faults_fp
    if args.privacy_audit:
        # The audit suite runs on the paper's estimation workload under
        # THIS run's topology/clipping knobs; its config is part of the
        # run's identity — a checkpoint records which adversary suite the
        # trajectory was audited under.
        from .audit import AuditConfig, audit_fingerprint
        audit_cfg = AuditConfig(agents=args.agents,
                                kappa=args.grad_clip_kappa,
                                dropout=args.topology_dropout,
                                seed=args.seed)
        run_meta["privacy_audit"] = audit_fingerprint(audit_cfg)
    if args.checkpoint_dir:
        if args.checkpoint_sync and args.checkpoint_writer:
            raise ValueError("--checkpoint-sync and --checkpoint-writer "
                             "are mutually exclusive")
        manager = CheckpointManager(args.checkpoint_dir,
                                    keep_last=args.keep_last,
                                    keep_every=args.keep_every,
                                    async_writes=not args.checkpoint_sync,
                                    writer=("sync" if args.checkpoint_sync
                                            else args.checkpoint_writer),
                                    fresh=not args.resume,
                                    run_meta=run_meta)

    start = 0
    history: list[dict] = []
    t0 = time.time()
    # Scanned loop only: its one step program is compiled ahead of the
    # first dispatch so compile time is reported apart from the steady
    # wall time of the steps that follow it (an eager tail included).
    compiled = compile_s = t_steady = None
    steps_timed = 0

    # Cumulative fault/sentinel counters (keys exist in aux only when the
    # corresponding layer is configured, so the fault-free loop never pays
    # a device->host sync here).
    fault_totals: dict[str, int] = {}
    rollbacks = 0
    streak = 0  # consecutive non-finite observations (chunk/step grain)
    warned_no_rollback = False

    def tally(aux) -> int:
        """Accumulate fault counters; returns this observation's
        non-finite count (0 when sentinels are off).  aux values are
        scalars in the eager loop, (unroll_k,) stacks in the scanned
        loop — the sum handles both."""
        nonf = 0
        for name in ("fault_down", "fault_corrupt", "fault_rejoin",
                     "fault_nonfinite"):
            if name in aux:
                v = int(np.asarray(aux[name]).sum())
                fault_totals[name] = fault_totals.get(name, 0) + v
                if name == "fault_nonfinite":
                    nonf = v
        return nonf

    def log(k, loss, cons, step_losses=None):
        rec = {"step": int(k), "loss": float(loss),
               "consensus_error": float(cons),
               "elapsed_s": round(time.time() - t0, 1)}
        if step_losses is not None:
            rec["step_losses"] = [float(x) for x in np.asarray(step_losses)]
        if monitor is not None:
            diag = monitor(jnp.asarray(int(k), jnp.int32))
            rec.update(b_window=b_window,
                       b_window_connected=bool(diag["connected"]),
                       b_window_union_min_degree=int(
                           diag["union_min_degree"]))
        if fault_totals:
            rec.update(fault_totals)  # cumulative, not per-interval
        history.append(rec)
        print(json.dumps(rec))

    def crosses(k_prev: int, k_next: int, every: int) -> bool:
        return k_next // every > k_prev // every

    def checkpoint_due(k_prev: int, k_next: int) -> bool:
        # Fire whenever (k_prev, k_next] crosses a checkpoint_every
        # boundary.  The scanned loop can only save at chunk boundaries,
        # so with unroll_k > checkpoint_every intermediate saves collapse
        # onto the chunk end (warned about below).
        return manager is not None and crosses(
            k_prev, k_next, args.checkpoint_every)

    def try_rollback(state):
        """Sentinel-triggered self-healing: once ``streak`` reaches
        --rollback-patience, restore the newest DURABLE checkpoint after
        an exponential backoff.  Bounded by --max-rollbacks — batches,
        keys, and fault draws are all derived from the absolute step, so
        a replay hits the identical non-finite state; the retries buy
        time for transient causes (a flaky host, an operator fixing
        flags) and then fail the run rather than loop forever.  Returns
        ``(state, restore_step, rolled)``; without a checkpoint manager
        rollback is unavailable and the nan-policy sentinels alone carry
        the run."""
        nonlocal rollbacks, streak, warned_no_rollback
        if streak < args.rollback_patience:
            return state, None, False
        if manager is None:
            if not warned_no_rollback:
                warned_no_rollback = True
                print(json.dumps({
                    "warning": "sustained non-finite state but no "
                               "--checkpoint-dir; rollback unavailable "
                               "(nan-policy sentinels still hold the "
                               "last finite state)"}))
            return state, None, False
        if rollbacks >= args.max_rollbacks:
            raise RuntimeError(
                f"training state stayed non-finite through {rollbacks} "
                f"rollback(s) (--max-rollbacks={args.max_rollbacks}); "
                "the failure replays deterministically — fix the fault "
                "config instead of retrying")
        manager.wait()  # only committed steps are rollback targets
        last = latest_step(args.checkpoint_dir)
        if last is None:
            if not warned_no_rollback:
                warned_no_rollback = True
                print(json.dumps({
                    "warning": "sustained non-finite state before any "
                               "durable checkpoint; rollback unavailable"}))
            return state, None, False
        time.sleep(args.rollback_backoff * (2 ** rollbacks))
        rollbacks += 1
        streak = 0
        state = place_state(
            load_checkpoint(args.checkpoint_dir, last, like=state))
        rec = {"rollback": rollbacks, "restored_step": last}
        history.append(rec)
        print(json.dumps(rec))
        return state, last, True

    try:
        if args.resume:
            last = latest_step(args.checkpoint_dir)
            if last is None:
                # Refuse rather than silently restart at step 0: if a
                # previous run DID consume steps, re-deriving
                # agent_key(key, step, agent) for them is exactly the key
                # reuse the privacy argument forbids.  A fresh run should
                # not pass --resume.
                raise FileNotFoundError(
                    f"--resume: no checkpoint found under "
                    f"{args.checkpoint_dir!r}; drop --resume for a fresh "
                    "run")
            stored_meta = read_run_meta(args.checkpoint_dir, last)
            stored_fp = stored_meta.get("mixing")
            if stored_meta.get("faults") != faults_fp:
                # A missing key means the trajectory ran WITHOUT fault
                # injection (pre-fault checkpoints recorded none) — that
                # IS a fingerprint, so None-vs-present mismatches refuse
                # too: a resumed run realizing a different fault stream
                # (or none) silently diverges from the trajectory it
                # claims to continue.
                raise ValueError(
                    f"--resume: checkpoint step_{last:08d} was written "
                    f"with fault config {stored_meta.get('faults')}, but "
                    f"this run built {faults_fp}; pass matching "
                    "--fault-* flags (or start a fresh run without "
                    "--resume)")
            if stored_fp is None:
                # Pre-fingerprint checkpoint: consistency CANNOT be
                # verified (notably `--topology erdos` runs, whose graph
                # seed the old CLI silently pinned to 0) — warn loudly
                # instead of silently proceeding.
                print(json.dumps({
                    "warning": "checkpoint records no mixing fingerprint "
                               "(written pre-PR4); cannot verify the "
                               "--topology* flags match the original run"}))
            elif stored_fp != mixing_fp:
                # A resumed run walking a DIFFERENT graph/mixing stream
                # would silently diverge from the trajectory it claims to
                # continue (and re-key W_k draws) — refuse loudly.
                raise ValueError(
                    f"--resume: checkpoint step_{last:08d} was written "
                    f"with mixing config {stored_fp}, but this run built "
                    f"{mixing_fp}; pass matching --topology* flags (or "
                    "start a fresh run without --resume)")
            state = place_state(
                load_checkpoint(args.checkpoint_dir, last, like=state))
            if int(state.step) != last:
                # batches/keys would be driven by the directory index while
                # the schedule and agent_key use state.step — refuse the
                # divergence
                raise ValueError(
                    f"checkpoint step_{last:08d} holds state.step="
                    f"{int(state.step)}; refusing to resume from a "
                    "mislabeled checkpoint")
            start = last
            print(json.dumps({"resumed_from": last,
                              "state_step": int(state.step)}))

        k = start
        if args.unroll_k > 1:
            if manager is not None and args.checkpoint_every % args.unroll_k:
                print(json.dumps({
                    "warning": f"checkpoint_every={args.checkpoint_every} is "
                               f"not a multiple of unroll_k={args.unroll_k}: "
                               "checkpoints land on chunk boundaries only"}))
            scanned = make_scanned_steps(step, args.unroll_k)
            # Outer while: a rollback abandons the in-flight prefetch
            # stream (its chunks are past the restored step) and restarts
            # it from the restored step — chunks are synthesized from the
            # absolute step index, so the replay is the original stream.
            while args.steps - k >= args.unroll_k:
                rolled = False
                n_chunks = (args.steps - k) // args.unroll_k
                with prefetch_chunks(pipeline, args.unroll_k, start_step=k,
                                     num_chunks=n_chunks, place=place,
                                     depth=args.prefetch_depth) as chunks:
                    for chunk in chunks:
                        keys = per_step_keys(key, k, args.unroll_k)
                        if compiled is None:
                            tc = time.perf_counter()
                            compiled = scanned.lower(state, chunk,
                                                     keys).compile()
                            compile_s = time.perf_counter() - tc
                            t_steady = time.perf_counter()
                        state, aux = scanned(state, chunk, keys)
                        steps_timed += args.unroll_k
                        k_next = k + args.unroll_k
                        nonf = tally(aux)
                        streak = streak + 1 if nonf else 0
                        # aux is stacked per step; reduce per chunk for
                        # logging.  Honor --log-every at chunk granularity
                        # — an unlogged chunk costs no device->host sync
                        # at all (tally syncs only when fault counters
                        # exist in aux).
                        if (crosses(k, k_next, args.log_every)
                                or k_next >= args.steps):
                            log(k_next - 1, aux["loss"].mean(),
                                aux["consensus_error"][-1],
                                step_losses=aux["loss"])
                        if nonf:
                            state, rk, rolled = try_rollback(state)
                            if rolled:
                                k = rk
                                break
                        if checkpoint_due(k, k_next) and not (
                                nonf and args.nan_policy == "warn"):
                            # Under 'warn' a non-finite interval may have
                            # poisoned the state itself — never make it a
                            # rollback target.  Under 'skip' the state is
                            # the held finite anchor and stays durable.
                            manager.save(k_next, state)
                        k = k_next
                if not rolled:
                    break

        # Eager loop: the whole run when --unroll-k 1, the tail otherwise.
        while k < args.steps:
            sk = jax.random.fold_in(key, k)
            batch = place(pipeline.batch_at(k))
            state, aux = step(state, batch, sk)
            steps_timed += 1
            nonf = tally(aux)
            streak = streak + 1 if nonf else 0
            if k % args.log_every == 0 or k == args.steps - 1:
                log(k, aux["loss"], aux["consensus_error"])
            if nonf:
                state, rk, rolled = try_rollback(state)
                if rolled:
                    k = rk
                    continue
            if checkpoint_due(k, k + 1) and not (
                    nonf and args.nan_policy == "warn"):
                manager.save(k + 1, state)
            k += 1

        timing = None
        if compiled is not None:
            jax.block_until_ready(state)
            timing = {"compile_s": compile_s,
                      "steady_s": time.perf_counter() - t_steady,
                      "steps_timed": steps_timed}
            print(json.dumps(timing))
        if manager is not None:
            # Terminal checkpoint: a run whose --steps doesn't cross a
            # --checkpoint-every boundary must still resume from its END,
            # never replay work (and never re-issue (key, step) draws).
            # `save` is idempotent, so a boundary landing exactly on
            # args.steps doesn't write twice; max(start, steps) is what
            # state.step holds even when a resume starts past --steps.
            tc = time.perf_counter()
            manager.save(max(start, args.steps), state)
            manager.wait()
            if timing is not None:
                # after the step clock stopped: the stall until the last
                # checkpoint is on disk, reported on its own
                timing["checkpoint_s"] = time.perf_counter() - tc
                print(json.dumps({"checkpoint_s": timing["checkpoint_s"]}))
    finally:
        if manager is not None:
            # Drains in-flight writes; re-raises a writer failure so the
            # train loop never reports success on a checkpoint that never
            # landed.
            manager.close()

    if faults is not None or args.nan_policy != "off":
        summary = {"fault_summary": dict(fault_totals),
                   "rollbacks": rollbacks}
        if manager is not None:
            summary["checkpoint_retries"] = manager.retries
        history.append(summary)
        print(json.dumps(summary))

    audit_report = None
    if audit_cfg is not None:
        from .audit import run_audit
        out_path = os.path.join(args.checkpoint_dir or ".",
                                "privacy_report.json")
        audit_report = run_audit(audit_cfg, out=out_path)
        print(json.dumps({
            "privacy_audit": "ok" if audit_report["ok"] else "FAILED",
            "parity_all_pass": audit_report["parity"]["all_pass"],
            "pdsgd_recovery_mse":
                audit_report["attacks"]["pdsgd_ls_recovery_mse"],
            "theorem5_mse_bound":
                audit_report["attacks"]["theorem5_mse_bound"],
            "report": out_path}))

    return {"state": state, "history": history, "resumed_from": start or None,
            "privacy_audit": audit_report, "fault_totals": fault_totals,
            "rollbacks": rollbacks, "step": step, "compiled": compiled,
            "timing": timing}


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()
    run_training(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
