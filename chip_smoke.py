"""Smoke test of PDSGD training on TPU chips, through the normal entry point.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # four chips (one host, 2x2)

One chip: `repro.launch.train.run_training` trains xlstm-125m at full width
(12 layers, d_model 768, vocab 50304) with 4 agents on a ring, per-agent
batch 4 x 1024 tokens, 8 steps scanned 4 per dispatch, through the fused
Lambda-obfuscation and gossip kernels (paper Eq. 3/4), with the
subprocess checkpoint writer.  Then one `pdsgd_update` at the same width
is checked three ways: the kernel with HBM bits against the jnp path on
the same bits, and the in-kernel PRNG draw replayed through the HBM-bits
kernel.

Four chips: granite-moe-1b-a400m at full size (24 layers, 1.34B params
per agent), 2 agents x fsdp 2 over all four chips, 3 steps through the
leafwise kernels, compared with the same steps through the jnp update on
the same mesh.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed.  Without a TPU, or outside a checkout, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))

ONE_CHIP_ARGV = ["--arch", "xlstm-125m", "--agents", "4",
                 "--topology", "ring", "--per-agent-batch", "4",
                 "--seq-len", "1024", "--steps", "8", "--unroll-k", "4",
                 "--log-every", "4"]
# Full depth, layers unrolled: compiling the kernel-path step for a v5e:2x2
# took 157 s on an x86 host CPU this way and 523 s with --scan-layers; it
# needs 13.4 GB per chip.
FOUR_CHIP_ARGV = ["--arch", "granite-moe-1b-a400m",
                  "--agents", "2", "--mesh-fsdp", "2", "--topology", "ring",
                  "--per-agent-batch", "4", "--seq-len", "1024",
                  "--steps", "3", "--log-every", "1"]

# The jnp update casts W and B to the bf16 parameter dtype and rounds
# each einsum to bf16; the kernel keeps them in f32.  Relative to the
# size of the update itself the two agree to a few bf16 ulps (2**-8).
UPDATE_RTOL = 2.0 ** -5
# Three steps of the full model under the two update paths: step 0 sees
# identical params, later steps differ by the bf16 rounding above.
LOSS_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def report(**rec) -> None:
    print(json.dumps(rec), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def check_knobs() -> None:
    """The kernel knobs must resolve to compiled Pallas with the in-kernel
    draw; an environment override that says otherwise fails the run."""
    from repro.kernels import runtime
    got = {"interpret": runtime.default_interpret(),
           "use_pallas": runtime.default_use_pallas(),
           "kernel_rng": runtime.default_kernel_rng()}
    report(phase="knobs", **got)
    want = {"interpret": False, "use_pallas": True, "kernel_rng": True}
    check(got == want, f"kernel knobs resolved to {got}, want {want}; "
          "unset REPRO_PALLAS_INTERPRET / REPRO_USE_PALLAS / "
          "REPRO_KERNEL_RNG")


def peak_bytes(devices) -> list[int]:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def kernel_calls(hlo_text: str) -> dict[str, int]:
    """tpu_custom_call instructions in a compiled program, by kernel."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split("=", 1)[0].strip().lstrip("%").split(".")[0]
        counts[name] = counts.get(name, 0) + 1
    return counts


def train_one_chip(ckpt_dir: str):
    import jax
    from repro.launch.train import build_parser, run_training

    # No periodic save inside the timed steps: the one checkpoint is the
    # terminal save, timed apart from them (timing["checkpoint_s"]).
    args = build_parser().parse_args(
        ONE_CHIP_ARGV + ["--checkpoint-dir", ckpt_dir,
                         "--checkpoint-every", "1000",
                         "--checkpoint-writer", "subprocess"])
    report(phase="config", model=args.arch, agents=args.agents,
           per_agent_batch=args.per_agent_batch, seq_len=args.seq_len,
           steps=args.steps, unroll_k=args.unroll_k,
           checkpoint_writer=args.checkpoint_writer)
    res = run_training(args)
    check(res["step"].inner is not None,
          "the step fell back to the per-step host schedule")
    params = res["state"].params
    n_params = sum(x.size for x in jax.tree.leaves(params)) // args.agents
    losses = [x for h in res["history"] for x in h.get("step_losses", [])]
    report(phase="train", params_per_agent=n_params,
           step_losses=losses, **res["timing"])
    check(len(losses) == args.steps, f"{len(losses)} losses logged, "
          f"want {args.steps}")
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(res["timing"]["steps_timed"] == args.steps,
          "the scanned loop did not run every step")
    calls = kernel_calls(res["compiled"].as_text())
    report(phase="kernels_in_step", tpu_custom_calls=calls)
    check(any("obfuscate" in k for k in calls),
          f"no obfuscate kernel in the compiled step: {calls}")
    check(any("gossip" in k for k in calls),
          f"no gossip kernel in the compiled step: {calls}")
    steps_saved = sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                         if d.startswith("step_"))
    report(phase="checkpoint", writer="subprocess", steps=steps_saved)
    check(steps_saved == [args.steps], f"checkpoints {steps_saved}")
    report(phase="memory",
           peak_bytes_in_use=peak_bytes(jax.local_devices()))
    return params


def unflatten_bits(bits, like):
    """Split the kernel's (m, D) bit buffer back into leaves shaped like
    ``like`` (the column order of `kernels.ops._flatten_concat`)."""
    import jax
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for leaf in leaves:
        n = leaf[0].size
        out.append(bits[:, off:off + n].reshape(leaf.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


def kernel_check(params) -> None:
    """One Eq. (4) update at the trained model's width, three ways."""
    import jax
    import jax.numpy as jnp
    from repro.core import make_topology
    from repro.core.pdsgd import krng_seed, pdsgd_update
    from repro.core.privacy import agent_key, sample_B
    from repro.kernels import fused_pdsgd_tree

    m = jax.tree.leaves(params)[0].shape[0]
    top = make_topology("ring", m)
    W = jnp.asarray(top.weights, jnp.float32)
    support = jnp.asarray(top.adjacency, jnp.float32)
    key, step, lam = jax.random.key(7), jnp.int32(0), jnp.float32(0.5)
    gkeys = jax.random.split(jax.random.key(8), len(jax.tree.leaves(params)))
    g = jax.tree.unflatten(
        jax.tree.structure(params),
        [jax.random.normal(k, p.shape, p.dtype)
         for k, p in zip(gkeys, jax.tree.leaves(params))])

    def update(use_pallas):
        return jax.jit(lambda p, g: pdsgd_update(
            p, g, key=key, step=step, W=W, support=support, lam_bar=lam,
            use_pallas=use_pallas, interpret=False, kernel_rng=False))

    @jax.jit
    def rel_diff(a, b, x):
        """||a - b|| / ||b - x|| over the whole tree, in f32."""
        sq = lambda t: sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                           for l in jax.tree.leaves(t))
        d = jax.tree.map(lambda u, v: u.astype(jnp.float32) - v, a, b)
        step_ = jax.tree.map(lambda u, v: u.astype(jnp.float32) - v, b, x)
        return jnp.sqrt(sq(d) / sq(step_))

    out_bits = update(True)(params, g)
    out_jnp = update(False)(params, g)
    rel = float(rel_diff(out_bits, out_jnp, params))
    del out_jnp
    report(phase="kernel_vs_jnp", rel_diff=rel, rtol=UPDATE_RTOL)
    check(math.isfinite(rel) and rel <= UPDATE_RTOL,
          f"HBM-bits kernel vs jnp update: rel diff {rel} > {UPDATE_RTOL}")
    del out_bits

    B = sample_B(agent_key(jax.random.fold_in(key, 2), step, 0), support)
    seed = krng_seed(key, step)
    out_krng, flats = jax.jit(lambda p, g: fused_pdsgd_tree(
        W, B, p, g, None, lam, interpret=False, kernel_rng=True, seed=seed,
        observe=True))(params, g)
    bits = unflatten_bits(flats.pop("bits"), params)
    del flats
    out_replay = jax.jit(lambda p, g, b: fused_pdsgd_tree(
        W, B, p, g, b, lam, interpret=False, kernel_rng=False))(
            params, g, bits)
    same = bool(jax.jit(lambda a, b: jnp.all(jnp.stack(
        [jnp.all(x == y) for x, y in zip(jax.tree.leaves(a),
                                         jax.tree.leaves(b))])))(
            out_krng, out_replay))
    report(phase="krng_replay", bitwise_equal=same)
    check(same, "in-kernel PRNG update != replay of its bits through the "
          "HBM-bits kernel")


def four_chip() -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.train import build_parser, run_training

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chip needs 4 chips, found "
          f"{len(devices)}")
    args = build_parser().parse_args(FOUR_CHIP_ARGV)
    report(phase="config", model=args.arch, agents=args.agents,
           mesh_fsdp=args.mesh_fsdp, per_agent_batch=args.per_agent_batch,
           seq_len=args.seq_len, steps=args.steps,
           num_layers=get_config(args.arch).num_layers)
    res = run_training(args)
    losses = np.asarray([h["loss"] for h in res["history"]])
    params = res["state"].params
    n_params = sum(x.size for x in jax.tree.leaves(params)) // args.agents
    spans = [{s.device for s in x.addressable_shards} == set(devices)
             and not x.sharding.is_fully_replicated
             for x in jax.tree.leaves(params)]
    report(phase="train_kernels", params_per_agent=n_params,
           losses=losses.tolist(), leaves=len(spans),
           leaves_on_all_devices=sum(spans),
           peak_bytes_in_use=peak_bytes(devices))
    check(np.all(np.isfinite(losses)), f"loss not finite: {losses}")
    check(all(spans), "a param leaf is not sharded over all 4 devices")
    del res, params

    old = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "0"
    try:
        ref = np.asarray([h["loss"] for h in
                          run_training(args)["history"]])
    finally:
        if old is None:
            del os.environ["REPRO_USE_PALLAS"]
        else:
            os.environ["REPRO_USE_PALLAS"] = old
    report(phase="train_jnp", losses=ref.tolist(), rtol=LOSS_RTOL)
    check(np.allclose(losses, ref, rtol=LOSS_RTOL, atol=0),
          f"kernel losses {losses} vs jnp losses {ref}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chip", action="store_true",
                   help="run the agents x fsdp path on 4 chips (only it)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: no src/repro next to this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r})", file=sys.stderr)
        return 1
    report(phase="device", **device)

    from repro.launch.compile_cache import use_compile_cache
    report(phase="compile_cache", dir=use_compile_cache())
    try:
        check_knobs()
        if args.four_chip:
            four_chip()
        else:
            with tempfile.TemporaryDirectory() as ckpt_dir:
                params = train_one_chip(ckpt_dir)
            kernel_check(params)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
