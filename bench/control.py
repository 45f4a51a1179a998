"""Readings that set a cell's limits: the control and the planted faults,
each compared with the reference as a run's first chunk is.

    python3 bench/control.py --workload <name> --seeds 11 12 13

For every seed it walks the reference over the cell's first chunk (the
comparison's ``ref`` side) and then, each drawing its own Lambda and B:

* ``sound``: the same float32 reference again: the spread that the
  random draws alone give;
* ``control``: the reference computed in the precision below the one
  the configuration states (`refs.common.CONTROL_MODE`);
* ``half_batch``: half of the step's batch left out, the loss the mean
  over the rest: half of each agent's rows, or, where an agent has one
  row, the rows of half of the agents (the others get no gradient);
* ``double_leaf``: the largest leaf moved double each step;
* ``unchanged``: the state returned unchanged while the step still
  reports its losses and consensus errors (change 0); it needs no run.

One JSON line per seed and reading.  The benchmark's own runs never run
this; it needs the chip only for the cell's sizes.  Where the traffic asks
for a mesh, every walk is spread over it as the program's state is
(`check.reference_chunk`'s ``shardings``).
"""
from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def halve(batch: dict) -> dict:
    return {k: v[: v.shape[0] // 2] for k, v in batch.items()}


def readings(cell, seed: int):
    import importlib

    import jax
    import numpy as np

    from bench import check
    from bench.refs.common import CONTROL_MODE
    from bench.run import arch_config, make_program
    from bench.seeds import jax_key
    from repro.data import make_lm_pipeline
    from repro.launch.steps import per_step_keys
    from repro.launch.train import build_parser

    sizes = cell.config["sizes"]
    ref = importlib.import_module(f"bench.refs.{cell.config['reference']}")
    pargs = build_parser().parse_args(cell.traffic["flags"])
    m, K, B = pargs.agents, pargs.unroll_k, pargs.per_agent_batch
    chunk = make_lm_pipeline(sizes["vocab_size"], m, B, pargs.seq_len,
                             seed=seed).chunk_at(0, K)
    keys = per_step_keys(jax_key(seed, "step_keys"), 0, K)
    shardings = None
    if pargs.mesh_fsdp > 1 or pargs.mesh_tensor > 1:
        shardings = make_program(arch_config(cell.config),
                                 pargs).params_shardings
    x0 = jax.jit(lambda k: ref.init(k, sizes), **({} if shardings is None
                 else dict(out_shardings=check.without_agent_axis(
                     shardings))))(jax_key(seed, "weights"))
    walk = partial(check.reference_chunk, ref, sizes, x0, chunk, keys, m=m,
                   algorithm=pargs.algorithm, lr=pargs.lr,
                   hold=pargs.warmup_hold, seed=seed, shardings=shardings)
    base = walk()
    n = [leaf.size for leaf in jax.tree.leaves(x0)]
    double = jax.tree.unflatten(jax.tree.structure(x0), [
        2.0 if i == int(np.argmax(n)) else 1.0 for i in range(len(n))])
    half = (dict(loss_fn=lambda p, b: ref.loss(
        p, halve(b), sizes, remat=shardings is not None))
            if B > 1 else dict(live=m // 2))
    out = {
        "sound": walk(draws="ctl"),
        "control": walk(draws="ctl", mode=CONTROL_MODE[sizes["dtype"]]),
        "half_batch": walk(draws="ctl", **half),
        "double_leaf": walk(draws="ctl", update_scale=double),
        "unchanged": dict(base, change=np.zeros_like(base["change"])),
    }
    return base, out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import check
    from bench.run import _listed, find_cell
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    cell = find_cell(ROOT, args.workload)
    for seed in args.seeds:
        base, out = readings(cell, seed)
        for name, walk in out.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": name,
                              **check.numbers(walk, base)}), flush=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "walks": {k: _listed(v) for k, v in
                                    dict(out, ref=base).items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
