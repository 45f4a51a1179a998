"""Helpers of the harness tests: a checkout-like directory holding a copy
of ``bench/`` and one small cell, and a runner that starts the harness in a
child process with the look for a chip skipped and, optionally, a fault
planted in the program underneath."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "xlstm-tiny.pdsgd.t"

RUNNER = r'''
import dataclasses, sys
from pathlib import Path
root = Path(sys.argv[1])
sys.path[:0] = [str(root), str(root / "src")]
fault = sys.argv[2]
import jax
import repro.core as core
real_step, real_scan = core.make_decentralized_step, core.make_scanned_steps

def halve(batch):
    return {k: v[: v.shape[0] // 2] for k, v in batch.items()}

if fault == "half_batch":
    core.make_decentralized_step = lambda loss_fn, *a, **kw: real_step(
        lambda p, b: loss_fn(p, halve(b)), *a, **kw)
elif fault == "unchanged":
    def scan(step, k, donate=True):
        f = real_scan(step, k, donate=False)
        return jax.jit(lambda s, c, ks: (s, f(s, c, ks)[1]))
    core.make_scanned_steps = scan
elif fault == "double_leaf":
    def scan(step, k, donate=True):
        f = real_scan(step, k, donate=False)
        def g(s, c, ks):
            new, aux = f(s, c, ks)
            old, nl = jax.tree.leaves(s.params), jax.tree.leaves(new.params)
            big = max(range(len(nl)), key=lambda i: nl[i].size)
            nl[big] = 2 * nl[big] - old[big]
            return dataclasses.replace(new, params=jax.tree.unflatten(
                jax.tree.structure(new.params), nl)), aux
        return jax.jit(g)
    core.make_scanned_steps = scan
import bench.run as run
sys.exit(run.main(sys.argv[3:], root=root, require_tpu=False))
'''


SIZES = {
    # 1 mLSTM block, d_model 32: seconds to compile
    "tiny": ("xlstm-125m-tiny", {"num_layers": 1, "d_model": 32,
                                 "num_heads": 2, "vocab_size": 64},
             ["--per-agent-batch", "2", "--seq-len", "8"], 0.6, 0.5),
    # an mLSTM and an sLSTM block, d_model 256: enough elements that the
    # random draws of Lambda move the update norms by a few percent
    "smoke": ("xlstm-125m-smoke", {"num_layers": 2, "d_model": 256,
                                   "num_heads": 4, "vocab_size": 1024},
              ["--per-agent-batch", "2", "--seq-len", "16"], 0.3, 0.2),
}


def make_root(tmp: Path, extra_metric: str | None = None,
              size: str = "tiny") -> Path:
    """tmp/root: bench/ copied, src/ linked, and a BENCHMARK.json whose one
    cell is a small float32 xLSTM with 4 agents; all its files are new.
    Its limits: loss0_gap 1e-6 (program and reference both in float32),
    loss_gap 1e-2, consensus0_gap and update_gap as SIZES gives (the
    Lambda draws' spread at that size)."""
    base, sizes, flags, consensus0_gap, update_gap = SIZES[size]
    root = tmp / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    b = root / "bench"
    (b / "configs" / "xlstm-tiny.json").write_text(json.dumps({
        "name": "xlstm-tiny", "base": base, "replace": {},
        "reference": "xlstm", "reduced": [],
        "sizes": dict(sizes, slstm_every=2, dtype="float32")}))
    (b / "traffic" / "t.json").write_text(json.dumps({
        "flags": ["--agents", "4", "--unroll-k", "2", *flags],
        "kernels": []}))
    (b / "limits" / f"{CELL}.json").write_text(json.dumps({"limits": {
        "loss0_gap": 1e-6, "loss_gap": 1e-2, "consensus0_gap": consensus0_gap,
        "update_gap": update_gap}}))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"] = [{"name": "xlstm-tiny", "source": "test",
                      "file": "bench/configs/xlstm-tiny.json",
                      "reduced": [], "why": "test"}]
    bm["workloads"] = [{"name": CELL, "config": "xlstm-tiny",
                        "traffic": "t", "chips": 1, "why": "test"}]
    for m in bm["per_layer"]:
        m.pop("workloads", None)
    if extra_metric:
        (b / "metrics" / f"{extra_metric}.py").write_text(
            "def read(ctx):\n    return float(ctx['tokens'])\n")
        bm["per_layer"].append({
            "name": extra_metric, "unit": "tokens", "better": "higher",
            "source": "host_clock", "layer": "test",
            "moves": "tokens_per_s_per_chip"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def drive(root: Path, fault: str = "none", trace: int = 0, seed: int = 5):
    """Run the cell once in a child process; (returncode, last stdout line
    parsed as JSON or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".cache"))
    p = subprocess.run(
        [sys.executable, "-c", RUNNER, str(root), fault, "--workload", CELL,
         "--seed", str(seed), "--seconds", "0.5", "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    return p.returncode, last_json(p.stdout), p.stderr


def last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None
