"""Helpers of the harness tests: a checkout-like directory holding a copy
of ``bench/`` and one small cell, and a runner that starts the harness in a
child process with the look for a chip skipped and, optionally, a fault
planted in the program underneath.  The sharded cell (`make_sharded_root`)
runs on four host devices of the CPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "xlstm-tiny.pdsgd.t"
SHARDED_CELL = "moe-tiny.pdsgd.fsdp2"

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


def _write_root(tmp: Path, cell: str, config: dict, flags: list,
                limits: dict, chips: int,
                extra_metric: str | None = None) -> Path:
    """tmp/root: bench/ copied, src/ linked, and a BENCHMARK.json whose one
    cell is ``cell`` (configuration, traffic and limits files all new)."""
    conf, traffic = cell.split(".", 1)
    root = tmp / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    b = root / "bench"
    (b / "configs" / f"{conf}.json").write_text(json.dumps(
        dict(config, name=conf, replace=config.get("replace", {}),
             reduced=[])))
    (b / "traffic" / f"{traffic}.json").write_text(json.dumps({
        "flags": flags, "kernels": []}))
    (b / "limits" / f"{cell}.json").write_text(json.dumps(
        {"limits": limits}))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"] = [{"name": conf, "source": "test",
                      "file": f"bench/configs/{conf}.json",
                      "reduced": [], "why": "test"}]
    bm["workloads"] = [{"name": cell, "config": conf, "traffic": traffic,
                        "chips": chips, "why": "test"}]
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


def make_root(tmp: Path, extra_metric: str | None = None,
              size: str = "tiny") -> Path:
    """A root whose one cell, `CELL`, is a small float32 xLSTM with 4
    agents on one device.  Its limits: loss0_gap 1e-6 (program and
    reference both in float32), loss_gap 1e-2, consensus0_gap and
    update_gap as SIZES gives (the Lambda draws' spread at that size)."""
    base, sizes, flags, consensus0_gap, update_gap = SIZES[size]
    return _write_root(
        tmp, CELL,
        {"base": base, "reference": "xlstm",
         "sizes": dict(sizes, slstm_every=2, dtype="float32")},
        ["--agents", "4", "--unroll-k", "2", *flags],
        {"loss0_gap": 1e-6, "loss_gap": 1e-2,
         "consensus0_gap": consensus0_gap, "update_gap": update_gap},
        chips=1, extra_metric=extra_metric)


# the granite family at test size: 2 layers of grouped-query attention and
# 2 routed experts, d_model 32
MOE_TINY = {"family": "moe", "num_layers": 2, "d_model": 32, "num_heads": 2,
            "num_kv_heads": 2, "head_dim": 16, "d_ff": 64, "vocab_size": 64,
            "num_experts": 2, "num_experts_per_tok": 1,
            "capacity_factor": 1.25, "rope_theta": 10000.0,
            "rotary_frac": 1.0, "norm": "rmsnorm", "tie_embeddings": True,
            "dtype": "float32"}
SHARDED_FLAGS = ["--agents", "2", "--mesh-fsdp", "2", "--unroll-k", "2",
                 "--per-agent-batch", "2", "--seq-len", "8"]


def make_sharded_root(tmp: Path, flags: tuple = ()) -> Path:
    """A root whose one cell, `SHARDED_CELL`, is a small float32 MoE of 2
    agents x fsdp 2 on four chips (``flags`` are added to its traffic's).
    Its limits are the small xLSTM cell's (`make_root`)."""
    return _write_root(
        tmp, SHARDED_CELL,
        {"base": "granite-moe-1b-a400m-tiny", "replace": {"num_layers": 2},
         "reference": "moe", "sizes": MOE_TINY},
        SHARDED_FLAGS + list(flags),
        {"loss0_gap": 1e-6, "loss_gap": 1e-2, "consensus0_gap": 0.6,
         "update_gap": 0.5}, chips=4)


def run_child(root: Path, fault: str = "none", trace: int = 0,
              seed: int = 5, cell: str = CELL, devices: int = 1):
    """Run ``cell`` once in a child process that sees ``devices`` CPU
    devices; the finished `subprocess.CompletedProcess`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".cache"))
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}").strip()
    return subprocess.run(
        [sys.executable, "-c", RUNNER, str(root), fault, "--workload", cell,
         "--seed", str(seed), "--seconds", "0.5", "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600)


def drive(root: Path, fault: str = "none", trace: int = 0, seed: int = 5):
    """Run `CELL` once in a child process; (returncode, last stdout line
    parsed as JSON or None, stderr)."""
    p = run_child(root, fault, trace, seed)
    return p.returncode, last_json(p.stdout), p.stderr


def last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None
