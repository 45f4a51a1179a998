"""Roofline analysis over the dry-run sweep results (deliverable g).

Reads benchmarks/results/dryrun/*.json (written by repro.launch.dryrun) and
derives, per (arch x shape x mesh):

  compute term    = HLO_FLOPs_per_device / peak FLOP/s
  memory term     = HLO_bytes_per_device / peak HBM bytes/s
  collective term = collective_bytes_per_device / ICI bytes/s per link

(cost_analysis and the SPMD HLO are per-partition, i.e. per-chip, so the
"/ chips" in the spec is already applied.)  Also reports MODEL_FLOPS =
6*N(_active)*D vs HLO FLOPs — the useful-compute ratio — and the dominant
term with a one-line remedy suggestion.

  PYTHONPATH=src python -m benchmarks.roofline [--results DIR] [--md FILE]

The peaks come from `PEAKS`, keyed by the chip's ``device_kind`` as JAX
reports it (a record's own ``device_kind``, else the v5e the dry-run
models); a kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Per-chip peaks keyed by `jax.devices()[i].device_kind`.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,  # bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 ICI links
        "ici_bytes_per_s_per_link": 50e9,
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, "
                  "1,600 Gbit/s ICI per chip",
    },
}

# The dry-run sweep (repro.launch.dryrun) models a v5e pod.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The `PEAKS` row for ``device_kind``; raises on a chip not in the
    table, since another chip's peaks would make every share wrong."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add a row with its source)") from None

REMEDY = {
    "compute": "raise arithmetic intensity: larger per-chip tiles / fewer remat recomputes",
    "memory": "fuse elementwise chains + flash-attention tiling to cut HBM round-trips",
    "collective": "reschedule collectives: ring gossip / overlap with compute / shard to cut all-gathers",
}


def _slstm_correction(arch: str, shape_kind: str, tokens: int, chips: int) -> float:
    """xLSTM's sLSTM time-scan FLOPs are invisible to XLA's while-loop cost
    analysis; add them analytically (models/xlstm.py)."""
    if arch != "xlstm-125m":
        return 0.0
    from repro.configs import get_config
    from repro.models.xlstm import slstm_flops_correction
    cfg = get_config(arch)
    # tokens = batch*seq (train/prefill) or batch (decode, seq=1)
    return slstm_flops_correction(cfg, 1, tokens) / chips


def load_results(results_dir: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def analyze(rec: dict) -> dict:
    device_kind = rec.get("device_kind", DRYRUN_DEVICE_KIND)
    peak = peaks(device_kind)
    flops = (rec.get("flops_per_device") or 0.0) + _slstm_correction(
        rec["arch"], rec["kind"], rec["tokens"], rec["chips"])
    mem_bytes = rec.get("bytes_per_device") or 0.0
    coll_bytes = rec.get("collectives", {}).get("total_bytes", 0)
    t_compute = flops / peak["flops"]
    t_memory = mem_bytes / peak["hbm_bytes_per_s"]
    t_coll = coll_bytes / peak["ici_bytes_per_s_per_link"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    # model flops: 6*N*D for train (fwd+bwd); 2*N*D for inference
    mult = 6 if rec["kind"] == "train" else 2
    n = rec["params"]["active"]
    model_flops = mult * n * rec["tokens"] / rec["chips"]
    ratio = model_flops / flops if flops else 0.0
    bound = max(terms.values())
    frac_of_roofline = (model_flops / peak["flops"]) / bound if bound else 0.0
    return {
        **{k: rec.get(k) for k in ("arch", "shape", "mesh", "kind", "chips",
                                   "gossip")},
        "device_kind": device_kind,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_device": model_flops,
        "hlo_flops_per_device": flops,
        "useful_ratio": ratio,
        "roofline_fraction": frac_of_roofline,
        "remedy": REMEDY[dominant],
        "compile_s": rec.get("compile_s"),
        "temp_gb": rec["memory"]["temp_bytes"] / 1e9,
        "arg_gb": rec["memory"]["argument_bytes"] / 1e9,
    }


def fmt_s(x):
    if x == 0:
        return "0"
    if x < 1e-3:
        return f"{x*1e6:.1f}us"
    if x < 1:
        return f"{x*1e3:.2f}ms"
    return f"{x:.2f}s"


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | compute | memory | collective | dominant "
           "| useful FLOPs ratio | roofline frac | temp GB/chip |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{fmt_s(r['t_compute_s'])} | {fmt_s(r['t_memory_s'])} | "
            f"{fmt_s(r['t_collective_s'])} | **{r['dominant']}** | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} | "
            f"{r['temp_gb']:.1f} |")
    return hdr + "\n".join(lines) + "\n"


def pick_hillclimb(rows: list[dict]) -> dict:
    """worst roofline fraction / most collective-bound / most representative
    of the paper's technique (train shape with the largest gossip share)."""
    single = [r for r in rows if r["mesh"] == "16x16"]
    nonzero = [r for r in single if r["hlo_flops_per_device"]]
    worst = min(nonzero, key=lambda r: r["roofline_fraction"])
    coll = max(single, key=lambda r: (r["t_collective_s"] /
                                      max(sum((r["t_compute_s"],
                                               r["t_memory_s"],
                                               r["t_collective_s"])), 1e-12)))
    train = [r for r in single if r["kind"] == "train"]
    paper = max(train, key=lambda r: r["t_collective_s"]) if train else None
    return {"worst_roofline": worst, "most_collective_bound": coll,
            "paper_representative": paper}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--results", default=os.path.join(
        os.path.dirname(__file__), "results", "dryrun"))
    p.add_argument("--md", default=os.path.join(
        os.path.dirname(__file__), "results", "roofline.md"))
    p.add_argument("--json", default=os.path.join(
        os.path.dirname(__file__), "results", "roofline.json"))
    args = p.parse_args(argv)

    recs = load_results(args.results)
    rows = [analyze(r) for r in recs]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    md = to_markdown(rows)
    print(md)
    picks = pick_hillclimb(rows)
    print("## Hillclimb picks")
    for why, r in picks.items():
        if r:
            print(f"- {why}: {r['arch']} x {r['shape']} "
                  f"(dominant={r['dominant']}, frac={r['roofline_fraction']:.3f})")
    with open(args.md, "w") as f:
        f.write(md)
    with open(args.json, "w") as f:
        json.dump({"rows": rows,
                   "picks": {k: (v["arch"], v["shape"]) for k, v in
                             picks.items() if v}}, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
