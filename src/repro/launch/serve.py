"""Serving CLI — a thin driver over `repro.serve` (the post-consensus
model; see DESIGN.md §2 Serving).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b-smoke \
      --slots 4 --requests 8 --prompt-len 32 --gen-tokens 16

Modes (``--mode auto`` picks per family):

* ``continuous`` — `serve.ServeEngine` slot-based continuous batching:
  queued requests prefill into free slots while the rest of the batch
  keeps decoding.  ``--arrival-rate`` turns the queue into an open-loop
  Poisson arrival process.
* ``static`` — same engine, gang admission (run-to-completion waves);
  the static-batching baseline continuous is measured against.
* ``oneshot`` — one fixed uniform batch through the device-resident
  chunk loop (`serve.loop`); the only mode for enc-dec (audio) models,
  whose cross-attention cache is encoder-length-shaped per request.

Two seed-driver bugs are fixed here rather than inherited: timing used
to fold JIT compile into the measured wall clock (now compile and
steady-state are reported separately), and temperature sampling used to
split keys off the SAME stream that synthesized the prompts/frames
(fold_in 1/2) — sampling keys now live in `serve.loop.SAMPLE_DOMAIN`,
keyed per (request, position), disjoint from every data stream.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models import build_model
from ..serve import (Request, ServeEngine, init_loop_state, make_decode_loop,
                     sequential_decode)
from ..serve.engine import Completion
from .compile_cache import use_compile_cache


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def _synthetic_requests(cfg, bundle, args):
    """Prompts/frames from the data key streams (fold_in 0/1/2); sampling
    keys never touch these (SAMPLE_DOMAIN separation)."""
    key = jax.random.key(args.seed + 1)
    n = args.requests
    prompts = np.asarray(jax.random.randint(
        key, (n, args.prompt_len), 0, cfg.vocab_size), np.int32)
    prefix = None
    if cfg.num_prefix_embeds:
        prefix = np.asarray(jax.random.normal(
            jax.random.fold_in(key, 2),
            (n, cfg.num_prefix_embeds, cfg.d_model), bundle.dtype) * 0.1)
    arrivals = np.zeros(n)
    if args.arrival_rate > 0:
        rng = np.random.default_rng(args.seed)
        arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate, n))
    return [Request(req_id=i, tokens=prompts[i],
                    max_new_tokens=args.gen_tokens,
                    arrival_time=float(arrivals[i]),
                    prefix_embeds=None if prefix is None else prefix[i])
            for i in range(n)]


def _summarize(completions: list[Completion], steady_chunk_s, compile_stats):
    done = [c for c in completions if c.first_token_at is not None]
    total_toks = sum(len(c.tokens) for c in completions)
    span = (max(c.finished_at for c in completions)
            - min(c.admitted_at for c in completions)) if completions else 0.0
    return {
        "completed": len(completions),
        "generated_tokens": total_toks,
        "tokens_per_s": round(total_toks / max(span, 1e-9), 1),
        "ttft_p50_ms": round(1e3 * _percentile(
            [c.ttft for c in done], 50), 2) if done else None,
        "latency_p50_ms": round(1e3 * _percentile(
            [c.latency for c in completions], 50), 2),
        "latency_p99_ms": round(1e3 * _percentile(
            [c.latency for c in completions], 99), 2),
        "steady_chunk_ms": (round(1e3 * float(np.median(steady_chunk_s)), 3)
                            if steady_chunk_s else None),
        "compile": {k: round(v, 3) for k, v in compile_stats.items()},
    }


def _total_len(cfg, args):
    # prefix embeds occupy cache positions ahead of the prompt (vlm)
    return args.prompt_len + args.gen_tokens + (cfg.num_prefix_embeds or 0)


def _run_engine(bundle, params, args, mesh):
    eng = ServeEngine(
        bundle, params, slots=args.slots,
        max_seq_len=_total_len(bundle.cfg, args),
        decode_chunk=args.decode_chunk, temperature=args.temperature,
        eos_id=args.eos_id, seed=args.seed,
        admission="gang" if args.mode == "static" else "continuous",
        mesh=mesh)
    compile_stats = eng.warmup(args.prompt_len)
    reqs = _synthetic_requests(bundle.cfg, bundle, args)
    completions = eng.run(reqs)
    out = _summarize(completions, eng.chunk_times[1:], compile_stats)
    out["steady_prefill_ms"] = round(
        1e3 * float(np.median(eng.prefill_times)), 3)
    if eng.audit is not None:
        out["sharding_audit"] = eng.audit
    first = min(completions, key=lambda c: c.req_id)
    out["generated_first_req"] = first.tokens
    if args.parity_check:
        out["parity"] = _parity(bundle, params, reqs, completions, args)
    return out


def _parity(bundle, params, reqs, completions, args):
    got = {c.req_id: c.tokens for c in completions}
    prefill, decode = jax.jit(bundle.prefill_fn), jax.jit(bundle.decode_fn)
    for r in reqs:
        batch = {"tokens": jnp.asarray(r.tokens, jnp.int32)[None]}
        if r.prefix_embeds is not None:
            batch["prefix_embeds"] = jnp.asarray(
                r.prefix_embeds, bundle.dtype)[None]
        ref = sequential_decode(
            bundle, params, batch, r.req_id, r.max_new_tokens,
            temperature=args.temperature, eos_id=args.eos_id,
            base_key=jax.random.key(args.seed),
            max_seq_len=_total_len(bundle.cfg, args),
            prefill=prefill, decode=decode)
        if got.get(r.req_id) != ref:
            return f"mismatch req {r.req_id}: {got.get(r.req_id)} != {ref}"
    return "ok"


def _run_oneshot(bundle, params, args):
    """One fixed uniform batch through the scanned decode loop (the only
    path for enc-dec models); compile and steady-state timed separately."""
    cfg = bundle.cfg
    key = jax.random.key(args.seed + 1)
    B = args.slots
    batch = {"tokens": jax.random.randint(key, (B, args.prompt_len), 0,
                                          cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(key, 1),
            (B, args.prompt_len, cfg.d_model), bundle.dtype) * 0.1
    if cfg.num_prefix_embeds:
        batch["prefix_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2),
            (B, cfg.num_prefix_embeds, cfg.d_model), bundle.dtype) * 0.1

    prefill = jax.jit(bundle.prefill_fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(prefill(params, batch))
    prefill_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(prefill(params, batch))
    prefill_s = time.perf_counter() - t0

    from ..models.common import pad_vocab
    loop = make_decode_loop(bundle, chunk=args.decode_chunk,
                            temperature=args.temperature, eos_id=args.eos_id)
    state = init_loop_state(out["cache"], B, pad_vocab(cfg.vocab_size),
                            jax.random.key(args.seed))
    state.update(
        logits=out["logits"].astype(jnp.float32),
        pos=jnp.full((B,), args.prompt_len, jnp.int32),
        req_id=jnp.arange(B, dtype=jnp.int32), active=jnp.ones((B,), bool),
        remaining=jnp.full((B,), args.gen_tokens, jnp.int32))
    toks_rows = [[] for _ in range(B)]
    chunk_times = []
    n_chunks = -(-args.gen_tokens // args.decode_chunk)
    for _ in range(n_chunks):
        t0 = time.perf_counter()
        state, toks, emitted = loop(params, state)
        toks, emitted = np.asarray(toks), np.asarray(emitted)
        chunk_times.append(time.perf_counter() - t0)
        for b in range(B):
            toks_rows[b].extend(toks[emitted[:, b], b].tolist())
    steady = chunk_times[1:] or chunk_times
    total = sum(len(r) for r in toks_rows)
    steady_tokens = total - min(args.decode_chunk * B, total)
    result = {
        "completed": B,
        "generated_tokens": total,
        "tokens_per_s": round(steady_tokens / max(sum(steady), 1e-9), 1)
        if len(chunk_times) > 1 else round(total / max(sum(chunk_times), 1e-9), 1),
        "steady_chunk_ms": round(1e3 * float(np.median(steady)), 3),
        "steady_prefill_ms": round(1e3 * prefill_s, 3),
        "compile": {"prefill_compile_s": round(prefill_compile_s, 3),
                    "chunk_compile_s": round(chunk_times[0], 3)},
        "generated_first_req": toks_rows[0],
    }
    if args.parity_check:
        ok = "ok"
        prefill_1 = jax.jit(bundle.prefill_fn)
        for b in range(B):
            b1 = {k: v[b:b + 1] for k, v in batch.items()}
            ref = sequential_decode(bundle, params, b1, b, args.gen_tokens,
                                    temperature=args.temperature,
                                    eos_id=args.eos_id,
                                    base_key=jax.random.key(args.seed),
                                    prefill=prefill_1)
            if ref != toks_rows[b]:
                ok = f"mismatch row {b}: {toks_rows[b]} != {ref}"
                break
        result["parity"] = ok
    return result


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="granite-8b-smoke")
    p.add_argument("--slots", type=int, default=4,
                   help="decode-batch capacity (requests in flight)")
    p.add_argument("--requests", type=int, default=None,
                   help="total requests to serve (default: slots)")
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen-tokens", type=int, default=16)
    p.add_argument("--decode-chunk", type=int, default=8,
                   help="tokens decoded per host round-trip (lax.scan)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mode", default="auto",
                   choices=["auto", "continuous", "static", "oneshot"])
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="open-loop Poisson arrivals per second (0: all at t0)")
    p.add_argument("--model-parallel", type=int, default=1,
                   help=">1: shard serving over a model axis "
                        "(SERVE_RULES + audit_rules gate)")
    p.add_argument("--parity-check", action="store_true",
                   help="re-decode every request sequentially and compare")
    args = p.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.mode == "auto":
        args.mode = "oneshot" if cfg.family == "audio" else "continuous"
    if args.requests is None:
        args.requests = args.slots

    mesh = None
    if args.model_parallel > 1:
        from .mesh import make_global_mesh
        mesh = make_global_mesh(model_parallel=args.model_parallel)
    bundle = build_model(cfg, mesh=mesh)
    params = bundle.init(jax.random.key(args.seed))

    if args.mode == "oneshot":
        result = _run_oneshot(bundle, params, args)
    else:
        result = _run_engine(bundle, params, args, mesh)
    result = dict({"arch": args.arch, "mode": args.mode,
                   "slots": args.slots, "requests": args.requests}, **result)
    print(json.dumps(result))
    return 0 if result.get("parity", "ok") == "ok" else 1


if __name__ == "__main__":
    raise SystemExit(main())
