"""Correctness of the beyond-paper §Perf code paths against the baselines:
chunked (flash-style) attention vs naive, and the shard_map deferred-combine
MoE vs the GSPMD all-reduce baseline (subprocess, 8 fake devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.models.common import attention, chunked_attention


@settings(max_examples=25, deadline=None)
@given(S=st.integers(4, 80), kv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2, 3]), chunk=st.integers(3, 48),
       window=st.one_of(st.none(), st.integers(1, 64)),
       seed=st.integers(0, 100))
def test_chunked_attention_property(S, kv, g, chunk, window, seed):
    """Property: blocked online-softmax == naive attention for any (ragged)
    chunking, GQA grouping, and window."""
    rng = np.random.default_rng(seed)
    H, hd, B = kv * g, 8, 1
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, kv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, kv, hd)).astype(np.float32))
    ref = attention(q, k, v, causal=True, window=window)
    out = chunked_attention(q, k, v, causal=True, window=window, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("S,H,KV,chunk,window", [
    (64, 4, 4, 16, None),
    (64, 4, 2, 16, None),          # GQA
    (96, 4, 1, 32, None),          # MQA + ragged tail (96 % 32 == 0, 3 ch)
    (100, 2, 2, 32, None),         # ragged: 100 % 32 != 0 -> padding path
    (128, 4, 2, 32, 48),           # sliding window crossing chunks
    (64, 2, 2, 64, None),          # single chunk == naive
    (64, 2, 2, 16, 16),            # window == chunk
])
def test_chunked_attention_matches_naive(S, H, KV, chunk, window):
    rng = np.random.default_rng(0)
    hd, B = 16, 2
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)).astype(np.float32))
    ref = attention(q, k, v, causal=True, window=window)
    out = chunked_attention(q, k, v, causal=True, window=window, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_chunked_attention_grad_matches():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)).astype(np.float32))
    f_ref = lambda q, k, v: attention(q, k, v, causal=True).sum()
    f_chk = lambda q, k, v: chunked_attention(q, k, v, causal=True,
                                              chunk=8).sum()
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_chk = jax.grad(f_chk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_chk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


_MOE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, {src!r})
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import moe as M

    cfg = get_config("olmoe-1b-7b-smoke")   # 4 experts top-2, d<=256
    cfg = dataclasses.replace(cfg, moe_impl="deferred")
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    pl = {{
        "router": jnp.asarray(rng.normal(size=(d, E)).astype(np.float32) * .1),
        "w_gate": jnp.asarray(rng.normal(size=(E, d, ff)).astype(np.float32) * .05),
        "w_up": jnp.asarray(rng.normal(size=(E, d, ff)).astype(np.float32) * .05),
        "w_down": jnp.asarray(rng.normal(size=(E, ff, d)).astype(np.float32) * .05),
    }}
    x = jnp.asarray(rng.normal(size=(4, 32, d)).astype(np.float32))
    base = M.moe_ffn_train(pl, x, dataclasses.replace(cfg, moe_impl="allreduce"))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    pls = {{
        "router": jax.device_put(pl["router"], NamedSharding(mesh, P())),
        "w_gate": jax.device_put(pl["w_gate"], NamedSharding(mesh, P(None, None, "model"))),
        "w_up": jax.device_put(pl["w_up"], NamedSharding(mesh, P(None, None, "model"))),
        "w_down": jax.device_put(pl["w_down"], NamedSharding(mesh, P(None, "model", None))),
    }}
    with mesh:
        out = jax.jit(lambda pl, x: M.moe_ffn_train(pl, x, cfg, mesh=mesh))(pls, xs)
    err = float(np.abs(np.asarray(out) - np.asarray(base)).max())
    rel = err / (float(np.abs(np.asarray(base)).max()) + 1e-9)
    print(json.dumps({{"err": err, "rel": rel}}))
""")


def test_moe_deferred_matches_allreduce_multidevice():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    script = _MOE_SCRIPT.format(src=src)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["rel"] < 1e-5, res


def test_roofline_peaks_keyed_by_device_kind():
    """Roofline shares use the peaks of the chip a record names; a chip
    missing from the table is an error, never another chip's peaks."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "roofline.py")
    spec = importlib.util.spec_from_file_location("roofline", path)
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(ValueError, match="no peak table"):
        roofline.peaks("cpu")
    rec = {"arch": "stablelm-3b", "kind": "train", "tokens": 1, "chips": 1,
           "params": {"active": 1}, "device_kind": "cpu",
           "memory": {"temp_bytes": 0, "argument_bytes": 0}}
    with pytest.raises(ValueError, match="no peak table"):
        roofline.analyze(rec)
    del rec["device_kind"]
    assert roofline.analyze(rec)["device_kind"] == "TPU v5 lite"
