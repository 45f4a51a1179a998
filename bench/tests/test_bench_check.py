"""The output check's walk on one device is the one it has always been.

`check.reference_chunk` gained a walk over a mesh (``shardings``) and the
references a per-layer rematerialization (``remat``).  Without them, the
walk must give, bit for bit, what it gave before they were added: the
frozen copies below are that earlier walk and the earlier losses of the
two references, and the small cells' numbers are compared exactly."""
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench.check import F32, U, _leaf_norms, leaf_change_norms
from bench.refs import moe, xlstm
from bench.refs.common import cross_entropy, mm, rms_norm


def _xlstm_loss(params, batch, s: dict, mode: str = "f32"):
    H, V = s["num_heads"], s["vocab_size"]
    x = params["embed"].astype(F32)[batch["tokens"]]
    seen = {"mlstm": 0, "slstm": 0}
    for kind in xlstm._kinds(s):
        p = jax.tree.map(lambda a, i=seen[kind]: a[i], params[kind])
        seen[kind] += 1
        x = (xlstm._mlstm if kind == "mlstm" else xlstm._slstm)(p, x, H,
                                                                 mode)
    x = rms_norm(x, params["final_norm_gamma"])
    logits = mm("bsd,vd->bsv", x, params["embed"][:V], mode)
    return cross_entropy(logits, batch["labels"])


def _moe_loss(params, batch, s: dict, mode: str = "f32"):
    x = params["embed"].astype(F32)[batch["tokens"]]
    for i in range(s["num_layers"]):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        x = x + moe._attention(p, rms_norm(x, p["attn_norm_gamma"]), s,
                               mode)
        x = x + moe._experts(p["moe"], rms_norm(x, p["mlp_norm_gamma"]), s,
                             mode)
    x = rms_norm(x, params["final_norm_gamma"])
    logits = mm("bsd,vd->bsv", x, params["embed"][:s["vocab_size"]], mode)
    return cross_entropy(logits, batch["labels"])


def _frozen_reference_chunk(ref, sizes: dict, x0, chunk: dict, step_keys,
                            *, m: int, algorithm: str, lr: float, hold: int,
                            seed: int, mode: str = "f32",
                            draws: str = "ref") -> dict:
    from bench.seeds import jax_key
    store = jnp.dtype(sizes["dtype"])
    support = U.ring_support(m)
    W = U.metropolis(support)
    lam_key = jax_key(seed, draws + "_lambda")
    loss_fn = partial(ref.loss, s=sizes, mode=mode)
    live = m
    vg = jax.jit(jax.value_and_grad(loss_fn))
    x = jax.tree.map(lambda a: jnp.broadcast_to(a, (m,) + a.shape), x0)
    K = chunk["tokens"].shape[0]
    losses, consensus, g0 = [], [], None
    with jax.default_matmul_precision("highest"):
        for k in range(K):
            ls, gs = [], []
            for a in range(m):
                xa = jax.tree.map(lambda t: t[a].astype(F32), x)
                if a < live:
                    batch = {n: jnp.asarray(v[k, a])
                             for n, v in chunk.items()}
                    l, g = vg(xa, batch)
                    ls.append(float(l))
                else:
                    g = jax.tree.map(jnp.zeros_like, xa)
                gs.append(g)
            g = jax.tree.map(lambda *t: jnp.stack(t), *gs)
            del gs
            if g0 is None:
                g0 = np.asarray(_leaf_norms(jax.tree.map(
                    lambda t: t.mean(0), g)))
            losses.append(float(np.mean(ls)))
            lam = U.step_size(k, lr, hold)
            if algorithm == "pdsgd":
                B = U.sample_b(step_keys[k], k, support)
                new = U.pdsgd(x, g, W, B, lam,
                              jax.random.fold_in(lam_key, k), store)
            else:
                new = U.dsgd(x, g, W, lam, store)
            x = jax.tree.map(lambda t: t.astype(store), new)
            consensus.append(float(U.consensus(x, store)))
            del g, new
    return {"losses": losses, "consensus": consensus,
            "change": np.asarray(leaf_change_norms(x, x0)),
            "grad0": g0}


def _tiny(name):
    import harness_util as H
    if name == "xlstm":
        _, sizes, *_ = H.SIZES["tiny"]
        return xlstm, _xlstm_loss, dict(sizes, slstm_every=2,
                                        dtype="float32")
    return moe, _moe_loss, H.MOE_TINY


@pytest.mark.parametrize("name", ["xlstm", "moe"])
@pytest.mark.parametrize("algorithm", ["pdsgd", "dsgd"])
def test_walk_on_one_device_is_unchanged(name, algorithm):
    from bench.seeds import jax_key
    from repro.data import make_lm_pipeline
    from repro.launch.steps import per_step_keys
    ref, frozen_loss, sizes = _tiny(name)
    seed, m, K = 3000000029, 4, 2
    chunk = make_lm_pipeline(sizes["vocab_size"], m, 2, 8,
                             seed=seed).chunk_at(0, K)
    keys = per_step_keys(jax_key(seed, "step_keys"), 0, K)
    x0 = jax.jit(lambda k: ref.init(k, sizes))(jax_key(seed, "weights"))
    kw = dict(m=m, algorithm=algorithm, lr=0.4, hold=200, seed=seed)
    now = check.reference_chunk(ref, sizes, x0, chunk, keys, **kw)
    before = _frozen_reference_chunk(
        SimpleNamespace(loss=frozen_loss), sizes, x0, chunk, keys, **kw)
    assert now["losses"] == before["losses"]
    assert now["consensus"] == before["consensus"]
    for k in ("change", "grad0"):
        assert np.array_equal(now[k], before[k]), k
