"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import (flash_attention, gossip_update, obfuscate_update,
                           ssd_intra_chunk, obfuscate_tree, gossip_tree)
from repro.kernels import ref

RNG = np.random.default_rng(0)


def _randn(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32)).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,hd,causal,window", [
    (2, 128, 2, 64, True, None),
    (1, 256, 4, 32, True, 64),
    (2, 64, 1, 128, False, None),
    (1, 512, 2, 16, True, 256),
])
def test_flash_attention_sweep(B, S, H, hd, causal, window, dtype):
    q, k, v = (_randn((B, S, H, hd), dtype) for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, window=window, bq=64, bk=64)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n", [(4, 512), (16, 1024), (32, 2048), (5, 512)])
def test_gossip_kernel_sweep(m, n, dtype):
    W = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    B = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    X, U = _randn((m, n), dtype), _randn((m, n), dtype)
    out = gossip_update(W, B, X, U)
    expect = ref.gossip_ref(W, B, X, U)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol,
                               rtol=tol)


@settings(max_examples=10, deadline=None)
@given(r=st.sampled_from([4, 8, 16]), c=st.sampled_from([256, 512, 1024]),
       lam=st.floats(1e-3, 1.0), seed=st.integers(0, 100))
def test_obfuscate_kernel_property(r, c, lam, seed):
    x = _randn((r, c), jnp.float32)
    g = _randn((r, c), jnp.float32)
    bits = jax.random.bits(jax.random.key(seed), (r, c), dtype=jnp.uint32)
    out = obfuscate_update(x, g, bits, lam, 0.4, 0.25, block=(r, 256))
    expect = ref.obfuscate_ref(x, g, bits, jnp.float32(lam), 0.4, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-6)
    # realized lambda within [0, 2 lam]
    lam_real = (0.4 * x - out) / (0.25 * jnp.where(jnp.abs(g) < 1e-6, 1e9, g))
    assert float(lam_real.max()) <= 2 * lam + 1e-4
    assert float(lam_real.min()) >= -1e-4


@pytest.mark.parametrize("G,Q,H,P,N", [(2, 64, 2, 8, 16), (4, 32, 3, 16, 8),
                                       (1, 128, 1, 4, 32)])
def test_ssd_chunk_kernel_sweep(G, Q, H, P, N):
    x = _randn((G, Q, H, P), jnp.float32)
    dt = jnp.abs(_randn((G, Q, H), jnp.float32)) * 0.5
    A = -np.abs(RNG.normal(size=(H,))).astype(np.float32)
    acum = jnp.cumsum(dt * A, axis=1)
    Bm = _randn((G, Q, N), jnp.float32)
    Cm = _randn((G, Q, N), jnp.float32)
    y, s = ssd_intra_chunk(x, dt, acum, Bm, Cm)
    y_ref, s_ref = ref.ssd_intra_chunk_ref(x, dt, acum, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-5)


def test_tree_wrappers_match_core_update():
    """obfuscate_tree + gossip_tree compose to the paper's Eq. (4) on a
    pytree — cross-check against core.pdsgd dense path up to RNG realization."""
    m = 6
    tree_x = {"a": _randn((m, 8, 4), jnp.float32), "b": _randn((m, 10), jnp.float32)}
    tree_u = {"a": _randn((m, 8, 4), jnp.float32), "b": _randn((m, 10), jnp.float32)}
    W = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    B = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    out = gossip_tree(W, B, tree_x, tree_u)
    for name in tree_x:
        expect = (np.einsum("ij,j...->i...", np.asarray(W), np.asarray(tree_x[name]))
                  - np.einsum("ij,j...->i...", np.asarray(B), np.asarray(tree_u[name])))
        np.testing.assert_allclose(np.asarray(out[name]), expect, atol=1e-5)


# -- column blocks sized from VMEM (kernels.blocks) ------------------------

# (streamed itemsizes, float32 temporaries) of one column, as the kernels
# count them: obfuscate (x, g, bits, v), gossip (X, U, x'), and the
# guarded gossip, whose per-link tensors grow with m.
_KERNEL_COLUMNS = {
    "obfuscate": lambda m: ((2, 2, 4, 2), 5),
    "gossip": lambda m: ((2, 2, 2), 0),
    "guarded": lambda m: ((2,) * 5, 4 * m + 5),
}


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("width", [40, 700, 5_000, 95_626_240, 211_393_536])
@pytest.mark.parametrize("m", [2, 4, 8, 32])
@pytest.mark.parametrize("kernel", sorted(_KERNEL_COLUMNS))
def test_column_block_rule(kernel, m, width, interpret):
    """The block is a multiple of 512, no wider than the width rounded up
    to 512, and its double-buffered blocks and temporaries fit the VMEM
    budget by the pessimistic count; it is the widest 512 * 2**k that
    does (unless the width caps it), and interpreted blocks stay under
    the element cap."""
    from repro.kernels.blocks import (INTERPRET_ELEMENTS, LANE_BLOCK,
                                      VMEM_BUDGET, column_block, vmem_rows)
    streamed, temps = _KERNEL_COLUMNS[kernel](m)
    bc = column_block(m, width, streamed, temps, interpret)
    per_col = (2 * sum(vmem_rows(m, s) * s for s in streamed)
               + temps * vmem_rows(m, 4) * 4)
    cap = -(-width // LANE_BLOCK) * LANE_BLOCK
    assert bc % LANE_BLOCK == 0 and LANE_BLOCK <= bc <= cap
    wide = bc * 2
    fits = lambda c: (c * per_col <= VMEM_BUDGET
                      and not (interpret and c * m > INTERPRET_ELEMENTS))
    assert bc == LANE_BLOCK or fits(bc)
    assert bc == cap or not fits(wide)
    # sublane padding: a 4-row bf16 block counts as 16 rows
    assert vmem_rows(4, 2) == 16 and vmem_rows(4, 4) == 8


def test_column_block_guarded_narrows_with_agents():
    """At the same width the guarded kernel's block is narrower than the
    plain gossip's once m is large, and narrows as m grows; at granite's
    4 agents both update kernels take one wide block."""
    from repro.kernels.gossip import gossip_block
    from repro.kernels.obfuscate import obfuscate_block
    n, bf16 = 95_626_240, jnp.bfloat16
    assert gossip_block(32, n, bf16, guarded=True) < gossip_block(32, n, bf16)
    guarded = [gossip_block(m, n, bf16, guarded=True) for m in (2, 8, 32)]
    assert guarded == sorted(guarded, reverse=True) and guarded[0] > guarded[2]
    # the VPU gossip holds no float32 (m, bn) temporaries: twice as wide
    assert gossip_block(4, n, bf16) == 2 * obfuscate_block(4, n, bf16, bf16)
    assert gossip_block(4, n, bf16) >= 32768


def _before_pdsgd(W, B, x_tree, g_tree, bits_tree, lam, mask):
    """The update as the kernels computed it with their former fixed
    blocks: pad to 512, obfuscate in (m, 256) tiles, gossip in 512
    columns."""
    from repro.kernels import masked_gossip_update
    from repro.kernels.ops import _flatten_concat, _pad_cols, _unflatten
    x, sizes, leaves = _flatten_concat(x_tree)
    g, _, _ = _flatten_concat(g_tree)
    bits, _, _ = _flatten_concat(bits_tree)
    (x, pad), (g, _), (bits, _) = (_pad_cols(a, 512) for a in (x, g, bits))
    u = obfuscate_update(x, g, bits, lam, 0.0, -1.0, block=(x.shape[0], 256))
    out = (gossip_update(W, B, x, u, block_n=512) if mask is None
           else masked_gossip_update(mask, B, x, u, block_n=512))
    return _unflatten(out[:, :x.shape[1] - pad], sizes, leaves, x_tree)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("m", [2, 4, 8])
def test_update_paths_block_parity(m, masked):
    """fused_pdsgd_tree (concat) and sharded_pdsgd_tree (leafwise), HBM
    bits, over a tree whose width is no multiple of the rule's block and
    spans several of them: bit for bit what the former fixed blocks gave,
    and the kernels/ref.py oracles up to float32 rounding."""
    from repro.core.mixing import metropolis_from_mask
    from repro.kernels import fused_pdsgd_tree
    from repro.kernels.gossip import gossip_block
    from repro.kernels.ops import _flatten_concat, sharded_pdsgd_tree
    shapes = {"emb": (97, 301), "w": (5003,), "b": (9,), "h": (64, 333)}
    ks = iter(jax.random.split(jax.random.key(m), 16))
    x = {k: jax.random.normal(next(ks), (m,) + s) for k, s in shapes.items()}
    g = {k: jax.random.normal(next(ks), (m,) + s) for k, s in shapes.items()}
    bits = {k: jax.random.bits(next(ks), (m,) + s, jnp.uint32)
            for k, s in shapes.items()}
    W = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    B = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    mask = None
    if masked:
        mask = jnp.ones((m, m), jnp.float32) - jnp.eye(m, dtype=jnp.float32)
        W = metropolis_from_mask(mask)
    width = _flatten_concat(x)[0].shape[1]
    bc = gossip_block(m, width, jnp.float32, interpret=True)
    assert width % bc and width > 2 * bc
    lam = 0.07
    before = _before_pdsgd(W, B, x, g, bits, lam, mask)
    fused = fused_pdsgd_tree(W, B, x, g, bits, lam, mask=mask,
                             interpret=True)
    leafwise = sharded_pdsgd_tree(W, B, x, g, bits, lam, mask=mask,
                                  interpret=True)
    for k in shapes:
        assert np.array_equal(np.asarray(fused[k]), np.asarray(before[k])), k
        assert np.array_equal(np.asarray(leafwise[k]),
                              np.asarray(before[k])), k
        u = ref.obfuscate_ref(x[k].reshape(m, -1), g[k].reshape(m, -1),
                              bits[k].reshape(m, -1), jnp.float32(lam),
                              0.0, -1.0)
        expect = ref.gossip_ref(W, B, x[k].reshape(m, -1), u)
        np.testing.assert_allclose(np.asarray(fused[k]).reshape(m, -1),
                                   np.asarray(expect), rtol=1e-6, atol=1e-6)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="the compiled kernels need the chip")
def test_update_kernels_block_invariant_tpu():
    """Compiled, each column of the update is a function of that column
    alone: the rule's wide blocks, the last one overhanging the buffer,
    give bit for bit what (m, 256) obfuscate tiles and 512-column gossip
    blocks give."""
    from repro.kernels.gossip import gossip_block
    m, n = 4, 3 * 65536 + 7 * 512
    x, u = _randn((m, n), jnp.bfloat16), _randn((m, n), jnp.bfloat16)
    bits = jax.random.bits(jax.random.key(2), (m, n), dtype=jnp.uint32)
    W = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    narrow = (obfuscate_update(x, u, bits, 0.05, 0.0, -1.0, block=(m, 256)),
              gossip_update(W, W, x, u, block_n=512))
    wide = (obfuscate_update(x, u, bits, 0.05, 0.0, -1.0),
            gossip_update(W, W, x, u))
    assert gossip_block(m, n, jnp.bfloat16) == 131072
    for a, b in zip(narrow, wide):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- the dense gossip's contraction on the VPU (kernels.gossip._mix) ------

_FORM_AGENTS = [2, 3, 4, 5, 8, 16, 17, 32]


def _ring_mask(m):
    a = np.zeros((m, m), np.float32)
    for i in range(m):
        a[i, (i + 1) % m] = a[(i + 1) % m, i] = 1.0
    return jnp.asarray(a)


def _within_bf16_ulp(out, expect):
    """|out - expect| is at most one bf16 unit in the last place of
    ``expect``, plus 2**-20 for a sum that cancels: there the two float32
    sums, added in different orders, differ by more than ``expect``'s
    own ulp."""
    out, expect = (np.asarray(a, np.float32) for a in (out, expect))
    mag = np.maximum(np.abs(expect), np.float32(2.0 ** -126))
    ulp = np.ldexp(np.float32(1.0), np.floor(np.log2(mag)).astype(int) - 7)
    return np.abs(out - expect) <= ulp + 2.0 ** -20


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("m", _FORM_AGENTS)
def test_dense_gossip_forms_match_oracle(m, masked):
    """The plain and masked kernels, from 2 to 32 agents, against the
    HIGHEST oracle: a grid whose last block overhangs n and a narrow
    buffer's one block (700 columns: one 512-column slice and part of
    one), float32 to float32 rounding and bf16 within one bf16 ulp."""
    from repro.core.mixing import metropolis_from_mask
    from repro.kernels import masked_gossip_update
    B = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    mask = _ring_mask(m)
    W = (metropolis_from_mask(mask) if masked else
         jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32)))
    for n, bn in ((2 * 1024 + 300, 1024), (700, None)):
        for dtype in (jnp.float32, jnp.bfloat16):
            X, U = _randn((m, n), dtype), _randn((m, n), dtype)
            out = (masked_gossip_update(mask, B, X, U, block_n=bn)
                   if masked else gossip_update(W, B, X, U, block_n=bn))
            expect = ref.gossip_ref(W, B, X, U)
            assert out.dtype == dtype and out.shape == (m, n)
            if dtype == jnp.float32:
                np.testing.assert_allclose(np.asarray(out),
                                           np.asarray(expect),
                                           rtol=1e-6, atol=1e-6)
            else:
                assert _within_bf16_ulp(out, expect).all(), (n, bn)


def _consensus_rows(m, n, seed=0):
    """float32 rows all equal to 3y, y bf16 numbers: with ring weights
    of 1/3, every product w x rounds to y exactly and every partial sum
    is exact, in any order."""
    y = jax.random.normal(jax.random.key(seed), (n,)).astype(jnp.bfloat16)
    return jnp.broadcast_to(3.0 * y.astype(jnp.float32), (m, n))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("m", [3, 4, 5, 17])
def test_gossip_keeps_consensus_exactly(m, masked):
    """Equal rows, U = 0 and ring Metropolis weights (1/3, no bf16
    number): x' is x bit for bit.  The same kernel with
    W rounded to bf16 -- what a one-pass MXU dot does -- does not: its
    rows sum to 1 + 2**-9."""
    from repro.core.mixing import metropolis_from_mask
    from repro.kernels import masked_gossip_update
    mask = _ring_mask(m)
    W = metropolis_from_mask(mask)
    X = _consensus_rows(m, 1024)
    U = jnp.zeros_like(X)
    B = jnp.full((m, m), 0.25, jnp.float32)
    out = (masked_gossip_update(mask, B, X, U, block_n=512) if masked
           else gossip_update(W, B, X, U, block_n=512))
    assert np.array_equal(np.asarray(out), np.asarray(X))
    rounded = W.astype(jnp.bfloat16).astype(jnp.float32)
    assert not np.array_equal(
        np.asarray(gossip_update(rounded, B, X, U, block_n=512)),
        np.asarray(X))


def _pallas_names(jaxpr):
    """The ``name`` of every pallas_call in a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _pallas_names(inner)


def test_gossip_calls_name_their_form():
    """The plain and masked kernels' calls say that they contract on the
    VPU, at the granite cell's 4 agents and at 32, and the compiled
    step's custom call keeps the name."""
    from repro.kernels import masked_gossip_update
    for m in (4, 32):
        eye, x = jnp.eye(m), jnp.ones((m, 512), jnp.bfloat16)
        plain = jax.make_jaxpr(lambda w, x: gossip_update(w, w, x, x))
        masked = jax.make_jaxpr(
            lambda w, x: masked_gossip_update(w, w, x, x))
        assert list(_pallas_names(plain(eye, x).jaxpr)) == [
            "_gossip_update_vpu"]
        assert list(_pallas_names(masked(eye, x).jaxpr)) == [
            "_masked_gossip_update_vpu"]


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="the compiled kernels need the chip")
def test_vpu_gossip_matches_highest_oracle_tpu():
    """Compiled at m = 4 over granite-width blocks (the last one
    overhanging n): the VPU form is within one bf16 ulp of XLA's HIGHEST
    einsum, and keeps float32 consensus rows bit for bit where a
    bf16-rounded W does not."""
    from repro.core.mixing import metropolis_from_mask
    from repro.kernels.gossip import gossip_block
    m, n = 4, 3 * 65536 + 7 * 512
    assert gossip_block(m, n, jnp.bfloat16) >= 65536
    X, U = _randn((m, n), jnp.bfloat16), _randn((m, n), jnp.bfloat16)
    W = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    B = jnp.asarray(RNG.dirichlet(np.ones(m), m).T.astype(np.float32))
    out = gossip_update(W, B, X, U)
    assert _within_bf16_ulp(out, ref.gossip_ref(W, B, X, U)).all()
    W = metropolis_from_mask(_ring_mask(m))
    X = _consensus_rows(m, n)
    U = jnp.zeros_like(X)
    assert np.array_equal(np.asarray(gossip_update(W, B, X, U)),
                          np.asarray(X))
    rounded = W.astype(jnp.bfloat16).astype(jnp.float32)
    assert not np.array_equal(np.asarray(gossip_update(rounded, B, X, U)),
                              np.asarray(X))


# -- in-kernel TPU randomness (the kernels.runtime knob) ------------------


def test_kernel_rng_knob_defaults_and_env(monkeypatch):
    """default_kernel_rng: backend-derived (False on this CPU container),
    REPRO_KERNEL_RNG overrides both ways; resolve passes explicit values
    through untouched."""
    from repro.kernels import runtime
    monkeypatch.delenv("REPRO_KERNEL_RNG", raising=False)
    expect = jax.default_backend() == "tpu"
    assert runtime.default_kernel_rng() is expect
    monkeypatch.setenv("REPRO_KERNEL_RNG", "1")
    assert runtime.default_kernel_rng() is True
    assert runtime.resolve_kernel_rng(None) is True
    monkeypatch.setenv("REPRO_KERNEL_RNG", "0")
    assert runtime.default_kernel_rng() is False
    assert runtime.resolve_kernel_rng(None) is False
    assert runtime.resolve_kernel_rng(True) is True
    assert runtime.resolve_kernel_rng(False) is False


def test_fused_pdsgd_kernel_rng_requires_seed():
    from repro.kernels import fused_pdsgd_tree
    m = 2
    x = {"a": _randn((m, 8), jnp.float32)}
    g = {"a": _randn((m, 8), jnp.float32)}
    W = jnp.eye(m)
    with pytest.raises(ValueError, match="seed"):
        fused_pdsgd_tree(W, W, x, g, None, 0.1, kernel_rng=True,
                         interpret=True)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="CPU-only gate: TPU has the lowering")
def test_kernel_rng_path_refuses_cpu_lowering():
    """pltpu.prng_seed has no CPU rule even under interpret=True — the
    krng path must fail LOUDLY off-TPU, never silently fall back (a
    silent fallback would realize a different Lambda stream than the
    run requested)."""
    from repro.kernels import obfuscate_update_krng
    x = _randn((2, 256), jnp.float32)
    g = _randn((2, 256), jnp.float32)
    seed = jnp.zeros((2,), jnp.uint32)
    with pytest.raises(NotImplementedError):
        jax.block_until_ready(obfuscate_update_krng(
            x, g, seed, 0.1, 0.0, -1.0, block=(2, 256), interpret=True))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs the Mosaic PRNG lowering")
def test_kernel_rng_replay_parity_tpu():
    """The krng kernel exports the bits it drew; replaying them through
    the HBM-bits kernel must reproduce the krng output bit-for-bit —
    the two randomness plumbing routes share ALL their math."""
    from repro.kernels import obfuscate_update, obfuscate_update_krng
    x = _randn((4, 512), jnp.float32)
    g = _randn((4, 512), jnp.float32)
    seed = jnp.asarray([7, 11], jnp.uint32)
    out, bits = obfuscate_update_krng(x, g, seed, 0.05, 0.0, -1.0,
                                      block=(4, 256))
    replay = obfuscate_update(x, g, bits, 0.05, 0.0, -1.0, block=(4, 256))
    assert np.array_equal(np.asarray(out), np.asarray(replay))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs the Mosaic PRNG lowering")
def test_kernel_rng_tile_streams_depend_only_on_coordinates_tpu():
    """Each tile's draw is a function of (seed, i, j) alone: widening the
    grid leaves the existing tiles' bits unchanged, and distinct tiles
    (and distinct seeds) draw distinct bits."""
    from repro.kernels import obfuscate_update_krng
    seed = jnp.asarray([7, 11], jnp.uint32)

    def bits(rows, cols, s=seed):
        x = jnp.zeros((rows, cols), jnp.float32)
        return np.asarray(obfuscate_update_krng(
            x, x, s, 0.05, 0.0, -1.0, block=(8, 256))[1])

    small, wide = bits(16, 512), bits(16, 1024)
    assert np.array_equal(small, wide[:, :512])
    tiles = [wide[r:r + 8, c:c + 256] for r in (0, 8) for c in (0, 256, 512)]
    for a in range(len(tiles)):
        for b in range(a + 1, len(tiles)):
            assert not np.array_equal(tiles[a], tiles[b]), (a, b)
    other = bits(16, 512, jnp.asarray([7, 12], jnp.uint32))
    assert not np.array_equal(small, other)


def test_kernel_rng_seed_words_fresh_every_step():
    """The in-kernel draw is seeded with the step index and random bits;
    with a tile's coordinates folded in (one row of tiles, as on the fused
    paths) no two steps and no two tiles of a step share seed words."""
    from repro.core.pdsgd import krng_seed
    from repro.kernels.obfuscate import tile_seed
    key, cols = jax.random.key(3), jnp.arange(0, 400_000, 97)
    seen = set()
    for k in range(4):
        seed = jnp.asarray(jax.jit(krng_seed)(key, jnp.int32(k)), jnp.int32)
        assert int(seed[0]) == k
        w0, w1 = tile_seed(seed, 0, cols)
        words = {(int(w0), int(w)) for w in np.asarray(w1)}
        assert len(words) == cols.size
        assert seen.isdisjoint(words)
        seen |= words


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs the Mosaic PRNG lowering")
def test_kernel_rng_consecutive_steps_draw_fresh_bits_tpu():
    """The same tile draws different Lambda bits at consecutive steps."""
    from repro.core.pdsgd import krng_seed
    from repro.kernels import obfuscate_update_krng
    x, key = jnp.zeros((4, 1024), jnp.float32), jax.random.key(5)
    a, b = (np.asarray(obfuscate_update_krng(
        x, x, krng_seed(key, jnp.int32(k)), 0.05, 0.0, -1.0,
        block=(4, 256))[1]) for k in (0, 1))
    for c in range(0, 1024, 256):
        assert not np.array_equal(a[:, c:c + 256], b[:, c:c + 256])


def test_mask_from_bits_math():
    """The in-kernel mask math on synthetic bits: symmetric, zero diag,
    gated by the base adjacency, and each kept edge corresponds to a
    sub-threshold upper-triangle U[0,1) draw (the exact
    `core.mixing.symmetric_edge_mask` formula on explicit bits)."""
    from repro.kernels.gossip import _mask_from_bits
    m = 8
    bits = jnp.asarray(RNG.integers(0, 2**32, (m, m), dtype=np.uint32))
    adj = jnp.asarray((RNG.random((m, m)) < 0.7).astype(np.float32))
    adj = jnp.triu(adj, k=1) + jnp.triu(adj, k=1).T
    mask = np.asarray(_mask_from_bits(bits, jnp.float32(0.5), adj))
    assert np.array_equal(mask, mask.T)
    assert np.all(np.diag(mask) == 0)
    assert np.all(mask <= np.asarray(adj))
    f = (np.asarray(bits) >> 9) | np.uint32(0x3F800000)
    u01 = f.view(np.float32) - 1.0
    keep = np.triu(u01 < 0.5, k=1).astype(np.float32)
    assert np.array_equal(mask, (keep + keep.T) * np.asarray(adj))


def test_fused_pdsgd_mask_seed_requires_keep_prob():
    from repro.kernels import fused_pdsgd_tree
    m = 2
    x = {"a": _randn((m, 8), jnp.float32)}
    g = {"a": _randn((m, 8), jnp.float32)}
    bits = {"a": jnp.zeros((m, 8), jnp.uint32)}
    W = jnp.eye(m)
    with pytest.raises(ValueError, match="keep_prob"):
        fused_pdsgd_tree(W, W, x, g, bits, 0.1,
                         mask_seed=jnp.zeros((2,), jnp.uint32),
                         interpret=True)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="CPU-only gate: TPU has the lowering")
def test_masked_gossip_krng_refuses_cpu_lowering():
    """Same loud-failure contract as the obfuscate krng kernel: no Mosaic
    PRNG rule off-TPU, so the in-kernel mask draw must raise rather than
    realize a graph from some other stream."""
    from repro.kernels import masked_gossip_update_krng
    m = 4
    adj = 1.0 - jnp.eye(m, dtype=jnp.float32)
    X = _randn((m, 512), jnp.float32)
    U = _randn((m, 512), jnp.float32)
    B = jnp.eye(m) * 0.1
    with pytest.raises(NotImplementedError):
        jax.block_until_ready(masked_gossip_update_krng(
            jnp.zeros((2,), jnp.uint32), 0.5, adj, B, X, U, interpret=True))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs the Mosaic PRNG lowering")
def test_masked_gossip_krng_replay_parity_tpu():
    """The krng kernel exports the realized (m, m) mask; replaying it
    through the HBM-mask kernel must reproduce the output bit-for-bit,
    and every column tile must have drawn the identical mask (the kernel
    re-seeds with the same words per tile)."""
    from repro.kernels import masked_gossip_update, masked_gossip_update_krng
    m, n = 8, 1024  # n > block so the grid has >1 tile
    adj = 1.0 - jnp.eye(m, dtype=jnp.float32)
    X = _randn((m, n), jnp.float32)
    U = _randn((m, n), jnp.float32)
    B = jnp.eye(m) * 0.1
    seed = jnp.asarray([3, 9], jnp.uint32)
    out, mask = masked_gossip_update_krng(seed, 0.6, adj, B, X, U,
                                          block_n=512)
    mask_np = np.asarray(mask)
    assert np.array_equal(mask_np, mask_np.T)
    assert np.all(np.diag(mask_np) == 0)
    replay = masked_gossip_update(mask, B, X, U, block_n=512)
    assert np.array_equal(np.asarray(out), np.asarray(replay))
    # determinism: same seed, same realized graph
    _, mask2 = masked_gossip_update_krng(seed, 0.6, adj, B, X, U,
                                         block_n=512)
    assert np.array_equal(mask_np, np.asarray(mask2))


# -- fused ring gossip (overlapped obfuscate + staged shifts) -------------


def _ring_tables(n_data, n_pod, n, seed=0):
    """(w_tab, b_tab, perms, X, U) on the regular torus — w_tab repeats
    the Metropolis self/edge weights into the (m, 1+ndirs) table form."""
    from repro.dist import collectives as C
    m = n_data * n_pod
    kb, kx, ku = jax.random.split(jax.random.key(seed), 3)
    b = C.sample_b_draws(kb, m, n_data, n_pod)
    ndirs = b.shape[1] - 1
    wts = C.torus_weights(n_data, n_pod)
    w_tab = jnp.concatenate(
        [jnp.full((m, 1), wts["w_self"], jnp.float32),
         jnp.full((m, ndirs), wts["w_edge"], jnp.float32)], axis=1)
    perms = C.perm_stack(n_data, n_pod)
    X = jax.random.normal(kx, (m, n), jnp.float32)
    U = jax.random.normal(ku, (m, n), jnp.float32)
    return w_tab, b, perms, X, U


@pytest.mark.parametrize("n_data,n_pod,n", [(8, 1, 512), (4, 2, 1024),
                                            (3, 1, 512)])
def test_ring_gossip_bitwise_vs_jitted_oracle(n_data, n_pod, n):
    """The fused ring kernel IS the jitted staged-ring jnp program, bit
    for bit (XLA:CPU contracts w*x - b*u into an FMA identically in
    both), and capture=True must not perturb the update output."""
    from repro.kernels import ring_gossip_update
    w_tab, b, perms, X, U = _ring_tables(n_data, n_pod, n)
    out = ring_gossip_update(w_tab, b, perms, X, U)
    out_c, v_c = ring_gossip_update(w_tab, b, perms, X, U, capture=True)
    ref_out, ref_v = jax.jit(ref.ring_gossip_ref)(w_tab, b, perms, X, U)
    assert np.array_equal(np.asarray(out), np.asarray(ref_out))
    assert np.array_equal(np.asarray(out_c), np.asarray(ref_out))
    assert np.array_equal(np.asarray(v_c), np.asarray(ref_v))


@pytest.mark.parametrize("n_data,n_pod", [(8, 1), (4, 2)])
def test_ring_gossip_matches_dense_coupling(n_data, n_pod):
    """Ring tables and the dense (W, B) they materialize agree: the
    kernel output is W X - B U up to FMA reassociation."""
    from repro.dist import collectives as C
    w_tab, b, perms, X, U = _ring_tables(n_data, n_pod, 512, seed=3)
    out = np.asarray(jax.block_until_ready(
        __import__("repro.kernels", fromlist=["ring_gossip_update"])
        .ring_gossip_update(w_tab, b, perms, X, U)))
    W, B = C.dense_coupling(b, n_data, n_pod)
    expect = np.asarray(W) @ np.asarray(X) - np.asarray(B) @ np.asarray(U)
    np.testing.assert_allclose(out, expect, atol=1e-5, rtol=1e-5)


def test_ring_obfuscate_bitwise_and_lambda_range():
    """ring_obfuscate_gossip == its jitted oracle bitwise on (out, v, u);
    every realized Λ_j^k draw lies in [0, 2 lam_bar) (Sec. III)."""
    from repro.kernels import ring_obfuscate_gossip
    lam = 0.05
    w_tab, b, perms, X, G = _ring_tables(8, 1, 512, seed=5)
    m, n = X.shape
    bits = jax.random.bits(jax.random.key(9), (m, n), dtype=jnp.uint32)
    out = ring_obfuscate_gossip(w_tab, b, perms, X, G, bits, lam)
    out_c, v, u = ring_obfuscate_gossip(w_tab, b, perms, X, G, bits, lam,
                                        capture=True)
    r_out, r_v, r_u = jax.jit(ref.ring_obfuscate_gossip_ref)(
        w_tab, b, perms, X, G, bits, lam)
    assert np.array_equal(np.asarray(out), np.asarray(r_out))
    assert np.array_equal(np.asarray(out_c), np.asarray(r_out))
    assert np.array_equal(np.asarray(v), np.asarray(r_v))
    assert np.array_equal(np.asarray(u), np.asarray(r_u))
    lam_real = np.asarray(u) / np.where(np.abs(np.asarray(G)) < 1e-6, 1e9,
                                        np.asarray(G))
    assert float(lam_real.max()) <= 2 * lam + 1e-6
    assert float(lam_real.min()) >= -1e-6


def test_ring_dropped_direction_v_exactly_zero():
    """A dropped link arrives as zeroed table entries; the staged buffer
    for that direction must be EXACTLY zero — a nonzero residue would be
    information leaving on a link the realization severed."""
    from repro.dist import collectives as C
    from repro.kernels import ring_gossip_update
    w_tab, b, perms, X, U = _ring_tables(8, 1, 512, seed=7)
    m, ndirs = X.shape[0], b.shape[1] - 1
    keep = jnp.ones((m, ndirs), jnp.float32).at[:, 0].set(0.0)
    b_m = C.mask_b_draws(b, keep)
    w_m = (w_tab.at[:, 0].add(w_tab[:, 1])).at[:, 1].set(0.0)
    _, v = ring_gossip_update(w_m, b_m, perms, X, U, capture=True)
    v = np.asarray(v)
    assert np.all(v[0] == 0.0)
    assert np.any(v[1] != 0.0)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="CPU-only gate: TPU has the lowering")
def test_ring_krng_refuses_cpu_lowering():
    """Same loud-failure contract as the other krng kernels: no Mosaic
    PRNG rule off-TPU, so the in-kernel ring Λ draw must raise rather
    than realize a different noise stream than the run requested."""
    from repro.kernels import ring_obfuscate_gossip_krng
    w_tab, b, perms, X, G = _ring_tables(8, 1, 512, seed=11)
    with pytest.raises(NotImplementedError):
        jax.block_until_ready(ring_obfuscate_gossip_krng(
            w_tab, b, perms, X, G, jnp.asarray([3, 9], jnp.int32), 0.1,
            interpret=True))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs the Mosaic PRNG lowering")
def test_ring_krng_replay_parity_tpu():
    """The fused ring krng kernel exports its Λ bits; replaying them
    through the HBM-bits ring kernel reproduces the output bit-for-bit,
    over a grid of several column tiles."""
    from repro.kernels import ring_obfuscate_gossip, ring_obfuscate_gossip_krng
    w_tab, b, perms, X, G = _ring_tables(8, 1, 1536, seed=13)
    seed = jnp.asarray([3, 9], jnp.uint32)
    out, bits = ring_obfuscate_gossip_krng(w_tab, b, perms, X, G, seed, 0.1,
                                           block_n=512)
    replay = ring_obfuscate_gossip(w_tab, b, perms, X, G, bits, 0.1,
                                   block_n=512)
    assert np.array_equal(np.asarray(out), np.asarray(replay))
    tiles = np.split(np.asarray(bits), 3, axis=1)
    assert not np.array_equal(tiles[0], tiles[1])


def test_ring_pdsgd_tree_matches_flat_kernel():
    """Tree wrapper == flat kernel on the concatenated leaves, bitwise,
    and observe=True taps the identical v/u stream without perturbing
    the params output."""
    from repro.kernels import ring_obfuscate_gossip, ring_pdsgd_tree
    from repro.kernels.ops import _flatten_concat
    w_tab, b, perms, _, _ = _ring_tables(8, 1, 512, seed=13)
    m = 8
    kx, kg = jax.random.split(jax.random.key(15))
    x_tree = {"a": jax.random.normal(kx, (m, 20, 10)),
              "c": jax.random.normal(kg, (m, 56))}
    g_tree = jax.tree.map(lambda t: t * 0.1, x_tree)
    bits_tree = jax.tree.map(
        lambda t: jax.random.bits(jax.random.key(17), t.shape[:1]
                                  + (int(np.prod(t.shape[1:])),),
                                  dtype=jnp.uint32).reshape(t.shape), x_tree)
    out_tree = ring_pdsgd_tree(w_tab, b, perms, x_tree, g_tree, bits_tree,
                               0.1, interpret=True)
    out_obs, flats = ring_pdsgd_tree(w_tab, b, perms, x_tree, g_tree,
                                     bits_tree, 0.1, interpret=True,
                                     observe=True)
    x_flat, _, _ = _flatten_concat(x_tree)
    g_flat, _, _ = _flatten_concat(g_tree)
    bits_flat, _, _ = _flatten_concat(bits_tree)
    ncols = x_flat.shape[1]
    pad = (-ncols) % 512
    xp = jnp.pad(x_flat, ((0, 0), (0, pad)))
    gp = jnp.pad(g_flat, ((0, 0), (0, pad)))
    bp = jnp.pad(bits_flat.view(jnp.uint32), ((0, 0), (0, pad)))
    flat_out, flat_v, flat_u = ring_obfuscate_gossip(
        w_tab, b, perms, xp, gp, bp, 0.1, capture=True, interpret=True)
    for name in x_tree:
        got = _flatten_concat({name: out_tree[name]})[0]
        obs = _flatten_concat({name: out_obs[name]})[0]
        assert np.array_equal(np.asarray(got), np.asarray(obs))
    all_out = _flatten_concat(out_tree)[0]
    assert np.array_equal(np.asarray(all_out),
                          np.asarray(flat_out[:, :ncols]))
    assert np.array_equal(np.asarray(flats["v"]),
                          np.asarray(flat_v[:, :, :ncols]))
    assert np.array_equal(np.asarray(flats["u"]),
                          np.asarray(flat_u[:, :ncols]))


def test_ring_pdsgd_tree_kernel_rng_requires_seed():
    from repro.kernels import ring_pdsgd_tree
    w_tab, b, perms, X, G = _ring_tables(8, 1, 512, seed=19)
    x = {"a": X}
    g = {"a": G}
    with pytest.raises(ValueError, match="seed"):
        ring_pdsgd_tree(w_tab, b, perms, x, g, None, 0.1, kernel_rng=True,
                        interpret=True)
