"""The paper's inherently privacy-preserving decentralized SGD (Eq. 3/4),
plus the two comparison baselines it is evaluated against:

  * ``pdsgd``        : x^{k+1} = W x^k - B^k (Lambda^k ∘ g^k)       (ours/paper)
  * ``dsgd``         : x^{k+1} = W x^k - lam^k g^k                  (Lian et al. [19])
  * ``dsgt``         : gradient tracking, x and tracker y both gossiped
                       ([49],[50]; 2x PDSGD's message volume)
  * ``dp_dsgd``      : dsgd with N(0, sigma_DP^2) noise added to g  (Table I baseline)

All steps are pure functions over pytrees whose leaves carry a leading agent
axis ``(m, ...)``.  On a production mesh that axis is sharded over
("pod","data") and the einsums below lower to GSPMD collectives; the
communication-optimal ring path lives in ``repro.dist.collectives``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Literal

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace as tr
from .mixing import MixingProcess, as_process
from .privacy import agent_key, leaf_keys, obfuscated_gradient, sample_B
from .schedules import Schedule
from .topology import Topology

__all__ = [
    "Algorithm",
    "DecentralizedState",
    "gossip_mix",
    "pdsgd_update",
    "dsgd_update",
    "dsgt_update",
    "dp_dsgd_update",
    "make_decentralized_step",
    "make_scanned_steps",
    "consensus_error",
    "replicate_params",
]

Pytree = Any
Algorithm = Literal["pdsgd", "dsgd", "dsgt", "dp_dsgd"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecentralizedState:
    """Training state: per-agent parameters and the iteration counter.

    ``tracker`` is algorithm-owned extra state carried through the step
    closure's state tuple: ``None`` for pdsgd/dsgd/dp_dsgd, and the pair
    ``(y, prev_grads)`` for dsgt (build with ``init_state(...,
    algorithm="dsgt")``).  Because it rides inside the state pytree it
    checkpoints, donates, and scans exactly like params.
    """

    params: Pytree  # leaves (m, ...)
    step: jax.Array  # scalar int32
    tracker: Pytree = None  # algorithm extra state (dsgt: (y, prev_grads))

    @property
    def num_agents(self) -> int:
        return jax.tree.leaves(self.params)[0].shape[0]


def replicate_params(params: Pytree, m: int) -> Pytree:
    """Broadcast a single parameter pytree to m identical agent copies."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (m,) + p.shape), params)


def consensus_error(params: Pytree, stored: bool = False) -> jax.Array:
    """sum_i ||x_i - x_bar||^2 — the disagreement Lyapunov term of Thm 1.

    ``stored=True`` measures the values the parameters' dtype holds: each
    leaf and its agent mean are rounded to that dtype with
    `jax.lax.reduce_precision`, and the squares are summed in float32.
    Inside a jitted step the compiler may hand this report the new
    parameters before they are rounded to their dtype (XLA's excess
    precision), and a convert back and forth does not stop it.  On a TPU
    mesh the report read three times the stored parameters' spread, as
    the unrounded update would give: most of a small update rounds away
    in the stored parameters (PERF.md).
    """
    def leaf(p):
        if stored:
            q = _as_stored(p.astype(jnp.float32), p.dtype)
            mean = _as_stored(q.mean(axis=0, keepdims=True), p.dtype)
            return jnp.sum(jnp.square(q - mean))
        mean = p.mean(axis=0, keepdims=True)
        return jnp.sum((p - mean) ** 2)

    return sum(jax.tree.leaves(jax.tree.map(leaf, params)))


def _as_stored(x: jax.Array, dtype) -> jax.Array:
    """float32 ``x`` rounded to the values ``dtype`` holds (nearest even),
    for bfloat16 or float32 parameters: bfloat16 keeps float32's exponent,
    so no value is flushed, as it would be for float16's subnormals."""
    f = jnp.finfo(dtype)
    if f.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


def gossip_mix(mat: jax.Array, params: Pytree) -> Pytree:
    """y_i = sum_j mat[i, j] * x_j over the leading agent axis of each leaf."""

    def leaf(p):
        y = jnp.einsum("ij,j...->i...", mat.astype(p.dtype), p,
                       preferred_element_type=jnp.float32)
        return y.astype(p.dtype)

    return jax.tree.map(leaf, params)


def _per_agent_obfuscated(key: jax.Array, step: jax.Array, grads: Pytree,
                          lam_bar: jax.Array) -> Pytree:
    """u_j = Lambda_j^k ∘ g_j with an independent private key per agent."""
    m = jax.tree.leaves(grads)[0].shape[0]
    keys = jax.vmap(lambda a: agent_key(key, step, a))(jnp.arange(m))
    return jax.vmap(lambda k, g: obfuscated_gradient(k, g, lam_bar))(keys, grads)


def _per_agent_bits(key: jax.Array, step: jax.Array, grads: Pytree) -> Pytree:
    """The raw uint32 draws behind `_per_agent_obfuscated`'s Lambda.

    Uses `privacy.leaf_keys` — the SAME per-(agent, leaf) derivation as the
    eager path — but stops at the counter output: `jax.random.uniform(k, s)`
    is bit-identical to mapping `jax.random.bits(k, s)` through the
    mantissa trick the obfuscate kernel applies in-VMEM, so the fused path
    realizes the *same* Lambda^k.
    """
    m = jax.tree.leaves(grads)[0].shape[0]
    keys = jax.vmap(lambda a: agent_key(key, step, a))(jnp.arange(m))

    def bits_one_agent(k, grads_i):
        ks, leaves, treedef = leaf_keys(k, grads_i)
        return jax.tree.unflatten(
            treedef,
            [jax.random.bits(kk, g.shape, dtype=jnp.uint32)
             for kk, g in zip(ks, leaves)])

    return jax.vmap(bits_one_agent)(keys, grads)


def krng_seed(key: jax.Array, step: jax.Array) -> jax.Array:
    """The (2,) uint32 seed of the in-kernel Lambda draw at ``step``: the
    step index, then 32 bits from the step's Lambda key.  The kernels fold
    each tile's coordinates into these words (`kernels.obfuscate.
    tile_seed`), so Lambda is fresh at every iteration by construction."""
    bits = jax.random.bits(agent_key(jax.random.fold_in(key, 1), step, 0),
                           (), jnp.uint32)
    return jnp.stack([jnp.asarray(step).astype(jnp.uint32), bits])


def pdsgd_update(
    params: Pytree,
    grads: Pytree,
    *,
    key: jax.Array,
    step: jax.Array,
    W: jax.Array,
    support: jax.Array,
    lam_bar: jax.Array,
    mask: jax.Array | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    observe: bool = False,
    corrupt: jax.Array | None = None,
    corrupt_mode: str = "nan",
    corrupt_scale: float = 1e4,
    guard_clip: float = 1e3,
    kernel_layout: str = "concat",
    mesh=None,
    leaf_specs: Pytree | None = None,
    kernel_rng: bool | None = None,
    torus_shape: tuple[int, int] | None = None,
) -> Pytree:
    """One iteration of Eq. (4): x^{k+1} = W_k x^k - B^k Lambda^k g^k.

    ``W``/``support`` are THIS step's realized coupling matrix and its
    support (constants for a static topology, per-step realizations from
    `mixing.MixingProcess.realize` for a time-varying one); ``support``
    is what B^k is sampled on, so the descent term also rides only
    realized links.

    ``use_pallas=True`` routes the whole update through the fused Pallas
    kernels (`kernels.fused_pdsgd_tree`): one flattened pass, u never
    materialized per leaf.  Because the kernel consumes the same counter
    bits the eager path feeds `jax.random.uniform`, both paths realize the
    identical Lambda^k/B^k draw — `tests/test_fast_path.py` pins them to
    each other.  ``None`` defers to `kernels.default_use_pallas` (True on
    TPU, False under the CPU interpreter where fused is a correctness path).
    ``mask`` (the realized edge mask) makes the fused path re-derive W_k
    in VMEM (`kernels.masked_gossip_update`) instead of staging it.

    ``kernel_layout`` picks the fused path's buffer layout: ``"concat"``
    (default) is the single flattened (m, ΣD) pass; ``"leafwise"`` is
    `kernels.sharded_pdsgd_tree` — per-leaf kernels, bit-identical to
    concat, that keep FSDP/tensor-sharded leaves sharded (with ``mesh``
    + ``leaf_specs`` the obfuscate kernel's math is an XLA fusion on each
    sharded leaf and the gossip contraction a GSPMD einsum).  The leafwise
    layout refuses ``observe`` — capture is defined on the concatenated
    wire buffer.  ``kernel_rng`` (None defers to
    `kernels.default_kernel_rng`, i.e. on for real TPUs) moves the
    Lambda draw in-VMEM on the concat path: the HBM bits staging
    disappears and the kernel PRNG is seeded from the same per-step
    Lambda key.

    ``kernel_layout="ring"`` is the communication-overlap layout: the
    realized (W, B^k) are split into per-direction tables
    (`dist.collectives.directional_weights` / `rows_from_dense` — the
    coupling support must lie inside the ``torus_shape`` = (n_data,
    n_pod) torus adjacency, default (m, 1) single ring) and the whole
    Eq. (4) update runs through `kernels.ring_pdsgd_tree`: Lambda-draw,
    obfuscate and the staged per-direction v_ij exchange fused in one
    pallas_call with double-buffered VMEM staging.  ``mask`` is
    subsumed — a dropped edge arrives here as a zero entry of the
    realized W_k/B^k, so its table slot is zero and the kernel emits an
    exactly-zero v for it.  ``observe=True`` records the KERNEL's own
    staged wire stream (scattered to the dense v_ij layout), and
    ``corrupt`` is refused (the guarded fault path stays dense).

    ``observe=True`` additionally returns the auditor-grade observation
    record of `privacy.observe.full_record` — the wire tensor v_ij plus
    the private quantities adversary views are restrictions of — as
    ``(new_params, record)``.  Capture is a pure function of values the
    update already computes (the fused path emits the KERNEL's own x/u
    buffers, so a capture there audits what the kernel realized, not a
    re-derivation), which is what guarantees capture-on never perturbs
    the trajectory.

    ``corrupt`` (an (m,) 0/1 vector from `faults.FaultProcess.realize`)
    selects the fault-tolerant gossip: corrupt agents' transmit buffers
    are poisoned per ``corrupt_mode``/``corrupt_scale`` and every
    per-link contribution is finite-guarded + clipped to
    ``guard_clip`` at the receiver (`faults.inject.guarded_gossip_mix`
    eagerly, `kernels.guarded_gossip_update` fused).  Incompatible with
    ``observe`` — a poisoned wire is not an audited scenario.
    """
    if corrupt is not None and observe:
        raise ValueError("observation capture with corrupt links is not "
                         "an audited scenario")
    with tr.region(tr.STEP_MIX):
        B = sample_B(agent_key(jax.random.fold_in(key, 2), step, 0), support)
    if use_pallas is None:
        from ..kernels import default_use_pallas
        use_pallas = default_use_pallas()
    if kernel_layout not in ("concat", "leafwise", "ring"):
        raise ValueError(f"unknown kernel_layout {kernel_layout!r}")
    if use_pallas and kernel_layout == "ring":
        if corrupt is not None:
            raise ValueError(
                "kernel_layout='ring' does not carry corrupt-link "
                "injection; the guarded fault path stays dense")
        from ..dist import collectives as C
        from ..kernels import ring_pdsgd_tree, runtime
        m = jax.tree.leaves(params)[0].shape[0]
        n_data, n_pod = torus_shape if torus_shape is not None else (m, 1)
        if n_data * n_pod != m:
            raise ValueError(
                f"torus_shape {n_pod}x{n_data} does not hold m={m} agents")
        with tr.region(tr.STEP_MIX):
            tabs = C.directional_weights(W, n_data, n_pod)
            w_tab = jnp.concatenate([tabs["w_self"][:, None], tabs["w_dir"]],
                                    axis=1)
            b_rows = C.rows_from_dense(B, n_data, n_pod)
            perms = C.perm_stack(n_data, n_pod)
        bits = seed = None
        with tr.region(tr.OBFUSCATE):
            if runtime.resolve_kernel_rng(kernel_rng):
                seed = krng_seed(key, step)
            else:
                bits = _per_agent_bits(jax.random.fold_in(key, 1), step,
                                       grads)
        out = ring_pdsgd_tree(w_tab, b_rows, perms, params, grads, bits,
                              lam_bar, interpret=interpret, observe=observe,
                              kernel_rng=kernel_rng, seed=seed)
        if not observe:
            return out
        new_params, flats = out
        from ..privacy import observe as O
        # Scatter the kernel's sender-major staged stream to the dense
        # v_ij layout: V[i, j] = v[d, j] where perms[d][i, j] == 1.
        V = sum(perms[di][:, :, None] * flats["v"][di][None, :, :]
                for di in range(perms.shape[0]))
        record = O.full_record(
            v=V, support=support, x_flat=flats["x"], u_flat=flats["u"],
            g_flat=O.flatten_agents(grads), W=W, B=B)
        return new_params, record
    if use_pallas and kernel_layout == "leafwise":
        if observe:
            raise ValueError(
                "observation capture is defined on the concatenated wire "
                "buffer; kernel_layout='leafwise' does not support it")
        from ..kernels import sharded_pdsgd_tree
        with tr.region(tr.OBFUSCATE):
            bits = _per_agent_bits(jax.random.fold_in(key, 1), step, grads)
        return sharded_pdsgd_tree(W, B, params, grads, bits, lam_bar,
                                  mask=mask, interpret=interpret,
                                  corrupt=corrupt,
                                  corrupt_mode=corrupt_mode,
                                  corrupt_scale=corrupt_scale,
                                  guard_clip=guard_clip,
                                  mesh=mesh, leaf_specs=leaf_specs)
    if use_pallas:
        from ..kernels import fused_pdsgd_tree, runtime
        bits = seed = None
        with tr.region(tr.OBFUSCATE):
            if runtime.resolve_kernel_rng(kernel_rng):
                # seed the TPU PRNG from the same per-step Lambda key the
                # HBM bits would have been drawn from; no bits staging
                seed = krng_seed(key, step)
            else:
                bits = _per_agent_bits(jax.random.fold_in(key, 1), step,
                                       grads)
        out = fused_pdsgd_tree(W, B, params, grads, bits, lam_bar,
                               mask=mask, interpret=interpret,
                               observe=observe, corrupt=corrupt,
                               corrupt_mode=corrupt_mode,
                               corrupt_scale=corrupt_scale,
                               guard_clip=guard_clip,
                               kernel_rng=kernel_rng, seed=seed)
        if not observe:
            return out
        new_params, flats = out
        x_flat, u_flat = flats["x"], flats["u"]
    else:
        with tr.region(tr.OBFUSCATE):
            u = _per_agent_obfuscated(jax.random.fold_in(key, 1), step,
                                      grads, lam_bar)
        if corrupt is not None:
            from ..faults.inject import guarded_gossip_mix
            with tr.region(tr.GOSSIP):
                return guarded_gossip_mix(W, B, params, u, corrupt,
                                          mode=corrupt_mode,
                                          scale=corrupt_scale,
                                          clip=guard_clip)
        with tr.region(tr.GOSSIP):
            mixed = gossip_mix(W, params)
            descent = gossip_mix(B, u)
            new_params = jax.tree.map(lambda a, b: a - b, mixed, descent)
        if not observe:
            return new_params
        from ..privacy import observe as O
        x_flat, u_flat = O.flatten_agents(params), O.flatten_agents(u)
    from ..privacy import observe as O
    record = O.full_record(
        v=O.wire_messages(W, B, x_flat, u_flat), support=support,
        x_flat=x_flat, u_flat=u_flat, g_flat=O.flatten_agents(grads),
        W=W, B=B)
    return new_params, record


def dsgd_update(
    params: Pytree,
    grads: Pytree,
    *,
    W: jax.Array,
    lam: jax.Array,
) -> Pytree:
    """Conventional decentralized SGD [19]: x^{k+1} = W x^k - lam g^k."""
    with tr.region(tr.GOSSIP):
        mixed = gossip_mix(W, params)
    return jax.tree.map(lambda a, g: a - lam * g.astype(a.dtype), mixed, grads)


def dsgt_update(
    params: Pytree,
    tracker: Pytree,
    grads: Pytree,
    prev_grads: Pytree,
    *,
    W: jax.Array,
    lam: jax.Array,
) -> tuple[Pytree, Pytree]:
    """Gradient-tracking DSGT ([49],[50]; Pu & Nedić):

        x^{k+1} = W x^k − lam y^k
        y^{k+1} = W y^k + g^{k+1} − g^k

    Included as the communication baseline the paper positions against:
    DSGT must share BOTH x and the tracker y every iteration — 2× the
    message volume of PDSGD, which shares only the single mixed variable
    v_ij (see the Sec. I discussion and `benchmarks.run::comm_cost`).
    `make_decentralized_step(algorithm="dsgt")` runs this recursion inline
    with the tracker pair (y^{k-1}, g^{k-1}) carried in
    ``DecentralizedState.tracker`` (a phase-shifted but equivalent
    formulation — see the note in its dsgt branch).
    """
    new_params = jax.tree.map(
        lambda x, y: x - lam * y.astype(x.dtype),
        gossip_mix(W, params), tracker)
    new_tracker = jax.tree.map(
        lambda y, g, gp: y + g - gp,
        gossip_mix(W, tracker), grads, prev_grads)
    return new_params, new_tracker


def dp_dsgd_update(
    params: Pytree,
    grads: Pytree,
    *,
    key: jax.Array,
    W: jax.Array,
    lam: jax.Array,
    sigma_dp: float,
) -> Pytree:
    """Differential-privacy baseline: Gaussian noise added to the gradient
    before the conventional update (Table I of the paper)."""
    leaves, treedef = jax.tree.flatten(grads)
    with tr.region(tr.OBFUSCATE):
        keys = jax.random.split(key, len(leaves))
        noisy = [
            g + sigma_dp * jax.random.normal(k, g.shape, dtype=g.dtype)
            for k, g in zip(keys, leaves)
        ]
    return dsgd_update(params, jax.tree.unflatten(treedef, noisy), W=W, lam=lam)


def make_decentralized_step(
    loss_fn: Callable[[Pytree, Any], jax.Array],
    topology: Topology | MixingProcess,
    schedule: Schedule,
    algorithm: Algorithm = "pdsgd",
    sigma_dp: float = 0.0,
    donate: bool = True,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    track_mean: bool = False,
    force_host_schedule: bool = False,
    observer=None,
    grad_clip: float | None = None,
    faults=None,
    nan_policy: str = "off",
    aggregation: str = "gossip",
    trim: int = 1,
    spmd_axis_name=None,
    kernel_layout: str = "concat",
    mesh=None,
    leaf_specs=None,
    kernel_rng: bool | None = None,
):
    """Build a jitted decentralized training step.

    loss_fn(params_i, batch_i) -> scalar loss for ONE agent; it is vmapped
    over the agent axis.  Returns ``step(state, batch, key) -> (state, aux)``
    where batch leaves have a leading (m, ...) axis.

    ``topology`` is a static `Topology` OR a `mixing.MixingProcess`: the
    step realizes W_k on device from the traced ``state.step`` each
    iteration (a static topology/process folds to the same frozen-W
    constants as before, bit-identically).  Because the realization keys
    fold_in from the absolute step, the eager loop, `make_scanned_steps`,
    and a ``--resume`` replay all walk the same W_k sequence.

    The stepsize schedule is evaluated ON DEVICE from the traced
    ``state.step`` — the returned step performs zero per-iteration host
    syncs and composes with `make_scanned_steps` (the un-jitted traceable
    body is exposed as ``step.inner``).  Schedules that cannot trace (and
    ``force_host_schedule=True``, kept for benchmarking the seed behavior)
    fall back to the old host round-trip, in which case ``step.inner`` is
    ``None``.

    ``use_pallas``/``interpret`` select the fused-kernel PDSGD path (see
    `pdsgd_update`); ``track_mean`` adds the agent-mean parameters to aux
    (what rate tests integrate — cheap for small models, off by default).

    ``observer`` (a `privacy.observe.Adversary`) turns on traced wire-tap
    capture: ``aux["observation"]`` carries that adversary's view of this
    step's messages (pdsgd: the v_ij tensor; dsgd/dp_dsgd: the broadcast
    states) as ordinary device arrays — under `make_scanned_steps` the
    scan stacks them into a (unroll_k, ...) observation buffer for free.
    Capture never changes the update (bit-parity pinned by
    tests/test_privacy_audit.py); dsgt is refused (its two-variable wire
    is not an audited scenario).

    ``grad_clip`` (kappa > 0) clips every gradient element to [-kappa,
    kappa] BEFORE the update and the capture — enforcing the bounded-
    gradient premise |g| <= kappa under which Theorem 5's uniform
    analysis states its entropy/MSE guarantees (`privacy.clip_gradients`).

    ``faults`` (a `faults.FaultProcess`) makes agent failure part of the
    traced step: the coupling is composed per step through
    `faults.realize_coupling` (every realized W_k doubly stochastic over
    the survivors), down agents hold their state frozen via traced
    ``jnp.where``, markov-rejoin agents optionally warm start from their
    stable neighbors (``rejoin='neighbor-avg'``), and corrupt transmits
    are neutralized by the per-link finite guard.  An inert process
    (all rates 0) is normalized to no-faults, so the rate-0 trajectory
    is byte-for-byte the fault-free code path.  pdsgd only.

    ``nan_policy`` adds traced isfinite sentinels on loss and updated
    params: ``"warn"`` only counts (``aux["fault_nonfinite"]``),
    ``"skip"`` additionally holds the pre-update state on a non-finite
    step — ``jnp.where(finite, new, old)`` is bitwise ``new`` when
    finite, so sentinels-on at fault rate 0 stays bit-identical.

    ``aggregation="trimmed_mean"`` swaps the W-gossip for coordinate-
    wise trimmed-mean robust aggregation over neighbor states
    (`faults.inject.trimmed_mean_mix`) with self-applied obfuscated
    descent; tolerates up to ``trim`` byzantine neighbors per agent but
    broadcasts raw states (see the privacy caveat there) — refused with
    ``observer``.

    Sharded big-model mode (`launch.steps.make_train_step(sharded=True)`
    sets these): ``spmd_axis_name`` names the mesh axis the agent vmap is
    sharded over (``jax.vmap(..., spmd_axis_name=...)``), so the logical
    constraints the model emits inside the per-agent loss compose with
    the agent axis; ``kernel_layout``/``mesh``/``leaf_specs``/
    ``kernel_rng`` pass through to `pdsgd_update` (leafwise kernels over
    sharded pytrees).  All default to the dense behavior — with the
    defaults this function is byte-for-byte the previous step builder.
    """
    if algorithm not in ("pdsgd", "dsgd", "dsgt", "dp_dsgd"):
        raise ValueError(f"unknown algorithm {algorithm!r}")
    if observer is not None and algorithm == "dsgt":
        raise ValueError("observation capture supports pdsgd/dsgd/dp_dsgd; "
                         "dsgt's two-variable exchange is not audited")
    if grad_clip is not None and not grad_clip > 0.0:
        raise ValueError(f"grad_clip must be > 0, got {grad_clip}")
    if nan_policy not in ("off", "warn", "skip"):
        raise ValueError(f"unknown nan_policy {nan_policy!r}; "
                         f"have ('off', 'warn', 'skip')")
    if aggregation not in ("gossip", "trimmed_mean"):
        raise ValueError(f"unknown aggregation {aggregation!r}; "
                         f"have ('gossip', 'trimmed_mean')")
    process = as_process(topology)
    if faults is not None and faults.is_inert:
        faults = None  # the rate-0 path IS the fault-free path
    if faults is not None:
        if algorithm != "pdsgd":
            raise ValueError(
                "fault injection composes with the paper's pdsgd update; "
                f"algorithm={algorithm!r} is not a fault scenario")
        if faults.num_agents != process.num_agents:
            raise ValueError(
                f"faults built for {faults.num_agents} agents but the "
                f"topology has {process.num_agents}")
        if observer is not None and faults.has_corruption:
            raise ValueError("observation capture with corrupt links is "
                             "not an audited scenario")
    if aggregation == "trimmed_mean":
        if algorithm != "pdsgd":
            raise ValueError("aggregation='trimmed_mean' is a pdsgd mode")
        if observer is not None:
            raise ValueError(
                "trimmed-mean aggregation broadcasts raw neighbor states "
                "(conventional-DSGD wire); capture of it is not an "
                "audited scenario")
        m_ = process.num_agents
        if not (1 <= trim and m_ - 2 * trim >= 1):
            raise ValueError(
                f"trim must satisfy 1 <= trim and m - 2*trim >= 1; "
                f"got trim={trim}, m={m_}")

    if kernel_layout == "leafwise" and observer is not None:
        raise ValueError("observation capture is defined on the "
                         "concatenated wire buffer; kernel_layout="
                         "'leafwise' does not support it")
    grad_fn = jax.vmap(jax.value_and_grad(loss_fn),
                       spmd_axis_name=spmd_axis_name)
    num_agents = process.num_agents

    def _rowwise(vec):
        """where-select rows of (m, ...)-leading leaves by an (m,) 0/1."""
        def f(new, old):
            c = vec.reshape(vec.shape + (1,) * (new.ndim - 1))
            return jnp.where(c > 0, new, old)
        return f

    def apply_update(state, batch, key, lam_bar):
        alive = corrupt = rejoin = None
        with tr.region(tr.STEP_MIX):
            if faults is None:
                W, support, mask = process.realize(state.step)
            else:
                from ..faults import realize_coupling
                W, support, mask, alive, corrupt = realize_coupling(
                    process, faults, state.step)
            # `held` is this step's hold/rollback anchor: the pre-update
            # state, with rejoining agents already warm started — what
            # down agents freeze to and what a skipped non-finite step
            # reverts to.
            held = state.params
            if (faults is not None and faults.has_crash
                    and not faults.is_failstop):
                prev = jnp.where(
                    state.step > 0,
                    faults.alive_at(jnp.maximum(state.step - 1, 0)),
                    jnp.ones_like(alive))
                rejoin = alive * (1.0 - prev)
                if faults.rejoin == "neighbor-avg":
                    from ..faults.inject import neighbor_avg_warmstart
                    held, _ = neighbor_avg_warmstart(state.params, mask,
                                                     alive, prev)
        with tr.region(tr.STEP_MODEL):
            losses, grads = grad_fn(held, batch)
        with tr.region(tr.STEP_UPDATE):
            new_params, new_tracker, observation = _update(
                state, held, grads, key, lam_bar, W, support, mask, alive,
                corrupt)
        with tr.region(tr.STEP_REPORT):
            return _report(state, held, losses, new_params, new_tracker,
                           observation, alive, corrupt, rejoin)

    def _update(state, held, grads, key, lam_bar, W, support, mask, alive,
                corrupt):
        """The update of `apply_update`: (new params, new tracker,
        observation or None)."""
        if grad_clip is not None:
            from .privacy import clip_gradients
            grads = clip_gradients(grads, grad_clip)
        new_tracker = state.tracker
        observation = None
        if algorithm == "pdsgd":
            if aggregation == "trimmed_mean":
                from ..faults.inject import trimmed_mean_mix
                with tr.region(tr.OBFUSCATE):
                    u = _per_agent_obfuscated(jax.random.fold_in(key, 1),
                                              state.step, grads, lam_bar)
                cz = (corrupt if corrupt is not None
                      else jnp.zeros((num_agents,), jnp.float32))
                with tr.region(tr.GOSSIP):
                    new_params = trimmed_mean_mix(
                        held, u, support, cz, trim=trim,
                        mode=(faults.corrupt_mode if faults is not None
                              else "nan"),
                        scale=(faults.corrupt_scale if faults is not None
                               else 1e4))
            else:
                corrupting = faults is not None and faults.has_corruption
                out = pdsgd_update(
                    held, grads, key=key, step=state.step, W=W,
                    support=support, lam_bar=lam_bar, mask=mask,
                    use_pallas=use_pallas, interpret=interpret,
                    observe=observer is not None,
                    corrupt=corrupt if corrupting else None,
                    corrupt_mode=(faults.corrupt_mode if corrupting
                                  else "nan"),
                    corrupt_scale=(faults.corrupt_scale if corrupting
                                   else 1e4),
                    guard_clip=(faults.guard_clip if corrupting else 1e3),
                    kernel_layout=kernel_layout, mesh=mesh,
                    leaf_specs=leaf_specs, kernel_rng=kernel_rng)
                if observer is not None:
                    new_params, record = out
                    from ..privacy import observe as O
                    observation = O.adversary_view(observer, record)
                else:
                    new_params = out
        elif algorithm == "dsgd":
            new_params = dsgd_update(held, grads, W=W, lam=lam_bar)
        elif algorithm == "dsgt":
            if state.tracker is None:
                raise ValueError(
                    "algorithm='dsgt' carries (y, prev_grads) in "
                    "state.tracker; build the state with "
                    "init_state(params, m, algorithm='dsgt')")
            # y^k = W y^{k-1} + g^k - g^{k-1}  (y^{-1} = g^{-1} = 0, so the
            # first tracker is exactly g^0); x^{k+1} = W x^k - lam y^k.
            # NOTE the tracker convention is phase-shifted vs `dsgt_update`:
            # state.tracker holds (y^{k-1}, g^{k-1}) and params advance with
            # the FRESH y^k, whereas dsgt_update takes y^k and advances
            # params with it before producing y^{k+1}.  Don't swap one for
            # the other without re-deriving the phase.
            y_prev, g_prev = state.tracker
            with tr.region(tr.GOSSIP):
                mixed_y = gossip_mix(W, y_prev)
            y = jax.tree.map(lambda t, g, gp: t + g - gp,
                             mixed_y, grads, g_prev)
            with tr.region(tr.GOSSIP):
                mixed = gossip_mix(W, held)
            new_params = jax.tree.map(
                lambda a, t: a - lam_bar * t.astype(a.dtype), mixed, y)
            new_tracker = (y, grads)
        elif algorithm == "dp_dsgd":
            new_params = dp_dsgd_update(
                held, grads, key=jax.random.fold_in(key, 3), W=W,
                lam=lam_bar, sigma_dp=sigma_dp)
        else:
            raise ValueError(f"unknown algorithm {algorithm!r}")
        if observer is not None and algorithm in ("dsgd", "dp_dsgd"):
            # State-sharing baselines: the wire carries x_j in the clear
            # (dp_dsgd noises the GRADIENT, not the transmitted state).
            from ..privacy import observe as O
            record = O.state_record(
                support=support, x_flat=O.flatten_agents(held),
                g_flat=O.flatten_agents(grads), W=W, lam=lam_bar)
            observation = O.adversary_view(observer, record)
        # Degradation: down agents neither transmit (the composed W/B
        # already guarantee that) nor update — their rows freeze to the
        # held state.  Applied BEFORE the sentinels so a frozen agent
        # can't be dragged backward by somebody else's non-finite step.
        if alive is not None:
            new_params = jax.tree.map(_rowwise(alive), new_params, held)
        return new_params, new_tracker, observation

    def _report(state, held, losses, new_params, new_tracker, observation,
                alive, corrupt, rejoin):
        """The sentinels and the aux of `apply_update`: (new state, aux)."""
        nonfinite = None
        if nan_policy != "off":
            finite = jnp.isfinite(losses).all()
            for leaf in jax.tree.leaves(new_params):
                finite &= jnp.isfinite(leaf).all()
            if new_tracker is not None:
                for leaf in jax.tree.leaves(new_tracker):
                    finite &= jnp.isfinite(leaf).all()
            nonfinite = (~finite).astype(jnp.int32)
            if nan_policy == "skip":
                # skip-and-hold: a non-finite step advances the counter
                # but leaves the state at the held anchor.  where(True,
                # new, old) is bitwise `new`, so this is exact identity
                # on every finite step.
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_params, held)
                if new_tracker is not None:
                    new_tracker = jax.tree.map(
                        lambda n, o: jnp.where(finite, n, o), new_tracker,
                        state.tracker)
        aux = {
            "loss": losses.mean(),
            # the stored values' spread, summed in float32, on a mesh (the
            # one place a chip has shown the compiler handing the report
            # an update before its rounding); every other path keeps its
            # former sum until the one-chip cell's check numbers may move
            # (ROADMAP D12)
            "consensus_error": consensus_error(new_params,
                                               stored=mesh is not None),
        }
        if alive is not None:
            aux["fault_down"] = (
                jnp.float32(num_agents) - alive.sum()).astype(jnp.int32)
            aux["fault_corrupt"] = corrupt.sum().astype(jnp.int32)
            aux["fault_rejoin"] = (
                rejoin.sum().astype(jnp.int32) if rejoin is not None
                else jnp.zeros((), jnp.int32))
        if nonfinite is not None:
            aux["fault_nonfinite"] = nonfinite
        if observation is not None:
            aux["observation"] = observation
        if track_mean:
            aux["params_mean"] = jax.tree.map(lambda p: p.mean(axis=0),
                                              new_params)
        return DecentralizedState(params=new_params, step=state.step + 1,
                                  tracker=new_tracker), aux

    def step_fn(state: DecentralizedState, batch, key: jax.Array):
        with tr.region(tr.STEP_UPDATE):
            lam_bar = jnp.asarray(
                schedule(state.step.astype(jnp.float32), 0),
                dtype=jnp.float32)
        return apply_update(state, batch, key, lam_bar)

    device_schedule = not force_host_schedule
    if device_schedule:
        try:
            jax.eval_shape(lambda s: schedule(s, 0),
                           jax.ShapeDtypeStruct((), jnp.float32))
        except Exception as e:
            # Deliberate feature-probe fallback — but never a silent one:
            # the host path costs a device->host sync every iteration.
            import warnings
            warnings.warn(
                f"schedule {getattr(schedule, 'name', schedule)!r} is not "
                f"device-traceable ({type(e).__name__}: {e}); falling back "
                "to the per-step host-sync path (10-30x slower hot loop, "
                "and make_scanned_steps will refuse this step)")
            device_schedule = False

    if device_schedule:
        jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())

        def step(state: DecentralizedState, batch, key: jax.Array):
            return jitted(state, batch, key)

        step.inner = step_fn
        return step

    # Legacy host path: one device->host sync per iteration to evaluate the
    # schedule in numpy.  Only reachable for non-traceable schedules or the
    # explicit benchmark baseline.
    jitted_host = jax.jit(apply_update, donate_argnums=(0,) if donate else ())

    def step(state: DecentralizedState, batch, key: jax.Array):
        lam_bar = jnp.asarray(
            schedule(np.asarray(int(state.step)), 0), dtype=jnp.float32)
        return jitted_host(state, batch, key, lam_bar)

    step.inner = None
    return step


def make_scanned_steps(step_fn, unroll_k: int, donate: bool = True):
    """Fuse ``unroll_k`` training iterations into one `jax.lax.scan`.

    Dispatch-bound small-model workloads (the paper's d=2 estimation
    problem) pay ~a millisecond of Python/dispatch per step in the eager
    loop; scanning k steps amortizes that to one dispatch per k.

    ``step_fn`` is a step from `make_decentralized_step` (its traceable
    ``.inner`` is used) or any pure ``(state, batch, key) -> (state, aux)``.
    Returns ``scanned(state, batches, keys) -> (state, aux_stacked)`` where
    every ``batches`` leaf gains a leading (unroll_k, ...) axis (``None``
    broadcasts for batchless objectives) and ``keys`` is a (unroll_k,) key
    array, e.g. from `jax.random.split`.
    """
    inner = getattr(step_fn, "inner", step_fn)
    if inner is None:
        raise ValueError(
            "step_fn evaluates its schedule on host (non-traceable); "
            "make_scanned_steps requires a device-resident step")

    def body(state, xs):
        batch, key = xs
        return inner(state, batch, key)

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def scanned(state: DecentralizedState, batches, keys: jax.Array):
        return jax.lax.scan(body, state, (batches, keys), length=unroll_k)

    return scanned


def init_state(params: Pytree, m: int,
               algorithm: Algorithm = "pdsgd") -> DecentralizedState:
    """Replicate params to m agents; ``algorithm`` sizes the extra state
    (dsgt needs a zero tracker pair, everything else carries None)."""
    replicated = replicate_params(params, m)
    tracker = None
    if algorithm == "dsgt":
        # Two independent zero trees: aliasing one buffer into both slots
        # would make the jitted step donate the same buffer twice.
        tracker = (jax.tree.map(jnp.zeros_like, replicated),
                   jax.tree.map(jnp.zeros_like, replicated))
    return DecentralizedState(params=replicated,
                              step=jnp.asarray(0, dtype=jnp.int32),
                              tracker=tracker)
