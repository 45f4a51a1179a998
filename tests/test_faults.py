"""The fault-injection subsystem (`repro.faults`).

Four contracts pinned here:

1. **Assumption 2 per realization** — every W_k composed through
   `realize_coupling` (any crash draw, markov or failstop) is doubly
   stochastic and symmetric with w_ii > 0; a dead agent's row collapses
   to e_i; corrupt is always a subset of alive; and the realization is
   random access in the absolute step (resume/scan/eager agree).
2. **Rate-0 bit-identity** — an inert FaultProcess and sentinels-on at
   fault rate 0 walk byte-for-byte the fault-free trajectory on the
   eager, fused-Pallas, and scanned paths.
3. **Degradation & healing** — the per-link finite guard neutralizes
   poisoned transmits (eager twin == Pallas kernel), trimmed-mean
   out-votes large-but-finite byzantine senders, neighbor-avg warm
   start heals rejoiners (and `audit` quantifies what that broadcast
   leaks), nan-sentinels count and skip-and-hold keeps state finite
   under raw unguarded chaos.
4. **Convergence under faults** — the paper's quadratic still reaches
   the no-fault floor under markov crash-restart churn.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (init_state, make_decentralized_step, make_mixing,
                        make_scanned_steps, make_topology)
from repro.core import mixing as MX
from repro.core import schedules as S
from repro.core.topology import erdos_renyi, metropolis_weights
from repro.faults import (FaultProcess, finite_guard, guarded_gossip_mix,
                          make_faults, neighbor_avg_warmstart,
                          poison_transmit, realize_coupling,
                          rejoin_leakage_report, trimmed_mean_mix)
from repro.launch.steps import per_step_keys


def _step_i32(k):
    return jnp.asarray(k, jnp.int32)


def _check_doubly_stochastic(Wn):
    m = Wn.shape[0]
    assert np.allclose(Wn.sum(0), 1.0, atol=1e-6)
    assert np.allclose(Wn.sum(1), 1.0, atol=1e-6)
    assert np.all(np.diag(Wn) > 0)
    assert np.allclose(Wn, Wn.T, atol=1e-7)


# -- 1. Assumption 2 per realization ------------------------------------

@pytest.mark.parametrize("restart_rate", [0.5, 0.0],
                         ids=["markov", "failstop"])
def test_coupled_realizations_doubly_stochastic(restart_rate):
    m = 8
    proc = make_mixing(make_topology("erdos", m, p=0.6, seed=1), rate=0.2,
                       seed=1)
    faults = make_faults(m, crash_rate=0.3, restart_rate=restart_rate,
                         seed=4)
    for k in (0, 1, 7, 40):
        W, support, mask, alive, corrupt = realize_coupling(
            proc, faults, _step_i32(k))
        Wn, a = np.asarray(W), np.asarray(alive)
        _check_doubly_stochastic(Wn)
        # support is mask + I, and exactly where W is nonzero
        np.testing.assert_array_equal(np.asarray(support),
                                      np.asarray(mask) + np.eye(m))
        assert np.array_equal(np.asarray(support) > 0, Wn > 0)
        # a dead agent mixes with nobody: its row is exactly e_i
        for i in np.nonzero(a == 0)[0]:
            e = np.zeros(m); e[i] = 1.0
            np.testing.assert_array_equal(Wn[i], e)
            np.testing.assert_array_equal(Wn[:, i], e)
        assert np.all(np.asarray(corrupt) <= a)  # dead agents transmit nothing


def test_failstop_agents_never_resurrect():
    faults = make_faults(6, crash_rate=0.2, seed=0)
    assert faults.is_failstop
    alive = np.stack([np.asarray(faults.alive_at(_step_i32(k)))
                      for k in range(40)])
    assert np.all(np.diff(alive, axis=0) <= 0)  # monotone down
    assert alive.sum() < alive.size  # somebody actually died in 40 steps


def test_markov_agents_crash_and_rejoin():
    faults = make_faults(6, crash_rate=0.2, restart_rate=0.5, seed=2)
    alive = np.stack([np.asarray(faults.alive_at(_step_i32(k)))
                      for k in range(60)])
    assert np.any(alive == 0)                   # outages happen
    assert np.any(np.diff(alive, axis=0) > 0)   # and end (down -> up)
    rejoin = np.stack([np.asarray(faults.rejoin_mask(_step_i32(k)))
                       for k in range(60)])
    np.testing.assert_array_equal(rejoin[0], np.zeros(6))  # nobody at k=0
    want = alive[1:] * (1.0 - alive[:-1])
    np.testing.assert_array_equal(rejoin[1:], want)


def test_realization_is_random_access():
    """realize(k) folds in from the absolute step: evaluation order and
    history are irrelevant — the resume/scan/eager agreement contract."""
    faults = make_faults(5, crash_rate=0.2, restart_rate=0.4,
                         corrupt_rate=0.3, seed=7)
    forward = [jax.tree.map(np.asarray, faults.realize(_step_i32(k)))
               for k in range(20)]
    faults2 = make_faults(5, crash_rate=0.2, restart_rate=0.4,
                          corrupt_rate=0.3, seed=7)
    for k in reversed(range(20)):  # fresh process, backwards
        a, c = faults2.realize(_step_i32(k))
        np.testing.assert_array_equal(np.asarray(a), forward[k][0])
        np.testing.assert_array_equal(np.asarray(c), forward[k][1])


def test_validation_refuses_stray_knobs():
    with pytest.raises(ValueError, match="crash-mode knob"):
        FaultProcess(num_agents=4, restart_rate=0.5)
    with pytest.raises(ValueError, match="crash-restart"):
        FaultProcess(num_agents=4, crash_rate=0.1, rejoin="neighbor-avg")
    with pytest.raises(ValueError, match="corruption knobs"):
        FaultProcess(num_agents=4, corrupt_mode="inf")
    with pytest.raises(ValueError, match="guard_clip"):
        FaultProcess(num_agents=4, corrupt_rate=0.1, guard_clip=0.0)
    with pytest.raises(ValueError, match="unknown rejoin"):
        make_faults(4, crash_rate=0.1, restart_rate=0.5, rejoin="teleport")
    # make_faults normalizes inert knobs instead of tripping validation
    assert make_faults(4).is_inert
    assert make_faults(4, corrupt_mode="inf").fingerprint() == \
        make_faults(4).fingerprint()


def test_fingerprint_normalizes_inert_knobs():
    a = make_faults(4, crash_rate=0.1, seed=3, rejoin="hold")
    b = make_faults(4, crash_rate=0.1, seed=3, max_outage=99)
    # failstop: max_outage drives nothing, fingerprints agree
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != make_faults(4, crash_rate=0.1,
                                          seed=4).fingerprint()
    fp = make_faults(4, corrupt_rate=0.2, guard_clip=None).fingerprint()
    assert fp["guard_clip"] == "off"  # JSON-stable, not null-ambiguous


def test_step_builder_refuses_bad_fault_combos():
    top = make_topology("ring", 4)
    loss = lambda p, b: jnp.sum(p ** 2)
    active = make_faults(4, crash_rate=0.1)
    with pytest.raises(ValueError, match="not a fault scenario"):
        make_decentralized_step(loss, top, S.harmonic(0.1),
                                algorithm="dsgd", faults=active)
    with pytest.raises(ValueError, match="4 agents"):
        make_decentralized_step(loss, make_topology("ring", 5),
                                S.harmonic(0.1), faults=active)
    from repro.privacy import observe as O
    with pytest.raises(ValueError, match="corrupt links"):
        make_decentralized_step(loss, top, S.harmonic(0.1),
                                observer=O.auditor(),
                                faults=make_faults(4, corrupt_rate=0.2))
    with pytest.raises(ValueError, match="trimmed-mean|raw neighbor"):
        make_decentralized_step(loss, top, S.harmonic(0.1),
                                observer=O.auditor(),
                                aggregation="trimmed_mean")
    with pytest.raises(ValueError, match="nan_policy"):
        make_decentralized_step(loss, top, S.harmonic(0.1),
                                nan_policy="panic")


def test_build_faults_cli_wiring():
    from repro.launch.train import build_faults, build_parser
    base = ["--arch", "stablelm-3b-smoke", "--agents", "4", "--steps", "2"]
    assert build_faults(build_parser().parse_args(base)) is None
    args = build_parser().parse_args(
        base + ["--fault-crash-rate", "0.1", "--fault-restart-rate", "0.5",
                "--fault-guard-clip", "0", "--seed", "11"])
    f = build_faults(args)
    assert f is not None and f.guard_clip is None
    assert f.seed == 11  # --fault-seed defaults to --seed


# -- 2. rate-0 bit-identity ---------------------------------------------

def _quadratic(m=5, d=3):
    top = make_topology("paper_fig1", m)
    rng = np.random.default_rng(0)
    batch = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))

    def loss(p, b):
        return jnp.sum((p - b) ** 2)

    return top, loss, batch, d


@pytest.mark.parametrize("use_pallas", [False, True])
def test_inert_faults_and_sentinels_bit_identical(use_pallas):
    """faults=<inert> + nan_policy='skip' is byte-for-byte the plain
    trajectory: where(finite, new, old) is bitwise `new` on finite
    steps, and an inert process is normalized to faults=None."""
    top, loss, batch, d = _quadratic()
    kw = dict(use_pallas=use_pallas, donate=False)
    plain = make_decentralized_step(loss, top, S.harmonic(0.2), **kw)
    fault = make_decentralized_step(loss, top, S.harmonic(0.2),
                                    faults=make_faults(top.num_agents),
                                    nan_policy="skip", **kw)
    a = init_state(jnp.zeros((d,)), top.num_agents)
    b = init_state(jnp.zeros((d,)), top.num_agents)
    for i in range(8):
        key = jax.random.key(i)
        a, _ = plain(a, batch, key)
        b, aux = fault(b, batch, key)
    np.testing.assert_array_equal(np.asarray(a.params), np.asarray(b.params))
    assert int(aux["fault_nonfinite"]) == 0
    assert "fault_down" not in aux  # inert process really became None


def test_inert_faults_bit_identical_scanned():
    top, loss, batch, d = _quadratic()
    n = 8
    keys = per_step_keys(jax.random.key(4), start_step=0, n=n)
    batches = jnp.broadcast_to(batch[None], (n,) + batch.shape)

    def run(**kw):
        step = make_decentralized_step(loss, top, S.harmonic(0.2), **kw)
        scanned = make_scanned_steps(step, n)
        state, _ = scanned(init_state(jnp.zeros((d,)), top.num_agents),
                           batches, keys)
        return np.asarray(jax.tree.leaves(state.params)[0])

    np.testing.assert_array_equal(
        run(), run(faults=make_faults(top.num_agents), nan_policy="skip"))


# -- crash faults: path agreement ---------------------------------------

def _crash_setup():
    top, loss, batch, d = _quadratic()
    proc = make_mixing(top, rate=0.2, seed=2)
    faults = make_faults(top.num_agents, crash_rate=0.2, restart_rate=0.5,
                         seed=5)
    return top, loss, batch, d, proc, faults


def test_crash_faults_eager_matches_fused():
    top, loss, batch, d, proc, faults = _crash_setup()
    kw = dict(faults=faults, nan_policy="warn", donate=False)
    step_e = make_decentralized_step(loss, proc, S.harmonic(0.2),
                                     use_pallas=False, **kw)
    step_f = make_decentralized_step(loss, proc, S.harmonic(0.2),
                                     use_pallas=True, **kw)
    a = init_state(jnp.zeros((d,)), top.num_agents)
    b = init_state(jnp.zeros((d,)), top.num_agents)
    downs = 0
    for i in range(10):
        key = jax.random.key(i)
        a, aux_a = step_e(a, batch, key)
        b, aux_b = step_f(b, batch, key)
        assert int(aux_a["fault_down"]) == int(aux_b["fault_down"])
        downs += int(aux_a["fault_down"])
    assert downs > 0  # the scenario actually exercised an outage
    np.testing.assert_allclose(np.asarray(a.params), np.asarray(b.params),
                               rtol=1e-6, atol=1e-6)


def test_crash_faults_eager_matches_scanned_bitwise():
    top, loss, batch, d, proc, faults = _crash_setup()
    n = 10
    keys = per_step_keys(jax.random.key(9), start_step=0, n=n)
    batches = jnp.broadcast_to(batch[None], (n,) + batch.shape)
    step = make_decentralized_step(loss, proc, S.harmonic(0.2),
                                   faults=faults, nan_policy="skip",
                                   donate=False)
    state_e = init_state(jnp.zeros((d,)), top.num_agents)
    e_down = []
    for i in range(n):
        state_e, aux = step(state_e, batches[i], keys[i])
        e_down.append(int(aux["fault_down"]))
    scanned = make_scanned_steps(step, n)
    state_s, aux_s = scanned(init_state(jnp.zeros((d,)), top.num_agents),
                             batches, keys)
    np.testing.assert_array_equal(np.asarray(state_e.params),
                                  np.asarray(state_s.params))
    np.testing.assert_array_equal(np.asarray(aux_s["fault_down"]),
                                  np.asarray(e_down, np.int32))


def test_down_agents_hold_their_state():
    """A down agent's row is frozen to the held anchor — bitwise."""
    top, loss, batch, d, proc, faults = _crash_setup()
    step = make_decentralized_step(loss, proc, S.harmonic(0.2),
                                   faults=faults, donate=False)
    state = init_state(jnp.zeros((d,)), top.num_agents)
    froze = 0
    for i in range(12):
        alive = np.asarray(faults.alive_at(_step_i32(i)))
        before = np.asarray(state.params)
        state, _ = step(state, batch, jax.random.key(i))
        after = np.asarray(state.params)
        for a_i in np.nonzero(alive == 0)[0]:
            np.testing.assert_array_equal(after[a_i], before[a_i])
            froze += 1
    assert froze > 0


# -- 3. degradation & healing mechanics ---------------------------------

def test_finite_guard_zeroes_nonfinite_and_clips():
    v = jnp.asarray([1.0, -5.0, jnp.nan, jnp.inf, -jnp.inf, 2e4])
    out = np.asarray(finite_guard(v, 1e3))
    np.testing.assert_array_equal(out, [1.0, -5.0, 0.0, 0.0, 0.0, 1e3])


@pytest.mark.parametrize("mode,scale", [("nan", 1e4), ("inf", 1e4),
                                        ("scale", 123.0)])
def test_poison_transmit_modes(mode, scale):
    x = jnp.ones((4, 3))
    corrupt = jnp.asarray([0.0, 1.0, 0.0, 1.0])
    out = np.asarray(poison_transmit(x, corrupt, mode, scale))
    np.testing.assert_array_equal(out[0], np.ones(3))
    np.testing.assert_array_equal(out[2], np.ones(3))
    if mode == "nan":
        assert np.all(np.isnan(out[1])) and np.all(np.isnan(out[3]))
    elif mode == "inf":
        assert np.all(np.isposinf(out[1]))
    else:
        np.testing.assert_array_equal(out[1], np.full(3, scale))


def _guard_fixture(m=8, n=256, seed=0):
    rng = np.random.default_rng(seed)
    adj = erdos_renyi(m, p=0.6, seed=seed)
    mask = jnp.asarray((adj & ~np.eye(m, dtype=bool)).astype(np.float32))
    W = MX.metropolis_from_mask(mask)
    B = jnp.asarray(rng.dirichlet(np.ones(m), m).T.astype(np.float32))
    X = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    U = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    corrupt = jnp.asarray([1.0, 0.0, 0.0, 1.0, 0.0, 0.0, 0.0, 0.0])
    return mask, W, B, X, U, corrupt


@pytest.mark.parametrize("mode,clip", [("nan", 1e3), ("inf", 1e3),
                                       ("scale", 1e3), ("scale", None)])
def test_guarded_kernel_matches_eager_guarded_mix(mode, clip):
    from repro.kernels import guarded_gossip_update
    mask, W, B, X, U, corrupt = _guard_fixture()
    XT = poison_transmit(X, corrupt, mode, 50.0)
    UT = poison_transmit(U, corrupt, mode, 50.0)
    out_k = guarded_gossip_update(mask, B, X, U, XT, UT, clip)
    out_e = guarded_gossip_mix(W, B, X, U, corrupt, mode=mode, scale=50.0,
                               clip=clip)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_e),
                               rtol=1e-5, atol=1e-5)


def test_guard_neutralizes_nan_senders_unguarded_does_not():
    mask, W, B, X, U, corrupt = _guard_fixture()
    guarded = np.asarray(guarded_gossip_mix(W, B, X, U, corrupt,
                                            mode="nan", scale=1e4, clip=1e3))
    assert np.all(np.isfinite(guarded))
    # corrupt senders' own rows use clean self terms but receive nothing
    # extra — they stay finite too; the guard is per incoming link.
    raw = np.asarray(guarded_gossip_mix(W, B, X, U, corrupt,
                                        mode="nan", scale=1e4, clip=None))
    assert np.any(~np.isfinite(raw))  # poison reaches unguarded receivers


def test_trimmed_mean_outvotes_finite_byzantine():
    """A large-but-finite scaled sender slips past the finite guard but
    is dropped by the coordinate-wise trim."""
    m, d = 6, 4
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    u = jnp.zeros((m, d), jnp.float32)
    support = jnp.ones((m, m), jnp.float32)  # complete graph
    corrupt = jnp.asarray([1.0, 0.0, 0.0, 0.0, 0.0, 0.0])
    out = np.asarray(trimmed_mean_mix(x, u, support, corrupt,
                                      trim=1, mode="scale", scale=1e6))
    assert np.all(np.isfinite(out))
    assert np.max(np.abs(out)) < 10.0  # the 1e6-scaled row was trimmed out
    # honest receivers stay within the clean candidates' range
    lo, hi = np.asarray(x).min(), np.asarray(x).max()
    assert out[1:].min() >= lo - 1e-6 and out[1:].max() <= hi + 1e-6


def test_trimmed_mean_refuses_bad_trim():
    x = jnp.zeros((4, 2))
    with pytest.raises(ValueError, match="trim"):
        trimmed_mean_mix(x, x, jnp.ones((4, 4)), jnp.zeros((4,)),
                         trim=2, mode="nan", scale=1e4)


def test_neighbor_avg_warmstart_heals_rejoiner():
    m, d = 4, 3
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    ring = make_topology("ring", m)
    mask = jnp.asarray(
        (np.asarray(ring.adjacency) & ~np.eye(m, dtype=bool)).astype(
            np.float32))
    alive = jnp.ones((m,), jnp.float32)
    prev = jnp.asarray([1.0, 0.0, 1.0, 1.0])  # agent 1 rejoins
    healed, rejoin = neighbor_avg_warmstart(x, mask, alive, prev)
    np.testing.assert_array_equal(np.asarray(rejoin), [0.0, 1.0, 0.0, 0.0])
    want = (np.asarray(x)[0] + np.asarray(x)[2]) / 2.0  # ring nbrs of 1
    np.testing.assert_allclose(np.asarray(healed)[1], want, rtol=1e-6)
    for i in (0, 2, 3):  # stable agents untouched, bitwise
        np.testing.assert_array_equal(np.asarray(healed)[i],
                                      np.asarray(x)[i])
    # no stable neighbor -> hold: cut agent 1's links
    healed2, _ = neighbor_avg_warmstart(x, jnp.zeros_like(mask), alive, prev)
    np.testing.assert_array_equal(np.asarray(healed2), np.asarray(x))


def test_rejoin_leakage_report_broadcast_vs_masked_wire():
    """The neighbor-avg broadcast is exactly recoverable; the ordinary
    PDSGD wire on the SAME links leaves the Theorem-5 residual."""
    m, d = 6, 5
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    proc = make_mixing(make_topology("complete", m), rate=0.0)
    faults = make_faults(m, crash_rate=0.3, restart_rate=0.9, seed=1)
    alive_prev = jnp.asarray([1.0, 0.0, 1.0, 1.0, 1.0, 1.0])
    alive = jnp.ones((m,), jnp.float32)
    W, support, mask, _, _ = realize_coupling(proc, faults, _step_i32(3))
    mask = jnp.ones((m, m), jnp.float32) - jnp.eye(m)  # all links realized
    W = MX.metropolis_from_mask(mask)
    B = jnp.asarray(rng.dirichlet(np.ones(m), m).T.astype(np.float32))
    rep = rejoin_leakage_report(params=x, u=u, W=W, B=B, mask=mask,
                                alive=alive, alive_prev=alive_prev)
    assert int(rep["links"]) == m - 1  # rejoiner hears all stable agents
    assert float(rep["broadcast_mse"]) < 1e-10
    assert float(rep["pdsgd_wire_mse"]) > 1e-3  # the b_ij/w_ij u_j residual
    assert float(rep["pdsgd_wire_mse"]) > float(rep["broadcast_mse"])


# -- sentinels: chaos stays contained -----------------------------------

def _chaos_step(nan_policy, d=3, m=5):
    top = make_topology("paper_fig1", m)
    faults = make_faults(m, corrupt_rate=0.4, corrupt_mode="nan",
                         guard_clip=None, seed=3)  # guard OFF: raw chaos
    loss = lambda p, b: jnp.sum((p - b) ** 2)
    return make_decentralized_step(loss, top, S.harmonic(0.1),
                                   faults=faults, nan_policy=nan_policy,
                                   donate=False), top, d


def test_skip_policy_holds_finite_state_under_raw_nan_chaos():
    rng = np.random.default_rng(0)
    step, top, d = _chaos_step("skip")
    batch = jnp.asarray(rng.normal(size=(top.num_agents, d)).astype(
        np.float32))
    state = init_state(jnp.zeros((d,)), top.num_agents)
    nonf = corrupt = 0
    for i in range(12):
        state, aux = step(state, batch, jax.random.key(i))
        nonf += int(aux["fault_nonfinite"])
        corrupt += int(aux["fault_corrupt"])
    assert corrupt > 0 and nonf > 0  # poison flowed and was caught
    assert np.all(np.isfinite(np.asarray(state.params)))


def test_warn_policy_counts_but_lets_nan_through():
    rng = np.random.default_rng(0)
    step, top, d = _chaos_step("warn")
    batch = jnp.asarray(rng.normal(size=(top.num_agents, d)).astype(
        np.float32))
    state = init_state(jnp.zeros((d,)), top.num_agents)
    nonf = 0
    for i in range(12):
        state, aux = step(state, batch, jax.random.key(i))
        nonf += int(aux["fault_nonfinite"])
    assert nonf > 0
    assert np.any(~np.isfinite(np.asarray(state.params)))


def test_off_policy_reports_no_sentinel_aux():
    step, top, d = _chaos_step("off")
    state = init_state(jnp.zeros((d,)), top.num_agents)
    state, aux = step(state, jnp.zeros((top.num_agents, d)),
                      jax.random.key(0))
    assert "fault_nonfinite" not in aux
    assert "fault_down" in aux  # fault counters still ride


# -- trimmed-mean through the step builder ------------------------------

def test_trimmed_mean_step_survives_scale_byzantine():
    m, d = 5, 3
    top = make_topology("complete", m)
    rng = np.random.default_rng(2)
    batch = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    loss = lambda p, b: jnp.sum((p - b) ** 2)
    # trim=1 tolerates ONE corrupt sender per step; a step with 2+ is
    # legitimately allowed to diverge.  Which seeds stay within that
    # depends on the PRNG implementation (jax_threefry_partitionable
    # changed the draws), so take the first seed whose realized stream
    # has corruption and never 2+ corrupt senders in one step.
    def stream(seed):
        f = make_faults(m, corrupt_rate=0.1, corrupt_mode="scale",
                        corrupt_scale=1e6, seed=seed)
        per_step = jax.vmap(lambda k: f.realize(k)[1].sum())(jnp.arange(30))
        return f, np.asarray(per_step)
    faults, per_step = next(
        (f, c) for f, c in map(stream, range(100))
        if c.max() == 1 and c.sum() >= 8)
    step = make_decentralized_step(loss, top, S.harmonic(0.1),
                                   faults=faults, aggregation="trimmed_mean",
                                   trim=1, donate=False)
    state = init_state(jnp.zeros((d,)), m)
    corrupt = 0
    for i in range(30):
        state, aux = step(state, batch, jax.random.key(i))
        corrupt += int(aux["fault_corrupt"])
    assert corrupt == per_step.sum() > 0  # byzantine steps happened
    p = np.asarray(state.params)
    assert np.all(np.isfinite(p)) and np.max(np.abs(p)) < 100.0


# -- 4. convergence under faults ----------------------------------------

def test_quadratic_converges_under_markov_crash_churn():
    """Fig-2-style check: with 20% per-step crash onsets (geometric
    restarts) the quadratic still drives the surviving consensus to the
    global optimum — within a modest factor of the no-fault floor."""
    m, d = 5, 2
    top = make_topology("paper_fig1", m)
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    x_star = np.asarray(targets).mean(0)

    def loss(p, b):
        return jnp.sum((p - b) ** 2)

    def run(faults):
        step = make_decentralized_step(loss, top, S.harmonic(0.3),
                                       faults=faults, donate=False)
        state = init_state(jnp.zeros((d,)), m)
        for k in range(400):
            state, _ = step(state, targets, jax.random.key(k))
        xbar = np.asarray(state.params).mean(0)
        return float(np.sum((xbar - x_star) ** 2))

    clean = run(None)
    churn = run(make_faults(m, crash_rate=0.2, restart_rate=0.5, seed=8))
    assert clean < 1e-3
    assert churn < 25 * max(clean, 1e-4) + 0.05  # reaches the same floor
