"""Compiled population search (repro.sim.search) vs its host oracle.

The headline deliverable: on a SHARED jax.random key schedule the fully
traced GA (population init + tournament selection + crossover/mutation +
argsort duplicate repair + KKT fitness, all inside one jit) must reproduce
the host oracle — numpy operators driven by the same keys, fitness through
the trusted scalar ``core.kkt`` — bit for bit: same winning assignment,
same q, same scheduled set, energy to fp32 tolerance. End-to-end, a
``FleetSim`` in ``compiled-ga`` mode must replay against
``run_host_policy`` with the host GA controller within the engine's
existing parity bands.

Property tests (hypothesis, or the vendored ``repro.testing.minihyp`` shim)
pin the GA operator invariants: every operator emits VALID chromosomes
(channel values in range, no client on two channels, participation ==
membership), mirroring ``core.genetic._repair_duplicates``'s contract.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis")  # real package or the conftest minihyp shim
from hypothesis import given, settings, strategies as st

from repro.core.genetic import GAConfig, SystemParams
from repro.sim import build_sim, search
from repro.wireless.channel import ChannelModel, ChannelParams

SYSP = SystemParams()


def _context(u, c, seed, kill=None):
    rng = np.random.default_rng(seed)
    rates = ChannelModel(
        ChannelParams(n_clients=u, n_channels=c), seed=seed
    ).draw_rates()
    if kill is not None:
        rates[kill, :] = 1e6  # ~1 Mbit/s: cannot carry Z bits in T_max
    d = np.maximum(rng.normal(1200, 300, u), 50)
    g = rng.uniform(0.5, 2.0, u); g /= g.mean()
    s = rng.uniform(0.5, 2.0, u); s /= s.mean()
    th = rng.uniform(0.2, 1.5, u)
    return rates, d, g, s, th


def _run_both(z, seed, lam1, lam2, repair, kill=None, u=8, c=8):
    rates, d, g, s, th = _context(u, c, seed, kill=kill)
    cfg = GAConfig(generations=5, population=10, elitism=2,
                   repair_infeasible=repair)
    key = jax.random.PRNGKey(seed + 100)
    host = search.run_ga_host(
        key, rates, d, g, s, th, lam1, lam2, SYSP, z, 100.0, cfg=cfg
    )
    fn = jax.jit(functools.partial(
        search.ga_decide, sysp=SYSP, z=z, v_weight=100.0, cfg=cfg
    ))
    comp = fn(
        key, jnp.asarray(rates, jnp.float32), jnp.asarray(d, jnp.float32),
        jnp.asarray(g, jnp.float32), jnp.asarray(s, jnp.float32),
        jnp.asarray(th, jnp.float32), lam1=jnp.float32(lam1),
        lam2=jnp.float32(lam2),
    )
    return host, comp


# ------------------------------------------------- bit-for-bit GA parity

@pytest.mark.parametrize("z,seed,lam1,lam2,repair,kill", [
    (5122, 1, 5.0, 20.0, False, None),     # tiny model, light queues
    (246590, 7, 30.0, 150.0, True, None),  # FEMNIST payload, repair mode
    (246590, 2, 10.0, 60.0, True, 2),      # infeasible client dropped
    (246590, 4, 10.0, 60.0, False, 5),     # infeasible -> fitness 0
    (576778, 5, 1.0, 120.0, True, None),   # CIFAR payload
])
def test_ga_matches_host_oracle_bit_for_bit(z, seed, lam1, lam2, repair, kill):
    """Same key schedule -> same winning assignment, q, schedule; energy to
    fp32 tolerance (the acceptance bar for the compiled search)."""
    host, comp = _run_both(z, seed, lam1, lam2, repair, kill=kill)
    np.testing.assert_array_equal(host.assign, np.asarray(comp.assign))
    np.testing.assert_array_equal(host.a, np.asarray(comp.a))
    np.testing.assert_array_equal(host.q, np.asarray(comp.q))
    np.testing.assert_allclose(
        host.energy, np.asarray(comp.energy), rtol=1e-4, atol=1e-12
    )
    np.testing.assert_allclose(
        float(host.quant_term), float(comp.quant_term), rtol=1e-4
    )
    if kill is not None:
        assert host.a[kill] == 0 and int(np.asarray(comp.a)[kill]) == 0


@pytest.mark.parametrize("u,c", [(6, 9), (10, 6)])
def test_ga_parity_rectangular_channel_matrix(u, c):
    """U != C: spare channels idle / spare clients unscheduled, both paths."""
    host, comp = _run_both(246590, 13, 20.0, 90.0, True, u=u, c=c)
    np.testing.assert_array_equal(host.assign, np.asarray(comp.assign))
    np.testing.assert_array_equal(host.q, np.asarray(comp.q))
    assert int(host.a.sum()) <= min(u, c)


def test_ga_winner_satisfies_round_constraints():
    """The winning decision respects C1-C5: injective assignment, q >= 1 and
    f in [f_min, f_max] for scheduled clients, latency <= T_max."""
    host, comp = _run_both(246590, 7, 30.0, 150.0, True)
    assign = np.asarray(comp.assign)
    used = assign[assign >= 0]
    assert len(set(used.tolist())) == len(used)
    a = np.asarray(comp.a).astype(bool)
    q = np.asarray(comp.q)
    f = np.asarray(comp.f)
    lat = np.asarray(comp.latency)
    assert np.all(q[a] >= 1) and np.all(q[a] <= 8)
    assert np.all(q[~a] == 0)
    assert np.all(f[a] >= SYSP.f_min * (1 - 1e-6))
    assert np.all(f[a] <= SYSP.f_max * (1 + 1e-6))
    assert np.all(lat[a] <= SYSP.t_max * (1 + 1e-5))
    # participation == membership of the kept assignment
    member = np.isin(np.arange(len(a)), used)
    np.testing.assert_array_equal(a, member)


def test_ga_all_infeasible_schedules_nobody():
    """Every client's rate too low for q = 1: both paths fall back to the
    empty assignment (run_ga's final fallback) instead of diverging."""
    u = c = 6
    z = 246590
    rates = np.full((u, c), 1e6)
    d = np.full(u, 1000.0)
    ones = np.ones(u)
    cfg = GAConfig(generations=3, population=8, repair_infeasible=False)
    key = jax.random.PRNGKey(0)
    host = search.run_ga_host(key, rates, d, ones, ones, ones, 10.0, 50.0,
                              SYSP, z, 100.0, cfg=cfg)
    comp = search.ga_decide(
        key, jnp.asarray(rates, jnp.float32), jnp.asarray(d, jnp.float32),
        jnp.asarray(ones, jnp.float32), jnp.asarray(ones, jnp.float32),
        jnp.asarray(ones, jnp.float32), jnp.float32(10.0), jnp.float32(50.0),
        SYSP, z, 100.0, cfg=cfg,
    )
    assert int(host.a.sum()) == 0 and int(np.asarray(comp.a).sum()) == 0
    assert np.all(host.assign == -1) and np.all(np.asarray(comp.assign) == -1)


# ------------------------------------------- end-to-end engine trajectory

N_ROUNDS = 5
GA_CFG = GAConfig(generations=4, population=8, elitism=2,
                  repair_infeasible=True)


@pytest.fixture(scope="module")
def ga_pair():
    sim_a = build_sim("tiny", n_clients=8, seed=1,
                      n_test=256, policy_mode="compiled-ga", ga_config=GA_CFG)
    res_c = sim_a.run_compiled(N_ROUNDS)
    sim_b = build_sim("tiny", n_clients=8, seed=1,
                      n_test=256, policy_mode="host-ga", ga_config=GA_CFG)
    res_h = sim_b.run(N_ROUNDS)
    return res_c, res_h


def test_engine_ga_trajectory_matches_host_replay(ga_pair):
    """FleetSim(compiled-ga) vs run_host_policy(HostGAPolicy) on the same
    key schedule: accuracy within the engine's 2e-2 parity band (in practice
    bit-equal), identical schedules and q."""
    res_c, res_h = ga_pair
    acc_h = np.array([r.accuracy for r in res_h.records])
    assert np.max(np.abs(acc_h - res_c.accuracy)) <= 2e-2
    np.testing.assert_array_equal(
        np.array([r.n_scheduled for r in res_h.records]), res_c.n_scheduled
    )
    np.testing.assert_array_equal(
        np.stack([r.q_levels for r in res_h.records]), res_c.q_levels
    )
    np.testing.assert_allclose(
        np.array([r.energy for r in res_h.records]), res_c.energy, rtol=1e-5,
        atol=1e-12,
    )


def test_engine_ga_cold_start_then_schedules(ga_pair):
    """Sound-form queues: with empty queues the GA minimizes V * energy by
    scheduling nobody, then the data queue fills and participation jumps
    (the doubly adaptive schedule's warm-up)."""
    res_c, _ = ga_pair
    assert res_c.n_scheduled[0] == 0
    assert res_c.n_scheduled[-1] > 0
    assert res_c.q_levels[-1].max() >= 1


def test_engine_ga_mode_one_compile():
    """The whole GA experiment lowers as ONE scan (dry-run path)."""
    sim = build_sim("tiny", n_clients=8, seed=0,
                    n_test=64, policy_mode="compiled-ga", ga_config=GA_CFG)
    lowered = sim.lower(3, with_eval=False)
    assert len(lowered.as_text()) > 0


def test_ga_kkt_grid_is_slot_bound():
    """The GA fitness solves the KKT on the slot axis: its 512-point grid
    is (S, 512) per chromosome, S = min(U, C), and no fleet-width (U, 512)
    grid survives into the lowered decision at C << U."""
    u, c = 64, 4
    rates, d, g, s, th = _context(u, c, 0)
    cfg = GAConfig(generations=2, population=8, elitism=2)
    fn = jax.jit(functools.partial(
        search.ga_decide, sysp=SYSP, z=246590, v_weight=100.0, cfg=cfg
    ))
    txt = fn.lower(
        jax.random.PRNGKey(0), *(jnp.asarray(x, jnp.float32)
                                 for x in (rates, d, g, s, th)),
        lam1=jnp.float32(10.0), lam2=jnp.float32(60.0),
    ).as_text()
    assert f"{u}x512x" not in txt
    assert f"tensor<{cfg.population}x{c}x512xf32>" in txt  # the fitness
    assert f"tensor<{c}x512xf32>" in txt                   # the winner


# -------------------------------------------------- operator property tests

def _random_maybe_invalid(seed, u, c):
    """Chromosomes with duplicates allowed — repair's input domain."""
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (c,), -1, u)
    ).astype(np.int64)


def _assert_valid(assign, u):
    assign = np.asarray(assign)
    assert np.all(assign >= -1) and np.all(assign < u)
    used = assign[assign >= 0]
    assert len(set(used.tolist())) == len(used), assign


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), u=st.integers(2, 12), c=st.integers(2, 12))
def test_property_repair_emits_valid_assignments(seed, u, c):
    """Repair: output injective + in range, preserves the client SET, keeps
    only channels that held the client in the input, fixes host == compiled,
    and is idempotent (the _repair_duplicates invariants)."""
    raw = _random_maybe_invalid(seed, u, c)
    comp = np.asarray(search.repair_duplicates(jnp.asarray(raw, jnp.int32)))
    host = search.repair_duplicates_host(raw)
    np.testing.assert_array_equal(comp, host)
    _assert_valid(comp, u)
    assert set(comp[comp >= 0].tolist()) == set(raw[raw >= 0].tolist())
    kept = comp >= 0
    np.testing.assert_array_equal(comp[kept], raw[kept])
    np.testing.assert_array_equal(
        np.asarray(search.repair_duplicates(jnp.asarray(comp, jnp.int32))), comp
    )


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), u=st.integers(2, 12), c=st.integers(2, 12))
def test_property_init_emits_valid_assignments(seed, u, c):
    """Random init: valid, schedules 1..min(U, C) clients, host == compiled."""
    key = jax.random.PRNGKey(seed)
    comp = np.asarray(search.random_assignment(key, u, c))
    host = search.random_assignment_host(key, u, c)
    np.testing.assert_array_equal(comp, host)
    _assert_valid(comp, u)
    n_sched = int((comp >= 0).sum())
    assert 1 <= n_sched <= min(u, c)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), u=st.integers(2, 10), c=st.integers(2, 10))
def test_property_evolution_emits_valid_assignments(seed, u, c):
    """A full evolution step (tournament + crossover + mutation + repair)
    only ever emits valid chromosomes, and every client's participation is
    consistent with membership (a_i = 1 iff i in assign)."""
    cfg = GAConfig(population=8, elitism=2, p_mutation=0.3)
    k_pop, k_j0, k_gen = jax.random.split(jax.random.PRNGKey(seed), 3)
    pop = jax.vmap(lambda k: search.random_assignment(k, u, c))(
        jax.random.split(k_pop, cfg.population)
    )
    j0 = jax.random.uniform(k_j0, (cfg.population,))
    nxt = np.asarray(search.next_generation(k_gen, pop, j0, cfg, u))
    assert nxt.shape == (cfg.population, c)
    for row in nxt:
        _assert_valid(row, u)
        # participation == membership (eq. C2/C3 consistency)
        member = np.isin(np.arange(u), row[row >= 0])
        onehot = (row[None, :] == np.arange(u)[:, None]) & (row[None, :] >= 0)
        np.testing.assert_array_equal(onehot.any(axis=1), member)
    # elites are carried over unchanged, in stable j0 order
    elite_idx = np.argsort(np.asarray(j0), kind="stable")[: cfg.elitism]
    np.testing.assert_array_equal(nxt[: cfg.elitism], np.asarray(pop)[elite_idx])
