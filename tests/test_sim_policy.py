"""Compiled fast-path policy vs its host oracle (repro.sim.policy).

The acceptance bar for the fleet engine: on fixed contexts the compiled
decision (greedy channels + vectorized KKT) must schedule exactly the same
clients as the numpy oracle that routes through the trusted scalar
``repro.core.kkt`` solver — and in practice match q/f too.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import kkt
from repro.core.genetic import SystemParams
from repro.sim import policy
from repro.wireless.channel import ChannelModel, ChannelParams

SYSP = SystemParams()


@pytest.mark.parametrize("u,c,seed", [(8, 8, 0), (12, 6, 1), (5, 9, 2), (32, 16, 3)])
def test_greedy_assign_matches_host(u, c, seed):
    rates = ChannelModel(ChannelParams(n_clients=u, n_channels=c), seed=seed).draw_rates()
    host = policy.greedy_assign_host(rates)
    comp = np.asarray(policy.greedy_assign(jnp.asarray(rates, jnp.float32)))
    np.testing.assert_array_equal(host, comp)
    # constraint C2/C3: injective (each client holds at most one channel)
    used = comp[comp >= 0]
    assert len(set(used.tolist())) == len(used)
    assert len(used) == min(u, c)


@pytest.mark.parametrize("z,lam2,vw", [
    (246590, 50.0, 100.0),    # FEMNIST payload, mid-training queue
    (246590, 500.0, 100.0),   # heavy queue
    (576778, 120.0, 1000.0),  # CIFAR payload, large V
    (5122, 20.0, 100.0),      # tiny model: cases collapse to the cap
])
def test_solve_kkt_matches_scalar_solver(z, lam2, vw):
    rng = np.random.default_rng(z % 97 + int(lam2))
    n = 160
    v = rng.uniform(3e7, 3e8, n)
    w = rng.uniform(0.02, 0.3, n)
    d = rng.uniform(100, 3000, n)
    th = rng.uniform(0.01, 3.0, n)
    qj, fj, feasj, _qhatj = policy.solve_kkt(
        jnp.asarray(v, jnp.float32), jnp.asarray(w, jnp.float32),
        jnp.asarray(d, jnp.float32), jnp.asarray(th, jnp.float32),
        jnp.float32(lam2), SYSP, z, vw, q_cap=8,
    )
    qj, fj, feasj = np.asarray(qj), np.asarray(fj), np.asarray(feasj)
    for i in range(n):
        env = kkt.ClientEnv(
            v=float(v[i]), w=float(w[i]), d_size=float(d[i]), z=z,
            theta_max=float(th[i]), lambda2=lam2, eps2=0.0, v_weight=vw,
            p=SYSP.p_tx, alpha=SYSP.alpha, gamma=SYSP.gamma, tau_e=SYSP.tau_e,
            t_max=SYSP.t_max, f_min=SYSP.f_min, f_max=SYSP.f_max,
            lipschitz=SYSP.lipschitz,
        )
        if kkt.q_max_feasible(env) < 1.0:
            assert not feasj[i], i
            continue
        q_hat, _, _case = kkt.solve_continuous(env)
        dec = kkt.integerize(env, float(np.clip(q_hat, 1.0, 8.0)))
        assert dec is not None
        assert feasj[i], i
        assert qj[i] == dec.q, (i, qj[i], dec.q)
        assert fj[i] == pytest.approx(dec.f, rel=1e-4)


@pytest.mark.parametrize("z,seed", [(5122, 0), (246590, 7), (246590, 11)])
def test_decide_matches_host_oracle_fixed_contexts(z, seed):
    """Acceptance: identical scheduled-client counts (and here: identical
    participation, q and close energy) on fixed contexts."""
    u = 8
    rng = np.random.default_rng(seed)
    rates = ChannelModel(ChannelParams(n_clients=u, n_channels=u), seed=seed).draw_rates()
    d = np.maximum(rng.normal(1200, 300, u), 50).astype(np.float64)
    g = rng.uniform(0.5, 2.0, u); g /= g.mean()
    s = rng.uniform(0.5, 2.0, u); s /= s.mean()
    th = rng.uniform(0.2, 1.5, u)
    lam2 = float(rng.uniform(0, 300))

    host = policy.decide_host(rates, d, g, s, th, lam2, SYSP, z, 100.0)
    comp = policy.decide(
        jnp.asarray(rates, jnp.float32), jnp.asarray(d, jnp.float32),
        jnp.asarray(g, jnp.float32), jnp.asarray(s, jnp.float32),
        jnp.asarray(th, jnp.float32), jnp.float32(lam2), SYSP, z, 100.0,
    )
    np.testing.assert_array_equal(host.a, np.asarray(comp.a))
    assert int(host.a.sum()) == int(np.asarray(comp.a).sum())
    np.testing.assert_array_equal(host.q, np.asarray(comp.q))
    np.testing.assert_allclose(host.energy, np.asarray(comp.energy), rtol=1e-4, atol=1e-12)
    np.testing.assert_allclose(float(host.data_term), float(comp.data_term), rtol=1e-4)
    np.testing.assert_allclose(float(host.quant_term), float(comp.quant_term), rtol=1e-4)


def test_decide_drops_infeasible_clients():
    """A client whose rate cannot carry even q = 1 within T_max must be
    unscheduled by both paths (the repair behaviour)."""
    u = 6
    z = 246590
    rng = np.random.default_rng(0)
    rates = ChannelModel(ChannelParams(n_clients=u, n_channels=u), seed=1).draw_rates()
    rates[2, :] = 1e6   # ~1 Mbit/s: 2 Z bits cannot fit in 20 ms
    d = np.full(u, 1000.0)
    ones = np.ones(u)
    host = policy.decide_host(rates, d, ones, ones, ones, 50.0, SYSP, z, 100.0)
    comp = policy.decide(
        jnp.asarray(rates, jnp.float32), jnp.asarray(d, jnp.float32),
        jnp.asarray(ones, jnp.float32), jnp.asarray(ones, jnp.float32),
        jnp.asarray(ones, jnp.float32), jnp.float32(50.0), SYSP, z, 100.0,
    )
    assert host.a[2] == 0 and int(np.asarray(comp.a)[2]) == 0
    np.testing.assert_array_equal(host.a, np.asarray(comp.a))
    # its channel is released (-1), not handed to another client mid-round
    assert np.asarray(comp.energy)[2] == 0.0


def test_bound_terms_match_numpy_reference():
    from repro.core import bounds

    consts = SYSP.bound_constants()
    rng = np.random.default_rng(4)
    u = 10
    a = (rng.uniform(size=u) > 0.3).astype(np.float64)
    d = rng.uniform(100, 2000, u)
    w_full = d / d.sum()
    w_round = a * d / max((a * d).sum(), 1e-12)
    g = rng.uniform(0.5, 2.0, u)
    s = rng.uniform(0.1, 1.0, u)
    th = rng.uniform(0.1, 2.0, u)
    q = rng.integers(1, 9, u)
    dt_np = bounds.data_term(consts, a, w_full, w_round, g, s)
    qt_np = bounds.quant_term(consts, w_round, 5122, th, q)
    dt_j = float(policy.data_term(consts, jnp.asarray(a, jnp.float32),
                                  jnp.asarray(w_full, jnp.float32),
                                  jnp.asarray(w_round, jnp.float32),
                                  jnp.asarray(g, jnp.float32),
                                  jnp.asarray(s, jnp.float32)))
    qt_j = float(policy.quant_term(consts, jnp.asarray(w_round, jnp.float32),
                                   5122, jnp.asarray(th, jnp.float32),
                                   jnp.asarray(q, jnp.int32)))
    assert dt_j == pytest.approx(dt_np, rel=1e-5)
    assert qt_j == pytest.approx(qt_np, rel=1e-5)


def _fleet_wide_solve(assign, v, w, d, theta, lam, sysp, z, v_weight, q_cap=8):
    """The KKT on every client: the reference that the slot-axis solve
    must equal wherever a > 0."""
    del assign
    return policy.solve_kkt(v, w, d, theta, lam, sysp, z, v_weight, q_cap=q_cap)


def _chromosome(rng, u, c, n_sched):
    assign = np.full(c, -1, np.int32)
    chans = rng.permutation(c)[:n_sched]
    assign[chans] = rng.permutation(u)[:n_sched]
    return assign


@pytest.mark.parametrize("case,u,c", [
    ("greedy", 32, 8),       # U > C, every channel used
    ("chromosome", 32, 8),   # random GA chromosome with -1 channels
    ("partial", 32, 8),      # two of eight channels used
    ("dropped", 32, 8),      # a scheduled client fails the feasibility gate
    ("population", 32, 8),   # the GA fitness: vmapped over chromosomes
    ("greedy", 5, 9),        # U <= C: the slot axis is the client axis
    ("chromosome", 6, 6),    # U == C with free channels
])
def test_slot_axis_solve_equals_fleet_wide_solve(monkeypatch, case, u, c):
    """``finish_decision`` solves the KKT only for the clients an assignment
    schedules; every output equals the fleet-wide solve masked by
    participation, bit for bit (``q_cont`` where a > 0, its contract)."""
    z = 246590
    rng = np.random.default_rng(u * 31 + c)
    rates = ChannelModel(ChannelParams(n_clients=u, n_channels=c), seed=u + c).draw_rates()
    if case == "dropped":
        rates[:, :] = np.maximum(rates, 1e8)
        rates[3, :] = 1e6   # cannot carry q = 1 within T_max
    d = np.maximum(rng.normal(1200, 300, u), 50)
    g = rng.uniform(0.5, 2.0, u); g /= g.mean()
    s = rng.uniform(0.5, 2.0, u); s /= s.mean()
    th = rng.uniform(0.2, 1.5, u)
    ctx = [jnp.asarray(x, jnp.float32) for x in (rates, d, g, s, th)]
    if case == "greedy":
        assigns = np.asarray(policy.greedy_assign(ctx[0]))[None]
    elif case == "partial":
        assigns = _chromosome(rng, u, c, 2)[None]
    elif case == "dropped":
        assigns = _chromosome(rng, u, c, c)[None]
        assigns[0, 1] = 3
    elif case == "population":
        assigns = np.stack([_chromosome(rng, u, c, int(n))
                            for n in rng.integers(1, c + 1, 6)])
    else:
        assigns = _chromosome(rng, u, c, min(u, c) - 2)[None]

    def finish(assign, rates, d, g, s, th):
        v_assigned, a0 = policy.participation_from_assign(assign, rates)
        return policy.finish_decision(
            assign, v_assigned, a0, d, g, s, th, jnp.float32(80.0), SYSP, z,
            100.0,
        )

    # the solve under test runs vmapped, as the GA fitness runs it; the
    # reference is jitted per assignment, since the vmapped fleet-wide solve
    # rounds some frequencies one ulp away from the unbatched one on the CPU
    got = jax.jit(jax.vmap(finish, in_axes=(0,) + (None,) * 5))(
        jnp.asarray(assigns), *ctx)
    with monkeypatch.context() as m:
        m.setattr(policy, "solve_scheduled", _fleet_wide_solve)
        ref = jax.jit(finish)
        want = jax.tree.map(lambda *xs: np.stack(xs),
                            *[ref(jnp.asarray(x), *ctx) for x in assigns])
    a = np.asarray(want.a) > 0
    assert a.any()
    if case == "dropped":
        assert not a[0, 3]
    for name in ("assign", "slots", "a", "q", "f", "v_assigned", "energy",
                 "latency", "data_term", "quant_term", "payload_bits"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)), name)
    np.testing.assert_array_equal(np.asarray(got.q_cont)[a],
                                  np.asarray(want.q_cont)[a])
    # off the assignment nothing is solved: q_hat reads 0, as in finish_host
    on = (assigns[:, None, :] == np.arange(u)[None, :, None]).any(axis=2)
    np.testing.assert_array_equal(np.asarray(got.q_cont)[~on], 0.0)
