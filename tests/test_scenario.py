"""Scenario-as-data tests (ISSUE 6): the cell-free (A, U, C) channel
against a numpy oracle, the A = 1 bit-for-bit legacy contract, the
association-rule invariants, the scenario registry/round-trip, and the
zero-retrace gate on the engine's dynamic scenario leaves.

The A = 1 contract is the load-bearing one: ``scenario="single_bs"`` (and
``scenario=None``) must reproduce the pre-scenario engine bit for bit —
same PRNG stream (the (1, U, C) fading tensor is the legacy (U, C) draw
reshaped), same association reduction (identity at one AP), same numpy
client drop and eps probe in ``build_sim``.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis")  # real package or the conftest minihyp shim
from hypothesis import given, settings, strategies as st

from repro.sim import (
    ASSOCIATIONS, DataSpec, LyapunovSpec, Scenario, Topology, build_sim,
    get_scenario, register_scenario, scenario_names,
)
from repro.sim import channel as simch
from repro.wireless.channel import ChannelModel, ChannelParams, ap_ring_layout

SEED = 21


def _oracle_ap_gains(key, params, distances):
    """Numpy replay of the (A, U, C) physics from the raw PRNG normals."""
    a = distances.shape[0]
    kx, ky = jax.random.split(key)
    shape = (a, params.n_clients, params.n_channels)
    nx = np.asarray(jax.random.normal(kx, shape), np.float64)
    ny = np.asarray(jax.random.normal(ky, shape), np.float64)
    k, zeta = params.rician_k, params.rician_zeta
    los = np.sqrt(k / (k + 1.0) * zeta)
    nlos = np.sqrt(zeta / (2.0 * (k + 1.0)))
    small = (los + nlos * nx) ** 2 + (nlos * ny) ** 2
    pl = (28.0 + 22.0 * np.log10(np.asarray(distances, np.float64))
          + 20.0 * np.log10(np.float32(params.carrier_ghz)))
    large = 10.0 ** ((-pl + params.antenna_gain_db) / 10.0)
    return small * large[:, :, None]


@pytest.fixture(scope="module")
def cellfree():
    params = ChannelParams(n_clients=6, n_channels=5)
    key = jax.random.PRNGKey(3)
    topo = Topology(ap_xy=ap_ring_layout(4, 0.5 * params.radius_m),
                    mode="cellfree", association="best")
    distances = topo.drop(jax.random.PRNGKey(7), params)
    return params, key, distances


def test_ap_gains_match_numpy_oracle(cellfree):
    params, key, distances = cellfree
    gains = np.asarray(simch.draw_ap_gains(key, params, distances))
    expect = _oracle_ap_gains(key, params, np.asarray(distances))
    assert gains.shape == (4, params.n_clients, params.n_channels)
    np.testing.assert_allclose(gains, expect, rtol=1e-5)


def test_rates_match_numpy_oracle_both_associations(cellfree):
    params, key, distances = cellfree
    d = np.asarray(distances, np.float64)
    ap_gains = _oracle_ap_gains(key, params, d)
    large = 10.0 ** ((-(28.0 + 22.0 * np.log10(d)
                        + 20.0 * np.log10(np.float32(params.carrier_ghz)))
                      + params.antenna_gain_db) / 10.0)
    best_idx = np.argmax(large, axis=0)                          # (U,)
    oracle = {
        "best": ap_gains[best_idx, np.arange(params.n_clients), :],
        "combine": ap_gains.sum(axis=0),
    }
    for assoc in ASSOCIATIONS:
        rates = np.asarray(simch.draw_rates(key, params, distances, assoc))
        snr = params.p_tx * oracle[assoc] / params.noise_power
        np.testing.assert_allclose(
            rates, params.bandwidth * np.log2(1.0 + snr), rtol=1e-5,
            err_msg=assoc,
        )


def test_a1_gain_draw_bit_identical_to_legacy():
    """The (1, U, C) tensor draw consumes the PRNG stream exactly like the
    legacy (U, C) draw: same key, same element count, row-major counters —
    so single-BS scenarios never perturb historical channel streams."""
    params = ChannelParams(n_clients=6, n_channels=8)
    host = ChannelModel(params, seed=5)
    sim = simch.SimChannel.from_host_model(host)
    key = jax.random.PRNGKey(13)
    # legacy draw, verbatim from the pre-scenario SimChannel.draw_gains
    k, zeta = params.rician_k, params.rician_zeta
    los = np.sqrt(k / (k + 1.0) * zeta)
    nlos_std = np.sqrt(zeta / (2.0 * (k + 1.0)))
    kx, ky = jax.random.split(key)
    shape = (params.n_clients, params.n_channels)
    x = los + nlos_std * jax.random.normal(kx, shape)
    y = nlos_std * jax.random.normal(ky, shape)
    legacy = (x**2 + y**2) * simch.large_scale(
        jnp.asarray(host.distances, jnp.float32), params
    )[:, None]
    for assoc in ASSOCIATIONS:
        ch = dataclasses.replace(sim, association=assoc)
        np.testing.assert_array_equal(
            np.asarray(ch.draw_gains(key)), np.asarray(legacy), err_msg=assoc,
        )


def test_association_invariants(cellfree):
    """combine is non-coherent power combining: it never loses to serving
    from the single best AP, and both rules are the identity at A = 1."""
    params, key, distances = cellfree
    g_best = np.asarray(simch.draw_rates(key, params, distances, "best"))
    g_comb = np.asarray(simch.draw_rates(key, params, distances, "combine"))
    assert np.all(g_comb >= g_best)
    assert np.any(g_comb > g_best)   # 4 APs: the other three contribute
    d1 = distances[:1]
    np.testing.assert_array_equal(
        np.asarray(simch.draw_rates(key, params, d1, "best")),
        np.asarray(simch.draw_rates(key, params, d1, "combine")),
    )


def test_best_selects_strongest_large_scale_ap(cellfree):
    params, key, distances = cellfree
    ap_gains = simch.draw_ap_gains(key, params, distances)
    eff = np.asarray(simch.effective_gains(ap_gains, distances, params, "best"))
    ap_star = np.argmin(np.asarray(distances), axis=0)  # nearest = strongest
    for i in range(params.n_clients):
        np.testing.assert_array_equal(eff[i], np.asarray(ap_gains)[ap_star[i], i])


def test_topology_drop_near_field_floor():
    params = ChannelParams(n_clients=64, n_channels=8, near_field_m=25.0)
    topo = Topology(ap_xy=ap_ring_layout(3, 0.5 * params.radius_m),
                    mode="cellfree")
    d = np.asarray(topo.drop(jax.random.PRNGKey(0), params))
    assert d.shape == (3, 64)
    assert d.min() >= 25.0
    assert d.max() <= 1.5 * params.radius_m + 1.0  # disc + ring offset


# ------------------------------------------------------- engine round-trip

def test_single_bs_scenario_bit_for_bit_legacy():
    """Golden A = 1 regression: scenario="single_bs" IS the legacy engine."""
    legacy = build_sim("tiny", n_clients=8, seed=SEED, n_test=256)
    scen = build_sim("tiny", scenario="single_bs", n_clients=8, seed=SEED,
                     n_test=256)
    assert scen.channel.n_aps == 1
    np.testing.assert_array_equal(np.asarray(legacy.channel.distances),
                                  np.asarray(scen.channel.distances))
    assert (legacy.eps1, legacy.eps2) == (scen.eps1, scen.eps2)
    r0 = legacy.run_compiled(4)
    r1 = scen.run_compiled(4)
    for field in ("accuracy", "energy", "q_levels", "n_scheduled", "rates",
                  "lambda1", "lambda2", "latency", "payload_bits"):
        np.testing.assert_array_equal(getattr(r0, field), getattr(r1, field),
                                      err_msg=field)


def test_cellfree_parity_with_host_oracle():
    """The host fast-path oracle replays a cell-free compiled scan decision
    for decision — the (A, U, C) draw + association runs on both sides."""
    sim = build_sim("tiny", scenario="cellfree_a4", n_clients=8, seed=SEED,
                    n_test=256)
    res_sim = sim.run_compiled(6)
    res_host = sim.run_host_policy(sim.make_host_policy(), 6, channel="sim")
    np.testing.assert_array_equal(
        res_sim.q_levels, np.stack([r.q_levels for r in res_host.records])
    )
    np.testing.assert_array_equal(
        res_sim.n_scheduled, [r.n_scheduled for r in res_host.records]
    )
    np.testing.assert_allclose(
        res_sim.energy, [r.energy for r in res_host.records], rtol=1e-5
    )
    acc_host = np.array([r.accuracy for r in res_host.records])
    assert np.max(np.abs(acc_host - res_sim.accuracy)) <= 1e-6


def test_noniid_scenario_threads_hetero_vector():
    sim = build_sim("tiny", scenario="noniid_a01", n_clients=8, seed=SEED,
                    n_test=64)
    assert sim.hetero is not None and sim.hetero.shape == (8,)
    assert sim.hetero.min() >= 1.0 and sim.hetero.max() > 1.0
    np.testing.assert_allclose(np.asarray(sim._dyn["hetero"]), sim.hetero,
                               rtol=1e-6)
    # heterogeneity-aware oracle parity: HostFastPolicy carries the same KL
    res_sim = sim.run_compiled(4, with_eval=False)
    res_host = sim.run_host_policy(sim.make_host_policy(), 4, channel="sim",
                                   with_eval=False)
    np.testing.assert_array_equal(
        res_sim.q_levels, np.stack([r.q_levels for r in res_host.records])
    )
    np.testing.assert_array_equal(
        res_sim.n_scheduled, [r.n_scheduled for r in res_host.records]
    )


def test_zero_retrace_across_dyn_leaves():
    """Scenarios sharing a pytree structure share ONE compiled scan: the
    distances / hetero / eps leaves are jit arguments, so varying them
    (an AP-position sweep, a different KL vector, other budgets) must not
    retrace. This is the CI scenario-matrix gate."""
    sim = build_sim("tiny", n_clients=8, seed=SEED, n_test=64)
    fn = sim._scan_fn(False)
    keys, ridx = sim._scan_xs(2)
    carry = sim._init_carry()
    data = sim.data()
    jax.block_until_ready(fn(data, sim._dyn, carry, keys, ridx)[0][0])
    dyn2 = {
        "distances": sim._dyn["distances"] * 1.5,
        "hetero": sim._dyn["hetero"] + 0.25,
        "eps": sim._dyn["eps"] * 0.5,
    }
    jax.block_until_ready(fn(data, dyn2, carry, keys, ridx)[0][0])
    assert fn._cache_size() == 1, "dyn leaves retraced the scan"


# ------------------------------------------------------ registry + pytree

def test_registry_presets():
    names = scenario_names()
    for expected in ("single_bs", "cellfree_a4", "noniid_a01"):
        assert expected in names
    sc = get_scenario("cellfree_a4", n_clients=32, n_channels=4)
    assert sc.channel.n_clients == 32 and sc.channel.n_channels == 4
    assert sc.topology.n_aps == 4 and sc.topology.association == "combine"
    with pytest.raises(KeyError):
        get_scenario("no_such_scenario")


def test_scenario_validation():
    topo = Topology(ap_xy=np.zeros((1, 2)))
    ch = ChannelParams(n_clients=4, n_channels=4)
    with pytest.raises(AssertionError):
        Scenario(name="bad", topology=topo, channel=ch, policy="not_a_policy")
    with pytest.raises(AssertionError):
        Topology(ap_xy=np.zeros((3, 2)), mode="single_bs")
    with pytest.raises(AssertionError):
        Topology(ap_xy=np.zeros((2, 2)), association="coherent")
    sc = Scenario(name="ok", topology=topo, channel=ch)
    assert sc.with_policy("no_quant").policy == "no_quant"
    assert sc.with_fleet(16, 8).channel.n_clients == 16


@settings(max_examples=5, deadline=None)
@given(
    n_aps=st.sampled_from([1, 2, 4]),
    association=st.sampled_from(list(ASSOCIATIONS)),
    policy=st.sampled_from(["qccf", "no_quant", "principle"]),
    hetero_weight=st.sampled_from([0.0, 1.0]),
)
def test_scenario_roundtrip_builds_and_lowers(n_aps, association, policy,
                                              hetero_weight):
    """Property: ANY valid scenario pytree round-trips through build_sim
    into one lowered scan — topologies and baselines are data, not engine
    edits."""
    params = ChannelParams(n_clients=4, n_channels=4)
    if n_aps == 1:
        topo = Topology(ap_xy=np.zeros((1, 2)), mode="single_bs",
                        association=association)
    else:
        topo = Topology(ap_xy=ap_ring_layout(n_aps, 0.5 * params.radius_m),
                        mode="cellfree", association=association)
    sc = Scenario(
        name="prop", topology=topo, channel=params, policy=policy,
        data=DataSpec(alpha_dirichlet=0.5),
        lyapunov=LyapunovSpec(hetero_weight=hetero_weight),
    )
    sim = build_sim("tiny", scenario=sc, seed=1, n_test=64)
    assert sim.channel.n_aps == n_aps
    assert sim.channel.association == association
    lowered = sim.lower(2)
    assert len(lowered.as_text()) > 0
