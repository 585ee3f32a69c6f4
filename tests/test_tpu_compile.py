"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The main path's wire kernels (``repro.kernels.stochastic_quant``) are
compiled by the TPU compiler at the FEMNIST width the fleet scan runs
them at (Z = 246590 padded to M = 1984 rows of 128 lanes, block_m = 64)
and must come out as Mosaic custom calls — what interpret-mode parity
tests cannot show (block-shape rules, memory spaces, supported casts).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest-xdist
worker imports this file.

The last test (CPU) gates that the fleet scan's lowered program does not
grow with the fleet's bytes: the fleet and test set are jit arguments.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import stochastic_quant as sq
from repro.sim import build_sim, engine

FEMNIST_Z = 246590
BLOCK_M = 64
M = engine._pad_len(FEMNIST_Z, BLOCK_M) // sq.LANES  # 1984


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache here; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # libtpu is installed: failing to describe the chip is a failure
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", [8, 64])
def test_aggregate_compiles_for_v5e(one_chip, k):
    txt = _compiled_text(
        lambda i, s, th, w, q: sq.aggregate(i, s, th, w, q, interpret=False,
                                            block_m=BLOCK_M),
        one_chip,
        ((k, M, sq.LANES), jnp.uint8), ((k, M, sq.LANES), jnp.uint8),
        ((k,), jnp.float32), ((k,), jnp.float32), ((k,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_quantize_compiles_for_v5e(one_chip):
    txt = _compiled_text(
        lambda x, r, s: sq.quantize(x, r, s, 8, interpret=False,
                                    block_m=BLOCK_M),
        one_chip,
        ((M, sq.LANES), jnp.float32), ((M, sq.LANES), jnp.uint32),
        ((), jnp.float32),
    )
    assert "tpu_custom_call" in txt


def test_dequantize_compiles_for_v5e(one_chip):
    txt = _compiled_text(
        lambda i, g, s: sq.dequantize(i, g, s, 8, interpret=False,
                                      block_m=BLOCK_M),
        one_chip,
        ((M, sq.LANES), jnp.uint8), ((M, sq.LANES), jnp.uint8),
        ((), jnp.float32),
    )
    assert "tpu_custom_call" in txt


def test_scan_text_independent_of_fleet_bytes():
    """4x the fleet's bytes must not show up in the lowered scan: only
    the argument shapes' digits may change."""
    sizes = {}
    for u in (16, 64):
        sim = build_sim("tiny", n_clients=u, n_channels=4, seed=0, n_test=64)
        fleet_bytes = sim.fleet.x.nbytes + sim.fleet.y.nbytes
        sizes[u] = (fleet_bytes, len(sim.lower(2, with_eval=True).as_text()))
    grown_bytes = sizes[64][0] - sizes[16][0]
    grown_text = abs(sizes[64][1] - sizes[16][1])
    assert grown_bytes > 10_000_000
    assert grown_text < 1e-4 * grown_bytes, sizes
    assert sizes[64][1] < sizes[64][0] / 10, sizes
