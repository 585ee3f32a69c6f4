"""FLOP counts and wire lengths of the yardstick against counts by hand."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, reference

from chip_fixtures import BENCH

FEMNIST = json.loads((BENCH / "configs" / "femnist_u1024_c8.json").read_text())
# the paper's CIFAR-10 CNN (Table I), at the widths of the repo's task
CIFAR = dict(FEMNIST, name="cifar10", model=dict(
    in_hw=32, in_ch=3, conv_channels=[64, 64], kernel=5, hidden=[384, 192],
    n_classes=10, extra_pool=True, z=576778))
CONFIGS = {"femnist": FEMNIST, "cifar10": CIFAR}

# per sample: 2 * H*W * k*k * Cin * Cout per conv (pre-pool), 2 * in * out
# per dense layer
FEMNIST_FWD = (2 * 28 * 28 * 25 * 1 * 32 + 2 * 14 * 14 * 25 * 32 * 64
               + 2 * 3136 * 62)                                  # 21,713,664
CIFAR_FWD = (2 * 32 * 32 * 25 * 3 * 64 + 2 * 16 * 16 * 25 * 64 * 64
             + 2 * 1024 * 384 + 2 * 384 * 192 + 2 * 192 * 10)    # 63,196,928


@pytest.mark.parametrize("name,fwd", [("femnist", FEMNIST_FWD),
                                      ("cifar10", CIFAR_FWD)])
def test_forward_flops_by_hand(name, fwd):
    assert flops.cnn_forward_flops(CONFIGS[name]["model"]) == fwd


def test_round_flops_counts_scheduled_clients_and_eval():
    m = FEMNIST["model"]
    one = flops.round_useful_flops(m, 6, 32, 1, 0)
    assert one == 3 * FEMNIST_FWD * 6 * 32
    assert flops.round_useful_flops(m, 6, 32, 8, 1024) == \
        8 * one + 1024 * FEMNIST_FWD
    assert flops.round_useful_flops(m, 6, 32, 0, 1024) == 1024 * FEMNIST_FWD


# Zpad = Z rounded up to block_m * 128 lanes: 31 * 8192 and 71 * 8192
@pytest.mark.parametrize("name,zpad", [("femnist", 253952),
                                       ("cifar10", 581632)])
def test_zpad(name, zpad):
    cfg = CONFIGS[name]
    assert flops.pad_len(cfg["model"]["z"], cfg["block_m"]) == zpad
    traffic = json.loads((BENCH / "traffic" / "greedy.json").read_text())
    assert reference.Reference(cfg, traffic, 0).zpad == zpad


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_model_has_the_stated_size(name):
    cfg = CONFIGS[name]
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg["model"], k),
                            jax.random.PRNGKey(0))
    z = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert z == cfg["model"]["z"]


@pytest.mark.parametrize("storage", [None, "bfloat16"])
def test_reference_runs_the_stated_precision(storage):
    """The reference's model at the configuration's dot precision, stored
    in its type; the control one type below, at the same dot precision."""
    traffic = json.loads((BENCH / "traffic" / "greedy.json").read_text())
    ref = reference.Reference(FEMNIST, traffic, 0, precision=storage)
    stated = FEMNIST["precision"]
    assert ref.dtype == jnp.dtype(storage or stated["storage"])
    flat = reference.flatten(ref.params0).astype(ref.dtype)
    x, y = jnp.zeros((2, 28, 28, 1)), jnp.zeros((2,), jnp.int32)
    text = str(jax.make_jaxpr(ref._eval)(flat, x, y))
    used = set(re.findall(r"precision=\(?Precision\.(\w+)", text))
    assert used == {stated["dot"].upper()}
