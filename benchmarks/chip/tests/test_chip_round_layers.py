"""The readers of the round's layers (``repro.obs.profile.ROUND_SCOPES``)
on a small built trace that carries every layer's scope."""
import numpy as np
import pytest

from chipbench import harness, manifest, tracing

from chip_fixtures import ROOT

MS = 1e6  # ns
BODY = "jit(run)/while/body/closed_call"

HLO = f"""
ENTRY %main {{
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={{op_name="jit(run)/while"}}
  %fusion.10 = u32[2] fusion(%k), metadata={{op_name="{BODY}/channel_draw/jit(_threefry_split)/slice"}}
  %fusion.11 = s32[8] fusion(%r), metadata={{op_name="{BODY}/decision/jit(argsort)/sort"}}
  %fusion.12 = f32[8] fusion(%g), metadata={{op_name="{BODY}/decision/vmap(kkt_solve)/mul"}}
  %fusion.13 = f32[8,128] fusion(%x), metadata={{op_name="{BODY}/slot_gather/while/body/dynamic_update_slice"}}
  %convolution.14 = f32[8] convolution(%a, %b), metadata={{op_name="{BODY}/fleet_local_sgd/vmap()/conv"}}
  %fusion.15 = u8[8,128] fusion(%f), metadata={{op_name="{BODY}/quantize_wire/floor"}}
  %pallas_aggregate.16 = f32[64,128]{{1,0:T(8,128)S(1)}} custom-call(%i, %s, %c), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/pallas_aggregate/pallas_call"}}
  %fusion.17 = f32[1024] fusion(%e), metadata={{op_name="{BODY}/server_update/scatter-add"}}
  %fusion.18 = f32[10] fusion(%m), metadata={{op_name="{BODY}/eval/jit(eval_metrics)/dot_general"}}
  %fusion.19 = f32[] fusion(%q), metadata={{op_name="{BODY}/telemetry/reduce_sum"}}
  %fusion.20 = f32[8] fusion(%d), metadata={{op_name="{BODY}/downlink/floor"}}
  %fusion.22 = s32[100,1024] fusion(%o), metadata={{op_name="jit(run)/while/body/dynamic_update_slice"}}
  %copy.21 = f32[8] copy(%w)
  %copy.23 = f32[8] copy(%v)
}}
"""

# name, start ms, duration ms: two rounds in a 10 ms window on one device
OPS = [
    ("while.1", 0.2, 8.8),
    ("fusion.10", 0.5, 0.2),
    ("fusion.11", 1.0, 0.6),
    ("fusion.12", 1.6, 0.4),
    ("fusion.13", 2.0, 0.5),
    ("convolution.14", 2.5, 2.0),
    ("fusion.15", 4.5, 0.3),
    ("pallas_aggregate.16", 4.8, 0.1),
    ("fusion.20", 4.9, 0.1),
    ("fusion.17", 5.0, 0.4),
    ("fusion.18", 5.4, 0.6),
    ("fusion.19", 6.0, 0.1),
    ("fusion.22", 6.1, 0.2),
    ("copy.21", 6.3, 0.2),
    ("copy.23", 9.6, 0.8),  # runs 0.4 ms past the window's end
]

# by hand: each layer's ms over 2 rounds; busy is the union of the
# leaves [0.5, 0.7] + [1.0, 6.5] + [9.6, 10.0] = 6.1 ms, of which the
# scan's output write (fusion.22), copy.21 and the window's share of
# copy.23 (0.2 + 0.2 + 0.4 ms) lie under no round layer
EXPECTED = {
    "channel_ms_per_round": 0.1,
    "decision_ms_per_round": 0.5,   # the KKT's 0.2 included
    "gather_ms_per_round": 0.25,
    "quantize_ms_per_round": 0.15,
    "update_ms_per_round": 0.2,
    "eval_ms_per_round": 0.3,
    "unattributed_share": 100 * 0.8 / 6.1,
}
SCOPE = {
    "channel_ms_per_round": "channel_draw",
    "decision_ms_per_round": "decision",
    "gather_ms_per_round": "slot_gather",
    "quantize_ms_per_round": "quantize_wire",
    "update_ms_per_round": "server_update",
    "eval_ms_per_round": "eval",
}


def built_trace(drop: str = "") -> tracing.Trace:
    """The trace of :data:`OPS`, less the ops traced under ``drop``."""
    hlo = tracing.HloIndex.of(HLO)
    ops = [tracing.Op(n, s * MS, d * MS, hlo.scopes.get(n, ""), 0,
                      kernel=n in hlo.kernels) for n, s, d in OPS]
    ops = [o for o in ops if not (drop and tracing.in_scope(o, drop))]
    spans = [tracing.Span("window", 0.0, 10 * MS)]
    return tracing.Trace(tracing.mark_leaves(ops), spans, 1)


def _read(metric: str, tr: tracing.Trace):
    mf = manifest.Manifest(ROOT)
    lo, hi = 0.0, 10 * MS
    ctx = harness.LayerContext(
        cell=mf.cell("femnist_u1024.greedy"), spans={}, trace=tr,
        window=(lo, hi), window_s=0.01,
        busy_s=tracing.busy_ns(tr, lo, hi) / 1e9, rounds=2,
        scheduled=np.array([8, 8]), peaks=manifest.load_peaks("TPU v5 lite"))
    return mf.reader(metric)(ctx)


def test_built_trace_busy_time():
    assert tracing.busy_ns(built_trace(), 0, 10 * MS) == pytest.approx(
        6.1 * MS)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_round_layer_reader_on_the_built_trace(metric):
    assert _read(metric, built_trace()) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(SCOPE))
def test_round_layer_reader_is_silent_without_its_scope(metric):
    assert _read(metric, built_trace(drop=SCOPE[metric])) is None


def test_unattributed_share_is_silent_without_round_layers(monkeypatch):
    """A program whose profile module names no round layers (one older
    than them) gives nothing to read."""
    from repro.obs import profile

    monkeypatch.delattr(profile, "ROUND_SCOPES")
    assert _read("unattributed_share", built_trace()) is None


def test_unattributed_share_counts_only_ops_outside_every_layer():
    tr = built_trace()
    tr.ops = [o for o in tr.ops if o.scope]  # the copies go
    assert _read("unattributed_share", tr) == pytest.approx(
        100 * 0.2 / (6.1 - 0.2 - 0.4))
