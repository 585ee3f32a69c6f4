"""The trace reduction and the per-layer readers on a small built trace."""
import json

import pytest

from chipbench import flops, harness, manifest, tracing

from chip_fixtures import BENCH, ROOT

MS = 1e6  # ns

HLO = """
ENTRY %main {
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(run)/while"}
  %fusion.1 = f32[8] fusion(%x), kind=kLoop, metadata={op_name="jit(run)/while/body/kkt_solve/mul" stack_frame_id=3}
  %convolution.2 = f32[8] convolution(%a, %b), metadata={op_name="jit(run)/while/body/fleet_local_sgd/vmap()/conv"}
  ROOT %fusion.3 = f32[8] fusion(%y), metadata={op_name="jit(run)/while/body/fleet_local_sgd/add"}
  %pallas_aggregate.4 = f32[64,128]{1,0:T(8,128)S(1)} custom-call(%i, %s, %c), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/pallas_aggregate/pallas_call"}
  %fusion.5 = f32[8] fusion(%z), metadata={op_name="jit(run)/while/body/kkt_solve_extra/x"}
  %fusion.7 = f32[8] fusion(%v), metadata={op_name="jit(run)/while/body/vmap(kkt_solve)/jit(take_along_axis)/gather"}
  %copy.6 = f32[8] copy(%w)
}
"""


def _op(name, start_ms, dur_ms, hlo, device=0):
    return tracing.Op(name, start_ms * MS, dur_ms * MS,
                      hlo.scopes.get(name, ""), device,
                      kernel=name in hlo.kernels)


def built_trace():
    """A 10 ms window on one device: a while op enclosing a KKT op, local
    SGD ops (one overlapping another), the aggregate kernel, an op of
    another scope, and idle gaps; a copy runs past the window's end."""
    hlo = tracing.HloIndex.of(HLO)
    ops = [
        _op("while.1", 0.4, 7.8, hlo),
        _op("fusion.1", 0.5, 1.0, hlo),
        _op("convolution.2", 2.0, 2.0, hlo),
        _op("fusion.3", 3.0, 2.0, hlo),
        _op("pallas_aggregate.4", 5.5, 0.5, hlo),
        _op("fusion.5", 7.0, 1.0, hlo),
        _op("copy.6", 9.0, 1.5, hlo),
        _op("fusion.7", 8.2, 0.3, hlo),
    ]
    spans = [tracing.Span("window", 0.0, 10 * MS),
             tracing.Span("experiment", 0.0, 8.5 * MS),
             tracing.Span("check", 8.5 * MS, 1.5 * MS)]
    return tracing.Trace(tracing.mark_leaves(ops), spans, 1)


def test_hlo_index_reads_scopes_and_kernels():
    hlo = tracing.HloIndex.of(HLO)
    assert hlo.scopes["fusion.3"].endswith("fleet_local_sgd/add")
    assert hlo.scopes["fusion.1"] == "jit(run)/while/body/kkt_solve/mul"
    assert "copy.6" not in hlo.scopes
    assert hlo.kernels == {"pallas_aggregate.4"}


def test_enclosing_ops_are_not_leaves():
    leaf = {o.name: o.leaf for o in built_trace().ops}
    assert not leaf["while.1"]
    assert all(v for k, v in leaf.items() if k != "while.1")


def test_busy_union_and_idle_gaps():
    tr = built_trace()
    # leaves: [0.5,1.5] [2,5] [5.5,6] [7,8] [8.2,8.5] [9,10]
    assert tracing.busy_ns(tr, 0, 10 * MS) == pytest.approx(6.8 * MS)
    gaps = tracing.idle_gaps(tr, 0, 10 * MS)
    assert [(round(a / MS, 6), round(b / MS, 6)) for a, b in gaps] == [
        (0, 0.5), (1.5, 2), (5, 5.5), (6, 7), (8, 8.2), (8.5, 9)]
    assert tracing.host_span_at(tr, 8.75 * MS, harness.HOST_SPANS) == "check"
    assert tracing.host_span_at(tr, 1.7 * MS, harness.HOST_SPANS) == \
        "experiment"


def test_busy_averages_over_devices():
    tr = built_trace()
    tr.ops.append(tracing.Op("fusion.9", 0.0, 10 * MS, "", 1))
    tr.n_devices = 2
    assert tracing.busy_ns(tr, 0, 10 * MS) == pytest.approx(8.4 * MS)


def test_scope_time_matches_whole_scope_names_only():
    tr = built_trace()
    assert tracing.scope_ns(tr, "kkt_solve", 0, 10 * MS) == \
        pytest.approx(1.3 * MS)
    assert tracing.scope_ns(tr, "fleet_local_sgd", 0, 10 * MS) == \
        pytest.approx(4 * MS)
    assert tracing.scope_ns(tr, "eval", 0, 10 * MS) == 0


def test_top_ops_are_leaves_sorted_by_device_time():
    top = tracing.top_ops(built_trace(), 0, 10 * MS, n=2)
    assert [n.split()[0] for n, _ in top] == ["convolution.2", "fusion.3"]
    assert top[0][0] == "convolution.2 fleet_local_sgd/vmap()/conv"
    assert top[0][1] == pytest.approx(2e-3)


def _ctx(tr):
    mf = manifest.Manifest(ROOT)
    lo, hi = 0.0, 10 * MS
    cell = mf.cell("femnist_u1024.greedy")
    return mf, harness.LayerContext(
        cell=cell,
        spans={"host_build": 31.0, "compile": 2.5}, trace=tr,
        window=(lo, hi), window_s=0.01,
        busy_s=tracing.busy_ns(tr, lo, hi) / 1e9, rounds=2,
        useful_flops=cell.runner.useful_flops(cell.config, cell.traffic,
                                              [8, 8]),
        peaks=manifest.load_peaks("TPU v5 lite"),
        memory={"argument": 5_300_000_000, "output": 2_000_000,
                "alias": 1_000_000, "temp": 300_000_000})


def test_readers_on_the_built_trace():
    mf, ctx = _ctx(built_trace())
    read = {m: mf.reader(m)(ctx) for m in mf.names("per_layer")}
    assert read["host_build_s"] == 31.0 and read["compile_s"] == 2.5
    assert read["kkt_ms_per_round"] == pytest.approx(0.65)
    assert read["local_sgd_ms_per_round"] == pytest.approx(2.0)
    assert read["device_idle_share"] == pytest.approx(32.0)
    assert read["aggregate_us_per_call"] == pytest.approx(500.0)
    cfg = json.loads((BENCH / "configs" / "femnist_u1024_c8.json").read_text())
    useful = 2 * flops.round_useful_flops(cfg["model"], 6, 32, 8, 1024)
    assert read["round_mfu"] == pytest.approx(100 * useful / 0.01 / 197e12)
    assert read["scan_hbm_gb"] == pytest.approx(5.601)


def test_readers_return_nothing_when_nothing_is_traced():
    tr = built_trace()
    tr.ops = [o for o in tr.ops if not o.scope]
    mf, ctx = _ctx(tr)
    ctx.memory = {}
    for m in ("kkt_ms_per_round", "local_sgd_ms_per_round",
              "aggregate_us_per_call", "scan_hbm_gb"):
        assert mf.reader(m)(ctx) is None
