"""Round-layer scopes, host spans and the recorder (repro.obs.profile).

  * every layer of ``ROUND_SCOPES`` names ops of the compiled scan, and
    the scopes leave the lowered program as it was;
  * ``span`` records nothing without a recorder; with one, spans nest
    with their parent and one experiment's spans share its ``run`` id;
  * the recorder counts JAX's traces and compiles: none for a second
    experiment of the same length, some for a new length.
"""
import contextlib
import re

import jax
import pytest

from repro.obs import Ledger, read_ledger, timed_phase
from repro.obs import profile
from repro.obs.metrics import MetricsConfig
from repro.obs.profile import ROUND_SCOPES, recording, span
from repro.sim import build_sim
from repro.sim import engine

KW = dict(n_clients=8, n_channels=4, seed=0, n_test=64)
POLICIES = ("greedy", "compiled-ga")
RUN_SPANS = ("sim.dispatch", "sim.device_wait", "sim.fetch")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _names_scope(op_names, scope: str) -> bool:
    """Some op_name has ``scope`` as a path part, also where a transform
    wraps it (``vmap(kkt_solve)``)."""
    part = re.compile(r"(?:[\w.]+\()*" + re.escape(scope) + r"\)*")
    return any(part.fullmatch(p) for n in op_names for p in n.split("/"))


@pytest.fixture(scope="module")
def compiled_op_names():
    """op_name metadata of the compiled tiny scan per policy, built with
    telemetry and the downlink on so that every layer is traced."""
    out = {}
    for policy in POLICIES:
        sim = build_sim("tiny", policy_mode=policy, downlink="quant",
                        telemetry=MetricsConfig(enabled=True), **KW)
        text = sim.lower(2, with_eval=True).compile().as_text()
        out[policy] = set(_OP_NAME.findall(text))
    return out


@pytest.mark.parametrize("scope", ROUND_SCOPES)
@pytest.mark.parametrize("policy", POLICIES)
def test_round_scope_names_ops_of_the_compiled_scan(compiled_op_names,
                                                    policy, scope):
    assert _names_scope(compiled_op_names[policy], scope), (
        f"no op of the compiled {policy} scan is traced under {scope!r}")


@pytest.mark.parametrize("policy", POLICIES)
def test_round_scopes_leave_the_lowered_program_unchanged(monkeypatch,
                                                          policy):
    """Scopes are metadata: without them the round lowers to the same
    StableHLO text (no debug info)."""
    def lowered():
        sim = build_sim("tiny", policy_mode=policy, **KW)
        return sim.lower(2, with_eval=True).as_text()

    scoped = lowered()
    monkeypatch.setattr(engine, "scope",
                        lambda name: contextlib.nullcontext())
    assert lowered() == scoped


# ------------------------------------------------------------ recorder


@pytest.fixture(scope="module")
def recorded():
    """A tiny sim built and run twice at one length, then once at
    another, under one recorder; the counts of each step."""
    counts = []
    with recording() as log:
        sim = build_sim("tiny", **KW)
        sim.run_compiled(3, with_eval=True)
        before = dict(log.counts)
        sim.run_compiled(3, with_eval=True)
        counts.append({k: log.counts[k] - before[k] for k in before})
        before = dict(log.counts)
        sim.run_compiled(4, with_eval=True)
        counts.append({k: log.counts[k] - before[k] for k in before})
    return log, counts


def test_build_spans_nest_under_sim_build(recorded):
    log, _ = recorded
    parent = {s.name: s.parent for s in log.spans}
    assert parent["sim.build"] is None
    for name in ("sim.data", "sim.fleet_stack", "sim.fleet_place",
                 "sim.channel"):
        assert parent[name] == "sim.build", name
    build = next(s for s in log.spans if s.name == "sim.build")
    parts = [s for s in log.spans if s.parent == "sim.build"]
    assert all(build.start_ns <= s.start_ns <= s.end_ns <= build.end_ns
               for s in parts)
    assert log.seconds("sim.build") >= sum(
        log.seconds(s.name) for s in parts)


def test_one_experiments_spans_share_its_run_id(recorded):
    log, _ = recorded
    runs = [s for s in log.spans if s.name == "sim.run"]
    assert [s.parent for s in runs] == [None] * 3
    ids = [s.ids["run"] for s in runs]
    assert len(set(ids)) == 3
    for run in runs:
        inner = [s for s in log.spans if s.parent == "sim.run"
                 and run.start_ns <= s.start_ns <= run.end_ns]
        assert [s.name for s in inner] == list(RUN_SPANS)
        assert {s.ids["run"] for s in inner} == {run.ids["run"]}


def test_same_length_run_traces_and_compiles_nothing(recorded):
    _, (same, new) = recorded
    assert same["traces"] == 0 and same["backend_compiles"] == 0
    assert new["traces"] >= 1 and new["backend_compiles"] >= 1


def test_no_recorder_keeps_nothing():
    with recording() as log:
        pass
    assert profile._RECORDER is None
    with span("outside", run=1) as s:
        jax.block_until_ready(jax.numpy.ones(3) + 1)
    assert s.seconds > 0
    assert log.spans == [] and not any(log.counts.values())


def test_nested_recorders_restore_the_outer_one():
    with recording() as outer:
        with span("a"):
            with recording() as inner:
                with span("b"):
                    pass
        with span("c"):
            pass
    assert [s.name for s in inner.spans] == ["b"]
    assert [(s.name, s.parent) for s in outer.spans] == [("a", None),
                                                         ("c", None)]


def test_span_as_decorator_enters_a_fresh_span_per_call():
    @span("work", kind="x")
    def work(n):
        return n + 1

    with recording() as log:
        assert work(1) == 2 and work(2) == 3
    assert [(s.name, s.ids) for s in log.spans] == [
        ("work", {"kind": "x"})] * 2
    assert log.spans[0].end_ns <= log.spans[1].start_ns


def test_timed_phase_is_a_span_and_keeps_its_ledger_event(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with recording() as log:
        with span("outer"):
            with timed_phase("phase_y", Ledger(path), n=3) as t:
                pass
    (rec,) = [s for s in log.spans if s.name == "phase_y"]
    assert rec.parent == "outer" and rec.ids == {"n": 3}
    assert (rec.end_ns - rec.start_ns) / 1e9 == pytest.approx(t.seconds)
    (ev,) = read_ledger(path)
    assert ev["phase"] == "phase_y" and ev["seconds"] == pytest.approx(
        t.seconds)


def test_segmented_and_host_runs_are_spanned(tmp_path):
    path = str(tmp_path / "run.jsonl")
    sim = build_sim("tiny", ledger=Ledger(path), **KW)
    with recording() as log:
        sim.run_compiled(3, with_eval=False, segment=2)
        sim.run_host_policy(sim.make_host_policy(), 2, with_eval=False)
    runs = [s for s in log.spans if s.name == "sim.run"]
    assert len(runs) == 2 and runs[0].ids["run"] != runs[1].ids["run"]
    seg = [s.name for s in log.spans
           if s.ids.get("run") == runs[0].ids["run"] and s.name != "sim.run"]
    # one dispatch for the schedule, then dispatch/wait/fetch per segment,
    # then the fetch of the whole result
    assert seg == (["sim.dispatch"] + list(RUN_SPANS) * 2 + ["sim.fetch"])
    timings = [e for e in read_ledger(path) if e["event"] == "timing"]
    assert [(e["phase"], e["entry"], e["rounds"]) for e in timings] == [
        ("run", "run_compiled", 3), ("run", "run_host_policy", 2)]
    for e, s in zip(timings, runs):
        assert e["seconds"] == pytest.approx((s.end_ns - s.start_ns) / 1e9)
