"""Telemetry layer (repro.obs): gating, taps, parity, ledger, timing.

The load-bearing guarantees:
  * telemetry OFF is free — the engine lowers the byte-identical scan;
  * telemetry ON is still one compile, and the dyn-leaf zero-retrace
    contract survives;
  * the compiled taps and the host replay record the same schema, with
    exact-input fields matching bit-for-bit;
  * every ledger line is schema-valid, and the null sink is a no-op.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.genetic import GAConfig
from repro.obs import (
    METRIC_FIELDS,
    Ledger,
    MetricsConfig,
    default_ledger,
    pytree_hash,
    read_ledger,
    timed_phase,
    validate_event,
)
from repro.obs.ledger import REPRO_LEDGER_ENV, _sanitize
from repro.sim import build_sim

SEED = 0

# field -> parity class against the host replay (see repro.obs.metrics):
# exact-input fields are bitwise, analog fields pass through the host's
# f64 scalar KKT or differently-fused wire arithmetic
EXACT_FIELDS = ("q_mean", "q_max", "n_timeout", "corr_q_d")
ANALOG_FIELDS = ("data_term", "quant_term", "energy_comp", "energy_comm",
                 "energy_timeout", "q_cont_mean", "quant_mse")


@pytest.fixture(scope="module")
def telem_sim():
    return build_sim("tiny", n_clients=8, seed=SEED, n_test=64,
                     telemetry=MetricsConfig(enabled=True))


# ------------------------------------------------------------ gating


def test_telemetry_off_is_byte_identical():
    """The entire PR rests on this: telemetry=None and an explicit
    enabled=False config lower the SAME program, and enabling the taps
    changes what the scan outputs (not how often it compiles)."""
    kw = dict(n_clients=8, seed=SEED, n_test=64)
    texts = {}
    for name, tele in (
        ("none", None),
        ("off", MetricsConfig(enabled=False)),
        ("on", MetricsConfig(enabled=True)),
    ):
        sim = build_sim("tiny", telemetry=tele, **kw)
        keys, ridx = sim._scan_xs(2)
        carry = sim._init_carry()
        texts[name] = sim._scan_fn(False).lower(
            sim.data(), sim._dyn, carry, keys, ridx
        ).as_text()
    assert texts["none"] == texts["off"], (
        "telemetry-off lowered HLO differs from the no-telemetry engine"
    )
    assert texts["on"] != texts["none"], (
        "enabling telemetry did not change the lowered scan — taps missing"
    )


def test_telemetry_on_zero_retrace(telem_sim):
    """Taps ride the scan as extra ys: varying dyn leaves must not retrace
    (the same contract test_scenario regresses for the off path)."""
    fn = telem_sim._scan_fn(False)
    keys, ridx = telem_sim._scan_xs(2)
    carry = telem_sim._init_carry()
    data = telem_sim.data()
    jax.block_until_ready(fn(data, telem_sim._dyn, carry, keys, ridx)[0][0])
    dyn2 = {
        "distances": telem_sim._dyn["distances"] * 1.5,
        "hetero": telem_sim._dyn["hetero"] + 0.25,
        "eps": telem_sim._dyn["eps"] * 0.5,
    }
    jax.block_until_ready(fn(data, dyn2, carry, keys, ridx)[0][0])
    assert fn._cache_size() == 1, "telemetry taps retraced the scan"


# ------------------------------------------------------------ taps


def test_metrics_stacked_and_consistent(telem_sim):
    """run_compiled returns {field: (N,) f32} covering every RoundMetrics
    slot, with internally consistent values."""
    n = 4
    res = telem_sim.run_compiled(n, with_eval=False)
    assert res.metrics is not None
    assert set(res.metrics) == set(METRIC_FIELDS)
    for name, arr in res.metrics.items():
        assert arr.shape == (n,), (name, arr.shape)
    m = res.metrics
    # energy split sums back to the per-round total
    np.testing.assert_allclose(
        m["energy_comp"] + m["energy_comm"], res.energy, rtol=1e-5
    )
    # q stats agree with the recorded integer levels
    qs = res.q_levels
    for r in range(n):
        sched = qs[r] > 0
        if sched.any():
            np.testing.assert_allclose(
                m["q_mean"][r], qs[r][sched].mean(), rtol=1e-6
            )
            assert m["q_max"][r] == qs[r].max()
    # the realized wire error is tapped and finite on scheduled rounds
    assert np.isfinite(m["quant_mse"]).all() and (m["quant_mse"] >= 0).all()
    # greedy mode: no GA stats
    assert np.isnan(m["ga_best"]).all() and np.isnan(m["ga_median"]).all()


def test_simresult_roundtrip_with_metrics(telem_sim):
    """SimResult.to_result() keeps adapting to the object API with the
    metrics payload present, and cum_energy stays a prefix sum."""
    res = telem_sim.run_compiled(3, with_eval=False)
    out = res.to_result()
    assert len(out.records) == 3
    np.testing.assert_allclose(out.cum_energy, np.cumsum(res.energy),
                               rtol=1e-6)
    for n, rec in enumerate(out.records):
        assert rec.round == n
        np.testing.assert_array_equal(rec.q_levels, res.q_levels[n])


def test_ga_fitness_tap():
    """compiled-ga rounds surface finite best/median population fitness,
    and best <= median (J0 is minimized)."""
    sim = build_sim(
        "tiny", n_clients=8, seed=SEED, n_test=64,
        policy_mode="compiled-ga",
        ga_config=GAConfig(generations=4, population=8,
                           repair_infeasible=True),
        telemetry=MetricsConfig(enabled=True),
    )
    res = sim.run_compiled(2, with_eval=False)
    m = res.metrics
    assert np.isfinite(m["ga_best"]).all()
    assert np.isfinite(m["ga_median"]).all()
    assert (m["ga_best"] <= m["ga_median"] + 1e-6).all()


# ------------------------------------------------------------ parity


def test_compiled_vs_host_metric_parity(telem_sim):
    """The host replay records the same schema: exact-input fields
    bit-for-bit, analog fields to the parity-suite tolerance."""
    n = 4
    res = telem_sim.run_compiled(n, with_eval=False)
    telem_sim.run_host_policy(telem_sim.make_host_policy(), n,
                              channel="sim", with_eval=False)
    host = telem_sim.last_host_metrics
    assert len(host) == n
    for field in EXACT_FIELDS:
        comp = np.asarray(res.metrics[field], np.float32)
        hst = np.asarray([h[field] for h in host], np.float32)
        np.testing.assert_array_equal(
            comp, hst, err_msg=f"exact-input field {field} drifted"
        )
    for field in ANALOG_FIELDS:
        comp = np.asarray(res.metrics[field], np.float64)
        hst = np.asarray([h[field] for h in host], np.float64)
        np.testing.assert_allclose(
            comp, hst, rtol=1e-5, atol=1e-10, equal_nan=True,
            err_msg=f"analog field {field} out of parity tolerance",
        )


# ------------------------------------------------------------ ledger


def test_ledger_smoke_run_schema_valid(tmp_path):
    """A telemetry run through a real ledger file: every line validates,
    the header is self-describing, and round rows carry the taps."""
    path = str(tmp_path / "run.jsonl")
    sim = build_sim("tiny", n_clients=8, seed=SEED, n_test=64,
                    telemetry=MetricsConfig(enabled=True),
                    ledger=Ledger(path))
    n = 3
    sim.run_compiled(n, with_eval=False)
    events = read_ledger(path)  # read_ledger validates every event
    kinds = [e["event"] for e in events]
    assert kinds.count("run_header") == 1
    assert kinds.count("round") == n
    assert kinds.count("timing") >= 1
    header = next(e for e in events if e["event"] == "run_header")
    for k in ("scenario_hash", "policy", "u", "c", "rounds", "jax_version"):
        assert k in header, f"run_header missing {k}"
    rounds = [e for e in events if e["event"] == "round"]
    assert [e["round"] for e in rounds] == list(range(n))
    for e in rounds:
        assert "energy" in e and "q_mean" in e and "quant_mse" in e
        # strict JSON: NaN must have been mapped to null, never emitted
        assert all(not (isinstance(v, float) and math.isnan(v))
                   for v in e.values())


def test_ledger_null_sink_is_noop(tmp_path):
    led = Ledger(None)
    assert not led.enabled
    assert led.write("round", round=0) is None
    assert led.run_header("x", "y") is None
    # and nothing on disk anywhere under tmp_path
    assert list(tmp_path.iterdir()) == []


def test_default_ledger_env_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv(REPRO_LEDGER_ENV, raising=False)
    assert not default_ledger().enabled
    p = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(REPRO_LEDGER_ENV, p)
    assert default_ledger().path == p
    # an explicit path wins over the env var
    q = str(tmp_path / "cli.jsonl")
    assert default_ledger(q).path == q


def test_ledger_write_failure_degrades_to_null_sink(tmp_path, monkeypatch):
    """A persistently failing append must not kill the run: one retry,
    then one RuntimeWarning, then the ledger becomes the null sink."""
    led = Ledger(str(tmp_path / "led.jsonl"))
    calls = {"n": 0}

    def boom(self, line):
        calls["n"] += 1
        raise OSError("disk on fire")

    monkeypatch.setattr(Ledger, "_append", boom)
    with pytest.warns(RuntimeWarning, match="disabling ledger"):
        assert led.write("round", round=0) is None
    assert calls["n"] == 2, "exactly one retry before degrading"
    assert not led.enabled
    # subsequent writes are silent no-ops (null sink), no more attempts
    assert led.write("round", round=1) is None
    assert calls["n"] == 2


def test_ledger_write_retries_transient_oserror(tmp_path, monkeypatch):
    """A transient failure (first append raises, retry succeeds) loses
    nothing: the event lands and the ledger stays enabled."""
    path = str(tmp_path / "led.jsonl")
    led = Ledger(path)
    real_append = Ledger._append
    state = {"fail_next": True}

    def flaky(self, line):
        if state["fail_next"]:
            state["fail_next"] = False
            raise OSError("transient")
        return real_append(self, line)

    monkeypatch.setattr(Ledger, "_append", flaky)
    ev = led.write("round", round=0)
    assert ev is not None and led.enabled
    (read,) = read_ledger(path)
    assert read["round"] == 0


def test_ledger_resume_event_schema(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = Ledger(path)
    led.write("resume", step=4, action="save", dir="/tmp/ck")
    led.write("resume", step=4, action="load", dir="/tmp/ck")
    evs = read_ledger(path)  # read_ledger validates every event
    assert [e["action"] for e in evs] == ["save", "load"]
    assert all(e["event"] == "resume" and e["step"] == 4 for e in evs)
    with pytest.raises(ValueError):
        validate_event({"schema": 1, "event": "resume", "run_id": "r",
                        "ts": 0.0, "step": 4})  # missing action


def test_validate_event_rejects_malformed():
    ok = {"schema": 1, "event": "round", "run_id": "r", "ts": 0.0, "round": 0}
    validate_event(dict(ok))
    with pytest.raises(ValueError):
        validate_event({k: v for k, v in ok.items() if k != "run_id"})
    with pytest.raises(ValueError):
        validate_event({**ok, "schema": 99})
    with pytest.raises(ValueError):
        validate_event({**ok, "event": "mystery"})
    with pytest.raises(ValueError):
        validate_event({k: v for k, v in ok.items() if k != "round"})


def test_sanitize_nan_and_numpy():
    out = _sanitize({
        "nan": float("nan"), "inf": float("inf"),
        "np": np.float32(1.5), "arr": np.arange(3),
        "nested": [np.int64(2), float("nan")],
    })
    assert out["nan"] is None and out["inf"] is None
    assert out["np"] == 1.5 and out["arr"] == [0, 1, 2]
    assert out["nested"] == [2, None]


def test_pytree_hash_discriminates():
    t1 = {"a": jnp.arange(4.0), "b": np.int32(3)}
    assert pytree_hash(t1) == pytree_hash(
        {"a": jnp.arange(4.0), "b": np.int32(3)}
    )
    assert pytree_hash(t1) != pytree_hash({"a": jnp.arange(4.0) + 1,
                                           "b": np.int32(3)})
    # dtype is part of the fingerprint even when bytes could collide
    assert pytree_hash(np.zeros(2, np.float32)) != pytree_hash(
        np.zeros(2, np.int32)
    )


# ------------------------------------------------------------ timing


def test_timed_phase_warmup_and_event(tmp_path):
    path = str(tmp_path / "t.jsonl")
    led = Ledger(path)
    order = []
    with timed_phase("phase_x", led, warmup=lambda: order.append("warm"),
                     n=7) as t:
        order.append("body")
    assert order == ["warm", "body"]
    assert t.seconds >= 0.0 and t.name == "phase_x"
    (ev,) = read_ledger(path)
    assert ev["event"] == "timing" and ev["phase"] == "phase_x"
    assert ev["n"] == 7 and ev["seconds"] == pytest.approx(t.seconds)


def test_timed_phase_without_ledger():
    with timed_phase("bare") as t:
        pass
    assert t.seconds >= 0.0


@pytest.mark.parametrize("phase", ["start", "stop"])
def test_maybe_trace_raises_when_profiler_fails(tmp_path, monkeypatch, phase):
    """A requested profile that cannot be captured fails the run instead
    of letting it exit 0 without a trace; no directory is a no-op."""
    from repro.obs import profile

    def broken(*_a, **_k):
        raise OSError("profiler backend unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace",
                        broken if phase == "start" else lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        broken if phase == "stop" else lambda: None)
    with pytest.raises(RuntimeError, match=f"failed to {phase}"):
        with profile.maybe_trace(str(tmp_path / "xprof")):
            pass
    with profile.maybe_trace(None):
        pass
