"""The benchmark's data files against the contract they are read by."""
import json
import re
import shutil
import time

import numpy as np
import pytest

from chipbench import flops, harness, manifest, reference

from chip_fixtures import BENCH, RESULT_KEYS, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]
FLEET_CONFIGS = [c for c in BENCH_JSON["configs"] if json.loads(
    (ROOT / c["file"]).read_text())["runner"] == "fleet_scan"]


def test_top_level_keys_and_command():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert BENCH_JSON["paths"] == ["benchmarks/chip"]
    assert BENCH_JSON["command"][1].startswith("benchmarks/chip/")
    assert isinstance(BENCH_JSON["run_seconds"], int)
    assert 1 <= BENCH_JSON["run_seconds"] <= 51


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_are_allowed_and_unique(key):
    names = [e["name"] for e in BENCH_JSON[key]]
    assert len(set(names)) == len(names)
    for n in names:
        assert NAME.match(n), n


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_unit_and_fields(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("w", BENCH_JSON["workloads"], ids=lambda w: w["name"])
def test_workload_files_exist(w):
    conf = {c["name"]: c for c in BENCH_JSON["configs"]}[w["config"]]
    assert (ROOT / conf["file"]).is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
    runner = manifest.Manifest(ROOT).cell(w["name"]).runner
    assert set(limits["limits"]) == set(runner.NUMBERS)
    assert w["chips"] in (1, 4)
    assert len(w["why"]) <= 200


@pytest.mark.parametrize("conf", BENCH_JSON["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_run(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["name"] == conf["name"]
    assert cfg["reduced"] == conf["reduced"] == []
    assert len(cfg["source"]) <= 200
    manifest.Manifest(ROOT).runner(cfg["runner"]).check_config(cfg)


@pytest.mark.parametrize("metric", BENCH_JSON["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_layer_moves_and_reader(metric):
    e2e = {m["name"]: m for m in BENCH_JSON["end_to_end"]}
    assert metric["layer"] and "\n" not in metric["layer"]
    moved = e2e[metric["moves"]]
    for w in BENCH_JSON["workloads"]:
        if manifest.Manifest.reports(metric, w["name"]):
            assert manifest.Manifest.reports(moved, w["name"])
    assert callable(manifest.Manifest(ROOT).reader(metric["name"]))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    mf = manifest.Manifest(ROOT)
    for w in mf.names("workloads"):
        cell = mf.cell(w)
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_unknown_device_has_no_peaks():
    assert manifest.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        manifest.load_peaks("TPU v99")


def test_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    """A later cell or metric is a new file plus a new entry: the harness
    finds both by name, and no file that exists is edited."""
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH_JSON))
    (chip / "traffic" / "greedy_r50.json").write_text(json.dumps(
        dict(json.loads((chip / "traffic" / "greedy.json").read_text()),
             rounds_per_experiment=50)))
    (chip / "workloads" / "femnist_u1024.greedy_r50.json").write_text(
        (chip / "workloads" / "femnist_u1024.greedy.json").read_text())
    (chip / "metrics" / "ops_per_round.py").write_text(
        "def read(ctx):\n    return len(ctx.trace.ops) / ctx.rounds\n")
    bench["workloads"].append({"name": "femnist_u1024.greedy_r50",
                               "config": "femnist_u1024_c8",
                               "traffic": "greedy_r50", "chips": 1,
                               "why": "50-round experiments"})
    bench["per_layer"].append({"name": "ops_per_round", "unit": "ops/round",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "rounds_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mf = manifest.Manifest(tmp_path)
    cell = mf.cell("femnist_u1024.greedy_r50")
    assert cell.traffic["rounds_per_experiment"] == 50
    assert "ops_per_round" in [m["name"] for m in cell.per_layer]
    assert callable(mf.reader("ops_per_round"))
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("conf", FLEET_CONFIGS, ids=lambda c: c["name"])
def test_every_seed_gets_the_same_fleet_shape(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    fleet = manifest.Manifest(ROOT).runner("fleet_scan")
    seeds = [0, 7, 2**31 + 5, 12345678901234]
    mapped = [fleet.program_seed(cfg, s) for s in seeds]
    assert mapped == [fleet.program_seed(cfg, s) for s in seeds]
    assert len(set(mapped)) == len(seeds)
    for m in mapped:
        assert 0 < m < 2**31
        assert reference.client_sizes(cfg, m).max() == \
            cfg["data"]["largest_client"]


# A runner of another program than the fleet scan: STEPS jitted steps
# x <- tanh(x @ w) under one named scope, and its plain float32 reference
# in numpy. ``{step}`` is the step's body, so that a fault can be planted.
TOY_RUNNER = '''"""Toy runner: jitted matmul steps, a numpy reference."""
import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("max_rel_gap",)


def check_config(cfg):
    if cfg["precision"] != "float32":
        raise ValueError("the toy runs float32 only")


def _inputs(cfg, seed):
    rng = np.random.default_rng(seed % 2**64)
    n = cfg["n"]
    x = rng.standard_normal((n, n), dtype=np.float32)
    w = rng.standard_normal((n, n), dtype=np.float32) / np.float32(n ** 0.5)
    return x, w


def _step(x, w):
    with jax.named_scope("toy_step"):
        return {step}


class Toy:
    def __init__(self, cell, seed):
        self.rounds = cell.traffic["steps"]
        self._n = cell.config["n"]
        self._args = tuple(jax.device_put(a)
                           for a in _inputs(cell.config, seed))
        self._run = jax.jit(lambda x, w: jax.lax.fori_loop(
            0, self.rounds, lambda i, x: _step(x, w), x))

    def compile(self):
        return self._run.lower(*self._args).compile()

    def experiment(self):
        return np.asarray(self._run(*self._args))

    def faults(self, res, warm):
        return [] if np.array_equal(res, warm) else ["differs from warm-up"]

    def useful_flops(self, res):
        return 2 * self._n ** 3 * self.rounds

    def outputs(self, res):
        return {{"x": res}}


def build(cell, seed):
    return Toy(cell, seed)


def check(cell, seed, outputs):
    x, w = _inputs(cell.config, seed)
    for _ in range(cell.traffic["steps"]):
        x = np.tanh(x @ w)
    return {{"max_rel_gap": float(np.max(np.abs(outputs["x"] - x))
                                  / np.max(np.abs(x)))}}
'''
TOY_STEPS = {"sound": "jnp.tanh(x @ w)",
             "state left unchanged": "x",
             "answer altered where produced":
                 "jnp.tanh(x @ w).at[0, 0].add(1)"}


def _add_toy(root, step):
    """The toy runner, its configuration, traffic and cell, added to a copy
    of the benchmark under ``root`` as new files and entries."""
    chip = root / "benchmarks" / "chip"
    (chip / "runners" / "toy_matmul.py").write_text(
        TOY_RUNNER.format(step=step))
    (chip / "configs" / "toy_n64.json").write_text(json.dumps(
        {"name": "toy_n64", "runner": "toy_matmul", "source": "test",
         "precision": "float32", "n": 64, "reduced": []}))
    (chip / "traffic" / "toy_steps.json").write_text(json.dumps(
        {"steps": 8, "trace_experiments": 1}))
    (chip / "workloads" / "toy_n64.steps.json").write_text(json.dumps(
        {"limits": {"max_rel_gap": 1e-4}}))
    bench = json.loads(json.dumps(BENCH_JSON))
    bench["configs"].append({"name": "toy_n64", "source": "test",
                             "file": "benchmarks/chip/configs/toy_n64.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy_n64.steps", "config": "toy_n64",
                               "traffic": "toy_steps", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("step", sorted(TOY_STEPS))
def test_new_runner_is_files_and_entries_only(tmp_path, step):
    """Another program is a runner file plus a configuration, traffic and
    cell: the harness runs it, the runner's own check decides ``correct``
    (a planted fault makes it false), and no file that exists is edited."""
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    _add_toy(tmp_path, TOY_STEPS[step])
    mf = manifest.Manifest(tmp_path)
    out = harness.run_cell(mf, "toy_n64.steps", 3, 0.2, False,
                           time.perf_counter(), require_tpu=False)
    assert list(out) == RESULT_KEYS
    assert list(out["metrics"]) == [m["name"]
                                    for m in BENCH_JSON["end_to_end"]]
    assert list(out["checks"]) == ["max_rel_gap", "failed_experiments"]
    assert out["correct"] is (step == "sound"), out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("runner", [None, "no_such_runner"])
def test_config_without_a_known_runner_fails_in_manifest_cell(tmp_path,
                                                              runner):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    _add_toy(tmp_path, TOY_STEPS["sound"])
    path = tmp_path / "benchmarks" / "chip" / "configs" / "toy_n64.json"
    cfg = json.loads(path.read_text())
    if runner is None:
        del cfg["runner"]
    else:
        cfg["runner"] = runner
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="runner"):
        manifest.Manifest(tmp_path).cell("toy_n64.steps")


def test_fleet_useful_flops_are_the_per_round_sum():
    """``round_mfu``'s count: one call over an experiment's sums equals
    ``flops.round_useful_flops`` summed round by round, to the FLOP."""
    cell = manifest.Manifest(ROOT).cell("femnist_u1024.greedy")
    cfg, t = cell.config, cell.config["training"]
    n_sched = np.random.default_rng(0).integers(0, 9, size=100,
                                                dtype=np.int32)
    per_round = sum(flops.round_useful_flops(
        cfg["model"], t["tau"], t["batch_size"], int(n), cfg["data"]["n_test"])
        for n in n_sched)
    got = cell.runner.useful_flops(cfg, cell.traffic, n_sched)
    assert type(got) is int and got == per_round
