"""The benchmark's data files against the contract they are read by."""
import json
import re
import shutil

import pytest

from chipbench import checks, harness, manifest, reference

from chip_fixtures import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]


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
    assert set(limits["limits"]) == set(checks.NUMBERS)
    assert w["chips"] in (1, 4)
    assert len(w["why"]) <= 200


@pytest.mark.parametrize("conf", BENCH_JSON["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_run(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["name"] == conf["name"]
    assert cfg["reduced"] == conf["reduced"] == []
    assert len(cfg["source"]) <= 200
    assert cfg["precision"]["storage"] == "float32"
    assert cfg["precision"]["dot"] in reference.DOT_PRECISION


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


@pytest.mark.parametrize("conf", BENCH_JSON["configs"], ids=lambda c: c["name"])
def test_every_seed_gets_the_same_fleet_shape(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    seeds = [0, 7, 2**31 + 5, 12345678901234]
    mapped = [harness.program_seed(cfg, s) for s in seeds]
    assert mapped == [harness.program_seed(cfg, s) for s in seeds]
    assert len(set(mapped)) == len(seeds)
    for m in mapped:
        assert 0 < m < 2**31
        assert reference.client_sizes(cfg, m).max() == \
            cfg["data"]["largest_client"]
