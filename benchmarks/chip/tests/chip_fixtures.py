"""A throwaway benchmark root with one tiny cell, for CPU runs of the
harness: the ``tiny`` task (16x16 images, a 5122-parameter CNN), 16
clients on 4 channels, 5-round experiments."""
import copy
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]

TINY_CELL = "tiny_u16.greedy"
# the keys of a ``--trace 0`` result line, in order, whatever the runner
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def tiny_config() -> dict:
    cfg = json.loads((BENCH / "configs" / "femnist_u1024_c8.json").read_text())
    cfg.update(name="tiny_u16_c4", task="tiny", n_clients=16, n_channels=4)
    cfg["model"] = dict(in_hw=16, in_ch=1, conv_channels=[8, 8], kernel=3,
                        hidden=[32], n_classes=10, extra_pool=False, z=5122)
    cfg["data"] = dict(cfg["data"], mu=200.0, beta=40.0, largest_client=268)
    return cfg


def make_root(tmp: pathlib.Path, traffic: dict, limits: dict) -> pathlib.Path:
    """A copy of the benchmark under ``tmp`` with the tiny cell added as
    files and entries only."""
    shutil.copytree(BENCH, tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = copy.deepcopy(bench)
    chip = tmp / "benchmarks" / "chip"
    (chip / "configs" / "tiny_u16_c4.json").write_text(json.dumps(tiny_config()))
    (chip / "traffic" / "tiny_greedy.json").write_text(json.dumps(traffic))
    (chip / "workloads" / f"{TINY_CELL}.json").write_text(
        json.dumps({"limits": limits}))
    bench["configs"].append({"name": "tiny_u16_c4", "source": "test",
                             "file": "benchmarks/chip/configs/tiny_u16_c4.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny_u16_c4",
                               "traffic": "tiny_greedy", "chips": 1,
                               "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


TINY_TRAFFIC = {"policy": "greedy", "rounds_per_experiment": 5,
                "with_eval": True, "reference_rounds": 3,
                "trace_experiments": 1}
TINY_LIMITS = {"decision_mismatch": 0, "energy_gap": 1e-4, "queue_gap": 1e-4,
               "loss_gap": 1e-4}
TINY_GA = dict(TINY_TRAFFIC, policy="compiled-ga", ga=dict(
    generations=30, population=32, p_crossover=0.8, p_mutation=0.08,
    iota=1.0, elitism=2, tournament=2, repair_infeasible=True))
