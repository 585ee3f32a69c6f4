"""The chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Prints the run's result as one JSON object on the last line of standard
output, and each number compared with the reference beside its limit as
the last lines of standard error. Exits non-zero, printing no result,
where JAX finds no TPU (or fewer chips than the cell asks for) and where
the program is not beside the benchmark.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness, manifest
    from repro.launch.compile_cache import enable_compile_cache

    mf = manifest.Manifest(ROOT)
    # an unknown cell, or a configuration naming no known runner, fails
    # here, before JAX starts
    mf.cell(args.workload)
    enable_compile_cache()
    harness.configure_cache()
    try:
        out = harness.run_cell(mf, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START - time.time()
                               + time.perf_counter())
    except harness.NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
