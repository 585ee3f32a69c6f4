"""Readings that the correctness limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,3

For each seed, in one process: the program's experiment (as the timed
window runs it) against the plain reference, and the reference's
stand-ins for the program against the same reference: the control
(``bfloat16``) and each planted fault (``unchanged``: the global model
never moves; ``half_batch``: local SGD on half of each batch;
``altered``: one scheduled client's quantization level changed). Prints
one JSON line per seed with every number of ``checks.NUMBERS``, and the
followed rounds' test loss of the program and of the reference. For
cells of the fleet-scan runner (``runners/fleet_scan.py``); needs the
chip, like ``run.py``.
"""
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds the reference follows (default: the "
                         "traffic's reference_rounds)")
    args = ap.parse_args()

    from chipbench import checks, harness, manifest, reference
    from repro.launch.compile_cache import enable_compile_cache

    mf = manifest.Manifest(ROOT)
    cell = mf.cell(args.workload)
    enable_compile_cache()
    harness.configure_cache()
    import gc

    import jax

    harness.device_info(True, cell.chips)
    cfg, tr, fleet = cell.config, cell.traffic, cell.runner
    n = args.rounds or tr["reference_rounds"]
    for arg in (int(s) for s in args.seeds.split(",")):
        built = fleet.build(cell, arg)
        seed = built.seed
        prog = built.outputs(built.experiment())
        del built
        gc.collect()
        jax.clear_caches()
        follows = tr["policy"] == "compiled-ga"

        losses = {}

        def judged(out):
            ref = reference.Reference(cfg, tr, seed,
                                      follow=out if follows else None).run(n)
            losses.setdefault("reference", ref["loss"].tolist())
            return checks.compare(out, ref)

        row = {"seed": arg, "program_seed": seed, "program": judged(prog),
               "control": judged(reference.Reference(
                   cfg, tr, seed, precision="bfloat16").run(n))}
        losses["program"] = prog["loss"][:n].tolist()
        for fault in reference.FAULTS:
            row[fault] = judged(reference.Reference(
                cfg, tr, seed, fault=fault).run(n))
        row["loss"] = losses
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
