"""``correct`` on the CPU at the tiny cell: true for the program as it is,
false for the control and for each fault a one-chip cell can have,
planted under the timed path (the harness's look for a chip skipped)."""
import dataclasses
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import checks, harness, manifest, reference

from chip_fixtures import (BENCH, RESULT_KEYS, ROOT, TINY_CELL, TINY_GA,
                           TINY_LIMITS, make_root)


def _run(root, seed=3):
    mf = manifest.Manifest(root)
    return harness.run_cell(mf, TINY_CELL, seed, 0.2, False,
                            time.perf_counter(), require_tpu=False)


def test_sound_program_is_correct(tiny_root):
    out = _run(tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out) == RESULT_KEYS
    assert set(out["metrics"]) == {"rounds_per_s", "setup_s", "peak_hbm_gb"}


def test_sound_genetic_search_is_correct(tmp_path):
    """The reference judges the search's decisions (``follow``): the
    program's decisions pass, the control's and an altered level do not."""
    root = make_root(tmp_path, TINY_GA, TINY_LIMITS)
    out = _run(root, seed=11)
    assert out["correct"], out["checks"]
    cell = manifest.Manifest(root).cell(TINY_CELL)
    for kw in ({"precision": "bfloat16"}, {"fault": "altered"}):
        bad = reference.Reference(cell.config, cell.traffic, 11, **kw).run(3)
        judge = reference.Reference(cell.config, cell.traffic, 11, follow=bad)
        assert not harness.within(checks.compare(bad, judge.run(3)),
                                 cell.limits), kw


def test_control_fails(tiny_root):
    """The reference in bfloat16, put in the program's place."""
    cell = manifest.Manifest(tiny_root).cell(TINY_CELL)
    sound = reference.Reference(cell.config, cell.traffic, 5).run(3)
    control = reference.Reference(cell.config, cell.traffic, 5,
                                  precision="bfloat16").run(3)
    assert not harness.within(checks.compare(control, sound), cell.limits)


def _half_batch(orig):
    def f(loss_fn, tau, batch_size, *a, **k):
        return orig(loss_fn, tau, batch_size // 2, *a, **k)
    return f


def _unchanged(orig):
    def f(loss_fn, tau, batch_size, params, *a, **k):
        stacked, g, s = orig(loss_fn, tau, batch_size, params, *a, **k)
        same = jax.tree_util.tree_map(
            lambda p, q: jnp.broadcast_to(p, q.shape), params, stacked)
        return same, g, s
    return f


def _altered_q(orig):
    def f(*a, **k):
        dec = orig(*a, **k)
        return dataclasses.replace(
            dec, q=jnp.where(dec.a > 0, 1 + dec.q % 8, 0).astype(dec.q.dtype))
    return f


@pytest.mark.parametrize("fault,target,wrap", [
    ("local SGD returns its state unchanged", "fleet_local_sgd", _unchanged),
    ("half of each batch left out", "fleet_local_sgd", _half_batch),
    ("quantization level altered where decided", "decide", _altered_q),
])
def test_fault_under_the_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                   fault, target, wrap):
    from repro.sim import engine

    owner = engine if target == "fleet_local_sgd" else engine.fast_policy
    monkeypatch.setattr(owner, target, wrap(getattr(owner, target)))
    out = _run(tiny_root)
    assert not out["correct"], (fault, out["checks"])


def _cli(cwd, *extra):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "femnist_u1024.greedy", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_no_tpu_no_result():
    r = _cli(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == "", r.stderr[-2000:]
    assert "no result" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _cli(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
