"""Path set-up for the benchmark's tests, and the tiny-cell fixture."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]


@pytest.fixture
def tiny_root(tmp_path):
    from chip_fixtures import TINY_LIMITS, TINY_TRAFFIC, make_root

    return make_root(tmp_path, TINY_TRAFFIC, TINY_LIMITS)
