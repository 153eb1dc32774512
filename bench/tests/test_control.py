"""The control, the reference in bfloat16 in the program's place, comes out
as not correct at a size a test run can hold."""
from __future__ import annotations

import importlib.util
import os

import pytest

from bench.lib.registry import BENCH_DIR
from bench.tests.helpers import SEED, small_bench


def _control():
    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(BENCH_DIR, "control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.control


@pytest.mark.parametrize("cell", ["hotspot_1024.job"])
def test_control_fails(cell):
    for seed in (SEED, 7, 2**32 + 5):
        r = _control()(small_bench(), cell, seed)
        assert not r["correct"]
        assert r["max_rel_err"] > 3 * r["limit"]
