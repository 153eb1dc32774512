"""Operations and bytes from the application's shape, seeded inputs, and
the peak table."""
from __future__ import annotations

import numpy as np
import pytest

from bench.lib.registry import Bench, UnknownDevice


@pytest.fixture(scope="module")
def bench():
    return Bench()


def test_hotspot_work_by_hand(bench):
    cfg = bench.config("hotspot_1024")
    ops, nbytes = cfg.module.work(cfg.params)
    # 4 steps x (read t, read power, write t) x 4 MiB
    assert nbytes == 50_331_648
    assert ops == 14 * 1024 * 1024 * 4


def test_peaks_by_device_kind(bench):
    p = bench.peaks("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(UnknownDevice, match="TPU v9"):
        bench.peaks("TPU v9")


@pytest.mark.parametrize("name", ["hotspot_1024"])
def test_inputs_come_from_the_seed(bench, name):
    cfg = bench.config(name)
    a = cfg.module.inputs(cfg.params, np.random.default_rng(2**31 + 11))
    b = cfg.module.inputs(cfg.params, np.random.default_rng(2**31 + 11))
    c = cfg.module.inputs(cfg.params, np.random.default_rng(5))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    assert {k: v.shape for k, v in a.items()} == \
        {k: v.shape for k, v in c.items()}


@pytest.mark.parametrize("shape", [(5, 7), (8, 3)])
def test_hotspot_reference_by_cell_loops(bench, shape):
    """The vectorised reference against the update written cell by cell,
    with neighbours clamped at the edges as Rodinia's kernel does."""
    cfg = bench.config("hotspot_1024")
    p = {**cfg.params, "rows": shape[0], "cols": shape[1], "iterations": 3}
    inp = cfg.module.inputs(p, np.random.default_rng(2**31 + 9))
    k = p["coefficients"]
    t = inp["t"].astype(np.float64)
    rows, cols = shape
    for _ in range(p["iterations"]):
        new = np.empty_like(t)
        for r in range(rows):
            for c in range(cols):
                n, s = t[max(r - 1, 0), c], t[min(r + 1, rows - 1), c]
                w, e = t[r, max(c - 1, 0)], t[r, min(c + 1, cols - 1)]
                new[r, c] = t[r, c] + k["cap"] * (
                    inp["p"][r, c] + k["ry"] * (n + s - 2 * t[r, c])
                    + k["rx"] * (w + e - 2 * t[r, c])
                    + k["rz"] * (k["amb"] - t[r, c]))
        t = new
    got = cfg.module.reference(p, inp)
    assert np.max(np.abs(got - t)) / np.max(np.abs(t)) < 1e-6
