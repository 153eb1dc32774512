"""srad_v1 at 502x458: the reference against Rodinia's kernels written
pixel by pixel, the control, and runs through the harness at sizes a test
run can hold, sound and with the timed path broken underneath."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench.lib import harness
from bench.lib.registry import Bench
from bench.tests.helpers import SEED, faulty_vector

CONFIG, CELL = "srad_v1_502x458", "srad_v1_502x458.job"
SMALL = {"rows": 37, "cols": 29, "iterations": 4}


def small_bench() -> Bench:
    b = Bench()
    config = b.config

    def small_config(name):
        cfg = config(name)
        if name != CONFIG:
            return cfg
        return dataclasses.replace(cfg, params={**cfg.params, **SMALL})

    b.config = small_config
    return b


def rodinia(p: dict, image: np.ndarray) -> np.ndarray:
    """Rodinia 3.1 srad_v1's kernels, one thread (pixel) at a time, in
    float32: extract, then per iteration prepare, the reduce passes (the
    512-thread tree, a partial last block's remainder added by its last
    busy thread), the host's statistics, srad and srad2; then compress."""
    f = np.float32
    nr, nc, nt = p["rows"], p["cols"], 512
    ne = nr * nc
    img = image.astype(f).copy()
    i_n = [max(i - 1, 0) for i in range(nr)]
    i_s = [min(i + 1, nr - 1) for i in range(nr)]
    j_w = [max(j - 1, 0) for j in range(nc)]
    j_e = [min(j + 1, nc - 1) for j in range(nc)]
    for ei in range(ne):                                  # extract
        img[ei] = np.exp(img[ei] / f(255))
    for _ in range(p["iterations"]):
        sums = img.copy(), (img * img).astype(f)          # prepare
        no, mul, blocks = ne, 1, -(-ne // nt)
        while True:                                       # reduce passes
            for s in sums:
                for bx in range(blocks):
                    nf = nt - (blocks * nt - no) if bx == blocks - 1 else nt
                    ps = [s[(bx * nt + tx) * mul] for tx in range(nf)]
                    df = nt if nf == nt else max(
                        i for i in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                        if nf >= i)
                    i = 2
                    while i <= df:
                        for tx in range(df):
                            if (tx + 1) % i == 0:
                                ps[tx] = f(ps[tx] + ps[tx - i // 2])
                        i *= 2
                    acc = ps[df - 1]
                    for tx in range(df, nf):
                        acc = f(acc + ps[tx])
                    s[bx * mul * nt] = acc
            if blocks == 1:
                break
            no, mul, blocks = blocks, mul * nt, -(-blocks // nt)
        mean = sums[0][0] / f(ne)                         # host
        mean2 = mean * mean
        q0 = (sums[1][0] / f(ne) - mean2) / mean2
        d = {k: np.zeros(ne, f) for k in ("n", "s", "w", "e", "c")}
        for ei in range(ne):                              # srad
            row, col = ei % nr, ei // nr
            jc = img[ei]
            dn = img[i_n[row] + nr * col] - jc
            ds = img[i_s[row] + nr * col] - jc
            dw = img[row + nr * j_w[col]] - jc
            de = img[row + nr * j_e[col]] - jc
            g2 = (dn * dn + ds * ds + dw * dw + de * de) / (jc * jc)
            lap = (dn + ds + dw + de) / jc
            num = f(0.5) * g2 - f(1 / 16) * (lap * lap)
            den = f(1) + f(0.25) * lap
            qsqr = num / (den * den)
            den = (qsqr - q0) / (q0 * (f(1) + q0))
            c = f(1) / (f(1) + den)
            c = f(0) if c < 0 else f(1) if c > 1 else c
            for k, v in (("n", dn), ("s", ds), ("w", dw), ("e", de),
                         ("c", c)):
                d[k][ei] = v
        for ei in range(ne):                              # srad2
            row, col = ei % nr, ei // nr
            cs = d["c"][i_s[row] + nr * col]
            ce = d["c"][row + nr * j_e[col]]
            dd = (d["c"][ei] * d["n"][ei] + cs * d["s"][ei]
                  + d["c"][ei] * d["w"][ei] + ce * d["e"][ei])
            img[ei] = img[ei] + f(0.25 * p["lambda"]) * dd
    for ei in range(ne):                                  # compress
        img[ei] = np.log(img[ei]) * f(255)
    return img


@pytest.mark.parametrize("shape", [(12, 9), (23, 25)],
                         ids=["one_partial_block", "two_passes"])
def test_reference_by_rodinias_kernels(shape):
    """12 x 9 is one partial block of 108 pixels (a tree over 64, a
    remainder of 44); 23 x 25 is 575 pixels, two blocks and a second pass.
    The reference sums exactly, the kernels in float32."""
    cfg = Bench().config(CONFIG)
    p = {**cfg.params, "rows": shape[0], "cols": shape[1], "iterations": 3}
    inp = cfg.module.inputs(p, np.random.default_rng(2**31 + 9))
    want = rodinia(p, inp["I"])
    got = cfg.module.reference(p, inp)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-5


def test_work_by_hand():
    cfg = Bench().config(CONFIG)
    ops, nbytes = cfg.module.work(cfg.params)
    assert nbytes == 8 * 229_916 * 50 == 91_966_400
    assert ops == (43 * 50 + 4) * 229_916


def test_inputs_are_every_buffer_the_entry_takes():
    cfg = small_bench().config(CONFIG)
    inp = cfg.module.inputs(cfg.params, np.random.default_rng(SEED))
    entry = cfg.module.entry(cfg.params)
    kernels = [s.kernel for s in entry.chain.all_steps]
    touched = {n for k in kernels for n in (*k.reads, *k.writes)}
    assert set(inp) == touched
    assert set(np.unique(inp["I"])) <= set(range(256))


def test_control_fails():
    from bench.tests.test_control import _control
    for seed in (SEED, 7, 2**32 + 5):
        r = _control()(small_bench(), CELL, seed)
        assert not r["correct"]
        assert r["max_rel_err"] > 3 * r["limit"]


def test_sound_runs_are_correct(monkeypatch):
    """The per-layer readers read the whole process's counters, as in a
    benchmark run: start them from zero."""
    from repro.core import api, kernel
    api.cache_clear()
    monkeypatch.setattr(kernel, "_TOTALS", kernel.ChainStats())
    b = small_bench()
    r = harness.run_cell(b, CELL, SEED, 0.3, traced=False)
    assert r["correct"], r["check"]
    assert set(r["metrics"]) == {"setup_s", "job_ms"}
    assert r["check"]["max_rel_err"]["value"] < 1e-4
    r = harness.run_cell(b, CELL, SEED, 0.3, traced=True)
    assert r["correct"], r["check"]
    m = r["metrics"]
    assert m["host_reads.job"]["value"] == SMALL["iterations"]
    serial = 2 + 3 * SMALL["iterations"]
    assert m["serial_launch_pct.job"]["value"] == pytest.approx(
        100 * serial / (serial + 2 * SMALL["iterations"]))


def _one_iteration_short(monkeypatch):
    """Plant a fault under the timed path: the chain runs one iteration
    fewer than the configuration asks for."""
    from repro.core import cuda_suite
    build = cuda_suite.entry_srad_v1

    def short(rows, cols, iters, lam):
        e = build(rows, cols, iters, lam)
        return dataclasses.replace(
            e, chain=dataclasses.replace(e.chain, repeat=iters - 1))

    monkeypatch.setattr(cuda_suite, "entry_srad_v1", short)


@pytest.mark.parametrize("fault", ["one_iteration_short", "unchanged",
                                   "half", "altered"])
def test_broken_run_is_not_correct(fault, monkeypatch):
    if fault == "one_iteration_short":
        _one_iteration_short(monkeypatch)
    else:
        from repro.core import lower_vector
        monkeypatch.setattr(lower_vector, "run",
                            faulty_vector(fault, lower_vector.run))
    r = harness.run_cell(small_bench(), CELL, SEED, 0.2, traced=False)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]
    check = r["check"]["max_rel_err"]
    assert not check["value"] <= check["limit"]
