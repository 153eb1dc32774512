"""Job cells: a sound run is correct, and a run with the timed path broken
underneath is not (state unchanged, half the blocks, one answer altered)."""
from __future__ import annotations

import pytest

from bench.lib import harness
from bench.tests.helpers import SEED, faulty_vector, small_bench

CELLS = ["hotspot_1024.job"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = harness.run_cell(small_bench(), cell, SEED, 0.3, traced=False)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "notes", "check"]
    assert set(r["metrics"]) == {"setup_s", "job_ms"}
    assert r["metrics"]["job_ms"]["value"] > 0
    assert r["check"]["max_rel_err"]["value"] < 1e-5


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_run_is_not_correct(cell, fault, monkeypatch):
    from repro.core import lower_vector
    monkeypatch.setattr(lower_vector, "run",
                        faulty_vector(fault, lower_vector.run))
    r = harness.run_cell(small_bench(), cell, SEED, 0.2, traced=False)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]
    check = r["check"]["max_rel_err"]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_is_correct(cell):
    """The ``--trace 1`` path, off the chip: the CPU has no TPU plane, so
    the device readers find nothing and leave their metrics out."""
    r = harness.run_cell(small_bench(), cell, SEED, 0.3, traced=True)
    assert r["correct"], r["check"]
    assert "setup_compile_s" in r["metrics"]
    assert not {"setup_s", "job_ms"} & set(r["metrics"])
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r)[-1] == "check"


def test_notes_name_the_slowest_jobs_phases():
    r = harness.run_cell(small_bench(), "hotspot_1024.job", SEED, 0.3,
                         traced=False)
    slow = r["notes"]["slowest_job"]
    assert 0 <= slow["index"] < r["attempted"]
    assert set(slow) == {"index", "h2d_s", "call_s", "wait_s", "d2h_s"}
    assert sum(slow[f"{p}_s"] for p in harness.PHASES) >= \
        r["notes"]["job_s_median"] > 0
