"""Differential conformance harness: the 24-kernel backend-agreement matrix.

The per-cell tests here are the tier-1 face of the acceptance criterion:
every suite kernel passes its NumPy oracle under loop/vector/shard/
shard_vector, with the shard legs bit-identical to their inner lowering
wherever ``combines`` is exact.  The full variant sweep (geometry
refactorizations, grain tails, dtypes, device counts) runs in the CI
conformance-gate job via ``python -m repro.core.conformance``; a
representative slice runs here so regressions surface in `pytest` too.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import conformance
from repro.core.backends import unregister_backend
from repro.core.conformance import (
    Cell,
    build_cases,
    grid_variants,
    report_to_json,
    run_cell,
    run_matrix,
)

CASES = {c.name: c for c in build_cases()}
BACKENDS = ("loop", "vector", "shard", "shard_vector")


def _base_cell(case, backend, *, grain=1, devices=None):
    entry = case.make(case.dtypes[0])
    cell, out = run_cell(entry, case, backend, case.dtypes[0], entry.grid,
                         entry.block, grain, devices)
    return entry, cell, out


# --- the matrix: every kernel x every required backend -----------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", CASES.values(), ids=lambda c: c.name)
def test_matrix_base_cell(case, backend):
    entry, cell, out = _base_cell(case, backend)
    assert cell.status == "pass", f"{cell.label()}: {cell.detail}"
    if backend in ("shard", "shard_vector") and case.exact_shard:
        anchor = conformance.BIT_ANCHOR[backend]
        _, _, anchor_out = _base_cell(case, anchor)
        for k, v in out.items():
            if k in entry.nondeterministic_shard:
                continue
            assert (np.asarray(v).tobytes()
                    == np.asarray(anchor_out[k]).tobytes()), (
                f"{case.name}: {backend} buffer {k!r} not bit-identical "
                f"to {anchor} at device_count={jax.device_count()}")


# --- variant axes: a representative slice ------------------------------------
@pytest.mark.parametrize("name", ["vecadd", "reduce_shared", "histogram"])
@pytest.mark.parametrize("backend", ["loop", "vector", "shard"])
def test_grid_refactorization_invariant(name, backend):
    """2-D/3-D Dim3 launches of a linearized kernel == the 1-D launch."""
    case = CASES[name]
    tag = case.dtypes[0]
    entry = case.make(tag)
    variants = grid_variants(entry.grid)
    assert variants, f"{name}: grid {entry.grid} has no factorizations"
    base_cell_, base_out = run_cell(entry, case, backend, tag, entry.grid,
                                    entry.block, 1, None)
    assert base_cell_.status == "pass"
    for gv in variants:
        cell, out = run_cell(entry, case, backend, tag, gv, entry.block, 1,
                             None)
        assert cell.status == "pass", f"{cell.label()}: {cell.detail}"
        for k in out:
            assert (np.asarray(out[k]).tobytes()
                    == np.asarray(base_out[k]).tobytes()), (
                f"{name}/{backend}: grid {gv} diverges from {entry.grid} "
                f"on {k!r}")


@pytest.mark.parametrize("name", ["vecadd", "scan_block", "needle_nw",
                                  "bfs_frontier"])
def test_grain_tail_invariant(name):
    """grain=3 leaves non-multiple tails in every fetch loop; results may
    not change (the masked-tail regression surface of the shard backend)."""
    case = CASES[name]
    tag = case.dtypes[0]
    entry = case.make(tag)
    for backend in ("loop", "shard"):
        _, out1 = run_cell(entry, case, backend, tag, entry.grid,
                           entry.block, 1, None)
        cell, out3 = run_cell(entry, case, backend, tag, entry.grid,
                              entry.block, 3, None)
        assert cell.status == "pass", f"{cell.label()}: {cell.detail}"
        for k in out1:
            if k in entry.nondeterministic_shard:
                continue
            assert (np.asarray(out1[k]).tobytes()
                    == np.asarray(out3[k]).tobytes()), (
                f"{name}/{backend}: grain=3 diverges on {k!r}")


@pytest.mark.parametrize("name,tag", [
    ("vecadd", "f64"), ("vecadd", "i32"), ("reduce_shared", "f64"),
    ("transpose_tiled", "i32"), ("pathfinder", "f32"), ("pathfinder", "f64"),
    ("needle_nw", "f32"),
])
@pytest.mark.parametrize("backend", ["loop", "vector"])
def test_dtype_variants(name, tag, backend):
    case = CASES[name]
    assert tag in case.dtypes
    entry = case.make(tag)
    cell, _ = run_cell(entry, case, backend, tag, entry.grid, entry.block,
                       1, None)
    assert cell.status == "pass", f"{cell.label()}: {cell.detail}"


# --- the device-resident replay leg (ISSUE 5) --------------------------------
def test_chain_cases_grow_mode_cells():
    """Every chain case sweeps device_resident + graph replay-mode cells,
    bit-anchored on the same backend's host-hop run."""
    rep = run_matrix(cases=[CASES["pathfinder"]],
                     backends=("loop", "vector"), variants=True)
    by_mode = {}
    for c in rep.cells:
        by_mode.setdefault(c.mode, []).append(c)
    assert set(by_mode) == {"host", "device_resident", "graph", "optimized",
                            "frontend"}
    assert not rep.disagreements
    for mode in ("device_resident", "graph", "optimized", "frontend"):
        assert {c.backend for c in by_mode[mode]} == {"loop", "vector"}
        for c in by_mode[mode]:
            assert c.anchor == f"{c.backend}/host"
            assert c.bit_required and c.bit_identical, c.label()


def test_single_launch_cases_have_no_replay_mode_cells():
    """No chain -> no replay legs; the optimized + frontend legs still
    run (vecadd has a .cu corpus source)."""
    rep = run_matrix(cases=[CASES["vecadd"]], backends=("loop",),
                     variants=True)
    assert {c.mode for c in rep.cells} == {"host", "optimized", "frontend"}


def test_mode_axis_in_matrix_json():
    rep = run_matrix(cases=[CASES["needle_nw"]], backends=("loop",),
                     variants=True)
    js = report_to_json(rep)
    modes = {c["mode"] for c in js["cells"]}
    assert {"host", "device_resident", "graph"} <= modes
    labeled = [c for c in rep.cells if c.mode == "graph"]
    assert labeled and "mode=graph" in labeled[0].label()


def test_mode_cell_detects_divergent_device_replay():
    """A device replay whose bits drift from host-hop must fail the cell
    (the gate self-test for the new axis)."""
    import dataclasses as dc
    case = CASES["needle_nw"]
    base = case.make("i32")
    chain = base.chain
    # a poisoned update hook: advances the diagonal by 2, desyncing the
    # device-resident replay from the host-hop one
    bad_step = dc.replace(chain.steps[0],
                          update=lambda b: {"diag": b["diag"] + 2})
    bad_entry = dc.replace(base, chain=dc.replace(chain,
                                                  steps=(bad_step,)))
    bad_case = dc.replace(case, make=lambda tag: bad_entry)
    rep = run_matrix(cases=[bad_case], backends=("loop",), variants=True)
    bad_cells = [c for c in rep.cells
                 if c.mode in ("device_resident", "graph")]
    assert bad_cells and all(c.status == "fail" for c in bad_cells)
    assert any("bits differ from host-hop" in c.detail
               or "oracle mismatch" in c.detail for c in bad_cells)
    # the optimized leg replays the same (poisoned) host path on both
    # sides, so it stays bit-identical - the poison is not a fusion bug
    opt = [c for c in rep.cells if c.mode == "optimized"]
    assert opt and all(c.status == "pass" for c in opt)


# --- the report --------------------------------------------------------------
def test_matrix_report_structure():
    cases = [CASES["vecadd"], CASES["bfs_frontier"]]
    rep = run_matrix(cases=cases, backends=("loop", "naive", "shard"),
                     variants=False)
    assert rep.n_kernels == 2
    assert not rep.disagreements
    js = report_to_json(rep)
    assert js["meta"]["n_kernels"] == 2
    assert js["meta"]["backends"] == ["loop", "naive", "shard"]
    assert js["summary"]["loop"]["pass"] == 2
    # naive cannot run bfs (warp) -> an unsupport cell, not a disagreement
    assert js["summary"]["naive"]["unsupport"] == 1
    assert js["disagreements"] == []
    assert len(js["cells"]) == len(rep.cells)
    assert js["kernels"]["bfs_frontier"]["rodinia"] == "bfs"
    # shard cells carry their bit-anchor verdict
    shard_cells = [c for c in rep.cells if c.backend == "shard"]
    assert all(c.anchor == "loop" and c.bit_identical for c in shard_cells)


def test_matrix_detects_disagreement():
    """A harness that cannot flag a broken backend verifies nothing."""
    conformance._register_broken_backend()
    try:
        rep = run_matrix(cases=[CASES["vecadd"]],
                         backends=("loop", "broken"), variants=False)
        assert len(rep.disagreements) == 1
        cell = rep.disagreements[0]
        assert cell.backend == "broken" and cell.status == "fail"
        assert "oracle mismatch" in cell.detail
        assert report_to_json(rep)["disagreements"]
    finally:
        unregister_backend("broken")


def test_skip_cell_for_unavailable_device_count():
    too_many = jax.device_count() + 1
    rep = run_matrix(cases=[CASES["vecadd"]], backends=("shard",),
                     device_counts=(1, too_many), variants=False)
    statuses = {c.devices: c.status for c in rep.cells}
    assert statuses[1] == "pass"
    assert statuses[too_many] == "skip"
    assert not rep.disagreements          # skips never count as failures


def test_cell_label_roundtrip():
    c = Cell(kernel="k", backend="shard", grid=(4, 2, 1), block=(64, 1, 1),
             dtype="f32", grain=3, devices=2, status="pass")
    assert "k/shard@dev2" in c.label() and "grain=3" in c.label()


# --- real multi-device conformance, even under a 1-device parent -------------
_CHILD = r"""
import jax
assert jax.device_count() == 4, jax.device_count()
import numpy as np
from repro.core.conformance import build_cases, run_cell, run_matrix
names = {"bfs_frontier", "backprop_layer", "lud_diag"}
cases = [c for c in build_cases() if c.name in names]
rep = run_matrix(cases=cases, backends=("loop", "vector", "shard",
                                        "shard_vector"),
                 device_counts=(1, 4), variants=False)
assert len(rep.cells) == 3 * (2 + 2 * 2), len(rep.cells)
bad = [c.label() + ": " + c.detail for c in rep.disagreements]
assert not bad, bad
# the multi-device legs really ran and owed (and met) bit-identity
multi = [c for c in rep.cells if c.devices == 4]
assert multi and all(c.status == "pass" and c.bit_identical for c in multi)
# device-resident chain replay at genuine 4-way sharding: bit-identical
# to the shard host-hop run outside the stop-poll-cadence scratch
case = next(c for c in build_cases() if c.name == "bfs_frontier")
entry = case.make("i32")
hc, ho = run_cell(entry, case, "shard", "i32", entry.grid, entry.block,
                  1, 4)
dc, do = run_cell(entry, case, "shard", "i32", entry.grid, entry.block,
                  1, 4, "device_resident")
assert hc.status == "pass" and dc.status == "pass", (hc.detail, dc.detail)
skip = set(entry.iteration_state) | set(entry.nondeterministic_shard)
for k in do:
    if k not in skip:
        assert (np.asarray(do[k]).tobytes()
                == np.asarray(ho[k]).tobytes()), k
print("child-ok")
"""


def test_multidevice_conformance_subprocess():
    """The Rodinia-mini shard legs at genuine 4-way sharding."""
    if jax.device_count() >= 4:      # multidevice CI job covers it in-process
        pytest.skip("parent already multi-device")
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), os.pardir, "src")]
            + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
    )
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "child-ok" in proc.stdout
