"""Differential cross-backend conformance harness.

CuPBoP's headline claim is *coverage* - 69.6% of Rodinia running unmodified
- and the way Polygeist-style transpilers validate coverage is differential:
run every workload under every lowering and demand agreement.  This module
makes that a first-class, machine-checkable property of the repo:

* a declarative :class:`ConformanceCase` registry pairs every
  ``cuda_suite`` kernel with its pure-NumPy oracle and declares which
  *variant axes* apply to it - alternate ``Dim3`` grid factorizations
  (2-D/3-D launches of linearized kernels must be invariant), grain sizes
  whose fetch loops leave non-multiple tails, extra dtypes (f32/f64/i32)
  for the dtype-polymorphic kernels, and forced device counts for the
  multi-device backends;
* :func:`run_matrix` sweeps backend x grid/block geometry x dtype x grain
  x devices x *replay mode* - LaunchChain workloads run a
  ``device_resident`` leg (on-device update hooks, stop flags polled
  every k iterations) and a ``graph`` leg (iterations captured once and
  replayed as fused jitted dispatches) that must be bit-identical to the
  same backend's per-iteration host-hop replay on every buffer except
  declared ``iteration_state`` scratch - checking every cell against the
  oracle (tolerance banded by dtype and per-case ``tol``) **and**
  against an anchor backend's bits:
  ``shard`` must be bit-identical to ``loop`` (and ``shard_vector`` to
  ``vector``) wherever the kernel's ``combines`` declaration is exact,
  because the shard backend replays the same inner lowering per block
  range - a bit difference there is a scheduler/combine bug, not float
  noise.  ``loop_nowarp``/``naive`` are the loop lowering restricted, so
  they owe bit-identity whenever they support the kernel at all;
* the result is a machine-readable matrix report
  (:func:`report_to_json`) with per-cell status and a ``disagreements``
  list; the CLI (``python -m repro.core.conformance --json out.json``)
  exits non-zero on any disagreement, which is what the CI
  conformance-gate job enforces (the JSON uploads as a workflow
  artifact).  ``--inject-disagreement`` registers a deliberately broken
  backend so CI can prove the gate trips.

f64 cells run under ``jax.enable_x64`` so the sweep works in
a default-configured process without flipping global state for f32 cells.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cuda_suite
from repro.core.backends import backend_names, get_backend
from repro.core.cuda_suite import SuiteEntry, run_entry
from repro.core.kernel import UnsupportedKernel

#: oracle tolerance floor per dtype tag (a case's own ``tol`` can widen it)
DTYPE_TOL = {"f32": 2e-5, "f64": 1e-12, "i32": 0.0}

#: which single-device backend a backend must bit-match, where exact
BIT_ANCHOR = {"shard": "loop", "shard_vector": "vector",
              "loop_nowarp": "loop", "naive": "loop"}

#: backends that sweep the geometry/grain variant axes (the fetch-loop and
#: block-range schedulers live here; naive/loop_nowarp/pallas share them)
VARIANT_BACKENDS = ("loop", "vector", "shard")

#: backends that sweep the extra-dtype axis
DTYPE_BACKENDS = ("loop", "vector")

#: backends that sweep the graph-captured chain-replay mode (the fused
#: replay jits every captured iteration; the single-device lowerings keep
#: that cell affordable, and the shard legs are covered by "device" mode)
GRAPH_MODE_BACKENDS = ("loop", "vector")

#: backends that sweep the barrier-fission optimizer mode: every kernel
#: re-runs with ``optimize=True`` and owes FULL bit-identity to the same
#: backend's unoptimized cell - fusion is pure stage composition, so any
#: bit drift means the optimizer broke semantics (core/optimize.py)
OPTIMIZED_BACKENDS = ("loop", "vector")

#: backends that sweep the CUDA-C frontend mode: kernels with a ``.cu``
#: corpus source (repro/frontend/corpus) re-run as their *translated*
#: twin and owe FULL bit-identity to the same backend's hand-written
#: host cell - the executable form of "ingests CUDA source without
#: changing semantics" (repro.frontend)
FRONTEND_BACKENDS = ("loop", "vector")


def _frontend_corpus() -> tuple[str, ...]:
    from repro.frontend.suite import CORPUS
    return CORPUS


@dataclasses.dataclass(frozen=True)
class ConformanceCase:
    """One suite kernel's conformance declaration.

    ``make(dtype_tag)`` builds the :class:`SuiteEntry` for that dtype (the
    first tag in ``dtypes`` is the suite's natural dtype and returns the
    shared base entry, so launch-cache warmth carries across cells);
    ``exact_shard`` declares whether the kernel's ``combines`` modes are
    exact merges (integer, max/min, owned-slice, or sums of disjoint
    writes into zeroed buffers), i.e. whether the shard legs owe
    bit-identity to their inner lowering.
    """

    name: str
    make: Callable[[str], SuiteEntry]
    dtypes: tuple[str, ...] = ("f32",)
    grains: tuple[int, ...] = (1, 3)
    exact_shard: bool = True


@dataclasses.dataclass
class Cell:
    """One matrix cell: a (kernel, backend, geometry, dtype, ...) run.

    ``mode`` is the replay axis: ``"host"`` (per-iteration host-hop
    baseline), ``"device_resident"`` (on-device updates, k-batched stop
    polls), ``"graph"`` (graph-captured fused replay), ``"optimized"``
    (the host path with the barrier-fission pass on, owing full
    bit-identity to the unoptimized host cell), or ``"frontend"`` (the
    kernel's ``.cu`` corpus source translated by :mod:`repro.frontend`,
    owing full bit-identity to the hand-written host cell).
    """

    kernel: str
    backend: str
    grid: tuple
    block: tuple
    dtype: str
    grain: int
    devices: int | None
    status: str                       # pass | fail | unsupport | skip
    mode: str = "host"
    max_abs_err: float | None = None
    anchor: str | None = None
    bit_required: bool = False
    bit_identical: bool | None = None
    detail: str = ""

    def label(self) -> str:
        dev = "" if self.devices is None else f"@dev{self.devices}"
        mode = "" if self.mode == "host" else f" mode={self.mode}"
        return (f"{self.kernel}/{self.backend}{dev} grid={self.grid} "
                f"block={self.block} {self.dtype} grain={self.grain}"
                f"{mode}")


@dataclasses.dataclass
class Report:
    cells: list[Cell]
    n_kernels: int
    backends: tuple[str, ...]

    @property
    def disagreements(self) -> list[Cell]:
        return [c for c in self.cells if c.status == "fail"]

    def summary(self) -> dict:
        out: dict[str, dict[str, int]] = {}
        for c in self.cells:
            row = out.setdefault(c.backend,
                                 {"pass": 0, "fail": 0, "unsupport": 0,
                                  "skip": 0})
            row[c.status] += 1
        return out


# --------------------------------------------------------------------------
# dtype helpers + variant entry builders.  Base entries come verbatim from
# build_suite(); these rebuild the dtype-polymorphic kernels at other dtypes
# with matching args and oracle.
# --------------------------------------------------------------------------
def _dt(tag: str):
    return {"f32": jnp.float32, "f64": jnp.float64, "i32": jnp.int32}[tag]


def _np_dt(tag: str):
    return {"f32": np.float32, "f64": np.float64, "i32": np.int32}[tag]


def _fvals(r, shape, tag):
    if tag == "i32":
        return r.integers(-50, 50, shape).astype(np.int32)
    return r.standard_normal(shape).astype(_np_dt(tag))


_BASE: dict[str, SuiteEntry] | None = None


def _base(name: str) -> SuiteEntry:
    global _BASE
    if _BASE is None:
        _BASE = {e.name: e for e in cuda_suite.build_suite(scale=1)}
    return _BASE[name]


def _mk_vecadd(tag: str) -> SuiteEntry:
    n, block = 1024, 128
    k = cuda_suite.make_vecadd(n)
    return SuiteEntry(
        "vecadd", ("spmd",), k, -(-n // block), block, None,
        lambda r: {"a": _fvals(r, n, tag), "b": _fvals(r, n, tag),
                   "c": np.zeros(n, _np_dt(tag))},
        lambda a: {"c": a["a"] + a["b"]})


def _mk_reduce_shared(tag: str) -> SuiteEntry:
    n, b = 1024, 128
    k = cuda_suite.make_reduce_shared(n, b, dtype=_dt(tag))
    return SuiteEntry(
        "reduce_shared", ("barrier",), k, n // b, b, None,
        lambda r: {"x": _fvals(r, n, tag),
                   "out": np.zeros(n // b, _np_dt(tag))},
        lambda a: {"out": a["x"].reshape(-1, b).sum(1)})


def _mk_reduce_warp(tag: str) -> SuiteEntry:
    n, b = 1024, 128
    k = cuda_suite.make_reduce_warp(n, b, dtype=_dt(tag))
    return SuiteEntry(
        "reduce_warp", ("warp",), k, n // b, b, None,
        lambda r: {"x": _fvals(r, n, tag),
                   "out": np.zeros(n // b, _np_dt(tag))},
        lambda a: {"out": a["x"].reshape(-1, b).sum(1)})


def _mk_matmul(tag: str) -> SuiteEntry:
    mm = 16
    k = cuda_suite.make_matmul_tiled(mm, mm, mm, tile=8, dtype=_dt(tag))
    return SuiteEntry(
        "matmul_tiled", ("barrier", "demotion"), k, (mm // 8) ** 2, 64,
        None,
        lambda r: {"a": _fvals(r, (mm, mm), tag),
                   "b": _fvals(r, (mm, mm), tag),
                   "c": np.zeros((mm, mm), _np_dt(tag))},
        lambda a: {"c": a["a"] @ a["b"]})


def _mk_stencil1d(tag: str) -> SuiteEntry:
    n, b = 1024, 128
    k = cuda_suite.make_stencil1d(n, b, dtype=_dt(tag))
    idx = np.arange(n)
    return SuiteEntry(
        "stencil1d", ("barrier",), k, n // b, b, None,
        lambda r: {"x": _fvals(r, n, tag), "y": np.zeros(n, _np_dt(tag))},
        lambda a: {"y": (0.25 * a["x"][np.clip(idx - 1, 0, None)]
                         + 0.5 * a["x"]
                         + 0.25 * a["x"][np.clip(idx + 1, None, n - 1)])})


def _mk_softmax(tag: str) -> SuiteEntry:
    rows, b = 8, 128
    k = cuda_suite.make_softmax_row(b, dtype=_dt(tag))

    def ref(a):
        e = np.exp(a["x"] - a["x"].max(1, keepdims=True))
        return {"y": e / e.sum(1, keepdims=True)}

    return SuiteEntry(
        "softmax_row", ("barrier",), k, rows, b, None,
        lambda r: {"x": _fvals(r, (rows, b), tag),
                   "y": np.zeros((rows, b), _np_dt(tag))},
        ref)


def _mk_scan(tag: str) -> SuiteEntry:
    b, n = 128, 512
    k = cuda_suite.make_scan_block(b, dtype=_dt(tag))
    return SuiteEntry(
        "scan_block", ("barrier", "demotion"), k, n // b, b, None,
        lambda r: {"x": _fvals(r, n, tag), "y": np.zeros(n, _np_dt(tag))},
        lambda a: {"y": np.cumsum(a["x"].reshape(-1, b), 1).reshape(-1)})


def _mk_transpose(tag: str) -> SuiteEntry:
    h = w = 32
    k = cuda_suite.make_transpose_tiled(h, w, dtype=_dt(tag))
    return SuiteEntry(
        "transpose_tiled", ("barrier",), k, (h // 8) * (w // 8), 64, None,
        lambda r: {"x": _fvals(r, (h, w), tag),
                   "y": np.zeros((w, h), _np_dt(tag))},
        lambda a: {"y": a["x"].T.copy()})


def _mk_pixel(tag: str) -> SuiteEntry:
    n, b = 1024, 128
    k = cuda_suite.make_pixel_pipeline(b, dtype=_dt(tag))
    return SuiteEntry(
        "pixel_pipeline", ("barrier",), k, n // b, b, None,
        lambda r: {"img": r.uniform(0.5, 2.0, n).astype(_np_dt(tag)),
                   "out": np.zeros(n, _np_dt(tag))},
        lambda a: {"out": np.exp(np.log(a["img"]) * _np_dt(tag)(0.85)
                                 + _np_dt(tag)(0.1))})


def _make_from(base_name: str, builder=None, base_tag: str = "f32"):
    def make(tag: str) -> SuiteEntry:
        if tag == base_tag or builder is None:
            return _base(base_name)
        return builder(tag)
    return make


def build_cases() -> list[ConformanceCase]:
    """The registry: every suite kernel, with its applicable variant axes."""
    return [
        ConformanceCase("vecadd", _make_from("vecadd", _mk_vecadd),
                        dtypes=("f32", "f64", "i32")),
        ConformanceCase("reverse", _make_from("reverse", base_tag="i32"),
                        dtypes=("i32",)),
        ConformanceCase("histogram", _make_from("histogram",
                                                base_tag="i32"),
                        dtypes=("i32",)),
        ConformanceCase("reduce_shared",
                        _make_from("reduce_shared", _mk_reduce_shared),
                        dtypes=("f32", "f64")),
        ConformanceCase("reduce_warp",
                        _make_from("reduce_warp", _mk_reduce_warp),
                        dtypes=("f32", "f64")),
        ConformanceCase("matmul_tiled",
                        _make_from("matmul_tiled", _mk_matmul),
                        dtypes=("f32", "f64")),
        ConformanceCase("stencil1d", _make_from("stencil1d", _mk_stencil1d),
                        dtypes=("f32", "f64")),
        ConformanceCase("stencil2d", _make_from("stencil2d")),
        ConformanceCase("softmax_row", _make_from("softmax_row",
                                                  _mk_softmax),
                        dtypes=("f32", "f64")),
        ConformanceCase("scan_block", _make_from("scan_block", _mk_scan),
                        dtypes=("f32", "f64")),
        ConformanceCase("transpose_tiled",
                        _make_from("transpose_tiled", _mk_transpose),
                        dtypes=("f32", "f64", "i32")),
        ConformanceCase("pixel_pipeline",
                        _make_from("pixel_pipeline", _mk_pixel),
                        dtypes=("f32", "f64")),
        ConformanceCase("bfs_frontier", _make_from("bfs_frontier",
                                                   base_tag="i32"),
                        dtypes=("i32",)),
        ConformanceCase(
            "pathfinder",
            _make_from("pathfinder",
                       lambda tag: cuda_suite.entry_pathfinder(
                           dtype=_dt(tag)),
                       base_tag="i32"),
            dtypes=("i32", "f32", "f64")),
        ConformanceCase(
            "needle_nw",
            _make_from("needle_nw",
                       lambda tag: cuda_suite.entry_needle_nw(
                           dtype=_dt(tag)),
                       base_tag="i32"),
            dtypes=("i32", "f32")),
        ConformanceCase("backprop_layer", _make_from("backprop_layer")),
        ConformanceCase("lud_diag", _make_from("lud_diag")),
        ConformanceCase("srad_step", _make_from("srad_step")),
        ConformanceCase("srad_v1", _make_from("srad_v1")),
        ConformanceCase("lavamd", _make_from("lavamd")),
        ConformanceCase("nn", _make_from("nn")),
        ConformanceCase("kmeans", _make_from("kmeans")),
        ConformanceCase("streamcluster",
                        _make_from("streamcluster", base_tag="i32"),
                        dtypes=("i32",)),
        ConformanceCase("hotspot", _make_from("hotspot")),
    ]


# --------------------------------------------------------------------------
# geometry variants: any Dim3 factorization of the same linear grid size is
# equivalent for kernels that read only linearized ids (x-fastest ordering
# makes linear bid identical), so 2-D/3-D launches must be bit-invariant
# --------------------------------------------------------------------------
def grid_variants(g: int) -> list[tuple]:
    out: list[tuple] = []
    for a in (2, 3, 4, 5, 7, 8):
        if g % a == 0 and g // a > 1:
            out.append((g // a, a))
            break
    for a in (2, 4):
        if g % (a * a) == 0 and g // (a * a) > 1:
            out.append((g // (a * a), a, a))
            break
    return out


def _tol_for(entry: SuiteEntry, case: ConformanceCase, tag: str) -> float:
    if tag == case.dtypes[0]:
        return max(entry.tol, DTYPE_TOL[tag])
    return DTYPE_TOL[tag] if tag != "f32" else max(entry.tol,
                                                   DTYPE_TOL["f32"])


def oracle_check(out, want, tol: float) -> tuple[float, list[str]]:
    """Compare outputs with the NumPy oracle: (max |err|, mismatches)."""
    bad, max_err = [], 0.0
    for k, v in want.items():
        got, v = np.asarray(out[k]), np.asarray(v)
        if got.shape != v.shape:
            bad.append(f"{k}: shape {got.shape} != {v.shape}")
            max_err = float("inf")
            continue
        err = float(np.max(np.abs(got.astype(np.float64)
                                  - v.astype(np.float64)))) if v.size else 0.0
        max_err = max(max_err, err)
        if not np.allclose(got, v, rtol=tol, atol=tol):
            bad.append(f"{k}: max|err|={err:.3g}")
    return max_err, bad


def _bits(out, exclude: tuple[str, ...]) -> dict[str, bytes]:
    return {k: np.asarray(v).tobytes() for k, v in out.items()
            if k not in exclude}


#: Cell.mode -> run_entry chain_mode ("optimized" replays the host path
#: with the barrier-fission pass enabled)
_CHAIN_MODE = {"host": "host", "device_resident": "device",
               "graph": "graph", "optimized": "host"}


def run_cell(entry: SuiteEntry, case: ConformanceCase, backend: str,
             tag: str, grid, block, grain: int, devices: int | None,
             mode: str = "host") -> tuple[Cell, dict | None]:
    """Run one matrix cell; returns (cell, out-buffers-or-None)."""
    from repro.core.dim3 import Dim3
    cell = Cell(kernel=case.name, backend=backend,
                grid=tuple(Dim3.of(grid)), block=tuple(Dim3.of(block)),
                dtype=tag, grain=grain, devices=devices, status="pass",
                mode=mode)
    geo_kw = {}
    if entry.chain is None:
        geo_kw = {"grid": grid, "block": block}
    try:
        ctx = (jax.enable_x64(True) if tag == "f64"
               else contextlib.nullcontext())
        with ctx:
            out, want = run_entry(entry, backend, grain=grain,
                                  devices=devices,
                                  chain_mode=_CHAIN_MODE[mode],
                                  optimize=True if mode == "optimized"
                                  else None, **geo_kw)
        tol = _tol_for(entry, case, tag)
        cell.max_abs_err, bad = oracle_check(out, want, tol)
        if bad:
            cell.status = "fail"
            cell.detail = "oracle mismatch: " + "; ".join(bad)
        return cell, out
    except UnsupportedKernel as e:
        cell.status = "unsupport"
        cell.detail = str(e).splitlines()[0]
        return cell, None


def run_matrix(cases: list[ConformanceCase] | None = None,
               backends: tuple[str, ...] | None = None,
               device_counts: tuple[int, ...] | None = None,
               variants: bool = True) -> Report:
    """Sweep the conformance matrix and return the report.

    ``device_counts`` applies to multi-device backends only (counts above
    ``jax.device_count()`` become ``skip`` cells); other backends run one
    cell per (geometry, dtype, grain) point.  With ``variants=False`` only
    the base geometry/dtype/grain cell runs per (kernel, backend).
    """
    cases = build_cases() if cases is None else cases
    backends = tuple(backend_names()) if backends is None else backends
    for b in backends:
        get_backend(b)                       # raise eagerly on typos
    avail = jax.device_count()
    if device_counts is None:
        device_counts = (1,) if avail == 1 else (1, avail)

    cells: list[Cell] = []
    for case in cases:
        entries = {tag: case.make(tag) for tag in case.dtypes}
        base_tag = case.dtypes[0]
        base = entries[base_tag]

        # axis points: (tag, grid, block, grain, mode); base point first
        points = [(base_tag, base.grid, base.block, 1, "host")]
        if variants:
            for g in case.grains:
                if g != 1:
                    points.append((base_tag, base.grid, base.block, g,
                                   "host"))
            if (base.chain is None and base.dim3_free
                    and isinstance(base.grid, int)):
                for gv in grid_variants(base.grid):
                    points.append((base_tag, gv, base.block, 1, "host"))
            for tag in case.dtypes[1:]:
                e = entries[tag]
                points.append((tag, e.grid, e.block, 1, "host"))
            if base.chain is not None:
                # the device-resident leg: every chain kernel replays with
                # on-device inter-launch state, owing bit-identity to the
                # same backend's host-hop replay (modulo iteration_state)
                points.append((base_tag, base.grid, base.block, 1,
                               "device_resident"))
                points.append((base_tag, base.grid, base.block, 1,
                               "graph"))
            # the barrier-fission leg: every kernel (plain and chain)
            # re-runs with optimize=True and owes FULL bit-identity to
            # the same backend's unoptimized cell - no exclusions at all,
            # because stage fusion must not change a single bit
            points.append((base_tag, base.grid, base.block, 1,
                           "optimized"))
            if case.name in _frontend_corpus():
                # the frontend leg: the kernel's .cu source, translated,
                # owes FULL bit-identity to the hand-written host cell
                points.append((base_tag, base.grid, base.block, 1,
                               "frontend"))

        anchors: dict[tuple, dict[str, bytes]] = {}
        host_bits: dict[tuple, dict[str, bytes]] = {}

        def anchor_key(anchor_backend, tag, grid, block, grain):
            return (anchor_backend, tag, repr(grid), repr(block), grain)

        def anchor_bits(anchor_backend, tag, grid, block, grain):
            key = anchor_key(anchor_backend, tag, grid, block, grain)
            if key not in anchors:
                e = entries[tag]
                geo = ({} if e.chain is not None
                       else {"grid": grid, "block": block})
                ctx = (jax.enable_x64(True) if tag == "f64"
                       else contextlib.nullcontext())
                with ctx:
                    out, _ = run_entry(e, anchor_backend, grain=grain, **geo)
                anchors[key] = _bits(out, e.nondeterministic_shard)
            return anchors[key]

        for backend in backends:
            multi = get_backend(backend).supports("multi_device")
            devs = device_counts if multi else (None,)
            for pi, (tag, grid, block, grain, mode) in enumerate(points):
                if pi > 0:       # variant points sweep a backend subset
                    if backend not in VARIANT_BACKENDS + ("shard_vector",):
                        continue
                    if tag != base_tag and backend not in DTYPE_BACKENDS:
                        continue
                    if (mode == "graph"
                            and backend not in GRAPH_MODE_BACKENDS):
                        continue
                    if (mode == "optimized"
                            and backend not in OPTIMIZED_BACKENDS):
                        continue
                    if (mode == "frontend"
                            and backend not in FRONTEND_BACKENDS):
                        continue
                for d in devs:
                    if d is not None and d > avail:
                        from repro.core.dim3 import Dim3
                        cells.append(Cell(
                            kernel=case.name, backend=backend,
                            grid=tuple(Dim3.of(grid)),
                            block=tuple(Dim3.of(block)), dtype=tag,
                            grain=grain, devices=d, status="skip",
                            mode=mode,
                            detail=f"only {avail} device(s) available"))
                        continue
                    if mode == "frontend":
                        # not a replay of the hand-written kernel but a
                        # *different* KernelDef (translated from the .cu
                        # corpus source) run through the normal host
                        # path, compared bit-for-bit against the
                        # hand-written host cell
                        from repro.core.dim3 import Dim3
                        from repro.frontend.suite import frontend_twin
                        cell = Cell(
                            kernel=case.name, backend=backend,
                            grid=tuple(Dim3.of(grid)),
                            block=tuple(Dim3.of(block)), dtype=tag,
                            grain=grain, devices=d, status="pass",
                            mode=mode)
                        try:
                            twin = frontend_twin(case.name)
                            out, _ = run_entry(twin, backend,
                                               grain=grain, devices=d,
                                               with_reference=False)
                            base_bits = host_bits.get((backend, d))
                            if out is not None and base_bits is not None:
                                got = _bits(out, ())
                                cell.anchor = f"{backend}/host"
                                cell.bit_required = True
                                cell.bit_identical = got == base_bits
                                if not cell.bit_identical:
                                    diff = [k for k in got
                                            if got[k] != base_bits.get(k)]
                                    cell.status = "fail"
                                    cell.detail = (
                                        f"ingested .cu bits differ from "
                                        f"hand-written twin on {diff}")
                        except UnsupportedKernel as e:
                            cell.status = "unsupport"
                            cell.detail = str(e).splitlines()[0]
                        cells.append(cell)
                        continue
                    entry = entries[tag]
                    cell, out = run_cell(entry, case, backend, tag, grid,
                                         block, grain, d, mode)
                    if mode == "host" and pi == 0 and out is not None:
                        host_bits[(backend, d)] = _bits(out, ())
                    if mode != "host":
                        # the device-resident/graph legs anchor on the SAME
                        # backend's host-hop bits; stop-poll-cadence scratch
                        # (iteration_state) is excluded, oracle outputs never
                        base_bits = host_bits.get((backend, d))
                        if out is not None and base_bits is not None:
                            # the optimized leg runs the same host-hop
                            # cadence, so even iteration_state scratch
                            # must match bit-for-bit
                            skip_bufs = (() if mode == "optimized" else
                                         tuple(entry.nondeterministic_shard)
                                         + tuple(entry.iteration_state))
                            got = {k: v for k, v in _bits(out, ()).items()
                                   if k not in skip_bufs}
                            ref = {k: v for k, v in base_bits.items()
                                   if k not in skip_bufs}
                            cell.anchor = f"{backend}/host"
                            cell.bit_required = True
                            cell.bit_identical = got == ref
                            if not cell.bit_identical:
                                diff = [k for k in got if got[k] != ref[k]]
                                cell.status = "fail"
                                cell.detail = (
                                    (cell.detail + " " if cell.detail
                                     else "")
                                    + f"{mode} replay bits differ from "
                                      f"host-hop on {diff}")
                        cells.append(cell)
                        continue
                    if out is not None and backend in set(
                            BIT_ANCHOR.values()):
                        # this cell IS someone's anchor: seed the cache so
                        # anchor_bits never re-runs loop/vector
                        anchors.setdefault(
                            anchor_key(backend, tag, grid, block, grain),
                            _bits(out, entry.nondeterministic_shard))
                    anchor = BIT_ANCHOR.get(backend)
                    if (out is not None and anchor is not None
                            and anchor in backends):
                        required = (not multi) or case.exact_shard
                        cell.anchor = anchor
                        cell.bit_required = required
                        got = _bits(out, entry.nondeterministic_shard)
                        cell.bit_identical = got == anchor_bits(
                            anchor, tag, grid, block, grain)
                        if required and not cell.bit_identical:
                            cell.status = "fail"
                            diff = [k for k in got
                                    if got[k] != anchor_bits(
                                        anchor, tag, grid, block, grain)[k]]
                            cell.detail = (cell.detail + " " if cell.detail
                                           else "") + (
                                f"bits differ from {anchor} on {diff}")
                    cells.append(cell)
    return Report(cells=cells, n_kernels=len(cases), backends=backends)


def report_to_json(report: Report) -> dict:
    import math

    def cell_dict(c: Cell) -> dict:
        d = dataclasses.asdict(c)
        # shape mismatches record inf, which json.dump would emit as the
        # non-RFC-8259 token Infinity; the detail string keeps the story
        if d["max_abs_err"] is not None and not math.isfinite(
                d["max_abs_err"]):
            d["max_abs_err"] = None
        return d

    _base("vecadd")                 # ensure the shared suite cache is built
    return {
        "meta": {
            "n_kernels": report.n_kernels,
            "backends": list(report.backends),
            "device_count": jax.device_count(),
            "jax": jax.__version__,
            "n_cells": len(report.cells),
        },
        "kernels": {n: {"rodinia": e.rodinia,
                        "features": list(e.features)}
                    for n, e in _BASE.items()},
        "summary": report.summary(),
        "cells": [cell_dict(c) for c in report.cells],
        "disagreements": [c.label() + (f" :: {c.detail}" if c.detail else "")
                          for c in report.disagreements],
    }


def _register_broken_backend() -> None:
    """A loop clone that perturbs its first written buffer (gate self-test:
    a conformance gate that cannot fail gates nothing)."""
    from repro.core import lower_loop
    from repro.core.backends import register_backend

    def broken(kernel, *, grid, block, glob, grain, dyn_shared, interpret):
        out = dict(lower_loop.run(kernel, grid=grid, block=block, glob=glob,
                                  grain=grain, dyn_shared=dyn_shared))
        name = tuple(kernel.writes)[0]
        out[name] = out[name] + jnp.ones((), out[name].dtype)
        return out

    register_backend("broken", broken, {"barrier", "warp", "dim3"},
                     overwrite=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable matrix report here")
    ap.add_argument("--backends", nargs="*", default=None)
    ap.add_argument("--kernels", nargs="*", default=None,
                    help="restrict to these suite kernels")
    ap.add_argument("--devices", nargs="*", type=int, default=None,
                    help="forced device counts for multi-device backends")
    ap.add_argument("--no-variants", action="store_true",
                    help="base cells only (smoke mode)")
    ap.add_argument("--inject-disagreement", action="store_true",
                    help="register a deliberately broken backend "
                         "(gate self-test)")
    args = ap.parse_args(argv)

    cases = build_cases()
    if args.kernels:
        known = {c.name for c in cases}
        bad = set(args.kernels) - known
        if bad:
            raise SystemExit(f"unknown kernel(s) {sorted(bad)}; "
                             f"have {sorted(known)}")
        cases = [c for c in cases if c.name in args.kernels]
    backends = tuple(args.backends) if args.backends else None
    if args.inject_disagreement:
        _register_broken_backend()
        if backends is None:
            backends = tuple(backend_names())

    report = run_matrix(
        cases=cases, backends=backends,
        device_counts=tuple(args.devices) if args.devices else None,
        variants=not args.no_variants)

    summary = report.summary()
    for b in report.backends:
        row = summary.get(b, {})
        print(f"{b:>14}: pass={row.get('pass', 0):<4} "
              f"fail={row.get('fail', 0):<3} "
              f"unsupport={row.get('unsupport', 0):<3} "
              f"skip={row.get('skip', 0)}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report_to_json(report), f, indent=2)
            f.write("\n")
        print(f"matrix report written: {args.json} "
              f"({len(report.cells)} cells)")
    if report.disagreements:
        print(f"conformance gate: FAILED "
              f"({len(report.disagreements)} disagreement(s))",
              file=sys.stderr)
        for c in report.disagreements[:20]:
            print(f"  {c.label()} :: {c.detail}", file=sys.stderr)
        return 1
    print(f"conformance gate: passed ({len(report.cells)} cells, "
          f"{report.n_kernels} kernels)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
