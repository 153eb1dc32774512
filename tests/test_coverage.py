"""Unit tests for the coverage sweep + gate (benchmarks/coverage.py and
benchmarks/check_coverage.py) without running the full suite: the sweep is
monkeypatched with small fake tables so percentage math, --update round-trips
and both gate branches (count regression AND percent dilution) are exercised
in milliseconds."""
import importlib.util
import json
import os
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "benchmarks")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # check_coverage does `import coverage`
    spec.loader.exec_module(mod)
    return mod


coverage_bench = _load("coverage")
check_coverage = _load("check_coverage")

FWS = ("loop", "naive")


def _table(rows):
    """rows: {kernel: {fw: status}} -> sweep-shaped {k: (row, features)}."""
    return {k: (dict(v), ("feat",)) for k, v in rows.items()}


def _patch_sweep(monkeypatch, table, fws=FWS):
    monkeypatch.setattr(coverage_bench, "run",
                        lambda: {k: (dict(r), f) for k, (r, f) in table.items()})
    monkeypatch.setattr(coverage_bench, "frameworks", lambda: fws)


# --- percentages() -----------------------------------------------------------
def test_percentages_unsupport_and_incorrect_count_against():
    t = _table({
        "a": {"loop": "correct", "naive": "correct"},
        "b": {"loop": "correct", "naive": "unsupport"},
        "c": {"loop": "correct", "naive": "unsupport"},
        "d": {"loop": "incorrect", "naive": "unsupport"},
    })
    pct = coverage_bench.percentages(t)
    assert pct["loop"] == 75.0       # incorrect is not coverage
    assert pct["naive"] == 25.0      # unsupport dilutes, never skipped


def test_percentages_empty_table_is_zero_per_registered_backend():
    pct = coverage_bench.percentages({})
    assert set(pct) == set(coverage_bench.frameworks())
    assert all(v == 0.0 for v in pct.values())


def test_paper_figures_constants():
    assert coverage_bench.PAPER_CUPBOP_PCT == 69.6
    assert coverage_bench.PAPER_PRIOR_PCT == 56.6


# --- check_coverage: --update round-trip -------------------------------------
def test_update_roundtrip_then_gate_passes(tmp_path, monkeypatch):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"},
        "b": {"loop": "correct", "naive": "unsupport"},
        "c": {"loop": "correct", "naive": "unsupport"},
    }))
    base = tmp_path / "baseline.json"
    assert check_coverage.main(["--update", "--baseline", str(base)]) == 0
    data = json.loads(base.read_text())
    assert data["n_kernels"] == 3
    assert data["backends"] == {"loop": 3, "naive": 1}
    assert data["percent"] == {"loop": 100.0, "naive": 33.3}
    # the freshly written baseline gates green against the same sweep
    assert check_coverage.main(["--baseline", str(base)]) == 0


def test_gate_trips_on_count_regression(tmp_path, monkeypatch):
    good = _table({"a": {"loop": "correct", "naive": "correct"},
                   "b": {"loop": "correct", "naive": "correct"}})
    _patch_sweep(monkeypatch, good)
    base = tmp_path / "baseline.json"
    assert check_coverage.main(["--update", "--baseline", str(base)]) == 0
    bad = _table({"a": {"loop": "correct", "naive": "correct"},
                  "b": {"loop": "correct", "naive": "incorrect"}})
    _patch_sweep(monkeypatch, bad)
    assert check_coverage.main(["--baseline", str(base)]) == 1


def test_gate_trips_on_percent_dilution(tmp_path, monkeypatch):
    """Counts stay flat while the suite grows: only the percentage branch
    catches this (the exact regression the paper's headline would show)."""
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"},
        "b": {"loop": "correct", "naive": "correct"}}))
    base = tmp_path / "baseline.json"
    assert check_coverage.main(["--update", "--baseline", str(base)]) == 0
    diluted = _table({
        "a": {"loop": "correct", "naive": "correct"},
        "b": {"loop": "correct", "naive": "correct"},
        "c": {"loop": "unsupport", "naive": "unsupport"}})
    _patch_sweep(monkeypatch, diluted)
    assert check_coverage.main(["--baseline", str(base)]) == 1


def test_gate_trips_on_suite_shrink(tmp_path, monkeypatch):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "unsupport"},
        "b": {"loop": "correct", "naive": "unsupport"}}))
    base = tmp_path / "baseline.json"
    assert check_coverage.main(["--update", "--baseline", str(base)]) == 0
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "unsupport"}}))
    assert check_coverage.main(["--baseline", str(base)]) == 1


def test_gate_allows_growth_with_hint(tmp_path, monkeypatch, capsys):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "unsupport"}}))
    base = tmp_path / "baseline.json"
    assert check_coverage.main(["--update", "--baseline", str(base)]) == 0
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"}}))
    assert check_coverage.main(["--baseline", str(base)]) == 0
    assert "refresh with" in capsys.readouterr().out


def test_missing_baseline_is_an_error(tmp_path, monkeypatch):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"}}))
    assert check_coverage.main(
        ["--baseline", str(tmp_path / "nope.json")]) == 2


# --- --disable self-test + --json artifact -----------------------------------
def test_disable_marks_kernel_unsupported(monkeypatch):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"},
        "b": {"loop": "correct", "naive": "correct"}}))
    counts, pct, n = check_coverage.current_counts(disable="b")
    assert n == 2
    assert counts == {"loop": 1, "naive": 1}
    assert pct == {"loop": 50.0, "naive": 50.0}


def test_disable_unknown_kernel_raises(monkeypatch):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"}}))
    with pytest.raises(SystemExit):
        check_coverage.current_counts(disable="no_such_kernel")


def test_json_artifact_written_even_when_gate_fails(tmp_path, monkeypatch):
    _patch_sweep(monkeypatch, _table({
        "a": {"loop": "correct", "naive": "correct"}}))
    base = tmp_path / "baseline.json"
    assert check_coverage.main(["--update", "--baseline", str(base)]) == 0
    art = tmp_path / "report.json"
    assert check_coverage.main(
        ["--baseline", str(base), "--json", str(art),
         "--disable", "a"]) == 1
    report = json.loads(art.read_text())
    assert report == {"n_kernels": 1, "backends": {"loop": 0, "naive": 0},
                      "percent": {"loop": 0.0, "naive": 0.0}}


def test_committed_baseline_matches_suite_shape():
    """The checked-in baseline must describe the real 24-kernel suite with
    percent entries for every backend (hand-edit guard)."""
    with open(os.path.join(_BENCH, "coverage_baseline.json")) as f:
        base = json.load(f)
    assert base["n_kernels"] == 24
    assert set(base["percent"]) == set(base["backends"])
    for fw, cnt in base["backends"].items():
        assert base["percent"][fw] == round(100.0 * cnt / base["n_kernels"], 1)
