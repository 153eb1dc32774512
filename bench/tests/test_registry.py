"""BENCHMARK.json keeps its contract, and every name in it leads to a file.

A new configuration, traffic mix or metric is found by its name alone:
the last test adds one of each to a copy of the benchmark, as files and
entries, and the registry finds them with no code changed.
"""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from bench.lib.registry import ROOT, Bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return Bench()


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_contract_shape(bench):
    spec = bench.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert spec["paths"] == ["bench"]
    assert spec["command"][1].startswith("bench/")
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/")
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(pairs) == len(spec["workloads"])
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 2)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in bench.configs
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in SOURCES
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_cell_reports_enough(bench):
    """setup_s, one more end-to-end metric and one per-layer metric; every
    per-layer metric's cells report the metric it moves."""
    for cell in bench.cells:
        e2e = {m["name"] for m in bench.metrics_for(cell, traced=False)}
        layer = bench.metrics_for(cell, traced=True)
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert layer, cell
        for m in layer:
            assert m["moves"] in e2e, (cell, m["name"])


def test_names_lead_to_files(bench):
    for name in bench.configs:
        cfg = bench.config(name)
        for fn in ("entry", "inputs", "reference", "work"):
            assert callable(getattr(cfg.module, fn)), (name, fn)
        assert cfg.module.OUTPUT
        assert cfg.limit > 0
        entry = bench.configs[name]
        assert cfg.params["source"] == entry["source"]
        assert cfg.params["reduced"] == entry["reduced"]
    for w in bench.cells.values():
        assert bench.traffic(w["traffic"])["kind"] == "job"
    for m in bench.spec["end_to_end"] + bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_new_entries_are_found_by_name(tmp_path):
    """A later PR adds files and entries; nothing else changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    base = json.loads((root / "bench/configs/hotspot_1024.json").read_text())
    (root / "bench/configs/hotspot_512.json").write_text(json.dumps(
        {**base, "rows": 512, "cols": 512}))
    shutil.copy(root / "bench/configs/hotspot_1024.py",
                root / "bench/configs/hotspot_512.py")
    (root / "bench/traffic/job_pool8.json").write_text(json.dumps(
        {"kind": "job", "pool": 8, "trace_s": 1.0}))
    (root / "bench/metrics/jobs_seen.py").write_text(
        "def read(run):\n    return run.attempted\n")
    spec["configs"].append({**spec["configs"][0], "name": "hotspot_512",
                            "file": "bench/configs/hotspot_512.json"})
    spec["workloads"].append({"name": "hotspot_512.job_pool8",
                              "config": "hotspot_512", "traffic": "job_pool8",
                              "chips": 1, "why": "a new cell"})
    spec["per_layer"].append({"name": "jobs_seen", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "device (XLA:TPU)",
                              "moves": "setup_s",
                              "workloads": ["hotspot_512.job_pool8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    b = Bench(root=str(root))
    cfg = b.config(b.cell("hotspot_512.job_pool8")["config"])
    assert cfg.params["rows"] == 512 and cfg.module.work(cfg.params)[1] == \
        3 * 4 * 512 * 512 * 4
    assert b.traffic("job_pool8")["pool"] == 8
    layer = [m["name"] for m in b.metrics_for("hotspot_512.job_pool8", True)]
    assert "jobs_seen" in layer and "setup_compile_s" in layer
    assert b.reader("jobs_seen")(type("R", (), {"attempted": 7})) == 7
    assert "jobs_seen" not in [
        m["name"] for m in b.metrics_for("hotspot_1024.job", True)]
