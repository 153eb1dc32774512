"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a configuration, a traffic mix or a metric by name:

* configuration ``<c>``: ``bench/configs/<c>.json`` (the sizes as run,
  ``source``, ``reduced``, ``assumed`` and the correctness limit) and
  ``bench/configs/<c>.py`` beside it (the entry builder, the input
  generator, the plain NumPy reference and the operation and byte counts);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, parameters that the one
  general generator in :mod:`bench.lib.traffic` reads;
* metric ``<m>``, end to end or per layer: ``bench/metrics/<m>.py`` with a
  ``read(run)`` function over the :class:`bench.lib.harness.Run` record;
* peaks: ``bench/peaks.json``, keyed by ``device_kind``.

A later PR adds a configuration, a mix or a metric by adding files and an
entry in ``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownDevice(KeyError):
    """The peak table has no entry for this ``device_kind``."""


def load_module(path: str, name: str):
    """Import one Python file by path (metric and config names hold dots)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Config:
    """One configuration: its sizes (``params``) and its module."""

    name: str
    params: dict
    module: object

    @property
    def limit(self) -> float:
        return float(self.params["check"]["max_rel_err"])


class Bench:
    """``BENCHMARK.json`` with the files its names lead to."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.dir = bench_dir or os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.cells = {w["name"]: w for w in self.spec["workloads"]}
        self.configs = {c["name"]: c for c in self.spec["configs"]}

    def cell(self, name: str) -> dict:
        try:
            return self.cells[name]
        except KeyError:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{sorted(self.cells)}") from None

    def config(self, name: str) -> Config:
        entry = self.configs[name]
        with open(os.path.join(self.root, entry["file"])) as f:
            params = json.load(f)
        base = os.path.splitext(os.path.join(self.root, entry["file"]))[0]
        return Config(name, params, load_module(base + ".py",
                                                f"bench_config_{name}"))

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", f"{name}.json")) as f:
            return json.load(f)

    def reader(self, metric: str):
        """The ``read(run)`` function of one metric."""
        path = os.path.join(self.dir, "metrics", f"{metric}.py")
        return load_module(path, f"bench_metric_{metric}").read

    def metrics_for(self, cell: str, traced: bool) -> list[dict]:
        """The end-to-end (untraced) or per-layer (traced) metrics that
        ``cell`` reports: those that list it, or list no cells at all."""
        kind = "per_layer" if traced else "end_to_end"
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", (cell,))]

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as f:
            table = json.load(f)
        try:
            return table["devices"][device_kind]
        except KeyError:
            raise UnknownDevice(
                f"no peaks for device_kind {device_kind!r} in "
                f"bench/peaks.json (known: {sorted(table['devices'])}); "
                f"add the device with its published source") from None
