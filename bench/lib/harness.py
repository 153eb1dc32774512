"""Run one cell once: set up, warm up, measure, check, reduce to metrics.

The program is driven only through its public entry point for a job,
``cuda_suite.run_entry``.  The benchmark's own host phases are wrapped in
``jax.profiler.TraceAnnotation`` spans (``bench.h2d``, ``bench.call``,
``bench.wait``, ``bench.d2h``) so that a traced run can put the device's
idle gaps down to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import trace as trace_mod
from bench.lib.clock import CompileClock
from bench.lib.registry import Bench, Config
from bench.lib.traffic import JobWindow, run_jobs

span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Run:
    """Everything one run measured; the metric readers read only this."""

    kind: str                      # the traffic mix's ``kind``
    chips: int
    work: tuple                    # (operations, bytes) of one job
    peaks: dict | None             # bench/peaks.json entry of this device
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    compile_in_window_s: float = 0.0
    window: JobWindow | None = None
    attempted: int = 0
    failed: int = 0
    phase_s: list = dataclasses.field(default_factory=list)
    trace_dir: str | None = None     # profiler log, read after the window
    trace: trace_mod.Trace | None = None
    traced_jobs: int = 0


PHASES = ("h2d", "call", "wait", "d2h")


class Jobs:
    """Closed loop: whole application jobs through ``run_entry``."""

    def __init__(self, cfg: Config, traffic: dict, pool: list):
        self.mod, self.pool = cfg.module, pool
        self.entry = cfg.module.entry(cfg.params)
        self.backend = cfg.params["backend"]
        self.outputs: list = []
        self.phase_s: list = []        # per job, seconds of each of PHASES

    def job(self, i: int) -> None:
        from repro.core import cuda_suite
        k = i % len(self.pool)
        t = [time.perf_counter()]
        with span("bench.h2d"):
            args = {n: jnp.asarray(v) for n, v in self.pool[k].items()}
            jax.block_until_ready(args)
        t.append(time.perf_counter())
        with span("bench.call"):
            out, _ = cuda_suite.run_entry(
                self.entry, self.backend, args=args, with_reference=False)
        t.append(time.perf_counter())
        with span("bench.wait"):
            res = jax.block_until_ready(out[self.mod.OUTPUT])
        t.append(time.perf_counter())
        with span("bench.d2h"):
            self.outputs.append((k, np.asarray(res)))
        t.append(time.perf_counter())
        self.phase_s.append(np.diff(t).tolist())

    def warm(self) -> None:
        self.job(0)
        self.outputs.clear()
        self.phase_s.clear()

    def measure(self, seconds: float, run: Run,
                trace_s: float | None) -> None:
        if trace_s is None:
            run.window = run_jobs(self.job, seconds)
            run.attempted = run.window.jobs
            return
        # the trace covers the jobs of the first trace_s seconds: a whole
        # window of while-loop iterations would overflow the profiler
        dirs: list = []
        with trace_mod.record(dirs):
            first = run_jobs(self.job, trace_s)
        run.trace_dir, run.traced_jobs = dirs[0], first.jobs
        rest = seconds - first.seconds
        run.attempted = first.jobs + (run_jobs(self.job, rest).jobs
                                      if rest > 0 else 0)


def compare(cfg: Config, pool: list, outputs: list) -> tuple[float, int]:
    """Worst relative error of the answers against the plain reference
    (``max |out - ref| / max |ref|`` per answer), and how many answers
    exceed the limit."""
    refs: dict = {}
    worst, bad = 0.0, 0
    for k, out in outputs:
        if k not in refs:
            refs[k] = cfg.module.reference(cfg.params, pool[k])
        ref = refs[k]
        err = float(np.max(np.abs(out.astype(np.float64) - ref))
                    / np.max(np.abs(ref)))
        if not err <= cfg.limit:          # NaN counts as wrong
            bad += 1
        worst = max(worst, err) if err == err else float("inf")
    return worst, bad


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             traced: bool, *, t0: float | None = None,
             peaks: dict | None = None) -> dict:
    """One run of cell ``name``: the result line as a dict (its ``check``
    key last).  No device check here: :func:`main` makes it."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = bench.cell(name)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    clock = CompileClock()
    rng = np.random.default_rng(seed)
    pool = [cfg.module.inputs(cfg.params, rng)
            for _ in range(int(traffic["pool"]))]
    run = Run(kind=traffic["kind"], chips=int(cell["chips"]),
              work=cfg.module.work(cfg.params), peaks=peaks)
    if run.kind != "job":
        raise ValueError(f"traffic {cell['traffic']!r}: kind {run.kind!r}; "
                         f"the harness drives closed-loop jobs only")
    driver = Jobs(cfg, traffic, pool)
    driver.warm()
    run.setup_s = time.perf_counter() - t0
    run.setup_compile_s = clock.seconds
    driver.measure(seconds, run,
                   float(traffic["trace_s"]) if traced else None)
    run.compile_in_window_s = clock.seconds - run.setup_compile_s
    mem = memory_peak(jax.devices()[:run.chips])
    outputs, run.phase_s = driver.outputs, driver.phase_s
    del driver          # the program's state goes before the reference runs
    if run.trace_dir is not None:
        run.trace = trace_mod.load(run.trace_dir)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    worst, bad = compare(cfg, pool, outputs)
    run.failed = bad
    metrics = {}
    for m in bench.metrics_for(name, traced):
        value = bench.reader(m["name"])(run)
        if value is None:
            if not traced:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in cell {name}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    result = {"correct": bad == 0 and worst <= cfg.limit,
              "attempted": run.attempted, "failed": bad,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.mean_busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = trace_mod.breakdown(run.trace)
    result["notes"] = notes(run)
    result["check"] = {"max_rel_err": {
        "value": worst if math.isfinite(worst) else None,
        "limit": cfg.limit}}
    return result


def notes(run: Run) -> dict:
    """What the run saw besides its metrics (not compared, not bounded)."""
    out = {"setup_compile_s": run.setup_compile_s,
           "compile_in_window_s": run.compile_in_window_s}
    if run.window is not None:
        out["window_s"] = run.window.seconds
    if run.phase_s:
        # where the slowest job of the run spent its time: a stall shows
        # in one phase of one job
        jobs = [sum(p) for p in run.phase_s]
        i = int(np.argmax(jobs))
        out["job_s_median"] = float(np.median(jobs))
        out["slowest_job"] = {"index": i, **{
            f"{name}_s": s for name, s in zip(PHASES, run.phase_s[i])}}
    return out


def main(args, t0: float) -> int:
    bench = Bench()
    cell = bench.cell(args.workload)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU (JAX found {dev.platform}); the benchmark "
              f"runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    peaks = bench.peaks(dev.device_kind)
    from repro.core import compile_cache
    compile_cache.use_jax_cache()
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t0=t0, peaks=peaks)
    notes_line = result.pop("notes")
    print("notes " + json.dumps(notes_line), flush=True)
    for k, v in result["check"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
