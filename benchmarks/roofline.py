"""Barrier-fission before/after roofline: what the optimizer buys per kernel.

For each single-launch suite kernel this times warm launches twice - base
vs ``optimize=True`` (the core/optimize.py barrier-fission pass) - on one
backend, verifies the optimized run is **bit-identical** to the base run
(the same contract the conformance matrix's ``optimized`` mode enforces;
any drift fails the benchmark), and places both runs on a calibrated
roofline: machine peaks are measured at startup (dense f32 matmul for
compute, large-array copy for bandwidth), each kernel's arithmetic
intensity decides its bound, and %-of-peak is reported before and after.

Flop counts use the kernel's declared ``est_block_work`` (the paper's
Table V '# inst' analogue) and byte counts the launch's argument sizes -
crude, but identical for base and optimized runs, so the *speedup* column
(what ``check_perf.py`` gates via ``perf_baseline.json``) is exact
wall-clock while the roofline placement is an honest estimate.

Chain entries are excluded (their wall-clock story is membench's) and
logged as such.  ``--smoke`` restricts to the fused kernels plus a vecadd
control at CI-sized iteration counts; ``--json`` dumps the machine-
readable report consumed by the perf gate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (api, compile_cache, cuda_suite, memory, optimize,
                        packing)
from repro.core.dim3 import Dim3

#: kernels with proven fusion regions (pixel_pipeline 2 pairs = one whole-
#: kernel region, matmul_tiled 2, scan_block 2, lud_diag 1) plus an
#: identity-plan control
SMOKE_KERNELS = ("pixel_pipeline", "matmul_tiled", "scan_block", "lud_diag",
                 "vecadd")


def calibrate_peaks() -> dict:
    """Measured machine peaks: f32 matmul flop/s and copy bytes/s."""
    n = 1024
    a = jnp.asarray(np.random.default_rng(0).standard_normal(
        (n, n), dtype=np.float32))
    mm = jax.jit(lambda x: x @ x)
    jax.block_until_ready(mm(a))
    t0 = time.perf_counter()
    reps = 8
    for _ in range(reps):
        out = mm(a)
    jax.block_until_ready(out)
    flops = 2.0 * n ** 3 * reps / (time.perf_counter() - t0)

    big = jnp.zeros(1 << 24, jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(cp(big))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = cp(big)
    jax.block_until_ready(out)
    # read + write = 2 touches per element
    bw = 2.0 * big.nbytes * reps / (time.perf_counter() - t0)
    return {"flops": flops, "bandwidth": bw, "ridge": flops / bw}


#: per-pass wall-time target: long enough to average out single-core
#: scheduler noise, short enough for repeats x kernels to stay CI-cheap
PASS_SECONDS = 0.15


def _time_entries(suite_entry, bufs, backend: str, repeats: int):
    """Best-of-``repeats`` mean dispatch seconds, base and optimized.

    Times the *compiled entries* (``api.compiled``) directly - arg
    re-marshalling would otherwise add a constant that drowns the stage
    savings (the vecadd control drifted +-5% through the full ``launch``
    path vs +-0.2% here).  Base and optimized loops alternate within each
    repeat, so slow system periods (shared CI runners) degrade both
    measurements rather than whichever happened to run second; iteration
    counts are auto-sized to ~PASS_SECONDS per pass.
    """
    kernel = suite_entry.kernel
    kw = dict(grid=suite_entry.grid, block=suite_entry.block, args=bufs,
              backend=backend, dyn_shared=suite_entry.dyn_shared)
    base_entry = api.compiled(kernel, **kw)
    opt_entry = api.compiled(kernel, optimize=True, **kw)
    leaves, _ = packing.pack(
        memory.resolve_launch_args(kernel, bufs))

    def one_pass(entry, iters: int) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            out = entry(*leaves)
        jax.block_until_ready({k: out[k] for k in kernel.writes})
        return (time.perf_counter() - t0) / iters

    jax.block_until_ready(base_entry(*leaves))
    jax.block_until_ready(opt_entry(*leaves))
    probe = one_pass(base_entry, 3)
    iters = max(10, min(500, int(PASS_SECONDS / max(probe, 1e-7))))
    base = opt = float("inf")
    for _ in range(repeats):
        base = min(base, one_pass(base_entry, iters))
        opt = min(opt, one_pass(opt_entry, iters))
    return base, opt, iters


def bench_kernel(entry, backend: str, repeats: int, peaks: dict) -> dict:
    rng_args = entry.make_args(np.random.default_rng(11))
    args = {k: jnp.asarray(v) for k, v in rng_args.items()}
    bufs = {k: (memory.ConstArray(v) if k in entry.const else v)
            for k, v in args.items()}

    base_out = api.launch(entry.kernel, grid=entry.grid,
                          block=entry.block, args=dict(bufs),
                          backend=backend, dyn_shared=entry.dyn_shared)
    opt_out = api.launch(entry.kernel, grid=entry.grid,
                         block=entry.block, args=dict(bufs),
                         backend=backend, dyn_shared=entry.dyn_shared,
                         optimize=True)
    mismatch = [k for k in entry.kernel.writes
                if np.asarray(base_out[k]).tobytes()
                != np.asarray(opt_out[k]).tobytes()]

    derived = optimize.optimize_launch(
        entry.kernel, grid=entry.grid, block=entry.block, args=args,
        dyn_shared=entry.dyn_shared)
    plan = getattr(derived, "plan", None)
    regions = list(plan.regions) if plan is not None else []
    pairs_fused = plan.n_fused_pairs if plan is not None else 0

    base_s, opt_s, iters = _time_entries(entry, bufs, backend, repeats)

    grid = Dim3.of(entry.grid)
    flops = float(entry.kernel.est_block_work) * grid.size
    bytes_ = float(sum(np.asarray(v).nbytes for v in rng_args.values()))
    intensity = flops / max(bytes_, 1.0)
    bound = "compute" if intensity > peaks["ridge"] else "memory"

    def pct_peak(seconds: float) -> float:
        if bound == "compute":
            return 100.0 * (flops / seconds) / peaks["flops"]
        return 100.0 * (bytes_ / seconds) / peaks["bandwidth"]

    return {
        "backend": backend,
        "iters": iters,
        "stages_before": len(entry.kernel.stages),
        "stages_after": len(derived.stages),
        "regions": regions,
        "pairs_fused": pairs_fused,
        "base_us": base_s * 1e6,
        "opt_us": opt_s * 1e6,
        "speedup": base_s / opt_s,
        "bit_identical": not mismatch,
        "bit_mismatch": mismatch,
        "flops_est": flops,
        "bytes_est": bytes_,
        "intensity": intensity,
        "bound": bound,
        "pct_peak_base": pct_peak(base_s),
        "pct_peak_opt": pct_peak(opt_s),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help=f"CI subset {SMOKE_KERNELS} at small iteration "
                         f"counts")
    ap.add_argument("--json", metavar="PATH")
    ap.add_argument("--backend", default="loop",
                    help="backend to time (default: loop, where stage "
                         "restarts cost the most)")
    ap.add_argument("--scale", type=int, default=4,
                    help="suite problem-size scale (default 4)")
    ap.add_argument("--repeats", type=int, default=7,
                    help="timing repeats; best (min) wins")
    ap.add_argument("--kernels", nargs="*", default=None)
    args = ap.parse_args(argv)

    entries = cuda_suite.build_suite(scale=args.scale)
    wanted = (set(args.kernels) if args.kernels
              else set(SMOKE_KERNELS) if args.smoke
              else None)
    excluded = [e.name for e in entries if e.chain is not None]
    entries = [e for e in entries if e.chain is None
               and (wanted is None or e.name in wanted)]
    if excluded:
        print(f"excluded,{len(excluded)},chain entries (membench's "
              f"territory): {' '.join(sorted(excluded))}")

    api.cache_clear()
    peaks = calibrate_peaks()
    print(f"peaks,{peaks['flops']/1e9:.1f},GF/s "
          f"{peaks['bandwidth']/1e9:.1f} GB/s "
          f"ridge={peaks['ridge']:.1f} flop/byte")

    results = {"mode": "smoke" if args.smoke else "full",
               "backend": args.backend, "scale": args.scale,
               "repeats": args.repeats,
               "peaks": peaks, "kernels": {}}
    print("kernel,stages,regions,base_us,opt_us,speedup,bits,bound,"
          "pct_peak_base,pct_peak_opt")
    failed = []
    for entry in entries:
        r = bench_kernel(entry, args.backend, args.repeats, peaks)
        results["kernels"][entry.name] = r
        if not r["bit_identical"]:
            failed.append((entry.name, r["bit_mismatch"]))
        print(f"{entry.name},{r['stages_before']}->{r['stages_after']},"
              f"{len(r['regions'])},{r['base_us']:.1f},{r['opt_us']:.1f},"
              f"{r['speedup']:.3f},"
              f"{'ok' if r['bit_identical'] else 'DIFFER'},{r['bound']},"
              f"{r['pct_peak_base']:.2f},{r['pct_peak_opt']:.2f}")

    fused = {n: r for n, r in results["kernels"].items()
             if r["pairs_fused"]}
    best = max(fused, key=lambda n: fused[n]["speedup"]) if fused else None
    results["fusion"] = {
        "pairs_fused": sum(r["pairs_fused"] for r in fused.values()),
        "speedup_best": fused[best]["speedup"] if best else 0.0,
        "best_kernel": best,
    }
    print(f"fusion,{results['fusion']['pairs_fused']},pairs fused; best "
          f"{best}={results['fusion']['speedup_best']:.3f}x")

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(results, fh, indent=2, sort_keys=True)
        print(f"json,{args.json},written")

    if failed:
        for name, bufs in failed:
            print(f"roofline: optimized bits differ from base for {name} "
                  f"on {bufs}", file=sys.stderr)
        print("roofline: FAILED (optimizer broke bit-identity)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    sys.exit(main())
