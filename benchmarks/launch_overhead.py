"""Paper Fig. 11 analog + compile-cache amortization.

Two experiments over the same vecadd launch sequence:

* **policies** - HAZARD_ONLY (CuPBoP: async launches, barrier only on the
  final read) vs SYNC_ALWAYS (HIP-CPU: barrier after every launch); the
  paper measures this software-scheduler gap as a 30 % slowdown (SV-B.2).
* **cache** - per-launch cost of the three compile-cache tiers: ``cold``
  (full trace+lower+XLA compile), ``warm`` (in-memory ``CompiledKernel``
  hit: dispatch only), and ``disk`` (new-process simulation: in-memory
  cache dropped, launch rebuilt from the on-disk artifact - the
  ``cudaModuleLoad`` path).

``--smoke`` shrinks iteration counts for CI; ``--json PATH`` dumps the
results; ``--check`` asserts the warm path is >= 5x faster than cold (the
amortization claim this repo's CI gates on).
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax.numpy as jnp
import numpy as np

from repro.core import Policy, Stream, api, compile_cache
from repro.core.cuda_suite import make_vecadd

N_LAUNCH = 1000
WARM_ITERS = 200


def bench_policies(n_launch: int) -> dict:
    n, block = 4096, 128
    rng = np.random.default_rng(0)
    kernel = make_vecadd(n)
    bufs = {"a": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "b": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "c": jnp.zeros(n, jnp.float32)}
    results = {}
    for pol in (Policy.HAZARD_ONLY, Policy.SYNC_ALWAYS):
        s = Stream(dict(bufs), policy=pol)
        cfg = kernel[-(-n // block), block, None, s]   # <<<g, b, 0, s>>>
        cfg()                                          # compile warmup
        s.synchronize()
        s.stats.syncs = 0
        t0 = time.perf_counter()
        for _ in range(n_launch):
            cfg()
        _ = s.memcpy_d2h("c")
        dt = time.perf_counter() - t0
        results[pol.value] = {"us_per_launch": dt * 1e6 / n_launch,
                              "syncs": s.stats.syncs}
        print(f"{pol.value},{dt*1e6/n_launch:.1f},us/launch syncs="
              f"{s.stats.syncs}")
    h = results["hazard_only"]["us_per_launch"]
    a = results["sync_always"]["us_per_launch"]
    results["async_speedup"] = a / h
    print(f"async_speedup,{a/h:.2f},hazard-only vs sync-always "
          f"(paper: CuPBoP 30% faster than HIP-CPU on FIR)")
    return results


def _timed_launch(kernel, args, **kw) -> float:
    import jax
    t0 = time.perf_counter()
    out = api.launch(kernel, args=args, **kw)
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def bench_cache(warm_iters: int) -> dict:
    n, block = 4096, 128
    rng = np.random.default_rng(0)
    kernel = make_vecadd(n)
    args = {"a": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "b": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "c": jnp.zeros(n, jnp.float32)}
    kw = dict(grid=-(-n // block), block=block, backend="loop")
    results = {}

    with tempfile.TemporaryDirectory() as cache_dir:
        api.enable_disk_cache(cache_dir)
        try:
            api.cache_clear()
            cold = _timed_launch(kernel, args, **kw)   # trace+lower+compile
            t0 = time.perf_counter()
            for _ in range(warm_iters):
                api.launch(kernel, args=args, **kw)
            import jax
            jax.block_until_ready(api.launch(kernel, args=args, **kw))
            warm = (time.perf_counter() - t0) / (warm_iters + 1)
            stats = api.cache_stats()
            assert stats.disk_stores >= 1, "artifact was not persisted"
            api.cache_clear()                  # "new process": memory gone
            disk = _timed_launch(kernel, args, **kw)
            assert api.cache_stats().disk_hits >= 1, "artifact not loaded"
        finally:
            api.disable_disk_cache()
            api.cache_clear()

    results["cold_us"] = cold * 1e6
    results["warm_us"] = warm * 1e6
    results["disk_us"] = disk * 1e6
    results["warm_speedup"] = cold / warm
    results["disk_speedup"] = cold / disk
    print(f"cache_cold,{cold*1e6:.1f},trace+lower+compile")
    print(f"cache_warm,{warm*1e6:.1f},CompiledKernel hit (dispatch only)")
    print(f"cache_disk,{disk*1e6:.1f},artifact reload (cudaModuleLoad)")
    print(f"warm_speedup,{cold/warm:.1f},cold/warm "
          f"(gate: >= 5x)")
    print(f"disk_speedup,{cold/disk:.1f},cold/disk")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced iteration counts for CI")
    ap.add_argument("--json", metavar="PATH",
                    help="write results as JSON")
    ap.add_argument("--check", action="store_true",
                    help="assert warm launches are >= 5x faster than cold")
    args = ap.parse_args(argv)

    n_launch = 50 if args.smoke else N_LAUNCH
    warm_iters = 50 if args.smoke else WARM_ITERS
    results = {"policies": bench_policies(n_launch),
               "cache": bench_cache(warm_iters)}

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"json,{args.json},written")
    if args.check:
        speedup = results["cache"]["warm_speedup"]
        assert speedup >= 5.0, (
            f"warm (cache-hit) launch must be >= 5x faster than cold "
            f"trace+lower, got {speedup:.1f}x")
        print(f"check,passed,warm {speedup:.1f}x >= 5x")
    return results


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
