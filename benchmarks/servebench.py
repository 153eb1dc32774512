"""Serving-tier load benchmark: batched warm-path vs request-at-a-time cold.

Drives the kernel service (:mod:`repro.serve.kernel_service`) with the
same round-robin suite workload mix under three regimes:

* **cold serial** - the baseline a service exists to beat: compile cache
  cleared, every request dispatched one-at-a-time through ``api.launch``
  and synced (first request per specialization pays the full trace+lower
  cost - the per-launch overhead Polygeist-style GPU-to-CPU translation
  measures as dominant);
* **closed-loop warm service** - N client threads, each submitting its
  next request when the previous completes, against a pre-warmed service
  that stacks compatible requests into batched dispatches;
* **open-loop service** - requests offered on a fixed-rate clock
  regardless of completions (arrival-driven, exposes queueing behavior).

Emits JSON for ``check_perf.py``; the committed floors gate
``serve.requests_per_sec``, ``serve.warm_hit_rate``, and the headline
``serve.throughput_speedup`` (batched-warm >= 2x cold serial).

``--smoke`` shrinks the mix for CI; ``--json`` dumps results;
``--check`` asserts the acceptance claims in-process.
"""
from __future__ import annotations

import argparse
import json
import threading
import time

import jax.numpy as jnp
import numpy as np

from repro.core import api, compile_cache
from repro.core.cuda_suite import build_suite
from repro.serve import KernelService, ServiceOverloaded

#: the serving mix: single-launch suite kernels spanning plain SPMD,
#: barriers, shared staging, and atomics (chains are unbatchable traffic
#: and are exercised by tests, not the throughput benchmark)
ROSTER = ["vecadd", "softmax_row", "reduce_shared", "stencil1d",
          "scan_block", "pixel_pipeline"]
BACKEND = "vector"


def build_requests(entries, n: int, seed: int = 0):
    """Round-robin (entry, args) mix, args pre-generated (never timed)."""
    rng = np.random.default_rng(seed)
    return [(entries[i % len(entries)], entries[i % len(entries)]
             .make_args(rng)) for i in range(n)]


def cold_serial(requests) -> dict:
    """One-request-at-a-time from a cold cache (compiles on the clock)."""
    api.cache_clear()
    t0 = time.perf_counter()
    for entry, args in requests:
        out = api.launch(entry.kernel, grid=entry.grid, block=entry.block,
                         args={k: jnp.asarray(v) for k, v in args.items()},
                         dyn_shared=entry.dyn_shared, backend=BACKEND)
        for name in entry.kernel.writes:
            out[name].block_until_ready()
    dt = time.perf_counter() - t0
    return {"requests_per_sec": round(len(requests) / dt, 4),
            "total_s": round(dt, 4)}


def _warm(svc: KernelService, entries, max_batch: int):
    """Pre-compile every endpoint's single path and its batch buckets."""
    rng = np.random.default_rng(1)
    size = 1
    while True:
        for e in entries:
            tickets = [svc.submit(e.name, e.make_args(rng))
                       for _ in range(size)]
            for t in tickets:
                t.result(timeout=600)
        if size >= max_batch:
            break
        size = min(size * 2, max_batch)


def closed_loop(svc: KernelService, requests, clients: int) -> dict:
    """Fixed concurrency: each client submits again on completion."""
    it = iter(requests)
    lock = threading.Lock()
    latencies: list[float] = []
    errors: list[Exception] = []

    def client():
        while True:
            with lock:
                item = next(it, None)
            if item is None:
                return
            entry, args = item
            while True:
                try:
                    t = svc.submit(entry.name, args)
                    break
                except ServiceOverloaded:
                    time.sleep(0.001)
            try:
                t.result(timeout=600)
            except Exception as e:   # noqa: BLE001 - recorded, not raised
                errors.append(e)
                continue
            with lock:
                latencies.append(t.latency_ms)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    dt = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} request(s) failed under load; "
                           f"first: {errors[0]!r}")
    return {"requests_per_sec": round(len(latencies) / dt, 4),
            "total_s": round(dt, 4),
            "p50_ms": round(float(np.percentile(latencies, 50)), 4),
            "p99_ms": round(float(np.percentile(latencies, 99)), 4)}


def open_loop(svc: KernelService, requests, rate_rps: float) -> dict:
    """Arrival-clock offered load; rejected arrivals count as shed."""
    tickets, shed = [], 0
    period = 1.0 / rate_rps
    t0 = time.perf_counter()
    for i, (entry, args) in enumerate(requests):
        wait = t0 + i * period - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        try:
            tickets.append(svc.submit(entry.name, args))
        except ServiceOverloaded:
            shed += 1
    lat = []
    for t in tickets:
        t.result(timeout=600)
        lat.append(t.latency_ms)
    dt = time.perf_counter() - t0
    return {"offered_rps": round(rate_rps, 4),
            "requests_per_sec": round(len(tickets) / dt, 4),
            "shed": shed,
            "p99_ms": round(float(np.percentile(lat, 99)), 4) if lat else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized mix (fewer kernels and requests)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--json", default=None)
    ap.add_argument("--check", action="store_true",
                    help="assert the acceptance claims")
    args = ap.parse_args(argv)

    roster = ROSTER[:4] if args.smoke else ROSTER
    n = args.requests or (96 if args.smoke else 360)
    clients = args.clients or (8 if args.smoke else 16)
    entries = [e for e in build_suite(scale=1)
               if e.chain is None and e.name in roster]
    requests = build_requests(entries, n)

    print(f"mix: {n} requests over {[e.name for e in entries]}, "
          f"{clients} clients, max_batch={args.max_batch}")
    cold = cold_serial(requests)
    print(f"cold serial: {cold['requests_per_sec']} req/s "
          f"({cold['total_s']}s)")

    api.cache_clear()
    svc = KernelService(backend=BACKEND, max_batch=args.max_batch,
                        admission_window_ms=args.window_ms,
                        default_timeout_s=600.0)
    try:
        for e in entries:
            svc.register_entry(e)
        _warm(svc, entries, args.max_batch)
        st0 = svc.stats()            # steady-state window starts here
        closed = closed_loop(svc, requests, clients)
        st = svc.stats()
        run_hits = st.cache_hits - st0.cache_hits
        run_misses = st.cache_misses - st0.cache_misses
        warm_hit_rate = round(run_hits / max(run_hits + run_misses, 1), 4)
        rate = max(closed["requests_per_sec"], 1.0)
        opened = open_loop(svc, build_requests(entries, max(n // 3, 8), 7),
                           rate_rps=rate)
    finally:
        svc.close()

    speedup = round(closed["requests_per_sec"]
                    / max(cold["requests_per_sec"], 1e-9), 4)
    results = {
        "workload": {"kernels": [e.name for e in entries], "requests": n,
                     "clients": clients, "max_batch": args.max_batch,
                     "window_ms": args.window_ms, "backend": BACKEND},
        "cold": cold,
        "serve": {
            "requests_per_sec": closed["requests_per_sec"],
            "throughput_speedup": speedup,
            "warm_hit_rate": warm_hit_rate,
            "lifetime_hit_rate": st.warm_hit_rate,
            "p50_ms": closed["p50_ms"],
            "p99_ms": closed["p99_ms"],
            "dispatches": st.dispatches,
            "batched_requests": st.batched_requests,
            "batch_occupancy": {str(k): v for k, v
                                in sorted(st.batch_occupancy.items())},
            "per_kernel": st.kernels,
            "max_queue_depth": st.max_queue_depth,
        },
        "open": opened,
    }
    print(f"warm service (closed loop): {closed['requests_per_sec']} req/s, "
          f"p50={closed['p50_ms']}ms p99={closed['p99_ms']}ms, "
          f"warm_hit_rate={warm_hit_rate} "
          f"(lifetime {st.warm_hit_rate}), "
          f"speedup={speedup}x over cold serial")
    print(f"open loop @ {opened['offered_rps']} req/s offered: "
          f"{opened['requests_per_sec']} req/s achieved, "
          f"p99={opened['p99_ms']}ms, shed={opened['shed']}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"results written to {args.json}")

    if args.check:
        assert speedup >= 2.0, \
            f"batched warm path only {speedup}x over cold serial (< 2x)"
        assert warm_hit_rate >= 0.5, \
            f"warm_hit_rate {warm_hit_rate} < 0.5"
        print("checks passed: speedup >= 2x, warm_hit_rate >= 0.5")
    return 0


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    raise SystemExit(main())
