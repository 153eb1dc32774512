"""Serving driver: kernel-service traffic.

It stands up a :class:`repro.serve.KernelService`, registers the
single-launch suite kernels as endpoints, replays a round-robin request
mix through the batching worker, and prints the
:class:`~repro.serve.ServiceStats` surface::

  PYTHONPATH=src python -m repro.launch.serve --smoke
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def serve_kernels(args) -> dict:
    """Smoke a kernel-service under round-robin suite traffic."""
    from repro.core.cuda_suite import build_suite
    from repro.serve import KernelService

    entries = [e for e in build_suite(scale=1) if e.chain is None]
    if args.kernels:
        keep = set(args.kernels)
        entries = [e for e in entries if e.name in keep]
        if not entries:
            raise SystemExit(f"no suite kernels match {sorted(keep)}")
    rng = np.random.default_rng(0)
    with KernelService(backend=args.backend, max_batch=args.max_batch,
                       admission_window_ms=args.window_ms,
                       default_timeout_s=args.timeout) as svc:
        for e in entries:
            svc.register_entry(e)
        t0 = time.perf_counter()
        # two waves: the first traces each specialization, the second is
        # the warm traffic the service exists for - so the demo's stats
        # show cache hits, not just one cold dispatch per endpoint
        for _wave in range(2):
            tickets = [svc.submit(entries[i % len(entries)].name,
                                  entries[i % len(entries)].make_args(rng))
                       for i in range(args.requests)]
            for t in tickets:
                t.result(timeout=args.timeout)
        dt = time.perf_counter() - t0
        stats = svc.stats()
    doc = stats.to_json()
    n = 2 * args.requests
    print(f"served {n} requests over {len(entries)} endpoints "
          f"in {dt:.2f}s ({n / dt:.1f} req/s) "
          f"warm_hit_rate={stats.warm_hit_rate} "
          f"dispatches={stats.dispatches} "
          f"occupancy={doc['batch_occupancy']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"stats written to {args.json}")
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=48,
                    help="requests per wave")
    ap.add_argument("--backend", default="vector")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--kernels", nargs="*", default=None,
                    help="restrict to these suite kernels")
    ap.add_argument("--json", default=None,
                    help="write the ServiceStats snapshot here")
    return serve_kernels(ap.parse_args(argv))


if __name__ == "__main__":
    from repro.core import compile_cache
    compile_cache.use_jax_cache()
    main()
