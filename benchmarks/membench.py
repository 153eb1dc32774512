"""Memory-runtime benchmark: host-sync traffic + chain replay throughput.

The host-hop LaunchChain driver round-trips through the host every
iteration - prepare hooks push fresh scalars (h2d) and stop flags read
back (d2h, a full sync).  Polygeist-style GPU-to-CPU work shows exactly
this traffic dominating translated-kernel runtime.  This benchmark
quantifies what the device-resident runtime buys, on real suite chains:

* **sync accounting** (bfs_frontier, the stop-flag chain): host syncs per
  chain iteration, host-hop (one per iteration) vs device-resident (one
  per ``check_every`` - the O(1/k) claim);
* **chain throughput** (needle_nw + pathfinder, the wavefront chains):
  microseconds per chain iteration under the three replay modes -
  host-hop, device-resident (eager, on-device updates), and graph
  (iteration body captured once via ``LaunchChain.capture_unit`` and
  replayed as ONE fused dispatch, timed steady-state the way a serving
  loop would run it).

``--smoke`` shrinks reps for CI; ``--json`` dumps results for
``check_perf.py``; ``--check`` asserts the headline claims (sync
reduction ~= check_every, graph replay beats host-hop).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Stream, api, compile_cache, memory
from repro.core.cuda_suite import build_suite, run_entry
from repro.core.kernel import ChainStats

BACKEND = "loop"


def _entry(name):
    return next(e for e in build_suite(scale=1) if e.name == name)


def _chain_bufs(entry, rng):
    args = entry.make_args(rng)
    return {k: (memory.ConstArray(jnp.asarray(v)) if k in entry.const
                else jnp.asarray(v))
            for k, v in args.items()}


def sync_accounting(reps: int) -> dict:
    """bfs: stop-flag reads per iteration, host-hop vs device-resident."""
    entry = _entry("bfs_frontier")
    k = entry.chain.check_every
    host, dev = ChainStats(), ChainStats()
    for _ in range(reps):
        run_entry(entry, BACKEND, chain_stats=host, with_reference=False)
        run_entry(entry, BACKEND, chain_mode="device", chain_stats=dev,
                  with_reference=False)
    host_per, dev_per = host.syncs_per_iteration, dev.syncs_per_iteration
    return {
        "workload": "bfs_frontier",
        "check_every": k,
        "host_hop_syncs_per_iter": round(host_per, 4),
        "device_syncs_per_iter": round(dev_per, 4),
        "reduction": round(host_per / max(dev_per, 1e-9), 4),
    }


def _time_mode(entry, mode: str, reps: int, args) -> float:
    """Seconds per chain iteration under one replay mode (warm)."""
    def one_pass():
        out, _ = run_entry(entry, BACKEND, args=args, chain_mode=mode,
                           with_reference=False)
        jax.block_until_ready(
            memory.unwrap(out[tuple(entry.kernel.writes)[0]]))

    one_pass()                        # warm the launch cache
    t0 = time.perf_counter()
    for _ in range(reps):
        one_pass()
    return (time.perf_counter() - t0) / (reps * entry.chain.repeat)


def _time_graph_replay(entry, reps: int, args) -> float:
    """Steady-state seconds per iteration of the captured chain unit.

    Capture + instantiate happen once (the cudaGraphInstantiate cost a
    serving loop pays at startup); the timed region is pure replay, each
    replay advancing the heap by ``repeat - 1`` iterations.
    """
    bufs = {k: (memory.ConstArray(jnp.asarray(v)) if k in entry.const
                else jnp.asarray(v)) for k, v in args.items()}
    stream = Stream(bufs)
    chain = entry.chain
    for step in chain.steps:          # iteration 0 is eager, as in run_graph
        stream.launch(step.kernel, grid=step.grid, block=step.block,
                      dyn_shared=step.dyn_shared, backend=BACKEND)
    unit = chain.repeat - 1
    ex = chain.capture_unit(stream, unit, backend=BACKEND)
    ex.launch(stream)                 # first replay pays the XLA compile
    stream.synchronize()
    t0 = time.perf_counter()
    for _ in range(reps):
        ex.launch(stream)
    stream.synchronize()
    return (time.perf_counter() - t0) / (reps * unit)


def chain_throughput(name: str, reps: int) -> dict:
    entry = _entry(name)
    args = entry.make_args(np.random.default_rng(0))
    api.cache_clear()
    host = _time_mode(entry, "host", reps, args)
    device = _time_mode(entry, "device", reps, args)
    graph = _time_graph_replay(entry, reps, args)
    return {
        "iterations": entry.chain.repeat,
        "host_us_per_iter": round(host * 1e6, 2),
        "device_us_per_iter": round(device * 1e6, 2),
        "graph_us_per_iter": round(graph * 1e6, 2),
        "device_speedup": round(host / device, 4),
        "graph_speedup": round(host / graph, 4),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", metavar="PATH")
    ap.add_argument("--check", action="store_true",
                    help="assert sync reduction + graph replay wins")
    args = ap.parse_args(argv)
    reps = 3 if args.smoke else 10

    sync = sync_accounting(max(2, reps // 2))
    print(f"sync,bfs host-hop,{sync['host_hop_syncs_per_iter']:.2f},"
          f"syncs/iter")
    print(f"sync,bfs device-resident,{sync['device_syncs_per_iter']:.2f},"
          f"syncs/iter (check_every={sync['check_every']})")
    print(f"sync_reduction,{sync['reduction']:.2f},x fewer host syncs "
          f"(gate: ~check_every)")

    chains = {}
    for name in ("needle_nw", "pathfinder"):
        r = chains[name] = chain_throughput(name, reps)
        print(f"chain,{name},host {r['host_us_per_iter']}us/iter, "
              f"device {r['device_us_per_iter']}us/iter, "
              f"graph {r['graph_us_per_iter']}us/iter")
        print(f"chain_speedup,{name},device {r['device_speedup']}x, "
              f"graph {r['graph_speedup']}x vs host-hop")

    # headline = the iteration-dominated wavefront chain (needle: 63 tiny
    # launches); pathfinder rides along as the ping-pong shape
    results = {
        "backend": BACKEND,
        "sync": sync,
        "chains": chains,
        "device_speedup": chains["needle_nw"]["device_speedup"],
        "graph_speedup": chains["needle_nw"]["graph_speedup"],
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"json,{args.json},written")
    if args.check:
        assert sync["reduction"] >= 2.0, (
            f"device-resident replay must cut host syncs by >= 2x "
            f"(check_every={sync['check_every']}), got "
            f"{sync['reduction']:.2f}x")
        assert results["graph_speedup"] > 1.0, (
            f"fused graph replay of the needle chain must beat the "
            f"host-hop driver, got {results['graph_speedup']:.2f}x")
        print(f"check,passed,syncs cut {sync['reduction']:.1f}x, graph "
              f"{results['graph_speedup']:.2f}x")
    return results


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
