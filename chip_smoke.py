"""Bring-up smoke test: CuPBoP-JAX's main path on one TPU chip.

Drives the entry points users call, in one process:

1. device check: refuses to run anywhere but on a TPU;
2. suite: all 24 ``build_suite(1)`` entries through ``run_entry`` on the
   ``vector`` lowering, each against its NumPy oracle at the entry's own
   tolerance, with the block schedule it traced; each entry is also
   tried on ``pallas`` (Mosaic, not the interpreter) and reported as
   ``compiled+correct`` or
   ``unsupported: <reason>`` - a refusal is not a failure, a wrong answer is;
3. hotspot at Rodinia's ``1024 2 4`` (a 1024x1024 grid, 4 iterations,
   inputs made from a seed in hotspot's file format) through the ``host``,
   ``device`` and ``graph`` chain modes: each against the oracle, the
   three bit-identical on ``t_out``, and the launch on the tiled block
   schedule (``CacheStats.vector_tiled``);
4. serving: a ``KernelService`` on ``vector`` with four single-launch
   endpoints (vecadd at 1M elements), two waves of concurrent requests,
   every answer checked, at least one stacked dispatch and no fallback of a
   stacked dispatch to singles.

``--four-chips`` runs only the multi-chip phase instead: hotspot 1024x1024
(a ``"sum"`` combine) and lavaMD at ``-boxes1d 10`` (1000 boxes, an
owned-slice ``"concat"`` combine) on ``shard_vector`` over four devices,
each bit-identical to ``vector`` on one chip.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # a four-chip host

Times printed along the way are one-off bring-up readings, not benchmark
metrics; ``trace`` and ``first_call`` are the program's own compile-layer
counters (``api.cache_stats()``: ``trace_s`` and ``first_call_s``, graph
replays included), and the closing line counts JAX persistent-cache hits.
The last line of standard output is one JSON object naming the device;
the exit code is non-zero on any failure.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (UnsupportedKernel, api, compile_cache,  # noqa: E402
                        lower_vector)
from repro.core.conformance import oracle_check  # noqa: E402
from repro.core.cuda_suite import (build_suite, entry_hotspot,  # noqa: E402
                                   entry_lavamd, run_entry)
from repro.serve import KernelService  # noqa: E402

SEED = 0
HOTSPOT = (1024, 1024, 4)        # Rodinia hotspot "1024 2 4"
LAVAMD = (1000, 100, 27)         # Rodinia lavaMD "-boxes1d 10"
SERVE_SCALE = 256                # build_suite scale: vecadd at 1M elements
SERVE_ROSTER = ("vecadd", "softmax_row", "reduce_shared", "stencil1d")


class SmokeFailure(RuntimeError):
    """A phase found a wrong answer or a missing mechanism."""


def compile_clock() -> tuple[float, float]:
    """Host seconds so far in the launch cache's miss path and in first
    dispatches of new specializations."""
    s = api.cache_stats()
    return s.trace_s, s.first_call_s


def count_cache_hits() -> list[int]:
    """A one-element counter of JAX persistent-cache hits from now on."""
    hits = [0]

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    return hits


def compile_since(c0: tuple[float, float]) -> str:
    trace, first = (b - a for a, b in zip(c0, compile_clock()))
    return f"trace={trace:.4f}s first_call={first:.4f}s"


def check(out, want, tol, what):
    err, bad = oracle_check(out, want, tol)
    if bad:
        raise SmokeFailure(f"{what}: oracle mismatch: {'; '.join(bad)}")
    return err


def bits(a) -> bytes:
    return np.asarray(a).tobytes()


def timed(fn):
    c0, t0 = compile_clock(), time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0, compile_since(c0)


def phase_suite(scale: int = 1) -> None:
    """Every suite entry on vector (must pass) and on pallas (reported)."""
    failures = []
    for e in build_suite(scale):
        rng = np.random.default_rng(SEED)
        args = e.make_args(rng)
        with lower_vector.schedules() as traced:
            out, want = run_entry(e, "vector", args=args)
        try:
            err = check(out, want, e.tol, f"{e.name} on vector")
            print(f"suite {e.name:16s} vector ok max_err={err:.3g} "
                  f"schedule={sorted(set(traced))}", flush=True)
        except SmokeFailure as f:
            failures.append(str(f))
            print(f"suite {e.name:16s} vector FAIL {f}", flush=True)
        try:
            out, want = run_entry(e, "pallas", args=args)
        except UnsupportedKernel as u:
            reason = str(u).splitlines()[0]
            print(f"suite {e.name:16s} pallas unsupported: {reason}",
                  flush=True)
            continue
        try:
            check(out, want, e.tol, f"{e.name} on pallas")
            print(f"suite {e.name:16s} pallas compiled+correct", flush=True)
        except SmokeFailure as f:
            failures.append(str(f))
            print(f"suite {e.name:16s} pallas FAIL {f}", flush=True)
    if failures:
        raise SmokeFailure(f"{len(failures)} suite failure(s): "
                           + " | ".join(failures))


def phase_hotspot(h: int, w: int, iters: int) -> None:
    """hotspot at deployment size through the three chain replay modes."""
    e = entry_hotspot(h, w, iters)
    t0 = time.perf_counter()
    args = e.make_args(np.random.default_rng(SEED))
    want = e.reference(args)
    print(f"hotspot {h}x{w} iters={iters}: inputs+oracle "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    t_out = {}
    tiled0 = api.cache_stats().vector_tiled
    for mode in ("host", "device", "graph"):
        def go(mode=mode):
            return run_entry(e, "vector", args=args, chain_mode=mode,
                             with_reference=False)[0]
        _, cold_s, compile_s = timed(go)
        out, warm_s, _ = timed(go)
        err = check(out, want, e.tol, f"hotspot {mode}")
        t_out[mode] = bits(out["t_out"])
        print(f"hotspot mode={mode:6s} wall={warm_s:.4f}s "
              f"first_run={cold_s:.4f}s {compile_s} "
              f"max_err={err:.3g}", flush=True)
    if len(set(t_out.values())) != 1:
        raise SmokeFailure("hotspot: host/device/graph t_out bits differ")
    tiled = api.cache_stats().vector_tiled - tiled0
    print(f"hotspot CacheStats.vector_tiled +{tiled}", flush=True)
    if tiled < 1:
        raise SmokeFailure("hotspot: the launch did not take the tiled "
                           "block schedule")


def phase_serving(scale: int) -> None:
    """Two waves of concurrent requests through a vector KernelService."""
    entries = {e.name: e for e in build_suite(scale)
               if e.name in SERVE_ROSTER}
    per_wave = 8                                  # requests per endpoint
    rng = np.random.default_rng(SEED)
    svc = KernelService(backend="vector", max_batch=8, autostart=False,
                        default_timeout_s=600.0)
    try:
        for e in entries.values():
            svc.register_entry(e)
        for wave in range(2):
            reqs = [(e, e.make_args(rng)) for _ in range(per_wave)
                    for e in entries.values()]
            tickets = [None] * len(reqs)

            def submit(lo, hi):
                for i in range(lo, hi):
                    e, a = reqs[i]
                    tickets[i] = svc.submit(e.name, a, tenant=f"t{i % 4}")

            # wave 0 queues before the worker starts (so it must stack);
            # wave 1 arrives from four client threads while it runs
            if wave == 0:
                submit(0, len(reqs))
                svc.start()
            else:
                step = -(-len(reqs) // 4)
                threads = [threading.Thread(target=submit,
                                            args=(i, min(i + step,
                                                         len(reqs))))
                           for i in range(0, len(reqs), step)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            t0, c0 = time.perf_counter(), compile_clock()
            for (e, a), t in zip(reqs, tickets):
                check(t.result(timeout=600.0), e.reference(a), e.tol,
                      f"serving {e.name} request {t.rid}")
            print(f"serving wave={wave} requests={len(reqs)} "
                  f"wall={time.perf_counter() - t0:.4f}s "
                  f"{compile_since(c0)}", flush=True)
        st = svc.stats()
    finally:
        svc.close()
    print(f"serving stats: completed={st.completed} failed={st.failed} "
          f"timed_out={st.timed_out} dispatches={st.dispatches} "
          f"occupancy={st.to_json()['batch_occupancy']} "
          f"batch_fallbacks={st.batch_fallbacks}", flush=True)
    if st.failed or st.timed_out or st.completed != st.submitted:
        raise SmokeFailure(f"serving: {st.failed} failed, {st.timed_out} "
                           f"timed out of {st.submitted}")
    if not any(size > 1 for size in st.batch_occupancy):
        raise SmokeFailure("serving: no stacked dispatch happened")
    if st.batch_fallbacks:
        raise SmokeFailure(f"serving: {st.batch_fallbacks} stacked "
                           f"dispatch(es) fell back to singles; last: "
                           f"{st.last_batch_error}")


def phase_four_chips(hotspot=HOTSPOT, lavamd=LAVAMD) -> None:
    """shard_vector on four devices, bit-identical to vector on one."""
    devs = jax.devices()[:4]
    if len({d.id for d in devs}) != 4:
        raise SmokeFailure(f"four-chip phase needs 4 devices, got {devs}")
    cases = (("hotspot", entry_hotspot(*hotspot), "t_out"),
             ("lavamd", entry_lavamd(*lavamd), "force"))
    with warnings.catch_warnings():
        # a concat that does not divide would degrade to "sum": an error
        warnings.filterwarnings("error", message=r".*combines='concat'")
        for name, e, buf in cases:
            args = e.make_args(np.random.default_rng(SEED))
            one, want = run_entry(e, "vector", args=args)
            four, _ = run_entry(e, "shard_vector", args=args, devices=4)
            err = check(four, want, e.tol, f"{name} on 4 devices")
            spread = len(four[buf].sharding.device_set)
            if spread != 4:
                raise SmokeFailure(f"{name}: result lives on {spread} "
                                   f"device(s), not the 4-device mesh")
            if bits(four[buf]) != bits(one[buf]):
                raise SmokeFailure(f"{name}: shard_vector on 4 devices "
                                   f"differs from vector on one")
            print(f"four-chip {name}: bit-identical to one-chip vector, "
                  f"{spread} devices, max_err={err:.3g}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_vector phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this test "
              f"never runs on another platform", file=sys.stderr)
        return 1
    print(f"jax compilation cache: {compile_cache.use_jax_cache()}",
          flush=True)
    cache_hits = count_cache_hits()
    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("suite", phase_suite),
               ("hotspot", lambda: phase_hotspot(*HOTSPOT)),
               ("serving", lambda: phase_serving(SERVE_SCALE))])
    t_start = time.perf_counter()
    for name, fn in phases:
        t0, c0 = time.perf_counter(), compile_clock()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - any failure fails the run
            traceback.print_exc()
            print(f"phase {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            return 1
        print(f"phase {name} ok: {time.perf_counter() - t0:.2f}s, "
              f"{compile_since(c0)}", flush=True)
    print(f"total {time.perf_counter() - t_start:.2f}s, "
          f"{compile_since((0.0, 0.0))}, persistent-cache hits "
          f"{cache_hits[0]}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
