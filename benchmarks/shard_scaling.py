"""Block-throughput scaling of the shard backend vs device count.

The paper's multi-worker claim (SIV, Fig. 7-9): threadblocks are the unit
of parallelism, so throughput should scale with workers until the hardware
runs out.  This benchmark launches an embarrassingly-parallel
compute-heavy kernel - each block pushes its threads through a dependent
FMA chain and accumulates a per-block checksum with ``atomicAdd`` (so the
cross-shard combine path is on the measured path too) - through the
``shard`` backend and reports blocks/s per device count.

Every device count runs in its **own subprocess** with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``: that is how a CPU
host gets an XLA worker pool (there is no way to resize it after jax
initializes), and it keeps the 1-device baseline free of the multi-device
client's extra threads.  The children force ``JAX_PLATFORMS=cpu``, so
this is a CPU benchmark and never a chip path; on a four-chip host,
``chip_smoke.py --four-chips`` drives ``shard_vector`` on the real mesh.
Each child times several repetitions and keeps
the best (shared CI runners are noisy; the minimum is the least-disturbed
estimate of the machine's capability).

``speedup`` (max-device throughput over 1-device throughput) is the
headline number; ``--check`` asserts it clears ``--min-speedup``
(default 2.0, which needs >= 2 physical cores under the forced devices -
CI smoke passes a lower bar sized to its 2-core-class runners).
``--json`` feeds the CI perf gate (``benchmarks/check_perf.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

N_BLOCKS, BLOCK, STEPS = 1536, 64, 256
SMOKE = (512, 64, 192)


def make_blocksum(n: int, steps: int):
    """EP kernel: y[bid] = sum over the block's threads of FMA-chain(x)."""
    import jax.numpy as jnp

    from repro.core.kernel import KernelDef

    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        v = st.glob["x"][jnp.minimum(gid, n - 1)]
        for _ in range(steps):
            v = v * 0.999 + 0.001
        v = jnp.where(gid < n, v, 0.0)
        bid = jnp.full(v.shape, ctx.bid)
        return st.set_glob(y=ctx.atomic_add(st.glob["y"], bid, v))

    # block b writes only y[b]: an owned-slice (concat) write, the
    # zero-communication combine path
    return KernelDef(f"blocksum_{steps}", (stage,), writes=("y",),
                     reads=("x", "y"), est_block_work=3.0 * steps,
                     combines={"y": "concat"})


def child(devices: int, n_blocks: int, block: int, steps: int,
          iters: int, reps: int) -> None:
    """One device-count measurement; prints a JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import api, compile_cache

    compile_cache.use_jax_cache()
    assert jax.device_count() >= devices, (
        f"child asked for {devices} devices but the process has "
        f"{jax.device_count()}; XLA_FLAGS was not honored")
    n = n_blocks * block
    kernel = make_blocksum(n, steps)
    rng = np.random.default_rng(0)
    args = {"x": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "y": jnp.zeros(n_blocks, jnp.float32)}
    kw = dict(grid=n_blocks, block=block, backend="shard", devices=devices)
    out = api.launch(kernel, args=args, **kw)       # compile warmup
    q = np.float32(0.999) ** steps     # v -> v*q + (1-q) after the chain
    want = np.sum(np.asarray(args["x"]).reshape(n_blocks, block) * q
                  + (1 - q), axis=1, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(out["y"]), want,
                               rtol=1e-3, atol=1e-3)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = api.launch(kernel, args=args, **kw)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    print(json.dumps({"devices": devices, "s_per_launch": best,
                      "blocks_per_s": n_blocks / best}))


def sweep(counts, n_blocks, block, steps, iters, reps) -> dict:
    results = {"n_blocks": n_blocks, "block": block, "steps": steps,
               "throughput": {}}
    for d in counts:
        env = dict(
            os.environ,
            XLA_FLAGS=f"--xla_force_host_platform_device_count={d}",
            JAX_PLATFORMS="cpu",
        )
        argv = [sys.executable, os.path.abspath(__file__), "--child",
                str(d), str(n_blocks), str(block), str(steps), str(iters),
                str(reps)]
        proc = subprocess.run(argv, env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            raise SystemExit(
                f"child (devices={d}) failed:\n{proc.stderr[-2000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        results["throughput"][str(d)] = row["blocks_per_s"]
        print(f"devices_{d},{row['blocks_per_s']:,.0f},blocks/s "
              f"({row['s_per_launch']*1e3:.1f} ms/launch)")
    base = results["throughput"][str(counts[0])]
    best_d = counts[-1]
    results["devices_max"] = best_d
    results["speedup"] = results["throughput"][str(best_d)] / base
    results["speedup_best"] = max(results["throughput"].values()) / base
    print(f"speedup,{results['speedup']:.2f},{best_d} devices vs "
          f"{counts[0]} (block-throughput)")
    print(f"speedup_best,{results['speedup_best']:.2f},best device count "
          f"in sweep vs {counts[0]}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced problem size for CI")
    ap.add_argument("--json", metavar="PATH")
    ap.add_argument("--check", action="store_true",
                    help="assert the max-device speedup clears the bar")
    ap.add_argument("--min-speedup", type=float, default=2.0)
    ap.add_argument("--devices", type=int, default=8,
                    help="max forced host device count (sweeps 1,2,4,..)")
    ap.add_argument("--child", nargs=6, metavar="N", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        child(*map(int, args.child))
        return None

    n_blocks, block, steps = SMOKE if args.smoke else (N_BLOCKS, BLOCK,
                                                       STEPS)
    iters, reps = (3, 3) if args.smoke else (4, 5)
    counts = [d for d in (1, 2, 4, 8, 16) if d <= args.devices]
    results = sweep(counts, n_blocks, block, steps, iters, reps)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"json,{args.json},written")
    if args.check:
        assert results["speedup"] >= args.min_speedup, (
            f"block-throughput at {results['devices_max']} devices must be "
            f">= {args.min_speedup}x the 1-device throughput, got "
            f"{results['speedup']:.2f}x")
        print(f"check,passed,{results['speedup']:.2f}x >= "
              f"{args.min_speedup}x")
    return results


if __name__ == "__main__":
    main()
