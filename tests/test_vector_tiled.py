"""The vector lowering's block schedules: tiled where the kernel's own
declarations make its blocks independent, serial everywhere else.

Every tiled launch must give the serial schedule's bits; the serial one is
reached through the module's private ``_run_serial`` (no public switch).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, launch, lower_vector
from repro.core.cuda_suite import build_suite, make_hotspot, run_entry
from repro.core.dim3 import Dim3
from repro.core.kernel import KernelDef

SUITE = build_suite(scale=1)

# entries whose launches are not all serial for want of combines
SCHEDULES = {
    "bfs_frontier": {"serial: reads written buffer 'visited'"},
    "pathfinder": {"tiled"},
    "needle_nw": {"serial: reads written buffer 'score'"},
    "backprop_layer": {"tiled"},
    "lud_diag": {"tiled"},
    "srad_step": {"serial: float reduce_sum in the block", "tiled"},
    "srad_v1": {"serial: reads written buffer 'I'",
                "serial: reads written buffer 'sums'", "tiled"},
    "lavamd": {"serial: float reduce_sum in the block"},
    "nn": {"tiled"},
    "kmeans": {"serial: reads written buffer 'assign'",
               "serial: reads written buffer 'cx'"},
    "streamcluster": {"serial: reads written buffer 'dirty'"},
    "hotspot": {"tiled"},
}


@pytest.fixture
def fresh_cache():
    api.cache_clear()
    yield
    api.cache_clear()


def _bits(out):
    return {k: np.asarray(v).tobytes() for k, v in out.items()}


def _schedules(entry):
    if entry.name in SCHEDULES:
        return SCHEDULES[entry.name]
    return {f"serial: no combines declared for {list(entry.kernel.writes)}"}


@pytest.mark.parametrize("entry", SUITE, ids=lambda e: e.name)
def test_suite_tiled_matches_serial(entry, fresh_cache, monkeypatch):
    args = entry.make_args(np.random.default_rng(3))
    with lower_vector.schedules() as traced:
        out, want = run_entry(entry, "vector", args=args)
    assert set(traced) == _schedules(entry)
    api.cache_clear()
    monkeypatch.setattr(lower_vector, "run", lower_vector._run_serial)
    serial, _ = run_entry(entry, "vector", args=args, with_reference=False)
    assert _bits(out) == _bits(serial)
    for k, v in want.items():
        np.testing.assert_allclose(np.asarray(out[k]), v, rtol=entry.tol,
                                   atol=entry.tol)


def make_owned(n_blocks: int, block: int) -> KernelDef:
    """y[b*block + t] = 2 x[...] + b: owned-slice writes, one per thread."""
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        v = st.glob["x"][gid % (n_blocks * block)] * 2.0 + ctx.bid
        return st.set_glob(y=st.glob["y"].at[gid].set(v, mode="drop"))

    return KernelDef("owned", (stage,), writes=("y",), reads=("x",),
                     combines={"y": "concat"})


def _owned_glob(n):
    x = jnp.arange(n, dtype=jnp.float32)
    return {"x": x, "y": jnp.full((n,), -1.0, jnp.float32)}


@pytest.mark.parametrize("tile", [None, 1, 3])
@pytest.mark.parametrize("bid_start,count", [(0, 10), (4, 8), (7, 3)])
def test_masked_tail_blocks(bid_start, count, tile, monkeypatch):
    """Blocks past the grid or the range write nothing: in one tile, and
    in a loop of tiles of ``tile`` blocks whose last tile runs past."""
    k, glob = make_owned(10, 4), _owned_glob(40)
    if tile is not None:
        nbytes = lower_vector._trace_block(k, Dim3(4), Dim3(10), glob,
                                           None)[2]
        monkeypatch.setattr(lower_vector, "_TILE_BYTES", tile * nbytes)
    kw = dict(grid=10, block=4, glob=glob, bid_start=bid_start, count=count)
    with lower_vector.schedules() as traced:
        tiled = jax.jit(lambda g: lower_vector.run(k, **{**kw, "glob": g}))(
            glob)
    serial = jax.jit(lambda g: lower_vector._run_serial(
        k, **{**kw, "glob": g}))(glob)
    assert traced == ["tiled"]
    assert _bits(tiled) == _bits(serial)
    y = np.asarray(tiled["y"])
    lo, hi = bid_start * 4, min(bid_start + count, 10) * 4
    assert (y[:lo] == -1).all() and (y[hi:] == -1).all()
    assert (y[lo:hi] == 2 * np.arange(lo, hi) + np.arange(lo, hi) // 4).all()


_SHARD_CHILD = r"""
import numpy as np, jax
assert jax.device_count() == 4, jax.device_count()
from repro.core import lower_vector
from repro.core.cuda_suite import build_suite, run_entry
names = {"hotspot", "pathfinder", "backprop_layer", "nn"}
for e in build_suite(1):
    if e.name not in names:
        continue
    args = e.make_args(np.random.default_rng(5))
    one, _ = run_entry(e, "vector", args=args, with_reference=False)
    for grain in (2, 3):
        with lower_vector.schedules() as traced:
            four, _ = run_entry(e, "shard_vector", args=args, grain=grain,
                                devices=4, with_reference=False)
        assert set(traced) == {"tiled"}, (e.name, traced)
        steps = e.chain.steps if e.chain else [e]
        for k in {w for s in steps for w in s.kernel.writes}:
            assert np.asarray(one[k]).tobytes() == \
                np.asarray(four[k]).tobytes(), (e.name, grain, k)
print("child-ok")
"""


def test_shard_vector_block_ranges_forced_devices():
    """shard_vector's traced block-range views, at grains 2 and 3 on four
    forced host devices, give vector's bits on the tiled schedule."""
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), os.pardir, "src")]
            + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
    )
    proc = subprocess.run([sys.executable, "-c", _SHARD_CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "child-ok" in proc.stdout


def test_launch_batch_of_tiled_kernel(fresh_cache):
    e = [e for e in SUITE if e.name == "hotspot"][0]
    rng = np.random.default_rng(11)
    reqs = [{k: jnp.asarray(v) for k, v in e.make_args(rng).items()}
            for _ in range(3)]
    rows = api.launch_batch(e.kernel, grid=e.grid, block=e.block,
                            args_list=reqs)
    singles = [launch(e.kernel, grid=e.grid, block=e.block, args=a)
               for a in reqs]
    for row, single in zip(rows, singles, strict=True):
        assert _bits(row) == _bits(single)
    entries = list(e.kernel._launch_cache.values())
    assert {x.schedule for x in entries} == {"tiled"}
    assert api.cache_stats().vector_tiled == 2      # batch of 3, and single


def make_reads_written() -> KernelDef:
    """y[gid] += 1 read back from y: blocks depend on y's old values."""
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        y = st.glob["y"]
        return st.set_glob(y=y.at[gid].set(y[gid] + 1.0, mode="drop"))

    return KernelDef("reads_written", (stage,), writes=("y",),
                     combines={"y": "concat"})


def make_claim() -> KernelDef:
    """atomicCAS claim of a flag per thread (the bfs idiom)."""
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        flag, old = ctx.atomic_cas(st.glob["flag"], gid % 4, 0, 1)
        won = st.glob["won"].at[gid].set(old == 0, mode="drop")
        return st.set_glob(flag=flag, won=won)

    return KernelDef("claim", (stage,), writes=("flag", "won"),
                     combines={"flag": "max", "won": "max"})


def make_float_add() -> KernelDef:
    def stage(ctx, st):
        return st.set_glob(y=ctx.atomic_add(st.glob["y"], ctx.tid % 2,
                                            jnp.ones(ctx.tid.shape)))

    return KernelDef("float_add", (stage,), writes=("y",),
                     combines={"y": "sum"})


def make_int_add_and_max() -> KernelDef:
    """Integer atomicAdd and float atomicMax across blocks: both commute."""
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        cnt = ctx.atomic_add(st.glob["cnt"], gid % 3, jnp.ones_like(gid))
        top = ctx.atomic_max(st.glob["top"], ctx.tid % 2,
                             jnp.sin(gid.astype(jnp.float32)))
        return st.set_glob(cnt=cnt, top=top)

    return KernelDef("int_add_max", (stage,), writes=("cnt", "top"),
                     combines={"cnt": "sum", "top": "max"})


@pytest.mark.parametrize("make,args,schedule", [
    (make_reads_written, {"y": np.zeros(32, np.float32)},
     "serial: reads written buffer 'y'"),
    (make_claim, {"flag": np.zeros(4, np.int32),
                  "won": np.zeros(32, np.bool_)},
     "serial: reads written buffer 'flag'"),
    (make_float_add, {"y": np.zeros(2, np.float32)},
     "serial: float add into 'y'"),
    (make_int_add_and_max, {"cnt": np.zeros(3, np.int32),
                            "top": np.full(2, -2.0, np.float32)},
     "tiled"),
], ids=["reads_written", "atomic_cas", "float_add", "int_add_max"])
def test_schedule_reason_on_entry(make, args, schedule, fresh_cache,
                                  monkeypatch):
    k = make()
    args = {n: jnp.asarray(v) for n, v in args.items()}
    entry = api.compiled(k, grid=4, block=8, args=args)
    assert entry.schedule == schedule
    out = launch(k, grid=4, block=8, args=args)
    monkeypatch.setattr(lower_vector, "run", lower_vector._run_serial)
    api.cache_clear()
    assert _bits(out) == _bits(launch(k, grid=4, block=8, args=args))


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in lower_vector._eqns(jaxpr)}


def test_hotspot_1024_has_no_block_loop():
    """Structure guard: a 1024x1024 hotspot launch is one tile, no loop."""
    k = make_hotspot(1024, 1024)
    grid = {n: jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
            for n in ("t", "p", "t_out")}
    with lower_vector.schedules() as traced:
        closed = jax.make_jaxpr(lambda g: lower_vector.run(
            k, grid=(128, 128), block=(8, 8), glob=g))(grid)
    assert traced == ["tiled"]
    prims = _primitives(closed.jaxpr)
    assert "scatter" in prims
    assert not prims & {"while", "scan"}, prims
    serial = jax.make_jaxpr(lambda g: lower_vector._run_serial(
        k, grid=(128, 128), block=(8, 8), glob=g))(grid)
    assert _primitives(serial.jaxpr) & {"while", "scan"}


def test_cache_stats_count_each_specialization_once(fresh_cache):
    hot = [e for e in SUITE if e.name == "hotspot"][0]
    bfs = [e for e in SUITE if e.name == "bfs_frontier"][0]
    for _ in range(2):
        run_entry(hot, "vector", with_reference=False)
    st = api.cache_stats()
    assert (st.vector_tiled, st.vector_serial) == (1, 0)
    run_entry(bfs, "vector", with_reference=False)
    run_entry(hot, "loop", with_reference=False)
    st = api.cache_stats()
    assert (st.vector_tiled, st.vector_serial) == (1, 1)
    api.cache_clear()
    assert (api.cache_stats().vector_tiled,
            api.cache_stats().vector_serial) == (0, 0)


# srad_v1's six kernels: the in-place ones and the reduction read what they
# write; prepare and srad write owned slices only
SRAD_V1_SCHEDULES = {
    "extract": "serial: reads written buffer 'I'",
    "prepare": "tiled",
    "reduce": "serial: reads written buffer 'sums'",
    "srad": "tiled",
    "srad2": "serial: reads written buffer 'I'",
    "compress": "serial: reads written buffer 'I'",
}


def test_srad_v1_schedule_of_each_kernel(fresh_cache):
    e = [e for e in SUITE if e.name == "srad_v1"][0]
    run_entry(e, "vector", with_reference=False)
    got = {}
    for step in e.chain.all_steps:
        for entry in step.kernel._launch_cache.values():
            got.setdefault(step.kernel.name, set()).add(entry.schedule)
    assert got == {k: {v} for k, v in SRAD_V1_SCHEDULES.items()}


def test_launches_are_counted_by_schedule(fresh_cache):
    """Every launch counts, warm ones too: srad_v1 runs extract and
    compress once and five steps an iteration, prepare and srad tiled."""
    e = [e for e in SUITE if e.name == "srad_v1"][0]
    hot = [e for e in SUITE if e.name == "hotspot"][0]
    for _ in range(2):
        run_entry(e, "vector", with_reference=False)
    st = api.cache_stats()
    iters = e.chain.repeat
    assert (st.tiled_launches, st.serial_launches) == (
        2 * 2 * iters, 2 * (2 + 3 * iters))
    run_entry(hot, "vector", with_reference=False)
    run_entry(hot, "loop", with_reference=False)
    st2 = api.cache_stats()
    assert st2.serial_launches == st.serial_launches
    assert st2.tiled_launches == st.tiled_launches + hot.chain.repeat
    api.cache_clear()
    assert (api.cache_stats().tiled_launches,
            api.cache_stats().serial_launches) == (0, 0)
