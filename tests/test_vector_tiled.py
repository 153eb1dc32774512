"""The vector lowering's block schedules: tiled where the kernel's own
declarations make its blocks independent, or where each block reads only
elements of its written buffers that no other block writes; serial
everywhere else.

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
from jax import lax

from repro.core import api, launch, lower_vector, memory
from repro.core.cuda_suite import (
    SRAD_THREADS,
    build_suite,
    make_hotspot,
    make_srad_reduce,
    run_entry,
    srad_reduce_passes,
)
from repro.core.dim3 import Dim3
from repro.core.kernel import KernelDef

SUITE = build_suite(scale=1)

# the block schedule of each suite kernel, by kernel name; srad_v1's
# in-place kernels and its reduction read only the elements they own,
# kmeans's assign reads its own points but adds float sums across blocks
KERNEL_SCHEDULES = {
    "vecadd": "serial: no combines declared for ['c']",
    "reverse": "serial: no combines declared for ['d']",
    "histogram_coalesced": "serial: no combines declared for ['hist']",
    "reduce_shared": "serial: no combines declared for ['out']",
    "reduce_warp": "serial: no combines declared for ['out']",
    "matmul_tiled": "serial: no combines declared for ['c']",
    "stencil1d": "serial: no combines declared for ['y']",
    "stencil2d": "serial: no combines declared for ['y']",
    "softmax_row": "serial: no combines declared for ['y']",
    "scan_block": "serial: no combines declared for ['y']",
    "transpose_tiled": "serial: no combines declared for ['y']",
    "pixel_pipeline": "serial: no combines declared for ['out']",
    "bfs_frontier": "serial: reads written buffer 'visited'",
    "pathfinder": "tiled",
    # the anti-diagonal comes from the ``diag`` buffer
    "needle_nw":
        "serial: data-dependent coordinates into written buffer 'score'",
    "backprop_layer": "tiled",
    "lud_diag": "tiled",
    "srad_stats": "tiled",
    "srad_update": "serial: float reduce_sum in the block",
    "extract": "tiled: owned reads of ['I']",
    "prepare": "tiled",
    "reduce": "tiled: owned reads of ['sums', 'sums2']",
    "srad": "tiled",
    "srad2": "tiled: owned reads of ['I']",
    "compress": "tiled: owned reads of ['I']",
    "lavamd": "serial: float reduce_sum in the block",
    "nn_reduce": "tiled",
    "nn_select": "tiled",
    "kmeans_assign": "serial: float add into 'sumx'",
    "kmeans_update": "tiled: owned reads of ['cx', 'cy']",
    "streamcluster": "serial: reads written buffer 'dirty'",
    "hotspot": "tiled",
}


def _launches(entry):
    """(kernel, grid, block, dyn_shared) of each launch the entry makes."""
    if entry.chain is None:
        return [(entry.kernel, entry.grid, entry.block, entry.dyn_shared)]
    return [(s.kernel, s.grid, s.block, s.dyn_shared)
            for s in entry.chain.all_steps]


@pytest.fixture
def fresh_cache():
    api.cache_clear()
    yield
    api.cache_clear()


def _bits(out):
    return {k: np.asarray(v).tobytes() for k, v in out.items()}


def _schedules(entry):
    return {KERNEL_SCHEDULES[k.name] for k, *_ in _launches(entry)}


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


def make_in_place(n_blocks: int, block: int) -> KernelDef:
    """y[i] = 3 y[i] + 1 in place: every block reads only what it writes."""
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        y = st.glob["y"]
        v = y[jnp.minimum(gid, n_blocks * block - 1)] * 3.0 + 1.0
        return st.set_glob(y=y.at[gid].set(v, mode="drop"))

    return KernelDef("in_place", (stage,), writes=("y",), reads=("y",),
                     combines={"y": "concat"})


@pytest.mark.parametrize("tile", [None, 1, 3])
@pytest.mark.parametrize("bid_start,count", [(0, 10), (4, 8), (7, 3)])
def test_owned_reads_in_place(bid_start, count, tile, monkeypatch):
    """An in-place kernel takes the tiled schedule on owned reads and gives
    the serial bits, in one tile and in loops of tiles, on range views."""
    k = make_in_place(10, 4)
    glob = {"y": jnp.arange(-20, 20, dtype=jnp.float32)}
    if tile is not None:
        nbytes = lower_vector._trace_block(k, Dim3(4), Dim3(10), glob,
                                           None)[2]
        monkeypatch.setattr(lower_vector, "_TILE_BYTES", tile * nbytes)
    kw = dict(grid=10, block=4, bid_start=bid_start, count=count)
    with lower_vector.schedules() as traced:
        tiled = jax.jit(lambda g: lower_vector.run(k, glob=g, **kw))(glob)
    serial = jax.jit(lambda g: lower_vector._run_serial(k, glob=g, **kw))(
        glob)
    assert traced == ["tiled: owned reads of ['y']"]
    assert _bits(tiled) == _bits(serial)
    y, y0 = np.asarray(tiled["y"]), np.asarray(glob["y"])
    lo, hi = bid_start * 4, min(bid_start + count, 10) * 4
    assert (y[:lo] == y0[:lo]).all() and (y[hi:] == y0[hi:]).all()
    assert (y[lo:hi] == y0[lo:hi] * np.float32(3) + np.float32(1)).all()


def test_owned_reads_under_vmap_and_jit():
    """The ownership proof evaluates coordinates from block ids alone, so
    the lowering traced under an outer vmap (a stacked batch) tiles too."""
    k = make_in_place(10, 4)
    ys = jnp.stack([jnp.arange(40, dtype=jnp.float32) * s for s in (1, -2)])
    with lower_vector.schedules() as traced:
        out = jax.jit(jax.vmap(lambda y: lower_vector.run(
            k, grid=10, block=4, glob={"y": y})["y"]))(ys)
    assert traced == ["tiled: owned reads of ['y']"]
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ys) * 3.0 + 1.0)


@pytest.mark.parametrize("mode", [None, "fill", "clip", "promise_in_bounds"])
def test_read_positions_name_the_elements_read(mode):
    """The positions an owned read records are the elements the gather
    reads, at every index mode: wrapped, clamped, or none where filled."""
    x = jnp.arange(12, dtype=jnp.int32).reshape(3, 4) * 10
    for idx in [(jnp.array([0, 2, 5, -1, -4]), jnp.array([1, 3, 9, -2, 0])),
                (1,), (slice(None), 2), (jnp.array([-9, 2]),)]:
        got = np.asarray(x[idx] if mode is None else x.at[idx].get(mode=mode))
        pos = np.asarray(lower_vector._read_positions(x.shape, idx, mode))
        read = pos >= 0
        assert read.any()
        assert (got[read] == np.asarray(x).reshape(-1)[pos[read]]).all()
        assert (read | (got == np.iinfo(np.int32).min)).all()


@pytest.mark.parametrize("npass", [0, 1])
def test_srad_reduce_passes_at_deployment_size(npass):
    """Both reduce passes of ``./srad 100 0.5 502 458`` (450 blocks, then
    one) take owned reads and give the serial schedule's bits."""
    ne = 502 * 458
    no, mul, blocks = srad_reduce_passes(ne)[npass]
    k = make_srad_reduce(no, mul, blocks)
    rng = np.random.default_rng(17 + npass)
    glob = {n: jnp.asarray(rng.uniform(1.0, 2.7, ne).astype(np.float32))
            for n in ("sums", "sums2")}
    kw = dict(grid=blocks, block=SRAD_THREADS)
    with lower_vector.schedules() as traced:
        tiled = jax.jit(lambda g: lower_vector.run(k, glob=g, **kw))(glob)
    serial = jax.jit(lambda g: lower_vector._run_serial(k, glob=g, **kw))(
        glob)
    assert traced == ["tiled: owned reads of ['sums', 'sums2']"]
    assert _bits(tiled) == _bits(serial)


_SHARD_CHILD = r"""
import numpy as np, jax
assert jax.device_count() == 4, jax.device_count()
from repro.core import lower_vector
from repro.core.cuda_suite import build_suite, entry_srad_v1, run_entry
names = {"hotspot", "pathfinder", "backprop_layer", "nn"}
# srad_v1 on a 64 x 64 image: eight blocks, so four devices own equal
# slices of every launch of 8 blocks
entries = [e for e in build_suite(1) if e.name in names]
for e in [*entries, entry_srad_v1(64, 64, 2)]:
    args = e.make_args(np.random.default_rng(5))
    one, _ = run_entry(e, "vector", args=args, with_reference=False)
    for grain in (2, 3):
        with lower_vector.schedules() as traced:
            four, _ = run_entry(e, "shard_vector", args=args, grain=grain,
                                devices=4, with_reference=False)
        assert {s.split(":")[0] for s in traced} == {"tiled"}, (e.name,
                                                               traced)
        steps = e.chain.steps if e.chain else [e]
        for k in {w for s in steps for w in s.kernel.writes}:
            assert np.asarray(one[k]).tobytes() == \
                np.asarray(four[k]).tobytes(), (e.name, grain, k)
print("child-ok")
"""


def test_shard_vector_block_ranges_forced_devices():
    """shard_vector's traced block-range views, at grains 2 and 3 on four
    forced host devices, give vector's bits on the tiled schedule, srad_v1's
    owned reads included."""
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
    """y[gid] += 1 read back from y: each block reads what it owns."""
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


def _read_then_write(read):
    """y[gid] = read(ctx, st, gid) + 1: a kernel reading y, to be refused."""
    def make() -> KernelDef:
        def stage(ctx, st):
            gid = ctx.bid * ctx.block_dim + ctx.tid
            v, y = read(ctx, st, gid)
            return st.set_glob(y=y.at[gid].set(v + 1.0, mode="drop"))

        return KernelDef("reads_y", (stage,), writes=("y",),
                         reads=("y", "at"), combines={"y": "concat"})
    return make


def _neighbour(ctx, st, gid):       # block b reads what block b + 1 writes
    y = st.glob["y"]
    return y[(gid + ctx.block_dim) % 32], y


def _data_index(ctx, st, gid):      # coordinates from a buffer's values
    y = st.glob["y"]
    return y[st.glob["at"][gid]], y


def _data_write(ctx, st, gid):      # a write masked by a buffer's values
    y = st.glob["y"]
    v = y[gid]
    return v, y.at[jnp.where(st.glob["at"][gid] > 100, gid, 64)].set(
        0.0, mode="drop")


def _after_write(ctx, st, gid):     # the block's own write, then a read
    y = st.glob["y"].at[gid].set(5.0, mode="drop")
    return y[gid], y


def _in_cond(ctx, st, gid):         # a read inside a nested trace
    y = st.glob["y"]
    return lax.cond(ctx.bid >= 0, lambda: y[gid],
                    lambda: jnp.zeros(gid.shape)), y


_READ_ARGS = {"y": np.arange(32, dtype=np.float32),
              "at": np.arange(32, dtype=np.int32)}


@pytest.mark.parametrize("make,args,schedule", [
    (make_reads_written, {"y": np.zeros(32, np.float32)},
     "tiled: owned reads of ['y']"),
    (make_claim, {"flag": np.zeros(4, np.int32),
                  "won": np.zeros(32, np.bool_)},
     "serial: reads written buffer 'flag'"),
    (make_float_add, {"y": np.zeros(2, np.float32)},
     "serial: float add into 'y'"),
    (make_int_add_and_max, {"cnt": np.zeros(3, np.int32),
                            "top": np.full(2, -2.0, np.float32)},
     "tiled"),
    (_read_then_write(_neighbour), _READ_ARGS,
     "serial: block 0 reads 'y' written by block 1"),
    (_read_then_write(_data_index), _READ_ARGS,
     "serial: data-dependent coordinates into written buffer 'y'"),
    (_read_then_write(_data_write), _READ_ARGS,
     "serial: data-dependent coordinates into written buffer 'y'"),
    (_read_then_write(_after_write), _READ_ARGS,
     "serial: reads 'y' after writing it"),
    (_read_then_write(_in_cond), _READ_ARGS,
     "serial: uses written buffer 'y' inside a nested trace"),
], ids=["reads_written", "atomic_cas", "float_add", "int_add_max",
        "cross_block_read", "data_dependent_read", "data_dependent_write",
        "read_after_write", "read_in_cond"])
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


@pytest.mark.parametrize("entry,name", [
    (e, name) for e in SUITE
    for name in dict.fromkeys(k.name for k, *_ in _launches(e))],
    ids=lambda p: p if isinstance(p, str) else p.name)
def test_schedule_of_each_suite_kernel(entry, name, fresh_cache):
    """Each launch of the kernel, bound as ``run_entry`` binds it, takes
    the kernel's schedule."""
    args = {k: memory.ConstArray(jnp.asarray(v)) if k in entry.const else v
            for k, v in entry.make_args(np.random.default_rng(0)).items()}
    got = {api.compiled(k, grid=g, block=b, args=args, backend="vector",
                        dyn_shared=ds).schedule
           for k, g, b, ds in _launches(entry) if k.name == name}
    assert got == {KERNEL_SCHEDULES[name]}


def test_launches_are_counted_by_schedule(fresh_cache):
    """Every launch counts, warm ones too: srad_v1 runs extract and
    compress once and five steps an iteration, all tiled, and all but
    prepare and srad on owned reads; kmeans's assign stays serial."""
    e = [e for e in SUITE if e.name == "srad_v1"][0]
    hot = [e for e in SUITE if e.name == "hotspot"][0]
    for _ in range(2):
        run_entry(e, "vector", with_reference=False)
    st = api.cache_stats()
    iters = e.chain.repeat
    assert (st.tiled_launches, st.serial_launches,
            st.owned_read_launches) == (
        2 * (2 + 5 * iters), 0, 2 * (2 + 3 * iters))
    run_entry(hot, "vector", with_reference=False)
    run_entry(hot, "loop", with_reference=False)
    st2 = api.cache_stats()
    assert st2.serial_launches == st.serial_launches
    assert st2.owned_read_launches == st.owned_read_launches
    assert st2.tiled_launches == st.tiled_launches + hot.chain.repeat
    km = [e for e in SUITE if e.name == "kmeans"][0]
    run_entry(km, "vector", with_reference=False)
    st3 = api.cache_stats()
    assert st3.serial_launches == st3.owned_read_launches - \
        st2.owned_read_launches > 0     # assign, then update, per iteration
    api.cache_clear()
    assert (api.cache_stats().tiled_launches,
            api.cache_stats().serial_launches,
            api.cache_stats().owned_read_launches) == (0, 0, 0)
