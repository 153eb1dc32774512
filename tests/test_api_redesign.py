"""CUDA-faithful API surface: dim3, triple-chevron, registry, streams+events."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Dim3,
    Policy,
    Runtime,
    Stream,
    UnknownBackend,
    backend_names,
    cache_clear,
    get_backend,
    launch,
    register_backend,
    supported,
    unregister_backend,
)
from repro.core import api
from repro.core.cuda_suite import make_stencil2d, make_vecadd
from repro.core.kernel import KernelDef

RNG = np.random.default_rng(0)


def _stencil_setup():
    h, w = 32, 64
    kernel = make_stencil2d(h, w)
    x = RNG.standard_normal((h, w)).astype(np.float32)
    args = {"x": jnp.asarray(x), "y": jnp.zeros((h, w), jnp.float32)}
    p = np.pad(x, 1, mode="edge")
    want = 0.2 * (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
                  + p[1:-1, :-2] + p[1:-1, 2:])
    return kernel, (w // 8, h // 8), (8, 8), args, want


# --- Dim3 --------------------------------------------------------------------
def test_dim3_normalization():
    assert Dim3.of(7) == Dim3(7, 1, 1)
    assert Dim3.of((2, 3)) == Dim3(2, 3, 1)
    assert Dim3.of((2, 3, 4)) == Dim3(2, 3, 4)
    assert Dim3.of(Dim3(5)) == Dim3(5)
    assert Dim3(2, 3, 4).size == 24
    with pytest.raises(ValueError):
        Dim3.of((1, 2, 3, 4))
    with pytest.raises(ValueError):
        Dim3.of(0)


def test_dim3_linearization_roundtrip():
    d = Dim3(3, 5, 7)
    for lin in range(d.size):
        x, y, z = d.coords(lin)
        assert d.linear(x, y, z) == lin
    # x-fastest ordering, as in CUDA
    assert d.coords(1) == (1, 0, 0)
    assert d.coords(3) == (0, 1, 0)
    assert d.coords(15) == (0, 0, 1)


@pytest.mark.parametrize("backend", ["loop", "vector", "pallas"])
def test_dim3_grid_equals_linear_grid(backend):
    """A 1-D kernel sees identical linear ids under any dim3 factoring."""
    n, block = 1024, 64
    k = make_vecadd(n)
    args = {"a": jnp.asarray(RNG.standard_normal(n).astype(np.float32)),
            "b": jnp.asarray(RNG.standard_normal(n).astype(np.float32)),
            "c": jnp.zeros(n, jnp.float32)}
    flat = launch(k, grid=16, block=block, args=args, backend=backend)
    for grid in ((4, 4), (2, 4, 2), (16, 1, 1)):
        out = launch(k, grid=grid, block=block, args=args, backend=backend)
        np.testing.assert_array_equal(np.asarray(out["c"]),
                                      np.asarray(flat["c"]))


@pytest.mark.parametrize("backend", ["loop", "vector", "pallas"])
def test_stencil2d_2d_launch(backend):
    """Acceptance: hotspot-style 2-D grid x 2-D block, identical everywhere."""
    kernel, grid, block, args, want = _stencil_setup()
    out = kernel[grid, block].on(backend=backend)(args)
    np.testing.assert_allclose(np.asarray(out["y"]), want,
                               rtol=2e-5, atol=2e-5)


# --- triple-chevron ----------------------------------------------------------
def test_chevron_matches_launch_bitwise():
    kernel, grid, block, args, _ = _stencil_setup()
    via_launch = launch(kernel, grid=grid, block=block, args=args)
    via_chevron = kernel[grid, block](args)
    via_kwargs = kernel[grid, block](**args)
    np.testing.assert_array_equal(np.asarray(via_launch["y"]),
                                  np.asarray(via_chevron["y"]))
    np.testing.assert_array_equal(np.asarray(via_launch["y"]),
                                  np.asarray(via_kwargs["y"]))


def test_chevron_dyn_shared_slot():
    from repro.core.cuda_suite import make_reverse
    d = np.arange(128, dtype=np.int32)
    out = make_reverse()[1, 128, 128](d=jnp.asarray(d))
    np.testing.assert_array_equal(np.asarray(out["d"]), d[::-1])


def test_chevron_stream_slot():
    n, block = 512, 128
    k = make_vecadd(n)
    s = Stream({"a": jnp.ones(n), "b": jnp.ones(n),
                "c": jnp.zeros(n)})
    ret = k[4, block, None, s]()
    assert ret is s
    np.testing.assert_allclose(s.memcpy_d2h("c"), 2.0)


def test_chevron_rejects_bad_config():
    k = make_vecadd(64)
    with pytest.raises(TypeError):
        k[4]                       # grid alone is not a launch config
    with pytest.raises(TypeError):
        k[1, 2, 3, 4, 5]           # too many chevron slots
    with pytest.raises(TypeError):
        k[4, 64].on(bogus=1)       # unknown execution option


def test_launch_config_on_rebinds():
    kernel, grid, block, args, want = _stencil_setup()
    cfg = kernel[grid, block]
    for backend in ("loop", "vector"):
        out = cfg.on(backend=backend, grain=2)(args)
        np.testing.assert_allclose(np.asarray(out["y"]), want,
                                   rtol=2e-5, atol=2e-5)


# --- backend registry --------------------------------------------------------
def test_registry_enumerates_builtins():
    names = backend_names()
    for expected in ("loop", "loop_nowarp", "naive", "vector", "pallas"):
        assert expected in names
    assert get_backend("loop").supports("barrier", "warp")
    assert not get_backend("naive").supports("barrier")


def test_coverage_enumerates_registry():
    """coverage() produces one Table-II row spanning every backend."""
    from repro.core import coverage
    from repro.core.cuda_suite import make_reduce_warp
    k = make_reduce_warp(128, 64)
    args = {"x": jnp.ones(128), "out": jnp.zeros(2)}
    row = coverage(k, grid=2, block=64, args=args)
    assert set(row) == set(backend_names())
    assert row["loop"] and row["vector"] and row["pallas"]
    assert not row["loop_nowarp"] and not row["naive"]   # warp kernel gaps


def test_registry_unknown_backend_errors():
    k = make_vecadd(64)
    args = {"a": jnp.ones(64), "b": jnp.ones(64), "c": jnp.zeros(64)}
    with pytest.raises(UnknownBackend):
        launch(k, grid=1, block=64, args=args, backend="tpu_v7")
    with pytest.raises(UnknownBackend):
        supported(k, "tpu_v7", args=args)


def test_registry_register_and_launch():
    from repro.core import lower_vector

    def echo_vector(kernel, *, grid, block, glob, grain, dyn_shared,
                    interpret):
        return lower_vector.run(kernel, grid=grid, block=block, glob=glob,
                                grain=grain, dyn_shared=dyn_shared)

    register_backend("vector_alias", echo_vector, {"barrier", "warp", "dim3"})
    try:
        assert "vector_alias" in backend_names()
        with pytest.raises(ValueError):   # duplicate registration
            register_backend("vector_alias", echo_vector)
        n = 256
        k = make_vecadd(n)
        args = {"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)}
        out = launch(k, grid=2, block=128, args=args, backend="vector_alias")
        np.testing.assert_allclose(np.asarray(out["c"]), 2.0)
        assert supported(k, "vector_alias", args=args)
    finally:
        unregister_backend("vector_alias")
    assert "vector_alias" not in backend_names()


# --- launch cache ------------------------------------------------------------
def test_cache_keyed_on_kernel_object_not_id():
    """Entries die with their kernel: no id()-reuse collisions, and
    cache_clear() empties the cache for benchmarks."""
    cache_clear()
    n = 128
    args = {"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)}
    k1 = make_vecadd(n)
    launch(k1, grid=1, block=n, args=args)
    assert api.cache_size() == 1
    del k1
    gc.collect()
    assert api.cache_size() == 0       # weakref entry died with the kernel
    k2 = make_vecadd(n)
    launch(k2, grid=1, block=n, args=args)
    launch(k2, grid=1, block=n, args=args)     # hit, not a second entry
    assert api.cache_size() == 1
    cache_clear()
    assert api.cache_size() == 0


def test_interpret_default_resolves_from_platform():
    """interpret=None means "interpret off the TPU"; the launch cache keys
    the resolved value, so the default and an explicit True share one
    specialization on the CPU."""
    n = 256
    k = make_vecadd(n)
    args = {"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)}
    default = api.compiled(k, grid=2, block=128, args=args,
                           backend="pallas")
    assert api.compiled(k, grid=2, block=128, args=args, backend="pallas",
                        interpret=True) is default
    assert jax.default_backend() != "tpu"
    out = launch(k, grid=2, block=128, args=args, backend="pallas")
    np.testing.assert_allclose(np.asarray(out["c"]), 2.0)
    assert api.cache_size() >= 1


# --- streams, events, hazards ------------------------------------------------
def test_stream_synchronize_empty_is_noop():
    s = Stream({"x": jnp.ones(4)})
    s.synchronize()
    assert s.stats.syncs == 0          # seed counted a sync here


def test_event_ordering_two_streams_shared_buffer():
    n, block = 512, 128
    k = make_vecadd(n)     # writes "c"
    counts = {}
    for pol in (Policy.HAZARD_ONLY, Policy.SYNC_ALWAYS):
        rt = Runtime({"a": jnp.ones(n), "b": jnp.ones(n),
                      "c": jnp.zeros(n)}, policy=pol)
        s0, s1 = rt.stream("compute"), rt.stream("copy")
        for _ in range(4):
            k[4, block, None, s0]()
        ev = rt.event("produced")
        ev.record(s0)                   # cudaEventRecord
        s1.wait_event(ev)               # cudaStreamWaitEvent
        host = s1.memcpy_d2h("c")       # ordered read on the other stream
        np.testing.assert_allclose(host, 2.0)
        assert ev.query()
        counts[pol] = rt.stats.syncs
    # acceptance: hazard-only pipeline syncs strictly less than HIP-CPU mode
    assert counts[Policy.HAZARD_ONLY] < counts[Policy.SYNC_ALWAYS]


def test_cross_stream_hazard_without_event():
    """A launch touching a buffer pending on another stream orders after it."""
    n, block = 512, 128

    def inc(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        return st.set_glob(c=st.glob["c"].at[gid].add(1.0))

    k_inc = KernelDef("inc", (inc,), writes=("c",))
    rt = Runtime({"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)})
    s0, s1 = rt.stream("s0"), rt.stream("s1")
    make_vecadd(n)[4, block, None, s0]()        # c = a + b on s0
    k_inc[4, block, None, s1]()                 # c += 1 on s1: RAW across streams
    assert s1.stats.barriers_inserted == 1
    np.testing.assert_allclose(rt.memcpy_d2h("c"), 3.0)


def test_chevron_stream_slot_honors_passed_values():
    """Buffer values passed to a stream-bound config are h2d writes, not
    silently discarded in favour of the stream's stale heap - and the
    kernel still reads the heap's unnamed buffers."""
    n, block = 256, 128
    k = make_vecadd(n)
    s = Stream({"a": jnp.zeros(n), "b": jnp.zeros(n), "c": jnp.zeros(n)})
    k[2, block, None, s](a=jnp.ones(n), b=jnp.ones(n))
    np.testing.assert_allclose(s.memcpy_d2h("c"), 2.0)
    # partial args: a comes from the call, b stays the heap's current value
    k[2, block, None, s](a=jnp.full(n, 5.0))
    np.testing.assert_allclose(s.memcpy_d2h("c"), 6.0)
    with pytest.raises(KeyError):
        k[2, block, None, s](nonexistent=None)


def test_stream_launch_forwards_execution_options():
    """on(interpret=..., pool=...) reaches api.launch through the stream."""
    seen = {}

    def recording(kernel, *, grid, block, glob, grain, dyn_shared, interpret):
        seen.update(grain=grain, interpret=interpret)
        from repro.core import lower_vector
        return lower_vector.run(kernel, grid=grid, block=block, glob=glob,
                                grain=grain, dyn_shared=dyn_shared)

    register_backend("recording", recording, {"barrier", "warp", "dim3"})
    try:
        n = 256
        k = make_vecadd(n)
        s = Stream({"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)})
        k[8, 32, None, s].on(backend="recording", grain="average",
                             interpret=False, pool=2)()
        assert seen["interpret"] is False
        assert seen["grain"] == 4          # average_grain(8 blocks, pool=2)
    finally:
        unregister_backend("recording")


def test_event_elapsed_measures_completion_not_sync_time():
    """elapsed() reflects when the fenced work finished, not when the host
    called synchronize() (cudaEventElapsedTime semantics)."""
    import time as _time
    n = 256
    rt = Runtime({"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)})
    s = rt.stream("s")
    make_vecadd(n)[2, 128, None, s]()
    e0 = rt.event().record(s)
    e1 = rt.event().record(s)
    _time.sleep(0.2)                   # host dawdles before asking
    assert e0.elapsed(e1) < 100.0      # gap is ~0, not the 200 ms sleep


def test_wait_event_fences_snapshot_not_later_writes():
    """cudaStreamWaitEvent waits on the record-time fence; work launched on
    the source stream after the record stays pending there."""
    n, block = 256, 128
    k = make_vecadd(n)
    rt = Runtime({"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)})
    s0, s1 = rt.stream("s0"), rt.stream("s1")
    k[2, block, None, s0]()                 # K1 writes c
    ev = rt.event().record(s0)
    k[2, block, None, s0]()                 # K2 re-writes c after the record
    s1.wait_event(ev)
    assert "c" in s0._pending               # K2's write is NOT cleared
    assert s0.stats.syncs == 0
    np.testing.assert_allclose(s0.memcpy_d2h("c"), 2.0)
    assert s0.stats.syncs == 1              # the d2h hazard, not the wait


def test_event_rerecord_supersedes_stale_watcher():
    """A watcher from an earlier record must not clobber completion state."""
    n = 256
    rt = Runtime({"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)})
    s = rt.stream("s")
    ev = rt.event().record(s)
    stale_gen = ev._gen
    ev.record(s)                            # re-record bumps the generation
    ev.synchronize()
    stamped = ev._time
    ev._watch(stale_gen, ())                # stale watcher fires late
    assert ev._time == stamped              # ignored: generation mismatch


def test_event_elapsed_monotonic():
    n = 256
    rt = Runtime({"a": jnp.ones(n), "b": jnp.ones(n), "c": jnp.zeros(n)})
    s = rt.stream("s")
    e0 = rt.event().record(s)
    make_vecadd(n)[2, 128, None, s]()
    e1 = rt.event().record(s)
    assert e0.elapsed(e1) >= 0.0
    with pytest.raises(RuntimeError):
        rt.event().synchronize()       # never recorded
