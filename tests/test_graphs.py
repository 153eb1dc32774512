"""Graph capture/instantiate/replay + compile-cache counters (ISSUE 2)."""
import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    GraphError,
    Runtime,
    Stream,
    api,
    compile_cache,
    launch,
)
from repro.core.cuda_suite import (
    OOB,
    build_suite,
    make_vecadd,
)
from repro.core.kernel import KernelDef

RNG = np.random.default_rng(7)


def make_scale(n, src, dst, scale):
    """dst = scale * src: a minimal declared-reads SPMD kernel."""

    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        val = st.glob[src][jnp.minimum(gid, n - 1)] * scale
        idx = jnp.where(gid < n, gid, OOB)
        return st.set_glob(
            **{dst: st.glob[dst].at[idx].set(val, mode="drop")})

    return KernelDef(f"scale_{src}_{dst}", (stage,), writes=(dst,),
                     reads=(src, dst))


# --- capture / instantiate / replay equivalence ------------------------------
@pytest.mark.parametrize("name", ["vecadd", "reduce_shared", "softmax_row",
                                  "stencil2d"])
def test_replay_matches_eager_suite_kernel(name):
    """Graph replay is bit-identical to the eager launch path."""
    e = next(e for e in build_suite(scale=1) if e.name == name)
    args = {k: jnp.asarray(v) for k, v in e.make_args(RNG).items()}
    eager = launch(e.kernel, grid=e.grid, block=e.block, args=args,
                   dyn_shared=e.dyn_shared)

    s = Stream(dict(args))
    g = s.begin_capture()
    e.kernel[e.grid, e.block, e.dyn_shared, s]()
    s.end_capture()
    ex = g.instantiate(s.buffers)
    ex.launch(s)
    for w in e.kernel.writes:
        np.testing.assert_array_equal(np.asarray(s.buffers[w]),
                                      np.asarray(eager[w]))


@pytest.mark.parametrize("backend", ["loop", "vector", "pallas"])
def test_replay_pipeline_all_backends(backend):
    """A 3-kernel chain replays correctly under every lowering."""
    n, block = 512, 128
    x = RNG.standard_normal(n).astype(np.float32)
    bufs = {"b0": jnp.asarray(x)}
    bufs.update({f"b{i}": jnp.zeros(n, jnp.float32) for i in (1, 2, 3)})
    s = Stream(bufs)
    g = s.begin_capture()
    for i in range(3):
        k = make_scale(n, f"b{i}", f"b{i+1}", 2.0)
        k[-(-n // block), block, None, s].on(backend=backend)()
    s.end_capture()
    g.instantiate(s.buffers).launch(s)
    np.testing.assert_allclose(s.memcpy_d2h("b3"), 8.0 * x, rtol=1e-6)


def test_replay_is_repeatable_and_counts_dispatches():
    n, block = 256, 128
    k = make_vecadd(n)
    s = Stream({"a": jnp.ones(n), "b": jnp.ones(n),
                "c": jnp.zeros(n, jnp.float32)})
    g = s.begin_capture()
    k[2, block, None, s]()
    s.end_capture()
    ex = g.instantiate(s.buffers)
    for _ in range(3):
        ex.launch(s)
    assert s.stats.graph_launches == 3
    assert ex.launches == 3
    np.testing.assert_allclose(s.memcpy_d2h("c"), 2.0)


def test_captured_h2d_and_update():
    """memcpy_h2d captures as a DAG node; update_h2d swaps its source."""
    n, block = 256, 128
    k = make_vecadd(n)
    s = Stream({"a": jnp.zeros(n, jnp.float32), "b": jnp.ones(n),
                "c": jnp.zeros(n, jnp.float32)})
    g = s.begin_capture()
    s.memcpy_h2d("a", np.full(n, 3.0, np.float32))
    k[2, block, None, s]()
    s.end_capture()
    assert [nd.kind for nd in g.nodes] == ["h2d", "kernel"]
    ex = g.instantiate(s.buffers)
    ex.launch(s)
    np.testing.assert_allclose(s.memcpy_d2h("c"), 4.0)
    ex.update_h2d("a", np.full(n, 9.0, np.float32))
    ex.launch(s)
    np.testing.assert_allclose(s.memcpy_d2h("c"), 10.0)
    with pytest.raises(GraphError):
        ex.update_h2d("nope", np.zeros(n, np.float32))


# --- cross-stream event dependencies ----------------------------------------
def test_replay_respects_cross_stream_event_deps():
    """record/wait_event edges order otherwise-independent streams."""
    n, block = 256, 128
    ka = make_scale(n, "a", "x", 2.0)     # stream A: x = 2a
    kb = make_scale(n, "a", "y", 3.0)     # stream B: y = 3a
    x0 = RNG.standard_normal(n).astype(np.float32)

    def capture(with_event):
        rt = Runtime({"a": jnp.asarray(x0),
                      "x": jnp.zeros(n, jnp.float32),
                      "y": jnp.zeros(n, jnp.float32)})
        sa, sb = rt.stream("A"), rt.stream("B")
        g = rt.begin_capture()
        ka[2, block, None, sa]()
        if with_event:
            ev = rt.event("produced")
            ev.record(sa)
            sb.wait_event(ev)
        kb[2, block, None, sb]()
        rt.end_capture()
        return rt, g

    # no event: the kernels are independent -> one topological level
    rt, g_free = capture(with_event=False)
    assert len(g_free.levels()) == 1 and len(g_free.nodes) == 2

    # with record/wait: B's kernel is transitively ordered after A's
    rt, g_dep = capture(with_event=True)
    kinds = [nd.kind for nd in g_dep.nodes]
    assert kinds == ["kernel", "event_record", "event_wait", "kernel"]
    rec, wait, consumer = g_dep.nodes[1], g_dep.nodes[2], g_dep.nodes[3]
    assert rec.idx in wait.deps          # wait depends on its record
    assert wait.idx in consumer.deps     # stream order after the wait
    levels = g_dep.levels()
    lvl = {i: d for d, idxs in enumerate(levels) for i in idxs}
    assert lvl[g_dep.nodes[0].idx] < lvl[consumer.idx]

    ex = g_dep.instantiate(rt.buffers)
    ex.launch(rt)
    np.testing.assert_allclose(rt.memcpy_d2h("x"), 2.0 * x0, rtol=1e-6)
    np.testing.assert_allclose(rt.memcpy_d2h("y"), 3.0 * x0, rtol=1e-6)


def test_raw_hazard_orders_nodes_across_streams():
    """A RAW hazard (no explicit event) still serializes the DAG."""
    n, block = 256, 128
    producer = make_scale(n, "a", "mid", 2.0)
    consumer = make_scale(n, "mid", "out", 5.0)
    rt = Runtime({"a": jnp.ones(n, jnp.float32),
                  "mid": jnp.zeros(n, jnp.float32),
                  "out": jnp.zeros(n, jnp.float32)})
    s0, s1 = rt.stream("s0"), rt.stream("s1")
    g = rt.begin_capture()
    producer[2, block, None, s0]()
    consumer[2, block, None, s1]()
    rt.end_capture()
    assert g.nodes[0].idx in g.nodes[1].deps   # RAW on "mid"
    assert len(g.levels()) == 2
    g.instantiate(rt.buffers).launch(rt)
    np.testing.assert_allclose(rt.memcpy_d2h("out"), 10.0)


# --- capture rules -----------------------------------------------------------
def test_capture_forbids_host_visible_ops():
    n = 128
    s = Stream({"a": jnp.ones(n)})
    s.begin_capture()
    with pytest.raises(GraphError):
        s.memcpy_d2h("a")
    with pytest.raises(GraphError):
        s.synchronize()
    with pytest.raises(GraphError):
        s.begin_capture()                     # double capture
    g = s.end_capture()
    with pytest.raises(GraphError):
        s.end_capture()                       # not capturing anymore
    assert g.nodes == []


def test_wait_on_foreign_or_uncaptured_event_raises():
    from repro.core import Event
    n = 128
    s = Stream({"a": jnp.ones(n)})
    s.begin_capture()
    with pytest.raises(GraphError):
        s.wait_event(Event("never-recorded"))
    s.end_capture()


def test_instantiate_during_capture_raises():
    s = Stream({"a": jnp.ones(8)})
    g = s.begin_capture()
    with pytest.raises(GraphError):
        g.instantiate()
    s.end_capture()


def test_runtime_capture_refuses_half_captured_state():
    """begin_capture must not attach any stream if one is already busy."""
    rt = Runtime({"a": jnp.ones(8)})
    sa, sb = rt.stream("A"), rt.stream("B")
    sb.begin_capture()
    with pytest.raises(GraphError, match="already capturing"):
        rt.begin_capture()
    assert sa._capture is None        # A was never attached
    sb.end_capture()
    rt.begin_capture()                # now fine
    rt.end_capture()


def test_update_h2d_validates_shape_and_ambiguity():
    n = 64
    s = Stream({"a": jnp.zeros(n, jnp.float32)})
    g = s.begin_capture()
    s.memcpy_h2d("a", np.ones(n, np.float32))
    s.memcpy_h2d("a", np.ones(n, np.float32))
    s.end_capture()
    ex = g.instantiate(s.buffers)
    with pytest.raises(GraphError, match="2 captured h2d nodes"):
        ex.update_h2d("a", np.ones(n, np.float32))
    s2 = Stream({"a": jnp.zeros(n, jnp.float32)})
    g2 = s2.begin_capture()
    s2.memcpy_h2d("a", np.ones(n, np.float32))
    s2.end_capture()
    ex2 = g2.instantiate(s2.buffers)
    with pytest.raises(GraphError, match="must match"):
        ex2.update_h2d("a", np.ones(n + 1, np.float32))


# --- Event.elapsed error contract (satellite fix) ----------------------------
def test_elapsed_raises_before_record():
    from repro.core import Event
    e1, e2 = Event("start"), Event("end")
    with pytest.raises(RuntimeError, match="has not been recorded"):
        e1.elapsed(e2)
    # one recorded, one not: still a clear error, never garbage/None
    s = Stream({"a": jnp.ones(8)})
    s.record(e1)
    with pytest.raises(RuntimeError, match="end event"):
        e1.elapsed(e2)


def test_elapsed_raises_for_captured_event():
    from repro.core import Event
    e = Event("captured")
    s = Stream({"a": jnp.ones(8)})
    s.begin_capture()
    s.record(e)
    s.end_capture()
    with pytest.raises(RuntimeError, match="captured into a graph"):
        e.elapsed(e)


def test_elapsed_happy_path_still_works():
    n, block = 256, 128
    k = make_vecadd(n)
    s = Stream({"a": jnp.ones(n), "b": jnp.ones(n),
                "c": jnp.zeros(n, jnp.float32)})
    e1 = s.record()
    k[2, block, None, s]()
    e2 = s.record()
    assert e1.elapsed(e2) >= 0.0


# --- compile-cache counters --------------------------------------------------
def test_cache_hit_miss_counters():
    api.cache_clear()
    n = 128
    k = make_vecadd(n)
    args = {"a": jnp.ones(n), "b": jnp.ones(n),
            "c": jnp.zeros(n, jnp.float32)}
    launch(k, grid=1, block=n, args=args)
    launch(k, grid=1, block=n, args=args)
    launch(k, grid=2, block=64, args=args)    # new geometry -> new entry
    s = api.cache_stats()
    assert (s.misses, s.hits) == (2, 1)
    assert api.cache_size() == 2
    api.cache_clear()
    assert api.cache_stats().misses == 0


def test_cache_lru_eviction_counter():
    api.cache_clear()
    api.cache_resize(2)
    try:
        n = 128
        k = make_vecadd(n)
        args = {"a": jnp.ones(n), "b": jnp.ones(n),
                "c": jnp.zeros(n, jnp.float32)}
        for grid in (1, 2, 4):
            launch(k, grid=grid, block=32, args=args)
        assert api.cache_size() == 2
        assert api.cache_stats().evictions == 1
        # grid=1 was evicted: relaunching it is a miss again
        launch(k, grid=1, block=32, args=args)
        assert api.cache_stats().misses == 4
    finally:
        api.cache_resize(256)
        api.cache_clear()


def test_disk_cache_roundtrip(tmp_path):
    """A 'new process' (in-memory cache cleared) reloads from disk."""
    api.cache_clear()
    api.enable_disk_cache(str(tmp_path))
    try:
        n = 128
        k = make_vecadd(n)
        args = {"a": jnp.ones(n), "b": jnp.ones(n),
                "c": jnp.zeros(n, jnp.float32)}
        launch(k, grid=1, block=n, args=args)
        assert api.cache_stats().disk_stores == 1
        assert len(list(tmp_path.glob("*.bin"))) == 1
        api.cache_clear()                     # simulate process restart
        out = launch(k, grid=1, block=n, args=args)
        s = api.cache_stats()
        assert s.disk_hits == 1 and s.misses == 1
        np.testing.assert_allclose(np.asarray(out["c"]), 2.0)
        # an equivalent kernel from the same factory shares the artifact
        out2 = launch(make_vecadd(n), grid=1, block=n, args=args)
        assert api.cache_stats().disk_hits == 2
        np.testing.assert_allclose(np.asarray(out2["c"]), 2.0)
    finally:
        api.disable_disk_cache()
        api.cache_clear()


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "repo"])
def test_jax_cache_dir_env_wins_else_fixed_repo_path(env_dir, tmp_path,
                                                     monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache goes
    to <repo>/.jax_cache, a path built from nothing that changes per run."""
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        path = compile_cache.use_jax_cache()
        now = jax.config.jax_compilation_cache_dir
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
    if env_dir:
        assert path == str(tmp_path) and now == was[0]
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == now == os.path.join(repo, ".jax_cache")


def test_compiled_preresolves_without_running():
    """api.compiled() warms the same entry a launch would dispatch through."""
    api.cache_clear()
    n = 128
    k = make_vecadd(n)
    args = {"a": jnp.ones(n), "b": jnp.ones(n),
            "c": jnp.zeros(n, jnp.float32)}
    ck = api.compiled(k, grid=1, block=n, args=args)
    assert ck.source == "trace" and ck.hits == 0
    assert api.cache_stats().misses == 1
    launch(k, grid=1, block=n, args=args)     # cache hit, no re-trace
    s = api.cache_stats()
    assert (s.misses, s.hits) == (1, 1) and ck.hits == 1
    api.cache_clear()


def test_fingerprint_large_array_closures_differ():
    """Captured arrays hash by content, not (truncating) repr."""
    def make_weighted(w):
        def stage(ctx, st):
            val = st.glob["x"][ctx.tid] * jnp.asarray(w)[0]
            return st.set_glob(y=st.glob["y"].at[ctx.tid].set(val))
        return KernelDef("weighted", (stage,), writes=("y",),
                         reads=("x", "y"))

    w1 = np.ones(2048, np.float32)
    w2 = w1.copy()
    w2[1024] = 5.0                  # deep inside repr's "..." truncation
    assert (make_weighted(w1).fingerprint()
            != make_weighted(w2).fingerprint())
    assert (make_weighted(w1).fingerprint()
            == make_weighted(w1.copy()).fingerprint())


def test_fingerprint_stability():
    n = 128
    assert make_vecadd(n).fingerprint() == make_vecadd(n).fingerprint()
    assert make_vecadd(n).fingerprint() != make_vecadd(n + 1).fingerprint()
    assert (make_scale(n, "a", "b", 2.0).fingerprint()
            != make_scale(n, "a", "b", 3.0).fingerprint())


def test_cache_entries_still_die_with_kernel():
    """The LRU order ring must not extend kernel lifetime (PR 1 contract)."""
    api.cache_clear()
    n = 128
    args = {"a": jnp.ones(n), "b": jnp.ones(n),
            "c": jnp.zeros(n, jnp.float32)}
    k = make_vecadd(n)
    launch(k, grid=1, block=n, args=args)
    assert api.cache_size() == 1
    del k
    gc.collect()
    assert api.cache_size() == 0


# --- memcpy nodes: d2d capture + async copy ordering (ISSUE 5) ---------------
def test_captured_d2d_replays_identically_to_eager():
    """A graph holding [h2d, d2d, kernel] nodes replays bit-identically
    to the same eager sequence."""
    from repro.core import cuda_memcpy_async
    n, block = 256, 128
    k = make_scale(n, "b", "c", 2.0)
    x = np.arange(n, dtype=np.float32)
    init = {"a": jnp.zeros(n, jnp.float32), "b": jnp.zeros(n, jnp.float32),
            "c": jnp.zeros(n, jnp.float32)}

    def pipeline(s):
        cuda_memcpy_async("a", x, stream=s)        # h2d node
        cuda_memcpy_async("b", "a", stream=s)      # d2d node
        k[2, block, None, s]()                     # kernel node

    eager = Stream(dict(init))
    pipeline(eager)
    captured = Stream(dict(init))
    g = captured.begin_capture()
    pipeline(captured)
    captured.end_capture()
    assert [nd.kind for nd in g.nodes] == ["h2d", "d2d", "kernel"]
    # the d2d node orders after the h2d writer of its source (RAW)
    assert g.nodes[0].idx in g.nodes[1].deps
    g.instantiate(captured.buffers).launch(captured)
    for name in ("a", "b", "c"):
        np.testing.assert_array_equal(captured.memcpy_d2h(name),
                                      eager.memcpy_d2h(name))
    np.testing.assert_allclose(captured.memcpy_d2h("c"), 2.0 * x)


def test_captured_update_node_replays_identically():
    """Stream.device_update captures as an update node inside the fused
    dispatch."""
    n, block = 256, 128
    k = make_scale(n, "a", "b", 3.0)
    init = {"a": jnp.ones(n, jnp.float32), "b": jnp.zeros(n, jnp.float32)}
    bump = lambda h: {"a": h["a"] + 1.0}

    eager = Stream(dict(init))
    eager.device_update(bump)
    k[2, block, None, eager]()
    captured = Stream(dict(init))
    g = captured.begin_capture()
    captured.device_update(bump)
    k[2, block, None, captured]()
    captured.end_capture()
    assert [nd.kind for nd in g.nodes] == ["update", "kernel"]
    assert g.nodes[0].idx in g.nodes[1].deps     # RAW on "a"
    g.instantiate(captured.buffers).launch(captured)
    np.testing.assert_array_equal(captured.memcpy_d2h("b"),
                                  eager.memcpy_d2h("b"))
    np.testing.assert_allclose(captured.memcpy_d2h("b"), 6.0)


def test_memcpy_async_observes_event_wait():
    """cudaMemcpyAsync on a stream that waited on an event orders after
    the fenced producer (cudaStreamWaitEvent -> copy)."""
    from repro.core import cuda_memcpy_async
    n, block = 256, 128
    producer = make_scale(n, "a", "x", 2.0)
    rt = Runtime({"a": jnp.ones(n, jnp.float32),
                  "x": jnp.zeros(n, jnp.float32),
                  "y": jnp.zeros(n, jnp.float32)})
    s0, s1 = rt.stream("compute"), rt.stream("copy")
    producer[2, block, None, s0]()
    ev = rt.event("produced")
    ev.record(s0)
    s1.wait_event(ev)
    cuda_memcpy_async("y", "x", stream=s1)       # must see s0's write
    np.testing.assert_allclose(s1.memcpy_d2h("y"), 2.0)


def test_memcpy_async_cross_stream_hazard_barrier():
    """A named d2d whose source has an in-flight foreign writer inserts
    the implicit barrier (Listing 4, stream-to-stream) - no event needed."""
    from repro.core import cuda_memcpy_async
    n, block = 256, 128
    producer = make_scale(n, "a", "x", 5.0)
    rt = Runtime({"a": jnp.ones(n, jnp.float32),
                  "x": jnp.zeros(n, jnp.float32),
                  "y": jnp.zeros(n, jnp.float32)})
    s0, s1 = rt.stream("s0"), rt.stream("s1")
    producer[2, block, None, s0]()
    assert "x" in s0._pending
    before = s1.stats.barriers_inserted
    cuda_memcpy_async("y", "x", stream=s1)
    assert s1.stats.barriers_inserted == before + 1
    np.testing.assert_allclose(s1.memcpy_d2h("y"), 5.0)


def test_raw_handle_copy_rejected_during_capture():
    from repro.core import GraphError, cuda_malloc, cuda_memcpy_async
    a = cuda_malloc((8,), jnp.float32)
    s = Stream({"x": jnp.zeros(8, jnp.float32)})
    s.begin_capture()
    with pytest.raises(GraphError, match="named heap buffer"):
        cuda_memcpy_async(a, np.ones(8, np.float32), stream=s)
    s.end_capture()


def test_captured_d2d_unknown_source_raises():
    from repro.core import GraphError
    s = Stream({"x": jnp.zeros(8, jnp.float32)})
    s.begin_capture()
    with pytest.raises(GraphError, match="d2d source"):
        s.memcpy_d2d("x", "ghost")
    s.end_capture()


def test_const_heap_buffer_replays_through_graph():
    """ConstArray heap entries unwrap at replay time (bfs's edges case)."""
    from repro.core import cuda_memcpy_to_symbol
    n, block = 256, 128
    k = make_scale(n, "a", "b", 2.0)
    s = Stream({"a": cuda_memcpy_to_symbol(np.ones(n, np.float32)),
                "b": jnp.zeros(n, jnp.float32)})
    g = s.begin_capture()
    k[2, block, None, s]()
    s.end_capture()
    g.instantiate(s.buffers).launch(s)
    np.testing.assert_allclose(s.memcpy_d2h("b"), 2.0)
