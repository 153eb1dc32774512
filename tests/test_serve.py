"""Kernel-serving tier: batching bit-identity, robustness, stats accounting.

The acceptance contracts of the serving layer (docs/serving.md):

* a stacked-batch dispatch is bit-identical to the N independent launches
  it replaces, on the loop AND vector backends;
* backpressure (bounded queue) and per-request timeouts fail loudly with
  typed errors instead of stalling the worker;
* a faulting tenant (const-space violation, sanitizer finding, freed
  handle) takes down only its own request - co-batched and subsequent
  requests keep serving;
* the stats counters add up: submitted = completed + failed + timed_out
  (+ still pending), occupancy histogram sums to dispatches.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, memory
from repro.core.cuda_suite import build_suite
from repro.core.kernel import KernelDef
from repro.serve import (
    KernelService,
    ServiceError,
    ServiceOverloaded,
    ServiceTimeout,
)

N = 256
BLOCK = 64
GRID = N // BLOCK


def make_vecadd():
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        return st.set_glob(c=st.glob["c"].at[gid].set(
            st.glob["a"][gid] + st.glob["b"][gid]))

    return KernelDef("serve_vecadd", (stage,), writes=("c",),
                     reads=("a", "b", "c"))


def vecadd_args(rng):
    return {"a": jnp.asarray(rng.standard_normal(N, dtype=np.float32)),
            "b": jnp.asarray(rng.standard_normal(N, dtype=np.float32)),
            "c": jnp.zeros(N, jnp.float32)}


@pytest.fixture
def kernel():
    return make_vecadd()


def _bits(x):
    return np.asarray(x).tobytes()


# -------------------------------------------------------------------------
# launch_batch: the stacked-dispatch primitive
# -------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["loop", "vector"])
@pytest.mark.parametrize("name", ["vecadd", "softmax_row", "reduce_shared"])
def test_launch_batch_bit_identical_to_independent(name, backend):
    entry = next(e for e in build_suite(scale=1) if e.name == name)
    rng = np.random.default_rng(0)
    args_list = [{k: jnp.asarray(v) for k, v in entry.make_args(rng).items()}
                 for _ in range(4)]
    solo = [api.launch(entry.kernel, grid=entry.grid, block=entry.block,
                       args=a, dyn_shared=entry.dyn_shared, backend=backend)
            for a in args_list]
    batched = api.launch_batch(entry.kernel, grid=entry.grid,
                               block=entry.block, args_list=args_list,
                               dyn_shared=entry.dyn_shared, backend=backend)
    for s, b in zip(solo, batched):
        for k in entry.kernel.writes:
            assert np.asarray(s[k]).dtype == np.asarray(b[k]).dtype
            assert _bits(s[k]) == _bits(b[k]), (name, backend, k)


def test_launch_batch_shares_cache_stats(kernel):
    api.cache_clear()
    rng = np.random.default_rng(1)
    args_list = [vecadd_args(rng) for _ in range(3)]
    api.launch_batch(kernel, grid=GRID, block=BLOCK, args_list=args_list,
                     backend="loop")
    s0 = api.cache_stats()
    api.launch_batch(kernel, grid=GRID, block=BLOCK, args_list=args_list,
                     backend="loop")
    s1 = api.cache_stats()
    assert (s1.hits, s1.misses) == (s0.hits + 1, s0.misses)


def test_launch_batch_rejects_incompatible_shapes(kernel):
    rng = np.random.default_rng(2)
    good = vecadd_args(rng)
    bad = {"a": jnp.zeros(N // 2, jnp.float32),
           "b": jnp.zeros(N // 2, jnp.float32),
           "c": jnp.zeros(N // 2, jnp.float32)}
    with pytest.raises(ValueError, match="request 1"):
        api.launch_batch(kernel, grid=GRID, block=BLOCK,
                         args_list=[good, bad], backend="loop")


def test_launch_batch_rejects_empty_and_multi_device(kernel):
    with pytest.raises(ValueError, match="non-empty"):
        api.launch_batch(kernel, grid=GRID, block=BLOCK, args_list=[])
    rng = np.random.default_rng(3)
    from repro.core.kernel import UnsupportedKernel
    with pytest.raises(UnsupportedKernel, match="single-device"):
        api.launch_batch(kernel, grid=GRID, block=BLOCK,
                         args_list=[vecadd_args(rng), vecadd_args(rng)],
                         backend="shard")


# -------------------------------------------------------------------------
# service-level batching
# -------------------------------------------------------------------------
def test_service_batches_compatible_requests(kernel):
    rng = np.random.default_rng(4)
    argses = [vecadd_args(rng) for _ in range(4)]
    svc = KernelService(backend="loop", autostart=False, max_batch=8)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        tickets = [svc.submit("vecadd", a) for a in argses]
        svc.start()
        results = [t.result(timeout=120) for t in tickets]
        st = svc.stats()
        # all four queued requests stacked into ONE dispatch
        assert st.batch_occupancy.get(4) == 1, st.batch_occupancy
        assert st.batched_requests == 4
        assert all(t.batch_size == 4 for t in tickets)
        for a, r in zip(argses, results):
            want = api.launch(kernel, grid=GRID, block=BLOCK, args=a,
                              backend="loop")
            assert _bits(r["c"]) == _bits(want["c"])
    finally:
        svc.close()


def test_service_isolates_incompatible_specializations(kernel):
    """Different arg shapes -> different batch keys -> separate dispatches."""
    other = KernelDef("serve_scale", (lambda ctx, st: st.set_glob(
        c=st.glob["c"].at[ctx.bid * ctx.block_dim + ctx.tid].set(
            st.glob["a"][ctx.bid * ctx.block_dim + ctx.tid] * 2.0)),),
        writes=("c",), reads=("a", "c"))
    rng = np.random.default_rng(5)
    svc = KernelService(backend="loop", autostart=False)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        svc.register("scale", other, grid=GRID, block=BLOCK)
        ta = [svc.submit("vecadd", vecadd_args(rng)) for _ in range(2)]
        a = vecadd_args(rng)
        tb = svc.submit("scale", {"a": a["a"], "c": a["c"]})
        svc.start()
        for t in [*ta, tb]:
            t.result(timeout=120)
        st = svc.stats()
        assert st.batch_occupancy.get(2) == 1      # the vecadd pair
        assert st.batch_occupancy.get(1) == 1      # the lone scale request
        assert _bits(tb.result()["c"]) == _bits(np.asarray(a["a"]) * 2.0)
    finally:
        svc.close()


# -------------------------------------------------------------------------
# robustness: backpressure, timeout, fault isolation
# -------------------------------------------------------------------------
def test_backpressure_raises_overloaded(kernel):
    rng = np.random.default_rng(6)
    svc = KernelService(backend="loop", autostart=False, max_queue=2)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        svc.submit("vecadd", vecadd_args(rng))
        svc.submit("vecadd", vecadd_args(rng))
        with pytest.raises(ServiceOverloaded):
            svc.submit("vecadd", vecadd_args(rng))
        assert svc.stats().rejected == 1
    finally:
        svc.close()


def test_queue_timeout_fails_request_not_worker(kernel):
    rng = np.random.default_rng(7)
    svc = KernelService(backend="loop", autostart=False)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        stale = svc.submit("vecadd", vecadd_args(rng), timeout=0.01)
        fresh = svc.submit("vecadd", vecadd_args(rng))
        time.sleep(0.05)
        svc.start()
        with pytest.raises(ServiceTimeout):
            stale.result(timeout=120)
        fresh.result(timeout=120)              # worker kept serving
        st = svc.stats()
        assert st.timed_out == 1 and st.completed == 1
    finally:
        svc.close()


def test_client_side_result_timeout(kernel):
    rng = np.random.default_rng(8)
    svc = KernelService(backend="loop", autostart=False)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        t = svc.submit("vecadd", vecadd_args(rng))
        with pytest.raises(ServiceTimeout):   # worker never started
            t.result(timeout=0.01)
    finally:
        svc.close()


def test_tenant_fault_isolated_from_cobatched_and_subsequent(kernel):
    rng = np.random.default_rng(9)
    svc = KernelService(backend="loop", autostart=False, max_batch=8)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        good_args = [vecadd_args(rng) for _ in range(2)]
        bad_args = vecadd_args(rng)
        # const-space violation: ConstArray bound to the write buffer
        bad_args["c"] = memory.ConstArray(jnp.zeros(N, jnp.float32))
        goods = [svc.submit("vecadd", a) for a in good_args]
        bad = svc.submit("vecadd", bad_args)
        svc.start()
        with pytest.raises(memory.UnsupportedSpace):
            bad.result(timeout=120)
        # co-batched requests survived the fallback to singles
        for t, a in zip(goods, good_args):
            want = api.launch(kernel, grid=GRID, block=BLOCK, args=a,
                              backend="loop")
            assert _bits(t.result(timeout=120)["c"]) == _bits(want["c"])
        # ... and the worker keeps serving afterwards
        after = svc.submit("vecadd", vecadd_args(rng))
        after.result(timeout=120)
        st = svc.stats()
        assert st.failed == 1 and st.completed == 3
    finally:
        svc.close()


def test_batch_fallback_to_singles_is_counted(kernel):
    """A stacked dispatch that fails and re-runs as singles is a visible
    fault: counted, with its error text, while its co-batched tenants
    still get correct results."""
    rng = np.random.default_rng(11)
    svc = KernelService(backend="loop", autostart=False, max_batch=8)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        good_args = [vecadd_args(rng) for _ in range(3)]
        bad_args = vecadd_args(rng)
        bad_args["c"] = memory.ConstArray(jnp.zeros(N, jnp.float32))
        goods = [svc.submit("vecadd", a) for a in good_args]
        bad = svc.submit("vecadd", bad_args)
        svc.start()
        with pytest.raises(memory.UnsupportedSpace):
            bad.result(timeout=120)
        for t, a in zip(goods, good_args):
            want = api.launch(kernel, grid=GRID, block=BLOCK, args=a,
                              backend="loop")
            assert _bits(t.result(timeout=120)["c"]) == _bits(want["c"])
            assert t.batch_size == 1            # served by the fallback
        st = svc.stats()
        assert st.batch_fallbacks == 1
        assert st.last_batch_error.startswith("UnsupportedSpace")
        assert st.to_json()["batch_fallbacks"] == 1
        # a clean stacked dispatch afterwards adds no fallback
        clean = [svc.submit("vecadd", vecadd_args(rng)) for _ in range(2)]
        for t in clean:
            t.result(timeout=120)
        assert svc.stats().batch_fallbacks == 1
    finally:
        svc.close()


def test_freed_handle_rejected_at_admission(kernel):
    rng = np.random.default_rng(10)
    svc = KernelService(backend="loop", autostart=False)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        buf = memory.cuda_malloc((N,), jnp.float32)
        memory.cuda_free(buf)
        args = vecadd_args(rng)
        args["a"] = buf
        with pytest.raises(memory.CudaError):
            svc.submit("vecadd", args)
        ok = svc.submit("vecadd", vecadd_args(rng))
        svc.start()
        ok.result(timeout=120)
    finally:
        svc.close()


def test_malformed_requests_rejected(kernel):
    svc = KernelService(backend="loop", autostart=False)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        rng = np.random.default_rng(11)
        args = vecadd_args(rng)
        with pytest.raises(ServiceError, match="unknown endpoint"):
            svc.submit("nope", args)
        with pytest.raises(ServiceError, match="missing buffer"):
            svc.submit("vecadd", {"a": args["a"]})
        extra = dict(args, zzz=jnp.zeros(4))
        with pytest.raises(ServiceError, match="unknown buffer"):
            svc.submit("vecadd", extra)
    finally:
        svc.close()


# -------------------------------------------------------------------------
# stats accounting
# -------------------------------------------------------------------------
def test_stats_counters_add_up(kernel):
    rng = np.random.default_rng(12)
    svc = KernelService(backend="loop", autostart=False, max_queue=4)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        tickets = [svc.submit("vecadd", vecadd_args(rng)) for _ in range(3)]
        bad = vecadd_args(rng)
        bad["c"] = memory.ConstArray(jnp.zeros(N, jnp.float32))
        tickets.append(svc.submit("vecadd", bad))
        with pytest.raises(ServiceOverloaded):
            svc.submit("vecadd", vecadd_args(rng))
        svc.start()
        for t in tickets:
            try:
                t.result(timeout=120)
            except Exception:
                pass
        st = svc.stats()
        assert st.submitted == 4 and st.rejected == 1
        assert st.submitted == st.completed + st.failed + st.timed_out
        assert sum(k * v for k, v in st.batch_occupancy.items()) \
            >= st.completed + st.failed
        assert sum(st.batch_occupancy.values()) == st.dispatches
        assert st.queue_depth == 0 and st.max_queue_depth == 4
        lat = st.kernels["vecadd"]
        assert lat["count"] == st.completed
        assert 0 < lat["p50_ms"] <= lat["p99_ms"]
        assert 0.0 <= st.warm_hit_rate <= 1.0
        assert st.streams["syncs"] >= st.streams["launches"] * 0  # present
    finally:
        svc.close()


def test_stats_json_roundtrips(kernel):
    import json
    rng = np.random.default_rng(13)
    with KernelService(backend="loop") as svc:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        svc.submit("vecadd", vecadd_args(rng)).result(timeout=120)
        doc = svc.stats().to_json()
    parsed = json.loads(json.dumps(doc))
    assert parsed["completed"] == 1
    assert "vecadd" in parsed["kernels"]
    assert parsed["batch_occupancy"] == {"1": 1}


def test_queue_wait_counts_from_submit_to_dispatch(kernel):
    """Requests queued 50 ms before the worker starts wait at least that
    long, and their wait is part of their latency."""
    rng = np.random.default_rng(14)
    svc = KernelService(backend="loop", autostart=False)
    try:
        svc.register("vecadd", kernel, grid=GRID, block=BLOCK)
        tickets = [svc.submit("vecadd", vecadd_args(rng)) for _ in range(3)]
        time.sleep(0.05)
        svc.start()
        for t in tickets:
            t.result(timeout=120)
        lat = svc.stats().kernels["vecadd"]
        assert 50.0 <= lat["queue_p50_ms"] <= lat["p50_ms"]
        assert lat["queue_p50_ms"] <= lat["queue_p99_ms"] <= lat["p99_ms"]
        assert all(t.submitted_at < t.dispatched_at < t.finished_at
                   for t in tickets)
    finally:
        svc.close()
