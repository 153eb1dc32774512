"""Property-based tests (hypothesis) on system invariants."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")  # not in the baked image
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import atomics, launch, warp
from repro.core import grain as grain_mod
from repro.core.cuda_suite import OOB, make_histogram, make_vecadd
from repro.core.kernel import KernelDef
from repro.core.memory import (
    DeviceBuffer,
    cuda_free,
    cuda_malloc,
    cuda_memcpy_async,
    cuda_memcpy_d2h,
    cuda_memcpy_h2d,
)
from repro.distributed import compression
from repro.models.common import cross_entropy
from repro.models.padding import gqa_pad_plan

SET = settings(max_examples=25, deadline=None)


# --- grain invariance: results never depend on the fetch schedule ----------
@SET
@given(n=st.integers(32, 512), block=st.sampled_from([32, 64, 128]),
       grain=st.integers(1, 20), seed=st.integers(0, 100))
def test_vecadd_grain_invariant(n, block, grain, seed):
    rng = np.random.default_rng(seed)
    k = make_vecadd(n)
    grid = -(-n // block)
    args = {"a": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "b": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "c": jnp.zeros(n, jnp.float32)}
    out = launch(k, grid=grid, block=block, args=args, backend="vector",
                 grain=grain)
    np.testing.assert_allclose(np.asarray(out["c"]),
                               np.asarray(args["a"]) + np.asarray(args["b"]),
                               rtol=1e-6)


@SET
@given(nbins=st.integers(2, 64), grain=st.integers(1, 8),
       seed=st.integers(0, 50))
def test_histogram_conserves_mass(nbins, grain, seed):
    rng = np.random.default_rng(seed)
    n, block, grid = 1024, 64, 4
    k = make_histogram(n, nbins, grid * block)
    x = rng.integers(0, nbins, n).astype(np.int32)
    out = launch(k, grid=grid, block=block,
                 args={"x": jnp.asarray(x),
                       "hist": jnp.zeros(nbins, jnp.int32)},
                 backend="vector", grain=grain)
    hist = np.asarray(out["hist"])
    assert hist.sum() == n
    np.testing.assert_array_equal(hist, np.bincount(x, minlength=nbins))


# --- warp ops ---------------------------------------------------------------
def _warps_ref(v):
    return np.asarray(v).reshape(-1, 32)


def _shfl_shift_ref(v, delta, direction):
    """NumPy oracle for shfl_up/down incl. CUDA's keep-own-value semantics
    when the source lane falls outside the warp."""
    w = _warps_ref(v)
    lane = np.arange(32)
    src = lane + direction * delta
    ok = (src >= 0) & (src < 32)
    gathered = w[:, np.clip(src, 0, 31)]
    return np.where(ok[None, :], gathered, w).reshape(-1)


@SET
@given(nwarps=st.integers(1, 4), delta=st.integers(0, 40),
       seed=st.integers(0, 50))
def test_shfl_up_matches_numpy(nwarps, delta, seed):
    v = np.random.default_rng(seed).standard_normal(
        nwarps * 32).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(warp.shfl_up(jnp.asarray(v),
                                                          delta)),
                                  _shfl_shift_ref(v, delta, -1))


@SET
@given(nwarps=st.integers(1, 4), delta=st.integers(0, 40),
       seed=st.integers(0, 50))
def test_shfl_down_matches_numpy(nwarps, delta, seed):
    v = np.random.default_rng(seed).standard_normal(
        nwarps * 32).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(warp.shfl_down(jnp.asarray(v),
                                                            delta)),
                                  _shfl_shift_ref(v, delta, +1))


@SET
@given(nwarps=st.integers(1, 3), src=st.integers(-64, 64),
       seed=st.integers(0, 50))
def test_shfl_scalar_src_matches_numpy(nwarps, src, seed):
    """Scalar-source shfl broadcasts lane ``src % 32`` warp-wide."""
    v = np.random.default_rng(seed).standard_normal(
        nwarps * 32).astype(np.float32)
    out = np.asarray(warp.shfl(jnp.asarray(v), src % 32))
    want = np.repeat(_warps_ref(v)[:, src % 32], 32)
    np.testing.assert_array_equal(out, want)


@SET
@given(nwarps=st.integers(1, 3), seed=st.integers(0, 50))
def test_shfl_per_thread_src_matches_numpy(nwarps, seed):
    r = np.random.default_rng(seed)
    v = r.standard_normal(nwarps * 32).astype(np.float32)
    src = r.integers(0, 64, nwarps * 32)          # lane ids wrap mod 32
    out = np.asarray(warp.shfl(jnp.asarray(v), jnp.asarray(src)))
    w, s = _warps_ref(v), _warps_ref(src) % 32
    want = np.take_along_axis(w, s, axis=1).reshape(-1)
    np.testing.assert_array_equal(out, want)


@SET
@given(nwarps=st.integers(1, 4), thresh=st.floats(-2.0, 2.0),
       seed=st.integers(0, 50))
def test_vote_matches_numpy(nwarps, thresh, seed):
    v = np.random.default_rng(seed).standard_normal(nwarps * 32)
    pred = jnp.asarray(v < thresh)
    w = _warps_ref(v) < thresh
    np.testing.assert_array_equal(
        np.asarray(warp.vote_all(pred)), np.repeat(w.all(1), 32))
    np.testing.assert_array_equal(
        np.asarray(warp.vote_any(pred)), np.repeat(w.any(1), 32))


@SET
@given(nwarps=st.integers(1, 4), thresh=st.floats(-2.0, 2.0),
       seed=st.integers(0, 50))
def test_ballot_matches_numpy(nwarps, thresh, seed):
    v = np.random.default_rng(seed).standard_normal(nwarps * 32)
    pred = _warps_ref(v) < thresh
    out = np.asarray(warp.ballot(jnp.asarray(v < thresh)))
    want = np.repeat((pred.astype(np.uint64)
                      << np.arange(32, dtype=np.uint64)).sum(1)
                     .astype(np.uint32), 32)
    np.testing.assert_array_equal(out, want)


@SET
@given(block=st.sampled_from([32, 64, 128]), thresh=st.floats(-2.0, 2.0),
       seed=st.integers(0, 50))
def test_syncthreads_count_matches_numpy(block, thresh, seed):
    v = np.random.default_rng(seed).standard_normal(block)
    out = np.asarray(warp.syncthreads_count(jnp.asarray(v < thresh), block))
    np.testing.assert_array_equal(out, np.full(block, int((v < thresh).sum()),
                                               np.int32))


@SET
@given(mask=st.sampled_from([1, 2, 4, 8, 16]), seed=st.integers(0, 50))
def test_shfl_xor_involution(mask, seed):
    v = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal(64).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(warp.shfl_xor(warp.shfl_xor(v, mask), mask)),
        np.asarray(v))


@SET
@given(nwarps=st.integers(1, 3), mask=st.integers(0, 63),
       seed=st.integers(0, 50))
def test_shfl_xor_scalar_mask_matches_numpy(nwarps, mask, seed):
    """shfl_xor vs a NumPy oracle over ALL masks 0..63: masks whose xor
    leaves the 32-lane segment must return the caller's own value (CUDA
    semantics), not a clamped lane-31 read."""
    v = np.random.default_rng(seed).standard_normal(
        nwarps * 32).astype(np.float32)
    out = np.asarray(warp.shfl_xor(jnp.asarray(v), mask))
    w = _warps_ref(v)
    src = np.arange(32) ^ mask
    ok = src < 32
    want = np.where(ok[None, :], w[:, np.clip(src, 0, 31)], w).reshape(-1)
    np.testing.assert_array_equal(out, want)


@SET
@given(nwarps=st.integers(1, 3), seed=st.integers(0, 50))
def test_shfl_xor_array_mask_matches_numpy(nwarps, seed):
    """Per-thread mask arrays (the form shfl accepts for src lanes)."""
    r = np.random.default_rng(seed)
    v = r.standard_normal(nwarps * 32).astype(np.float32)
    mask = r.integers(0, 64, nwarps * 32)
    out = np.asarray(warp.shfl_xor(jnp.asarray(v), jnp.asarray(mask)))
    w, m = _warps_ref(v), _warps_ref(mask)
    src = np.arange(32)[None, :] ^ m
    ok = src < 32
    want = np.where(ok, np.take_along_axis(w, np.clip(src, 0, 31), axis=1),
                    w).reshape(-1)
    np.testing.assert_array_equal(out, want)


@SET
@given(seed=st.integers(0, 50))
def test_warp_reduce_matches_numpy(seed):
    v = np.random.default_rng(seed).standard_normal(96).astype(np.float32)
    out = np.asarray(warp.reduce(jnp.asarray(v), "add"))
    want = np.repeat(v.reshape(3, 32).sum(1), 32)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


# --- atomics: OOB/negative-index sweeps vs a NumPy oracle --------------------
_RMW_REF = {"add": lambda a, b: a + b, "max": max, "min": min}


@SET
@given(n=st.integers(2, 16), nthr=st.integers(1, 32),
       op=st.sampled_from(["add", "max", "min"]), seed=st.integers(0, 200))
def test_atomic_rmw_index_sweep_matches_numpy(n, nthr, op, seed):
    """Sweep negative, in-range, past-the-end and duplicate indices: every
    out-of-range index must store nothing (the pre-fix drop-mode scatter
    wrapped negatives onto the tail), duplicates must all apply."""
    r = np.random.default_rng(seed)
    arr = r.integers(-50, 50, n).astype(np.int32)
    idx = r.integers(-n - 2, n + 3, nthr)
    val = r.integers(-50, 50, nthr).astype(np.int32)
    fn = getattr(atomics, f"atomic_{op}")
    out = np.asarray(fn(jnp.asarray(arr), jnp.asarray(idx), jnp.asarray(val)))
    want = arr.copy()
    for i, v in zip(idx, val):
        if 0 <= i < n:
            want[i] = _RMW_REF[op](want[i], v)
    np.testing.assert_array_equal(out, want)


@SET
@given(n=st.integers(2, 12), nthr=st.integers(1, 24),
       seed=st.integers(0, 200))
def test_atomic_cas_first_index_sweep_matches_numpy(n, nthr, seed):
    """cas_first under the same sweep: only the first occurrence of an
    in-range index whose compare matches the pre-image stores; negative
    indices must never claim (or corrupt) the tail."""
    r = np.random.default_rng(seed)
    arr = r.integers(0, 3, n).astype(np.int32)
    idx = r.integers(-n - 2, n + 3, nthr)
    cmp = r.integers(0, 3, nthr).astype(np.int32)
    val = r.integers(10, 20, nthr).astype(np.int32)
    out = np.asarray(atomics.atomic_cas_first(
        jnp.asarray(arr), jnp.asarray(idx), jnp.asarray(cmp),
        jnp.asarray(val)))
    want = arr.copy()
    seen = set()
    for t in range(nthr):
        i = int(idx[t])
        first = i not in seen
        seen.add(i)
        if first and 0 <= i < n and arr[i] == cmp[t]:
            want[i] = val[t]
    np.testing.assert_array_equal(out, want)


# --- device-memory runtime: copy round-trips + donation (ISSUE 5) ------------
_DTYPES = {"f32": np.float32, "f64": np.float64, "i32": np.int32}


def _host_values(seed, shape, tag, layout):
    """A host array in the requested memory layout (incl. non-contiguous)."""
    r = np.random.default_rng(seed)
    if tag == "i32":
        base = r.integers(-1000, 1000, size=shape).astype(np.int32)
    else:
        base = r.standard_normal(shape).astype(_DTYPES[tag])
    if layout == "contiguous":
        return base
    if layout == "strided":                    # every-other-element view
        wide = np.repeat(base, 2, axis=-1)
        view = wide[..., ::2]
        assert not view.flags["C_CONTIGUOUS"]
        return view
    view = base.T                              # transposed view
    if view.ndim > 1:
        assert not view.flags["C_CONTIGUOUS"]
    return view


@SET
@given(seed=st.integers(0, 1000),
       shape=st.sampled_from([(7,), (16,), (3, 5), (4, 4), (2, 3, 4)]),
       tag=st.sampled_from(["f32", "f64", "i32"]),
       layout=st.sampled_from(["contiguous", "strided", "transposed"]))
def test_h2d_d2h_roundtrip_bit_identical(seed, shape, tag, layout):
    """h2d -> d2h returns the exact bits for every dtype and layout,
    including non-contiguous host views (f64 under scoped x64, as the
    conformance matrix runs it)."""
    host = _host_values(seed, shape, tag, layout)
    ctx = (jax.enable_x64(True) if tag == "f64"
           else contextlib.nullcontext())
    with ctx:
        buf = cuda_memcpy_h2d(host)
        back = cuda_memcpy_d2h(buf)
    assert back.dtype == host.dtype
    assert np.ascontiguousarray(host).tobytes() == back.tobytes()
    cuda_free(buf)


@SET
@given(seed=st.integers(0, 1000),
       shape=st.sampled_from([(8,), (3, 5), (2, 3, 4)]),
       tag=st.sampled_from(["f32", "f64", "i32"]),
       layout=st.sampled_from(["contiguous", "strided"]))
def test_d2d_roundtrip_bit_identical(seed, shape, tag, layout):
    """h2d -> d2d -> d2h preserves bits; the source stays intact."""
    host = _host_values(seed, shape, tag, layout)
    ctx = (jax.enable_x64(True) if tag == "f64"
           else contextlib.nullcontext())
    with ctx:
        src = cuda_memcpy_h2d(host)
        dst = cuda_malloc(src.shape, src.dtype)
        assert cuda_memcpy_async(dst, src) is dst
        want = np.ascontiguousarray(host).tobytes()
        assert cuda_memcpy_d2h(dst).tobytes() == want
        assert cuda_memcpy_d2h(src).tobytes() == want


def _rw_kernel(n, declared: bool):
    """x = x * 3 + 1: reads and writes the same buffer."""
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        val = st.glob["x"][jnp.minimum(gid, n - 1)] * 3 + 1
        idx = jnp.where(gid < n, gid, OOB)
        return st.set_glob(x=st.glob["x"].at[idx].set(val, mode="drop"))

    return KernelDef("rw_affine", (stage,), writes=("x",), reads=("x",),
                     donates=("x",) if declared else ())


@SET
@given(seed=st.integers(0, 500), n=st.sampled_from([32, 64, 96]),
       declared=st.booleans(), backend=st.sampled_from(["loop", "vector"]))
def test_donation_never_aliases_read_buffer_unless_declared(
        seed, n, declared, backend):
    """The donation property: a kernel that reads its written buffer may
    alias (consume) the handle's input storage ONLY when donates declares
    it; otherwise the input survives the launch bit-for-bit."""
    host = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    k = _rw_kernel(n, declared)
    h = cuda_memcpy_h2d(host)
    out = launch(k, grid=1, block=n, args={"x": h}, backend=backend)
    want = host * 3 + 1
    # atol: near x = -1/3 a fused and an unfused multiply-add differ by an
    # ulp of 1.0, far beyond rtol; aliasing is what this property checks
    tol = dict(rtol=1e-6, atol=1e-6)
    if declared:
        # aliased: same handle, now holding the output
        assert out["x"] is h and h.live
        np.testing.assert_allclose(np.asarray(h), want, **tol)
    else:
        # no alias: plain-array result, input handle untouched
        assert not isinstance(out["x"], DeviceBuffer)
        np.testing.assert_allclose(np.asarray(out["x"]), want, **tol)
        assert cuda_memcpy_d2h(h).tobytes() == host.tobytes()


# --- scheduler (Fig. 6 semantics) --------------------------------------------
@SET
@given(grid=st.integers(1, 200), pool=st.integers(1, 16),
       grain=st.integers(1, 64))
def test_schedule_covers_all_blocks(grid, pool, grain):
    tr = grain_mod.schedule_trace(grid, pool, grain)
    assert sum(tr.per_worker_blocks) == grid
    assert tr.n_fetches == -(-grid // grain)
    assert 0 < tr.utilization <= 1.0 + 1e-9


# --- GQA padding plan invariants ---------------------------------------------
@SET
@given(hkv=st.integers(1, 48), r=st.integers(1, 8),
       align=st.sampled_from([2, 4, 8, 16]))
def test_pad_plan_invariants(hkv, r, align):
    hq = hkv * r
    plan = gqa_pad_plan(hq, hkv, align)
    assert plan.hq_p % align == 0 and plan.hkv_p % align == 0
    assert plan.hq_p == plan.hkv_p * plan.group_p
    # every original q head appears exactly once
    real_q = [m for m in plan.qmap if m >= 0]
    assert sorted(real_q) == list(range(hq))
    # q -> kv grouping preserved: padded q j maps to padded kv j//g whose
    # original kv equals the original q's kv owner
    for j, src in enumerate(plan.qmap):
        if src < 0:
            continue
        kv_owner = plan.kvmap[j // plan.group_p]
        assert kv_owner == src // r


# --- compression ---------------------------------------------------------------
@SET
@given(seed=st.integers(0, 100),
       scale=st.floats(1e-4, 1e4),
       bits=st.sampled_from([4, 8]))
def test_quantize_bounded(seed, scale, bits):
    g = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal(128).astype(np.float32) * scale)
    q, s = compression.quantize(g, bits)
    err = np.abs(np.asarray(compression.dequantize(q, s) - g))
    assert err.max() <= float(s) * 0.5 * 1.001 + 1e-12


# --- loss ---------------------------------------------------------------------
@SET
@given(seed=st.integers(0, 50), vpad=st.integers(0, 7))
def test_cross_entropy_vs_naive(seed, vpad):
    rng = np.random.default_rng(seed)
    V = 11
    logits = rng.standard_normal((3, 5, V + vpad)).astype(np.float32)
    logits[..., V:] = rng.standard_normal((3, 5, vpad)) * 10  # garbage pad
    targets = rng.integers(0, V, (3, 5)).astype(np.int32)
    ours = float(cross_entropy(jnp.asarray(logits), jnp.asarray(targets),
                               real_vocab=V))
    p = logits[..., :V]
    p = p - p.max(-1, keepdims=True)
    logp = p - np.log(np.exp(p).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp, targets[..., None], -1).mean()
    np.testing.assert_allclose(ours, want, rtol=1e-4, atol=1e-5)
