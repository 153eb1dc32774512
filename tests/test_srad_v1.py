"""Rodinia srad_v1 (``entry_srad_v1``): the six kernels against the
benchmark's own NumPy reference, the chain's host reads of the reduced
sums, and the counters that read them.

64 x 40 is 2,560 pixels, five full blocks: the first reduce pass takes the
all-full path and the second a partial block of five sums.  37 x 29 is
1,073 pixels: a partial last block of 49 in the first pass, of three in
the second.
"""
import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.cuda_suite import (entry_srad_v1, make_srad_reduce,
                                   run_entry, srad_reduce_passes)
from repro.core.kernel import (ChainStats, ChainStep, KernelDef, LaunchChain,
                               chain_totals)

_CONFIG = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                       "configs", "srad_v1_502x458")
SHAPES = [(64, 40), (37, 29)]
ITERS = 4


def _bench_config():
    spec = importlib.util.spec_from_file_location("srad_v1_config",
                                                  _CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(_CONFIG + ".json") as f:
        return mod, json.load(f)


BENCH, PARAMS = _bench_config()


def _params(rows, cols):
    return {**PARAMS, "rows": rows, "cols": cols, "iterations": ITERS}


@pytest.mark.parametrize("backend", ["vector", "loop"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_matches_the_benchmark_reference(shape, backend):
    p = _params(*shape)
    args = BENCH.inputs(p, np.random.default_rng(2**31 + 77))
    out, _ = run_entry(BENCH.entry(p), backend, args=args,
                       with_reference=False)
    ref = BENCH.reference(p, args)
    err = np.max(np.abs(np.asarray(out["I"], np.float64) - ref)) \
        / np.max(np.abs(ref))
    assert err <= PARAMS["check"]["max_rel_err"], err


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_host_and_device_replays_agree(shape):
    """The host computes q0sqr from the sums it reads back; the device
    replay computes it on the device: the same bits."""
    e = entry_srad_v1(*shape, iters=ITERS)
    args = e.make_args(np.random.default_rng(5))
    host, _ = run_entry(e, "vector", args=args, with_reference=False)
    for mode in ("device", "graph"):
        dev, _ = run_entry(e, "vector", args=args, chain_mode=mode,
                           with_reference=False)
        for k in host:
            assert (np.asarray(host[k]).tobytes()
                    == np.asarray(dev[k]).tobytes()), (mode, k)


def test_reduce_passes_follow_rodinias_host_loop():
    assert srad_reduce_passes(502 * 458) == [(229916, 1, 450),
                                             (450, 512, 1)]
    assert srad_reduce_passes(2560) == [(2560, 1, 5), (5, 512, 1)]
    assert srad_reduce_passes(300) == [(300, 1, 1)]
    assert srad_reduce_passes(512 * 512 + 1) == [
        (262145, 1, 513), (513, 512, 2), (2, 262144, 1)]


@pytest.mark.parametrize("no", [1, 2, 5, 300, 512, 1000, 1536])
def test_reduce_sums_every_value(no):
    """Each pass leaves every block's sum in its first slot: partial and
    full last blocks, and a block of one value."""
    vals = np.random.default_rng(no).uniform(1, 3, 2048).astype(np.float32)
    bufs = {"sums": jnp.asarray(vals), "sums2": jnp.asarray(vals * 2)}
    blocks = -(-no // 512)
    out = api.launch(make_srad_reduce(no, 1, blocks), grid=blocks,
                     block=512, args=bufs, backend="vector")
    for b in range(blocks):
        part = vals[b * 512:min(no, (b + 1) * 512)].astype(np.float64)
        np.testing.assert_allclose(float(out["sums"][b * 512]), part.sum(),
                                   rtol=1e-6)
        np.testing.assert_allclose(float(out["sums2"][b * 512]),
                                   2 * part.sum(), rtol=1e-6)


def test_host_reads_are_counted_per_iteration():
    e = entry_srad_v1(iters=ITERS)
    before = chain_totals()
    stats = {}
    for mode in ("host", "device", "graph"):
        stats[mode] = ChainStats()
        run_entry(e, "vector", chain_mode=mode, chain_stats=stats[mode],
                  with_reference=False)
    assert stats["host"].host_reads == ITERS
    assert stats["device"].host_reads == stats["graph"].host_reads == 0
    launches = 2 + ITERS * len(e.chain.steps)
    for s in stats.values():
        assert (s.runs, s.iterations, s.launches) == (1, ITERS, launches)
    after = chain_totals()
    assert after.runs - before.runs == 3
    assert after.host_reads - before.host_reads == ITERS
    assert after.iterations - before.iterations == 3 * ITERS


def _scale_chain():
    """x *= 2, the host reads x[0:2], the next step's prepare adds their
    sum to y; its update does the same on the device."""
    def double(ctx, st):
        return st.set_glob(x=st.glob["x"].at[ctx.tid].set(
            st.glob["x"][ctx.tid] * 2))

    def add(ctx, st):
        return st.set_glob(y=st.glob["y"].at[ctx.tid].set(
            st.glob["y"][ctx.tid] + st.glob["s"][0]))

    k1 = KernelDef("double", (double,), writes=("x",), reads=("x",))
    k2 = KernelDef("add", (add,), writes=("y",), reads=("y", "s"))
    seen = []

    def prepare(it, b):
        seen.append(b["x"])
        return {"s": jnp.asarray(b["x"].sum(keepdims=True))}

    chain = LaunchChain(steps=(
        ChainStep(k1, 1, 4, read={"x": 2}),
        ChainStep(k2, 1, 4, prepare=prepare,
                  update=lambda b: {"s": b["x"][:2].sum(keepdims=True)})),
        repeat=3)
    return chain, seen


def test_next_prepare_sees_the_host_copy():
    chain, seen = _scale_chain()
    bufs = {"x": jnp.arange(1.0, 5.0), "y": jnp.zeros(4),
            "s": jnp.zeros(1)}

    def step(s, b):
        return api.launch(s.kernel, grid=s.grid, block=s.block, args=b,
                          backend="loop")

    host = chain.run(step, dict(bufs))
    assert [v.tolist() for v in seen] == [[2, 4], [4, 8], [8, 16]]
    assert all(isinstance(v, np.ndarray) for v in seen)
    assert np.asarray(host["y"]).tolist() == [6 + 12 + 24] * 4
    dev = chain.run_device(step, dict(bufs))
    assert len(seen) == 3          # no host copies in the device replay
    assert np.asarray(dev["y"]).tolist() == np.asarray(host["y"]).tolist()
