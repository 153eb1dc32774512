"""Device-resident LaunchChain replay (ISSUE 5 tentpole).

The host-hop chain driver round-trips every iteration through host-side
prepare hooks and host-read stop flags; the device-resident modes keep
inter-launch state on device (``ChainStep.update``), poll stop flags every
k iterations (``LaunchChain.device_stop``/``check_every``), and optionally
capture the whole iteration body into a graph replayed as fused jitted
dispatches.  These tests pin the three-way bit-identity contract and the
host-sync accounting the membench benchmark measures.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Stream
from repro.core.cuda_suite import build_suite, run_entry
from repro.core.kernel import (
    ChainStats,
    ChainStep,
    LaunchChain,
    UnsupportedKernel,
)

SUITE = {e.name: e for e in build_suite(scale=1)}
CHAIN_NAMES = ("bfs_frontier", "pathfinder", "needle_nw", "srad_step",
               "srad_v1")


def _compare(entry, host_out, out, context):
    skip = set(entry.iteration_state) | set(entry.nondeterministic_shard)
    for k in host_out:
        if k in skip:
            continue
        assert (np.asarray(out[k]).tobytes()
                == np.asarray(host_out[k]).tobytes()), (
            f"{context}: buffer {k!r} not bit-identical to host-hop")


# --- the acceptance matrix: chain x backend x replay mode --------------------
@pytest.mark.parametrize("backend", ["loop", "vector", "shard"])
@pytest.mark.parametrize("name", CHAIN_NAMES)
def test_device_resident_bit_identical_to_host_hop(name, backend):
    entry = SUITE[name]
    host_out, want = run_entry(entry, backend)
    out, _ = run_entry(entry, backend, chain_mode="device")
    _compare(entry, host_out, out, f"{name}/{backend}/device")
    # the oracle outputs themselves stay exactly right
    for k, v in want.items():
        tol = entry.tol
        np.testing.assert_allclose(np.asarray(out[k]), v, rtol=tol,
                                   atol=tol)


@pytest.mark.parametrize("backend", ["loop", "vector"])
@pytest.mark.parametrize("name", CHAIN_NAMES)
def test_graph_replay_bit_identical_to_host_hop(name, backend):
    entry = SUITE[name]
    host_out, _ = run_entry(entry, backend)
    stats = ChainStats()
    out, _ = run_entry(entry, backend, chain_mode="graph",
                       chain_stats=stats)
    _compare(entry, host_out, out, f"{name}/{backend}/graph")
    assert stats.graph_replays >= 1


def test_graph_mode_under_shard_backend():
    """Captured sharded chain launches replay inside the fused dispatch."""
    entry = SUITE["pathfinder"]
    host_out, _ = run_entry(entry, "shard")
    out, _ = run_entry(entry, "shard", chain_mode="graph")
    _compare(entry, host_out, out, "pathfinder/shard/graph")


def test_chain_mode_rejected_for_single_launch_entries():
    with pytest.raises(ValueError, match="needs a LaunchChain"):
        run_entry(SUITE["vecadd"], "loop", chain_mode="device")
    with pytest.raises(ValueError, match="unknown chain_mode"):
        run_entry(SUITE["pathfinder"], "loop", chain_mode="warp9")


# --- host-sync accounting: the O(1/k) claim ----------------------------------
def test_host_syncs_drop_to_one_in_k():
    """bfs reads its stop flag back every iteration host-hop; the
    device-resident replay polls it every check_every=k iterations."""
    entry = SUITE["bfs_frontier"]
    host = ChainStats()
    run_entry(entry, "loop", chain_stats=host)
    assert host.iterations > 4          # the ring graph takes several levels
    assert host.host_syncs >= host.iterations - 1   # one per iteration
    k = entry.chain.check_every
    dev = ChainStats()
    run_entry(entry, "loop", chain_mode="device", chain_stats=dev)
    assert dev.host_syncs <= host.host_syncs / k + 1
    assert dev.syncs_per_iteration <= 1.0 / k + 0.01
    # wider poll period -> even fewer syncs, same result
    wide = ChainStats()
    out_w, _ = run_entry(entry, "loop", chain_mode="device",
                         chain_stats=wide, check_every=16)
    out_h, _ = run_entry(entry, "loop")
    _compare(entry, out_h, out_w, "bfs/check_every=16")
    assert wide.host_syncs <= dev.host_syncs


def test_fixed_repeat_chain_graph_is_single_dispatch():
    """Without a stop flag the whole remaining chain fuses into ONE graph
    replay - zero mid-chain host syncs."""
    for name in ("pathfinder", "needle_nw", "srad_step", "srad_v1"):
        stats = ChainStats()
        run_entry(SUITE[name], "loop", chain_mode="graph",
                  chain_stats=stats)
        assert stats.graph_replays == 1, name
        assert stats.host_syncs == 0, name
        assert stats.iterations == SUITE[name].chain.repeat, name


def test_stop_flag_chain_graph_polls_per_unit():
    entry = SUITE["bfs_frontier"]
    stats = ChainStats()
    out, _ = run_entry(entry, "loop", chain_mode="graph",
                       chain_stats=stats)
    host_out, _ = run_entry(entry, "loop")
    _compare(entry, host_out, out, "bfs/graph")
    assert stats.graph_replays >= 2          # converges over several units
    # one poll per replay boundary (incl. the terminating one) - never
    # one per iteration
    assert stats.host_syncs <= stats.graph_replays
    assert stats.host_syncs < stats.iterations


# --- driver-level contracts --------------------------------------------------
def _counting_chain(n, repeat, stop_after=None, with_update=True):
    """A one-kernel chain bumping a device counter each iteration."""
    from repro.core.cuda_suite import OOB

    def stage(ctx, st):
        idx = jnp.where(ctx.tid == 0, 0, OOB)
        cnt = st.glob["cnt"].at[idx].add(1, mode="drop")
        return st.set_glob(cnt=cnt)

    from repro.core.kernel import KernelDef
    k = KernelDef("count", (stage,), writes=("cnt",), reads=("cnt",))
    step = ChainStep(
        k, 1, 32,
        prepare=None if with_update else (lambda it, b: {}),
        update=(lambda b: {}) if with_update else None)
    stop = None
    if stop_after is not None:
        stop = lambda b: int(np.asarray(b["cnt"])[0]) >= stop_after
    return k, LaunchChain(steps=(step,), repeat=repeat, stop=stop)


def test_run_device_matches_run_for_plain_chain():
    from repro.core.api import launch as api_launch
    _, chain = _counting_chain(8, repeat=5)
    launch_step = lambda step, b: api_launch(
        step.kernel, grid=step.grid, block=step.block, args=b,
        backend="loop")
    a = chain.run(launch_step, {"cnt": jnp.zeros(8, jnp.int32)})
    b = chain.run_device(launch_step, {"cnt": jnp.zeros(8, jnp.int32)})
    assert int(np.asarray(a["cnt"])[0]) == 5
    np.testing.assert_array_equal(np.asarray(a["cnt"]),
                                  np.asarray(b["cnt"]))


def test_run_graph_rejects_host_only_prepare():
    """A chain step with host prepare but no device update cannot be
    captured - the error must say what to declare."""
    _, chain = _counting_chain(8, repeat=4, with_update=False)
    s = Stream({"cnt": jnp.zeros(8, jnp.int32)})
    with pytest.raises(UnsupportedKernel, match="ChainStep.update"):
        chain.run_graph(s, backend="loop")


def test_run_graph_never_exceeds_repeat_bound():
    """A stop-flag chain whose predicate never fires must still stop at
    exactly `repeat` iterations in graph mode, even when check_every does
    not divide repeat - 1 (the tail runs eagerly, not as an overshooting
    replay)."""
    _, chain = _counting_chain(8, repeat=6, stop_after=10_000)
    assert chain.check_every == 1
    import dataclasses as dc
    chain = dc.replace(chain, check_every=4)     # 5 remaining = 4 + 1 tail
    s = Stream({"cnt": jnp.zeros(8, jnp.int32)})
    stats = ChainStats()
    out = chain.run_graph(s, stats=stats, backend="loop")
    assert int(np.asarray(out["cnt"])[0]) == 6
    assert stats.iterations == 6


def test_run_graph_single_iteration_skips_capture():
    _, chain = _counting_chain(8, repeat=1)
    s = Stream({"cnt": jnp.zeros(8, jnp.int32)})
    out = chain.run_graph(s, backend="loop")
    assert int(np.asarray(out["cnt"])[0]) == 1


def test_device_stop_overshoot_is_bounded():
    """A converged stop-flag chain overshoots at most check_every-1
    iterations in device mode (and keeps the result correct)."""
    entry = SUITE["bfs_frontier"]
    host = ChainStats()
    run_entry(entry, "loop", chain_stats=host)
    dev = ChainStats()
    run_entry(entry, "loop", chain_mode="device", chain_stats=dev)
    k = entry.chain.check_every
    assert dev.iterations < host.iterations + k


def test_device_update_infers_writes_and_marks_pending():
    s = Stream({"a": jnp.zeros(8, jnp.float32),
                "b": jnp.ones(8, jnp.float32)})
    written = s.device_update(lambda h: {"a": h["b"] + 1})
    assert written == ("a",)
    assert "a" in s._pending
    np.testing.assert_array_equal(s.memcpy_d2h("a"), 2.0)
