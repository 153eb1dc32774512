"""The trace reduction: union, busy and idle share, gaps, op self times.

On hand-made intervals, and on traces this test records itself with
``jax.profiler`` on the CPU (the CPU backend's op events stand in for a
TPU plane's).
"""
from __future__ import annotations

import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import trace as T


def test_union_busy_gaps_by_hand():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 25, 26),
          ("e", 40, 40)]
    assert T.union(ev) == [(0, 15), (20, 30)]
    assert T.busy_ns(ev, 0, 50) == 25
    assert T.busy_ns(ev, 12, 22) == 5           # clipped to the window
    assert T.gaps(ev, 0, 50) == [(30, 50), (15, 20)]
    assert T.idle_pct(25, 50) == 50.0


def test_self_time_and_op_names():
    ev = [("while.1", 0, 100), ("fusion.1", 10, 20), ("fusion.2", 30, 60),
          ("copy.3", 35, 40), ("fusion.1", 70, 80)]
    assert T.self_ns(ev) == {"while.1": 50, "fusion.1": 20, "fusion.2": 25,
                             "copy.3": 5}
    assert T.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"
    assert T.op_name("dot_general.1") == "dot_general.1"


def test_gap_goes_to_the_host_span_it_overlaps_most():
    host = [("bench.window", 0, 100), ("bench.d2h", 10, 20),
            ("bench.wait", 18, 60)]
    assert T.what_host_did((15, 50), host) == "bench.wait"
    assert T.what_host_did((11, 14), host) == "bench.d2h"
    assert T.what_host_did((70, 80), host) == "host.other"


def _cpu_plane(name):
    return name == "/host:CPU"


def _cpu_ops(name):
    return name.startswith("tf_XLA")


def _load_cpu(d):
    """The CPU backend's op events stand in for a TPU's programs and ops."""
    return T.load(d, device_plane=_cpu_plane, busy_line=_cpu_ops,
                  op_line=_cpu_ops)


def test_recorded_trace_busy_idle_and_breakdown():
    x = jnp.ones((256, 256), jnp.float32)
    f = jax.jit(lambda a: (a @ a).sum())
    f(x).block_until_ready()
    got = []
    with T.record(got):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.05)
    tr = _load_cpu(got[0])
    shutil.rmtree(got[0])
    assert 0.15 <= tr.window_s < 5.0
    busy = tr.mean_busy_s()
    assert 0.0 < busy < tr.window_s
    idle = T.idle_pct(busy, tr.window_s)
    assert 50.0 < idle < 100.0                  # mostly asleep
    bd = T.breakdown(tr)
    assert bd["device_ops"] and len(bd["device_ops"]) <= 10
    assert all(s > 0 for _, s in bd["device_ops"])
    assert bd["idle_gaps"][0][0] == "bench.wait"
    assert bd["idle_gaps"][0][1] >= 0.04


def test_missing_window_is_an_error(tmp_path):
    d = str(tmp_path / "tr")
    jax.profiler.start_trace(d)
    jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="bench.window"):
        T.load(d)
