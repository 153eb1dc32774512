"""Cells at sizes a test run can hold, driven through the harness.

``small_bench`` keeps every name of ``BENCHMARK.json`` and shrinks only
the scale of each configuration and the window; the harness's look for a
chip is skipped by calling :func:`bench.lib.harness.run_cell` directly.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench.lib.registry import Bench

SMALL = {"hotspot_1024": {"rows": 32, "cols": 64}}
SEED = 2**31 + 12345


def small_bench() -> Bench:
    b = Bench()
    config = b.config

    def small_config(name):
        cfg = config(name)
        return dataclasses.replace(cfg, params={**cfg.params, **SMALL[name]})

    b.config = small_config
    return b


def alter_one(x):
    """Scale one element of ``x`` by 1.01: an answer altered where made."""
    flat = x.reshape(-1)
    return flat.at[0].multiply(jnp.asarray(1.01, x.dtype)).reshape(x.shape)


def faulty_vector(fault: str, run):
    """``lower_vector.run`` with one fault planted underneath the timed
    path: the state returned unchanged, half of the blocks left out, or
    one answer altered."""
    def broken(kernel, **kw):
        if fault == "unchanged":
            return kw["glob"]
        if fault == "half":
            from repro.core.dim3 import Dim3
            kw.setdefault("count", Dim3.of(kw["grid"]).size)
            return run(kernel, **{**kw, "count": kw["count"] // 2})
        out = dict(run(kernel, **kw))
        name = kernel.writes[0]
        out[name] = alter_one(out[name])
        return out
    return broken
