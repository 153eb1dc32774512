"""The per-layer readers of the program's own counters
(``api.cache_stats()``): a number after a launch, nothing after
``cache_clear()``."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.lib.registry import Bench
from repro.core import api
from repro.core.cuda_suite import make_vecadd

READERS = ("setup_trace_s", "setup_first_call_s")


@pytest.fixture(scope="module")
def bench():
    return Bench()


def _launch_twice():
    k = make_vecadd(256)
    args = {n: jnp.ones(256, jnp.float32) for n in "abc"}
    for _ in range(2):            # a cold launch, then a warm one
        api.launch(k, grid=2, block=128, args=args, backend="vector")


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_program_counter(bench, name):
    api.cache_clear()
    try:
        _launch_twice()
        value = bench.reader(name)(None)
        assert isinstance(value, float) and value > 0
        api.cache_clear()
        assert bench.reader(name)(None) is None
    finally:
        api.cache_clear()


@pytest.mark.parametrize("name", READERS)
def test_reader_is_listed_as_a_program_counter(bench, name):
    (m,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert m["source"] == "program_counter"
    assert bench.metrics_for("hotspot_1024.job", traced=True).count(m) == 1
