"""Compiles for a described TPU v5e chip: no chip needed, nothing runs.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached.  These tests compile the ``vector`` lowering at
deployment sizes (Rodinia's hotspot 1024x1024, nw 2048, pathfinder at
102,400 columns, srad_v1's six kernels at 502x458) for one v5e chip, and pin the ``pallas`` rule: without
interpret mode Mosaic refuses the suite's gather-mode kernels, and the
launch path reports that refusal as ``UnsupportedKernel``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import api, memory, packing
from repro.core.cuda_suite import (entry_hotspot, entry_needle_nw,
                                   entry_pathfinder, entry_srad_v1,
                                   make_vecadd)
from repro.core.dim3 import Dim3
from repro.core.kernel import UnsupportedKernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _chip_shapes(kernel, args, sharding):
    leaves, _ = packing.pack(memory.resolve_launch_args(kernel, args))
    return [jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                 sharding=sharding) for x in leaves]


def _hotspot():
    e = entry_hotspot(1024, 1024)
    z = np.zeros((1024, 1024), np.float32)   # shapes only: skip the text I/O
    return e, {"t": z, "p": z, "t_out": z}


def _needle():
    e = entry_needle_nw(2048)
    return e, e.make_args(np.random.default_rng(0))


def _pathfinder():
    e = entry_pathfinder(400)                # 102,400 columns
    return e, e.make_args(np.random.default_rng(0))


@pytest.mark.parametrize("build", [_hotspot, _needle, _pathfinder],
                         ids=["hotspot_1024", "needle_nw_2048",
                              "pathfinder_102400"])
def test_vector_compiles_for_one_v5e_at_deployment_size(build, one_chip):
    entry, args = build()
    (step,) = entry.chain.steps
    ck = api.compiled(step.kernel, grid=step.grid, block=step.block,
                      args=args, backend="vector")
    compiled = ck.fn.lower(*_chip_shapes(step.kernel, args,
                                         one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= sum(
        np.asarray(v).nbytes for v in args.values())
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_srad_v1_compiles_for_one_v5e_at_deployment_size(one_chip):
    """Every launch specialization of ``./srad 100 0.5 502 458``: extract,
    prepare, both reduce passes (450 blocks, then one), srad, srad2 and
    compress, each on the block schedule it takes on the CPU."""
    entry = entry_srad_v1(502, 458, iters=100)
    args = entry.make_args(np.random.default_rng(0))
    schedules = {}
    for step in entry.chain.all_steps:
        ck = api.compiled(step.kernel, grid=step.grid, block=step.block,
                          args=args, backend="vector")
        schedules.setdefault(step.kernel.name, set()).add(
            ck.schedule.split(":")[0])
        compiled = ck.fn.lower(*_chip_shapes(step.kernel, args,
                                             one_chip)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    assert schedules == {"extract": {"serial"}, "prepare": {"tiled"},
                         "reduce": {"serial"}, "srad": {"tiled"},
                         "srad2": {"serial"}, "compress": {"serial"}}


def test_pallas_mosaic_refusal_is_unsupported(one_chip):
    n, block = 4096, 128
    kernel = make_vecadd(n)
    args = {k: np.zeros(n, np.float32) for k in ("a", "b", "c")}
    _, treedef = packing.pack(args)
    fn = api._build(kernel, "pallas", Dim3.of(n // block), Dim3.of(block),
                    1, None, treedef, False, None, "blocks")
    shapes = _chip_shapes(kernel, args, one_chip)
    with pytest.raises(Exception) as refused:
        fn.lower(*shapes).compile()
    assert not isinstance(refused.value, UnsupportedKernel)
    with pytest.raises(UnsupportedKernel,
                       match=r"^pallas/Mosaic: NotImplementedError: Only 2D "
                             r"gather is supported"):
        api.mosaic_compile(fn, shapes)
