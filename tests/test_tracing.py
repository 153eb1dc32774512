"""Launch-path counters, kernel-named device programs and host spans.

* ``api.cache_stats()`` times the miss path (``trace_s``), the first
  dispatch of each specialization (``first_calls``/``first_call_s``) and
  warm launches (``warm_launches``/``warm_launch_s``);
* every device program is named for its kernel: ``jit_<kernel>__<backend>``,
  ``..__batch`` for stacked batches, ``..__graph`` for graph replays;
* inside a ``jax.profiler`` session the launch path, chain replay and the
  kernel service write ``cupbop.*`` spans on the host, nested as the code
  nests them.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, packing
from repro.core.kernel import ChainStep, KernelDef, LaunchChain
from repro.core.streams import Stream
from repro.serve import KernelService

N, BLOCK = 256, 64
GRID = N // BLOCK


def make_vecadd(name="vec.add"):
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        return st.set_glob(c=st.glob["c"].at[gid].set(
            st.glob["a"][gid] + st.glob["b"][gid]))

    return KernelDef(name, (stage,), writes=("c",), reads=("a", "b", "c"))


def vecadd_args(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.standard_normal(N, dtype=np.float32)),
            "b": jnp.asarray(rng.standard_normal(N, dtype=np.float32)),
            "c": jnp.zeros(N, jnp.float32)}


@pytest.fixture(autouse=True)
def fresh_cache():
    api.cache_clear()
    yield
    api.cache_clear()


TIMES = ("trace_s", "first_call_s", "warm_launch_s")
COUNTS = ("first_calls", "warm_launches")


def test_cold_then_warm_launches_are_counted_apart():
    k, args = make_vecadd(), vecadd_args()
    warm = 5
    for _ in range(1 + warm):
        out = api.launch(k, grid=GRID, block=BLOCK, args=args)
    np.testing.assert_array_equal(out["c"], args["a"] + args["b"])
    s = api.cache_stats()
    assert (s.misses, s.hits) == (1, warm)
    assert (s.first_calls, s.warm_launches) == (1, warm)
    assert all(getattr(s, f) > 0 for f in TIMES), s


def test_cache_clear_zeroes_the_new_fields():
    k, args = make_vecadd(), vecadd_args()
    for _ in range(2):
        api.launch(k, grid=GRID, block=BLOCK, args=args)
    api.cache_clear()
    s = api.cache_stats()
    assert all(getattr(s, f) == 0 for f in TIMES + COUNTS), s


def test_compiled_then_launch_counts_one_first_call():
    k, args = make_vecadd(), vecadd_args()
    api.compiled(k, grid=GRID, block=BLOCK, args=args)
    s = api.cache_stats()
    assert (s.misses, s.first_calls) == (1, 0) and s.trace_s > 0
    api.launch(k, grid=GRID, block=BLOCK, args=args)
    s = api.cache_stats()
    assert (s.hits, s.first_calls, s.warm_launches) == (1, 1, 0)
    api.launch(k, grid=GRID, block=BLOCK, args=args)
    assert api.cache_stats().warm_launches == 1


def test_batch_first_dispatch_is_counted_not_warm():
    k = make_vecadd()
    batch = [vecadd_args(i) for i in range(3)]
    for _ in range(2):
        api.launch_batch(k, grid=GRID, block=BLOCK, args_list=batch)
    s = api.cache_stats()
    assert (s.misses, s.hits, s.first_calls, s.warm_launches) == (1, 1, 1, 0)
    assert s.trace_s > 0 and s.first_call_s > 0


def test_graph_replay_counts_its_first_dispatch():
    k, args = make_vecadd(), vecadd_args()
    stream = Stream(dict(args))
    graph = stream.begin_capture()
    stream.launch(k, grid=GRID, block=BLOCK)
    stream.end_capture()
    ex = graph.instantiate(stream.buffers)
    for _ in range(3):
        ex.launch(stream)
    np.testing.assert_array_equal(stream.buffers["c"], args["a"] + args["b"])
    s = api.cache_stats()
    assert (s.first_calls, s.warm_launches) == (1, 0) and s.first_call_s > 0


def _module_name(what: str, k: KernelDef) -> str:
    args = vecadd_args()
    if what == "launch":
        entry = api.compiled(k, grid=GRID, block=BLOCK, args=args)
        lowered = entry.fn.lower(*packing.pack(args)[0])
    elif what == "batch":
        api.launch_batch(k, grid=GRID, block=BLOCK, args_list=[args, args])
        (entry,) = [e for key, e in getattr(k, api._CACHE_ATTR).items()
                    if key[0] == "batch"]
        leaves = packing.pack(args)[0]
        lowered = entry.fn.lower(*(jnp.stack([x, x]) for x in leaves))
    else:
        stream = Stream(dict(args))
        graph = stream.begin_capture()
        stream.launch(k, grid=GRID, block=BLOCK)
        stream.end_capture()
        ex = graph.instantiate(stream.buffers)
        lowered = ex._jit.lower(ex._heap_inputs(stream.buffers), ())
    return lowered.as_text().split(" ", 2)[1]


@pytest.mark.parametrize("what, want", [
    ("launch", "@jit_vec_add__vector"),
    ("batch", "@jit_vec_add__vector__batch"),
    ("graph", "@jit_vec_add__vector__graph"),
])
def test_device_program_is_named_for_its_kernel(what, want):
    assert _module_name(what, make_vecadd()) == want


def test_graph_name_holds_each_kernel_once():
    a, b = make_vecadd("first"), make_vecadd("second")
    stream = Stream(vecadd_args())
    graph = stream.begin_capture()
    for k in (a, b, a):
        stream.launch(k, grid=GRID, block=BLOCK, backend="loop"
                      if k is b else "vector")
    stream.end_capture()
    ex = graph.instantiate(stream.buffers)
    assert ex._jit.__name__ == "first__vector__second__loop__graph"


def test_program_name_keeps_only_identifier_characters():
    assert api.program_name("a-b.c/d", "vector") == "a_b_c_d__vector"


# -- spans -------------------------------------------------------------------
def _host_spans(log_dir: str) -> dict:
    """``cupbop.*`` events of every host line: ``(plane, line index) ->
    [(name, start_ns, end_ns)]``."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):    # one line per thread
            evs = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events
                   if ev.name.startswith("cupbop.")]
            if evs:
                out[(plane.name, i)] = evs
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session around a cold launch, a host-mode chain with a
    stop flag, a stacked batch, a graph-replayed chain and one service
    dispatch."""
    api.cache_clear()
    k, args = make_vecadd(), vecadd_args()
    chain = LaunchChain(steps=(ChainStep(k, GRID, BLOCK,
                                         update=lambda b: {}),),
                        repeat=3, stop=lambda b: False)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        api.launch(k, grid=GRID, block=BLOCK, args=args)
        chain.run(lambda s, b: api.launch(s.kernel, grid=s.grid,
                                          block=s.block, args=b), dict(args))
        api.launch_batch(k, grid=GRID, block=BLOCK, args_list=[args, args])
        chain.run_graph(Stream(dict(args)), check_every=1)
        with KernelService(backend="vector") as svc:
            svc.register("vecadd", k, grid=GRID, block=BLOCK)
            svc.submit("vecadd", args).result(timeout=120)
    finally:
        jax.profiler.stop_trace()
    return _host_spans(log_dir)


def _named(traced, name) -> list:
    return [ev for evs in traced.values() for ev in evs if ev[0] == name]


@pytest.mark.parametrize("inner, outer", [
    ("cupbop.compile", ("cupbop.launch", "cupbop.launch_batch")),
    ("cupbop.dispatch", ("cupbop.launch", "cupbop.launch_batch")),
    ("cupbop.launch", ("cupbop.chain.iteration", "cupbop.launch_batch",
                       "cupbop.serve.dispatch", None)),
    ("cupbop.launch_batch", ("cupbop.serve.dispatch", None)),
    ("cupbop.chain.iteration", (None,)),
    ("cupbop.chain.stop", (None,)),
    ("cupbop.graph.replay", (None,)),
    ("cupbop.serve.dispatch", (None,)),
])
def test_span_is_recorded_and_nested(traced, inner, outer):
    """Every ``inner`` span lies directly inside a span named in ``outer``
    on its own host line (``None``: inside no ``cupbop`` span)."""
    found = 0
    for evs in traced.values():
        for ev in (e for e in evs if e[0] == inner):
            found += 1
            around = [o for o in evs if o is not ev and _inside(ev, o)]
            parent = max(around, key=lambda o: o[1])[0] if around else None
            assert parent in outer, (inner, parent)
    assert found, f"no {inner} span in the trace"


def test_spans_count_what_ran(traced):
    assert len(_named(traced, "cupbop.chain.iteration")) == 3
    assert len(_named(traced, "cupbop.chain.stop")) == 2 + 1
    # cold launch, 3 chain launches, graph chain's first eager launch and
    # tail, one service request
    assert len(_named(traced, "cupbop.launch")) >= 5
    assert len(_named(traced, "cupbop.graph.replay")) >= 1
