"""Dry-run machinery: HLO analysis accuracy, input specs, and a true
multi-device numerical-equivalence test (subprocess, 8 forced CPU devices)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.launch import hlo_analysis as H

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_hlo_analysis_matches_xla_loop_free():
    def f(x, w1, w2):
        return jnp.sum(jnp.tanh(x @ w1) @ w2)
    args = (jax.ShapeDtypeStruct((128, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 512), jnp.float32),
            jax.ShapeDtypeStruct((512, 64), jnp.float32))
    c = jax.jit(f).lower(*args).compile()
    ours = H.analyze(c.as_text())
    xla = c.cost_analysis()["flops"]
    assert abs(ours.flops - xla) / xla < 0.05


def test_hlo_analysis_scan_trip_count():
    def g(x, ws):
        def body(cr, w):
            return jnp.tanh(cr @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)
    c = jax.jit(g).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)).compile()
    ours = H.analyze(c.as_text())
    expect = 2 * 128 * 256 * 256 * 10
    assert abs(ours.flops - expect) / expect < 0.05
    # XLA itself undercounts by ~the trip count (the reason this module exists)
    assert c.cost_analysis()["flops"] < expect / 5


def test_input_specs_shapes():
    from repro.launch import specs
    cfg = registry.get("qwen2.5-32b")
    b = specs.input_specs(cfg, "train_4k")
    assert b["tokens"].shape == (256, 4096)
    d = specs.input_specs(cfg, "decode_32k")
    assert d["tokens"].shape == (128, 1)
    assert d["cache"]["k"].shape == (64, 128, 32768, 16, 128)  # kv padded 16
    v = specs.input_specs(registry.get("internvl2-76b"), "prefill_32k")
    assert v["tokens"].shape == (32, 32768 - 1024)
    assert v["patch_embeds"].shape == (32, 1024, 8192)
    a = specs.input_specs(registry.get("musicgen-medium"), "train_4k")
    assert a["tokens"].shape == (256, 4096, 4)


def test_all_dryrun_cells_have_results():
    """The committed sweep must cover every assigned cell on both meshes."""
    out = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun")
    if not os.path.isdir(out):
        pytest.skip("dry-run sweep not generated yet")
    missing, failed = [], []
    for arch, shape in registry.cells():
        for mesh in ("16x16", "2x16x16"):
            p = os.path.join(out, f"{arch}_{shape}_{mesh}.json")
            if not os.path.exists(p):
                missing.append((arch, shape, mesh))
                continue
            with open(p) as f:
                if json.load(f).get("status") != "ok":
                    failed.append((arch, shape, mesh))
    assert not missing, f"missing cells: {missing}"
    assert not failed, f"failed cells: {failed}"


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import registry
from repro.distributed import sharding as shd
from repro.launch import specs
from repro.models import transformer as T
from repro.optim import adamw
from repro.train import step as train_mod

cfg = registry.smoke("granite-3-2b").replace(d_model=64, num_heads=4,
                                             num_kv_heads=2, tp_align=2)
opt_cfg = adamw.AdamWConfig(total_steps=10, warmup_steps=1)
params = T.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw.init_state(opt_cfg, params)
rng = np.random.default_rng(0)
batch = {"tokens": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}

# single device reference
p1, o1, m1 = jax.jit(train_mod.make_train_step(cfg, opt_cfg))(
    params, opt, batch)

# sharded on a (2 data, 4 model) mesh
mesh = shd.make_mesh((2, 4), ("data", "model"))
with shd.use_mesh(mesh):
    pspec = shd.param_specs(params, mesh)
    ps = jax.device_put(params, pspec)
    os_ = adamw.AdamWState(step=opt.step,
                           m=jax.device_put(opt.m, shd.param_specs(opt.m, mesh)),
                           v=jax.device_put(opt.v, shd.param_specs(opt.v, mesh)))
    p2, o2, m2 = jax.jit(train_mod.make_train_step(cfg, opt_cfg))(
        ps, os_, batch)

assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (
    float(m1["loss"]), float(m2["loss"]))
err = max(float(jnp.abs(a - b).max())
          for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert err < 5e-3, err
print("MULTIDEV_OK", float(m1["loss"]), err)
"""


def test_sharded_equals_single_device():
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "MULTIDEV_OK" in r.stdout


_RESHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, jax.numpy as jnp, numpy as np
from repro.checkpoint.ckpt import CheckpointManager
from repro.configs import registry
from repro.distributed import sharding as shd
from repro.models import transformer as T

tmp = sys.argv[1]
cfg = registry.smoke("qwen2-0.5b").replace(tp_align=2)
params = T.init_params(cfg, jax.random.PRNGKey(0))
mesh1 = shd.make_mesh((2, 4), ("data", "model"))
p1 = shd.shard_params(params, mesh1)
mgr = CheckpointManager(tmp)
mgr.save(1, p1, blocking=True)
# elastic: restore onto a different mesh topology
mesh2 = shd.make_mesh((2, 2, 2), ("pod", "data", "model"))
p2, _ = mgr.restore(params, mesh=mesh2)
err = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
          for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
assert err == 0.0, err
print("RESHARD_OK")
"""


def test_elastic_reshard_restore(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _RESHARD_SCRIPT,
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "RESHARD_OK" in r.stdout
