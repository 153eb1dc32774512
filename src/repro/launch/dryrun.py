"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, compiles,
fits, and emit its roofline terms.  (Deliverables e + g.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--out experiments/dryrun]

Per cell this produces JSON with:
  memory_analysis      per-chip argument/output/temp bytes (proves it fits)
  cost                 loop-aware FLOPs / HBM bytes / per-chip collective
                       link-bytes from the post-SPMD HLO (hlo_analysis.py;
                       XLA's own cost_analysis is recorded too but visits
                       while bodies once - see DESIGN.md)
  roofline             the three terms in seconds + dominant + MFU bound
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time
import traceback

import jax

from repro.configs import registry
from repro.configs.registry import SHAPES
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw
from repro.train import step as train_mod

# TPU v5e-class hardware constants (per assignment)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 4.9e10              # B/s per link


def model_flops_per_chip(cfg, shape_name, n_chips):
    """Strict assignment metric: 6*N*D (train) / 2*N*D (inference)."""
    sh = SHAPES[shape_name]
    tokens = sh["global_batch"] * (sh["seq_len"] if sh["kind"] != "decode"
                                   else 1)
    n_active = cfg.param_count(active_only=True)
    mult = 6 if sh["kind"] == "train" else 2
    return mult * n_active * tokens / n_chips


def attn_adjusted_model_flops_per_chip(cfg, shape_name, n_chips):
    """6ND plus the intrinsic attention/state-mixing matmuls (PaLM-style MFU
    accounting, unpadded head counts) - the 'achievable useful flops'."""
    sh = SHAPES[shape_name]
    S = sh["seq_len"]
    decode = sh["kind"] == "decode"
    tokens = sh["global_batch"] * (1 if decode else S)
    fb = 2 if decode else (6 if sh["kind"] == "train" else 2)
    mix_fwd_per_tok = 0.0
    if cfg.rwkv is not None:
        H, hd = cfg.d_model // cfg.rwkv.head_dim, cfg.rwkv.head_dim
        mix_fwd_per_tok = 4.0 * H * hd * hd * cfg.num_layers
    elif cfg.ssm is not None:
        s = cfg.ssm
        d_inner = s.expand * cfg.d_model
        H = d_inner // s.head_dim
        mix_fwd_per_tok = (2.0 * H * s.head_dim * (2 * s.state_dim
                                                   + s.chunk / 2)
                           * cfg.num_layers)
        if cfg.attn_every:
            ctx = S if decode else S / 2
            napps = -(-cfg.num_layers // cfg.attn_every)
            mix_fwd_per_tok += (4.0 * cfg.num_heads * cfg.hd * ctx * napps)
    else:
        ctx = S if decode else S / 2
        mix_fwd_per_tok = 4.0 * cfg.num_heads * cfg.hd * ctx * cfg.num_layers
    base = model_flops_per_chip(cfg, shape_name, n_chips)
    return base + (fb / 2.0) * mix_fwd_per_tok * tokens / n_chips


ACT_BUDGET = int(float(os.environ.get("REPRO_ACT_BUDGET_GB", "3"))
                 * 2**30)   # per-chip bytes allowed for the residual carry


def pick_microbatches(cfg, shape_name, mesh) -> int:
    """Gradient-accumulation factor so the layer-scan residual carry fits.

    The saved per-layer carry is (B_chip/mb) * S * D * 2B * L; pick the
    smallest power-of-two mb that brings it under ACT_BUDGET."""
    sh = SHAPES[shape_name]
    if sh["kind"] != "train":
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    b_chip = max(1, sh["global_batch"] // dp)
    carry = b_chip * sh["seq_len"] * cfg.d_model * 2 * cfg.num_layers
    if cfg.seq_parallel and sh["seq_len"] % sizes.get("model", 1) == 0:
        carry //= sizes.get("model", 1)   # SP shards the residual carry
    mb = 1
    while carry / mb > ACT_BUDGET and mb < b_chip:
        mb *= 2
    return mb


def lower_cell(cfg, shape_name, mesh, serve_pure_tp: bool = False):
    """Returns the lowered computation for one cell.

    ``serve_pure_tp`` (optimization O2): inference has no optimizer states,
    so weights replicate across 'data' (pure TP) instead of FSDP - kills the
    per-token weight all-gathers that dominate decode collectives."""
    sh = SHAPES[shape_name]
    kind = sh["kind"]
    bstruct = specs.input_specs(cfg, shape_name)
    opt_cfg = adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype)
    mb = pick_microbatches(cfg, shape_name, mesh)

    rules = {"fsdp": ()} if (serve_pure_tp and kind != "train") else None
    with shd.use_mesh(mesh, rules=rules):
        if kind == "train":
            pstruct, ostruct, pspec, ospec, bspec = specs.train_shardings(
                cfg, mesh, bstruct)

            def fn(p, o, b):
                return train_mod.train_step(cfg, opt_cfg, p, o, b,
                                            microbatches=mb)

            lowered = jax.jit(fn, in_shardings=(pspec, ospec, bspec),
                              donate_argnums=(0, 1)).lower(
                pstruct, ostruct, bstruct)
        elif kind == "prefill":
            pstruct = T.abstract_params(cfg)
            pspec = specs.param_specs(pstruct, mesh)
            bspec = specs.batch_sharding(bstruct, mesh)

            def fn(p, b):
                return T.prefill(cfg, p, b, max_len=sh["seq_len"])

            lowered = jax.jit(fn, in_shardings=(pspec, bspec)).lower(
                pstruct, bstruct)
        else:  # decode
            pstruct = T.abstract_params(cfg)
            pspec = specs.param_specs(pstruct, mesh)
            cspec = specs.cache_sharding(bstruct["cache"], mesh)
            tspec = specs.batch_sharding(
                {"tokens": bstruct["tokens"]}, mesh)["tokens"]

            def fn(p, c, t):
                return T.decode_step(cfg, p, c, t)

            lowered = jax.jit(fn, in_shardings=(pspec, cspec, tspec),
                              donate_argnums=(1,)).lower(
                pstruct, bstruct["cache"], bstruct["tokens"])
    return lowered


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None,
             serve_pure_tp: bool = False) -> dict:
    cfg = registry.get(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "multi_pod": multi_pod, "status": "ok",
           "serve_pure_tp": serve_pure_tp,
           "overrides": {k: str(v) for k, v in (overrides or {}).items()},
           "microbatches": pick_microbatches(cfg, shape_name, mesh)}
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape_name, mesh,
                             serve_pure_tp=serve_pure_tp)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        m = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "peak_per_chip_gb": round(
                (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes) / 2**30,
                3),
        }
        rec["memory"]["fits_16gb_hbm"] = \
            rec["memory"]["peak_per_chip_gb"] <= 16.0
        xla_cost = compiled.cost_analysis()
        rec["xla_flops_once"] = float(xla_cost.get("flops", -1))

        hlo = compiled.as_text()
        costs = hlo_analysis.analyze(hlo, num_partitions=n_chips)
        rec["cost"] = {
            "flops_per_chip": costs.flops,
            "hbm_bytes_per_chip": costs.bytes,
            "coll_link_bytes_per_chip": costs.coll_bytes,
            "coll_counts": dict(costs.coll_counts),
        }
        mf = model_flops_per_chip(cfg, shape_name, n_chips)
        mfa = attn_adjusted_model_flops_per_chip(cfg, shape_name, n_chips)
        t_c = costs.flops / PEAK_FLOPS
        t_m = costs.bytes / HBM_BW
        t_x = costs.coll_bytes / ICI_BW
        dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
        rec["roofline"] = {
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "dominant": dom[1],
            "model_flops_per_chip": mf,
            "attn_adj_model_flops_per_chip": mfa,
            "model_over_hlo_flops": mf / max(costs.flops, 1.0),
            "adj_model_over_hlo_flops": mfa / max(costs.flops, 1.0),
            "bound_step_s": max(t_c, t_m, t_x),
            "mfu_bound": mf / PEAK_FLOPS / max(t_c, t_m, t_x),
        }
    except Exception as e:  # broad on purpose: a failed cell is a bug, record it
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def optimized_overrides(arch: str, shape_name: str):
    """The beyond-paper configuration per cell (EXPERIMENTS.md SPerf):
    O1 seq-parallel for train cells, O2 pure-TP params for serve cells.
    (O3b and O4 are now the defaults in moe.py / transformer.py.)"""
    kind = SHAPES[shape_name]["kind"]
    overrides = {}
    if kind == "train":
        overrides["seq_parallel"] = True
    return overrides, kind != "train"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the SPerf beyond-paper config (O1/O2)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = registry.cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}" + \
                ("_opt" if args.optimized else "")
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") == "ok":
                        print(f"[skip] {tag}")
                        continue
            if args.optimized:
                ov, tp = optimized_overrides(arch, shape)
                rec = run_cell(arch, shape, mp, overrides=ov,
                               serve_pure_tp=tp)
            else:
                rec = run_cell(arch, shape, mp)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec.get("roofline", {})
            print(f"[{rec['status']}] {tag} compile={rec.get('compile_s')}s "
                  f"mem={rec.get('memory', {}).get('peak_per_chip_gb')}GB "
                  f"dom={r.get('dominant')} mfu_bound="
                  f"{r.get('mfu_bound', 0):.3f}"
                  + ("" if rec["status"] == "ok" else
                     " ERR " + rec.get("error", "")[:160]))


if __name__ == "__main__":
    main()
