"""Logical-axis sharding rules (MaxText-style), resolved against any mesh.

Model code annotates activations with *logical* axis names via ``constrain``;
parameters get specs from path-based rules in ``param_specs``.  Resolution is
mesh-shape aware: a logical axis maps to its mesh axes only when the dimension
size divides the axis size and the axis is not already taken by another dim -
this makes the same model code valid on the 16x16 pod mesh, the 2x16x16
multi-pod mesh, a tiny test mesh, or a single CPU device (everything resolves
to replicated).

FSDP is intra-pod only ('data'); across pods we run plain DP over DCN
(gradients cross pods once per step; see distributed/compression.py).
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> ordered mesh-axis candidates (prefix-greedy)
RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "tp": ("model",),
    "expert": ("model",),
    "heads": ("model",),
    "vocab": ("model",),
    "seq": (),              # sequence unsharded by default
    "seq_sp": ("model",),   # Megatron sequence parallelism (cfg.seq_parallel)
    "kv_seq": (),           # hillclimb: ("data",) when cfg.seq_shard_long
    "none": (),
}

_ACTIVE: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_active_mesh", default=None
)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE.get()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Install ``mesh`` (and optional rule overrides) for model annotations."""
    tok = _ACTIVE.set(mesh)
    global RULES
    old = RULES
    if rules:
        RULES = {**RULES, **rules}
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield mesh
        else:
            yield None
    finally:
        _ACTIVE.reset(tok)
        RULES = old


def make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def resolve(mesh: Mesh, shape, logical: tuple[Optional[str], ...]) -> P:
    """Map logical dim names to a PartitionSpec valid for ``shape`` on ``mesh``."""
    assert len(logical) == len(shape), (logical, shape)
    used: set[str] = set()
    out = []
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
    for dim, name in zip(shape, logical, strict=True):
        if name is None or name == "none":
            out.append(None)
            continue
        cands = [a for a in RULES.get(name, ()) if a in sizes and a not in used]
        picked: list[str] = []
        prod = 1
        for a in cands:  # greedy prefix while divisibility holds
            if dim % (prod * sizes[a]) == 0:
                picked.append(a)
                prod *= sizes[a]
            else:
                break
        used.update(picked)
        out.append(tuple(picked) if len(picked) > 1 else
                   (picked[0] if picked else None))
    return P(*out)


def constrain(x, *logical: Optional[str]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = _ACTIVE.get()
    if mesh is None or mesh.devices.size == 1:
        return x
    spec = resolve(mesh, x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter specs: path-regex -> logical names per dim (rightmost dims; any
# leading dims - e.g. the stacked layer axis - are replicated).
# ---------------------------------------------------------------------------
PARAM_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    (r"embed/tok$",            ("vocab", "fsdp")),
    (r"embed/codebooks$",      ("none", "vocab", "fsdp")),
    (r"patch_proj$",           ("fsdp", "tp")),
    (r"(wq|wk|wv|w_in)$",      ("fsdp", "tp")),
    (r"(bq|bk|bv)$",           ("tp",)),
    (r"wo$",                   ("tp", "fsdp")),
    (r"(w_gate|w_up)$",        ("fsdp", "tp")),
    (r"w_down$",               ("tp", "fsdp")),
    (r"router$",               ("fsdp", "none")),
    (r"experts/(w_gate|w_up)$", ("expert", "fsdp", "tp")),
    (r"experts/w_down$",       ("expert", "tp", "fsdp")),
    (r"(in_proj|rkvg|w1)$",    ("fsdp", "tp")),
    (r"(out_proj|w2)$",        ("tp", "fsdp")),
    (r"lm_head$",              ("fsdp", "vocab")),
    (r"lm_heads$",             ("none", "fsdp", "vocab")),
    # norms, biases, decays, small states: replicated
    (r".*",                    ()),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def spec_for_path(mesh: Mesh, path_str: str, shape) -> P:
    for pat, logical in PARAM_RULES:
        if re.search(pat, path_str):
            names: list = [None] * len(shape)
            if logical:
                k = min(len(logical), len(shape))
                names[len(shape) - k:] = list(logical)[-k:] if k < len(logical) \
                    else list(logical)
            return resolve(mesh, shape, tuple(names))
    return P()


def param_specs(params_shape, mesh: Mesh):
    """pytree of NamedSharding matching a params (shape) pytree."""
    def one(path, leaf):
        return NamedSharding(mesh, spec_for_path(mesh, _path_str(path),
                                                 leaf.shape))
    return jax.tree_util.tree_map_with_path(one, params_shape)


def shard_params(params, mesh: Mesh):
    return jax.device_put(params, param_specs(params, mesh))
