"""CUDA atomics, adapted for TPU.

TPU Pallas exposes no global-memory atomics.  The semantics-preserving
adaptation relies on two facts of the lowered execution model:

* within one vectorized scatter, XLA's ``scatter-add`` accumulates duplicate
  indices deterministically - a *stronger* guarantee than CUDA's unordered
  atomicAdd;
* across blocks, grid steps of a Pallas kernel on one TensorCore (and the
  block fori_loop of the loop/vector lowerings) execute sequentially, so
  read-modify-write accumulation into the output buffer is race-free.

atomicCAS has no order-free equivalent; we provide the *first-wins* variant
(lowest thread id wins each index), which is sufficient for the lock/claim
idioms in Crystal-style database kernels and is deterministic.

**Cross-shard combining.**  The grid-serial argument above breaks once the
*shard* backend (:mod:`repro.core.lower_shard`) spreads blocks over XLA
devices: two blocks on different devices may hit the same output element,
and each device only sees its own partial result.  The adaptation is the
classic partials-plus-reduce: every shard runs its block range against the
*launch-time* value of each written buffer, then :func:`combine_partials`
merges the per-shard partials with a cross-device collective keyed off the
kernel's ``KernelDef.combines`` declaration:

* ``"sum"`` (default) - ``psum`` of per-shard *deltas* added back onto the
  launch-time value.  A shard that never touched an element contributes a
  delta of exactly zero, so this is exact for cross-block ``atomicAdd``
  accumulation (adds commute across shards) and for disjoint writes into
  zero-initialized buffers (delta == written value).  A disjoint
  *overwrite* of elements holding large prior values is reconstructed as
  ``in + (out - in)``, which rounds in floating point once ``|in|`` and
  ``|out|`` differ by more than the mantissa - declare such buffers
  ``"concat"`` (below) or keep them integer;
* ``"max"`` / ``"min"`` - ``pmax``/``pmin`` of the per-shard results, the
  cross-block ``atomicMax``/``atomicMin`` semantics;
* ``"concat"`` - an *owned-slice* declaration: block ``b`` writes only the
  buffer's leading-axis rows ``[b*rpb, (b+1)*rpb)`` (``rpb`` = rows /
  n_blocks), so each shard owns its contiguous slice and the results
  assemble with **zero cross-device communication** (the shard backend
  shards the output instead of reducing it).  This is the fast path for
  embarrassingly-parallel kernels: collectives rendezvous every device
  thread, which on oversubscribed CPU hosts costs more than the compute
  being combined.  When the grid or buffer does not divide evenly the
  backend falls back to ``"sum"`` with a warning (exact for accumulation
  and zero-initialized buffers; float overwrites of large prior values
  round - see above).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

#: combine modes accepted in ``KernelDef.combines``.  sum/max/min reduce
#: via collectives (:func:`combine_partials`); concat is structural and
#: handled by the shard backend's output sharding.
CROSS_SHARD_COMBINES = ("sum", "max", "min", "concat")


def combine_partials(mode: str, before, after, axis_name: str):
    """Merge one written buffer's per-shard partials across ``axis_name``.

    ``before`` is the buffer's launch-time (replicated) value, ``after``
    the shard-local value once the shard's block range ran.  Must be called
    inside a ``shard_map`` over ``axis_name``; the result is replicated.
    """
    if mode == "sum":
        return before + lax.psum(after - before, axis_name)
    if mode == "max":
        return lax.pmax(after, axis_name)
    if mode == "min":
        return lax.pmin(after, axis_name)
    raise ValueError(
        f"cross-shard combine mode {mode!r} is not a collective reduction; "
        f"reducible modes: sum/max/min (concat is resolved by the shard "
        f"backend's output sharding, not here)")


def _drop_negative(arr, idx):
    """Sanitize negative indices to the past-the-end drop sentinel.

    ``.at[idx].op(val, mode="drop")`` only drops *past-the-end* indices:
    JAX applies negative indexing before the OOB mode, so ``-1`` silently
    wraps to the last element - exactly the left-halo stencil index a
    CUDA kernel expects to be discarded.  Rewriting negatives to
    ``arr.shape[0]`` makes them genuinely out of bounds, restoring the
    documented OOB-drop contract.
    """
    idx = jnp.asarray(idx)
    return jnp.where(idx < 0, arr.shape[0], idx)


def atomic_add(arr, idx, val):
    return arr.at[_drop_negative(arr, idx)].add(val, mode="drop")


def atomic_max(arr, idx, val):
    return arr.at[_drop_negative(arr, idx)].max(val, mode="drop")


def atomic_min(arr, idx, val):
    return arr.at[_drop_negative(arr, idx)].min(val, mode="drop")


def _first_occurrence(idx):
    """Mask of chunk positions that are the first occurrence of their index."""
    n = idx.shape[0]
    eq = idx[None, :] == idx[:, None]                       # [t, t']
    lower = jnp.tril(jnp.ones((n, n), dtype=bool), k=-1)
    return ~jnp.any(eq & lower, axis=1)


def _serial_rmw(arr, idx, update):
    """Serialize a read-modify-write over the thread chunk in thread order.

    ``update(t, cur)`` returns the value to store at ``idx[t]`` given the
    currently-observed ``cur`` (return ``cur`` to store nothing).  Indices
    outside ``[0, arr.shape[0])`` - negative or at/past the end - mark
    inactive threads: they observe a clamped gather but always store the
    observed value back (a no-op), matching the ``mode="drop"`` contract
    of :func:`atomic_add`/``max``/``min``.  Returns ``(new_arr, old)``
    where ``old[t]`` is the value thread ``t`` observed - exactly CUDA's
    return-the-previous-value contract, under the deterministic
    thread-order serialization.
    """
    idx = jnp.asarray(idx)
    size = arr.shape[0]

    def body(t, carry):
        a, old = carry
        active = (idx[t] >= 0) & (idx[t] < size)
        safe = jnp.clip(idx[t], 0, size - 1)
        cur = a[safe]
        new = jnp.where(active, update(t, cur), cur)
        a = a.at[safe].set(new)
        return a, old.at[t].set(cur)

    old0 = jnp.zeros(idx.shape, arr.dtype)
    return lax.fori_loop(0, idx.shape[0], body, (arr, old0))


def atomic_cas(arr, idx, cmp, val):
    """``atomicCAS``: returns ``(new_arr, old)`` with serialized semantics.

    Threads of the chunk execute in thread order: each observes the value
    its predecessors left at ``arr[idx[t]]`` and swaps in ``val[t]`` iff it
    equals ``cmp[t]``.  ``old[t] == cmp[t]`` therefore tells thread ``t``
    whether *it* performed the store - the claim/visited-flag idiom of
    Rodinia BFS (``if (atomicCAS(&visited[n], 0, 1) == 0) ...``) - and the
    serialization makes the unordered CUDA primitive deterministic.

    Inactive threads pass ``idx >= arr.shape[0]`` (never stores) or a
    ``cmp`` that cannot match (e.g. ``-1`` against a 0/1 flag array).
    """
    cmp = jnp.broadcast_to(jnp.asarray(cmp), jnp.shape(idx))
    val = jnp.broadcast_to(jnp.asarray(val), jnp.shape(idx))
    return _serial_rmw(arr, idx,
                       lambda t, cur: jnp.where(cur == cmp[t], val[t], cur))


def atomic_exch(arr, idx, val):
    """``atomicExch``: returns ``(new_arr, old)``, serialized thread order.

    Every active thread stores its value; each observes what its
    predecessors left behind, and the last duplicate's value survives -
    a valid serialization of the unordered CUDA exchange, made
    deterministic.
    """
    val = jnp.broadcast_to(jnp.asarray(val), jnp.shape(idx))
    return _serial_rmw(arr, idx, lambda t, cur: val[t])


def atomic_cas_first(arr, idx, cmp, val):
    """compare-and-swap, first-wins across duplicate indices.

    For each position ``idx[t]``: if ``arr[idx[t]] == cmp[t]`` the value of
    the *lowest* t whose compare succeeds is stored.  Like
    :func:`atomic_cas` but returns only the updated array (legacy form).

    Indices outside ``[0, arr.shape[0])`` - negative or at/past the end -
    mark inactive threads and store nothing, matching :func:`_serial_rmw`:
    a bare ``arr[idx]`` gather or ``mode="drop"`` scatter would wrap a
    negative index onto ``arr[-1]`` and corrupt the last element.
    """
    idx = jnp.asarray(idx)
    n = arr.shape[0]
    active = (idx >= 0) & (idx < n)
    is_first = _first_occurrence(idx)
    old = arr[jnp.clip(idx, 0, n - 1)]
    ok = (old == cmp) & is_first & active
    safe_idx = jnp.where(ok, idx, n)                        # OOB drops
    return arr.at[safe_idx].set(jnp.where(ok, val, 0), mode="drop")
