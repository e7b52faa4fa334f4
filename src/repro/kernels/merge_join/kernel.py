"""Pallas TPU kernel: the merge-join verdict stage (injectivity filter).

A sort-merge join splits into two kinds of work.  The *irregular* part —
key sort, binary-search run bounds, run-length expansion — is
permutation/scatter shaped and belongs to XLA's native sort/gather
machinery (``ops.py`` runs it with ``jnp`` under one jit).  The *regular*
part is the per-pair verdict: after expansion every candidate assignment
is one row-aligned tile of int32 vertex ids, and the injectivity check

    keep[t] = ∀j  new[t, j] ∉ old[t, :]  ∧  ∀j<j'  new[t, j] ≠ new[t, j']

is an elementwise compare-reduce with zero cross-row traffic — the same
shape as the ``dominance_scan_pairs`` leaf verdict, so it streams through
VMEM the same way: (block_t, C) tiles, one pass, one (block_t, C)
compare per live new column (a (block_t, Cn, Co) intermediate at the
128-lane padded widths would be 128 MB, far past VMEM).

Column counts are tiny (≤ query size), so ops.py pads the last dim with
sentinels that cannot collide (old → −1, new column j → −(j+2)) rather
than tiling it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["injectivity_mask_kernel", "injectivity_mask_pallas"]


def injectivity_mask_kernel(old_ref, new_ref, out_ref, *, n_new: int):
    old = old_ref[...]  # (block_t, Co) int32
    new = new_ref[...]  # (block_t, Cn) int32
    lane = jax.lax.broadcasted_iota(jnp.int32, new.shape, 1)
    bad = jnp.zeros((new.shape[0],), bool)
    # one (block_t, C) compare per live new column: collisions with the
    # old columns, and duplicates among the new columns (j < j' only);
    # the lane padding past ``n_new`` holds sentinels and is never a probe
    for j in range(n_new):
        col = new[:, j : j + 1]
        bad |= jnp.any(col == old, axis=1)
        bad |= jnp.any((col == new) & (lane > j), axis=1)
    out_ref[...] = (~bad).astype(jnp.int32).reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("n_new", "block_t", "interpret"))
def injectivity_mask_pallas(
    old, new, *, n_new: int | None = None, block_t: int = 1024, interpret: bool = True
):
    """old (T, Co), new (T, Cn) int32 → (T,) int32 keep mask.

    ``n_new`` is the count of live new columns (default all ``Cn``); the
    rest are lane padding.  T must be a multiple of block_t and block_t of
    128 (ops.py pads + buckets); padded rows carry non-colliding sentinels
    and come back keep=1 — callers AND the result with their validity
    mask.  The verdicts leave lane-dense, as in ``dominance_scan``.
    """
    T, Co = old.shape
    Cn = new.shape[1]
    assert T % block_t == 0 and block_t % 128 == 0, (T, block_t)
    grid = (T // block_t,)
    return pl.pallas_call(
        functools.partial(injectivity_mask_kernel, n_new=Cn if n_new is None else n_new),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, Co), lambda i: (i, 0)),
            pl.BlockSpec((block_t, Cn), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t // 128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T // 128, 128), jnp.int32),
        interpret=interpret,
    )(old, new).reshape(T)
