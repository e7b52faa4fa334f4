"""Pallas TPU kernel: fused path label + dominance leaf scan (Lemmas 4.1+4.2).

The online hot loop of GNN-PE: for one query path embedding against a
block of candidate path embeddings, compute

    keep[i] = all_t(q[t] <= emb[i,t] + eps)        (dominance, Lemma 4.2)
            & all_t(|q0[t] - emb0[i,t]| <= eps)    (label equality, Lemma 4.1)

Multi-GNN embeddings are handled by *concatenating* them along the
feature dim before the call (dominance over the concat ≡ AND of per-GNN
dominance), so one kernel pass fuses every filter the paper applies.

Memory-bound: ~0.25 flop/byte — the BlockSpec streams (block_n, D) tiles
through VMEM at HBM bandwidth, one pass, no intermediate materialization.

Per-row verdicts leave the kernel lane-dense: a block of ``block`` rows
writes a (block // 128, 128) int32 tile of a (rows // 128, 128) output,
which the wrappers flatten.  The TPU compiler refuses (or aborts on) a
1-D int32 output block unless it happens to match XLA's own 1-D tiling,
so ``block`` must be a multiple of 128, and of 1024 whenever it is
smaller than the whole array (8 sublanes per output tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "dominance_scan_kernel",
    "dominance_scan_pallas",
    "dominance_scan_batch_kernel",
    "dominance_scan_batch_pallas",
    "dominance_scan_pairs_kernel",
    "dominance_scan_pairs_pallas",
]


def dominance_scan_kernel(q_ref, q0_ref, emb_ref, emb0_ref, out_ref, *, eps: float):
    emb = emb_ref[...]  # (block_n, D) VMEM tile
    emb0 = emb0_ref[...]
    q = q_ref[...]  # (1, D)
    q0 = q0_ref[...]
    dom = jnp.all(q <= emb + eps, axis=-1)
    lab = jnp.all(jnp.abs(emb0 - q0) <= eps, axis=-1)
    out_ref[...] = (dom & lab).astype(jnp.int32).reshape(out_ref.shape)


def dominance_scan_batch_kernel(q_ref, q0_ref, emb_ref, emb0_ref, out_ref, *, eps: float):
    """(block_q, D) query tile × (block_n, D) path tile → (block_q, block_n)."""
    q = q_ref[...]
    q0 = q0_ref[...]
    emb = emb_ref[...]
    emb0 = emb0_ref[...]
    dom = jnp.all(q[:, None, :] <= emb[None, :, :] + eps, axis=-1)
    lab = jnp.all(jnp.abs(emb0[None, :, :] - q0[:, None, :]) <= eps, axis=-1)
    out_ref[...] = (dom & lab).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "eps", "interpret")
)
def dominance_scan_batch_pallas(
    q, q0, emb, emb0, *, block_q: int = 8, block_n: int = 512,
    eps: float = 1e-6, interpret: bool = True,
):
    """Batched scan: Q query paths × N data paths in one fused pass.

    q: (Q, D), q0: (Q, D0); emb: (N, D), emb0: (N, D0) → (Q, N) int32.
    Q % block_q == 0 and N % block_n == 0 (ops.py pads + buckets).  The
    2D grid streams (block_q, D)×(block_n, D) tiles; the (bq, bn, D)
    compare intermediate stays in VMEM (~block_q·block_n·D·4 B — keep
    block_q·block_n ≲ 8K lanes at D ≤ 128).
    """
    Q, D = q.shape
    D0 = q0.shape[1]
    N = emb.shape[0]
    assert Q % block_q == 0 and N % block_n == 0, (Q, block_q, N, block_n)
    grid = (Q // block_q, N // block_n)
    return pl.pallas_call(
        functools.partial(dominance_scan_batch_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, D0), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_n, D), lambda qi, ni: (ni, 0)),
            pl.BlockSpec((block_n, D0), lambda qi, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.int32),
        interpret=interpret,
    )(q, q0, emb, emb0)


def dominance_scan_pairs_kernel(qg_ref, q0g_ref, eg_ref, e0g_ref, out_ref, *, eps: float):
    """Row-aligned tiles: pair t is (query qg[t] vs path eg[t]) → out[t]."""
    dom = jnp.all(qg_ref[...] <= eg_ref[...] + eps, axis=-1)
    lab = jnp.all(jnp.abs(e0g_ref[...] - q0g_ref[...]) <= eps, axis=-1)
    out_ref[...] = (dom & lab).astype(jnp.int32).reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_t", "eps", "interpret"))
def dominance_scan_pairs_pallas(
    qg, q0g, eg, e0g, *, block_t: int = 2048, eps: float = 1e-6, interpret: bool = True
):
    """Packed (query, path) pairs: qg,eg (T, D); q0g,e0g (T, D0) → (T,).

    The engine's work-proportional leaf scan: each query contributes only
    its OWN surviving leaf rows (gathered outside), so T = Σ_q rows_q —
    the same row count the per-query traversal touches, fused into one
    streaming pass.  The dense (Q, N) form above is the alternative when
    queries share most candidate rows.
    """
    T, D = qg.shape
    assert T % block_t == 0 and block_t % 128 == 0, (T, block_t)
    D0 = q0g.shape[1]
    grid = (T // block_t,)
    return pl.pallas_call(
        functools.partial(dominance_scan_pairs_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, D), lambda i: (i, 0)),
            pl.BlockSpec((block_t, D0), lambda i: (i, 0)),
            pl.BlockSpec((block_t, D), lambda i: (i, 0)),
            pl.BlockSpec((block_t, D0), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t // 128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T // 128, 128), jnp.int32),
        interpret=interpret,
    )(qg, q0g, eg, e0g).reshape(T)


@functools.partial(jax.jit, static_argnames=("block_n", "eps", "interpret"))
def dominance_scan_pallas(
    q, q0, emb, emb0, *, block_n: int = 1024, eps: float = 1e-6, interpret: bool = True
):
    """q: (D,), q0: (D0,); emb: (N, D), emb0: (N, D0) → keep mask (N,) int32.

    N must be a multiple of block_n; D and D0 lane multiples (ops.py pads).
    The dominance (D) and label (D0) widths are independent — path label
    embeddings are (l+1)·d while dominance embeddings concat the multi-GNNs.
    """
    N, D = emb.shape
    D0 = emb0.shape[1]
    assert N % block_n == 0 and block_n % 128 == 0, (N, block_n)
    grid = (N // block_n,)
    return pl.pallas_call(
        functools.partial(dominance_scan_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, D), lambda i: (0, 0)),  # query broadcast to every tile
            pl.BlockSpec((1, D0), lambda i: (0, 0)),
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),  # streamed path tiles
            pl.BlockSpec((block_n, D0), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n // 128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N // 128, 128), jnp.int32),
        interpret=interpret,
    )(q[None, :], q0[None, :], emb, emb0).reshape(N)
