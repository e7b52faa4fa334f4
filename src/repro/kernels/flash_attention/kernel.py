"""Pallas TPU kernel: flash attention forward (causal / sliding-window).

Tiled online-softmax attention: grid (batch·heads, q_blocks); each
program streams KV tiles through VMEM while a (block_q, dh) accumulator,
running max and running denominator stay resident.  The pure-jnp
``chunked_attention`` in models/transformer.py computes identical math
(it is the XLA fallback used by the dry-run); this kernel is the TPU
hot path for train/prefill shapes.

Block sizes are (128, 128) by default — MXU-aligned on both the q and
kv tile dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]


def flash_attention_kernel(
    q_ref, k_ref, v_ref, out_ref, *, block_q: int, block_k: int, seq_len: int,
    causal: bool, window: int | None, scale: float
):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, dh)
    m = jnp.full((block_q,), -1e30, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    n_k = seq_len // block_k

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot(p, v)
        return m_new, l_new, acc_new

    # causal: skip fully-masked KV tiles beyond the diagonal
    upper = n_k if not causal else (qi + 1) * block_q // block_k + (1 if block_q % block_k else 0)
    upper = min(upper, n_k) if isinstance(upper, int) else upper
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "causal", "window", "interpret")
)
def flash_attention_pallas(
    q, k, v, *, block_q: int = 128, block_k: int = 128, causal: bool = True,
    window: int | None = None, interpret: bool = True
):
    """q,k,v: (BH, S, dh) → (BH, S, dh).  S must divide by both blocks."""
    BH, S, dh = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = 1.0 / np.sqrt(dh)
    grid = (BH, S // block_q)
    return pl.pallas_call(
        functools.partial(
            flash_attention_kernel,
            block_q=block_q,
            block_k=block_k,
            seq_len=S,
            causal=causal,
            window=window,
            scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, dh), lambda b, i: (b, 0, 0)),  # full KV row in VMEM/ANY
            pl.BlockSpec((1, S, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        interpret=interpret,
    )(q, k, v)
