"""Jit-composable device primitives for the sort-merge join.

Same family layout as ``dominance_scan``: ``kernel.py`` holds the Pallas
verdict kernel, ``ref.py`` the NumPy oracle, and this module the wrappers
the engine actually calls.  Unlike the scan wrappers these are *traceable
building blocks*, not entry points: ``core/matcher.py`` composes them
inside one jitted join step per (bucketed shape, column signature), so
sort → search → expand → filter → dedup fuse into a single XLA
computation and the assembled table never leaves the device between
steps.

Key representation: multi-word int32 keys (31 payload bits per word, see
ref.py) — this JAX build runs without x64, so the host join's uint64
lex-keys split across words while keeping word-lex order == row-lex
order.  All shapes are expected pre-padded/bucketed by the caller;
padded rows must carry out-of-range sentinel ids so they sort last and
never equal a live key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...shapes import lanes, pow2_at_least
from .kernel import injectivity_mask_pallas
from .ref import (
    dedup_mask_ref,
    expand_pairs_ref,
    injectivity_mask_ref,
    pack_words_ref,
    run_bounds_ref,
)

__all__ = [
    "key_words",
    "pack_words",
    "pack_words_ref",
    "lex_order",
    "run_bounds",
    "run_bounds_ref",
    "run_lookup",
    "expand_pairs",
    "expand_pairs_ref",
    "injectivity_mask",
    "injectivity_mask_ref",
    "dedup_mask",
    "dedup_mask_ref",
]


def key_words(n_cols: int, bits: int) -> int:
    """Words needed for an ``n_cols``-column key at ``bits`` bits/column."""
    return max((n_cols * bits + 30) // 31, 1)


def pack_words(rows, bits: int):
    """(R, C) int32 (non-negative, < 2**bits) → (R, K) int32 key words.

    Word-lex order == row-lex order and word equality == row equality
    (see ref.py for the bit layout).  Everything stays in int32: a
    column straddles at most one word boundary, and both fragments fit
    31 bits, so no intermediate ever needs the missing 64-bit lane.
    """
    if not (1 <= bits <= 31):
        raise ValueError(f"bits must be in [1, 31], got {bits}")
    R, C = rows.shape
    B = C * bits
    K = key_words(C, bits)
    pad = K * 31 - B
    words = [jnp.zeros((R,), jnp.int32) for _ in range(K)]
    for j in range(C):
        v = rows[:, j].astype(jnp.int32)
        start = pad + j * bits
        end = start + bits
        wa, wb = start // 31, (end - 1) // 31
        if wa == wb:
            words[wa] = words[wa] | (v << (31 * (wa + 1) - end))
        else:
            n_lo = end - 31 * wb
            words[wa] = words[wa] | (v >> n_lo)
            words[wb] = words[wb] | ((v & ((1 << n_lo) - 1)) << (31 * (wb + 1) - end))
    return jnp.stack(words, axis=1)


def lex_order(words):
    """Stable sort order of (R, K) key words (word 0 most significant)."""
    return jnp.lexsort(tuple(words[:, k] for k in range(words.shape[1] - 1, -1, -1)))


def _words_le(a, b):
    """Lexicographic a <= b for (..., K) word keys (unrolled over K)."""
    out = jnp.ones(a.shape[:-1], bool)
    for k in range(a.shape[-1] - 1, -1, -1):
        out = (a[..., k] < b[..., k]) | ((a[..., k] == b[..., k]) & out)
    return out


def _search_init(probe_words, n: int):
    """Initial [lo, hi) = [0, n) per probe row, derived from the probe
    operand so the binary-search carry varies over the same mesh axes
    as the loop body's output (``shard_map`` checks carry types)."""
    lo = jnp.zeros_like(probe_words[:, 0], dtype=jnp.int32)
    return lo, jnp.full_like(lo, n)


def run_bounds(sorted_words, probe_words):
    """For each probe key, the [lo, hi) run of equal keys in the sorted
    array — one vectorized binary search per side, ``ceil(log2 N)``
    fori steps of a K-word compare (no 64-bit scalar ever formed)."""
    n = sorted_words.shape[0]
    steps = max(int(n).bit_length(), 1)

    def search(strict_less):
        lo, hi = _search_init(probe_words, n)

        def body(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            mw = sorted_words[jnp.clip(mid, 0, n - 1)]
            # the clip re-reads sorted[n-1] once [lo, hi) collapses at the
            # array end — advance only while the interval is non-empty
            adv = strict_less(mw) & (lo < hi)
            return jnp.where(adv, mid + 1, lo), jnp.where(adv, hi, mid)

        lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
        return lo

    # side="left": advance while sorted[mid] < probe; "right": while <= probe
    left = search(lambda mw: ~_words_le(probe_words, mw))
    right = search(lambda mw: _words_le(mw, probe_words))
    return left, right


def run_lookup(sorted_words, probe_words):
    """Same contract as ``run_bounds`` (oracle: ``run_bounds_ref``) with
    HALF the search work: one left-side binary search per probe, then the
    run's right end reads off a precomputed run-end table (reverse cummin
    over the key-change boundaries).  Preferred on backends where gathers
    dominate (every search step gathers (M, K) words)."""
    n = sorted_words.shape[0]
    steps = max(int(n).bit_length(), 1)
    lo, hi = _search_init(probe_words, n)

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        mw = sorted_words[jnp.clip(mid, 0, n - 1)]
        adv = ~_words_le(probe_words, mw) & (lo < hi)
        return jnp.where(adv, mid + 1, lo), jnp.where(adv, hi, mid)

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    change = jnp.concatenate(
        [jnp.any(sorted_words[1:] != sorted_words[:-1], axis=1), jnp.ones((1,), bool)]
    )
    boundary = jnp.where(change, jnp.arange(n, dtype=jnp.int32), n)
    run_end = jax.lax.associative_scan(jnp.minimum, boundary, reverse=True) + 1
    loc = jnp.clip(lo, 0, n - 1)
    eq = (lo < n) & jnp.all(sorted_words[loc] == probe_words, axis=1)
    return lo, jnp.where(eq, run_end[loc], lo)


def expand_pairs(lo, hi, cap: int):
    """Run-length pair expansion to a static ``cap``: probe row r[i]
    pairs with sorted row c[i] for every c in [lo, hi); padded tail rows
    come back with valid=False.  The caller buckets ``cap`` to a power
    of two above the (host-synced) total so the jit cache stays small."""
    reps = (hi - lo).astype(jnp.int32)
    total = jnp.sum(reps)
    idx = jnp.arange(lo.shape[0], dtype=jnp.int32)
    r = jnp.repeat(idx, reps, total_repeat_length=cap)
    ends = jnp.cumsum(reps)
    starts_flat = jnp.repeat(ends - reps, reps, total_repeat_length=cap)
    pos = jnp.arange(cap, dtype=jnp.int32) - starts_flat
    c = jnp.repeat(lo.astype(jnp.int32), reps, total_repeat_length=cap) + pos
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    return r, c, valid


def injectivity_layout(T: int, co: int, cn: int, interpret: bool):
    """(Tp, Co_p, Cn_p, block_t) the Pallas verdict runs at.  Output tiles
    are lane-dense: ≥ 128 rows a block, and 8 × 128 whenever the grid has
    more than one block; 1024 rows of 128-lane compares fit the 16 MB of
    scoped VMEM (2048 do not).  The (tiny) column dims lane-pad on real
    TPUs only."""
    block_t = min(1024, pow2_at_least(T, 128))
    Tp = ((T + block_t - 1) // block_t) * block_t
    if interpret:
        return Tp, co, cn, block_t
    return Tp, lanes(co), lanes(cn), block_t


def injectivity_mask(old, new, use_pallas: bool = False, interpret: bool | None = None):
    """Row-aligned injectivity verdict (see kernel.py): keep[t] iff row
    t's new columns collide with nothing.  ``use_pallas`` routes through
    the Pallas kernel (interpret mode off-TPU); default is the jnp form,
    which XLA fuses into the surrounding join step."""
    if new.shape[1] == 0:
        return jnp.ones(old.shape[0], bool)
    if not use_pallas:
        ok = ~jnp.any(new[:, :, None] == old[:, None, :], axis=(1, 2))
        for j in range(new.shape[1]):
            for j2 in range(j + 1, new.shape[1]):
                ok &= new[:, j] != new[:, j2]
        return ok
    interpret = (jax.default_backend() != "tpu") if interpret is None else interpret
    T, cn = old.shape[0], new.shape[1]
    Tp, co_p, cn_p, block_t = injectivity_layout(T, old.shape[1], cn, interpret)
    # sentinels never collide: old pads/lanes −1, new column j pads −(j+2)
    oldp = jnp.pad(old, ((0, Tp - T), (0, 0)), constant_values=-1)
    fill = jnp.broadcast_to(
        -(jnp.arange(cn, dtype=jnp.int32)[None, :] + 2), (Tp - T, cn)
    )
    newp = jnp.concatenate([new.astype(jnp.int32), fill], axis=0)
    oldp = jnp.pad(oldp, ((0, 0), (0, co_p - old.shape[1])), constant_values=-1)
    lane_fill = jnp.broadcast_to(
        -(jnp.arange(cn, cn_p, dtype=jnp.int32)[None, :] + 2), (Tp, cn_p - cn)
    )
    newp = jnp.concatenate([newp, lane_fill], axis=1)
    mask = injectivity_mask_pallas(
        oldp, newp, n_new=cn, block_t=block_t, interpret=interpret
    )
    return mask[:T].astype(bool)


def dedup_mask(words, valid):
    """Row dedup over packed keys: stable order with invalid rows forced
    last, plus the first-occurrence keep mask aligned to that order —
    matcher composes it with a compaction argsort to rebuild the table."""
    keys = [words[:, k] for k in range(words.shape[1] - 1, -1, -1)]
    keys.append((~valid).astype(jnp.int32))  # primary: valid rows first
    order = jnp.lexsort(tuple(keys))
    ws = words[order]
    keep = valid[order]
    same = jnp.all(ws[1:] == ws[:-1], axis=1)
    keep = keep & jnp.concatenate([jnp.ones((1,), bool), ~same])
    return order.astype(jnp.int32), keep
