"""Dominance-embedding training (paper Alg. 2) with a verified fallback.

Trains the GAT encoder on every (unit star, substructure) pair of a
partition with the hinge loss of Eq. (7) until the loss is *exactly*
zero (the paper overfits deliberately).  Differences from the paper,
both conservative:

* a small training margin ``δ`` inside the hinge (verify still checks
  the exact ``o(s) ⪯ o(g)``) — reaches exact zero in far fewer epochs;
* vertices whose pairs still violate after the epoch budget fall back to
  the all-ones embedding (the paper's own high-degree trick), so the
  no-false-dismissal guarantee never depends on optimizer luck.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..shapes import pad_rows, pow2_at_least
from .encoder import EncoderConfig, MonotoneEncoder, make_encoder
from .stars import PairDataset, StarTensors

__all__ = ["TrainConfig", "TrainResult", "train_dominance", "dominance_violations"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 2e-2
    margin: float = 0.03
    max_epochs: int = 600
    batch_size: int = 16384
    check_every: int = 25
    seed: int = 0


@dataclasses.dataclass
class TrainResult:
    params: dict
    epochs: int
    final_violations: int
    fallback_vertices: np.ndarray  # star indices forced to all-ones
    loss_history: list


def _pair_loss(encoder, params, stars_dev, pair_idx, pair_mask, margin):
    """Hinge dominance loss (Eq. 7) over a batch of (g, s) pairs."""
    c = stars_dev["center_labels"][pair_idx]
    ll = stars_dev["leaf_labels"][pair_idx]
    full_mask = stars_dev["leaf_mask"][pair_idx]
    o_g = encoder.embed_stars(params, c, ll, full_mask)
    o_s = encoder.embed_stars(params, c, ll, pair_mask & full_mask)
    viol = jnp.maximum(0.0, o_s - o_g + margin)
    return jnp.sum(viol * viol)


@partial(jax.jit, static_argnums=(0,))
def _adam_step(encoder, params, opt, stars_dev, pair_idx, pair_mask, lr, margin, t):
    loss, grads = jax.value_and_grad(
        lambda p: _pair_loss(encoder, p, stars_dev, pair_idx, pair_mask, margin)
    )(params)
    b1, b2, eps = 0.9, 0.999, 1e-8
    new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    new_v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    mh = jax.tree.map(lambda m: m / (1 - b1**t), new_m)
    vh = jax.tree.map(lambda v: v / (1 - b2**t), new_v)
    new_params = jax.tree.map(lambda p, m, v: p - lr * m / (jnp.sqrt(v) + eps), params, mh, vh)
    return new_params, {"m": new_m, "v": new_v}, loss


@partial(jax.jit, static_argnums=(0,))
def _exact_violation_mask(encoder, params, stars_dev, pair_idx, pair_mask):
    """Exact (margin-free) check of o(s) ⪯ o(g) per pair → bool (P,)."""
    c = stars_dev["center_labels"][pair_idx]
    ll = stars_dev["leaf_labels"][pair_idx]
    full_mask = stars_dev["leaf_mask"][pair_idx]
    o_g = encoder.embed_stars(params, c, ll, full_mask)
    o_s = encoder.embed_stars(params, c, ll, pair_mask & full_mask)
    return jnp.any(o_s > o_g, axis=-1)


def _stars_on_device(stars: StarTensors) -> dict:
    """The star arrays on the device, rows padded to a bucket (pairs only
    index real rows, so the padding is never read)."""
    n = pow2_at_least(stars.centers.shape[0])
    return {
        k: jnp.asarray(pad_rows(getattr(stars, k), n))
        for k in ("center_labels", "leaf_labels", "leaf_mask")
    }


# pairs per exact-check call: the TPU compile time of the check's star
# gathers grows with the gathered row count (~3 s at 8192 pairs, ~25 s at
# 65536 for a v5e), and a build compiles each chunk bucket once
VIOLATION_CHUNK = 8192


def dominance_violations(encoder, params, stars: StarTensors, pairs: PairDataset) -> np.ndarray:
    """Per-pair exact violation mask, computed in chunks padded to a
    power-of-two bucket and fetched together at the end."""
    stars_dev = _stars_on_device(stars)
    out, lens = [], []
    for lo in range(0, pairs.n_pairs, VIOLATION_CHUNK):
        idx = pairs.star_idx[lo : lo + VIOLATION_CHUNK]
        n = pow2_at_least(idx.shape[0], floor=1024)
        out.append(
            _exact_violation_mask(
                encoder,
                params,
                stars_dev,
                jnp.asarray(pad_rows(idx, n)),
                jnp.asarray(pad_rows(pairs.subset_mask[lo : lo + VIOLATION_CHUNK], n)),
            )
        )
        lens.append(idx.shape[0])
    if not out:
        return np.zeros((0,), bool)
    return np.concatenate([v[:k] for v, k in zip(jax.device_get(out), lens)])


def train_dominance(
    cfg: EncoderConfig,
    stars: StarTensors,
    pairs: PairDataset,
    tcfg: TrainConfig = TrainConfig(),
) -> TrainResult:
    """Alg. 2: epochs of Adam on Eq. (7) + exact testing epoch until L == 0."""
    encoder = make_encoder(cfg)
    key = jax.random.PRNGKey(tcfg.seed)
    params = encoder.init(key)
    if isinstance(encoder, MonotoneEncoder) or pairs.n_pairs == 0:
        # dominance holds by construction — nothing to train
        viol = dominance_violations(encoder, params, stars, pairs)
        assert not viol.any(), "monotone encoder must be violation-free"
        return TrainResult(params, 0, 0, np.zeros((0,), np.int32), [])

    stars_dev = _stars_on_device(stars)
    opt = {
        "m": jax.tree.map(jnp.zeros_like, params),
        "v": jax.tree.map(jnp.zeros_like, params),
    }
    P = pairs.n_pairs
    bs = min(tcfg.batch_size, P)
    rng = np.random.default_rng(tcfg.seed)
    loss_hist: list[float] = []
    t = 0
    epochs_run = 0
    for epoch in range(tcfg.max_epochs):
        epochs_run = epoch + 1
        perm = rng.permutation(P)
        epoch_loss = 0.0
        for lo in range(0, P, bs):
            sel = perm[lo : lo + bs]
            t += 1
            params, opt, loss = _adam_step(
                encoder,
                params,
                opt,
                stars_dev,
                jnp.asarray(pairs.star_idx[sel]),
                jnp.asarray(pairs.subset_mask[sel]),
                tcfg.lr,
                tcfg.margin,
                t,
            )
            epoch_loss += float(loss)
        loss_hist.append(epoch_loss)
        if epoch % tcfg.check_every == tcfg.check_every - 1 or epoch_loss == 0.0:
            viol = dominance_violations(encoder, params, stars, pairs)
            if not viol.any():
                return TrainResult(params, epochs_run, 0, np.zeros((0,), np.int32), loss_hist)
    # Budget exhausted: force the offending centers to all-ones (safe).
    viol = dominance_violations(encoder, params, stars, pairs)
    bad_stars = np.unique(pairs.star_idx[viol]).astype(np.int32)
    return TrainResult(params, epochs_run, int(viol.sum()), bad_stars, loss_hist)
