"""GPipe pipeline parallelism over a ``pipe`` mesh axis (DESIGN §5).

Stage ``k`` lives on mesh slot ``k``; microbatches stream left→right via
``ppermute`` shifts.  Tick ``t``: stage 0 injects microbatch ``t`` (while
any remain), every stage applies its params to whatever activation just
arrived, and the last stage banks the finished microbatch ``t-(P-1)``.
``M + P - 1`` ticks drain the schedule; the bubble is the usual
``(P-1)/(M+P-1)`` fraction.  Output is bit-equal to applying the stages
sequentially (no collectives touch the math — verified by
tests/test_pipeline_parallel.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn, stage_params, xs, mesh, axis: str = "pipe"):
    """Run ``stage_fn`` over ``n_stages`` pipeline stages.

    stage_fn: (params_k, x) → y, same shape as x.
    stage_params: tree whose leaves lead with the stage dim (n_stages, ...).
    xs: (M, B, D) microbatches.
    Returns (M, B, D) = stage_{P-1}(… stage_0(xs) …).
    """
    n_stages = int(mesh.shape[axis])
    M = int(xs.shape[0])
    shift = [(k, k + 1) for k in range(n_stages - 1)]

    def local(w, xs_rep):
        w0 = jax.tree.map(lambda a: a[0], w)  # this device's stage params
        idx = jax.lax.axis_index(axis)
        y0 = jnp.zeros(xs_rep.shape[1:], xs_rep.dtype)
        outs0 = jnp.zeros_like(xs_rep)

        def tick(carry, t):
            prev_y, outs = carry
            recv = jax.lax.ppermute(prev_y, axis, shift)
            x_in = jnp.where(idx == 0, xs_rep[jnp.clip(t, 0, M - 1)], recv)
            y = stage_fn(w0, x_in)
            # bank finished microbatch (meaningful on the last stage only;
            # other stages write too, but their outs are never read)
            oi = jnp.maximum(t - (n_stages - 1), 0)
            banked = jax.lax.dynamic_update_index_in_dim(outs, y, oi, 0)
            outs = jnp.where(t >= n_stages - 1, banked, outs)
            return (y, outs), None

        (_, outs), _ = jax.lax.scan(tick, (y0, outs0), jnp.arange(M + n_stages - 1))
        return outs[None]  # (1, M, B, D) per device → (P, M, B, D) global

    w_specs = jax.tree.map(lambda _: P(axis), stage_params)
    f = jax.shard_map(
        local, mesh=mesh, in_specs=(w_specs, P()), out_specs=P(axis), check_vma=False
    )
    return f(stage_params, xs)[-1]
