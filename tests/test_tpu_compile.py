"""Compile the main-path Pallas kernels, the stacked probe's mask and
cell programs, and the query-side encoder program, for a described TPU
v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so tiling, layout and VMEM refusals surface here
instead of on the chip.  Each case compiles a kernel's jitted entry point
as its ops wrapper calls it: at the padded shapes and block size the
wrapper's layout helper picks for the engine's widths, with
``interpret=False``, as a program of its own.  Nothing runs: results are
held to the references by tests/test_kernels.py,
tests/test_device_join.py and tests/test_query_embed.py.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.dominance_scan.kernel import (
    dominance_scan_batch_pallas,
    dominance_scan_pairs_pallas,
    dominance_scan_pallas,
)
from repro.kernels.dominance_scan.ops import batch_layout, pairs_layout, scan_layout
from repro.kernels.merge_join.kernel import injectivity_mask_pallas
from repro.kernels.merge_join.ops import injectivity_layout

# l=2, d=2, n=2: dominance features (l+1)·d·(1+n) = 18, labels (l+1)·d = 6;
# the group level concatenates (q, q0, -q0) against a vacuous 1-wide label
PATH_WIDTHS = (18, 6)
GROUP_WIDTHS = (30, 1)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry compiled for a described chip cannot be read back).
    The topology is described first, so a skip leaves nothing changed."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs under /tmp
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _compile(kernel, sharding, shapes, dtype=jnp.float32, **statics):
    """Compile ``kernel`` for the described chip on abstract operands of
    ``shapes``; the Mosaic kernel must be in the program."""
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]
    compiled = kernel.lower(*args, interpret=False, **statics).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("widths", [PATH_WIDTHS, GROUP_WIDTHS], ids=["path", "group"])
@pytest.mark.parametrize("T", [300, 2048, 4096, 65536])
def test_dominance_scan_pairs_compiles(one_chip, T, widths):
    """Leaf (and group-MBR) pair scan: one block, and 2 and 32 blocks of 2048."""
    Tp, Dp, D0p, block_t = pairs_layout(T, *widths, block_t=2048, interpret=False)
    assert Tp // block_t == {300: 1, 2048: 1, 4096: 2, 65536: 32}[T]
    _compile(
        dominance_scan_pairs_pallas, one_chip,
        [(Tp, Dp), (Tp, D0p), (Tp, Dp), (Tp, D0p)], block_t=block_t,
    )


@pytest.mark.parametrize("N", [1000, 8192])
def test_dominance_scan_compiles(one_chip, N):
    Np, Dp, D0p, block_n = scan_layout(N, *PATH_WIDTHS, block_n=1024, interpret=False)
    _compile(
        dominance_scan_pallas, one_chip, [(Dp,), (D0p,), (Np, Dp), (Np, D0p)],
        block_n=block_n,
    )


@pytest.mark.parametrize("Q,N", [(5, 1000), (16, 8192)])
def test_dominance_scan_batch_compiles(one_chip, Q, N):
    Qp, Np, Dp, D0p = batch_layout(Q, N, *PATH_WIDTHS, block_q=8, block_n=512)
    _compile(
        dominance_scan_batch_pallas, one_chip, [(Qp, Dp), (Qp, D0p), (Np, Dp), (Np, D0p)],
        block_q=8, block_n=512,
    )


@pytest.mark.parametrize("T", [100, 8192])
def test_injectivity_mask_compiles(one_chip, T):
    """Join verdict: 5 bound columns against 3 new ones (an 8-vertex query)."""
    Tp, co_p, cn_p, block_t = injectivity_layout(T, 5, 3, interpret=False)
    _compile(
        injectivity_mask_pallas, one_chip, [(Tp, co_p), (Tp, cn_p)], dtype=jnp.int32,
        n_new=3, block_t=block_t,
    )


def test_stacked_probe_mask_and_cell_programs_compile(one_chip):
    """The stacked probe's mask program (with the per-slot funnel sums the
    host reads in its first wait) and its cell expansion, at the served
    cell's scale: 100 partition slots, queries bucketed to 8, ~320 leaf
    blocks of 8 groups each."""
    from repro.core import GnnPeConfig, GnnPeEngine
    from repro.graphs import erdos_renyi

    g = erdos_renyi(300, avg_degree=4, n_labels=5, seed=1)
    probe = GnnPeEngine(GnnPeConfig(
        n_partitions=2, encoder="monotone", n_multi=2, emb_dim=2, index_kind="grouped",
        group_size=16, block_size=128, seed=1,
    )).build(g).stacked_probe()
    st = probe.stacked
    S, Qp, n_lv = 100, 8, len(st.level_hi)
    widths = [320 // 16 ** (n_lv - 1 - i) or 1 for i in range(n_lv)]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    levels = tuple(
        tuple(sds((S, w, x.shape[2])) for w, x in zip(widths, arrs))
        for arrs in (st.level_hi, st.level_lo0, st.level_hi0)
    )
    G = widths[-1] * st.groups.gpb
    groups = (sds((S, G, st.groups.hi.shape[2])), sds((S, G, st.groups.lo0.shape[2])),
              sds((S, G, st.groups.hi0.shape[2])), sds((S, widths[-1]), jnp.int32))
    masks = probe._mask_fn(True, 1e-6).lower(
        levels, groups, sds((S, Qp, st.level_hi[0].shape[2])),
        sds((S, Qp, st.level_lo0[0].shape[2])),
    ).compile()
    assert masks.as_text().startswith("HloModule jit_slot_fn")
    cell_cap = 4096
    cells = probe._cells_fn(True, cell_cap).lower(
        sds((S, 1, G), jnp.bool_), 10, sds((S,), jnp.int32),
        sds((S, G), jnp.int32), sds((S, G), jnp.int32),
    )
    # the survivor compaction scatters nothing per mask cell (jnp.nonzero's
    # bincount did: one update per cell, serialised on the chip); the one
    # scatter left is the per-slot pair sum, one update per cell slot
    scatters = re.findall(
        r'"stablehlo\.scatter".*?\}\) : \(([^)]*)\) ->', cells.as_text(), re.S
    )
    updates = [re.findall(r"tensor<([^>]*)>", sig)[2] for sig in scatters]
    sizes = [math.prod(int(d) for d in u.split("x")[:-1]) for u in updates]
    assert cell_cap in sizes and S * G not in sizes, sizes
    assert cells.compile().as_text().startswith("HloModule jit_cells")


def test_query_embed_program_compiles(one_chip):
    """The query-side encoder program of one tick, at the served cell's
    scale: the monotone encoder over 15 labels, 100 partition models,
    n_multi 2, θ 10, one 8-row star bucket."""
    from repro.core.encoder import EncoderConfig, make_encoder
    from repro.core.engine import _embed_query_stars

    m, rows, theta, n_multi = 100, 8, 10, 2
    enc = make_encoder(EncoderConfig(n_labels=15, out_dim=2, theta=theta, kind="monotone"))

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds((m,) + x.shape, x.dtype), jax.eval_shape(enc.init, jax.random.key(0))
    )
    centers, leaves = sds((rows,), jnp.int32), sds((rows, theta), jnp.int32)
    lowered = _embed_query_stars.lower(
        enc, params, [params] * n_multi, centers, leaves, sds((rows, theta), jnp.bool_),
        [(centers, leaves)] * n_multi, sds((rows,), jnp.bool_),
    )
    assert lowered.out_info.shape == (2 + n_multi, m, rows, 2)
    assert lowered.compile().as_text().startswith("HloModule jit__embed_query_stars")
