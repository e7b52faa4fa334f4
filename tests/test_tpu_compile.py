"""Compile the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so tiling, layout and VMEM refusals surface here
instead of on the chip.  Each case compiles a kernel's jitted entry point
as its ops wrapper calls it: at the padded shapes and block size the
wrapper's layout helper picks for the engine's widths, with
``interpret=False``, as a program of its own.  Nothing runs: results are
held to the references by tests/test_kernels.py and
tests/test_device_join.py.
"""
from __future__ import annotations

import os

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
