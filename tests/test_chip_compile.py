"""The Pallas kernels, compiled by the REAL TPU compiler for a described
v5e — no chip attached, nothing runs (tools/chip_compile.py is the
by-hand twin at full size).

Interpret mode (every other test of these kernels) cannot see what
Mosaic refuses: an unsupported op, a misaligned block, a bad memory
space. PR 23 found all three kinds only here — the fused SHA kernel
had passed every interpret-mode test since it was written and could
not lower at all. These compiles guard that at no chip time.

Only what compiles in seconds may live here (the suite runs out its
clock): the kernels at their real `BLOCK_B` lane width and real block
shapes, but the tree at 4 points (two levels, one of them re-packed to
full sublanes) instead of 32 — the 32-point tree alone takes ~16 min,
superlinear in its unrolled size. Fast-memory FIT at full size and the
whole jitted pipelines are checked by tools/chip_compile.py, not here.

The topology is described inside a module-scoped fixture (one process
at a time may load libtpu; every xdist worker imports this file), with
the persistent compilation cache off around the compiles (a
described-topology entry is written but can never be read back), in
the test's own process. Keep every such test in THIS file.
"""

import numpy as np
import pytest

import jax

from fabric_tpu.ops import fused_verify as fv, limb, ptree

L = limb.L
LANES = 2 * ptree.BLOCK_B       # a grid of 2: the DMA form prefetches


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 (any failure = no compiler)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    from jax.experimental.compilation_cache import (
        compilation_cache as cc,
    )
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not XLA
    return compiled


@pytest.mark.parametrize("dma", [False, True], ids=["plain", "dma"])
def test_fused_sha_kernel_compiles(one_chip, no_cache, dma):
    """ops/fused_verify.sha_windows, both forms, 16-bit windows, 4 SHA
    blocks per lane (the ~250-byte messages of a block's signatures)."""
    nb = 4
    _compile(
        lambda b, n, d, h, r, w: fv.sha_windows(
            b, n, d, h, r, w, wbits_g=16, wbits_q=16, interpret=False,
            dma=dma),
        one_chip,
        ((LANES, nb, 16), np.uint32), ((LANES,), np.int32),
        ((LANES, 8), np.uint32), ((LANES,), bool),
        ((LANES, L), np.int32), ((LANES, L), np.int32))


def test_tree_kernel_compiles(one_chip, no_cache):
    """ops/ptree.tree_verify_points at BLOCK_B lanes per program."""
    points = 4
    _compile(
        lambda p, r, rpn, pm: ptree.tree_verify_points(
            p, r, rpn, pm, interpret=False),
        one_chip,
        ((LANES, points, 3, L), np.int32), ((LANES, L), np.int32),
        ((LANES, L), np.int32), ((LANES,), bool))
