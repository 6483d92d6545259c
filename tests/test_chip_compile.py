"""The program the chip runs, compiled by the REAL TPU compiler for a
described v5e — no chip attached, nothing runs (tools/chip_compile.py
is the by-hand twin and supplies the program and its shapes).

A CPU run cannot see what the TPU compiler refuses (PR 23 found three
such refusals only this way). This compile guards the one P-256 program
every ledger line has measured — `comb_digest`, K = 4, 16-bit windows
on both bases, one 2,048-lane span — at no chip time: ~70 s here, one
core.

The topology is described inside a module-scoped fixture (one process
at a time may load libtpu; every xdist worker imports this file), with
the persistent compilation cache off around the compile (a
described-topology entry is written but can never be read back), in
the test's own process. Keep every such test in THIS file.
"""

import importlib.util
import os

import pytest

import jax

from fabric_tpu.bccsp import tpu as tpumod
from fabric_tpu.ops import comb, limb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _chip_compile():
    spec = importlib.util.spec_from_file_location(
        "chip_compile_under_test",
        os.path.join(ROOT, "tools", "chip_compile.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_comb_digest_span_compiles(one_chip, no_cache):
    """`jit_comb_digest` at the shape a one-chip provider dispatches
    for every block: K = 4 key slots, q16, SPAN_LANES_PER_DEVICE."""
    table = _chip_compile().programs(
        tpumod.SPAN_LANES_PER_DEVICE, 4, one_chip)
    fn, shapes = table["digest_q16"]()
    lowered = fn.lower(*shapes)
    assert "jit_comb_digest" in lowered.as_text()[:200]
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    # the resident 16-bit tables are the arguments' bulk: one for G
    # and one a key slot for Q (~252 MB each)
    table_bytes = comb.NWIN_G16 * comb.NENT_G16 * 3 * limb.L * 4
    assert ma.argument_size_in_bytes >= 5 * table_bytes
    assert ma.temp_size_in_bytes < 1 << 30
