"""The program the chip runs, compiled by the REAL TPU compiler for a
described v5e — no chip attached, nothing runs (tools/chip_compile.py
is the by-hand twin and supplies the program and its shapes).

A CPU run cannot see what the TPU compiler refuses (PR 23 found three
such refusals only this way). This compile guards the one P-256 program
every ledger line has measured — `comb_digest` against the key-table
pool at its shipped 13 slots, 16-bit windows on both bases, one
2,048-lane span — and the donating pool write, at no chip time: ~60 s
here, one core.

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
    for every block: a pool of 13 key slots, q16,
    SPAN_LANES_PER_DEVICE."""
    table = _chip_compile().programs(
        tpumod.SPAN_LANES_PER_DEVICE, 13, one_chip)
    fn, shapes = table["digest_q16"]()
    lowered = fn.lower(*shapes)
    assert "jit_comb_digest" in lowered.as_text()[:200]
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    # the resident 16-bit tables are the arguments' bulk: one for G
    # and one a key slot for Q, each as the chip lays it out — a
    # 20-limb coordinate in 24 words, which is what the provider
    # budgets (`_slab_bytes` on a TPU)
    table_bytes = comb.NWIN_G16 * comb.NENT_G16 * 3 * 24 * 4
    assert 14 * table_bytes <= ma.argument_size_in_bytes \
        < 14 * table_bytes + (8 << 20)
    assert ma.temp_size_in_bytes < 1 << 30


def test_pool_write_is_in_place(one_chip, no_cache):
    """The write of one slab into the 13-slot pool: the pool is donated
    and the output takes its place — no second 4 GB array, no
    temporary."""
    fn, shapes = _chip_compile().programs(
        tpumod.SPAN_LANES_PER_DEVICE, 13, one_chip)["pool_write"]()
    compiled = fn.lower(*shapes).compile()
    ma = compiled.memory_analysis()
    table_bytes = comb.NWIN_G16 * comb.NENT_G16 * 3 * limb.L * 4
    assert ma.output_size_in_bytes >= 13 * table_bytes
    assert ma.alias_size_in_bytes == ma.output_size_in_bytes
    assert ma.temp_size_in_bytes < table_bytes
