"""Compile the P-256 block-validation programs for a DESCRIBED v5e.

No chip is needed: the TPU compiler installed beside JAX compiles for a
topology that is described, not attached (`JAX_PLATFORMS=cpu` stays
set). Nothing runs, so this says nothing about results or run time —
it answers two questions before any chip time is spent: does the
chip's compiler accept the program at its real shapes, and how many
seconds does it take. Prints one JSON line per program with the
compile seconds and `memory_analysis()`.

    python tools/chip_compile.py --list
    python tools/chip_compile.py digest_q16 qtab16
    python tools/chip_compile.py --lanes 2048 digest_q16

Run the programs one after another (one process describes the topology
at a time — libtpu's lock).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _provider():
    """A TPUProvider steered onto its TPU branches: on a described
    topology `jax.devices()` still answers CPU, so the script (not the
    program) pins what `_on_tpu()` would resolve to on the chip."""
    from fabric_tpu.bccsp import tpu as tpumod

    prov = tpumod.TPUProvider(use_g16=True)
    prov._on_tpu = lambda: True
    return prov


def programs(lanes: int, K: int, dev):
    """name -> (jitted fn, argument shapes). Shapes mirror what
    `TPUProvider._dispatch_comb_digest` stages for one span of `lanes`
    signatures against a key-table pool of K slots (the table builders
    work at one key whatever K)."""
    import numpy as np

    from fabric_tpu.ops import comb, limb

    L = limb.L
    i32, u8, u32 = np.int32, np.uint8, np.uint32
    s = lambda shape, dt: _sds(shape, dt, dev)     # noqa: E731
    ent16 = comb.NWIN_G16 * comb.NENT_G16
    ent8 = comb.NWIN * comb.NENT
    g16 = s((ent16, 3, L), i32)
    g0 = s((0, 3, L), i32)

    def digest(q16):
        prov = _provider()
        prov._use_g16 = q16
        fn = prov._comb_pipeline_digest()
        ent = ent16 if q16 else ent8
        return (fn, (s((lanes,), i32), s((ent * K, 3, L), i32),
                     g16 if q16 else g0, s((lanes, 32), u8),
                     s((lanes, 32), u8), s((lanes, 32), u8),
                     s((lanes,), bool), s((lanes, 8), u32)))

    def qtab():
        import jax
        return (jax.jit(comb.build_q_tables),
                (s((1, L), i32), s((1, L), i32)))

    def g16tab():
        import jax
        return (jax.jit(comb._combine_windows),
                (s((ent8, 3, L), i32), s((comb.NWIN_G16,), i32)))

    def qtab16():
        import jax
        return (jax.jit(comb.build_q16_tables),
                (s((ent8, 3, L), i32),))

    def pool_write():
        return (_provider()._pool_write_fn(),
                (s((ent16 * K, 3, L), i32), s((ent16, 3, L), i32),
                 s((), i32)))

    return {
        "digest_q16": lambda: digest(True),
        "digest_q8": lambda: digest(False),
        "g16": g16tab,
        "qtab8": qtab,
        "qtab16": qtab16,
        "pool_write": pool_write,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("names", nargs="*")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--lanes", type=int, default=32768)
    ap.add_argument("--keys", type=int, default=13,
                    help="slots of the key-table pool (13 = the "
                         "shipped TableCacheMB at 16-bit windows)")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # a described-topology compile is written to the persistent cache
    # but cannot be read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    dev = SingleDeviceSharding(topo.devices[0])
    table = programs(args.lanes, args.keys, dev)
    if args.list:
        print("\n".join(table))
        return 0
    rc = 0
    for name in args.names or list(table):
        row = {"program": name, "lanes": args.lanes, "K": args.keys,
               "device_kind": topo.devices[0].device_kind}
        try:
            fn, shapes = table[name]()
            t0 = time.perf_counter()
            lowered = fn.lower(*shapes)
            row["lower_s"] = round(time.perf_counter() - t0, 1)
            t0 = time.perf_counter()
            compiled = lowered.compile()
            row["compile_s"] = round(time.perf_counter() - t0, 1)
            ma = compiled.memory_analysis()
            row["memory"] = {k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
            row["ok"] = True
        except Exception as e:        # the compiler's refusal IS the result
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:2000]
            rc = 1
        print(json.dumps(row), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
