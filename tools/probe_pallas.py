"""Probe: Pallas VMEM tree kernel vs XLA fusion-island tree on real TPU.

Measures the comb verify pipeline (16/16-bit windows, 3 keys) on
device-resident operands, both tree implementations, plus compile
times. Not part of the test suite — a builder's measurement harness
(run on the chip: `python tools/probe_pallas.py`; the Pallas tree
alone takes ~16 min to compile — tools/chip_compile.py).
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(os.environ.get("PROBE_BATCH", "30720"))
NKEYS = 3
ITERS = int(os.environ.get("PROBE_ITERS", "5"))
TREES = os.environ.get("PROBE_TREES", "pallas,xla").split(",")
BLOCK_B = int(os.environ.get("PROBE_BLOCK_B", "512"))


def main():
    import jax
    import jax.numpy as jnp
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from fabric_tpu.common import jaxenv
    from fabric_tpu.ops import comb, limb, p256, ptree

    jaxenv.enable_compilation_cache()
    ptree.BLOCK_B = BLOCK_B
    rng = np.random.default_rng(99)

    keys = [ec.generate_private_key(ec.SECP256R1()) for _ in range(NKEYS)]
    pubs = [k.public_key().public_numbers() for k in keys]
    digests = rng.integers(0, 2**32, size=(BATCH, 8), dtype=np.uint32)
    # sign the digest bytes as prehashed messages
    from cryptography.hazmat.primitives.asymmetric.utils import Prehashed
    rs, ws, rpns = [], [], []
    for i in range(BATCH):
        d = digests[i].astype(">u4").tobytes()
        der = keys[i % NKEYS].sign(d, ec.ECDSA(Prehashed(hashes.SHA256())))
        r, s = decode_dss_signature(der)
        rs.append(r)
        ws.append(pow(s, -1, p256.N))
        rpns.append(r + p256.N if r + p256.N < p256.P else r)
    key_idx = (np.arange(BATCH, dtype=np.int32) % NKEYS)
    premask = np.ones(BATCH, dtype=bool)

    qx = jnp.asarray(limb.ints_to_limbs([p.x for p in pubs]))
    qy = jnp.asarray(limb.ints_to_limbs([p.y for p in pubs]))
    t0 = time.perf_counter()
    q8 = jax.jit(comb.build_q_tables)(qx, qy)
    q16 = jax.jit(comb.build_q16_tables, static_argnums=1)(q8, NKEYS)
    g16 = comb.g16_tables()
    jax.block_until_ready((q16, g16))
    print(f"table build: {time.perf_counter() - t0:.1f}s", flush=True)

    args = [jnp.asarray(a) for a in (
        digests, key_idx, limb.ints_to_limbs(rs), limb.ints_to_limbs(rpns),
        limb.ints_to_limbs(ws), premask)]
    jax.block_until_ready(args)
    dw, ki, r_l, rpn_l, w_l, pm = args

    for tree in TREES:
        fn = jax.jit(lambda dw, ki, r, rpn, w, pm, q, g:
                     comb.comb_verify_with_tables(
                         dw, ki, q, r, rpn, w, pm, g16=g, q16=True,
                         tree=tree))
        t0 = time.perf_counter()
        out = np.asarray(fn(dw, ki, r_l, rpn_l, w_l, pm, q16, g16))
        compile_s = time.perf_counter() - t0
        assert out.all(), f"{tree}: valid signatures rejected!"
        times = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            out = fn(dw, ki, r_l, rpn_l, w_l, pm, q16, g16)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        best = min(times)
        print(f"tree={tree:7s} compile={compile_s:7.1f}s "
              f"steady={best*1e3:8.1f}ms  {BATCH/best:9.0f} sigs/s "
              f"(times: {[round(t*1e3) for t in times]})", flush=True)


if __name__ == "__main__":
    main()
