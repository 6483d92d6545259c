"""Fixed-base comb kernel tests (fabric_tpu/ops/comb.py).

Ground truth: the Python-int projective reference in ops/p256.py, itself
pinned against OpenSSL in test_p256.py.
"""

import hashlib
import os
import random

import numpy as np

import pytest

import jax
import jax.numpy as jnp

from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature,
)

from fabric_tpu.ops import comb, limb, p256

rng = random.Random(4242)


def _point(k: int):
    priv = ec.derive_private_key(k, ec.SECP256R1())
    nums = priv.public_key().public_numbers()
    return (nums.x, nums.y)


class TestGTables:
    def test_entries_match_int_reference(self):
        t = comb.g_tables()
        assert t.shape == (comb.NWIN * comb.NENT, 3, limb.L)
        for i, j in [(0, 0), (0, 1), (0, 255), (7, 3), (31, 17)]:
            got = tuple(limb.limbs_to_int(t[i * comb.NENT + j, c])
                        for c in range(3))
            k = (j << (comb.WBITS * i)) % p256.N
            want = p256.scalar_mul_int(k, (p256.GX, p256.GY, 1))
            assert (p256.to_affine_int(got) == p256.to_affine_int(want)), \
                (i, j)


class TestQTables:
    def test_entries_match_int_reference(self):
        ks = [5, 424242]
        pts = [_point(k) for k in ks]
        qx = jnp.asarray(limb.ints_to_limbs([p[0] for p in pts]))
        qy = jnp.asarray(limb.ints_to_limbs([p[1] for p in pts]))
        flat = np.asarray(jax.jit(comb.build_q_tables)(qx, qy))
        K = len(ks)
        assert flat.shape == (K * comb.NWIN * comb.NENT, 3, limb.L)
        for i, k_idx, j in [(0, 0, 0), (0, 1, 1), (3, 0, 2),
                            (31, 1, 255), (16, 0, 128)]:
            # key-major: a key's table is one contiguous slab
            row = (k_idx * comb.NWIN + i) * comb.NENT + j
            got = tuple(
                limb.limbs_to_int(
                    np.asarray(p256.FP.canonical(jnp.asarray(flat[row, c]))))
                for c in range(3))
            scalar = j << (comb.WBITS * i)
            want = p256.scalar_mul_int(
                scalar, (pts[k_idx][0], pts[k_idx][1], 1))
            assert (p256.to_affine_int(got) == p256.to_affine_int(want)), \
                (i, k_idx, j)


class TestCombDoubleScalarMul:
    def test_matches_generic_ladder(self):
        B, K = 6, 2
        key_pts = [_point(rng.randrange(1, p256.N)) for _ in range(K)]
        u1s = [rng.randrange(0, p256.N) for _ in range(B)]
        u2s = [rng.randrange(0, p256.N) for _ in range(B)]
        u1s[3] = 0                      # zero scalar: all-infinity windows
        u2s[4] = 0
        key_idx = [i % K for i in range(B)]

        u1 = jnp.asarray(limb.ints_to_limbs(u1s))
        u2 = jnp.asarray(limb.ints_to_limbs(u2s))
        qx = jnp.asarray(limb.ints_to_limbs([p[0] for p in key_pts]))
        qy = jnp.asarray(limb.ints_to_limbs([p[1] for p in key_pts]))

        def run(u1, u2, idx, qx, qy):
            g = jnp.asarray(comb.g_tables())
            q = comb.build_q_tables(qx, qy)
            return comb.comb_double_scalar_mul(u1, u2, idx, g, q)

        X, Y, Z = jax.jit(run)(
            u1, u2, jnp.asarray(key_idx, dtype=jnp.int32), qx, qy)
        for i in range(B):
            want = p256.cadd_int(
                p256.scalar_mul_int(u1s[i], (p256.GX, p256.GY, 1)),
                p256.scalar_mul_int(
                    u2s[i],
                    (key_pts[key_idx[i]][0], key_pts[key_idx[i]][1], 1)),
            )
            got = tuple(
                limb.limbs_to_int(np.asarray(p256.FP.canonical(v[i])))
                for v in (X, Y, Z))
            assert (p256.to_affine_int(got) ==
                    p256.to_affine_int(want)), f"lane {i}"


@pytest.mark.skipif(
    os.environ.get("FTPU_SLOW") != "1",
    reason="heavy differential; set FTPU_SLOW=1 (10+ min compile)")
class TestG16Windows:
    def test_g16_matches_generic_ladder(self):
        """16-bit G-side windows (48-point tree) agree with the int
        reference on R = u1*G + u2*Q."""
        B, K = 4, 2
        key_pts = [_point(rng.randrange(1, p256.N)) for _ in range(K)]
        u1s = [rng.randrange(0, p256.N) for _ in range(B)]
        u2s = [rng.randrange(0, p256.N) for _ in range(B)]
        u1s[1] = 0
        key_idx = [i % K for i in range(B)]
        u1 = jnp.asarray(limb.ints_to_limbs(u1s))
        u2 = jnp.asarray(limb.ints_to_limbs(u2s))
        qx = jnp.asarray(limb.ints_to_limbs([p[0] for p in key_pts]))
        qy = jnp.asarray(limb.ints_to_limbs([p[1] for p in key_pts]))
        g16 = comb.g16_tables()

        def run(u1, u2, idx, qx, qy, g16):
            q = comb.build_q_tables(qx, qy)
            return comb.comb_double_scalar_mul(
                u1, u2, idx, None, q, g16=g16)

        X, Y, Z = jax.jit(run)(
            u1, u2, jnp.asarray(key_idx, dtype=jnp.int32), qx, qy, g16)
        for i in range(B):
            want = p256.cadd_int(
                p256.scalar_mul_int(u1s[i], (p256.GX, p256.GY, 1)),
                p256.scalar_mul_int(
                    u2s[i],
                    (key_pts[key_idx[i]][0], key_pts[key_idx[i]][1], 1)))
            got = tuple(
                limb.limbs_to_int(np.asarray(p256.FP.canonical(v[i])))
                for v in (X, Y, Z))
            assert (p256.to_affine_int(got) ==
                    p256.to_affine_int(want)), f"lane {i}"


@pytest.mark.skipif(
    os.environ.get("FTPU_SLOW") != "1",
    reason="heavy differential; set FTPU_SLOW=1 (20+ min compile)")
class TestQ16Windows:
    def test_q16_matches_int_reference(self):
        """16-bit windows on BOTH sides (32-point tree)."""
        B, K = 3, 2
        key_pts = [_point(rng.randrange(1, p256.N)) for _ in range(K)]
        u1s = [rng.randrange(0, p256.N) for _ in range(B)]
        u2s = [rng.randrange(0, p256.N) for _ in range(B)]
        key_idx = [i % K for i in range(B)]
        u1 = jnp.asarray(limb.ints_to_limbs(u1s))
        u2 = jnp.asarray(limb.ints_to_limbs(u2s))
        qx = jnp.asarray(limb.ints_to_limbs([p[0] for p in key_pts]))
        qy = jnp.asarray(limb.ints_to_limbs([p[1] for p in key_pts]))
        g16 = comb.g16_tables()
        q8 = jax.jit(comb.build_q_tables)(qx, qy)
        q16 = jax.jit(comb.build_q16_tables)(q8)

        def run(u1, u2, idx, q16, g16):
            return comb.comb_double_scalar_mul(
                u1, u2, idx, None, q16, g16=g16, q16=True)

        X, Y, Z = jax.jit(run)(
            u1, u2, jnp.asarray(key_idx, dtype=jnp.int32), q16, g16)
        for i in range(B):
            want = p256.cadd_int(
                p256.scalar_mul_int(u1s[i], (p256.GX, p256.GY, 1)),
                p256.scalar_mul_int(
                    u2s[i],
                    (key_pts[key_idx[i]][0], key_pts[key_idx[i]][1], 1)))
            got = tuple(
                limb.limbs_to_int(np.asarray(p256.FP.canonical(v[i])))
                for v in (X, Y, Z))
            assert (p256.to_affine_int(got) ==
                    p256.to_affine_int(want)), f"lane {i}"


class TestCombVerifyCore:
    def test_valid_and_tampered(self):
        B, K = 8, 3
        privs = [ec.generate_private_key(ec.SECP256R1()) for _ in range(K)]
        key_pts = [p.public_key().public_numbers() for p in privs]
        msgs, sigs, key_idx = [], [], []
        for i in range(B):
            k = i % K
            msg = f"comb tx {i}".encode() * (i + 1)
            der = privs[k].sign(msg, ec.ECDSA(hashes.SHA256()))
            msgs.append(msg)
            sigs.append(decode_dss_signature(der))
            key_idx.append(k)
        # tamper: lane 5 message, lane 6 sig, lane 7 wrong key
        msgs[5] = msgs[5] + b"!"
        sigs[6] = (sigs[6][0], (sigs[6][1] * 3) % p256.N or 1)
        key_idx[7] = (key_idx[7] + 1) % K
        premask = np.ones((B,), dtype=bool)
        premask[4] = False              # host-side gate rejection

        words = np.zeros((B, 8), dtype=np.uint32)
        for i, m in enumerate(msgs):
            words[i] = np.frombuffer(hashlib.sha256(m).digest(), dtype=">u4")
        rs = [s[0] for s in sigs]
        ws = [pow(s[1], -1, p256.N) for s in sigs]
        rpn = [r + p256.N if r + p256.N < p256.P else r for r in rs]
        out = jax.jit(comb.comb_verify_core)(
            jnp.asarray(words),
            jnp.asarray(key_idx, dtype=jnp.int32),
            jnp.asarray(limb.ints_to_limbs([p.x for p in key_pts])),
            jnp.asarray(limb.ints_to_limbs([p.y for p in key_pts])),
            jnp.asarray(limb.ints_to_limbs(rs)),
            jnp.asarray(limb.ints_to_limbs(rpn)),
            jnp.asarray(limb.ints_to_limbs(ws)),
            jnp.asarray(premask),
        )
        assert np.asarray(out).tolist() == [
            True, True, True, True, False, False, False, False]


@pytest.mark.skipif(
    os.environ.get("FTPU_SLOW") != "1",
    reason="heavy differential; set FTPU_SLOW=1 (20+ min compile)")
class TestProvider16BitPath:
    def test_provider_g16_q16_matches_sw_and_caches(self):
        """TPUProvider(use_g16=True): the 32-point-tree product path
        agrees with the sw oracle and finds both keys' slabs in the
        pool on a second batch."""
        from fabric_tpu.bccsp import bccsp as api
        from fabric_tpu.bccsp.sw import SWProvider
        from fabric_tpu.bccsp.tpu import TPUProvider

        sw = SWProvider()
        tpu = TPUProvider(min_batch=1, use_g16=True)
        privs = [ec.generate_private_key(ec.SECP256R1())
                 for _ in range(2)]
        keys = [tpu.key_import(p.public_key(),
                               api.ECDSAPublicKeyImportOpts())
                for p in privs]

        def batch(tag):
            items = []
            for i in range(12):
                msg = f"{tag} {i}".encode() * 2
                sig = privs[i % 2].sign(msg, ec.ECDSA(hashes.SHA256()))
                if i % 4 == 3:
                    msg += b"!"
                items.append(api.VerifyItem(key=keys[i % 2],
                                            signature=sig, message=msg))
            return items

        b1 = batch("one")
        assert tpu.verify_batch(b1) == sw.verify_batch(b1)
        assert tpu.stats["key_slot_builds"] == 2
        b2 = batch("two")        # same keys: resident slabs reused
        assert tpu.verify_batch(b2) == sw.verify_batch(b2)
        assert (tpu.stats["key_slot_builds"],
                tpu.stats["key_slot_hits"]) == (2, 2)
        assert tpu.stats["comb_batches"] == 2
