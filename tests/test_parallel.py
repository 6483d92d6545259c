"""Multi-chip sharding correctness: sharded == unsharded verify results.

The reference scales validation with a bounded goroutine pool
(`core/peer/peer.go:501`); the rebuild shards the signature-batch axis of
one XLA program over a `jax.sharding.Mesh` (SURVEY §2.10). These tests run
on the virtual 8-device CPU mesh forced by conftest.py and assert the
sharded program is bit-identical to the single-device one on a batch mixing
valid and tampered signatures.
"""

import hashlib

import jax
import numpy as np
import pytest
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

from fabric_tpu.ops import limb, p256, sha256
from fabric_tpu.ops import verify as verify_ops
from fabric_tpu.parallel import batch_mesh, shard_batch, sharded_verify_fn


def _signed_batch(batch):
    """(blocks, nblocks, qx, qy, r, rpn, w, premask) + expected accept mask.

    Even lanes carry valid signatures; every third lane is tampered so the
    expected mask is non-trivial.
    """
    msgs, keys, sigs, want = [], [], [], []
    for i in range(batch):
        priv = ec.generate_private_key(ec.SECP256R1())
        msg = f"tx payload {i}".encode() * (1 + i % 3)
        der = priv.sign(msg, ec.ECDSA(hashes.SHA256()))
        r, s = decode_dss_signature(der)
        nums = priv.public_key().public_numbers()
        if i % 3 == 2:
            msg = msg + b"!"  # digest mismatch -> reject
            want.append(False)
        else:
            want.append(True)
        msgs.append(msg)
        keys.append((nums.x, nums.y))
        sigs.append((r, s))
    blocks, nblocks = sha256.pack_messages(msgs, 2)
    qx = limb.ints_to_limbs([k[0] for k in keys])
    qy = limb.ints_to_limbs([k[1] for k in keys])
    rs = [sg[0] for sg in sigs]
    ws = [pow(sg[1], -1, p256.N) for sg in sigs]
    rpn = [r + p256.N if r + p256.N < p256.P else r for r in rs]
    args = (
        blocks,
        nblocks,
        qx,
        qy,
        limb.ints_to_limbs(rs),
        limb.ints_to_limbs(rpn),
        limb.ints_to_limbs(ws),
        np.ones((batch,), dtype=bool),
    )
    return args, np.asarray(want)


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh from conftest")
    return batch_mesh(8)


class TestShardedVerify:
    def test_sharded_matches_unsharded_and_expected(self, mesh8):
        args, want = _signed_batch(16)
        unsharded = np.asarray(jax.jit(verify_ops.verify_pipeline)(*args))
        dev_args = shard_batch(mesh8, *args)
        sharded = np.asarray(sharded_verify_fn(mesh8)(*dev_args))
        assert sharded.tolist() == unsharded.tolist()
        assert sharded.tolist() == want.tolist()

    def test_output_sharded_over_mesh(self, mesh8):
        args, _ = _signed_batch(8)
        out = sharded_verify_fn(mesh8)(*shard_batch(mesh8, *args))
        out.block_until_ready()
        # the result must actually live sharded across all 8 devices
        assert len({s.device for s in out.addressable_shards}) == 8

    def test_dryrun_in_process_on_cpu_mesh(self):
        import __graft_entry__ as graft

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh from conftest")
        graft._dryrun_in_process(8)


class TestShardedComb:
    def test_comb_sharded_matches_unsharded(self, mesh8):
        """The flagship comb kernel under batch sharding + replicated
        tables must be bit-identical to the single-device program."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from fabric_tpu.ops import comb
        from fabric_tpu.parallel import BATCH_AXIS, sharded_comb_fns

        B, K = 16, 2
        privs = [ec.generate_private_key(ec.SECP256R1())
                 for _ in range(K)]
        words = np.zeros((B, 8), dtype=np.uint32)
        rs, ws, rpns, key_idx, want = [], [], [], [], []
        for i in range(B):
            k = i % K
            msg = f"comb shard {i}".encode()
            der = privs[k].sign(msg, ec.ECDSA(hashes.SHA256()))
            r, s = decode_dss_signature(der)
            words[i] = np.frombuffer(
                hashlib.sha256(msg).digest(), dtype=">u4")
            if i % 3 == 2:
                r = (r * 5) % p256.N or 1     # tamper -> reject
                want.append(False)
            else:
                want.append(True)
            rs.append(r)
            ws.append(pow(s, -1, p256.N))
            rpns.append(r + p256.N if r + p256.N < p256.P else r)
            key_idx.append(k)
        nums = [p.public_key().public_numbers() for p in privs]
        qx = limb.ints_to_limbs([n.x for n in nums])
        qy = limb.ints_to_limbs([n.y for n in nums])
        args = (words, np.asarray(key_idx, np.int32),
                limb.ints_to_limbs(rs), limb.ints_to_limbs(rpns),
                limb.ints_to_limbs(ws), np.ones((B,), bool))

        def unsharded(words, kidx, r, rpn, w, premask):
            q = comb.build_q_tables(jnp.asarray(qx), jnp.asarray(qy))
            return comb.comb_verify_with_tables(
                words, kidx, q, r, rpn, w, premask)

        base = np.asarray(jax.jit(unsharded)(*args))

        mesh = batch_mesh(8)
        build, vfn = sharded_comb_fns(mesh)
        rep = NamedSharding(mesh, P())
        s_ = NamedSharding(mesh, P(BATCH_AXIS))
        q_flat = build(jax.device_put(qx, rep), jax.device_put(qy, rep))
        sharded = vfn(jax.device_put(args[0], s_),
                      jax.device_put(args[1], s_), q_flat,
                      *(jax.device_put(a, s_) for a in args[2:]))
        sharded = np.asarray(sharded)
        assert sharded.tolist() == base.tolist() == want

        # the provider's mesh layout (shard_map, per-shard comb
        # programs) must agree bit for bit too
        from fabric_tpu.parallel import shardmap_comb_verify
        smap = shardmap_comb_verify(mesh, q16=False)
        out = smap(jax.device_put(args[0], s_),
                   jax.device_put(args[1], s_), q_flat,
                   jax.device_put(
                       jnp.zeros((0, 3, limb.L), jnp.int32), rep),
                   *(jax.device_put(a, s_) for a in args[2:]))
        assert np.asarray(out).tolist() == want

    def test_shardmap_q16_gate_runs(self, mesh8):
        """The 16-bit-window (flagship) configuration compiles and
        executes under shard_map at full production table shapes —
        zero-filled tables (building real ones is the single-chip
        bench's multi-minute job), premask all False, so every lane
        must reject without touching table contents."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from fabric_tpu.ops import comb
        from fabric_tpu.parallel import BATCH_AXIS, shardmap_comb_verify

        B = 16
        rep = NamedSharding(mesh8, P())
        s_ = NamedSharding(mesh8, P(BATCH_AXIS))
        q16 = jax.device_put(
            jnp.zeros((comb.NWIN_G16 * comb.NENT_G16, 3, limb.L),
                      jnp.int32), rep)
        g16 = jax.device_put(
            jnp.zeros((comb.NWIN_G16 * comb.NENT_G16, 3, limb.L),
                      jnp.int32), rep)
        fn = shardmap_comb_verify(mesh8, q16=True)
        out = fn(jax.device_put(np.zeros((B, 8), np.uint32), s_),
                 jax.device_put(np.zeros(B, np.int32), s_), q16, g16,
                 *(jax.device_put(np.zeros((B, limb.L), np.int32), s_)
                   for _ in range(3)),
                 jax.device_put(np.zeros(B, bool), s_))
        assert np.asarray(out).tolist() == [False] * B

    def test_shardmap_q16_real_tables_match_oracle(self, mesh8):
        """Round-4 verdict #4: REAL 16-bit table contents sharded over
        8 devices must reproduce the oracle bits for a mixed
        valid/invalid batch — the zero-table gate above only proves
        compile+execute. Private scalar 1 makes Q == G, so the real
        8-bit Q table is the host G-table CONSTANT and the real
        16-bit table builds in ONE vectorized device pass (feasible
        on the CPU mesh; same builder, same layout as the provider's
        multi-minute production build)."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from fabric_tpu.ops import comb
        from fabric_tpu.parallel import BATCH_AXIS, shardmap_comb_verify

        priv = ec.derive_private_key(1, ec.SECP256R1())
        B = 16
        words = np.zeros((B, 8), np.uint32)
        rs, rpns, ws, premask, want = [], [], [], [], []
        for i in range(B):
            msg = f"q16 real lane {i}".encode()
            der = priv.sign(msg, ec.ECDSA(hashes.SHA256()))
            r, s = decode_dss_signature(der)
            words[i] = np.frombuffer(
                hashlib.sha256(msg).digest(), dtype=">u4")
            ok = True
            if i % 4 == 1:                      # tampered r
                r = (r * 7) % p256.N or 1
                ok = False
            elif i % 4 == 2:                    # tampered digest
                words[i] = np.frombuffer(
                    hashlib.sha256(b"swapped").digest(), dtype=">u4")
                ok = False
            pm = i % 4 != 3                     # parse-failed lane
            premask.append(pm)
            want.append(ok and pm)
            rs.append(r)
            ws.append(pow(s, -1, p256.N))
            rpns.append(r + p256.N if r + p256.N < p256.P else r)

        q8 = jnp.asarray(comb.g_tables())       # REAL table for Q == G
        q_flat = jax.jit(comb.build_q16_tables)(q8)
        g16 = comb.g16_tables()
        rep = NamedSharding(mesh8, P())
        s_ = NamedSharding(mesh8, P(BATCH_AXIS))
        fn = shardmap_comb_verify(mesh8, q16=True)
        out = fn(jax.device_put(words, s_),
                 jax.device_put(np.zeros(B, np.int32), s_),
                 jax.device_put(q_flat, rep),
                 jax.device_put(jnp.asarray(g16), rep),
                 jax.device_put(limb.ints_to_limbs(rs), s_),
                 jax.device_put(limb.ints_to_limbs(rpns), s_),
                 jax.device_put(limb.ints_to_limbs(ws), s_),
                 jax.device_put(np.asarray(premask), s_))
        out = np.asarray(out)
        assert out.tolist() == want
        assert any(want) and not all(want)

    def test_mesh_provider_verify_prepared(self, mesh8):
        """TPUProvider with a mesh: the prepared-array entry compiles
        the shard_map comb pipeline and matches the sw oracle."""
        from fabric_tpu.bccsp.sw import SWProvider
        from fabric_tpu.bccsp.tpu import TPUProvider
        from fabric_tpu.bccsp import utils as butils

        from fabric_tpu.bccsp.bccsp import ECDSAKeyGenOpts

        sw = SWProvider()
        prov = TPUProvider(min_batch=8, mesh=mesh8, use_g16=False,
                           max_keys=4)
        key = sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
        n = 16
        digests, r_a, rpn_a, w_a, ok_a, sigs = [], [], [], [], [], []
        for i in range(n):
            digest = hashlib.sha256(f"lane {i}".encode()).digest()
            sig = sw.sign(key, digest)
            if i % 4 == 3:
                sig = butils.marshal_signature(
                    1234567, butils.unmarshal_signature(sig)[1])
            sigs.append(sig)
            digests.append(np.frombuffer(digest, np.uint8))
            rr, ss = butils.unmarshal_signature(sig)
            r_a.append(np.frombuffer(rr.to_bytes(32, "big"), np.uint8))
            rpn = rr + p256.N if rr + p256.N < p256.P else rr
            rpn_a.append(np.frombuffer(rpn.to_bytes(32, "big"),
                                       np.uint8))
            w_a.append(np.frombuffer(
                pow(ss, -1, p256.N).to_bytes(32, "big"), np.uint8))
            ok_a.append(1)
        out = prov.verify_prepared(
            np.stack(digests), np.stack(r_a), np.stack(rpn_a),
            np.stack(w_a), np.asarray(ok_a, np.uint8),
            np.zeros(n, np.int32), [key], lambda i: sigs[i])
        want = [sw.verify(key, sigs[i],
                          bytes(digests[i].tobytes()))
                for i in range(n)]
        assert out == want
        assert want == [i % 4 != 3 for i in range(n)]
        assert prov.stats["comb_batches"] >= 1
