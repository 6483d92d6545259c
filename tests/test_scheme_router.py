"""Multi-scheme device verify (ISSUE 8 tentpole): the scheme-dispatch
router, the Ed25519 batch path, BLS aggregate verify, faults and
observability.

The contract under test: `TPUProvider.verify_batch` partitions lanes
by scheme — P-256 to the existing comb/tree pipeline, Ed25519 to the
new batch kernel, BLS to the pairing path, everything else to sw —
and the combined bitmap is BIT-IDENTICAL to all-sw on mixed batches,
invalid signatures, padded non-dividing tails and RFC 8032 edge
vectors. Armed `tpu.ed25519` / `tpu.bls_aggregate` faults serve the
host path with identical verdicts, then the breaker re-enters.

Wheel-free via the recorder-stub idiom (tests/test_shard_verify.py):
the P-256 pipelines are premask recorders; the Ed25519 pipeline stub
REPLAYS the staged device operand rows through `ed25519_host` integer
math — so the staging (gates, challenge, row packing, padding,
scatter) is pinned end to end bit-exactly without the multi-minute
kernel compile, which the slow-marked test at the bottom covers for
real.
"""

import hashlib

import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, utils
from fabric_tpu.bccsp import ed25519_host as edh
from fabric_tpu.bccsp.bccsp import BLSKeyGenOpts, Ed25519KeyGenOpts
from fabric_tpu.bccsp.sw import (
    ECDSAPublicKey,
    SWProvider,
    bls_aggregate_signatures,
)
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import faults

_SW = SWProvider()
_P256 = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(2)]
_ED = [_SW.key_gen(Ed25519KeyGenOpts(ephemeral=True)) for _ in range(2)]
_BLS = _SW.key_gen(BLSKeyGenOpts(ephemeral=True))

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



class _NotP256(ECDSAPublicKey):
    """A P-256 key masquerading as an unknown curve: the device must
    route it to the per-lane sw path (where the math still verifies),
    exercising the ecdsa-other scheme lane on a wheel-free host."""

    def __init__(self, inner: ECDSAPublicKey):
        self._pub = inner._pub
        self.x, self.y = inner.x, inner.y
        self._xy_cache = None

    def is_p256(self) -> bool:
        return False


def _stubbed_provider(mesh=None, **kw):
    kw.setdefault("min_batch", 1)
    kw.setdefault("use_g16", False)
    kw.setdefault("pipeline_chunk", 0)
    tpu = TPUProvider(mesh=mesh, **kw)
    calls = {"p256_premask": [], "ed_premask": [], "ed_chunks": 0}

    def fake_qtab_fn():
        return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                           dtype=np.int32)

    def fake_pipeline_digest():
        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            calls["p256_premask"].append(np.asarray(premask).copy())
            return np.asarray(premask)
        return run

    def fake_ladder():
        def run(blocks, nblocks, qx, qy, r, rpn, w, premask, digests,
                has_digest):
            return np.asarray(premask)
        return run

    def fake_ed_pipeline():
        def run(tab, s8, k8, anx8, ay8, rx8, ry8, premask):
            # replay the STAGED rows through the host integer math:
            # verdicts depend on exactly what the provider packed, so
            # a staging bug (wrong row, wrong padding, wrong scatter)
            # flips a bit the parity assertions catch
            pm = np.asarray(premask).copy()
            calls["ed_premask"].append(pm)
            calls["ed_chunks"] += 1
            out = np.zeros(len(pm), dtype=bool)
            for i in range(len(pm)):
                if not pm[i]:
                    continue
                s, k, anx, ay, rx, ry = (
                    int.from_bytes(bytes(np.asarray(a)[i]), "big")
                    for a in (s8, k8, anx8, ay8, rx8, ry8))
                acc = edh.pt_add(
                    edh.scalar_mult(s, edh.from_affine(edh.BX,
                                                       edh.BY)),
                    edh.scalar_mult(k, edh.from_affine(anx, ay)))
                out[i] = edh.pt_equal(acc, edh.from_affine(rx, ry))
            return out
        return run

    tpu._slab_rows = lambda: SLAB_ROWS

    tpu._qtab_fn = fake_qtab_fn
    tpu._comb_pipeline_digest = fake_pipeline_digest
    tpu._pipeline = fake_ladder
    tpu._ed25519_pipeline = fake_ed_pipeline
    tpu._ed_table = lambda: np.zeros((1,), dtype=np.int32)
    return tpu, calls


def _mixed_corpus(n):
    """n lanes cycling P-256 / Ed25519 / BLS / ecdsa-other / invalid
    variants. Returns (items, expected) with expected == the sw-oracle
    bitmap."""
    items, expected = [], []
    for i in range(n):
        m = f"scheme lane {i}".encode()
        kind = i % 6
        if kind == 0:               # valid P-256
            k = _P256[i % 2]
            sig = _SW.sign(k, hashlib.sha256(m).digest())
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
            expected.append(True)
        elif kind == 1:             # Ed25519: valid / wrong-message
            k = _ED[i % 2]
            if i % 12 == 7:
                sig = _SW.sign(k, b"some other message")
                expected.append(False)
            else:
                sig = _SW.sign(k, m)
                expected.append(True)
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
        elif kind == 2:             # BLS per-lane (sw pairing path)
            sig = _SW.sign(_BLS, m)
            if i % 12 == 8:
                sig = _SW.sign(_BLS, m + b"!")
                expected.append(False)
            else:
                expected.append(True)
            items.append(VerifyItem(key=_BLS.public_key(),
                                    signature=sig, message=m))
        elif kind == 3:             # "unknown curve" -> sw lane
            k = _P256[0]
            sig = _SW.sign(k, hashlib.sha256(m).digest())
            items.append(VerifyItem(key=_NotP256(k.public_key()),
                                    signature=sig, message=m))
            expected.append(True)
        elif kind == 4:             # invalid P-256 (high-S, host gate)
            k = _P256[1]
            sig = _SW.sign(k, hashlib.sha256(m).digest())
            r, s = utils.unmarshal_signature(sig)
            items.append(VerifyItem(
                key=k.public_key(),
                signature=utils.marshal_signature(r, utils.P256_N - s),
                message=m))
            expected.append(False)
        else:                       # Ed25519 host-gate invalids
            k = _ED[0]
            sig = _SW.sign(k, m)
            s_int = int.from_bytes(sig[32:], "little")
            if i % 12 == 5 and s_int + edh.L < (1 << 256):
                sig = sig[:32] + (s_int + edh.L).to_bytes(32, "little")
            else:                   # non-canonical R encoding
                sig = edh.P.to_bytes(32, "little") + sig[32:]
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
            expected.append(False)
    return items, expected


class TestMixedSchemeRouting:
    def test_mixed_batch_bitmap_parity(self):
        """One verify_batch over all schemes at once: bitmap identical
        to all-sw, every lane routed, per-scheme accounting split."""
        faults.clear()
        tpu, calls = _stubbed_provider()
        items, expected = _mixed_corpus(96)
        out = tpu.verify_batch(items)
        assert out == _SW.verify_batch(items) == expected
        assert any(expected) and not all(expected)
        st = tpu.scheme_stats
        assert st["dispatches"].get("p256") == 1
        assert st["dispatches"].get("ed25519") == 1
        assert st["lanes"].get("bls") == 16
        assert st["sw_lanes"].get("bls") == 16
        # the fake-curve lanes took the consolidated sw-scatter helper
        assert st["sw_lanes"].get("ecdsa-other") == 16
        assert tpu.stats["nonp256_sw_lanes"] == 16
        assert tpu.stats["ed25519_batches"] == 1
        # total routed lanes == batch (no scheme silently dropped)
        assert sum(st["lanes"].values()) == 96

    def test_pure_p256_batch_keeps_legacy_path(self):
        """An all-P-256 batch must take the pre-router pipeline (the
        common case pays the router one list scan, nothing else)."""
        faults.clear()
        tpu, calls = _stubbed_provider()
        k = _P256[0]
        items = []
        for i in range(32):
            m = f"pure {i}".encode()
            items.append(VerifyItem(
                key=k.public_key(),
                signature=_SW.sign(k, hashlib.sha256(m).digest()),
                message=m))
        assert tpu.verify_batch(items) == [True] * 32
        assert tpu.stats["comb_batches"] == 1
        assert tpu.stats["ed25519_batches"] == 0
        assert tpu.scheme_stats["lanes"] == {"p256": 32}

    def test_ed25519_nondividing_tail_padded_dead(self):
        """70 Ed25519 lanes bucket to 128: the staged rows carry 58
        padded lanes whose premask is dead, and padding never leaks a
        verdict."""
        faults.clear()
        tpu, calls = _stubbed_provider(min_batch=16)
        k = _ED[0]
        items, expected = [], []
        for i in range(70):
            m = f"tail {i}".encode()
            sig = _SW.sign(k, m if i % 5 else b"wrong")
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
            expected.append(bool(i % 5))
        out = tpu.verify_batch(items)
        assert out == _SW.verify_batch(items) == expected
        pm = calls["ed_premask"][-1]
        assert len(pm) == 128
        assert not pm[70:].any()

    def test_small_ed25519_subbatch_rides_sw(self):
        """A mixed batch whose Ed25519 remainder is below MinBatch
        must not pay kernel-dispatch latency for 3 lanes."""
        faults.clear()
        tpu, calls = _stubbed_provider(min_batch=8)
        items, expected = [], []
        k = _P256[0]
        for i in range(16):
            m = f"p {i}".encode()
            items.append(VerifyItem(
                key=k.public_key(),
                signature=_SW.sign(k, hashlib.sha256(m).digest()),
                message=m))
            expected.append(True)
        for i in range(3):
            m = f"e {i}".encode()
            items.append(VerifyItem(key=_ED[0].public_key(),
                                    signature=_SW.sign(_ED[0], m),
                                    message=m))
            expected.append(True)
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["ed25519_batches"] == 0
        assert tpu.scheme_stats["sw_lanes"].get("ed25519") == 3

    def test_ed25519_disabled_serves_host_path(self):
        """BCCSP.TPU.Ed25519: false pins Ed25519 lanes to the host
        reference — verdicts identical, no device dispatch."""
        faults.clear()
        tpu, calls = _stubbed_provider(ed25519=False)
        items, expected = [], []
        for i in range(24):
            m = f"off {i}".encode()
            items.append(VerifyItem(key=_ED[0].public_key(),
                                    signature=_SW.sign(_ED[0], m),
                                    message=m))
            expected.append(True)
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["ed25519_batches"] == 0
        assert calls["ed_chunks"] == 0


class TestShardedSchemeRouting:
    @pytest.fixture(scope="class")
    def mesh8(self):
        import jax

        from fabric_tpu.parallel import batch_mesh
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh")
        return batch_mesh(8)

    def test_mixed_batch_sharded_parity(self, mesh8):
        """The router under a device mesh: the Ed25519 sub-batch's
        operand rows ride the round-robin span feeder (`_shard_put`)
        exactly like the P-256 operands, buckets stay mesh-aligned,
        and the combined bitmap matches the mesh-less provider and
        the sw oracle lane for lane."""
        faults.clear()
        sharded, calls8 = _stubbed_provider(mesh=mesh8)
        single, _ = _stubbed_provider()
        items, expected = _mixed_corpus(90)
        out8 = sharded.verify_batch(items)
        assert out8 == single.verify_batch(items) == expected
        # every staged ed25519 span divides the mesh
        assert all(len(p) % 8 == 0 for p in calls8["ed_premask"])
        assert sharded.stats["ed25519_batches"] == 1


class TestEd25519EdgeVectors:
    """RFC 8032 edge handling: the policy gates live in ONE place
    (`ed25519_host.prep_verify`), so host verify, the sw provider and
    the router path must agree lane for lane."""

    def _router_verdict(self, pub_raw, sig, msg):
        faults.clear()
        tpu, _ = _stubbed_provider(min_batch=1)
        from fabric_tpu.bccsp.sw import Ed25519PublicKey
        items = [VerifyItem(key=Ed25519PublicKey(pub_raw),
                            signature=sig, message=msg)] * 16
        out = tpu.verify_batch(items)
        assert len(set(out)) == 1
        return out[0]

    def test_rfc8032_vector_accepts(self):
        seed = bytes.fromhex(
            "9d61b19deffd5a60ba844af492ec2cc4"
            "4449c5697b326919703bac031cae7f60")
        pk = edh.public_from_seed(seed)
        assert pk.hex() == ("d75a980182b10ab7d54bfed3c964073a"
                            "0ee172f3daa62325af021a68f707511a")
        sig = edh.sign(seed, b"")
        assert edh.verify(pk, sig, b"")
        assert self._router_verdict(pk, sig, b"") is True

    def test_noncanonical_s_rejected_identically(self):
        seed = edh.generate_seed()
        pk = edh.public_from_seed(seed)
        sig = edh.sign(seed, b"msg")
        s = int.from_bytes(sig[32:], "little") + edh.L
        assert s < (1 << 256)
        bad = sig[:32] + s.to_bytes(32, "little")
        assert edh.verify(pk, bad, b"msg") is False
        assert self._router_verdict(pk, bad, b"msg") is False

    def test_noncanonical_point_encoding_rejected(self):
        seed = edh.generate_seed()
        pk = edh.public_from_seed(seed)
        sig = edh.sign(seed, b"msg")
        # R replaced by a y >= p encoding: host gate, dead lane
        bad = (edh.P + 1).to_bytes(32, "little") + sig[32:]
        assert edh.verify(pk, bad, b"msg") is False
        assert self._router_verdict(pk, bad, b"msg") is False

    def test_small_order_points_rejected_identically(self):
        seed = edh.generate_seed()
        sig = edh.sign(seed, b"msg")
        # the order-8 torsion component: A replaced by the order-2
        # point (0, -1), canonical encoding — decodes fine, rejected
        # by the small-order gate on host AND router paths
        small = edh.encode_point(0, edh.P - 1)
        assert edh.decode_point(small) is not None
        assert edh.verify(small, sig, b"msg") is False
        assert self._router_verdict(small, sig, b"msg") is False
        # and a small-order R with a valid A
        pk = edh.public_from_seed(seed)
        bad = small + sig[32:]
        assert edh.verify(pk, bad, b"msg") is False
        assert self._router_verdict(pk, bad, b"msg") is False


class TestSchemeFaults:
    def test_armed_ed25519_fault_falls_back_bit_identical(self):
        faults.clear()
        faults.arm("tpu.ed25519", mode="error", count=1)
        try:
            tpu, _ = _stubbed_provider(min_batch=1)
            items, expected = _mixed_corpus(48)
            assert tpu.verify_batch(items) == expected
            assert tpu.stats["sw_fallbacks"] == 1
            assert tpu.stats["ed25519_batches"] == 0
            # breaker re-entry: the next batch rides the kernel again
            assert tpu.verify_batch(items) == expected
            assert tpu.stats["ed25519_batches"] == 1
        finally:
            faults.clear()

    def test_armed_bls_aggregate_fault_falls_back_bit_identical(self):
        faults.clear()
        try:
            tpu, _ = _stubbed_provider()
            msgs = [f"blk {i}".encode() for i in range(4)]
            sigs = [_SW.sign(_BLS, m) for m in msgs]
            agg = bls_aggregate_signatures(sigs)
            keys = [_BLS.public_key()] * 4
            assert tpu.verify_aggregate(keys, msgs, agg) is True
            faults.arm("tpu.bls_aggregate", mode="error", count=2)
            assert tpu.verify_aggregate(keys, msgs, agg) is True
            bad = msgs[:3] + [b"forged"]
            assert tpu.verify_aggregate(keys, bad, agg) is False
        finally:
            faults.clear()


class TestAggregateVerify:
    def test_aggregate_accept_reject(self):
        faults.clear()
        tpu, _ = _stubbed_provider()
        other = _SW.key_gen(BLSKeyGenOpts(ephemeral=True))
        msgs = [b"m1", b"m2", b"m3"]
        sigs = [_SW.sign(_BLS, msgs[0]), _SW.sign(_BLS, msgs[1]),
                _SW.sign(other, msgs[2])]
        keys = [_BLS.public_key(), _BLS.public_key(),
                other.public_key()]
        agg = bls_aggregate_signatures(sigs)
        assert tpu.verify_aggregate(keys, msgs, agg) is True
        assert _SW.verify_aggregate(keys, msgs, agg) is True
        # tampered message / reordered keys / truncated set
        assert tpu.verify_aggregate(keys, [b"m1", b"mX", b"m3"],
                                    agg) is False
        assert tpu.verify_aggregate(list(reversed(keys)), msgs,
                                    agg) is False
        assert tpu.verify_aggregate(keys[:2], msgs[:2], agg) is False
        assert tpu.stats["bls_aggregate_checks"] >= 4

    def test_malformed_aggregate_signature_is_false(self):
        faults.clear()
        tpu, _ = _stubbed_provider()
        keys = [_BLS.public_key()]
        assert tpu.verify_aggregate(keys, [b"m"], b"\x01" * 96) is False
        assert tpu.verify_aggregate(keys, [b"m"], b"short") is False

    def test_non_bls_keys_raise(self):
        faults.clear()
        tpu, _ = _stubbed_provider()
        with pytest.raises(TypeError):
            tpu.verify_aggregate([_P256[0].public_key()], [b"m"],
                                 b"\x00" * 96)
        with pytest.raises(TypeError):
            _SW.verify_aggregate([_ED[0].public_key()], [b"m"],
                                 b"\x00" * 96)

    def test_admission_window_passes_aggregate_through(self):
        from fabric_tpu.bccsp.admission import AdmissionWindow
        faults.clear()
        tpu, _ = _stubbed_provider()
        win = AdmissionWindow.shared(tpu)
        msgs = [b"w1", b"w2"]
        agg = bls_aggregate_signatures(
            [_SW.sign(_BLS, m) for m in msgs])
        assert win.verify_aggregate([_BLS.public_key()] * 2, msgs,
                                    agg) is True


class TestBlockWriterAggregate:
    """The orderer consenter-identity wiring: a BLS cluster identity's
    span signatures verify as ONE aggregate pairing check before
    anything touches the store."""

    class _Store:
        def __init__(self):
            self.blocks = []

        def add_block(self, b):
            self.blocks.append(b)

        def get_block_by_number(self, n):
            return self.blocks[n]

    class _Signer:
        def __init__(self, key, tamper=False):
            self._key = key
            self._tamper = tamper

        def serialize(self):
            return b"bls-orderer"

        def sign(self, msg):
            return _SW.sign(self._key,
                            msg + (b"CORRUPT" if self._tamper else b""))

        def verify_item(self, msg, sig):
            return VerifyItem(key=self._key.public_key(),
                              signature=sig, message=msg)

    @staticmethod
    def _blocks(n):
        from fabric_tpu.protoutil import protoutil as pu
        out = []
        for i in range(n):
            b = pu.new_block(i, b"")
            b.data.data.append(f"tx {i}".encode())
            b.header.data_hash = pu.block_data_hash(b.data)
            out.append(b)
        return out

    def test_bls_span_aggregate_self_verify(self):
        from fabric_tpu.orderer.blockwriter import BlockWriter
        faults.clear()
        tpu, _ = _stubbed_provider()
        store = self._Store()
        bw = BlockWriter(store, self._Signer(_BLS), csp=tpu)
        bw.write_blocks(self._blocks(3))
        assert len(store.blocks) == 3
        # the span verified as ONE aggregate pairing check, not 3 lanes
        assert tpu.stats["bls_aggregate_checks"] == 1

    def test_corrupted_bls_signer_appends_nothing(self):
        from fabric_tpu.orderer.blockwriter import BlockWriter
        faults.clear()
        tpu, _ = _stubbed_provider()
        store = self._Store()
        bw = BlockWriter(store, self._Signer(_BLS, tamper=True),
                         csp=tpu)
        with pytest.raises(ValueError, match="refusing to append"):
            bw.write_blocks(self._blocks(2))
        assert not store.blocks


class TestSchemeObservability:
    def test_scheme_gauges_published(self):
        """bccsp_scheme_{lanes,sw_lanes,dispatches} render on /metrics
        with their canonical help text and a scheme label."""
        import time

        from fabric_tpu.common import metrics as m
        from fabric_tpu.common import profiling

        faults.clear()
        tpu, _ = _stubbed_provider()
        items, _ = _mixed_corpus(48)
        tpu.verify_batch(items)
        provider = m.PrometheusProvider()
        t = profiling.publish_provider_stats(provider, tpu,
                                             poll_s=0.01)
        assert t is not None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            text = provider.render()
            if 'bccsp_scheme_lanes{scheme="ed25519"}' in text:
                break
            time.sleep(0.02)
        text = provider.render()
        assert 'bccsp_scheme_lanes{scheme="p256"}' in text
        assert 'bccsp_scheme_lanes{scheme="ed25519"}' in text
        assert 'bccsp_scheme_sw_lanes{scheme="bls"}' in text
        assert 'bccsp_scheme_dispatches{scheme="ed25519"} 1' in text
        assert "scheme-dispatch router" in text


@pytest.mark.slow
class TestRealEd25519Kernel:
    def test_real_kernel_parity_vs_host_oracle(self):
        """Full provider, REAL MontMod comb+ladder kernel: verdicts
        bit-identical to the host oracle on a mixed valid/invalid
        batch. Minutes of XLA compile — slow suite only; tier-1
        covers the same staging with the host-math recorder."""
        faults.clear()
        tpu = TPUProvider(min_batch=4, use_g16=False,
                          pipeline_chunk=0)
        items, expected = [], []
        for i in range(8):
            m = f"real {i}".encode()
            sig = _SW.sign(_ED[0], m if i % 3 else b"wrong")
            items.append(VerifyItem(key=_ED[0].public_key(),
                                    signature=sig, message=m))
            expected.append(bool(i % 3))
        assert tpu.verify_batch(items) == expected == \
            _SW.verify_batch(items)
        assert tpu.stats["ed25519_batches"] == 1
