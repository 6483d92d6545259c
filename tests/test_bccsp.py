"""BCCSP provider tests.

The centerpiece is the differential gate (SURVEY §7 step 3): the tpu
provider must produce bit-identical accept/reject to the sw oracle over an
adversarial corpus (bad DER, high-S, out-of-range scalars, tampered
digests, wrong keys) — the reference's semantics at `bccsp/sw/ecdsa.go:41-57`.
"""

import functools
import hashlib
import os

import numpy as np
import pytest

from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature,
)

from fabric_tpu.bccsp import (
    AES256KeyGenOpts,
    ECDSAKeyGenOpts,
    VerifyItem,
    X509PublicKeyImportOpts,
)
from fabric_tpu.bccsp import factory, utils
from fabric_tpu.bccsp.keystore import FileKeyStore
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider, host_prep_scalars


class TestDERUtils:
    def test_roundtrip(self):
        for r, s in [(1, 1), (utils.P256_N - 1, utils.P256_HALF_N),
                     (0x80, 0x7F), (1 << 255, 1 << 200)]:
            der = utils.marshal_signature(r, s)
            assert utils.unmarshal_signature(der) == (r, s)

    def test_trailing_bytes_after_sequence_tolerated(self):
        # Go asn1.Unmarshal returns trailing data as `rest`; the
        # reference ignores it — parity requires acceptance.
        der = utils.marshal_signature(5, 7) + b"garbage"
        assert utils.unmarshal_signature(der) == (5, 7)

    @pytest.mark.parametrize("mutate", [
        lambda d: d[:-1],                      # truncated
        lambda d: b"\x31" + d[1:],             # wrong outer tag
        lambda d: d[:2] + b"\x03" + d[3:],     # wrong inner tag
        lambda d: d[:4] + b"\x00" + d[4:-1],   # non-minimal integer pad
        lambda d: b"",                         # empty
    ])
    def test_malformed_rejected(self, mutate):
        der = utils.marshal_signature(0x1234, 0x90FF)
        with pytest.raises(utils.SignatureFormatError):
            utils.unmarshal_signature(mutate(der))

    def test_nonpositive_rejected(self):
        # hand-encode r = 0 and a negative s
        zero_r = bytes.fromhex("30080202000002020001")
        with pytest.raises(utils.SignatureFormatError):
            utils.unmarshal_signature(zero_r)
        neg_s = bytes.fromhex("3006020101020181")   # s = -127
        with pytest.raises(utils.SignatureFormatError):
            utils.unmarshal_signature(neg_s)

    def test_low_s(self):
        assert utils.is_low_s(utils.P256_HALF_N)
        assert not utils.is_low_s(utils.P256_HALF_N + 1)
        assert utils.to_low_s(utils.P256_N - 5) == 5


class TestSWProvider:
    def test_sign_verify_roundtrip(self):
        csp = SWProvider()
        key = csp.key_gen(ECDSAKeyGenOpts(ephemeral=True))
        digest = csp.hash(b"the tx payload")
        sig = csp.sign(key, digest)
        # produced signatures are always low-S (reference signECDSA)
        _, s = utils.unmarshal_signature(sig)
        assert utils.is_low_s(s)
        assert csp.verify(key.public_key(), sig, digest)
        assert not csp.verify(key.public_key(), sig, csp.hash(b"other"))

    def test_keystore_roundtrip(self, tmp_path):
        ks = FileKeyStore(str(tmp_path))
        csp = SWProvider(ks)
        key = csp.key_gen(ECDSAKeyGenOpts())
        got = csp.get_key(key.ski())
        assert got.ski() == key.ski()
        assert got.private()

    def test_aes_roundtrip(self):
        csp = SWProvider()
        key = csp.key_gen(AES256KeyGenOpts(ephemeral=True))
        pt = b"private collection payload" * 3
        ct = csp.encrypt(key, pt)
        assert csp.decrypt(key, ct) == pt
        assert ct[16:] != pt

    def test_x509_import(self):
        from fabric_tpu.bccsp.bccsp import ECDSAPrivateKeyImportOpts
        from tests.certgen import make_self_signed
        cert, priv = make_self_signed("org1-admin")
        csp = SWProvider()
        pub = csp.key_import(cert, X509PublicKeyImportOpts())
        digest = csp.hash(b"msg")
        sig = csp.sign(csp.key_import(priv, ECDSAPrivateKeyImportOpts()),
                       digest)
        assert csp.verify(pub, sig, digest)


class TestFactory:
    def test_config_parse(self):
        opts = factory.FactoryOpts.from_config({
            "Default": "TPU",
            "SW": {"Hash": "SHA2", "Security": 256,
                   "FileKeyStore": {"KeyStore": "/tmp/ks"}},
            "TPU": {"MinBatch": 8, "MaxBlocks": 32},
        })
        assert opts.default == "TPU"
        assert opts.sw.keystore_path == "/tmp/ks"
        assert opts.tpu.min_batch == 8
        # flagship comb knobs default sanely: use_g16 auto (None); the
        # 4,000 MB table budget holds 13 of MaxKeys' 32 slots at 16-bit
        # windows as the chip lays them out (302 MB a key)
        assert opts.tpu.use_g16 is None
        assert opts.tpu.chunk == 32768
        assert opts.tpu.max_keys == 32
        assert opts.tpu.table_cache_bytes == 4000 << 20
        assert opts.tpu.table_cache_bytes // (16 * 65536 * 288) == 13

    def test_config_parse_comb_knobs(self):
        """UseG16/Chunk/MaxKeys/TableCacheMB reach the provider through
        new_bccsp — the measured configuration must be the shipped one
        (round-2 verdict: factory never plumbed use_g16)."""
        opts = factory.FactoryOpts.from_config({
            "Default": "TPU",
            "TPU": {"UseG16": True, "Chunk": 1024, "MaxKeys": 8,
                    "TableCacheMB": 512},
        })
        assert opts.tpu.use_g16 is True
        assert opts.tpu.chunk == 1024
        assert opts.tpu.max_keys == 8
        assert opts.tpu.table_cache_bytes == 512 << 20
        csp = factory.new_bccsp(opts)
        assert isinstance(csp, TPUProvider)
        assert csp._use_g16 is True
        assert csp._chunk == 1024
        assert csp._max_keys == 8
        assert csp._table_cache_bytes == 512 << 20

    def test_singleton(self):
        factory._reset_for_tests()
        a = factory.get_default()
        b = factory.get_default()
        assert a is b
        factory._reset_for_tests()


class TestKeyPoolDispatch:
    """The pool under the real dispatch path (round-2 advisor HIGH,
    as it reads today: a key's slot must not depend on where the key
    first appears in a batch — a later batch with another appearance
    order would comb every signature against the wrong key). Slot
    bookkeeping alone: tests/test_key_pool.py."""

    ROWS = 8        # a stub slab: the pool is a few hundred bytes

    @classmethod
    def _stubbed_provider(cls, monkeypatch, **kw):
        """TPUProvider with the table build and the jitted comb
        pipeline replaced by recorders, so the slot logic runs the
        real dispatch path (and the real pool write) without device
        math. A stub slab holds its key's first limb in every row."""
        import jax.numpy as jnp

        kw.setdefault("min_batch", 1)
        kw.setdefault("use_g16", False)
        tpu = TPUProvider(**kw)
        calls = {"builds": [], "key_idx": [], "pool": [], "ladder": 0}

        def fake_qtab_fn():
            def build(qx, qy):
                calls["builds"].append(int(np.asarray(qx)[0, 0]))
                return jnp.full((cls.ROWS, 3, 20), calls["builds"][-1],
                                dtype=jnp.int32)
            return build

        def fake_pipeline_digest():
            def run(key_idx, q_flat, g16, r8, rpn8, w8, premask,
                    digests):
                calls["key_idx"].append(np.asarray(key_idx).copy())
                calls["pool"].append(np.asarray(q_flat).copy())
                return np.asarray(premask)
            return run

        def fake_ladder():
            def run(blocks, nblocks, qx, qy, r, rpn, w, premask,
                    digests, has_digest):
                calls["ladder"] += 1
                return np.asarray(premask)
            return run

        monkeypatch.setattr(tpu, "_slab_rows", lambda: cls.ROWS)
        monkeypatch.setattr(tpu, "_qtab_fn", fake_qtab_fn)
        monkeypatch.setattr(tpu, "_comb_pipeline_digest",
                            fake_pipeline_digest)
        monkeypatch.setattr(tpu, "_pipeline", fake_ladder)
        return tpu, calls

    @staticmethod
    def _items(keys, order):
        """One VerifyItem per entry of `order` (indices into keys),
        signature irrelevant (stub pipeline returns premask)."""
        sw = SWProvider()
        out = []
        for i, ki in enumerate(order):
            m = f"m{i}".encode()
            sig = sw.sign(keys[ki], hashlib.sha256(m).digest())
            out.append(VerifyItem(key=keys[ki].public_key(), signature=sig,
                                  message=m))
        return out

    @staticmethod
    def _marker(key) -> int:
        """What the stub builder writes into `key`'s slab."""
        from fabric_tpu.ops import limb
        return int(limb.be_bytes_to_limbs(np.asarray(
            key.public_key().x_bytes(), np.uint8).reshape(1, 32))[0, 0])

    def test_a_key_keeps_its_slot_whatever_the_appearance_order(
            self, monkeypatch):
        keys = [SWProvider().key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(2)]
        tpu, calls = self._stubbed_provider(monkeypatch)
        # appearance order key0-first, then key1-first: the same keys
        tpu.verify_batch(self._items(keys, [0, 1, 0, 1]))
        tpu.verify_batch(self._items(keys, [1, 0, 1, 0]))
        # two slabs, built once each — the second batch HIT both
        assert len(calls["builds"]) == 2
        assert tpu.stats["key_slot_builds"] == 2
        assert tpu.stats["key_slot_hits"] == 2
        assert tpu.stats["key_slots_resident"] == 2
        ki1, ki2 = calls["key_idx"]
        slot = {0: ki1[0], 1: ki1[1]}          # batch-1 slot per key
        assert slot[0] != slot[1]
        assert ki1.tolist()[:4] == [slot[0], slot[1], slot[0], slot[1]]
        assert ki2.tolist()[:4] == [slot[1], slot[0], slot[1], slot[0]]

    def test_every_lane_indexes_its_own_keys_slab(self, monkeypatch):
        """What the kernel is handed: the rows a lane's slot selects
        in the pool are the rows built for that lane's key."""
        keys = [SWProvider().key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(5)]
        tpu, calls = self._stubbed_provider(monkeypatch)
        order = [3, 0, 4, 1, 2, 0, 3]
        tpu.verify_batch(self._items(keys, order))
        kidx, pool = calls["key_idx"][0], calls["pool"][0]
        assert pool.shape[0] == tpu._key_capacity() * self.ROWS
        for lane, ki in enumerate(order):
            rows = pool[kidx[lane] * self.ROWS:
                        (kidx[lane] + 1) * self.ROWS]
            assert (rows == self._marker(keys[ki])).all()

    def test_lru_eviction_by_key(self, monkeypatch):
        keys = [SWProvider().key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(3)]
        tpu, calls = self._stubbed_provider(monkeypatch, max_keys=2)
        tpu.verify_batch(self._items(keys, [0, 0]))
        tpu.verify_batch(self._items(keys, [1, 1]))
        tpu.verify_batch(self._items(keys, [0, 0]))      # hit -> MRU
        assert tpu.stats["key_slot_evictions"] == 0
        tpu.verify_batch(self._items(keys, [2, 2]))      # evicts LRU {1}
        assert tpu.stats["key_slot_evictions"] == 1
        assert tpu.stats["key_slots_resident"] == 2
        tpu.verify_batch(self._items(keys, [0, 0]))      # still there
        assert tpu.stats["key_slot_builds"] == 3
        tpu.verify_batch(self._items(keys, [1, 1]))      # {1} rebuilt
        assert tpu.stats["key_slot_builds"] == 4
        assert tpu.stats["key_slot_evictions"] == 2
        # the newest batch's lanes read key 1's rows
        kidx, pool = calls["key_idx"][-1], calls["pool"][-1]
        rows = pool[kidx[0] * self.ROWS:(kidx[0] + 1) * self.ROWS]
        assert (rows == self._marker(keys[1])).all()

    def test_two_batches_sharing_all_but_one_key_build_one_slab(
            self, monkeypatch):
        keys = [SWProvider().key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(25)]
        tpu, calls = self._stubbed_provider(monkeypatch)
        tpu.verify_batch(self._items(keys, list(range(24))))
        before = dict(tpu.stats)
        tpu.verify_batch(self._items(keys, list(range(24, -1, -1))))
        moved = _moved(tpu, before)
        assert moved["key_slot_lookups"] == 25
        assert moved["key_slot_hits"] == 24
        assert moved["key_slot_builds"] == 1
        assert "key_slot_evictions" not in moved
        assert calls["builds"][-1] == self._marker(keys[24])

    def test_a_budget_under_one_slab_goes_to_the_ladder(self,
                                                        monkeypatch):
        keys = [SWProvider().key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(2)]
        tpu, calls = self._stubbed_provider(monkeypatch,
                                            table_cache_bytes=8)
        assert tpu._key_capacity() == 0
        out = tpu.verify_batch(self._items(keys, [0, 1]))
        assert out == [True, True]   # stub premask passthrough
        assert tpu.stats["ladder_batches"] == 1 and calls["ladder"] == 1
        assert not calls["builds"] and tpu._pool is None


def _corpus():
    """(description, VerifyItem) pairs with a mix of valid/invalid."""
    sw = SWProvider()
    items = []
    keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(3)]

    def sign(key, msg):
        return sw.sign(key, hashlib.sha256(msg).digest())

    for i in range(4):
        k = keys[i % 3]
        m = f"valid payload {i}".encode() * (i + 1)
        items.append((True, VerifyItem(
            key=k.public_key(), signature=sign(k, m), message=m)))
    # digest mode
    m = b"digest-mode payload"
    items.append((True, VerifyItem(
        key=keys[0].public_key(), signature=sign(keys[0], m),
        digest=hashlib.sha256(m).digest())))
    # tampered message
    m = b"tampered"
    items.append((False, VerifyItem(
        key=keys[0].public_key(), signature=sign(keys[0], m),
        message=m + b"!")))
    # wrong key
    items.append((False, VerifyItem(
        key=keys[1].public_key(), signature=sign(keys[0], m), message=m)))
    # high-S: rewrite a valid signature into its high-S twin
    der = sign(keys[2], m)
    r, s = utils.unmarshal_signature(der)
    items.append((False, VerifyItem(
        key=keys[2].public_key(),
        signature=utils.marshal_signature(r, utils.P256_N - s), message=m)))
    # malformed DER
    items.append((False, VerifyItem(
        key=keys[0].public_key(), signature=der[:-2], message=m)))
    # trailing garbage after a valid signature -> still accepted
    items.append((True, VerifyItem(
        key=keys[2].public_key(), signature=der + b"\x00\x01", message=m)))
    # r >= n (encode r = n, s valid range)
    items.append((False, VerifyItem(
        key=keys[0].public_key(),
        signature=utils.marshal_signature(utils.P256_N, 5), message=m)))
    # long message (multi-block SHA path)
    big = os.urandom(500)
    items.append((True, VerifyItem(
        key=keys[1].public_key(), signature=sign(keys[1], big),
        message=big)))
    # empty message
    items.append((True, VerifyItem(
        key=keys[1].public_key(), signature=sign(keys[1], b""),
        message=b"")))
    return items


# ---------------------------------------------------------------------------
# one lane kind at a time, on the program that serves it
# ---------------------------------------------------------------------------

LANES = 16          # TPUProvider(min_batch=4)'s bucket for these batches
LANE_KINDS = ("message", "digest", "empty_message", "long_message",
              "tampered", "wrong_key", "high_s", "malformed_der",
              "trailing_der_bytes", "r_out_of_range", "short_digest",
              "non_p256")
# a prepared batch arrives hashed, 32 bytes a lane: its lanes are
# digest lanes, and the message kinds are native block prep's business
# upstream of the seam
PREPARED_KINDS = ("digest", "tampered", "wrong_key", "high_s",
                  "malformed_der", "trailing_der_bytes",
                  "r_out_of_range", "non_p256")
SW_LANE_KINDS = ("non_p256", "short_digest")    # verified lane by lane
FALLBACK_COUNTERS = ("sw_fallbacks", "host_hash_fallbacks",
                     "degraded_batches", "pairing_fallbacks",
                     "breaker_trips", "compile_failures")


POOL_SLOTS = 25     # slots of `lane_provider`'s pool


@functools.lru_cache(maxsize=None)
def _lane_keys():
    """26 P-256 keys (one more than `lane_provider`'s pool has slots:
    the wide consortium's 25 and one over) and one P-384 key."""
    from fabric_tpu.bccsp.bccsp import ECDSAPrivateKeyImportOpts
    sw = SWProvider()
    p256 = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
            for _ in range(POOL_SLOTS + 1)]
    p384 = sw.key_import(ec.generate_private_key(ec.SECP384R1()),
                         ECDSAPrivateKeyImportOpts())
    return p256, p384


def _kind_lane(kind, i, key_no, nkeys, second=False):
    """Lane `i` of a batch over `nkeys` P-256 keys, signed by key
    `key_no`, of one kind (of a kind's two lanes the second is the
    tampered P-384 one)."""
    sw = SWProvider()
    p256, p384 = _lane_keys()
    k = p256[key_no]
    pub = k.public_key()
    m = f"lane {i} of kind {kind}".encode()
    if kind == "empty_message":
        m = b""
    elif kind == "long_message":        # beyond MaxBlocks = 64 blocks
        m = bytes(range(256)) * 20 + m
    if kind == "non_p256":
        pub = p384.public_key()
        sig = sw.sign(p384, hashlib.sha256(m).digest())
        if second:
            m += b"!"
        return VerifyItem(key=pub, signature=sig, message=m)
    sig = sw.sign(k, hashlib.sha256(m).digest())
    if kind == "digest":
        return VerifyItem(key=pub, signature=sig,
                          digest=hashlib.sha256(m).digest())
    if kind == "short_digest":          # not a SHA-256 digest
        return VerifyItem(key=pub, signature=sig, digest=b"\x00" * 20)
    if kind == "tampered":
        m += b"!"
    elif kind == "wrong_key":
        pub = p256[(key_no + 1) % nkeys].public_key()
    elif kind == "high_s":
        r, s_ = utils.unmarshal_signature(sig)
        sig = utils.marshal_signature(r, utils.P256_N - s_)
    elif kind == "malformed_der":
        sig = sig[:-2]
    elif kind == "trailing_der_bytes":  # Go's asn1 ignores the rest
        sig += b"\x00\x01"
    elif kind == "r_out_of_range":
        sig = utils.marshal_signature(utils.P256_N, 5)
    return VerifyItem(key=pub, signature=sig, message=m)


def _kind_batch(kinds, lanes=LANES, nkeys=3):
    """`lanes` valid message lanes that between them use all `nkeys`
    keys, with two lanes of each of `kinds` among them; the sw
    provider's verdicts."""
    spots = {}
    for j, kind in enumerate(kinds):
        spots[1 + 2 * j] = (kind, False)
        spots[lanes - 2 - 2 * j] = (kind, True)
    assert len(spots) == 2 * len(kinds) and max(spots) < lanes
    assert lanes - len(spots) >= nkeys
    items, plain = [], 0
    for i in range(lanes):
        if i in spots:
            kind, second = spots[i]
            items.append(_kind_lane(kind, i, i % nkeys, nkeys, second))
        else:           # the plain lanes go round all the keys
            items.append(_kind_lane("message", i, plain % nkeys, nkeys))
            plain += 1
    want = SWProvider().verify_batch(items)
    assert any(want)
    return items, want


def _message_lanes(items):
    """Lanes the provider must hash itself: live P-256 message lanes."""
    return sum(1 for it in items
               if it.digest is None and it.key.is_p256()
               and host_prep_scalars(it.key, it.signature) is not None)


def _prepared_args(items):
    """`items` as native block prep hands them to `verify_prepared`:
    digests, the scalars of every signature that parses, lanes grouped
    by key."""
    n = len(items)
    der_ok = np.zeros(n, dtype=bool)
    r, rpn, w = (np.zeros((n, 32), dtype=np.uint8) for _ in range(3))
    keys, slot, key_idx = [], {}, np.zeros(n, dtype=np.int32)
    digests = np.zeros((n, 32), dtype=np.uint8)
    for i, it in enumerate(items):
        prep = (host_prep_scalars(it.key, it.signature)
                if it.key.is_p256() else None)
        if prep is not None:
            der_ok[i] = True
            r[i], rpn[i], w[i] = (np.frombuffer(b, np.uint8)
                                  for b in prep)
        if (it.key.x, it.key.y) not in slot:
            slot[it.key.x, it.key.y] = len(keys)
            keys.append(it.key)
        key_idx[i] = slot[it.key.x, it.key.y]
        digests[i] = np.frombuffer(
            it.digest or hashlib.sha256(it.message).digest(), np.uint8)
    return (digests, r, rpn, w, der_ok, key_idx, keys,
            lambda i: items[i].signature)


def _verify(prov, entry, items):
    if entry == "verify_prepared":
        return prov.verify_prepared(*_prepared_args(items))
    return prov.verify_batch(items)


def _moved(prov, before):
    return {k: prov.stats[k] - v for k, v in before.items()
            if isinstance(v, (int, float)) and prov.stats[k] != v}


@pytest.fixture(scope="module")
def lane_provider():
    """ONE provider for every lane-kind case: the program of a lane
    shape is traced and loaded once for the module, whatever the keys
    (8-bit windows on the CPU: a pool of 25 slots is 49 MB)."""
    return TPUProvider(min_batch=4, max_keys=POOL_SLOTS)


class TestDifferential:
    def test_tpu_matches_sw_bit_identical(self):
        expected_and_items = _corpus()
        items = [it for _, it in expected_and_items]
        expected = [e for e, _ in expected_and_items]
        sw = SWProvider()
        tpu = TPUProvider(min_batch=4)
        got_sw = sw.verify_batch(items)
        got_tpu = tpu.verify_batch(items)
        assert got_sw == expected
        assert got_tpu == got_sw

    def test_small_batch_uses_sw_fallback(self):
        tpu = TPUProvider(min_batch=1000)
        items = [it for _, it in _corpus()[:3]]
        assert tpu.verify_batch(items) == [True, True, True]

    def test_device_path_actually_runs(self):
        """The differential test is meaningless if the broad exception
        fallback silently routed everything to sw — pin the device path."""
        expected_and_items = _corpus()
        items = [it for _, it in expected_and_items]
        tpu = TPUProvider(min_batch=4)

        def boom(_items):
            raise AssertionError("sw fallback ran; device path failed")
        tpu._sw.verify_batch = boom
        assert tpu.verify_batch(items) == [e for e, _ in expected_and_items]

    def test_hash_on_host_and_device_hash_agree(self):
        """The default (host SHA-256 → digest lanes) and the
        device-SHA `comb` program (HashOnHost: false) must be bit-identical
        on a mixed valid/tampered/digest-lane batch — and both must run
        the device path, not the sw fallback."""
        expected_and_items = _corpus()
        items = [it for _, it in expected_and_items]
        expected = [e for e, _ in expected_and_items]
        host = TPUProvider(min_batch=4, hash_on_host=True)
        dev = TPUProvider(min_batch=4, hash_on_host=False)

        def boom(_items):
            raise AssertionError("sw fallback ran; device path failed")
        host._sw.verify_batch = boom
        dev._sw.verify_batch = boom
        got_host = host.verify_batch(items)
        got_dev = dev.verify_batch(items)
        assert got_host == expected
        assert got_dev == expected
        # prove the modes actually diverged in staging
        assert host.stats["host_hashed_lanes"] > 0
        assert dev.stats["host_hashed_lanes"] == 0

    def test_oversize_message_hashes_host_side_on_device_path(self):
        """A message beyond the SHA block budget (nb bucket = None) must
        be hashed host-side and the batch still verified on-device."""
        sw = SWProvider()
        keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(2)]
        huge = os.urandom(5000)   # > max_message_len(max_blocks=64) = 4087
        items = []
        expected = []
        for i in range(6):
            k = keys[i % 2]
            m = huge if i == 0 else f"small {i}".encode()
            sig = sw.sign(k, hashlib.sha256(m).digest())
            ok = i != 3
            if not ok:
                m = m + b"!"   # tamper one lane
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
            expected.append(ok)
        tpu = TPUProvider(min_batch=4)

        def boom(_items):
            raise AssertionError("sw fallback ran; device path failed")
        tpu._sw.verify_batch = boom
        assert tpu.verify_batch(items) == expected

    @pytest.mark.parametrize("entry, kind", [
        ("verify_batch", k) for k in LANE_KINDS] + [
        ("verify_prepared", k) for k in PREPARED_KINDS])
    def test_lane_kind_served_by_comb_digest(self, lane_provider, entry,
                                             kind):
        """Every kind of lane a batch can carry, through the entry that
        can carry it: the sw provider's verdict, from `comb_digest`
        after a host hash — no other program, no fallback rung."""
        prov = lane_provider
        items, want = _kind_batch((kind,))
        before = dict(prov.stats)
        events = len(prov.device_cost.events)
        assert _verify(prov, entry, items) == want
        moved = _moved(prov, before)
        assert moved.pop("comb_batches") == 1
        hashed = _message_lanes(items) if entry == "verify_batch" else 0
        assert moved.pop("host_hashed_lanes", 0) == hashed
        assert moved.pop("nonp256_sw_lanes", 0) == \
            (2 if kind in SW_LANE_KINDS else 0)
        assert not set(moved) & set(FALLBACK_COUNTERS), moved
        assert "ladder_batches" not in moved
        assert "pipeline_batches" not in moved  # one span: no overlap
        assert {e["kind"] for e in prov.device_cost.events[events:]} \
            <= {"qtab", "pool_write", "comb_digest"}

    KIND_SETS = [
        ("verify_batch", ("message", "digest", "empty_message",
                          "long_message")),
        ("verify_batch", ("tampered", "wrong_key", "high_s",
                          "malformed_der", "r_out_of_range",
                          "short_digest", "non_p256")),
        ("verify_prepared", ("digest", "tampered", "wrong_key", "high_s",
                             "malformed_der", "non_p256")),
    ]
    KIND_IDS = ["accept_kinds", "reject_kinds", "prepared"]

    @pytest.mark.parametrize("nkeys", [3, 17, 25])
    @pytest.mark.parametrize("entry, kinds", KIND_SETS, ids=KIND_IDS)
    def test_any_number_of_keys_the_pool_holds_served_by_comb_digest(
            self, lane_provider, entry, kinds, nkeys):
        """3, 17 and 25 distinct keys (the last a wide consortium's
        block): every tamper kind, both entries, the sw provider's
        verdicts — and the SAME compiled program: once the 64-lane
        shape is in, no batch moves the compile counters or names a
        program to the seam, whatever its keys."""
        prov = lane_provider
        warm, _ = _kind_batch(("digest",), lanes=64, nkeys=3)
        prov.verify_batch(warm)         # the 64-lane shape, once
        fn = prov._comb_pipeline_digest()
        items, want = _kind_batch(kinds, lanes=64, nkeys=nkeys)
        before = dict(prov.stats)
        events = len(prov.device_cost.events)
        assert _verify(prov, entry, items) == want
        moved = _moved(prov, before)
        assert moved.pop("comb_batches") == 1
        assert moved.pop("key_slot_lookups") == nkeys
        assert moved.get("key_slot_hits", 0) + \
            moved.get("key_slot_builds", 0) == nkeys
        assert "ladder_batches" not in moved
        assert not set(moved) & set(FALLBACK_COUNTERS), moved
        assert not [k for k in moved if k.startswith("compile_")], moved
        assert prov.device_cost.events[events:] == []
        assert prov._comb_pipeline_digest() is fn

    @pytest.mark.parametrize("entry, kinds", KIND_SETS, ids=KIND_IDS)
    def test_more_keys_than_slots_served_by_ladder(self, lane_provider,
                                                   entry, kinds):
        """The other side of the one choice: 26 distinct keys are one
        more than the pool has slots, and the batch goes to the
        ladder."""
        prov = lane_provider
        items, want = _kind_batch(kinds, lanes=64,
                                  nkeys=POOL_SLOTS + 1)
        assert len({(it.key.x, it.key.y) for it in items
                    if it.key.is_p256()}) == prov._key_capacity() + 1
        before = dict(prov.stats)
        assert _verify(prov, entry, items) == want
        moved = _moved(prov, before)
        assert moved.pop("ladder_batches") == 1
        assert "comb_batches" not in moved
        assert not set(moved) & set(FALLBACK_COUNTERS), moved

    def test_program_inventory(self, lane_provider):
        """What stops a sixth tier arriving unannounced: after
        prewarm() and one batch on each side of the pool's capacity,
        every program the compile seam has named — here and in every
        case above that ran on this provider — is one of the six a
        P-256 batch can need."""
        prov = lane_provider
        prov.prewarm(buckets=(LANES,), bounded=True)
        assert prov.stats["prewarm_done"] == 1
        for lanes, nkeys, counter in (
                (LANES, 3, "comb_batches"),
                (64, POOL_SLOTS + 1, "ladder_batches")):
            items, want = _kind_batch(("digest", "tampered"), lanes,
                                      nkeys)
            before = dict(prov.stats)
            assert prov.verify_batch(items) == want
            assert _moved(prov, before).get(counter) == 1
        kinds = {e["kind"] for e in prov.device_cost.events}
        assert {"qtab", "pool_write", "comb_digest", "ladder"} <= kinds
        assert kinds <= {"qtab", "qtab16", "pool_write", "comb_digest",
                         "comb", "ladder"}
        assert not [e for e in prov.device_cost.events if e["error"]]
        assert not [k for k in prov.stats if "fused" in k]
