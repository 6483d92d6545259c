"""BucketFloor padding semantics (ISSUE 1 satellite).

`BCCSP.TPU.BucketFloor` pads modest device batches up to a fixed
bucket so they pin an already-AOT-compiled shape. Padded lanes are
PREMASKED — they must never flip a real lane's verdict, and a
floor-padded batch must be bit-identical to the unpadded result and
the sw oracle, including the all-invalid and single-key (K=1) corner
cases.

Device math uses the recorder-stub idiom (tests/test_bccsp.py
TestQ16TableCache): real staging — bucketing, premask assembly,
canonical key order — with the jitted kernel replaced by a premask
recorder, and a corpus whose verdicts are decided by host
pre-validation. The `slow`-marked test runs the same comparison
through the real compiled kernel.
"""

import hashlib

import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import faults

_SW = SWProvider()
_KEYS = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(2)]

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



def _stubbed_provider(monkeypatch, **kw):
    kw.setdefault("min_batch", 1)
    kw.setdefault("use_g16", False)
    tpu = TPUProvider(**kw)
    calls = {"premask": [], "key_idx": []}

    def fake_qtab_fn():
        return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                           dtype=np.int32)

    def fake_pipeline_digest():
        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            calls["premask"].append(np.asarray(premask).copy())
            calls["key_idx"].append(np.asarray(key_idx).copy())
            return np.asarray(premask)
        return run

    def fake_ladder():
        def run(blocks, nblocks, qx, qy, r, rpn, w, premask, digests,
                has_digest):
            calls["premask"].append(np.asarray(premask).copy())
            calls["key_idx"].append(
                np.zeros(len(np.asarray(premask)), dtype=np.int32))
            return np.asarray(premask)
        return run

    monkeypatch.setattr(tpu, "_slab_rows", lambda: SLAB_ROWS)

    monkeypatch.setattr(tpu, "_qtab_fn", fake_qtab_fn)
    monkeypatch.setattr(tpu, "_comb_pipeline_digest",
                        fake_pipeline_digest)
    # an all-dead batch has an empty key map and routes to the generic
    # ladder pipeline — stub that too (premask passthrough)
    monkeypatch.setattr(tpu, "_pipeline", fake_ladder)
    return tpu, calls


def _corpus(n, n_keys=2, all_invalid=False):
    """Premask-decided corpus: valid low-S signatures (True) and
    malformed-DER / high-S lanes (False)."""
    items, expected = [], []
    for i in range(n):
        k = _KEYS[i % n_keys]
        m = f"floor {i}".encode()
        sig = _SW.sign(k, hashlib.sha256(m).digest())
        if all_invalid or i % 3 == 2:
            r, s = utils.unmarshal_signature(sig)
            sig = (sig[:-2] if i % 2 else
                   utils.marshal_signature(r, utils.P256_N - s))
            expected.append(False)
        else:
            expected.append(True)
        items.append(VerifyItem(key=k.public_key(), signature=sig,
                                message=m))
    return items, expected


class TestBucketMath:
    def test_floor_pins_small_batches(self):
        tpu = TPUProvider(min_batch=16, bucket_floor=64)
        assert tpu._bucket(10) == 64
        assert tpu._bucket(64) == 64
        assert tpu._bucket(65) == 128      # beyond the floor: pow2
        tpu_nofloor = TPUProvider(min_batch=16)
        assert tpu_nofloor._bucket(10) == 16


class TestBucketFloorPadding:
    def test_padded_lanes_are_premasked_dead(self, monkeypatch):
        faults.clear()   # this test pins kernel internals, not fallback behavior
        tpu, calls = _stubbed_provider(monkeypatch, bucket_floor=64)
        items, expected = _corpus(10)
        out = tpu.verify_batch(items)
        assert out == expected == _SW.verify_batch(items)
        # the kernel saw the full floor bucket with every padded lane
        # premasked dead
        premask = calls["premask"][0]
        assert len(premask) == 64
        assert not premask[10:].any()

    def test_floor_matches_unpadded_lane_for_lane(self, monkeypatch):
        items, expected = _corpus(10)
        floored, _ = _stubbed_provider(monkeypatch, bucket_floor=64)
        plain, _ = _stubbed_provider(monkeypatch)
        assert floored.verify_batch(items) == \
            plain.verify_batch(items) == expected

    def test_all_invalid_batch(self, monkeypatch):
        faults.clear()   # this test pins kernel internals, not fallback behavior
        tpu, calls = _stubbed_provider(monkeypatch, bucket_floor=32)
        items, expected = _corpus(9, all_invalid=True)
        out = tpu.verify_batch(items)
        assert out == [False] * 9 == _SW.verify_batch(items)
        assert not calls["premask"][0].any()   # nothing reaches device

    def test_single_key_k1(self, monkeypatch):
        faults.clear()   # this test pins kernel internals, not fallback behavior
        tpu, calls = _stubbed_provider(monkeypatch, bucket_floor=32)
        items, expected = _corpus(7, n_keys=1)
        out = tpu.verify_batch(items)
        assert out == expected == _SW.verify_batch(items)
        # one distinct key: every live lane maps to slot 0
        assert not calls["key_idx"][0].any()

    def test_digest_lanes_under_floor(self, monkeypatch):
        """Digest-mode items (no message) through a floored bucket."""
        tpu, _ = _stubbed_provider(monkeypatch, bucket_floor=16)
        items, expected = [], []
        for i in range(5):
            k = _KEYS[i % 2]
            dg = hashlib.sha256(f"dg {i}".encode()).digest()
            sig = _SW.sign(k, dg)
            if i == 3:
                sig = sig[:-1]
                expected.append(False)
            else:
                expected.append(True)
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    digest=dg))
        assert tpu.verify_batch(items) == expected \
            == _SW.verify_batch(items)


@pytest.mark.slow
class TestBucketFloorRealKernel:
    def test_floor_padded_bit_identical_to_sw(self):
        """Real compiled kernel: floor padding is invisible next to the
        sw oracle, including lanes only curve math can reject."""
        sw = SWProvider()
        keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(2)]
        items, expected = [], []
        for i in range(10):
            k = keys[i % 2]
            m = f"real floor {i}".encode()
            sig = sw.sign(k, hashlib.sha256(m).digest())
            ok = i % 4 != 1
            if not ok:
                m += b"!"     # tampered: device math must reject
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
            expected.append(ok)
        tpu = TPUProvider(min_batch=1, bucket_floor=16)

        def boom(_items):
            raise AssertionError("sw fallback ran; device path failed")
        tpu._sw.verify_batch = boom
        assert tpu.verify_batch(items) == expected == \
            sw.verify_batch(items)
