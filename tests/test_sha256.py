"""Differential tests: fabric_tpu.ops.sha256 vs hashlib."""

import hashlib
import random

import numpy as np
import pytest

from fabric_tpu.ops import sha256


def _ref(msg: bytes) -> np.ndarray:
    d = hashlib.sha256(msg).digest()
    return np.frombuffer(d, dtype=">u4").astype(np.uint32)


class TestSha256:
    def test_known_vectors(self):
        msgs = [b"", b"abc", b"a" * 55, b"a" * 56, b"a" * 64, b"a" * 119]
        got = sha256.sha256_host(msgs)
        for i, m in enumerate(msgs):
            assert (got[i] == _ref(m)).all(), f"mismatch for {m!r}"

    def test_random_lengths_mixed_bucket(self):
        rng = random.Random(7)
        msgs = [
            bytes(rng.randrange(256) for _ in range(rng.randrange(0, 300)))
            for _ in range(32)
        ]
        got = sha256.sha256_host(msgs)
        for i, m in enumerate(msgs):
            assert (got[i] == _ref(m)).all()

    def test_block_boundaries(self):
        # padding boundary cases: 55/56 force 1 vs 2 blocks, 119/120 2 vs 3
        msgs = [b"x" * k for k in (0, 1, 54, 55, 56, 63, 64, 118, 119, 120)]
        got = sha256.sha256_host(msgs)
        for i, m in enumerate(msgs):
            assert (got[i] == _ref(m)).all()

    def test_max_message_len(self):
        assert sha256.max_message_len(1) == 55
        assert sha256.max_message_len(2) == 119
        m = b"z" * sha256.max_message_len(3)
        got = sha256.sha256_host([m], nb=3)
        assert (got[0] == _ref(m)).all()

    def test_too_long_raises(self):
        with pytest.raises(ValueError):
            sha256.pack_messages([b"x" * 200], nb=2)


def _pack_reference(msgs, nb):
    """The pre-round-20 per-message pack, pinned verbatim: the
    vectorized `pack_messages` must stay byte-identical to THIS."""
    B = len(msgs)
    out = np.zeros((B, nb, 16), dtype=np.uint32)
    counts = np.zeros((B,), dtype=np.int32)
    for i, m in enumerate(msgs):
        if len(m) > sha256.max_message_len(nb):
            raise ValueError(f"message {i} too long for {nb} blocks")
        k = (len(m) + 9 + 63) // 64
        counts[i] = k
        padded = m + b"\x80" + b"\x00" * (k * 64 - len(m) - 9) \
            + (8 * len(m)).to_bytes(8, "big")
        words = np.frombuffer(padded, dtype=">u4").astype(np.uint32)
        out[i, :k, :] = words.reshape(k, 16)
    return out, counts


class TestPackMessages:
    def test_byte_identical_to_reference(self):
        rng = np.random.default_rng(7)
        for trial in range(9):
            nb = [1, 2, 4][trial % 3]
            B = int(rng.integers(1, 70))
            msgs = [rng.integers(0, 256, size=int(n),
                                 dtype=np.uint8).tobytes()
                    for n in rng.integers(
                        0, sha256.max_message_len(nb) + 1, size=B)]
            if B > 2:
                msgs[0] = b""                             # SHA("")
                msgs[1] = bytes(sha256.max_message_len(nb))  # max fit
            got = sha256.pack_messages(msgs, nb)
            want = _pack_reference(msgs, nb)
            assert (got[0] == want[0]).all()
            assert (got[1] == want[1]).all()
            assert got[0].dtype == np.uint32
            assert got[0].flags["C_CONTIGUOUS"]

    def test_empty_batch(self):
        blocks, counts = sha256.pack_messages([], 2)
        assert blocks.shape == (0, 2, 16) and counts.shape == (0,)

    def test_too_long_error_parity(self):
        msgs = [b"a", b"x" * 100]
        with pytest.raises(ValueError) as got:
            sha256.pack_messages(msgs, 1)
        with pytest.raises(ValueError) as want:
            _pack_reference(msgs, 1)
        assert str(got.value) == str(want.value)

    def test_digests_unchanged(self):
        msgs = [b"", b"abc", b"m" * 200, b"x" * sha256.max_message_len(2)]
        got = sha256.sha256_host(msgs, nb=4)
        for i, m in enumerate(msgs):
            want = np.frombuffer(hashlib.sha256(m).digest(), dtype=">u4")
            assert (got[i] == want).all()
