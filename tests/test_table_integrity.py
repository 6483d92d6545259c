"""Persisted-table integrity (ISSUE 2 satellite): sha256 sidecars.

Every *.npy the warm machinery writes carries a `<path>.sha256`
sidecar; a load whose bytes no longer match falls back to a REBUILD.
The failure mode this closes: the old loader only checked dtype and
byte COUNT, so same-size corruption (bit rot, a torn write that
survived rename) fed the verify kernel wrong curve points — silent
verdict flips. Builders are stubbed (test_key_pool idiom); the
G-table path runs its real 2-second host build.
"""

import os

import numpy as np
import pytest

from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.ops import comb

ROWS = 4


def _stub(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(TPUProvider, "_slab_rows", lambda self: ROWS)
    monkeypatch.setattr(
        TPUProvider, "_qtab_fn", lambda self: lambda qx, qy: jnp.arange(
            ROWS * 60, dtype=jnp.int32).reshape(ROWS, 3, 20))


_KEY = bytes([7]) * 64


def _admit(prov, kb=_KEY):
    with prov._pool_lock:
        prov._key_slots({kb: 0}, np.zeros(1, np.int32))


def _flip_one_payload_byte(path):
    """Same-size corruption: flip a byte in the npy payload (past the
    header) so the legacy dtype/nbytes checks still pass."""
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        last = f.read(1)[0]
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last ^ 0xFF]))


class TestSidecarHelpers:
    def test_roundtrip_and_mismatch(self, tmp_path):
        p = str(tmp_path / "t.npy")
        np.save(p, np.arange(16, dtype=np.int32))
        assert comb.verify_digest_sidecar(p) is None    # no sidecar yet
        comb.write_digest_sidecar(p)
        assert comb.verify_digest_sidecar(p) is True
        _flip_one_payload_byte(p)
        assert comb.verify_digest_sidecar(p) is False
        comb.drop_digest_sidecar(p)
        assert comb.verify_digest_sidecar(p) is None


class TestKeyTableIntegrity:
    def test_persist_writes_sidecar(self, monkeypatch, tmp_path):
        _stub(monkeypatch)
        warm = str(tmp_path / "warm")
        p1 = TPUProvider(use_g16=False, max_keys=3, warm_keys_dir=warm)
        _admit(p1)
        p1.flush_warm_tables()
        path = p1._slab_path(_KEY)
        assert os.path.exists(path)
        assert os.path.exists(path + ".sha256")
        assert comb.verify_digest_sidecar(path) is True

    def test_same_size_corruption_rebuilds(self, monkeypatch,
                                           tmp_path):
        _stub(monkeypatch)
        warm = str(tmp_path / "warm")
        p1 = TPUProvider(use_g16=False, max_keys=3, warm_keys_dir=warm)
        _admit(p1)
        p1.flush_warm_tables()
        path = p1._slab_path(_KEY)
        _flip_one_payload_byte(path)     # nbytes/dtype still "valid"

        p2 = TPUProvider(use_g16=False, max_keys=3, warm_keys_dir=warm)
        assert p2._restore_slabs() == 0          # corrupt bytes refused
        _admit(p2)
        assert p2.stats["key_slot_disk_loads"] == 0
        assert p2.stats["key_slot_builds"] == 1  # rebuilt instead
        p2.flush_warm_tables()                   # and written anew
        assert comb.verify_digest_sidecar(path) is True

    def test_eviction_removes_file_and_sidecar(self, monkeypatch,
                                               tmp_path):
        _stub(monkeypatch)
        warm = str(tmp_path / "warm")
        p1 = TPUProvider(use_g16=False, max_keys=1, warm_keys_dir=warm)
        _admit(p1)
        p1.flush_warm_tables()
        path = p1._slab_path(_KEY)
        assert os.path.exists(path + ".sha256")
        _admit(p1, bytes([8]) * 64)              # one slot: evicts _KEY
        p1.flush_warm_tables()
        assert not os.path.exists(path)
        assert not os.path.exists(path + ".sha256")
        assert os.path.exists(p1._slab_path(bytes([8]) * 64))


class TestGTableIntegrity:
    def test_corrupt_gtab_cache_rebuilds(self, tmp_path, monkeypatch):
        cache = str(tmp_path / "gtab8.npy")
        monkeypatch.setenv("FABRIC_TPU_GTAB_CACHE", cache)
        comb.g_tables.cache_clear()
        try:
            good = comb.g_tables()
            assert os.path.exists(cache + ".sha256")
            _flip_one_payload_byte(cache)
            comb.g_tables.cache_clear()
            again = comb.g_tables()      # detects mismatch, rebuilds
            assert np.array_equal(good, again)
            # the rebuild re-published consistent bytes + sidecar
            assert comb.verify_digest_sidecar(cache) is True
        finally:
            comb.g_tables.cache_clear()
