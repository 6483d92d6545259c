"""Warm-restart wiring: Q-table key-set persistence → prewarm rebuild.

Round-3 verdict: the warm-keys machinery existed but was unreachable
(no `WarmKeysDir` in the factory, `prewarm()` never called
`_prewarm_tables()`). These tests pin the WIRING end to end — config →
factory → provider, build → persist, fresh provider → prewarm →
cache hit — with the table builders stubbed (the real 16-bit comb
build is a multi-minute device job, not a unit concern).
"""

import json
import os

import numpy as np

from fabric_tpu.bccsp import factory
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.ops import limb


def _limbs(kb: bytes):
    qk = np.frombuffer(kb, dtype=np.uint8).reshape(1, 64).copy()
    return (limb.be_bytes_to_limbs(qk[:, :32]),
            limb.be_bytes_to_limbs(qk[:, 32:]))


def _stub_builders(monkeypatch, builds):
    import jax.numpy as jnp

    def fake_qtab_fn(self, K):
        return lambda qx, qy: jnp.zeros((2, 3, 20), jnp.int32)

    def fake_q16_fn(self, K):
        def build(q8, k):
            builds.append(k)
            return jnp.zeros((4, 3, 20), jnp.int32)
        return build

    monkeypatch.setattr(TPUProvider, "_qtab_fn", fake_qtab_fn)
    monkeypatch.setattr(TPUProvider, "_q16_fn", fake_q16_fn)


def test_factory_passes_warm_keys_dir(tmp_path):
    warm = str(tmp_path / "warm")
    opts = factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"WarmKeysDir": warm}})
    assert opts.tpu.warm_keys_dir == warm
    prov = factory.new_bccsp(opts)
    assert prov._warm_keys_dir == warm
    # unset stays disabled
    assert factory.FactoryOpts.from_config(
        {"Default": "TPU"}).tpu.warm_keys_dir is None


def test_build_persists_and_fresh_provider_prewarms(tmp_path,
                                                    monkeypatch):
    builds: list = []
    _stub_builders(monkeypatch, builds)
    warm = str(tmp_path / "warm")
    kb = bytes(range(64))

    prov = TPUProvider(warm_keys_dir=warm, use_g16=True)
    qx, qy = _limbs(kb)
    assert prov._q16_cached((kb,), 1, qx, qy) is not None
    assert prov.stats["q16_builds"] == 1
    # table bytes land asynchronously; prewarm only restores sets
    # whose bytes exist on disk (stub bytes fail the size check, so
    # the fresh provider below exercises the REBUILD fallback)
    prov.flush_warm_tables()

    # the key set was persisted (MRU first, hex encoded)
    sets = json.load(open(os.path.join(warm, "warm_keysets.json")))
    assert sets == [[kb.hex()]]

    # "restarted peer": a fresh provider over the same dir rebuilds the
    # persisted set during prewarm, so the first block's table lookup
    # is a cache HIT — zero builds on the serving path
    prov2 = TPUProvider(warm_keys_dir=warm, use_g16=True)
    assert prov2._prewarm_tables() == 1
    assert prov2.stats["q16_builds"] == 1
    before = prov2.stats["q16_builds"]
    assert prov2._q16_cached((kb,), 1, qx, qy) is not None
    assert prov2.stats["q16_builds"] == before  # served from cache


def test_prewarm_invokes_table_rebuild(monkeypatch):
    """prewarm() (the node-assembly entry point) must reach
    _prewarm_tables when the 16-bit path is enabled."""
    from fabric_tpu.ops import comb
    called = []
    monkeypatch.setattr(TPUProvider, "_prewarm_tables",
                        lambda self: called.append(True) or 0)
    monkeypatch.setattr(comb, "g16_tables", lambda: None)
    prov = TPUProvider(use_g16=True)
    prov.prewarm(buckets=(), key_counts=(), wait_restore=True)
    assert called


def test_corrupt_warm_file_ignored(tmp_path):
    warm = str(tmp_path / "warm")
    os.makedirs(warm)
    with open(os.path.join(warm, "warm_keysets.json"), "w") as f:
        f.write("{not json")
    prov = TPUProvider(warm_keys_dir=warm, use_g16=True)
    assert prov._load_warm_keys() == []
    assert prov._prewarm_tables() == 0
