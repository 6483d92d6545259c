"""Warm-restart wiring: key-table persistence -> prewarm restore.

Round-3 verdict: the warm-keys machinery existed but was unreachable
(no `WarmKeysDir` in the factory, `prewarm()` never reached the
restore). These tests pin the WIRING end to end — config -> factory ->
provider, build -> a file a key, fresh provider -> prewarm -> the key
in its slot — with the table builders stubbed (the real 16-bit comb
build is a device job, not a unit concern). Slot bookkeeping:
tests/test_key_pool.py.
"""

import os

import numpy as np

from fabric_tpu.bccsp import factory
from fabric_tpu.bccsp.tpu import TPUProvider

ROWS = 4


def _stub_builders(monkeypatch, builds):
    import jax.numpy as jnp

    def fake_qtab_fn(self):
        return lambda qx, qy: jnp.zeros((2, 3, 20), jnp.int32)

    def fake_q16_fn(self):
        def build(q8):
            builds.append(1)
            return jnp.ones((ROWS, 3, 20), jnp.int32)
        return build

    from fabric_tpu.ops import comb
    monkeypatch.setattr(comb, "g16_tables",
                        lambda: jnp.zeros((0, 3, 20), jnp.int32))
    monkeypatch.setattr(TPUProvider, "_slab_rows", lambda self: ROWS)
    monkeypatch.setattr(TPUProvider, "_qtab_fn", fake_qtab_fn)
    monkeypatch.setattr(TPUProvider, "_q16_fn", fake_q16_fn)


def _admit(prov, kb):
    with prov._pool_lock:
        return prov._key_slots({kb: 0}, np.zeros(1, np.int32))[0][0]


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

    prov = TPUProvider(warm_keys_dir=warm, use_g16=True, max_keys=4)
    _admit(prov, kb)
    assert prov.stats["key_slot_builds"] == 1 and len(builds) == 1
    # table bytes land asynchronously, a file a key
    prov.flush_warm_tables()
    assert sorted(os.listdir(warm)) == [
        f"slab16_{kb.hex()}.npy", f"slab16_{kb.hex()}.npy.sha256"]

    # "restarted peer": a fresh provider over the same dir reads the
    # slab back during prewarm, so the first block's lookup is a HIT —
    # zero builds on the serving path
    prov2 = TPUProvider(warm_keys_dir=warm, use_g16=True, max_keys=4)
    prov2.prewarm(buckets=(), wait_restore=True)
    assert prov2.stats["key_slot_disk_loads"] == 1
    assert prov2.stats["key_slots_resident"] == 1
    _admit(prov2, kb)
    assert prov2.stats["key_slot_hits"] == 1
    assert prov2.stats["key_slot_builds"] == 0 and len(builds) == 1


def test_prewarm_invokes_the_restore(monkeypatch):
    """prewarm() (the node-assembly entry point) must reach
    _restore_slabs when the 16-bit path is enabled."""
    from fabric_tpu.ops import comb
    called = []
    monkeypatch.setattr(TPUProvider, "_restore_slabs",
                        lambda self: called.append(True) or 0)
    monkeypatch.setattr(comb, "g16_tables", lambda: None)
    prov = TPUProvider(use_g16=True)
    prov.prewarm(buckets=(), wait_restore=True)
    assert called


def test_files_that_are_no_slabs_are_ignored(tmp_path, monkeypatch):
    _stub_builders(monkeypatch, [])
    warm = str(tmp_path / "warm")
    os.makedirs(warm)
    kb = bytes(range(64))
    for name, body in (
            ("warm_keysets.json", b"{not json"),        # an old index
            ("slab16_zz.npy", b"junk"),                 # no key in it
            (f"slab16_{kb.hex()}.npy", b"not an array"),
            (f"slab8_{kb.hex()}.npy", b"another width")):
        with open(os.path.join(warm, name), "wb") as f:
            f.write(body)
    prov = TPUProvider(warm_keys_dir=warm, use_g16=True, max_keys=4)
    assert prov._restore_slabs() == 0
    assert prov.stats["key_slots_resident"] == 0
    # and the key still builds when a batch asks for it
    _admit(prov, kb)
    assert prov.stats["key_slot_builds"] == 1
