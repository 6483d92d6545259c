"""The key-table pool: one resident array of per-key slabs.

A P-256 key's comb table lives in a slot of ONE device array
(`TPUProvider._pool`); a lane carries its key's slot. These tests pin
the slot bookkeeping — capacity, admission, least-recently-used
eviction that never takes a slot of the batch in hand, the in-place
write, persistence a key, restore — with the table builders stubbed (a
stub slab carries its key's first byte in every row, so the pool's
CONTENT is checked too; the real tables are the comb differential
suites' concern, tests/test_comb.py and tests/test_bccsp.py). The pool
write is the provider's own program.
"""

import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import tracing
from fabric_tpu.ops import comb, limb

ROWS = 4                            # rows of a stub slab
SLAB = ROWS * 3 * limb.L * 4        # its bytes


def _key(i: int) -> bytes:
    return bytes([i]) * 64


def _marker(kb: bytes) -> int:
    """What the stub builder writes into `kb`'s slab: the key's first
    limb."""
    return int(limb.be_bytes_to_limbs(
        np.frombuffer(kb[:32], np.uint8).reshape(1, 32))[0, 0])


def _provider(monkeypatch, builds=None, **kw):
    builds = [] if builds is None else builds

    def fake_qtab_fn(self):
        def build(qx, qy):
            builds.append(int(np.asarray(qx)[0, 0]))
            return jnp.full((ROWS, 3, limb.L), builds[-1], jnp.int32)
        return build

    monkeypatch.setattr(TPUProvider, "_slab_rows", lambda self: ROWS)
    monkeypatch.setattr(TPUProvider, "_qtab_fn", fake_qtab_fn)
    monkeypatch.setattr(TPUProvider, "_q16_fn",
                        lambda self: lambda slab: slab)
    monkeypatch.setattr(comb, "g16_tables",
                        lambda: jnp.zeros((0, 3, limb.L), jnp.int32))
    kw.setdefault("use_g16", False)
    return TPUProvider(**kw), builds


def _slots(prov, keys):
    """One batch naming `keys` (one lane each): {key: slot}."""
    key_map = {kb: j for j, kb in enumerate(keys)}
    with prov._pool_lock:
        lanes, pool, _ = prov._key_slots(
            key_map, np.arange(len(keys), dtype=np.int32))
    assert pool is prov._pool
    return dict(zip(keys, lanes.tolist()))


def _rows(prov, slot):
    return np.asarray(prov._pool)[slot * ROWS:(slot + 1) * ROWS]


@pytest.mark.parametrize("kw, slots", [
    ({"max_keys": 5}, 5),                                   # MaxKeys
    ({"max_keys": 32, "table_cache_bytes": 3 * SLAB + 1}, 3),  # budget
    ({"table_cache_bytes": SLAB - 1}, 0),           # not one slab
], ids=["max_keys", "table_budget", "none"])
def test_capacity_is_max_keys_within_the_table_budget(monkeypatch, kw,
                                                      slots):
    prov, _ = _provider(monkeypatch, **kw)
    assert prov._key_capacity() == slots
    assert prov.stats["key_slot_capacity"] == slots
    assert prov._pool is None          # sized, not yet allocated
    if slots:
        _slots(prov, [_key(1)])
        assert prov._pool.shape == (slots * ROWS, 3, limb.L)
        assert prov.stats["key_table_bytes"] == slots * SLAB


def test_shipped_budget_is_13_slots_as_the_chip_holds_them(monkeypatch):
    """TableCacheMB 4,000 over a slab as the DEVICE holds it: a row's
    20-limb coordinates sit in 24 words on the chip, 302 MB a key at
    16-bit windows and not the rows' own 252 — 13 slots, the most that
    costs the comb program nothing there (PERF.md, Findings PR 34).
    Off the chip a slab is its rows."""
    prov = TPUProvider(use_g16=True)
    assert prov._slab_bytes() == 251_658_240
    assert prov._key_capacity() == 16
    monkeypatch.setattr(TPUProvider, "_on_tpu",
                        classmethod(lambda cls: True))
    chip = TPUProvider(use_g16=True)
    assert chip._slab_bytes() == 301_989_888
    assert chip._key_capacity() == 13
    assert chip._slab_shape() == (16 * 65536, 3, limb.L)


def test_capacity_is_sized_from_the_chips_memory_not_from_what_is_free(
        monkeypatch):
    """Half of `bytes_limit`: `bytes_in_use` moves while prewarm's g16
    thread allocates, and the pool's shape is part of a program's
    key."""
    from fabric_tpu.common import devicecost
    rows = [{"device": 0, "bytes_limit": 10 * SLAB + 1,
             "bytes_in_use": 9 * SLAB}]
    monkeypatch.setattr(devicecost, "device_memory", lambda: rows)
    prov, _ = _provider(monkeypatch, max_keys=32)
    assert prov._key_capacity() == 5


def test_a_key_is_built_once_and_found_again(monkeypatch):
    prov, builds = _provider(monkeypatch, max_keys=8)
    first = _slots(prov, [_key(1), _key(2), _key(3)])
    again = _slots(prov, [_key(3), _key(1), _key(2)])  # another order
    assert first == again and len(set(first.values())) == 3
    assert builds == [_marker(_key(i)) for i in (1, 2, 3)]
    st = prov.stats
    assert (st["key_slot_lookups"], st["key_slot_hits"],
            st["key_slot_builds"], st["key_slots_resident"]) == (6, 3, 3, 3)


def test_two_batches_sharing_all_but_one_key_build_one_slab(monkeypatch):
    prov, builds = _provider(monkeypatch, max_keys=25)
    _slots(prov, [_key(i) for i in range(24)])
    before = len(builds)
    got = _slots(prov, [_key(i) for i in range(1, 25)])
    assert builds[before:] == [_marker(_key(24))]
    assert prov.stats["key_slot_evictions"] == 0
    assert len(set(got.values())) == 24


def test_a_slab_lands_in_its_slot_and_nowhere_else(monkeypatch):
    prov, _ = _provider(monkeypatch, max_keys=6)
    got = _slots(prov, [_key(i) for i in (9, 4, 7)])
    for kb, slot in got.items():
        assert (_rows(prov, slot) == _marker(kb)).all()
    free = set(range(6)) - set(got.values())
    for slot in free:
        assert not _rows(prov, slot).any()


def test_the_write_is_in_place(monkeypatch):
    """A copy of a 6 GB pool would double the peak: the write donates
    the array, so the handle from before it is gone."""
    prov, _ = _provider(monkeypatch, max_keys=4)
    _slots(prov, [_key(1)])
    old = prov._pool
    _slots(prov, [_key(2)])
    assert old.is_deleted() and not prov._pool.is_deleted()


def test_eviction_is_lru_and_never_of_the_batch_in_hand(monkeypatch):
    """Six keys in rotation through four slots: every batch finds its
    keys' rows, and no key of the batch in hand loses its slot to
    another key of the same batch."""
    prov, builds = _provider(monkeypatch, max_keys=4)
    keys = [_key(i) for i in range(1, 7)]
    for rnd in range(12):
        batch = [keys[(rnd + j) % 6] for j in range(3)]
        got = _slots(prov, batch)
        assert len(set(got.values())) == 3
        for kb, slot in got.items():
            assert (_rows(prov, slot) == _marker(kb)).all()
        assert prov.stats["key_slots_resident"] <= 4
    assert prov.stats["key_slot_evictions"] > 0
    assert prov.stats["key_slot_builds"] == len(builds) \
        == 4 + prov.stats["key_slot_evictions"]


def test_the_least_recently_used_key_goes_first(monkeypatch):
    prov, _ = _provider(monkeypatch, max_keys=3)
    _slots(prov, [_key(1), _key(2), _key(3)])
    _slots(prov, [_key(1)])                 # 2 is now the oldest
    _slots(prov, [_key(4)])
    assert list(prov._slot_of) == [_key(3), _key(1), _key(4)]
    # a full batch may evict everything but itself
    got = _slots(prov, [_key(5), _key(6), _key(4)])
    assert set(prov._slot_of) == {_key(4), _key(5), _key(6)}
    assert len(set(got.values())) == 3


def test_a_failed_build_leaves_the_pool_as_it_was(monkeypatch):
    """A build that fails degrades the batch as a table failure always
    did (the caller's breaker serves sw); the pool keeps its keys and
    its free slots, and the next batch builds."""
    prov, builds = _provider(monkeypatch, max_keys=3)
    _slots(prov, [_key(1)])
    real = TPUProvider._build_slab
    state = {"fail": True}

    def flaky(self, kb):
        if state["fail"]:
            raise RuntimeError("table build failed")
        return real(self, kb)
    monkeypatch.setattr(TPUProvider, "_build_slab", flaky)
    with pytest.raises(RuntimeError):
        _slots(prov, [_key(1), _key(2)])
    assert list(prov._slot_of) == [_key(1)]
    assert (_rows(prov, prov._slot_of[_key(1)])
            == _marker(_key(1))).all()
    state["fail"] = False
    got = _slots(prov, [_key(1), _key(2)])
    assert (_rows(prov, got[_key(2)]) == _marker(_key(2))).all()


def test_a_write_that_lost_the_pool_drops_it(monkeypatch):
    """The write donates the pool: where it fails after the runtime
    took the array nothing resident is readable, so every key goes and
    the next batch fills a new pool."""
    prov, builds = _provider(monkeypatch, max_keys=3)
    _slots(prov, [_key(1), _key(2)])

    real_write = prov._pool_write_fn()
    state = {"lose": True}

    def write(pool, slab, row0):
        if state["lose"]:
            pool.delete()
            raise RuntimeError("device lost mid-write")
        return real_write(pool, slab, row0)
    monkeypatch.setattr(prov, "_pool_write_fn", lambda: write)
    with pytest.raises(RuntimeError):
        _slots(prov, [_key(3)])
    assert prov._pool is None and not prov._slot_of
    assert prov.stats["key_slots_resident"] == 0
    state["lose"] = False
    before = len(builds)
    got = _slots(prov, [_key(1), _key(3)])
    assert len(builds) == before + 2
    for kb, slot in got.items():
        assert (_rows(prov, slot) == _marker(kb)).all()


def test_persist_and_restore_round_trip(monkeypatch, tmp_path):
    warm = str(tmp_path / "warm")
    p1, builds = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    _slots(p1, [_key(1), _key(2)])
    p1.flush_warm_tables()
    for kb in (_key(1), _key(2)):
        path = p1._slab_path(kb)
        assert os.path.basename(path) == f"slab8_{kb.hex()}.npy"
        assert comb.verify_digest_sidecar(path) is True
    # "restarted peer": the slabs come back into slots, nothing built
    p2, builds2 = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    assert p2._restore_slabs() == 2
    got = _slots(p2, [_key(2), _key(1)])
    assert builds2 == []
    assert (p2.stats["key_slot_disk_loads"], p2.stats["key_slot_hits"],
            p2.stats["key_slot_builds"]) == (2, 2, 0)
    for kb, slot in got.items():
        assert (_rows(p2, slot) == _marker(kb)).all()


def test_no_more_than_two_slabs_wait_for_their_host_copy(monkeypatch,
                                                         tmp_path):
    """A writer holds its slab's device buffer until it has copied it
    to the host; a wide channel's first block builds a key after a
    key. The third build waits for a copy instead of leaving a third
    slab on the device beside the pool."""
    from fabric_tpu.common import faults
    prov, _ = _provider(monkeypatch, max_keys=4,
                        warm_keys_dir=str(tmp_path / "warm"))
    gate, at_disk = threading.Event(), []
    monkeypatch.setattr(
        faults, "check",
        lambda point: point == "tpu.table_persist"
        and (at_disk.append(1), gate.wait(30)))
    _slots(prov, [_key(1), _key(2)])    # two writers, held before the copy
    third = threading.Thread(target=_slots, args=(prov, [_key(3)]))
    third.start()
    third.join(0.5)
    assert third.is_alive() and len(at_disk) == 2
    assert _key(3) in prov._slot_of     # in its slot, no writer yet
    gate.set()
    third.join(30)
    assert not third.is_alive()
    prov.flush_warm_tables()
    assert len(at_disk) == 3
    assert len([n for n in os.listdir(tmp_path / "warm")
                if n.endswith(".npy")]) == 3
    assert prov.stats["warm_table_persist_failures"] == 0


def test_restore_takes_free_slots_only_and_goes_first(monkeypatch,
                                                      tmp_path):
    warm = str(tmp_path / "warm")
    p1, _ = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    _slots(p1, [_key(i) for i in (1, 2, 3, 4)])
    p1.flush_warm_tables()
    # a smaller pool with one live key: two free slots, four files
    p2, builds2 = _provider(monkeypatch, max_keys=3, warm_keys_dir=warm)
    _slots(p2, [_key(9)])
    assert p2._restore_slabs() == 2
    assert p2.stats["key_slot_evictions"] == 0
    assert list(p2._slot_of)[-1] == _key(9)    # restored keys are cold
    p2.flush_warm_tables()
    assert len([n for n in os.listdir(warm) if n.endswith(".npy")]) == 5
    # a live miss takes a restored key's slot, never the live key's
    cold = list(p2._slot_of)[0]
    _slots(p2, [_key(7)])
    assert cold not in p2._slot_of and _key(9) in p2._slot_of
    p2.flush_warm_tables()
    assert not os.path.exists(p2._slab_path(cold))       # mirrors pool
    assert not os.path.exists(p2._slab_path(cold) + ".sha256")


def test_live_miss_reads_its_slab_from_disk_not_a_rebuild(monkeypatch,
                                                          tmp_path):
    """A restore still in flight is served as a miss is: the batch
    admits the key itself (from the file where it is there), and the
    restore then finds it resident."""
    warm = str(tmp_path / "warm")
    p1, _ = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    _slots(p1, [_key(1), _key(2)])
    p1.flush_warm_tables()
    p2, builds2 = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    got = _slots(p2, [_key(1)])             # before any restore ran
    assert builds2 == [] and p2.stats["key_slot_disk_loads"] == 1
    assert (_rows(p2, got[_key(1)]) == _marker(_key(1))).all()
    assert p2._restore_slabs() == 1         # only key 2 was left to do
    assert p2.stats["key_slot_disk_loads"] == 2


def test_prewarm_restores_in_the_background(monkeypatch, tmp_path):
    warm = str(tmp_path / "warm")
    p1, _ = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    _slots(p1, [_key(5)])
    p1.flush_warm_tables()
    p2, builds2 = _provider(monkeypatch, max_keys=4, warm_keys_dir=warm)
    p2.prewarm(buckets=(), wait_restore=True)
    assert p2._restore_thread is not None
    assert _key(5) in p2._slot_of and builds2 == []


def test_concurrent_batches_keep_the_accounting_consistent(monkeypatch):
    prov, builds = _provider(monkeypatch, max_keys=4)
    keys = [_key(i) for i in range(1, 9)]
    errors = []

    def work(seed):
        try:
            for rnd in range(20):
                batch = [keys[(seed + rnd + j) % 8] for j in range(3)]
                key_map = {kb: j for j, kb in enumerate(batch)}
                with prov._pool_lock:
                    lanes, pool, _ = prov._key_slots(
                        key_map, np.arange(3, dtype=np.int32))
                    got = np.asarray(pool)
                for kb, slot in zip(batch, lanes.tolist()):
                    rows = got[slot * ROWS:(slot + 1) * ROWS]
                    assert (rows == _marker(kb)).all()
        except Exception as e:      # noqa: BLE001 (reported below)
            errors.append(e)

    threads = [threading.Thread(target=work, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    st = prov.stats
    assert st["key_slot_lookups"] == 4 * 20 * 3
    assert st["key_slot_hits"] + st["key_slot_builds"] == \
        st["key_slot_lookups"]
    assert st["key_slot_builds"] == len(builds) \
        == st["key_slots_resident"] + st["key_slot_evictions"]
    assert sorted(prov._slot_of.values()) == list(range(4))


def test_spans_carry_what_a_batch_did_to_the_pool(monkeypatch):
    if not tracing.enabled():
        pytest.skip("tracing is off")
    prov, _ = _provider(monkeypatch, max_keys=2)
    _slots(prov, [_key(1), _key(2)])
    mark = len(tracing.snapshot())
    _slots(prov, [_key(1), _key(3)])
    events = [e for e in tracing.snapshot()[mark:] if e[0] == "X"]
    tables = [e for e in events if e[1] == "tpu.tables"][-1]
    assert {k: tables[8][k] for k in ("keys", "hits", "built",
                                      "evicted")} == {
        "keys": 2, "hits": 1, "built": 1, "evicted": 1}
    build = [e for e in events if e[1] == "tpu.table_build"][-1]
    assert build[8]["source"] == "build" and build[8]["bytes"] == SLAB
    assert build[8]["slot"] == prov._slot_of[_key(3)]
    assert build[4] == tables[3]        # a child of the batch's span
