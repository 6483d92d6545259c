"""The read side of a block's ledger commit: every rwset decoded once,
committed state read once in bulk, history fed from what commit holds.

Differential throughout: the bulk read against a backend forced to
point reads, history from decoded rwsets against history from
envelopes, crash recovery against the commit it replays — the same
codes, the same `UpdateBatch`, the same rows.
"""

import hashlib
import random
import shutil

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.common import tracing
from fabric_tpu.ledger import KVLedger
from fabric_tpu.ledger import pvtdata as pvt
from fabric_tpu.ledger.history import HistoryDB
from fabric_tpu.ledger.kvdb import CACHE_KIB, DBHandle, KVStore, WriteBatch
from fabric_tpu.ledger.statedb import (
    Height,
    StateDB,
    UpdateBatch,
    VersionedDB,
)
from fabric_tpu.ledger.txmgr import (
    TxMgr,
    extract_tx_rwset,
    parse_block_rwsets,
    serialize_metadata,
)
from fabric_tpu.protos import common, proposal as proppb
from fabric_tpu.protos import rwset as rwpb, transaction as txpb

VALID = txpb.TxValidationCode.VALID
MVCC = txpb.TxValidationCode.MVCC_READ_CONFLICT
PHANTOM = txpb.TxValidationCode.PHANTOM_READ_CONFLICT
BAD_RWSET = txpb.TxValidationCode.BAD_RWSET
ENDORSEMENT = txpb.TxValidationCode.ENDORSEMENT_POLICY_FAILURE

NS, COLL = "cc", "secrets"
HNS = pvt.hash_ns(NS, COLL)


def hkey(key: str) -> str:
    return pvt.hashed_key_str(pvt.key_hash(key))


def _set_version(msg, ver) -> None:
    if ver is not None:
        msg.version.block_num, msg.version.tx_num = ver.block, ver.tx


def make_tx(reads=(), writes=(), md_writes=(), ranges=(), hreads=(),
            hwrites=(), hmd_writes=(), raw=None) -> rwpb.TxReadWriteSet:
    """One namespace, one collection. reads / hreads: (key, Height |
    None); writes / hwrites: (key, value | None = delete); md_writes /
    hmd_writes: (key, {name: value}); ranges: (start, end, [(key,
    Height)], exhausted); `raw` replaces the namespace's KVRWSet bytes
    (an rwset that does not parse)."""
    kv = rwpb.KVRWSet()
    for key, ver in reads:
        _set_version(kv.reads.add(key=key), ver)
    for key, value in writes:
        if value is None:
            kv.writes.add(key=key, is_delete=True)
        else:
            kv.writes.add(key=key, value=value)
    for key, entries in md_writes:
        mw = kv.metadata_writes.add(key=key)
        for name in sorted(entries):
            mw.entries.add(name=name, value=entries[name])
    for start, end, seen, exhausted in ranges:
        rqi = kv.range_queries_info.add(start_key=start, end_key=end,
                                        itr_exhausted=exhausted)
        for key, ver in seen:
            _set_version(rqi.raw_reads.kv_reads.add(key=key), ver)
    txrw = rwpb.TxReadWriteSet(data_model=rwpb.TxReadWriteSet.KV)
    nsrw = txrw.ns_rwset.add(namespace=NS)
    nsrw.rwset = kv.SerializeToString(deterministic=True) \
        if raw is None else raw
    if hreads or hwrites or hmd_writes:
        hset = rwpb.HashedRWSet()
        for key, ver in hreads:
            _set_version(hset.hashed_reads.add(key_hash=pvt.key_hash(key)),
                         ver)
        for key, value in hwrites:
            hw = hset.hashed_writes.add(key_hash=pvt.key_hash(key))
            if value is None:
                hw.is_delete = True
            else:
                hw.value_hash = pvt.value_hash(value)
        for key, entries in hmd_writes:
            mw = hset.metadata_writes.add(key_hash=pvt.key_hash(key))
            for name in sorted(entries):
                mw.entries.add(name=name, value=entries[name])
        chrw = nsrw.collection_hashed_rwset.add(collection_name=COLL)
        chrw.rwset = hset.SerializeToString(deterministic=True)
        chrw.pvt_rwset_hash = b"\x01" * 32
    return txrw


class PointReadDB(VersionedDB):
    """A real state DB whose bulk read answers nothing, so every key
    MVCC asks about falls through to a point read."""

    def __init__(self, inner: StateDB):
        self._inner = inner

    def get_states_many(self, pairs):
        return {}

    def get_state(self, ns, key):
        return self._inner.get_state(ns, key)

    def get_state_range(self, ns, start_key, end_key):
        return self._inner.get_state_range(ns, start_key, end_key)


class GetStateOnlyDB(VersionedDB):
    """A backend written before `get_states_many`: the seam's default
    serves the bulk read from `get_state`."""

    def __init__(self, inner: StateDB):
        self._inner = inner

    def get_state(self, ns, key):
        return self._inner.get_state(ns, key)

    def get_state_metadata(self, ns, key):
        return self._inner.get_state_metadata(ns, key)

    def iterate_all(self):
        return self._inner.iterate_all()

    def get_state_range(self, ns, start_key, end_key):
        return self._inner.get_state_range(ns, start_key, end_key)

    def apply_updates(self, batch, height):
        self._inner.apply_updates(batch, height)

    def savepoint(self):
        return self._inner.savepoint()


def seeded_db() -> StateDB:
    """k0-k5 at (1, i); k4 and k5 carry metadata; one hashed key."""
    db = StateDB(DBHandle(KVStore(":memory:"), "s"))
    batch = UpdateBatch()
    for i in range(6):
        md = serialize_metadata({"VP": b"policy%d" % i}) if i >= 4 else b""
        batch.put(NS, f"k{i}", b"v%d" % i, Height(1, i), metadata=md)
    batch.put(HNS, hkey("p0"), pvt.value_hash(b"secret"), Height(1, 6))
    db.apply_updates(batch, Height(1, 6))
    return db


def both_ways(db: StateDB, block_num: int, rwsets, flags=None):
    """(codes, batch) through the bulk read, after holding the run
    forced to point reads to the same codes and batch."""
    bulk, point = TxMgr(db), TxMgr(PointReadDB(db))
    codes, batch = bulk.validate_and_prepare(block_num, rwsets, flags)
    pcodes, pbatch = point.validate_and_prepare(block_num, rwsets, flags)
    assert codes == pcodes
    assert batch.updates == pbatch.updates
    assert list(batch.updates) == list(pbatch.updates)   # and their order
    assert bulk.fallthrough == 0 and point.prefetched == 0
    assert bulk.reads_checked == point.reads_checked
    return codes, batch


# (name, rwsets, upstream flags | None, expected codes, checks on the batch)
H = Height
CASES = [
    ("read_after_write_in_block",
     [make_tx(reads=[("k0", H(1, 0))], writes=[("k0", b"a")]),
      make_tx(reads=[("k0", H(1, 0))], writes=[("k0", b"b")]),
      make_tx(reads=[("k1", H(1, 1))], writes=[("k1", b"c")])],
     None, [VALID, MVCC, VALID],
     lambda b: b[(NS, "k0")].value == b"a" and
     b[(NS, "k0")].version == H(2, 0) and b[(NS, "k1")].version == H(2, 2)),
    ("stale_version",
     [make_tx(reads=[("k0", H(0, 0))], writes=[("k0", b"a")]),
      make_tx(reads=[("k1", H(1, 1))])],
     None, [MVCC, VALID], lambda b: b == {}),
    ("reads_of_absent_keys",
     [make_tx(reads=[("nope", None)], writes=[("nope", b"n")]),
      make_tx(reads=[("nada", H(1, 0))]),
      make_tx(reads=[("k2", None)])],
     None, [VALID, MVCC, MVCC],
     lambda b: list(b) == [(NS, "nope")] and b[(NS, "nope")].metadata == b""),
    ("delete_then_metadata_only_is_a_noop",
     [make_tx(writes=[("k4", None)]),
      make_tx(md_writes=[("k4", {"VP": b"late"})]),
      make_tx(md_writes=[("nope", {"VP": b"x"})])],
     None, [VALID, VALID, VALID],
     lambda b: b == {(NS, "k4"): None}),
    ("metadata_only_write_keeps_the_value",
     [make_tx(md_writes=[("k1", {"VP": b"new"})])],
     None, [VALID],
     lambda b: b[(NS, "k1")].value == b"v1" and
     b[(NS, "k1")].metadata == serialize_metadata({"VP": b"new"})),
    ("value_write_keeps_existing_metadata",
     [make_tx(writes=[("k5", b"fresh")]),
      make_tx(writes=[("k5", b"fresher")])],
     None, [VALID, VALID],
     lambda b: b[(NS, "k5")].value == b"fresher" and
     b[(NS, "k5")].metadata == serialize_metadata({"VP": b"policy5"})),
    ("value_and_metadata_in_one_tx",
     [make_tx(writes=[("k4", b"both")], md_writes=[("k4", {"VP": b"m"})])],
     None, [VALID],
     lambda b: b[(NS, "k4")].metadata == serialize_metadata({"VP": b"m"})),
    ("hashed_reads_and_writes",
     [make_tx(hreads=[("p0", H(1, 6))], hwrites=[("p0", b"s2")]),
      make_tx(hreads=[("p0", H(1, 6))]),
      make_tx(hreads=[("p1", None)], hwrites=[("p1", b"s3")],
              hmd_writes=[("p1", {"VP": b"h"})]),
      make_tx(hwrites=[("p1", None)])],
     None, [VALID, MVCC, VALID, VALID],
     lambda b: b[(HNS, hkey("p0"))].value == pvt.value_hash(b"s2") and
     b[(HNS, hkey("p1"))] is None),
    ("range_query_phantom",
     [make_tx(ranges=[("k0", "k3", [("k0", H(1, 0)), ("k1", H(1, 1)),
                                    ("k2", H(1, 2))], True)],
              writes=[("k1x", b"new")]),
      make_tx(ranges=[("k0", "k3", [("k0", H(1, 0)), ("k1", H(1, 1)),
                                    ("k2", H(1, 2))], True)])],
     None, [VALID, PHANTOM], lambda b: list(b) == [(NS, "k1x")]),
    ("unparseable_rwset",
     [make_tx(writes=[("k0", b"a")]),
      make_tx(raw=b"\xff\xff\xff\x07garbage"),
      None,
      make_tx(reads=[("k0", H(1, 0))])],
     None, [VALID, BAD_RWSET, BAD_RWSET, MVCC],
     lambda b: list(b) == [(NS, "k0")]),
    ("flagged_upstream_is_not_decoded",
     [make_tx(raw=b"\xff\xff\xff\x07garbage"),
      make_tx(reads=[("k0", H(1, 0))], writes=[("k0", b"a")]),
      make_tx(reads=[("k0", H(1, 0))])],
     [ENDORSEMENT, ENDORSEMENT, VALID], [ENDORSEMENT, ENDORSEMENT, VALID],
     lambda b: b == {}),
]


class TestBulkReadAgainstPointReads:
    @pytest.mark.parametrize("name, rwsets, flags, want, check", CASES,
                             ids=[c[0] for c in CASES])
    def test_case(self, name, rwsets, flags, want, check):
        codes, batch = both_ways(seeded_db(), 2, rwsets, flags)
        assert codes == want
        assert check(batch.updates)

    @staticmethod
    def _random_tx(rng: random.Random, committed: dict):
        """A transaction drawn over a small key pool so that blocks
        hold conflicts of every kind; `committed` is (ns, key) ->
        Height as of the previous block."""
        keys = [f"k{i:02d}" for i in range(24)]
        pkeys = [f"p{i}" for i in range(6)]

        def seen(ns, key, raw_key):
            ver = committed.get((ns, raw_key))
            roll = rng.random()
            if roll < 0.15:             # stale, or a version for nothing
                return key, Height(0, rng.randrange(3))
            if roll < 0.2:
                return key, None
            return key, ver

        kind = rng.random()
        if kind < 0.04:
            return None
        if kind < 0.08:
            return make_tx(raw=bytes(rng.randrange(128, 256)
                                     for _ in range(9)))
        kw = {}
        picked = rng.sample(keys, rng.randrange(1, 4))
        kw["reads"] = [seen(NS, k, k) for k in picked
                       if rng.random() < 0.8]
        kw["writes"] = [(k, None if rng.random() < 0.15
                         else b"v" + bytes([rng.randrange(256)]))
                        for k in picked if rng.random() < 0.7]
        if rng.random() < 0.25:
            kw["md_writes"] = [(rng.choice(keys),
                                {"VP": bytes([rng.randrange(256)])})]
        if rng.random() < 0.2:
            pk = rng.choice(pkeys)
            kw["hreads"] = [seen(HNS, pk, hkey(pk))]
            if rng.random() < 0.7:
                kw["hwrites"] = [(pk, None if rng.random() < 0.2
                                  else bytes([rng.randrange(256)]))]
            if rng.random() < 0.3:
                kw["hmd_writes"] = [(pk, {"VP": b"h"})]
        if rng.random() < 0.1:
            lo, hi = sorted(rng.sample(range(24), 2))
            start, end = f"k{lo:02d}", f"k{hi:02d}"
            rows = sorted((k, v) for (ns, k), v in committed.items()
                          if ns == NS and start <= k < end)
            if rows and rng.random() < 0.3:
                rows.pop(rng.randrange(len(rows)))
            kw["ranges"] = [(start, end, rows, True)]
        return make_tx(**kw)

    @pytest.mark.parametrize("seed", range(12))
    def test_random_chain(self, seed):
        """Eight blocks of 40 transactions, state carried from block
        to block, a tenth of each block flagged upstream."""
        rng = random.Random(seed)
        db = StateDB(DBHandle(KVStore(":memory:"), "s"))
        committed: dict = {}
        seen_codes = set()
        for block_num in range(1, 9):
            rwsets = [self._random_tx(rng, committed) for _ in range(40)]
            flags = [ENDORSEMENT if rng.random() < 0.1 else VALID
                     for _ in rwsets]
            codes, batch = both_ways(db, block_num, rwsets, flags)
            seen_codes.update(codes)
            db.apply_updates(batch, Height(block_num, len(rwsets) - 1))
            for nk, vv in batch.updates.items():
                if vv is None:
                    committed.pop(nk, None)
                else:
                    committed[nk] = vv.version
        assert {VALID, MVCC, BAD_RWSET, ENDORSEMENT} <= seen_codes

    def test_bulk_read_of_the_seam(self):
        """Every asked pair is answered, absent ones with None, by the
        embedded engine and by the seam's default alike."""
        db = seeded_db()
        pairs = [(NS, "k0"), (NS, "nope"), (HNS, hkey("p0")), (NS, "k0")]
        got = db.get_states_many(pairs)
        assert got == VersionedDB.get_states_many(db, pairs)
        assert set(got) == set(pairs) and got[(NS, "nope")] is None
        assert got[(NS, "k0")] == db.get_state(NS, "k0")
        assert db.get_state_metadata_many([(NS, "k4"), (NS, "k0")]) == {
            (NS, "k4"): serialize_metadata({"VP": b"policy4"}),
            (NS, "k0"): None}

    def test_pass_collects_what_mvcc_reads(self):
        parsed = parse_block_rwsets(
            [make_tx(reads=[("k0", None)], writes=[("k1", b"a")],
                     md_writes=[("k2", {"VP": b"m"})],
                     ranges=[("k7", "k9", [("k8", H(1, 0))], True)],
                     hreads=[("p0", None)], hwrites=[("p1", b"s")],
                     hmd_writes=[("p2", {"VP": b"h"})]),
             make_tx(reads=[("k5", None)])],
            [VALID, ENDORSEMENT])
        assert list(parsed.keys) == [
            (NS, "k0"), (NS, "k1"), (NS, "k2"), (HNS, hkey("p0")),
            (HNS, hkey("p1")), (HNS, hkey("p2"))]
        assert parsed.txs[1] is None and len(parsed.txs[0]) == 1


# -- whole ledgers: history, recovery, the statement count --

class _Signer:
    def __init__(self, identity=b"endorser"):
        self._id = identity

    def serialize(self):
        return self._id

    def sign(self, msg):
        return hashlib.sha256(self._id + msg).digest()


def envelope(txrw: rwpb.TxReadWriteSet) -> bytes:
    prop, _tx_id = pu.create_proposal("ch1", NS, [b"invoke"],
                                      creator=b"client")
    presp = pu.create_proposal_response(
        pu.marshal(prop), pu.marshal(txrw), b"", proppb.Response(status=200),
        proppb.ChaincodeID(name=NS), _Signer())
    return pu.marshal(pu.create_signed_tx(prop, [presp], _Signer(b"client")))


def new_ledger(path, **kw) -> KVLedger:
    led = KVLedger("ch1", str(path), **kw)
    genesis = pu.new_block(0, b"")
    genesis.data.data.append(b"config-placeholder")
    genesis.header.data_hash = pu.block_data_hash(genesis.data)
    led.initialize_from_genesis(genesis)
    return led


def next_block(led: KVLedger, envs) -> common.Block:
    block = pu.new_block(led.height, led.block_store.last_block_hash)
    block.data.data.extend(envs)
    block.header.data_hash = pu.block_data_hash(block.data)
    return block


def chain_envs(seed: int, blocks: int = 3, txs: int = 12):
    """[(envelopes, upstream flags)] per block: writes, deletes,
    metadata, hashed writes, stale reads, one envelope that is no
    endorser transaction and one rwset that does not parse."""
    rng = random.Random(seed)
    out = []
    for _ in range(blocks):
        envs, flags = [], []
        for i in range(txs):
            key = f"k{rng.randrange(8)}"
            if i == 3:
                envs.append(b"not-an-envelope")
            elif i == 5:
                envs.append(envelope(make_tx(raw=b"\xff\xff\xff\x07zz")))
            else:
                envs.append(envelope(make_tx(
                    reads=[(key, Height(9, 9))]
                    if i == 7 or rng.random() < 0.2 else [],
                    writes=[(key, None if rng.random() < 0.2 else
                             b"v%d" % rng.randrange(100)),
                            (f"w{rng.randrange(4)}", b"x")],
                    md_writes=[(key, {"VP": b"p"})]
                    if rng.random() < 0.3 else [],
                    hwrites=[(f"p{rng.randrange(3)}", b"s")]
                    if rng.random() < 0.3 else [])))
            flags.append(ENDORSEMENT if i not in (3, 5, 7) and
                         rng.random() < 0.15 else VALID)
        out.append((envs, flags))
    return out


def ledger_rows(led: KVLedger):
    """Everything the read side could have changed: state (values,
    versions, metadata), the history rows, the savepoint."""
    return (list(led.state_db.iterate_all()),
            list(led.history_db._db.iterate()),
            led.state_db.savepoint())


class TestHistoryAndRecovery:
    @pytest.mark.parametrize("seed", range(4))
    def test_history_rows_from_decoded_rwsets_and_from_envelopes(
            self, tmp_path, seed):
        envs, flags = chain_envs(seed, blocks=1, txs=30)[0]
        block = pu.new_block(7, b"")
        block.data.data.extend(envs)
        rwsets = [extract_tx_rwset(e) for e in envs]
        codes, _ = TxMgr(seeded_db()).validate_and_prepare(7, rwsets, flags)
        stores = []
        for parsed in (parse_block_rwsets(rwsets, flags), None):
            hist = HistoryDB(DBHandle(KVStore(":memory:"), "h"))
            n = hist.commit_block(block, codes, parsed)
            stores.append((n, list(hist._db.iterate())))
        assert stores[0] == stores[1]
        assert stores[0][0] == len(stores[0][1]) > 0

    @pytest.mark.parametrize("seed", range(4))
    def test_recovery_replays_the_commit(self, tmp_path, seed):
        """A crash between block append and state commit: the reopened
        ledger ends where the one that never crashed does."""
        chain = chain_envs(100 + seed)
        whole = new_ledger(tmp_path / "whole")
        crashed = new_ledger(tmp_path / "crashed")
        for envs, flags in chain[:-1]:
            for led in (whole, crashed):
                led.commit_block(next_block(led, envs), flags)
        envs, flags = chain[-1]
        block = next_block(whole, envs)
        codes = whole.commit_block(block, flags)
        assert VALID in codes and MVCC in codes and BAD_RWSET in codes
        torn = next_block(crashed, envs)
        for idx in (common.BlockMetadataIndex.TRANSACTIONS_FILTER,
                    common.BlockMetadataIndex.COMMIT_HASH):
            torn.metadata.metadata[idx] = block.metadata.metadata[idx]
        crashed.block_store.add_block(torn)
        before = ledger_rows(crashed)
        crashed.close()
        reopened = KVLedger("ch1", str(tmp_path / "crashed"))
        assert ledger_rows(reopened) == ledger_rows(whole) != before
        assert reopened.pvt_store.get_missing() == \
            whole.pvt_store.get_missing()
        whole.close()
        reopened.close()


def smallbank_block(rng: random.Random, accounts: int, versions: dict,
                    txs: int = 500):
    """Caliper's Smallbank shape: three functions in five read and
    write one account, two in five read and write two."""
    rwsets = []
    for _ in range(txs):
        n = 1 if rng.random() < 0.6 else 2
        keys = [f"acct{a:06d}" for a in rng.sample(range(accounts), n)]
        rwsets.append(make_tx(
            reads=[(k, versions[k]) for k in keys],
            writes=[(k, b"{\"balance\":%d}" % rng.randrange(10 ** 6))
                    for k in keys]))
    return rwsets


class TestStatementCount:
    def _ledgers(self, tmp_path, accounts):
        plain = new_ledger(tmp_path / "plain")
        seam = new_ledger(
            tmp_path / "seam",
            state_db_factory=lambda _id, h: GetStateOnlyDB(StateDB(h)))
        batch = UpdateBatch()
        versions = {}
        for a in range(accounts):
            key = f"acct{a:06d}"
            versions[key] = Height(1, a)
            batch.put(NS, key, b"{\"balance\":0}", versions[key])
        for led in (plain, seam):
            led.state_db.apply_updates(batch, Height(0, 0))
        return plain, seam, versions

    def test_default_block_commits_in_four_statements(self, tmp_path):
        """A 500-transaction Smallbank block: MVCC's ~1,300 point
        reads are two bulk statements, nothing falls through, and a
        backend that only has `get_state` gives the same codes."""
        accounts = 4000
        plain, seam, versions = self._ledgers(tmp_path, accounts)
        rwsets = smallbank_block(random.Random(31), accounts, versions)
        envs = [envelope(t) for t in rwsets]
        tracing.configure(enabled=True, ring_size=4096, sample_every=1)
        tracing.reset()
        try:
            codes = plain.commit_block(next_block(plain, envs),
                                       rwsets=rwsets)
            spans = [e for e in tracing.snapshot()
                     if e[1] == "ledger.mvcc"]
        finally:
            tracing.configure(enabled=True, ring_size=4096)
            tracing.reset()
        attrs = spans[-1][8]
        distinct = {r.key for t in rwsets
                    for r in _kv(t).reads}
        assert attrs["prefetched"] == len(distinct) > 500
        assert attrs["selects"] <= 4
        assert attrs["fallthrough"] == 0
        assert attrs["reads"] == sum(len(_kv(t).reads) for t in rwsets)
        assert codes.count(MVCC) > 0 and codes.count(VALID) > 400
        assert seam.commit_block(next_block(seam, envs)) == codes
        assert list(seam.state_db.iterate_all()) == \
            list(plain.state_db.iterate_all())
        assert seam.txmgr.fallthrough == 0
        plain.close()
        seam.close()

    def test_thread_io_books_the_threads_calls(self, tmp_path):
        """`commit.commit`'s `syscr` / `syscw`: the calling thread's
        read and write system calls over the stretch, where the kernel
        keeps that account; nothing with tracing off."""
        tracing.configure(enabled=True, ring_size=4096, sample_every=1)
        tracing.reset()
        try:
            sp = tracing.span("commit.commit", block=1)
            with sp, tracing.thread_io(sp):
                with open(tmp_path / "f", "wb") as f:
                    for _ in range(5):
                        f.write(b"x" * 10)
                        f.flush()
            ev = [e for e in tracing.snapshot()
                  if e[1] == "commit.commit"][-1]
            if tracing._thread_io() is None:
                assert "syscw" not in (ev[8] or {})
            else:
                assert ev[8]["syscw"] >= 5 and ev[8]["syscr"] >= 0
            tracing.set_enabled(False)
            off = tracing.span("commit.commit")
            with off, tracing.thread_io(off):
                pass
            assert tracing.snapshot()[-1] == ev
        finally:
            tracing.configure(enabled=True, ring_size=4096)
            tracing.reset()


class TestPageCache:
    """A store keeps its working set in memory (`CACHE_KIB`): how much
    of the file it holds, not what a commit writes or when."""

    def test_cache_size_moves_and_the_durability_settings_do_not(
            self, tmp_path):
        def pragma(store, name):
            return store._conn.execute("PRAGMA " + name).fetchone()[0]
        store = KVStore(str(tmp_path / "index.db"))
        store.put(b"k", b"v")
        assert CACHE_KIB == 65536
        assert pragma(store, "cache_size") == -CACHE_KIB
        assert pragma(store, "journal_mode") == "wal"
        assert pragma(store, "synchronous") == 1
        assert pragma(store, "wal_autocheckpoint") == 1000
        assert pragma(store, "page_size") == 4096
        assert pragma(store, "locking_mode") == "normal"
        store.close()
        mem = KVStore(":memory:")
        mem.put(b"k", b"v")
        assert mem.get(b"k") == b"v"
        assert pragma(mem, "cache_size") == -CACHE_KIB
        mem.close()

    @staticmethod
    def _key(i: int) -> bytes:
        return hashlib.sha256(b"%d" % i).digest()

    def _rounds(self, store: KVStore, rows: int):
        """Ten rounds of what a block asks of the file: one 500-key
        `get_many` and one 700-row `write_batch` on random keys.
        -> (what the reads answered, the thread's syscr a round)."""
        rng = random.Random(36)
        answers = []
        sp = tracing.span("commit.commit")
        with sp, tracing.thread_io(sp):
            for rnd in range(10):
                got = store.get_many(
                    [self._key(rng.randrange(rows)) for _ in range(500)])
                answers.append(sorted(got.items()))
                batch = WriteBatch()
                for _ in range(700):
                    batch.put(self._key(rng.randrange(rows)),
                              b"r%02d" % rnd + bytes(196))
                store.write_batch(batch)
        ev = [e for e in tracing.snapshot() if e[1] == "commit.commit"][-1]
        syscr = (ev[8] or {}).get("syscr")
        return answers, None if syscr is None else syscr / 10

    def test_working_set_is_read_once_not_every_round(self, tmp_path):
        """The mechanism by its counter: on a file past 8 MB, once
        scanned, the rounds' read system calls stay under a third of
        what the same rounds cost a connection left at sqlite's default
        2 MB (what is left is the auto-checkpoint reading the WAL's
        frames back)."""
        rows, per_batch = 33000, 1375
        seed = KVStore(str(tmp_path / "seed.db"))
        for lo in range(0, rows, per_batch):
            batch = WriteBatch()
            for i in range(lo, lo + per_batch):
                batch.put(self._key(i), bytes(200))
            seed.write_batch(batch)
        seed.close()        # the last connection checkpoints the WAL
        assert (tmp_path / "seed.db").stat().st_size > 8 << 20
        shutil.copy(tmp_path / "seed.db", tmp_path / "small.db")
        cached = KVStore(str(tmp_path / "seed.db"))
        small = KVStore(str(tmp_path / "small.db"))
        small._conn.execute("PRAGMA cache_size=-2000")
        assert list(cached.iterate()) == list(small.iterate())
        tracing.configure(enabled=True, ring_size=4096, sample_every=1)
        tracing.reset()
        try:
            got_small, reads_small = self._rounds(small, rows)
            got_cached, reads_cached = self._rounds(cached, rows)
        finally:
            tracing.configure(enabled=True, ring_size=4096)
            tracing.reset()
        assert got_cached == got_small
        if tracing._thread_io() is None:
            assert reads_cached is None and reads_small is None
        else:
            assert reads_small > 1000
            assert reads_cached < reads_small / 3
        cached.close()
        small.close()

    def test_second_connection_writes_are_seen_through_the_cache(
            self, tmp_path):
        """`ledgerutil` / `nodeops` open a running node's `index.db`
        from a second process: what the second store commits, the
        first reads, though its cache held the old pages."""
        path = str(tmp_path / "index.db")
        node = KVStore(path)
        batch = WriteBatch()
        for i in range(2000):
            batch.put(self._key(i), b"old")
        node.write_batch(batch)
        keys = [self._key(i) for i in range(0, 2000, 4)]
        assert set(node.get_many(keys).values()) == {b"old"}
        tool = KVStore(path)
        batch = WriteBatch()
        for k in keys:
            batch.put(k, b"new")
        batch.delete(self._key(1))
        batch.put(b"added", b"by the tool")
        tool.write_batch(batch)
        assert set(node.get_many(keys).values()) == {b"new"}
        assert node.get(self._key(1)) is None
        assert node.get(self._key(2)) == b"old"
        assert node.get(b"added") == b"by the tool"
        node.put(b"added", b"by the node")
        assert tool.get(b"added") == b"by the node"
        assert list(tool.iterate()) == list(node.iterate())
        tool.close()
        node.close()


def _kv(txrw: rwpb.TxReadWriteSet) -> rwpb.KVRWSet:
    kv = rwpb.KVRWSet()
    kv.ParseFromString(txrw.ns_rwset[0].rwset)
    return kv
