"""MVCC transaction manager: simulation and block validation.

Rebuild of `core/ledger/kvledger/txmgmt/` — the simulator
(`txmgr/lockbased_tx_simulator.go`) records reads with committed
versions and buffered writes; the block validator
(`validation/validator.go:81-260`) replays each tx's read set against
the state DB plus the updates of earlier valid txs in the same block
(validateKVRead:174, validateRangeQuery:213 phantom detection), marking
MVCC conflicts; surviving writes land in one UpdateBatch stamped with
(block, tx) heights (batch_preparer.go:72).
"""

from __future__ import annotations

import logging
from typing import NamedTuple, Optional, Sequence

from google.protobuf.message import DecodeError

from fabric_tpu import protoutil as pu
from fabric_tpu.ledger import pvtdata as pvt
from fabric_tpu.ledger.statedb import (
    Height,
    StateDB,
    UpdateBatch,
    VersionedDB,
    VersionedValue,
)
from fabric_tpu.protos import rwset as rwpb, transaction as txpb

logger = logging.getLogger("ledger.txmgr")


class PvtDataNotAvailable(Exception):
    """The key exists on-chain (hash present) but this peer holds no
    cleartext — the chaincode call must fail, not silently read None."""


# -- key metadata codec (state-based endorsement parameters etc.) --
# Stored form: a KVMetadataWrite with only `entries` set, deterministic.

def serialize_metadata(entries: dict[str, bytes]) -> bytes:
    mw = rwpb.KVMetadataWrite()
    for name in sorted(entries):
        mw.entries.add(name=name, value=entries[name])
    return mw.SerializeToString(deterministic=True)


def deserialize_metadata(raw: Optional[bytes]) -> dict[str, bytes]:
    if not raw:
        return {}
    mw = rwpb.KVMetadataWrite()
    mw.ParseFromString(raw)
    return {e.name: e.value for e in mw.entries}


def _pb_version(v: Optional[Height]) -> Optional[rwpb.Version]:
    if v is None:
        return None
    return rwpb.Version(block_num=v.block, tx_num=v.tx)


def _height_of(v: rwpb.Version) -> Optional[Height]:
    # proto3 can't distinguish "unset" from (0,0) on a submessage field
    # unless we check presence at the KVRead level
    return Height(v.block_num, v.tx_num)


class TxSimulator:
    """Collects a read-write set over the committed state (reference:
    lockbased_tx_simulator.go)."""

    def __init__(self, statedb: StateDB, tx_id: str = ""):
        self._db = statedb
        self.tx_id = tx_id
        self._reads: dict[tuple[str, str], Optional[Height]] = {}
        self._writes: dict[tuple[str, str], Optional[bytes]] = {}
        self._range_queries: list[rwpb.RangeQueryInfo] = []
        # private collections: hashed reads go on-chain for MVCC;
        # cleartext writes stay off-chain (reference:
        # lockbased_tx_simulator.go + rwsetutil pvt builders)
        self._pvt_reads: dict[tuple[str, str, str],
                              Optional[Height]] = {}
        self._pvt_writes: dict[tuple[str, str, str],
                               Optional[bytes]] = {}
        # key metadata updates (VALIDATION_PARAMETER etc.) — full-map
        # replacement per key, like the reference's SetStateMetadata
        self._metadata_writes: dict[tuple[str, str], dict[str, bytes]] = {}
        self._pvt_metadata_writes: dict[tuple[str, str, str],
                                        dict[str, bytes]] = {}
        self._done = False

    # -- chaincode-facing ops --

    def get_state(self, ns: str, key: str) -> Optional[bytes]:
        # read-your-writes within the simulation
        if (ns, key) in self._writes:
            return self._writes[(ns, key)]
        vv = self._db.get_state(ns, key)
        if (ns, key) not in self._reads:
            self._reads[(ns, key)] = vv.version if vv else None
        return vv.value if vv else None

    def put_state(self, ns: str, key: str, value: bytes) -> None:
        if not key:
            raise ValueError("empty key")
        self._writes[(ns, key)] = value

    def del_state(self, ns: str, key: str) -> None:
        self._writes[(ns, key)] = None

    def get_state_metadata(self, ns: str, key: str) -> dict[str, bytes]:
        """Key metadata map (read-your-writes). NOT recorded in the
        read-set — like the reference's queryExecutor metadata reads,
        which are not MVCC-tracked (the VSCC re-reads committed
        metadata at validation time instead)."""
        if (ns, key) in self._metadata_writes:
            return dict(self._metadata_writes[(ns, key)])
        return deserialize_metadata(
            self._db.get_state_metadata(ns, key))

    def set_state_metadata(self, ns: str, key: str,
                           metadata: dict[str, bytes]) -> None:
        if not key:
            raise ValueError("empty key")
        self._metadata_writes[(ns, key)] = dict(metadata)

    def get_state_range(self, ns: str, start: str, end: str,
                        limit: int = 0) -> list[tuple[str, bytes]]:
        """Range read with phantom protection: the returned keys (and
        their versions) are recorded as a RangeQueryInfo."""
        rqi = rwpb.RangeQueryInfo(start_key=start, end_key=end)
        out = []
        raw_reads = rqi.raw_reads
        exhausted = True
        for key, vv in self._db.get_state_range(ns, start, end):
            kr = raw_reads.kv_reads.add(key=key)
            kr.version.CopyFrom(_pb_version(vv.version))
            out.append((key, vv.value))
            if limit and len(out) >= limit:
                exhausted = False
                break
        rqi.itr_exhausted = exhausted
        self._range_queries.append((ns, rqi))
        return out

    def get_query_result(self, ns: str, query: str,
                         page_size: int = 0, bookmark: str = ""
                         ) -> tuple[list[tuple[str, bytes]], str]:
        """Rich (JSON selector) query against committed state
        (reference: statecouchdb ExecuteQuery). Returned keys are
        recorded as reads; result sets are NOT re-validated for
        phantoms (the documented CouchDB caveat)."""
        results, next_bm = self._db.execute_query(
            ns, query, page_size, bookmark)
        for key, _raw, version in results:
            if (ns, key) not in self._reads and \
                    (ns, key) not in self._writes:
                self._reads[(ns, key)] = version
        return [(k, raw) for k, raw, _v in results], next_bm

    # -- private data (reference: handler HandleGetState/PutState private
    #    variants → simulator GetPrivateData/SetPrivateData) --

    def get_private_data(self, ns: str, coll: str, key: str
                         ) -> Optional[bytes]:
        if (ns, coll, key) in self._pvt_writes:
            return self._pvt_writes[(ns, coll, key)]
        # MVCC read recorded against the HASHED version (identical on
        # every peer whether or not it holds the cleartext)
        hver = self._db.get_version(
            pvt.hash_ns(ns, coll),
            pvt.hashed_key_str(pvt.key_hash(key)))
        if (ns, coll, key) not in self._pvt_reads:
            self._pvt_reads[(ns, coll, key)] = hver
        vv = self._db.get_state(pvt.pvt_ns(ns, coll), key)
        if vv is None and hver is not None:
            raise PvtDataNotAvailable(
                f"private data for [{ns}/{coll}/{key}] exists on-chain "
                f"but this peer does not hold the cleartext")
        return vv.value if vv else None

    def get_private_data_hash(self, ns: str, coll: str, key: str
                              ) -> Optional[bytes]:
        """Readable by non-members too; records a HASHED read so
        decisions taken on the hash are MVCC-protected (reference
        GetPrivateDataHash — e.g. _lifecycle commit vs a concurrent
        re-approval)."""
        hver = self._db.get_version(
            pvt.hash_ns(ns, coll),
            pvt.hashed_key_str(pvt.key_hash(key)))
        if (ns, coll, key) not in self._pvt_reads and \
                (ns, coll, key) not in self._pvt_writes:
            self._pvt_reads[(ns, coll, key)] = hver
        vv = self._db.get_state(
            pvt.hash_ns(ns, coll),
            pvt.hashed_key_str(pvt.key_hash(key)))
        return vv.value if vv else None

    def put_private_data(self, ns: str, coll: str, key: str,
                         value: bytes) -> None:
        if not key:
            raise ValueError("empty key")
        self._pvt_writes[(ns, coll, key)] = value

    def del_private_data(self, ns: str, coll: str, key: str) -> None:
        self._pvt_writes[(ns, coll, key)] = None

    def get_private_data_metadata(self, ns: str, coll: str, key: str
                                  ) -> dict[str, bytes]:
        if (ns, coll, key) in self._pvt_metadata_writes:
            return dict(self._pvt_metadata_writes[(ns, coll, key)])
        return deserialize_metadata(self._db.get_state_metadata(
            pvt.hash_ns(ns, coll), pvt.hashed_key_str(pvt.key_hash(key))))

    def set_private_data_metadata(self, ns: str, coll: str, key: str,
                                  metadata: dict[str, bytes]) -> None:
        if not key:
            raise ValueError("empty key")
        self._pvt_metadata_writes[(ns, coll, key)] = dict(metadata)

    # -- result --

    def get_tx_simulation_results(self) -> rwpb.TxReadWriteSet:
        self._done = True
        by_ns: dict[str, rwpb.KVRWSet] = {}

        def ns_set(ns: str) -> rwpb.KVRWSet:
            if ns not in by_ns:
                by_ns[ns] = rwpb.KVRWSet()
            return by_ns[ns]

        for (ns, key), ver in sorted(self._reads.items()):
            kr = ns_set(ns).reads.add(key=key)
            if ver is not None:
                kr.version.CopyFrom(_pb_version(ver))
        for ns, rqi in self._range_queries:
            ns_set(ns).range_queries_info.add().CopyFrom(rqi)
        for (ns, key), value in sorted(self._writes.items()):
            kw = ns_set(ns).writes.add(key=key)
            if value is None:
                kw.is_delete = True
            else:
                kw.value = value
        for (ns, key), entries in sorted(self._metadata_writes.items()):
            mw = ns_set(ns).metadata_writes.add(key=key)
            for name in sorted(entries):
                mw.entries.add(name=name, value=entries[name])

        # hashed collection rwsets ride in the PUBLIC results — that is
        # what goes on-chain and what MVCC replays on every peer
        hashed_by_nc: dict[tuple[str, str], rwpb.HashedRWSet] = {}
        for (ns, coll, key), ver in sorted(self._pvt_reads.items()):
            h = hashed_by_nc.setdefault((ns, coll), rwpb.HashedRWSet())
            hr = h.hashed_reads.add(key_hash=pvt.key_hash(key))
            if ver is not None:
                hr.version.CopyFrom(_pb_version(ver))
        for (ns, coll, key), value in sorted(self._pvt_writes.items()):
            h = hashed_by_nc.setdefault((ns, coll), rwpb.HashedRWSet())
            hw = h.hashed_writes.add(key_hash=pvt.key_hash(key))
            if value is None:
                hw.is_delete = True
            else:
                hw.value_hash = pvt.value_hash(value)
        for (ns, coll, key), entries in sorted(
                self._pvt_metadata_writes.items()):
            h = hashed_by_nc.setdefault((ns, coll), rwpb.HashedRWSet())
            mw = h.metadata_writes.add(key_hash=pvt.key_hash(key))
            for name in sorted(entries):
                mw.entries.add(name=name, value=entries[name])

        pvt_colls = self._pvt_collection_rwsets()
        txrw = rwpb.TxReadWriteSet(data_model=rwpb.TxReadWriteSet.KV)
        all_ns = sorted(set(by_ns) | {ns for ns, _ in hashed_by_nc})
        for ns in all_ns:
            nsrw = txrw.ns_rwset.add(namespace=ns)
            nsrw.rwset = by_ns.get(ns, rwpb.KVRWSet()).SerializeToString(
                deterministic=True)
            for (hns, coll) in sorted(hashed_by_nc):
                if hns != ns:
                    continue
                chrw = nsrw.collection_hashed_rwset.add(
                    collection_name=coll)
                chrw.rwset = hashed_by_nc[(hns, coll)].SerializeToString(
                    deterministic=True)
                cleartext = pvt_colls.get((ns, coll))
                if cleartext is not None:
                    chrw.pvt_rwset_hash = pvt.pvt_rwset_hash(cleartext)
        return txrw

    def _pvt_collection_rwsets(self) -> dict[tuple[str, str], bytes]:
        """Marshaled cleartext KVRWSet per (ns, coll) — only collections
        with writes (reads need no cleartext distribution)."""
        by_nc: dict[tuple[str, str], rwpb.KVRWSet] = {}
        for (ns, coll, key), value in sorted(self._pvt_writes.items()):
            kv = by_nc.setdefault((ns, coll), rwpb.KVRWSet())
            kw = kv.writes.add(key=key)
            if value is None:
                kw.is_delete = True
            else:
                kw.value = value
        return {nc: kv.SerializeToString(deterministic=True)
                for nc, kv in by_nc.items()}

    def get_private_simulation_results(
            self) -> Optional[rwpb.TxPvtReadWriteSet]:
        """The cleartext side (endorser → transient store / gossip
        distribution). None when the tx touched no private writes."""
        colls = self._pvt_collection_rwsets()
        if not colls:
            return None
        txpvt = rwpb.TxPvtReadWriteSet(
            data_model=rwpb.TxReadWriteSet.KV)
        by_ns: dict[str, list[tuple[str, bytes]]] = {}
        for (ns, coll), raw in sorted(colls.items()):
            by_ns.setdefault(ns, []).append((coll, raw))
        for ns in sorted(by_ns):
            nspvt = txpvt.ns_pvt_rwset.add(namespace=ns)
            for coll, raw in by_ns[ns]:
                nspvt.collection_pvt_rwset.add(collection_name=coll,
                                               rwset=raw)
        return txpvt


def extract_tx_rwset(env_bytes: bytes) -> Optional[rwpb.TxReadWriteSet]:
    """Pull the simulation results out of a tx envelope; None if the
    envelope isn't a well-formed endorser tx."""
    try:
        action = pu.get_action_from_envelope(env_bytes)
        txrw = rwpb.TxReadWriteSet()
        txrw.ParseFromString(action.results)
        return txrw
    except Exception:
        return None


class CollRWSet(NamedTuple):
    """One collection's hashed rwset of one transaction, decoded."""
    name: str
    hashed_ns: str
    rwset: rwpb.HashedRWSet
    pvt_rwset_hash: bytes


class NsRWSet(NamedTuple):
    """One namespace of one transaction's rwset, decoded."""
    namespace: str
    kv: rwpb.KVRWSet
    colls: list[CollRWSet]


class BlockRWSets:
    """A block's rwsets decoded once (`parse_block_rwsets`), for MVCC,
    the private-data commit and history alike.

    `txs[i]` is the transaction's namespaces, or None where there is
    nothing to validate: flagged invalid upstream, no rwset, or one
    that does not parse (MVCC marks the last two BAD_RWSET). `keys`
    holds every (ns, key) a point read or a write of the block names
    — hashed ones under their hashed namespace — which is what MVCC
    reads from committed state, in one bulk read."""

    __slots__ = ("txs", "keys")

    def __init__(self, txs: list[Optional[list[NsRWSet]]],
                 keys: dict[tuple[str, str], None]):
        self.txs = txs
        self.keys = keys


def _parse_tx(txrw: rwpb.TxReadWriteSet, keys: dict) -> list[NsRWSet]:
    out = []
    for nsrw in txrw.ns_rwset:
        ns = nsrw.namespace
        kv = rwpb.KVRWSet()
        kv.ParseFromString(nsrw.rwset)
        for group in (kv.reads, kv.writes, kv.metadata_writes):
            for item in group:
                keys[(ns, item.key)] = None
        colls = []
        for chrw in nsrw.collection_hashed_rwset:
            hset = rwpb.HashedRWSet()
            hset.ParseFromString(chrw.rwset)
            hns = pvt.hash_ns(ns, chrw.collection_name)
            for group in (hset.hashed_reads, hset.hashed_writes,
                          hset.metadata_writes):
                for item in group:
                    keys[(hns, pvt.hashed_key_str(item.key_hash))] = None
            colls.append(CollRWSet(chrw.collection_name, hns, hset,
                                   chrw.pvt_rwset_hash))
        out.append(NsRWSet(ns, kv, colls))
    return out


def parse_block_rwsets(
        tx_rwsets: Sequence[Optional[rwpb.TxReadWriteSet]],
        flags: Optional[Sequence[int]] = None) -> BlockRWSets:
    """The one decoding pass over a block's rwsets (commit and crash
    recovery both start here). Transactions `flags` already marks
    invalid are not decoded: nothing reads them."""
    txs: list[Optional[list[NsRWSet]]] = []
    keys: dict[tuple[str, str], None] = {}
    for tx_num, txrw in enumerate(tx_rwsets):
        tx = None
        if txrw is not None and not (
                flags and flags[tx_num] != txpb.TxValidationCode.VALID):
            try:
                tx = _parse_tx(txrw, keys)
            except DecodeError:
                logger.warning("tx %d: rwset does not parse", tx_num)
        txs.append(tx)
    return BlockRWSets(txs, keys)


class _Committed:
    """Committed state as one block's MVCC sees it: the bulk read of
    the keys the block names, and a counted point read for a key it
    did not name (absent is an answer of the bulk read, not a miss)."""

    __slots__ = ("_db", "_got", "fallthrough")

    def __init__(self, statedb: VersionedDB, keys):
        self._db = statedb
        self._got = statedb.get_states_many(list(keys)) if keys else {}
        self.fallthrough = 0

    def __len__(self) -> int:
        return len(self._got)

    def get(self, ns: str, key: str) -> Optional[VersionedValue]:
        try:
            return self._got[(ns, key)]
        except KeyError:
            self.fallthrough += 1
            return self._db.get_state(ns, key)


class TxMgr:
    """Block-level validate-and-prepare (reference:
    `validation/validator.go` validateAndPrepareBatch)."""

    def __init__(self, statedb: VersionedDB):
        self.statedb = statedb
        # cumulative (the `ledger.mvcc` span books a block's share):
        # key reads MVCC has checked, keys it read from committed
        # state in bulk, point reads the bulk read could not answer
        self.reads_checked = 0
        self.prefetched = 0
        self.fallthrough = 0

    def validate_and_prepare(
        self, block_num: int,
        tx_rwsets: Sequence[Optional[rwpb.TxReadWriteSet]],
        flags: Optional[list[int]] = None,
        parsed: Optional[BlockRWSets] = None,
    ) -> tuple[list[int], UpdateBatch]:
        """For each tx (None = already invalid upstream): MVCC-check its
        reads against committed state + earlier in-block updates; valid
        txs contribute writes. `parsed` is `parse_block_rwsets` of the
        same rwsets and flags where the caller needs it afterwards too.
        Returns (validation codes, batch)."""
        n = len(tx_rwsets)
        codes = list(flags) if flags else \
            [txpb.TxValidationCode.VALID] * n
        if parsed is None:
            parsed = parse_block_rwsets(tx_rwsets, codes)
        committed = _Committed(self.statedb, parsed.keys)
        batch = UpdateBatch()

        for tx_num, tx in enumerate(parsed.txs):
            if codes[tx_num] != txpb.TxValidationCode.VALID:
                continue
            if tx is None:
                codes[tx_num] = txpb.TxValidationCode.BAD_RWSET
                continue
            code = self._validate_tx(tx, batch, committed)
            codes[tx_num] = code
            if code == txpb.TxValidationCode.VALID:
                self._apply_writes(tx, batch, committed,
                                   Height(block_num, tx_num))
        self.prefetched += len(committed)
        self.fallthrough += committed.fallthrough
        return codes, batch

    # -- per-tx checks --

    def _validate_tx(self, tx: list[NsRWSet], batch: UpdateBatch,
                     committed: _Committed) -> int:
        for ns, kv, colls in tx:
            self.reads_checked += len(kv.reads)
            for read in kv.reads:
                if not self._validate_read(ns, read.key, read, batch,
                                           committed):
                    return txpb.TxValidationCode.MVCC_READ_CONFLICT
            for rqi in kv.range_queries_info:
                if not self._validate_range_query(ns, rqi, batch):
                    return txpb.TxValidationCode.PHANTOM_READ_CONFLICT
            # hashed collection reads: same MVCC rule over the hashed
            # namespace (deterministic on every peer)
            for coll in colls:
                for hread in coll.rwset.hashed_reads:
                    if not self._validate_read(
                            coll.hashed_ns,
                            pvt.hashed_key_str(hread.key_hash), hread,
                            batch, committed):
                        return txpb.TxValidationCode.MVCC_READ_CONFLICT
        return txpb.TxValidationCode.VALID

    @staticmethod
    def _validate_read(ns: str, key: str, read, batch: UpdateBatch,
                       committed: _Committed) -> bool:
        """Reference: validator.go:174 validateKVRead — a read conflicts
        if the key was updated in this block by an earlier valid tx, or
        its committed version differs from the read version. `read` is
        a KVRead or a KVReadHash: the version is all that is read."""
        in_batch, _ = batch.get(ns, key)
        if in_batch:
            return False
        vv = committed.get(ns, key)
        read_ver = _height_of(read.version) if read.HasField("version") \
            else None
        return (vv.version if vv is not None else None) == read_ver

    def _validate_range_query(self, ns: str, rqi: rwpb.RangeQueryInfo,
                              batch: UpdateBatch) -> bool:
        """Reference: validator.go:213 validateRangeQuery — re-execute
        the range over (committed state + batch) and require the same
        keys/versions the simulator saw."""
        current: list[tuple[str, Optional[Height]]] = []
        seen = set()
        for key, vv in self.statedb.get_state_range(
                ns, rqi.start_key, rqi.end_key):
            in_batch, bv = batch.get(ns, key)
            if in_batch:
                seen.add(key)
                if bv is not None:
                    current.append((key, bv.version))
                continue
            current.append((key, vv.version))
        for (bns, key), bv in batch.updates.items():
            if bns != ns or key in seen or bv is None:
                continue
            if rqi.start_key <= key and (not rqi.end_key or
                                         key < rqi.end_key):
                current.append((key, bv.version))
        current.sort()

        expected = [
            (kr.key,
             _height_of(kr.version) if kr.HasField("version") else None)
            for kr in rqi.raw_reads.kv_reads
        ]
        if not rqi.itr_exhausted:
            # simulator stopped early: only the observed prefix must match
            current = current[:len(expected)]
        return current == expected

    @staticmethod
    def _existing(ns: str, key: str, batch: UpdateBatch,
                  committed: _Committed):
        """Current VersionedValue: this block's batch first, then
        committed state. None when absent/deleted."""
        in_batch, vv = batch.get(ns, key)
        if in_batch:
            return vv
        return committed.get(ns, key)

    def _apply_ns_writes(self, ns: str, writes, metadata_writes,
                        batch: UpdateBatch, committed: _Committed,
                        height: Height) -> None:
        """Value + metadata writes of one tx within one namespace.

        Reference semantics (validator batch preparation + statedb):
        a value write preserves the key's existing metadata unless the
        same tx also writes metadata; a metadata-only write to an
        absent key is a no-op; a delete clears both.
        """
        md_map = {}
        for mw in metadata_writes:
            md_map[mw.key] = serialize_metadata(
                {e.name: e.value for e in mw.entries})
        for w in writes:
            if w.is_delete:
                md_map.pop(w.key, None)
                batch.delete(ns, w.key, height)
                continue
            if w.key in md_map:
                md = md_map.pop(w.key)
            else:
                cur = self._existing(ns, w.key, batch, committed)
                md = cur.metadata if cur else b""
            batch.put(ns, w.key, w.value, height, metadata=md)
        for key, md in md_map.items():          # metadata-only updates
            cur = self._existing(ns, key, batch, committed)
            if cur is None:
                continue
            batch.put(ns, key, cur.value, height, metadata=md)

    def _apply_writes(self, tx: list[NsRWSet], batch: UpdateBatch,
                      committed: _Committed, height: Height) -> None:
        for ns, kv, colls in tx:
            self._apply_ns_writes(ns, kv.writes, kv.metadata_writes,
                                  batch, committed, height)
            for coll in colls:
                writes = [rwpb.KVWrite(
                    key=pvt.hashed_key_str(hw.key_hash),
                    is_delete=hw.is_delete, value=hw.value_hash)
                    for hw in coll.rwset.hashed_writes]
                mwrites = [rwpb.KVMetadataWrite(
                    key=pvt.hashed_key_str(mw.key_hash),
                    entries=mw.entries)
                    for mw in coll.rwset.metadata_writes]
                self._apply_ns_writes(coll.hashed_ns, writes, mwrites,
                                      batch, committed, height)
