"""Versioned state database.

Rebuild of `core/ledger/kvledger/txmgmt/statedb/` (statedb.go interface
+ stateleveldb impl): world state as (namespace, key) → (version,
value); version = (block, tx) height of the writing transaction — the
MVCC clock. A savepoint records the last committed height for
crash recovery (reference: bookkeeping + statedb savepoint key).
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Iterator, Optional

from fabric_tpu.ledger.kvdb import DBHandle

_SAVEPOINT = b"\x00savepoint"
_SEP = b"\x00"


@dataclass(frozen=True, order=True)
class Height:
    block: int
    tx: int

    def pack(self) -> bytes:
        return struct.pack(">QQ", self.block, self.tx)

    @classmethod
    def unpack(cls, raw: bytes) -> "Height":
        b, t = struct.unpack(">QQ", raw)
        return cls(b, t)


@dataclass
class VersionedValue:
    value: bytes
    version: Height
    # serialized repeated KVMetadataEntry (state-based endorsement
    # parameters etc.) — shares the key's version, exactly like the
    # reference's statedb.VersionedValue{Value, Metadata, Version}
    metadata: bytes = b""


class UpdateBatch:
    """Accumulates the writes of one block's valid txs (reference:
    statedb.UpdateBatch)."""

    def __init__(self):
        self.updates: dict[tuple[str, str], Optional[VersionedValue]] = {}

    def put(self, ns: str, key: str, value: bytes, version: Height,
            metadata: bytes = b"") -> None:
        self.updates[(ns, key)] = VersionedValue(value, version, metadata)

    def delete(self, ns: str, key: str, version: Height) -> None:
        self.updates[(ns, key)] = None

    def get(self, ns: str, key: str):
        """(present, versioned_value_or_None)."""
        if (ns, key) in self.updates:
            return True, self.updates[(ns, key)]
        return False, None


def _encode(vv: VersionedValue) -> bytes:
    """version(16) | u32 metadata length | metadata | value."""
    md = vv.metadata or b""
    return vv.version.pack() + struct.pack(">I", len(md)) + md + vv.value


def _decode(raw: bytes) -> VersionedValue:
    version = Height.unpack(raw[:16])
    (mdlen,) = struct.unpack(">I", raw[16:20])
    return VersionedValue(raw[20 + mdlen:], version, raw[20:20 + mdlen])


def _parse_doc(value: bytes):
    """JSON document or None (non-JSON / non-object values carry no
    index entries)."""
    import json as _json
    try:
        doc = _json.loads(value)
    except Exception:
        return None
    return doc if isinstance(doc, dict) else None


_IDX_PREFIX = b"\x00idx\x00"     # system keyspace (leading NUL: no
#                                  namespace key can start with it)
_IDX_DEF_PREFIX = b"\x00idxdef\x00"   # persisted index definitions
_IDX_SEP = b"\x00\x00"


class VersionedDB:
    """The pluggable state-database seam (reference:
    `core/ledger/kvledger/txmgmt/statedb/statedb.go` VersionedDB).

    Everything above this line — TxMgr/TxSimulator MVCC, the
    committer, snapshots, fastvalidate's metadata probes — talks ONLY
    to this surface, so a deployment can swap the embedded engine for
    an external service (statehttp.HTTPVersionedDB is the in-tree
    example, playing CouchDB's role: rich queries execute inside the
    database with its own indexes and pagination).

    Contract notes: `get_state_range` yields (key, VersionedValue) in
    key order over [start, end) (end="" = unbounded within ns);
    `execute_query` returns ([(key, raw_value, Height)], bookmark);
    `apply_updates` must persist the batch and savepoint atomically;
    `savepoint()` is None only before the first apply_updates."""

    def get_state(self, ns: str, key: str):
        raise NotImplementedError

    def get_state_metadata(self, ns: str, key: str):
        raise NotImplementedError

    def get_state_metadata_many(self, wanted):
        return {nk: self.get_state_metadata(*nk) for nk in wanted}

    def get_states_many(self, pairs):
        """{(ns, key): VersionedValue | None} for every pair asked:
        absent is an answer. A block's MVCC reads committed state
        through this once; a backend with a bulk read overrides it."""
        return {nk: self.get_state(*nk) for nk in pairs}

    def get_version(self, ns: str, key: str):
        vv = self.get_state(ns, key)
        return vv.version if vv is not None else None

    def get_state_range(self, ns: str, start_key: str, end_key: str):
        raise NotImplementedError

    def execute_query(self, ns: str, query: str, page_size: int = 0,
                      bookmark: str = ""):
        raise NotImplementedError

    def define_index(self, ns: str, name: str, index_json: str) -> None:
        raise NotImplementedError

    def apply_updates(self, batch: "UpdateBatch", height: Height) -> None:
        raise NotImplementedError

    def apply_writes_only(self, batch: "UpdateBatch") -> None:
        raise NotImplementedError

    def savepoint(self) -> Optional[Height]:
        raise NotImplementedError

    def iterate_all(self):
        raise NotImplementedError

    def close(self) -> None:
        pass


class StateDB(VersionedDB):
    def __init__(self, db: DBHandle):
        self._db = db
        # materialized rich-query indexes (reference: statecouchdb's
        # CouchDB Mango indexes from chaincode META-INF). Entries live
        # in the SAME keyspace/batch as state writes, and the
        # DEFINITIONS are persisted alongside, so a restarted node
        # keeps maintaining (and serving) its indexes.
        from fabric_tpu.ledger import richquery
        self.indexes = richquery.IndexRegistry()
        self.query_stats = {"index_scans": 0, "full_scans": 0}
        for k, v in self._db.iterate(
                _IDX_DEF_PREFIX,
                _IDX_DEF_PREFIX[:-1] + b"\x01"):
            try:
                ns_b, name_b = k[len(_IDX_DEF_PREFIX):].split(
                    _IDX_SEP, 1)
                self.indexes.define(ns_b.decode(), name_b.decode(),
                                    v.decode())
            except Exception:
                import logging
                logging.getLogger("statedb").exception(
                    "unreadable persisted index definition %r", k)

    # -- materialized index plumbing --

    @staticmethod
    def _idx_key(ns: str, name: str, enc_values: list[bytes],
                 state_key: str) -> bytes:
        from fabric_tpu.ledger.richquery import _escape
        parts = [_escape(ns.encode()), _escape(name.encode())]
        parts.extend(enc_values)
        parts.append(_escape(state_key.encode()))
        return _IDX_PREFIX + _IDX_SEP.join(parts)

    def _idx_entries(self, ns: str, key: str, value: bytes,
                     idxs: dict = None) -> list[bytes]:
        """Index keys a (ns, key, value) document contributes (empty
        for non-JSON values or docs missing an indexed field). The
        document parses ONCE regardless of index count."""
        if idxs is None:
            idxs = self.indexes.for_ns(ns)
        doc = _parse_doc(value)
        if doc is None:
            return []
        out = []
        for name, fields in idxs.items():
            out.extend(self._entries_for_index(ns, name, fields, key,
                                               value, doc=doc))
        return out

    def _maintain_indexes(self, wb, ns: str, key: str,
                          new_vv: Optional[VersionedValue]) -> None:
        idxs = self.indexes.for_ns(ns)
        if not idxs:
            return
        old = self.get_state(ns, key)
        if old is not None:
            for ik in self._idx_entries(ns, key, old.value, idxs):
                wb.delete(ik)
        if new_vv is not None:
            for ik in self._idx_entries(ns, key, new_vv.value, idxs):
                wb.put(ik, b"")

    def _entries_for_index(self, ns: str, name: str,
                           fields: list, key: str,
                           value: bytes, doc=None) -> list[bytes]:
        """Index keys one (key, value) contributes to ONE index."""
        from fabric_tpu.ledger import richquery
        if doc is None:
            doc = _parse_doc(value)
        if doc is None:
            return []
        enc = []
        for f in fields:
            found, v = richquery._field(doc, f)
            if not found:
                return []
            enc.append(richquery.encode_index_value(v))
        return [self._idx_key(ns, name, enc, key)]

    def define_index(self, ns: str, name: str,
                     index_json: str) -> None:
        """Register an index, persist its definition, and (re)build it
        over existing state (reference: installing a chaincode's
        META-INF index into CouchDB triggers an index build). A
        re-install first drops the old entries, so stale values never
        linger."""
        from fabric_tpu.ledger.richquery import _escape
        def_key = (_IDX_DEF_PREFIX + _escape(ns.encode()) + _IDX_SEP +
                   _escape(name.encode()))
        if self._db.get(def_key) == index_json.encode():
            self.indexes.define(ns, name, index_json)
            return                       # already built, same shape
        self.indexes.define(ns, name, index_json)
        fields = self.indexes.fields(ns, name)
        # drop any previous incarnation of this index's entries
        base = (_IDX_PREFIX + _escape(ns.encode()) + _IDX_SEP +
                _escape(name.encode()) + _IDX_SEP)
        wb = self._db.new_batch()
        for k, _v in self._db.iterate(base, base[:-1] + b"\x01"):
            wb.delete(k)
        for key, vv in self.get_state_range(ns, "", ""):
            for ik in self._entries_for_index(ns, name, fields, key,
                                              vv.value):
                wb.put(ik, b"")
            if len(wb.ops) >= 10000:
                self._db.write_batch(wb)
                wb = self._db.new_batch()
        wb.put(def_key, index_json.encode())
        self._db.write_batch(wb)

    def index_scan(self, ns: str, name: str, enc_lo: bytes,
                   enc_hi: bytes, start_after: bytes = None):
        """State keys whose leading indexed value falls in
        [enc_lo, enc_hi), in index order. `start_after` (an index key
        from a previous page's bookmark) SEEKS the scan — pagination
        is O(page), not O(scanned-so-far)."""
        from fabric_tpu.ledger.richquery import _escape, _unescape
        base = _IDX_PREFIX + _escape(ns.encode()) + _IDX_SEP + \
            _escape(name.encode()) + _IDX_SEP
        lo = base + enc_lo
        hi = base + enc_hi
        if start_after is not None:
            if start_after >= hi:
                return
            lo = max(lo, start_after + b"\x00")
        for k, _v in self._db.iterate(lo, hi):
            yield (_unescape(k.split(_IDX_SEP)[-1]).decode(), k)

    @staticmethod
    def _k(ns: str, key: str) -> bytes:
        return ns.encode() + _SEP + key.encode()

    def get_state(self, ns: str, key: str) -> Optional[VersionedValue]:
        raw = self._db.get(self._k(ns, key))
        if raw is None:
            return None
        return _decode(raw)

    def get_state_metadata(self, ns: str, key: str) -> Optional[bytes]:
        """Serialized metadata entries of a key, or None when the key is
        absent/has no metadata (reference: statedb GetStateMetadata)."""
        vv = self.get_state(ns, key)
        return vv.metadata if vv and vv.metadata else None

    def get_states_many(
            self, pairs: list[tuple[str, str]]
    ) -> dict[tuple[str, str], Optional[VersionedValue]]:
        """One bulk read (a statement per 500 keys, in key order) in
        place of a point read per pair."""
        by_raw = {self._k(ns, k): (ns, k) for ns, k in pairs}
        raw = self._db.get_many(sorted(by_raw))
        return {nk: _decode(raw[rk]) if rk in raw else None
                for rk, nk in by_raw.items()}

    def get_state_metadata_many(
            self, pairs: list[tuple[str, str]]
    ) -> dict[tuple[str, str], Optional[bytes]]:
        """Batched get_state_metadata over (ns, key) pairs — one probe
        per block for the key-level validation-parameter lookups instead
        of one per written key."""
        return {nk: vv.metadata if vv and vv.metadata else None
                for nk, vv in self.get_states_many(pairs).items()}

    def get_version(self, ns: str, key: str) -> Optional[Height]:
        vv = self.get_state(ns, key)
        return vv.version if vv else None

    def get_state_range(self, ns: str, start_key: str, end_key: str
                        ) -> Iterator[tuple[str, VersionedValue]]:
        """[start, end) ordered scan within a namespace; empty end_key
        scans to the namespace end (reference: GetStateRangeScanIterator)."""
        lo = self._k(ns, start_key)
        # next-prefix bound: every key of `ns` starts with ns+\x00, so
        # ns+\x01 is one past the whole namespace
        hi = self._k(ns, end_key) if end_key else ns.encode() + b"\x01"
        for k, raw in self._db.iterate(lo, hi):
            key = k.split(_SEP, 1)[1].decode()
            yield key, _decode(raw)

    def apply_updates(self, batch: UpdateBatch, height: Height) -> None:
        """Atomically apply a block's updates + the savepoint
        (reference: stateleveldb ApplyUpdates). Materialized index
        entries ride the same batch."""
        wb = self._db.new_batch()
        for (ns, key), vv in batch.updates.items():
            self._maintain_indexes(wb, ns, key, vv)
            if vv is None:
                wb.delete(self._k(ns, key))
            else:
                wb.put(self._k(ns, key), _encode(vv))
        wb.put(_SAVEPOINT, height.pack())
        self._db.write_batch(wb)

    def iterate_all(self) -> Iterator[tuple[str, str, VersionedValue]]:
        """Every (ns, key, versioned value), ordered — the snapshot
        export walk (reference: statedb GetFullScanIterator). Keys
        with a leading NUL are system keyspaces (savepoint,
        materialized indexes — derived data, rebuilt not exported)."""
        for k, raw in self._db.iterate(start=b"", end=None):
            if k.startswith(b"\x00"):
                continue
            ns, _, key = k.partition(_SEP)
            yield (ns.decode(), key.decode(), _decode(raw))

    def apply_writes_only(self, batch: UpdateBatch) -> None:
        """Apply updates WITHOUT advancing the savepoint — the
        reconciliation path back-fills old-block private data and must
        not disturb crash-recovery bookkeeping."""
        wb = self._db.new_batch()
        for (ns, key), vv in batch.updates.items():
            self._maintain_indexes(wb, ns, key, vv)
            if vv is None:
                wb.delete(self._k(ns, key))
            else:
                wb.put(self._k(ns, key), _encode(vv))
        self._db.write_batch(wb)

    def savepoint(self) -> Optional[Height]:
        raw = self._db.get(_SAVEPOINT)
        return Height.unpack(raw) if raw else None

    def execute_query(self, ns: str, query: str, page_size: int = 0,
                      bookmark: str = ""):
        """Rich (Mango-selector) query — the engine's own planner and
        materialized indexes (reference: statecouchdb ExecuteQuery)."""
        from fabric_tpu.ledger import richquery
        return richquery.execute_query(self, ns, query, page_size,
                                       bookmark)
