"""Embedded ordered KV store.

Role of goleveldb in the reference (`common/ledger/util/leveldbhelper`,
used by the block index, statedb, history db, pvtdata store,
bookkeeping). The interface is leveldb-shaped — get/put/delete,
write-batch, ordered range iteration, named sub-DBs via key prefixes —
backed here by SQLite (stdlib, crash-safe WAL); the interface leaves
room for a C++ LSM engine drop-in if profiling demands it.

When and how bytes reach the disk — `journal_mode=WAL`,
`synchronous=NORMAL`, sqlite's default `wal_autocheckpoint` and
`page_size` — is the deployment's durability setting (the benchmark
configuration's `ledger` group states it), and a change of speed is no
reason to touch it. The page cache is not of that kind: it is memory,
not durability — how much of the file a store keeps by it, not what a
commit writes or when it is synced — and every store asks for
`CACHE_KIB` (64 MiB). A ledger's `index.db` is 17-20 MB after 180
default blocks (block index, history and state are prefixes of the one
file), so the cache holds all of it, where sqlite's default 2 MB made
every block re-read ~3,400 leaves and spill ~1,350 dirty pages to the
WAL before its commit (PERF.md, Findings PR 31 and PR 36). The bound on
memory is 64 MiB a `KVStore`, allocated page by page as touched, so a
small store pays for the pages it has: one store a channel's ledger,
one an orderer channel, one the transient store. On a chain whose
tx-id and history leaves have outgrown the cache (~600 default blocks)
those leaves miss again, while the state table's leaves and every
interior page stay hot.
"""

from __future__ import annotations

import sqlite3
import threading
from typing import Iterator, Optional

# page cache of one store's connection, in KiB (sqlite's default is 2,000)
CACHE_KIB = 65536


class WriteBatch:
    def __init__(self):
        self.ops: list[tuple[bytes, Optional[bytes]]] = []

    def put(self, key: bytes, value: bytes) -> None:
        self.ops.append((key, value))

    def delete(self, key: bytes) -> None:
        self.ops.append((key, None))


class KVStore:
    """One ordered keyspace on disk (":memory:" for tests)."""

    def __init__(self, path: str):
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        # cumulative SELECT statements (the `ledger.mvcc` span books a
        # block's share)
        self.selects = 0
        cur = self._conn.cursor()
        cur.execute("PRAGMA journal_mode=WAL")
        cur.execute("PRAGMA synchronous=NORMAL")
        cur.execute(f"PRAGMA cache_size=-{CACHE_KIB}")
        cur.execute("CREATE TABLE IF NOT EXISTS kv "
                    "(k BLOB PRIMARY KEY, v BLOB NOT NULL) WITHOUT ROWID")
        self._conn.commit()

    def get(self, key: bytes) -> Optional[bytes]:
        with self._lock:
            self.selects += 1
            row = self._conn.execute(
                "SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
        return row[0] if row else None

    def get_many(self, keys: list[bytes]) -> dict[bytes, bytes]:
        """Present keys only — one SELECT..IN per 500 keys instead of a
        round trip each (the block validator's dup-txid and key-metadata
        probes are whole-block batches)."""
        out: dict[bytes, bytes] = {}
        with self._lock:
            for lo in range(0, len(keys), 500):
                chunk = keys[lo:lo + 500]
                self.selects += 1
                q = ("SELECT k, v FROM kv WHERE k IN (%s)"
                     % ",".join("?" * len(chunk)))
                for k, v in self._conn.execute(q, chunk):
                    out[bytes(k)] = bytes(v)
        return out

    def put(self, key: bytes, value: bytes) -> None:
        with self._lock:
            self._conn.execute(
                "INSERT INTO kv(k, v) VALUES(?, ?) "
                "ON CONFLICT(k) DO UPDATE SET v = excluded.v",
                (key, value))
            self._conn.commit()

    def delete(self, key: bytes) -> None:
        with self._lock:
            self._conn.execute("DELETE FROM kv WHERE k = ?", (key,))
            self._conn.commit()

    def write_batch(self, batch: WriteBatch, sync: bool = True) -> None:
        """Atomic multi-op commit (leveldb WriteBatch semantics).

        Ops run as executemany over maximal same-kind runs — one
        Python→SQLite call per run, not per op (a 10k-tx block's index
        batch is ~10k puts; per-op execute was a measured slice of the
        commit floor). Runs preserve put/delete ordering per key."""
        with self._lock:
            cur = self._conn.cursor()
            ops = batch.ops
            i, n = 0, len(ops)
            while i < n:
                j = i
                is_del = ops[i][1] is None
                while j < n and (ops[j][1] is None) == is_del:
                    j += 1
                if is_del:
                    cur.executemany("DELETE FROM kv WHERE k = ?",
                                    [(k,) for k, _ in ops[i:j]])
                else:
                    cur.executemany(
                        "INSERT INTO kv(k, v) VALUES(?, ?) "
                        "ON CONFLICT(k) DO UPDATE SET v = excluded.v",
                        ops[i:j])
                i = j
            self._conn.commit()

    def iterate(self, start: bytes = b"", end: Optional[bytes] = None
                ) -> Iterator[tuple[bytes, bytes]]:
        """Ordered [start, end) scan; end=None = to the end of keyspace."""
        with self._lock:
            self.selects += 1
            if end is None:
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k >= ? ORDER BY k",
                    (start,)).fetchall()
            else:
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k >= ? AND k < ? "
                    "ORDER BY k", (start, end)).fetchall()
        yield from ((bytes(k), bytes(v)) for k, v in rows)

    def close(self) -> None:
        with self._lock:
            self._conn.commit()
            self._conn.close()


class DBHandle:
    """A named sub-keyspace of a KVStore (reference:
    leveldbhelper.Provider GetDBHandle — one physical DB, per-ledger
    prefixes)."""

    def __init__(self, store: KVStore, name: str):
        self._store = store
        self._prefix = name.encode() + b"\x00"

    def _k(self, key: bytes) -> bytes:
        return self._prefix + key

    def get(self, key: bytes) -> Optional[bytes]:
        return self._store.get(self._k(key))

    def get_many(self, keys: list[bytes]) -> dict[bytes, bytes]:
        """Present keys only, unprefixed."""
        plen = len(self._prefix)
        got = self._store.get_many([self._k(k) for k in keys])
        return {k[plen:]: v for k, v in got.items()}

    def put(self, key: bytes, value: bytes) -> None:
        self._store.put(self._k(key), value)

    def delete(self, key: bytes) -> None:
        self._store.delete(self._k(key))

    def new_batch(self) -> "PrefixedBatch":
        return PrefixedBatch(self._prefix)

    def write_batch(self, batch: "PrefixedBatch") -> None:
        self._store.write_batch(batch)

    def iterate(self, start: bytes = b"", end: Optional[bytes] = None):
        lo = self._k(start)
        hi = self._k(end) if end is not None else \
            self._prefix[:-1] + b"\x01"   # one past the prefix byte
        for k, v in self._store.iterate(lo, hi):
            yield k[len(self._prefix):], v


class PrefixedBatch(WriteBatch):
    def __init__(self, prefix: bytes):
        super().__init__()
        self._prefix = prefix

    def put(self, key: bytes, value: bytes) -> None:
        super().put(self._prefix + key, value)

    def delete(self, key: bytes) -> None:
        super().delete(self._prefix + key)
