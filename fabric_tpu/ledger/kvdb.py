"""Embedded ordered KV store.

Role of goleveldb in the reference (`common/ledger/util/leveldbhelper`,
used by the block index, statedb, history db, pvtdata store,
bookkeeping). The interface is leveldb-shaped — get/put/delete,
write-batch, ordered range iteration, named sub-DBs via key prefixes —
backed here by SQLite (stdlib, crash-safe WAL); the interface leaves
room for a C++ LSM engine drop-in if profiling demands it.

When and how bytes reach the disk — `journal_mode=WAL`,
`synchronous=NORMAL` and `page_size` — is the deployment's durability
setting (the benchmark configuration's `ledger` group states it), and a
change of speed is no reason to touch it. A commit appends its pages to
the WAL unsynced; a checkpoint syncs the WAL, copies its frames back
into the database file and syncs that. Who checkpoints depends on the
store's owner:

- a channel's ledger asks its `index.db` for `checkpoint_behind()`: the
  writer stops auto-checkpointing (`wal_autocheckpoint=0`) and every
  commit requests a `PRAGMA wal_checkpoint(PASSIVE)` on a second
  connection that one daemon thread owns, which waits for no reader and
  no writer. `KVLedger` calls `settle()` before a block's first write;
- every other store keeps sqlite's inline auto-checkpoint, which runs
  inside whichever commit leaves the WAL at 1,000 frames or more (the
  orderer's block index, the transient store, `stateserver`,
  `ledgerutil`, `nodeops`): none has a block boundary to settle at.

The ledger's sync points are then: the block file is appended and
fsynced before any derived write, and that fsync makes a block durable
(`BlockStore.add_block`); every commit's frames are synced (WAL fsync,
backfill, database fsync) by a checkpoint that starts after that
commit, at least as often as the auto-checkpoint, which waits for 1,000
frames; every checkpoint a block requested has finished before the next
block writes anything, where the auto-checkpoint gave no such bound. A
crash loses what it lost before: derived writes of blocks whose block
file is fsynced, which recovery replays; after a clean close the file
holds the same rows. A WAL copied whole is written again from its first
frame by the next commit, so it stays about a block long.

The page cache is not of that kind: it is memory, not durability — how
much of the file a store keeps by it, not what a commit writes or when
it is synced — and every store asks for
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

import contextlib
import sqlite3
import threading
import weakref
from typing import Iterator, Optional

from fabric_tpu.common import tracing
from fabric_tpu.common.flogging import must_get_logger

logger = must_get_logger("kvdb")

# page cache of one store's connection, in KiB (sqlite's default is 2,000)
CACHE_KIB = 65536


class _Checkpointer:
    """The WAL checkpoint of one store on a daemon thread with a
    connection of its own. `request()` after each commit; requests made
    while a checkpoint runs coalesce into one more run after it.
    `settle()` waits for every checkpoint requested so far.
    `writer_lock` is the lock every statement of the store's own
    connection holds."""

    def __init__(self, path: str, writer_lock: threading.Lock):
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute("PRAGMA synchronous=NORMAL")
        self._writer_lock = writer_lock
        self._cv = threading.Condition()
        self._requested = 0
        self._done = 0          # requests the finished checkpoints covered
        self._stopped = False
        self._thread = threading.Thread(
            target=self._run, name="kvdb-checkpoint", daemon=True)
        self._thread.start()

    def request(self) -> None:
        with self._cv:
            self._requested += 1
            self._cv.notify_all()

    def settle(self) -> None:
        with self._cv:
            want = self._requested
            while self._done < want and not self._stopped:
                self._cv.wait()

    def stop(self) -> None:
        """The thread exits without another checkpoint and closes its
        connection; what the WAL holds stays there for the next one."""
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        # the store's finalizer may run on any thread, this one too
        if self._thread is not threading.current_thread():
            self._thread.join()

    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    while self._done == self._requested and \
                            not self._stopped:
                        self._cv.wait()
                    if self._stopped:
                        return
                    want = self._requested
                    covered = want - self._done
                self._checkpoint(covered)
                with self._cv:
                    self._done = want
                    self._cv.notify_all()
        finally:
            with self._cv:
                self._stopped = True
                self._cv.notify_all()
            self._conn.close()

    def _pass(self) -> tuple[int, int]:
        """(frames in the WAL, frames copied back) of one PASSIVE pass."""
        _busy, log, backfilled = self._conn.execute(
            "PRAGMA wal_checkpoint(PASSIVE)").fetchone()
        return log, backfilled

    def _checkpoint(self, requests: int) -> None:
        sp = tracing.span("ledger.checkpoint", requests=requests)
        try:
            with sp, tracing.thread_io(sp):
                log, backfilled = self._pass()
                passes = 1
                if backfilled < log:
                    # a statement of the writer's connection began while
                    # the pass held the read marks and took an older
                    # one, which bounds the copy; under the writer's
                    # lock none can
                    with self._writer_lock:
                        log, backfilled = self._pass()
                    passes = 2
                sp.set(log=log, backfilled=backfilled, passes=passes)
        except sqlite3.Error:
            # the frames stay in the WAL: the next checkpoint, or the
            # last connection's close, copies them
            logger.exception("WAL checkpoint failed")


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
        self._path = path
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        self._ckpt: Optional[_Checkpointer] = None
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

    def checkpoint_behind(self) -> None:
        """Checkpoint on a thread of its own, behind this store's writer
        (module docstring): every commit from now on requests one, and
        `settle()` waits for them. A ":memory:" store has no file a
        second connection could reach and keeps its inline checkpoint."""
        if self._path == ":memory:" or self._ckpt is not None:
            return
        with self._lock:
            self._conn.execute("PRAGMA wal_autocheckpoint=0")
            # a WAL frame: a 24-byte header and a page
            self._frame_bytes = self._conn.execute(
                "PRAGMA page_size").fetchone()[0] + 24
        ckpt = self._ckpt = _Checkpointer(self._path, self._lock)
        # a store dropped without close() takes its thread with it
        self._stop_ckpt = weakref.finalize(self, ckpt.stop)

    def settle(self) -> None:
        """Return once every checkpoint requested so far has finished."""
        if self._ckpt is not None:
            self._ckpt.settle()

    def _committed(self) -> None:
        if self._ckpt is not None:
            self._ckpt.request()

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

    def _book_frames(self, sp, syscr: int, syscw: int, wchar: int) -> None:
        sp.set(frames=wchar // self._frame_bytes)

    @contextlib.contextmanager
    def _writing(self, ops: int) -> Iterator[sqlite3.Connection]:
        """The one commit path of the store (`put`, `delete`,
        `write_batch`): the body's statements under the writer's lock,
        then the commit. A `kvdb.write` span covers the whole — `ops`,
        the rows put or deleted, and, on a store that checkpoints
        behind, `frames`: the WAL frames the commit appended (the
        thread's bytes written over the span, a cache spill during the
        statements included, over a frame's size). A store that
        checkpoints inline reads nothing: a commit there may copy pages
        into the file as well. Its child `kvdb.commit` is sqlite's
        writing of the frames; the span's self time is the lock, the
        statements, the B-tree work in the page cache and, where
        `frames` is booked, the two readings of it."""
        sp = tracing.span("kvdb.write", ops=ops)
        io = tracing.thread_io(sp, self._book_frames) \
            if self._ckpt is not None else contextlib.nullcontext()
        with sp, io, self._lock:
            yield self._conn
            with tracing.span("kvdb.commit"):
                self._conn.commit()
        self._committed()

    def put(self, key: bytes, value: bytes) -> None:
        with self._writing(1) as conn:
            conn.execute(
                "INSERT INTO kv(k, v) VALUES(?, ?) "
                "ON CONFLICT(k) DO UPDATE SET v = excluded.v",
                (key, value))

    def delete(self, key: bytes) -> None:
        with self._writing(1) as conn:
            conn.execute("DELETE FROM kv WHERE k = ?", (key,))

    def write_batch(self, batch: WriteBatch, sync: bool = True) -> None:
        """Atomic multi-op commit (leveldb WriteBatch semantics).

        Ops run as executemany over maximal same-kind runs — one
        Python→SQLite call per run, not per op (a 10k-tx block's index
        batch is ~10k puts; per-op execute was a measured slice of the
        commit floor). Runs preserve put/delete ordering per key."""
        ops = batch.ops
        with self._writing(len(ops)) as conn:
            cur = conn.cursor()
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
        """Settles, stops the checkpointer and closes its connection
        first, so that the writer's close, the last, checkpoints the WAL
        and removes it."""
        if self._ckpt is not None:
            self._ckpt.settle()
            self._stop_ckpt()
            self._ckpt = None
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
