"""The store books every commit, and `thread_io` books no read of its own.

`KVStore` opens a `kvdb.write` span around each commit (`ops`, and
`frames` on a store that checkpoints behind) with a `kvdb.commit` child
around sqlite's commit call. The frames are the thread's bytes written
over the span divided by a frame's size, held here to the WAL-index
header's own count (`mxFrame`, offset 16 of the `-shm` file:
https://www.sqlite.org/walformat.html).
"""

import os
import random
import struct

import pytest

from fabric_tpu.common import tracing
from fabric_tpu.ledger.kvdb import KVStore, WriteBatch

needs_io = pytest.mark.skipif(
    tracing._thread_io() is None,
    reason="the kernel keeps no per-thread I/O account here")


@pytest.fixture
def recorder():
    tracing.configure(enabled=True, ring_size=4096, sample_every=1)
    tracing.reset()
    yield
    tracing.configure(enabled=True, ring_size=4096)
    tracing.reset()


def _events(name: str) -> list:
    return [e for e in tracing.snapshot() if e[1] == name]


def _wal_index(path) -> tuple:
    """(mxFrame, salts) of the WAL-index header: the frames the WAL
    holds, and what changes when the WAL restarts from its first frame."""
    with open(str(path) + "-shm", "rb") as f:
        head = f.read(48)
    return struct.unpack_from("=I", head, 16)[0], head[32:40]


@needs_io
@pytest.mark.parametrize("behind", [False, True])
def test_frames_are_the_wal_index_headers_count(tmp_path, recorder, behind):
    """Every commit path, a WAL restart after each settled checkpoint
    among them. A store that checkpoints inline books none: a commit
    there may copy pages into the file too."""
    path = tmp_path / "index.db"
    store = KVStore(str(path))
    if behind:
        store.checkpoint_behind()
    rng = random.Random(40)
    restarts = 0
    for rnd in range(8):
        before = _wal_index(path)
        if rnd == 3:
            store.put(rng.randbytes(32), bytes(100))
            ops = 1
        elif rnd == 5:
            store.delete(b"absent")
            ops = 1
        else:
            batch = WriteBatch()
            ops = rng.randrange(50, 1500)
            for _ in range(ops):
                batch.put(rng.randbytes(32), rng.randbytes(100))
            store.write_batch(batch)
        after = _wal_index(path)
        restarted = after[1] != before[1]
        restarts += restarted
        want = after[0] if restarted else after[0] - before[0]
        write = _events("kvdb.write")[-1][8]
        assert write["ops"] == ops
        if behind:
            assert write["frames"] == want, (rnd, write, before, after)
        else:
            assert "frames" not in write
        store.settle()
    assert restarts >= (6 if behind else 0)
    store.close()


def test_each_commit_has_its_commit_child(tmp_path, recorder):
    store = KVStore(str(tmp_path / "index.db"))
    batch = WriteBatch()
    batch.put(b"a", b"1")
    batch.delete(b"b")
    store.write_batch(batch)
    store.put(b"c", b"2")
    store.delete(b"a")
    writes, commits = _events("kvdb.write"), _events("kvdb.commit")
    assert [w[8]["ops"] for w in writes] == [2, 1, 1]
    assert [c[4] for c in commits] == [w[3] for w in writes]
    assert all(c[8] is None for c in commits)
    # the writes open no span of their own beyond the two
    assert {e[1] for e in tracing.snapshot()} == {"kvdb.write",
                                                  "kvdb.commit"}
    store.close()


def test_a_memory_store_books_no_frames(recorder, monkeypatch):
    reads = []
    monkeypatch.setattr(tracing, "_thread_io", lambda: reads.append(1))
    store = KVStore(":memory:")
    store.checkpoint_behind()       # has no file to checkpoint behind
    batch = WriteBatch()
    for i in range(2000):
        batch.put(b"%05d" % i, bytes(200))
    store.write_batch(batch)
    write = _events("kvdb.write")[-1][8]
    assert write == {"ops": 2000}
    assert reads == []
    store.close()


def test_disabled_records_and_reads_nothing(tmp_path, recorder,
                                             monkeypatch):
    store = KVStore(str(tmp_path / "index.db"))
    store.checkpoint_behind()
    tracing.set_enabled(False)
    reads = []
    monkeypatch.setattr(tracing, "_thread_io",
                        lambda: reads.append(1))
    batch = WriteBatch()
    batch.put(b"a", b"1")
    store.write_batch(batch)
    store.put(b"b", b"2")
    store.delete(b"a")
    store.settle()
    assert reads == []
    assert tracing.snapshot() == []
    assert store.get(b"b") == b"2" and store.get(b"a") is None
    store.close()


@needs_io
def test_a_span_around_nothing_books_no_read(recorder):
    sp = tracing.span("probe")
    with sp, tracing.thread_io(sp):
        pass
    assert _events("probe")[-1][8] == {"syscr": 0, "syscw": 0}


@needs_io
def test_nested_readings_leave_the_outer_count_alone(tmp_path, recorder):
    """Three nested `thread_io` spans and two reads of a file: the
    outer span books the two reads, each inner one none, and a `book`
    of its own gets the bytes written."""
    path = tmp_path / "f"
    path.write_bytes(bytes(64))
    fd = os.open(path, os.O_RDWR)
    got = []
    try:
        outer = tracing.span("outer")
        with outer, tracing.thread_io(outer):
            for _ in range(3):
                inner = tracing.span("inner")
                with inner, tracing.thread_io(inner):
                    pass
            os.pread(fd, 16, 0)
            os.pread(fd, 16, 16)
            wrote = tracing.span("wrote")
            with wrote, tracing.thread_io(
                    wrote, lambda sp, r, w, b: got.append((r, w, b))):
                os.pwrite(fd, bytes(10), 0)
                os.pwrite(fd, bytes(30), 10)
    finally:
        os.close(fd)
    assert _events("outer")[-1][8] == {"syscr": 2, "syscw": 2}
    assert [e[8] for e in _events("inner")] == \
        [{"syscr": 0, "syscw": 0}] * 3
    assert got == [(0, 2, 40)]
    assert _events("wrote")[-1][8] is None
