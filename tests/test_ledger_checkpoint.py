"""The ledger's store checkpoints its WAL behind the committer.

`KVLedger` asks its `index.db` for `KVStore.checkpoint_behind()`: every
commit requests a PASSIVE checkpoint on a daemon thread with a
connection of its own, and the ledger settles them before a block's
first write. Differential throughout: the same blocks into a ledger
whose store keeps sqlite's inline auto-checkpoint give the same rows.
"""

import os
import random
import shutil
import sqlite3
import sys
import threading

import pytest

from fabric_tpu.common import tracing
from fabric_tpu.ledger import KVLedger
from fabric_tpu.ledger.kvdb import KVStore
from fabric_tpu.ledger.statedb import Height
from tests.test_ledger_readside import envelope, make_tx, new_ledger, \
    next_block

BLOCKS, TXS, ACCOUNTS = 60, 500, 10_000


def chain(seed: int, blocks: int, txs: int) -> list:
    """Default-shaped blocks: each transaction writes one or two of
    `ACCOUNTS` accounts (~700 writes a 500-tx block), a third of them
    read one first (a conflict once it has been written)."""
    rng = random.Random(seed)
    out = []
    for _ in range(blocks):
        envs = []
        for _ in range(txs):
            keys = [f"acct{rng.randrange(ACCOUNTS)}"
                    for _ in range(1 if rng.random() < 0.6 else 2)]
            reads = [(keys[0], None)] if rng.random() < 0.33 else []
            envs.append(envelope(make_tx(
                reads=reads,
                writes=[(k, b'{"balance":%d}' % rng.randrange(10 ** 6)
                         + bytes(90)) for k in keys])))
        out.append(envs)
    return out


def rows(path) -> list:
    conn = sqlite3.connect(str(path))
    try:
        return conn.execute("SELECT k, v FROM kv ORDER BY k").fetchall()
    finally:
        conn.close()


def inline_ledger(path, monkeypatch) -> KVLedger:
    """A ledger whose store keeps sqlite's inline auto-checkpoint."""
    with monkeypatch.context() as m:
        m.setattr(KVStore, "checkpoint_behind", lambda self: None)
        return new_ledger(path)


def feed(led: KVLedger, blocks) -> list:
    """Commit each block under a `commit.commit` span that books the
    committing thread's system calls, as the peer does. -> [syscr]."""
    reads = []
    for envs in blocks:
        commit = tracing.span("commit.commit")
        with commit, tracing.thread_io(commit):
            led.commit_block(next_block(led, envs))
        ev = [e for e in tracing.snapshot() if e[1] == "commit.commit"][-1]
        reads.append((ev[8] or {}).get("syscr"))
    return reads


@pytest.fixture(scope="module")
def fed(tmp_path_factory):
    """The same 60 blocks into a ledger that checkpoints behind and one
    that checkpoints inline, traced; both closed. -> what each left."""
    blocks = chain(39, BLOCKS, TXS)
    out = {}
    tracing.configure(enabled=True, ring_size=1 << 15, sample_every=1)
    try:
        for mode in ("behind", "inline"):
            tracing.reset()
            path = tmp_path_factory.mktemp(mode)
            with pytest.MonkeyPatch.context() as m:
                led = inline_ledger(path, m) if mode == "inline" \
                    else new_ledger(path)
            ckpt = led._kv._ckpt
            syscr = feed(led, blocks)
            events = tracing.snapshot()
            led.close()
            out[mode] = dict(
                path=path, syscr=syscr, events=events, ledger=led,
                thread=ckpt._thread if ckpt is not None else None)
    finally:
        tracing.configure(enabled=True, ring_size=4096)
        tracing.reset()
    return out


def test_behind_and_inline_leave_the_same_rows(fed):
    got = rows(fed["behind"]["path"] / "index.db")
    assert len(got) > 10_000
    assert got == rows(fed["inline"]["path"] / "index.db")


def test_every_checkpoint_copies_the_whole_wal(fed):
    events = fed["behind"]["events"]
    ckpts = [e[8] for e in events if e[1] == "ledger.checkpoint"]
    assert len(ckpts) >= BLOCKS
    assert all(a["backfilled"] == a["log"] > 0 for a in ckpts), \
        [a for a in ckpts if a["backfilled"] != a["log"]]
    # one settle a block, inside the block store's span, after the
    # block file's fsync and before the index write
    by_id = {e[3]: e for e in events}
    settles = [e for e in events if e[1] == "ledger.settle"]
    assert len(settles) == BLOCKS + 1          # and the genesis block
    assert {by_id[e[4]][1] for e in settles} == {"ledger.blockstore"}
    assert not [e for e in fed["inline"]["events"]
                if e[1] == "ledger.checkpoint"]


def test_the_committer_reads_no_wal_frames_back(fed):
    """`commit.commit`'s `syscr`: with the checkpoint behind, a block's
    commits read next to nothing; inline, the auto-checkpoint reads the
    WAL's frames back on the committing thread."""
    if tracing._thread_io() is None:
        pytest.skip("the kernel keeps no per-thread I/O account here")
    behind, inline = fed["behind"]["syscr"], fed["inline"]["syscr"]
    assert max(behind) < 100, behind
    assert sum(inline[-10:]) > 10 * 100, inline[-10:]


def _commits(events) -> dict:
    """{`commit.commit` span id: [(`kvdb.write` event, its parent's
    name)]}, and every `kvdb.commit` checked to be a child of a
    `kvdb.write`."""
    by_id = {e[3]: e for e in events}
    out = {e[3]: [] for e in events if e[1] == "commit.commit"}
    for e in events:
        if e[1] == "kvdb.commit":
            assert by_id[e[4]][1] == "kvdb.write"
        if e[1] != "kvdb.write":
            continue
        up = by_id.get(e[4])
        parent = up[1] if up is not None else None
        while up is not None and up[1] != "commit.commit":
            up = by_id.get(up[4])
        if up is not None:
            out[up[3]].append((e, parent))
    return out


@pytest.mark.parametrize("mode", ["behind", "inline"])
def test_every_commit_of_a_block_is_booked(fed, mode):
    """Each block's three commits are one `kvdb.write` each, under the
    span that names the keyspace, with one `kvdb.commit` child."""
    events = fed[mode]["events"]
    commits = _commits(events)
    assert len(commits) == BLOCKS
    for writes in commits.values():
        assert sorted(p for _, p in writes) == [
            "blockstore.index", "ledger.history", "ledger.state"]
        assert all(e[8]["ops"] > 0 for e, _ in writes)
    n = sum(len(w) for w in commits.values())
    assert len([e for e in events if e[1] == "kvdb.commit"]) >= n


def test_a_blocks_frames_are_its_writes(fed):
    """With the checkpoint behind, the committer writes nothing but WAL
    frames, a header and a page each: the `frames` of a block's
    `kvdb.write` spans are `commit.commit`'s `syscw` / 2, and the
    tracer's own reads of `/proc` are not in its `syscr`."""
    if tracing._thread_io() is None:
        pytest.skip("the kernel keeps no per-thread I/O account here")
    events = fed["behind"]["events"]
    by_id = {e[3]: e for e in events}
    for span_id, writes in _commits(events).items():
        io = by_id[span_id][8]
        frames = sum(e[8]["frames"] for e, _ in writes)
        assert frames > 0
        assert abs(frames - io["syscw"] / 2) <= 5, (frames, io)
    # a block reads at most one: the process's first blocks may load
    # code the path meets for the first time
    reads = fed["behind"]["syscr"]
    assert sum(r > 1 for r in reads) <= 2, reads


@pytest.mark.parametrize("mode", ["behind", "inline"])
def test_close_leaves_no_thread_and_no_wal(fed, mode):
    got = fed[mode]
    if mode == "behind":
        assert got["thread"] is not None and not got["thread"].is_alive()
    else:
        assert got["thread"] is None
    assert got["ledger"]._kv._ckpt is None
    assert not os.path.exists(got["path"] / "index.db-wal")


@pytest.mark.parametrize("wal", ["kept", "lost"])
def test_a_crash_before_the_checkpoint_recovers(tmp_path, wal):
    """The helper is killed before it checkpoints the last block; the
    crash image is what the disk holds then, its unsynced WAL kept or
    lost. Reopened, the ledger replays what it lacks from the block
    file (fsynced first) and holds what a clean close leaves."""
    src, image = tmp_path / "node", tmp_path / "image"
    blocks = chain(3939, 8, 50)
    led = new_ledger(src)
    for envs in blocks[:-1]:
        led.commit_block(next_block(led, envs))
    led._kv.settle()
    led._kv._ckpt.stop()
    led.commit_block(next_block(led, blocks[-1]))
    assert os.path.getsize(src / "index.db-wal") > 0
    shutil.copytree(src / "chains", image / "chains")
    shutil.copy(src / "index.db", image / "index.db")
    if wal == "kept":
        shutil.copy(src / "index.db-wal", image / "index.db-wal")
    # the crashed process's connections are dropped: the image is read
    # by a new one, and the node's own close makes the reference
    led.close()
    want = rows(src / "index.db")
    back = KVLedger("ch1", str(image))
    assert back.height == len(blocks) + 1
    assert back.state_db.savepoint() == Height(len(blocks), 49)
    back.close()
    assert rows(image / "index.db") == want


def _pragma(store: KVStore, name: str):
    return store._conn.execute("PRAGMA " + name).fetchone()[0]


def _owned_store(owner: str, root):
    """(the store `owner` opens, how it closes it)."""
    if owner == "ledger":
        led = new_ledger(root / "ledger")
        return led._kv, led.close
    if owner == "orderer":
        from fabric_tpu.orderer.multichannel import OrdererLedger
        led = OrdererLedger(str(root / "orderer"))
        return led._kv, led.close
    if owner == "transient":
        from fabric_tpu.core.transientstore import TransientStore
        ts = TransientStore(str(root / "transient.db"))
        return ts._kv, ts.close
    if owner == "stateserver":
        from fabric_tpu.ledger.stateserver import StateServer
        srv = StateServer(str(root / "state"))
        srv.start()
        srv._db("ch1")
        return srv._stores["ch1"], lambda: (srv.stop(),
                                           srv._httpd.server_close())
    if owner == "ledgerutil":
        from fabric_tpu.internal import ledgerutil
        os.makedirs(root / "ledgers" / "ch1")
        blocks, kv = ledgerutil._open_store(str(root / "ledgers"), "ch1")
        return kv, lambda: (blocks.close(), kv.close())
    raise AssertionError(owner)


@pytest.mark.parametrize("owner", ["ledger", "orderer", "transient",
                                   "stateserver", "ledgerutil",
                                   "nodeops"])
def test_only_the_ledgers_store_checkpoints_behind(tmp_path, monkeypatch,
                                                   owner):
    if owner == "nodeops":
        from fabric_tpu.internal import nodeops
        new_ledger(tmp_path / "ledgers" / "ch1").close()
        seen = []

        class Seen(KVStore):
            def close(self):
                seen.append((_pragma(self, "wal_autocheckpoint"),
                             self._ckpt))
                super().close()
        monkeypatch.setattr(nodeops, "KVStore", Seen)
        assert nodeops.rebuild_dbs(str(tmp_path / "ledgers")) == ["ch1"]
        assert seen == [(1000, None)]
        return
    store, close = _owned_store(owner, tmp_path)
    store.put(b"k", b"v")
    try:
        if owner == "ledger":
            assert _pragma(store, "wal_autocheckpoint") == 0
            assert store._ckpt._thread.is_alive()
        else:
            assert _pragma(store, "wal_autocheckpoint") == 1000
            assert store._ckpt is None
    finally:
        close()


def test_commits_and_settles_from_many_threads(tmp_path):
    """More committing threads than cores, a switch every microsecond:
    every request is counted and covered once all have settled, no
    settle hangs, and every row is there."""
    store = KVStore(str(tmp_path / "index.db"))
    store.checkpoint_behind()
    writers, commits = 2 * (os.cpu_count() or 4), 40
    errors = []

    def commit(i):
        try:
            for j in range(commits):
                store.put(b"%03d-%03d" % (i, j), bytes(300))
                if j % 4 == 3:
                    store.settle()
        except Exception as e:      # noqa: BLE001 (asserted below)
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=commit, args=(i,))
                   for i in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads) and not errors
        last = threading.Thread(target=store.settle)
        last.start()
        last.join(timeout=60)
        assert not last.is_alive()
    finally:
        sys.setswitchinterval(interval)
    ckpt = store._ckpt
    assert ckpt._done == ckpt._requested == writers * commits
    assert len(list(store.iterate())) == writers * commits
    store.close()
    assert not ckpt._thread.is_alive()
    assert not os.path.exists(tmp_path / "index.db-wal")


def test_a_memory_store_starts_no_thread():
    before = threading.active_count()
    store = KVStore(":memory:")
    store.checkpoint_behind()
    store.put(b"k", b"v")
    store.settle()
    assert store._ckpt is None
    assert threading.active_count() == before
    assert store.get(b"k") == b"v"
    store.close()
