"""The per-channel ledger: block store + state DB + history DB.

Rebuild of `core/ledger/kvledger/kv_ledger.go`: the commit pipeline
(`commit`, :593-692) runs (1) MVCC validate-and-prepare, (2) block +
index append, (3) state commit, (4) history commit, stamping the
TRANSACTIONS_FILTER metadata and the commit-hash chain, with the same
phase timings surfaced as metrics. Crash recovery replays blocks the
state/history DBs missed (`recoverDBs`, :352).
"""

from __future__ import annotations

import hashlib
import os
from typing import Callable, Optional, Sequence

from fabric_tpu import protoutil as pu
from fabric_tpu.common import metrics as metrics_mod, tracing
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.ledger import pvtdata as pvt
from fabric_tpu.ledger.blkstorage import BlockStore
from fabric_tpu.ledger.history import HistoryDB
from fabric_tpu.ledger.kvdb import DBHandle, KVStore
from fabric_tpu.ledger.statedb import Height, StateDB, UpdateBatch
from fabric_tpu.ledger.txmgr import (
    BlockRWSets,
    TxMgr,
    TxSimulator,
    extract_tx_rwset,
    parse_block_rwsets,
)
from fabric_tpu.protos import common, rwset as rwpb, transaction as txpb

logger = must_get_logger("kvledger")

BLOCK_PROCESSING_TIME = metrics_mod.HistogramOpts(
    namespace="ledger", name="block_processing_time",
    help="The time to commit one block end to end: MVCC validation, "
         "block + private-data storage, state and history commit.",
    label_names=("channel",))
BLOCKSTORAGE_COMMIT_TIME = metrics_mod.HistogramOpts(
    namespace="ledger", name="blockstorage_and_pvtdata_commit_time",
    help="The time to append the block and its private data to "
         "durable storage.", label_names=("channel",))
STATEDB_COMMIT_TIME = metrics_mod.HistogramOpts(
    namespace="ledger", name="statedb_commit_time",
    help="The time to apply a block's write-set to the state DB.",
    label_names=("channel",))
BLOCKCHAIN_HEIGHT = metrics_mod.GaugeOpts(
    namespace="ledger", name="blockchain_height",
    help="The height of the chain (number of committed blocks).",
    label_names=("channel",))
BLOCKSTORAGE_ONLY_COMMIT_TIME = metrics_mod.HistogramOpts(
    namespace="ledger", name="blockstorage_commit_time",
    help="The time to append the block (without private data) to the "
         "block store.", label_names=("channel",))
TRANSACTION_COUNT = metrics_mod.CounterOpts(
    namespace="ledger", name="transaction_count",
    help="The number of transactions committed, by validation code.",
    label_names=("channel", "validation_code"))


class LedgerError(Exception):
    pass


class KVLedger:
    """Reference: kvLedger (`kv_ledger.go`)."""

    def __init__(self, ledger_id: str, ledger_dir: str,
                 metrics_provider=None, state_db_factory=None):
        self.ledger_id = ledger_id
        self._dir = ledger_dir
        os.makedirs(ledger_dir, exist_ok=True)
        self._kv = KVStore(os.path.join(ledger_dir, "index.db"))
        # the WAL is checkpointed behind the committer and settled
        # before each block's first write (`_settle`)
        self._kv.checkpoint_behind()
        self._settled = True
        self.block_store = BlockStore(
            ledger_dir, DBHandle(self._kv, "blkindex"))
        # pluggable state DB (reference statedb.go VersionedDB): the
        # factory builds an alternate backend (e.g. the HTTP external
        # engine, statecouchdb's role); default = embedded sqlite
        if state_db_factory is not None:
            self.state_db = state_db_factory(
                ledger_id, DBHandle(self._kv, "statedb"))
        else:
            self.state_db = StateDB(DBHandle(self._kv, "statedb"))
        self.history_db = HistoryDB(DBHandle(self._kv, "historydb"))
        self.txmgr = TxMgr(self.state_db)
        self.pvt_store = pvt.PvtDataStore(DBHandle(self._kv, "pvtstore"))
        # (ns, coll) -> CollectionConfig | None; wired by the channel
        # from its chaincode definitions (the reference resolves this
        # through confighistory at commit time)
        self._collection_info: Callable[[str, str],
                                        Optional[pvt.CollectionConfig]] \
            = lambda ns, coll: None

        provider = metrics_provider or metrics_mod.DisabledProvider()
        self._m_block_time = provider.new_histogram(
            BLOCK_PROCESSING_TIME).with_labels("channel", ledger_id)
        self._m_store_time = provider.new_histogram(
            BLOCKSTORAGE_COMMIT_TIME).with_labels("channel", ledger_id)
        self._m_state_time = provider.new_histogram(
            STATEDB_COMMIT_TIME).with_labels("channel", ledger_id)
        self._m_height = provider.new_gauge(
            BLOCKCHAIN_HEIGHT).with_labels("channel", ledger_id)
        self._m_blkstore_time = provider.new_histogram(
            BLOCKSTORAGE_ONLY_COMMIT_TIME).with_labels(
            "channel", ledger_id)
        self._m_tx_count = provider.new_counter(TRANSACTION_COUNT)

        from fabric_tpu.ledger.snapshot import SnapshotRequests
        self.snapshot_requests = SnapshotRequests(
            DBHandle(self._kv, "snapshotreq"))
        self._meta = DBHandle(self._kv, "ledgermeta")

        # collection-config history: a state listener over the commit
        # path (reference core/ledger/confighistory — registered as a
        # ledger.StateListener on the lifecycle namespaces)
        from fabric_tpu.ledger.confighistory import ConfigHistoryMgr
        self.config_history = ConfigHistoryMgr(
            DBHandle(self._kv, "confighist"))
        self._state_listeners = [self.config_history]

        self._check_data_format()
        self._recover_dbs()
        self._commit_hash = self._load_commit_hash()

    # bump when derived-DB encodings change
    # 2.1: confighist keyspace added (rebuilt from block replay by
    #      `peer node upgrade-dbs` — without the bump an existing
    #      ledger would silently serve an EMPTY config history and
    #      resolve historical private-data gaps under today's configs)
    DATA_FORMAT = b"2.1"

    def _check_data_format(self) -> None:
        """Refuse to serve data written in an older derived-DB format
        (reference: dataformat.CheckVersion → 'run peer node
        upgrade-dbs'). Fresh ledgers are stamped with the current
        format; `peer node upgrade-dbs` drops derived DBs and restamps
        so the next open replays them in the new encoding."""
        fmt = self._meta.get(b"datafmt")
        if fmt is None:
            if self.block_store.height == 0 and \
                    self.state_db.savepoint() is None:
                self._meta.put(b"datafmt", self.DATA_FORMAT)
                return
            fmt = b"1.0"   # pre-versioning data
        if fmt != self.DATA_FORMAT:
            raise LedgerError(
                f"ledger {self.ledger_id!r} holds data in format "
                f"{fmt.decode()} but this binary requires "
                f"{self.DATA_FORMAT.decode()}; run "
                f"`peer node upgrade-dbs` first")

    # -- lifecycle --

    def initialize_from_genesis(self, genesis: common.Block) -> None:
        if self.block_store.height != 0:
            raise LedgerError("ledger already initialized")
        self.commit_block(genesis)

    def _load_commit_hash(self) -> bytes:
        """The commit-hash chain head is recovered from the LAST stored
        block's COMMIT_HASH metadata — the block append is the
        durability point of the hash, so this cannot race a separately
        persisted copy (a meta key written after the state commit could
        be stale after a crash, silently forking this peer's chain from
        peers that did not crash)."""
        height = self.block_store.height
        if height == 0:
            return b""
        last = self.block_store.get_block_by_number(height - 1)
        if last is None:
            # bootstrapped from snapshot, no blocks yet: the adopted
            # hash was persisted at import
            return self._meta.get(b"commit_hash") or b""
        md = last.metadata.metadata
        if len(md) > common.BlockMetadataIndex.COMMIT_HASH:
            return bytes(md[common.BlockMetadataIndex.COMMIT_HASH])
        return b""

    def _recover_dbs(self) -> None:
        """Replay blocks the state DB missed (crash between block append
        and state commit — reference kv_ledger.go:352 recoverDBs)."""
        sp = self.state_db.savepoint()
        next_block = (sp.block + 1) if sp else 0
        while next_block < self.block_store.height:
            block = self.block_store.get_block_by_number(next_block)
            logger.info("recovering state for block %d", next_block)
            self._apply_block_to_state(block)
            next_block += 1

    # -- queries --

    @property
    def height(self) -> int:
        return self.block_store.height

    def new_tx_simulator(self, tx_id: str = "") -> TxSimulator:
        return TxSimulator(self.state_db, tx_id)

    def get_state(self, ns: str, key: str) -> Optional[bytes]:
        vv = self.state_db.get_state(ns, key)
        return vv.value if vv else None

    def get_transaction_by_id(self, tx_id: str):
        return self.block_store.get_tx_by_id(tx_id)

    def existing_tx_ids(self, tx_ids: list[str]) -> set[str]:
        """Batched duplicate-txid probe (validator fast path)."""
        return self.block_store.existing_tx_ids(tx_ids)

    def define_index(self, ns: str, name: str,
                     index_json: str) -> None:
        """Register + build a rich-query index for a chaincode
        namespace (reference: CouchDB indexes installed from a
        chaincode package's META-INF/statedb/couchdb/indexes)."""
        self.state_db.define_index(ns, name, index_json)

    def set_collection_info_source(self, fn) -> None:
        self._collection_info = fn

    def get_private_data(self, ns: str, coll: str, key: str
                         ) -> Optional[bytes]:
        vv = self.state_db.get_state(pvt.pvt_ns(ns, coll), key)
        return vv.value if vv else None

    def get_private_data_hash(self, ns: str, coll: str, key: str
                              ) -> Optional[bytes]:
        vv = self.state_db.get_state(
            pvt.hash_ns(ns, coll),
            pvt.hashed_key_str(pvt.key_hash(key)))
        return vv.value if vv else None

    def get_pvt_data_by_num(self, block_num: int, tx_num: int):
        return self.pvt_store.get_pvt_data(block_num, tx_num)

    def missing_pvt_data(self, max_entries: int = 0):
        return self.pvt_store.get_missing(max_entries)

    def get_history_for_key(self, ns: str, key: str):
        return self.history_db.get_history_for_key(
            self.block_store, ns, key)

    # -- snapshots (reference: snapshot.go / snapshot_mgmt.go) --

    @property
    def commit_hash(self) -> bytes:
        return self._commit_hash

    def adopt_commit_hash(self, commit_hash: bytes,
                          bootstrap_block: int) -> None:
        self._meta.put(b"commit_hash", commit_hash)
        self._commit_hash = commit_hash

    def adopt_bootstrap_config_block(self, block_bytes: bytes) -> None:
        self._meta.put(b"bootstrap_config_block", block_bytes)

    def bootstrap_config_block(self) -> Optional[common.Block]:
        raw = self._meta.get(b"bootstrap_config_block")
        if raw is None:
            return None
        block = common.Block()
        block.ParseFromString(raw)
        return block

    def generate_snapshot(self, out_dir: Optional[str] = None) -> dict:
        from fabric_tpu.ledger import snapshot as snap
        if out_dir is None:
            out_dir = os.path.join(self._dir, "snapshots", "completed",
                                   str(self.height - 1))
        return snap.generate_snapshot(self, out_dir)

    def snapshots_dir(self) -> str:
        return os.path.join(self._dir, "snapshots", "completed")

    def _maybe_generate_snapshots(self) -> None:
        due = self.snapshot_requests.due(self.height)
        for h in due:
            try:
                meta = self.generate_snapshot()
                logger.info("[%s] snapshot generated at height %d "
                            "(requested %d): %s", self.ledger_id,
                            self.height, h,
                            meta["last_block_hash"][:16])
            except Exception:
                logger.exception("[%s] snapshot generation failed",
                                 self.ledger_id)
            finally:
                self.snapshot_requests.cancel(h)

    # -- commit --

    def commit_block(self, block: common.Block,
                     flags: Optional[Sequence[int]] = None,
                     pvt_data: Optional[dict] = None,
                     rwsets=None, tx_ids=None) -> list[int]:
        """The commit pipeline. `flags` carries upstream validation
        results (sig/policy failures from the txvalidator); MVCC runs
        here. `pvt_data` maps tx_num → TxPvtReadWriteSet (cleartext the
        peer holds — from its transient store or gossip pull). `rwsets`
        / `tx_ids` optionally carry the already-parsed TxReadWriteSet
        list and tx-id scan from the intake path (one decode pass per
        block instead of one per layer). Returns final per-tx
        validation codes."""
        n = len(block.data.data)
        block_num = block.header.number
        self._settled = False
        # one span per stretch, each boundary's clock read once: the
        # spans' own readings feed the histograms and the log line
        # below (with tracing disabled `timed` still reads the clock)
        mvcc = tracing.timed("ledger.mvcc", txs=n)
        with mvcc:
            is_config = self._is_config_block(block)
            if is_config or block_num == 0:
                codes = list(flags) if flags else \
                    [txpb.TxValidationCode.VALID] * n
                batch = None
            else:
                if rwsets is None:
                    rwsets = [extract_tx_rwset(e)
                              for e in block.data.data]
                txmgr = self.txmgr
                reads0, prefetched0, fallthrough0, selects0 = (
                    txmgr.reads_checked, txmgr.prefetched,
                    txmgr.fallthrough, self._kv.selects)
                codes, batch, parsed = self._validate_and_prepare(
                    block_num, rwsets, flags, pvt_data or {})
                mvcc.set(valid=codes.count(txpb.TxValidationCode.VALID),
                         reads=txmgr.reads_checked - reads0,
                         writes=len(batch.updates),
                         prefetched=txmgr.prefetched - prefetched0,
                         selects=self._kv.selects - selects0,
                         fallthrough=txmgr.fallthrough - fallthrough0)

            # TRANSACTIONS_FILTER: one code byte per tx
            block.metadata.metadata[
                common.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
                bytes(codes)
            # commit-hash chain (reference kv_ledger.go commitHash);
            # only adopted in-memory once add_block accepts the block,
            # so a rejected block (wrong number / previous_hash) cannot
            # poison the chain
            new_commit_hash = hashlib.sha256(
                self._commit_hash + bytes(codes) +
                block.header.data_hash).digest()
            block.metadata.metadata[
                common.BlockMetadataIndex.COMMIT_HASH] = new_commit_hash

        store = tracing.timed("ledger.blockstore")
        with store:
            store.set(bytes=self.block_store.add_block(
                block, tx_ids=tx_ids, before_index=self._settle))
            self._commit_hash = new_commit_hash

        state = tracing.timed("ledger.state")
        if batch is not None:
            # history BEFORE the statedb savepoint: its puts are
            # idempotent empty entries, so a crash in between is
            # healed by replay — the reverse order would permanently
            # lose block N's history
            history = tracing.span("ledger.history")
            with history:
                history.set(rows=self.history_db.commit_block(
                    block, codes, parsed))
            with state:
                state.set(rows=len(batch.updates))
                # listeners BEFORE the savepoint advances: a crash in
                # between is healed by replay re-notifying (idempotent
                # writes); the reverse order would lose block N's
                # confighistory forever (recovery starts above the
                # savepoint)
                self._notify_state_listeners(block_num, batch)
                self.state_db.apply_updates(
                    batch, Height(block_num, max(n - 1, 0)))
                # bookkeeping for purged entries is dropped only AFTER
                # the state deletes are durable: a crash in between
                # re-purges (idempotent) on the next commit instead of
                # leaking keys
                self._drop_expired_bookkeeping(block_num)
        else:
            # config/genesis blocks still advance the savepoint
            with state:
                self.state_db.apply_updates(UpdateBatch(),
                                            Height(block_num, 0))
        t0, t1, t2, t3 = mvcc.t0, mvcc.t1, store.t1, state.t1

        self._maybe_generate_snapshots()
        self._m_block_time.observe(t3 - t0)
        self._m_store_time.observe(t2 - t1)
        self._m_blkstore_time.observe(t2 - t1)
        self._m_state_time.observe(t3 - t2)
        self._m_height.set(self.height)
        from collections import Counter as _Counter
        for code, cnt in _Counter(codes).items():
            try:
                cname = txpb.TxValidationCode.Name(code)
            except ValueError:
                cname = str(code)
            self._m_tx_count.with_labels(
                "channel", self.ledger_id,
                "validation_code", cname).add(cnt)
        logger.info(
            "[%s] committed block [%d] with %d tx(s) in %.1fms "
            "(state_validation=%.1fms block_commit=%.1fms "
            "state_commit=%.1fms)",
            self.ledger_id, block_num, n, (t3 - t0) * 1e3,
            (t1 - t0) * 1e3, (t2 - t1) * 1e3, (t3 - t2) * 1e3)
        return codes

    def _apply_block_to_state(self, block: common.Block) -> None:
        """Recovery path: re-run MVCC for an already-stored block using
        its recorded TRANSACTIONS_FILTER as upstream flags. Private
        cleartext is replayed from the pvt store (written before the
        state apply, so it survives the crash being recovered from)."""
        self._settled = False
        if self._is_config_block(block) or block.header.number == 0:
            self._settle()
            self.state_db.apply_updates(
                UpdateBatch(), Height(block.header.number, 0))
            return
        block_num = block.header.number
        filt = block.metadata.metadata[
            common.BlockMetadataIndex.TRANSACTIONS_FILTER]
        rwsets = [extract_tx_rwset(e) for e in block.data.data]
        flags = [
            filt[i] if i < len(filt) else txpb.TxValidationCode.VALID
            for i in range(len(rwsets))
        ]
        pvt_data = {}
        for tx_num in range(len(rwsets)):
            stored = self.pvt_store.get_pvt_data(block_num, tx_num)
            if stored is not None:
                pvt_data[tx_num] = stored
        codes, batch, parsed = self._validate_and_prepare(
            block_num, rwsets, flags, pvt_data)
        # same history/listener-before-savepoint ordering as
        # commit_block
        self._settle()
        self.history_db.commit_block(block, codes, parsed)
        self._notify_state_listeners(block_num, batch)
        self.state_db.apply_updates(
            batch, Height(block_num, max(len(rwsets) - 1, 0)))
        self._drop_expired_bookkeeping(block_num)

    def _settle(self) -> None:
        """Before a block's first write to the store, once a block:
        every checkpoint the earlier commits requested has finished
        (`KVStore.checkpoint_behind`). The block's own reads, MVCC's
        bulk read among them, ran beside the checkpoint."""
        if self._settled:
            return
        self._settled = True
        with tracing.span("ledger.settle"):
            self._kv.settle()

    def _validate_and_prepare(
            self, block_num: int, rwsets, flags, pvt_data: dict
    ) -> tuple[list[int], UpdateBatch, BlockRWSets]:
        """What commit and crash recovery share before anything is
        written: the one decoding pass over the block's rwsets, MVCC
        over one bulk read of committed state, the private-data
        commit. History takes the decoded rwsets afterwards."""
        flags = list(flags) if flags else None
        parsed = parse_block_rwsets(rwsets, flags)
        codes, batch = self.txmgr.validate_and_prepare(
            block_num, rwsets, flags, parsed=parsed)
        self._commit_pvt_data(block_num, parsed, codes, pvt_data, batch)
        return codes, batch, parsed

    def _notify_state_listeners(self, block_num: int,
                                batch: UpdateBatch) -> None:
        """Reference: ledger.StateListener.HandleStateUpdates invoked
        with the block's committed public updates (kv_ledger commit →
        confighistory.Mgr). Runs before the statedb savepoint advances
        and PROPAGATES failures (reference semantics: a listener error
        fails the commit) — crash recovery then replays the block and
        re-notifies; listener writes are idempotent."""
        for listener in self._state_listeners:
            interest = listener.interested_in_namespaces()
            updates = {k: v for k, v in batch.updates.items()
                       if k[0] in interest}
            if updates:
                listener.handle_state_updates(block_num, updates)

    # -- private data commit (reference: commitToPvtAndBlockStore +
    #    pvtdatastorage Commit + expiry keeper) --

    def _commit_pvt_data(self, block_num: int, parsed: BlockRWSets,
                         codes: list[int], pvt_data: dict,
                         batch: UpdateBatch) -> None:
        """Verify supplied cleartext against the on-chain hashes, apply
        it to the private namespaces, persist it to the pvt store,
        record missing collections + BTL expiry, and fold purges of
        already-expired keys into `batch`."""
        store_batch = self.pvt_store._db.new_batch()
        accepted: dict[int, rwpb.TxPvtReadWriteSet] = {}
        missing: list[pvt.MissingPvtData] = []
        expiry: dict[int, list] = {}   # expiry_block -> entries

        for tx_num, tx in enumerate(parsed.txs):
            if tx is None or \
                    codes[tx_num] != txpb.TxValidationCode.VALID:
                continue
            supplied = self._index_supplied_pvt(pvt_data.get(tx_num))
            kept = rwpb.TxPvtReadWriteSet(
                data_model=rwpb.TxReadWriteSet.KV)
            for ns, _kv, colls in tx:
                ns_kept = None
                for coll, _hns, hset, pvt_rwset_hash in colls:
                    if not hset.hashed_writes:
                        continue   # read-only: no cleartext to commit
                    raw = supplied.get((ns, coll))
                    if raw is None or \
                            pvt.pvt_rwset_hash(raw) != pvt_rwset_hash:
                        if raw is not None:
                            logger.warning(
                                "[%s] pvt data for tx %d [%s/%s] does "
                                "not match its on-chain hash; treating "
                                "as missing", self.ledger_id, tx_num,
                                ns, coll)
                        missing.append(pvt.MissingPvtData(
                            block_num, tx_num, ns, coll))
                        self._record_expiry_hashes(
                            expiry, block_num, ns, coll, hset)
                        continue
                    self._apply_pvt_writes(
                        batch, expiry, block_num,
                        Height(block_num, tx_num), ns, coll, raw, hset)
                    if ns_kept is None:
                        ns_kept = kept.ns_pvt_rwset.add(namespace=ns)
                    ns_kept.collection_pvt_rwset.add(
                        collection_name=coll, rwset=raw)
            if kept.ns_pvt_rwset:
                accepted[tx_num] = kept

        self.pvt_store.prepare_batch(store_batch, block_num, accepted,
                                     missing)
        for exp_block in sorted(expiry):
            self.pvt_store.record_expiry(store_batch, exp_block,
                                         block_num, expiry[exp_block])
        if store_batch.ops:
            self._settle()
            self.pvt_store._db.write_batch(store_batch)

        # fold purges of entries that expire AT this block into the
        # state batch (reference: PurgeExpiredData during commit)
        for _raw_key, entries in self.pvt_store.expired_entries(
                block_num):
            h = Height(block_num, 0)
            for ns, coll, key, kh in entries:
                batch.delete(pvt.hash_ns(ns, coll),
                             pvt.hashed_key_str(kh), h)
                if key:
                    batch.delete(pvt.pvt_ns(ns, coll), key, h)

    @staticmethod
    def _index_supplied_pvt(txpvt) -> dict:
        out = {}
        if txpvt is None:
            return out
        for nspvt in txpvt.ns_pvt_rwset:
            for cpvt in nspvt.collection_pvt_rwset:
                out[(nspvt.namespace, cpvt.collection_name)] = cpvt.rwset
        return out

    def _btl(self, ns: str, coll: str) -> int:
        cfg = self._collection_info(ns, coll)
        return cfg.block_to_live if cfg else 0

    def _record_expiry_hashes(self, expiry: dict, block_num: int,
                              ns: str, coll: str, hset) -> None:
        """Missing-cleartext case: the hashes still expire on schedule."""
        btl = self._btl(ns, coll)
        if not btl:
            return
        entries = expiry.setdefault(block_num + btl + 1, [])
        for hw in hset.hashed_writes:
            entries.append((ns, coll, "", hw.key_hash))

    def _apply_pvt_writes(self, batch: UpdateBatch, expiry: dict,
                          block_num: int, height: Height, ns: str,
                          coll: str, raw: bytes, hset) -> None:
        kv = rwpb.KVRWSet()
        kv.ParseFromString(raw)
        pns = pvt.pvt_ns(ns, coll)
        btl = self._btl(ns, coll)
        entries = expiry.setdefault(block_num + btl + 1, []) if btl \
            else None
        hashes = {pvt.key_hash(w.key): w for w in kv.writes}
        for w in kv.writes:
            if w.is_delete:
                batch.delete(pns, w.key, height)
            else:
                batch.put(pns, w.key, w.value, height)
        if entries is not None:
            for hw in hset.hashed_writes:
                w = hashes.get(hw.key_hash)
                entries.append((ns, coll, w.key if w else "",
                                hw.key_hash))

    def commit_pvt_data_of_old_blocks(
            self, block_num: int, tx_num: int, ns: str, coll: str,
            coll_rwset_bytes: bytes) -> bool:
        """Reconciliation path (reference:
        `CommitPvtDataOfOldBlocks`, gossip/privdata/reconcile.go):
        cleartext for an already-committed block arrives late. It is
        accepted only if (a) it hashes to the block's recorded
        pvt_rwset_hash and (b) per key, the hashed state's current
        version still points at (block_num, tx_num) — otherwise a later
        tx superseded the key and the stale cleartext must not be
        applied to current state (it is still stored for serving
        historical pvt queries)."""
        block = self.block_store.get_block_by_number(block_num)
        if block is None or tx_num >= len(block.data.data):
            return False
        txrw = extract_tx_rwset(block.data.data[tx_num])
        if txrw is None:
            return False
        chrw = next(
            (c for nsrw in txrw.ns_rwset if nsrw.namespace == ns
             for c in nsrw.collection_hashed_rwset
             if c.collection_name == coll), None)
        if chrw is None or \
                pvt.pvt_rwset_hash(coll_rwset_bytes) != \
                chrw.pvt_rwset_hash:
            return False

        kv = rwpb.KVRWSet()
        kv.ParseFromString(coll_rwset_bytes)
        height = Height(block_num, tx_num)
        batch = UpdateBatch()
        pns = pvt.pvt_ns(ns, coll)
        hns = pvt.hash_ns(ns, coll)
        for w in kv.writes:
            hkey = pvt.hashed_key_str(pvt.key_hash(w.key))
            if self.state_db.get_version(hns, hkey) != height:
                continue  # superseded (or expired) since
            if w.is_delete:
                batch.delete(pns, w.key, height)
            else:
                batch.put(pns, w.key, w.value, height)
        if batch.updates:
            self.state_db.apply_writes_only(batch)

        # persist + clear the missing marker
        store_batch = self.pvt_store._db.new_batch()
        existing = self.pvt_store.get_pvt_data(block_num, tx_num) or \
            rwpb.TxPvtReadWriteSet(data_model=rwpb.TxReadWriteSet.KV)
        nspvt = next((n for n in existing.ns_pvt_rwset
                      if n.namespace == ns), None)
        if nspvt is None:
            nspvt = existing.ns_pvt_rwset.add(namespace=ns)
        if not any(c.collection_name == coll
                   for c in nspvt.collection_pvt_rwset):
            nspvt.collection_pvt_rwset.add(collection_name=coll,
                                           rwset=coll_rwset_bytes)
        self.pvt_store.prepare_batch(store_batch, block_num,
                                     {tx_num: existing})
        self.pvt_store.resolve_missing(
            store_batch, pvt.MissingPvtData(block_num, tx_num, ns,
                                            coll))
        self.pvt_store._db.write_batch(store_batch)
        return True

    def _drop_expired_bookkeeping(self, block_num: int) -> None:
        expired = self.pvt_store.expired_entries(block_num)
        if not expired:
            return
        store_batch = self.pvt_store._db.new_batch()
        for raw_key, _entries in expired:
            self.pvt_store.drop_expiry_key(store_batch, raw_key)
        self.pvt_store._db.write_batch(store_batch)

    @staticmethod
    def _is_config_block(block: common.Block) -> bool:
        return pu.is_config_block(block)

    def close(self) -> None:
        self.block_store.close()
        self._kv.close()
