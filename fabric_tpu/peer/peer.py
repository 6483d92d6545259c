"""The peer singleton and its per-channel resources.

Rebuild of `core/peer/peer.go` (per-channel bundle of ledger, policy
manager, MSP manager, tx validator — :335-344) and the channel wiring
part of `internal/peer/node/start.go:189-911`. A `Peer` owns the
ledger manager, the chaincode runtime, the endorser, and N `Channel`s;
each `Channel` owns the batched TxValidator + committer and updates its
config bundle when config blocks commit.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Optional

from fabric_tpu.protos import common, configtx as ctxpb, transaction as txpb
from fabric_tpu.protoutil import protoutil as pu
from fabric_tpu.common import tracing
from fabric_tpu.common.channelconfig import Bundle
from fabric_tpu.common.configtx import Validator as ConfigTxValidator
from fabric_tpu.internal.configtxgen import genesis as genesis_mod
from fabric_tpu.core import endorser as endorser_mod
from fabric_tpu.core.chaincode import ChaincodeDefinition, ChaincodeSupport
from fabric_tpu.core.committer import LedgerCommitter
from fabric_tpu.core.transientstore import TransientStore
from fabric_tpu.core.txvalidator import TxValidator
from fabric_tpu.ledger.ledgermgmt import LedgerManager
from fabric_tpu.peer.mcs import MSPMessageCryptoService

logger = logging.getLogger("peer")


from fabric_tpu.common import metrics as _pm  # noqa: E402

PVT_COMMIT_BLOCK_DURATION = _pm.HistogramOpts(
    namespace="gossip", subsystem="privdata",
    name="commit_block_duration",
    help="The time the coordinator took to store a block together "
         "with its private data in seconds.", label_names=("channel",))
PVT_PULL_DURATION = _pm.HistogramOpts(
    namespace="gossip", subsystem="privdata", name="pull_duration",
    help="The time to gather a block's private data from the "
         "transient store at commit in seconds.",
    label_names=("channel",))
PVT_PURGE_DURATION = _pm.HistogramOpts(
    namespace="gossip", subsystem="privdata", name="purge_duration",
    help="The time to purge committed transactions' private data "
         "from the transient store in seconds.",
    label_names=("channel",))


class Channel:
    """Per-channel resources (reference: `core/peer/peer.go` Channel)."""

    def __init__(self, peer: "Peer", channel_id: str, ledger):
        self.channel_id = channel_id
        self.ledger = ledger
        self._peer = peer
        self._lock = threading.Lock()
        self._bundle: Optional[Bundle] = None
        self._definitions: dict[str, ChaincodeDefinition] = {}
        self._commit_listeners: list[Callable] = []
        self._commit_cond = threading.Condition()
        # blocks whose WHOLE ledger commit (block store, history,
        # state) is done: an open ledger has recovered, so that is its
        # height; from then on `_notify_commit` advances it. The block
        # store's own height runs ahead of it inside a commit.
        self._applied_height = ledger.height

        cfg_block = self._find_last_config_block()
        self._apply_config(cfg_block)
        # the ledger resolves collection configs (BTL etc.) through the
        # channel's chaincode definitions
        ledger.set_collection_info_source(self._collection_info)

        from fabric_tpu.core.txvalidator import TxValidatorMetrics
        self.validator = TxValidator(
            channel_id, ledger, self.bundle, peer.csp,
            self.chaincode_definition,
            configtx_validator_source=self.configtx_validator,
            metrics=TxValidatorMetrics(peer.metrics_provider,
                                       channel=channel_id))
        self.committer = LedgerCommitter(
            ledger, on_config_block=self._on_config_block)
        # overlapped intake (Peer.CommitPipeline.Depth > 0): validate
        # block N+1 on the device while block N's host commit runs
        self.commit_pipeline = None
        depth = getattr(peer, "commit_pipeline_depth", 0) or 0
        if depth > 0:
            from fabric_tpu.core.commitpipeline import CommitPipeline
            self.commit_pipeline = CommitPipeline(
                self, mcs=peer.mcs, depth=depth,
                metrics_provider=peer.metrics_provider,
                # e2e_commit_seconds/trace-track attribution: the
                # peer's gossip endpoint names the committing node
                node_id=getattr(peer, "endpoint", None))
        _prov = peer.metrics_provider or _pm.DisabledProvider()
        self._m_pvt_commit = _prov.new_histogram(
            PVT_COMMIT_BLOCK_DURATION).with_labels(
            "channel", channel_id)
        self._m_pvt_pull = _prov.new_histogram(
            PVT_PULL_DURATION).with_labels("channel", channel_id)
        self._m_pvt_purge = _prov.new_histogram(
            PVT_PURGE_DURATION).with_labels("channel", channel_id)

    # -- config --

    def _find_last_config_block(self) -> common.Block:
        """O(1) via the LAST_CONFIG pointer the orderer stamps into
        every block's SIGNATURES metadata (protoutil
        get_last_config_index); linear scan only as a salvage path for
        chains written before the pointer existed."""
        height = self.ledger.height
        tip = self.ledger.block_store.get_block_by_number(height - 1)
        if tip is not None:
            if pu.is_config_block(tip):
                return tip
            try:
                cfg = self.ledger.block_store.get_block_by_number(
                    pu.get_last_config_index(tip))
                if cfg is not None and pu.is_config_block(cfg):
                    return cfg
            except Exception:
                logger.warning("[%s] last-config pointer unreadable; "
                               "falling back to scan", self.channel_id)
        for num in range(height - 1, -1, -1):
            block = self.ledger.block_store.get_block_by_number(num)
            if block is not None and pu.is_config_block(block):
                return block
        # join-by-snapshot: no blocks on disk, the snapshot carried the
        # governing config block
        if hasattr(self.ledger, "bootstrap_config_block"):
            block = self.ledger.bootstrap_config_block()
            if block is not None:
                return block
        raise ValueError(f"no config block found on {self.channel_id}")

    def _apply_config(self, block: common.Block) -> None:
        env = pu.extract_envelope(block, 0)
        payload = pu.get_payload(env)
        cfg_env = ctxpb.ConfigEnvelope()
        cfg_env.ParseFromString(payload.data)
        bundle = Bundle(self.channel_id, cfg_env.config, self._peer.csp)
        with self._lock:
            self._bundle = bundle
            self._configtx_validator = ConfigTxValidator(
                self.channel_id, cfg_env.config, bundle.policy_manager)
        logger.info("[%s] channel config applied from block %d",
                    self.channel_id, block.header.number)

    def _on_config_block(self, block: common.Block) -> None:
        try:
            self._apply_config(block)
        except Exception:
            logger.exception("[%s] failed to apply config block %d",
                             self.channel_id, block.header.number)
            raise

    def bundle(self) -> Bundle:
        with self._lock:
            return self._bundle

    def configtx_validator(self) -> ConfigTxValidator:
        with self._lock:
            return self._configtx_validator

    # -- chaincode definitions (lifecycle-lite; the state-backed
    #    _lifecycle SCC replaces this as the source of truth later) --

    def define_chaincode(self, definition: ChaincodeDefinition) -> None:
        with self._lock:
            self._definitions[definition.name] = definition
        # install the chaincode's rich-query indexes (reference:
        # CouchDB index build on chaincode installation)
        for name, index_json in getattr(definition, "indexes", ()):
            try:
                self.ledger.define_index(definition.name, name,
                                         index_json)
            except Exception:
                logger.exception("[%s] index %s for chaincode %s "
                                 "failed to build", self.channel_id,
                                 name, definition.name)

    def chaincode_definition(self, name: str
                             ) -> Optional[ChaincodeDefinition]:
        """Committed `_lifecycle` state is the source of truth
        (reference: the lifecycle cache over the state DB); the
        in-memory table is the dev-mode / pre-lifecycle fallback."""
        from fabric_tpu.core.scc import lifecycle as lc
        raw = self.ledger.get_state(lc.NAMESPACE,
                                    lc._DEF_PREFIX + name)
        if raw is not None:
            try:
                return lc.definition_from_state(raw)
            except Exception:
                logger.exception("[%s] undecodable committed "
                                 "definition for %s", self.channel_id,
                                 name)
        with self._lock:
            return self._definitions.get(name)

    def _collection_info(self, ns: str, coll: str):
        from fabric_tpu.core.scc import lifecycle as lc
        if coll.startswith("_implicit_org_"):
            # org-scoped implicit collections exist on EVERY namespace
            # (reference: implicit collections of _lifecycle + per-cc)
            return lc.implicit_collection_config(
                coll[len("_implicit_org_"):])
        definition = self.chaincode_definition(ns)
        return definition.collection(coll) if definition else None

    # -- block intake (what the deliver client calls) --

    def process_block(self, block: common.Block) -> list[int]:
        """validate (batched) → gather private data → commit; returns
        final tx codes. Reference: gossip/state deliverPayloads →
        coordinator.StoreBlock (`gossip/privdata/coordinator.go:152`,
        SURVEY §3.4)."""
        n = len(block.data.data)
        num = block.header.number
        with tracing.span("peer.block", block=num, txs=n):
            with tracing.span("commit.validate", block=num, txs=n):
                flags = self.validator.validate(block)
            rwsets = None
            if not pu.is_config_block(block) and num != 0:
                from fabric_tpu.ledger.kvledger import extract_tx_rwset
                with tracing.span("intake.rwsets", txs=n):
                    rwsets = [extract_tx_rwset(e)
                              for e in block.data.data]
            with tracing.span("intake.txids", txs=n):
                tx_ids = self.ledger.block_store.block_tx_ids(block)
            commit = tracing.span("commit.commit", block=num, txs=n)
            with commit, tracing.thread_io(commit):
                return self.commit_validated(block, flags,
                                             rwsets=rwsets,
                                             tx_ids=tx_ids)

    def commit_validated(self, block: common.Block, flags: list[int],
                         rwsets=None, tx_ids=None) -> list[int]:
        """The host half of block intake: gather private data →
        commit → purge → notify, with the validation verdicts (and
        optionally the parsed rwsets + scanned tx-ids — each envelope
        decoded exactly once per block) already in hand. The commit
        pipeline calls this for block N while block N+1 validates.
        Each boundary's clock is read once, by the span that starts or
        ends there; the three privdata histograms read the spans."""
        n = len(block.data.data)
        pull = tracing.timed("commit.pvt", txs=n)
        with pull:
            pvt_data, committed_txids = self._gather_pvt_data(
                block, flags, rwsets=rwsets, tx_ids=tx_ids)
        codes = self.committer.commit(block, flags, pvt_data=pvt_data,
                                      rwsets=rwsets, tx_ids=tx_ids)
        purge = None
        if committed_txids:
            purge = tracing.timed("commit.pvt",
                                  txs=len(committed_txids))
            with purge:
                self._peer.transient_store.purge_by_txids(
                    committed_txids)
            self._m_pvt_purge.observe(purge.seconds)
        notify = tracing.timed("commit.notify", txs=n)
        with notify:
            self._notify_commit(block, codes, tx_ids=tx_ids)
        self._m_pvt_pull.observe(pull.seconds)
        self._m_pvt_commit.observe(
            (purge or notify).t0 - pull.t1)
        return codes

    def _gather_pvt_data(self, block: common.Block, flags: list[int],
                         rwsets=None, tx_ids=None
                         ) -> tuple[dict, list[str]]:
        """Transient-store lookup per valid tx that advertises hashed
        collection writes (the gossip pull for still-missing data is
        the reconciler's job). `rwsets`/`tx_ids` reuse the intake
        path's single parse pass when provided."""
        from fabric_tpu.ledger.kvledger import extract_tx_rwset
        pvt_data: dict[int, object] = {}
        txids: list[str] = []
        store = self._peer.transient_store
        for i, env_bytes in enumerate(block.data.data):
            if flags[i] != txpb.TxValidationCode.VALID:
                continue
            txrw = rwsets[i] if rwsets is not None else \
                extract_tx_rwset(env_bytes)
            if txrw is None or not any(
                    nsrw.collection_hashed_rwset
                    for nsrw in txrw.ns_rwset):
                continue
            if tx_ids is not None:
                tx_id = tx_ids[i]
            else:
                try:
                    env = pu.unmarshal_envelope(env_bytes)
                    tx_id = pu.get_channel_header(
                        pu.get_payload(env)).tx_id
                except Exception:
                    continue
            if not tx_id:
                continue
            txids.append(tx_id)
            stored = store.get(tx_id)
            if stored is not None:
                pvt_data[i] = stored
        return pvt_data, txids

    # -- commit notification (gateway CommitStatus; reference:
    #    internal/pkg/gateway/commit) --

    def _notify_commit(self, block: common.Block,
                       codes: list[int], tx_ids=None) -> None:
        events = []
        if tx_ids is not None:
            events = [(tid, codes[i]) for i, tid in enumerate(tx_ids)
                      if tid]
        else:
            for i, env_bytes in enumerate(block.data.data):
                try:
                    env = pu.unmarshal_envelope(env_bytes)
                    ch = pu.get_channel_header(pu.get_payload(env))
                    if ch.tx_id:
                        events.append((ch.tx_id, codes[i]))
                except Exception:
                    continue
        with self._commit_cond:
            self._applied_height = block.header.number + 1
            self._commit_cond.notify_all()
        for cb in list(self._commit_listeners):
            try:
                cb(self.channel_id, block, dict(events))
            except Exception:
                logger.exception("commit listener failed")

    def add_commit_listener(self, cb: Callable) -> None:
        self._commit_listeners.append(cb)

    def wait_for_height(self, height: int,
                        timeout: Optional[float] = None) -> bool:
        """True once `height` blocks are committed AND applied: a
        caller that then reads state or the commit hash sees them."""
        with self._commit_cond:
            return self._commit_cond.wait_for(
                lambda: self._applied_height >= height, timeout)

    def tx_validation_code(self, tx_id: str) -> Optional[int]:
        ptx = self.ledger.get_transaction_by_id(tx_id)
        if ptx is None:
            return None
        return ptx.validation_code

    # -- duck-type for the shared DeliverHandler (peer-side deliver
    #    events service) --

    @property
    def height(self) -> int:
        return self.ledger.height

    def get_block(self, number: int):
        return self.ledger.block_store.get_block_by_number(number)

    def wait_for_block(self, number: int,
                       timeout: Optional[float] = None) -> bool:
        return self.wait_for_height(number + 1, timeout)


class Peer:
    """Reference: `core/peer/peer.go` Peer + the wiring in
    `internal/peer/node/start.go` serve()."""

    def __init__(self, ledger_root: str, local_msp, csp,
                 metrics_provider=None, state_db_factory=None,
                 commit_pipeline_depth: int = 0):
        self.csp = csp
        self.local_msp = local_msp
        self.metrics_provider = metrics_provider
        # Peer.CommitPipeline.Depth (0 = off): blocks validated ahead
        # of the one being committed, per channel
        self.commit_pipeline_depth = int(commit_pipeline_depth or 0)
        self.signer = local_msp.get_default_signing_identity()
        self.ledger_mgr = LedgerManager(
            ledger_root, metrics_provider=metrics_provider,
            state_db_factory=state_db_factory)
        self.transient_store = TransientStore(
            os.path.join(ledger_root, "transient.db"))
        self.chaincode_support = ChaincodeSupport(
            channel_source=lambda cid: self.channels.get(cid),
            metrics_provider=metrics_provider)
        self.channels: dict[str, Channel] = {}
        self._lock = threading.Lock()
        self.mcs = MSPMessageCryptoService(
            lambda cid: (self.channels[cid].bundle()
                         if cid in self.channels else None),
            local_deserializer=local_msp)
        self.gossip_service = None   # attached by node assembly
        self.endorser = endorser_mod.Endorser(
            self.signer, self.chaincode_support, self._channel_support,
            metrics=endorser_mod.EndorserMetrics(metrics_provider))
        from fabric_tpu.core.scc import register_system_chaincodes
        register_system_chaincodes(self)
        # reopen any previously joined channels (start.go:770
        # peerInstance.Initialize)
        for channel_id in self.ledger_mgr.ledger_ids():
            ledger = self.ledger_mgr.open(channel_id)
            self._register_channel(channel_id, ledger)

    def _register_channel(self, channel_id: str, ledger) -> Channel:
        channel = Channel(self, channel_id, ledger)
        with self._lock:
            self.channels[channel_id] = channel
        return channel

    def _channel_support(self, channel_id: str
                         ) -> Optional[endorser_mod.ChannelSupport]:
        channel = self.channels.get(channel_id)
        if channel is None:
            return None
        bundle = channel.bundle()
        distributor = None
        if self.gossip_service is not None:
            gs = self.gossip_service
            distributor = (lambda tx_id, height, pvt_results:
                           gs.distribute_private_data(
                               channel_id, tx_id, height, pvt_results))
        return endorser_mod.ChannelSupport(
            ledger=channel.ledger,
            policy_manager=bundle.policy_manager,
            deserializer=bundle.msp_manager,
            transient_store=self.transient_store,
            pvt_distributor=distributor,
            acls=(bundle.application.acls
                  if bundle.application else None),
            cc_definition=channel.chaincode_definition)

    # -- channel lifecycle (reference: cscc JoinChain →
    #    peer.CreateChannel, core/peer/channel.go) --

    def join_channel(self, genesis_block: common.Block) -> Channel:
        cfg = genesis_mod.config_from_block(genesis_block)
        env = pu.extract_envelope(genesis_block, 0)
        ch = pu.get_channel_header(pu.get_payload(env))
        channel_id = ch.channel_id
        if channel_id in self.channels:
            raise ValueError(f"already joined {channel_id}")
        # sanity: the config must parse into a bundle before we commit
        Bundle(channel_id, cfg, self.csp)
        ledger = self.ledger_mgr.create(genesis_block, channel_id)
        return self._register_channel(channel_id, ledger)

    def join_channel_by_snapshot(self, snapshot_dir: str,
                                 channel_id: str) -> Channel:
        """Join without replaying history (reference:
        `internal/peer/channel/joinbysnapshot.go`)."""
        ledger = self.ledger_mgr.create_from_snapshot(snapshot_dir,
                                                      channel_id)
        return self._register_channel(channel_id, ledger)

    def channel(self, channel_id: str) -> Optional[Channel]:
        return self.channels.get(channel_id)

    def close(self) -> None:
        for channel in list(self.channels.values()):
            if channel.commit_pipeline is not None:
                channel.commit_pipeline.stop()
        self.transient_store.close()
        self.ledger_mgr.close()
