"""Pipelined block intake (ISSUE 4 tentpole): parity + barriers.

The contract under test: the CommitPipeline (validate block N+1 on
stage A while block N commits on stage B) produces BIT-IDENTICAL
per-tx validation codes, TRANSACTIONS_FILTER bytes and commit hashes
to the sequential `Channel.process_block` path — on a mixed stream
containing a config block, a validation-parameter-style state update,
and a duplicate txid across adjacent in-flight blocks — and that
every failure mode degrades to the sequential path rather than a
wrong answer:

  * config-block / state-update barriers drain the pipeline so
    validate-ahead never reads a stale bundle or stale state;
  * a stage-A fault (`commit.validate_ahead` / `commit.barrier`)
    demotes the block to the sequential fallback on the commit
    worker;
  * speculative validation publishes nothing early — a crash between
    validate(N+1) and commit(N) leaves no trace and replays
    identically through the real block store;
  * a forged block rejects (sticky CommitPipelineError) and reset()
    recovers to the committed height.

Wheel-free per the PR 3 idiom: a stub validator whose verdicts depend
on COMMITTED state + the adopted config (the exact dependencies the
barriers exist for) over the REAL `peer.Channel` commit glue, REAL
KVLedger, REAL LedgerCommitter and REAL CommitPipeline.
"""

import os
import threading
import time

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.common import faults
from fabric_tpu.core.commitpipeline import (
    CommitPipeline,
    CommitPipelineError,
)
from fabric_tpu.core.committer import LedgerCommitter
from fabric_tpu.core.txvalidator import ValidationResult
from fabric_tpu.ledger import KVLedger
from fabric_tpu.ledger.kvdb import DBHandle, KVStore
from fabric_tpu.ledger.kvledger import extract_tx_rwset
from fabric_tpu.ledger.statedb import StateDB
from fabric_tpu.ledger.txmgr import TxSimulator
from fabric_tpu.peer import peer as peer_mod
from fabric_tpu.peer.mcs import BlockVerificationError
from fabric_tpu.protos import common, proposal as proppb
from fabric_tpu.protos import transaction as txpb

TVC = txpb.TxValidationCode
CHANNEL = "pipech"
CC = "mycc"


class FakeSigner:
    def __init__(self, identity=b"endorser"):
        self._id = identity

    def serialize(self):
        return self._id

    def sign(self, msg):
        import hashlib
        return hashlib.sha256(self._id + msg).digest()


# ---------------------------------------------------------------- streams

def _tx_env(scratch_db: StateDB, key: str, value: bytes = b"v"
            ) -> tuple[bytes, str]:
    """A committed-format endorser tx writing one key (write-only
    rwset: immune to MVCC, so verdicts are purely the stub
    validator's)."""
    sim = TxSimulator(scratch_db, "sim")
    sim.put_state(CC, key, value)
    results = pu.marshal(sim.get_tx_simulation_results())
    prop, tx_id = pu.create_proposal(CHANNEL, CC, [b"invoke"],
                                     creator=b"client")
    resp = proppb.Response(status=200)
    presp = pu.create_proposal_response(
        pu.marshal(prop), results, b"", resp,
        proppb.ChaincodeID(name=CC), FakeSigner())
    env = pu.create_signed_tx(prop, [presp], FakeSigner(b"client"))
    return pu.marshal(env), tx_id


def _config_env(mode: bytes) -> bytes:
    """A CONFIG-typed envelope whose payload data carries the stub
    "mode" the validator adopts at commit (the bundle-update analog)."""
    ch = pu.make_channel_header(common.HeaderType.CONFIG, CHANNEL)
    sh = pu.create_signature_header(b"orderer", pu.random_nonce())
    payload = pu.make_payload(ch, sh, mode)
    return pu.marshal(common.Envelope(payload=pu.marshal(payload)))


def _chain_blocks(env_lists: list[list[bytes]]) -> list[bytes]:
    """Serialize a header-chained stream: genesis (config, mode A) +
    one block per env list. Returned raw so each twin parses private
    copies."""
    blocks = []
    genesis = pu.new_block(0, b"")
    genesis.data.data.append(_config_env(b"A"))
    genesis.header.data_hash = pu.block_data_hash(genesis.data)
    blocks.append(genesis)
    for envs in env_lists:
        prev = blocks[-1]
        blk = pu.new_block(prev.header.number + 1,
                           pu.block_header_hash(prev.header))
        for e in envs:
            blk.data.data.append(e)
        blk.header.data_hash = pu.block_data_hash(blk.data)
        blocks.append(blk)
    return [b.SerializeToString() for b in blocks]


# ------------------------------------------------------------- the seam

class SeamValidator:
    """State- and config-dependent verdicts over the real commit glue.

    Rules (per written key):
      need_mode_B:*     VALID only when the ADOPTED config mode is B
      need_policy_v2:*  VALID only when committed state __policy==v2
      __policy          always VALID; marks the block vp_dirty (the
                        BlockOverlay / record_valid analog)
    plus the duplicate-txid rule over known_txids + the ledger index.
    Validating ahead of the governing commit therefore yields WRONG
    codes — exactly what the pipeline barriers must prevent.
    """

    def __init__(self, ledger):
        self._ledger = ledger
        self.mode = b"A"
        self.calls: list[int] = []          # block numbers validated

    def adopt_config(self, block):
        env = pu.extract_envelope(block, 0)
        self.mode = pu.get_payload(env).data

    def _tx_code(self, env_bytes: bytes, known: set,
                 seen: set) -> tuple[int, str]:
        try:
            env = pu.unmarshal_envelope(env_bytes)
            ch = pu.get_channel_header(pu.get_payload(env))
        except Exception:
            return TVC.MARSHAL_TX_ERROR, ""
        if ch.type == common.HeaderType.CONFIG:
            return TVC.VALID, ""
        tx_id = ch.tx_id
        if tx_id in seen or tx_id in known or \
                self._ledger.get_transaction_by_id(tx_id) is not None:
            return TVC.DUPLICATE_TXID, tx_id
        seen.add(tx_id)
        txrw = extract_tx_rwset(env_bytes)
        if txrw is None:
            return TVC.INVALID_ENDORSER_TRANSACTION, tx_id
        for nsrw in txrw.ns_rwset:
            from fabric_tpu.protos import rwset as rwpb
            kv = rwpb.KVRWSet()
            kv.ParseFromString(nsrw.rwset)
            for w in kv.writes:
                if w.key.startswith("need_mode_B:") and \
                        self.mode != b"B":
                    return TVC.ENDORSEMENT_POLICY_FAILURE, tx_id
                if w.key.startswith("need_policy_v2:") and \
                        self._ledger.get_state(CC, "__policy") != b"v2":
                    return TVC.ENDORSEMENT_POLICY_FAILURE, tx_id
        return TVC.VALID, tx_id

    def validate_ahead(self, block, known_txids=None
                       ) -> ValidationResult:
        t0 = time.perf_counter()
        known = set(known_txids or ())
        seen: set = set()
        codes = []
        vp_dirty = False
        for env_bytes in block.data.data:
            code, _tx = self._tx_code(env_bytes, known, seen)
            codes.append(code)
            if code == TVC.VALID:
                txrw = extract_tx_rwset(env_bytes)
                if txrw is not None and any(
                        w.key == "__policy"
                        for nsrw in txrw.ns_rwset
                        for w in _kv(nsrw.rwset).writes):
                    vp_dirty = True
        self.calls.append(block.header.number)
        return ValidationResult(codes=codes, n_items=len(codes),
                                duration_s=time.perf_counter() - t0,
                                vp_dirty=vp_dirty)

    def publish_validation(self, block, result) -> None:
        while len(block.metadata.metadata) <= \
                common.BlockMetadataIndex.TRANSACTIONS_FILTER:
            block.metadata.metadata.append(b"")
        block.metadata.metadata[
            common.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
            bytes(result.codes)

    def validate(self, block):
        result = self.validate_ahead(block)
        self.publish_validation(block, result)
        return result.codes


def _kv(raw):
    from fabric_tpu.protos import rwset as rwpb
    kv = rwpb.KVRWSet()
    kv.ParseFromString(raw)
    return kv


class _StubTransientStore:
    def get(self, tx_id):
        return None

    def purge_by_txids(self, tx_ids):
        pass


class _StubPeer:
    def __init__(self):
        self.transient_store = _StubTransientStore()


def make_seam_channel(root: str, name: str = CHANNEL):
    """A real `peer.Channel` (commit glue, metrics, notification)
    over a real KVLedger, skipping the Bundle-building __init__ —
    the wheel-free seam."""
    from fabric_tpu.common import metrics as _pm
    ledger = KVLedger(name, os.path.join(root, name))
    ch = peer_mod.Channel.__new__(peer_mod.Channel)
    ch.channel_id = name
    ch.ledger = ledger
    ch._peer = _StubPeer()
    ch._lock = threading.Lock()
    ch._commit_listeners = []
    ch._commit_cond = threading.Condition()
    ch._applied_height = ledger.height
    ch.commit_pipeline = None
    validator = SeamValidator(ledger)
    ch.validator = validator
    ch.committer = LedgerCommitter(
        ledger, on_config_block=validator.adopt_config)
    prov = _pm.DisabledProvider()
    ch._m_pvt_commit = prov.new_histogram(
        peer_mod.PVT_COMMIT_BLOCK_DURATION).with_labels(
        "channel", name)
    ch._m_pvt_pull = prov.new_histogram(
        peer_mod.PVT_PULL_DURATION).with_labels("channel", name)
    ch._m_pvt_purge = prov.new_histogram(
        peer_mod.PVT_PURGE_DURATION).with_labels("channel", name)
    return ch


def _parse(raw: bytes) -> common.Block:
    blk = common.Block()
    blk.ParseFromString(raw)
    return blk


def _filters(ledger, upto: int) -> list[bytes]:
    out = []
    for n in range(1, upto):
        blk = ledger.block_store.get_block_by_number(n)
        out.append(bytes(blk.metadata.metadata[
            common.BlockMetadataIndex.TRANSACTIONS_FILTER]))
    return out


@pytest.fixture()
def stream(tmp_path):
    """Genesis + 5 blocks exercising every barrier:
      b1  plain writes (incl. txid T1)
      b2  __policy=v2 (vp_dirty)  +  duplicate of T1 (adjacent-block
          dup: caught only via known_txids threading)
      b3  need_policy_v2 txs — correct ONLY after b2's state commit
      b4  config block: mode B
      b5  need_mode_B txs — correct ONLY after b4's adoption
    """
    scratch = StateDB(DBHandle(KVStore(
        str(tmp_path / "scratch.db")), "s"))
    e_plain1, _t1 = _tx_env(scratch, "k1")
    dup_env = e_plain1            # same bytes, same txid
    e_plain2, _ = _tx_env(scratch, "k2")
    e_policy, _ = _tx_env(scratch, "__policy", b"v2")
    e_need_p1, _ = _tx_env(scratch, "need_policy_v2:a")
    e_need_p2, _ = _tx_env(scratch, "need_policy_v2:b")
    e_need_b1, _ = _tx_env(scratch, "need_mode_B:a")
    e_plain3, _ = _tx_env(scratch, "k3")
    return _chain_blocks([
        [e_plain1, e_plain2],
        [e_policy, dup_env],
        [e_need_p1, e_need_p2],
        [_config_env(b"B")],
        [e_need_b1, e_plain3],
    ])


def _run_sequential(tmp_path, stream, sub="seq"):
    ch = make_seam_channel(str(tmp_path / sub))
    ch.ledger.initialize_from_genesis(_parse(stream[0]))
    codes = [ch.process_block(_parse(raw)) for raw in stream[1:]]
    return ch, codes


class TestAppliedHeight:
    def test_wait_for_height_waits_for_state_not_the_block_store(
            self, tmp_path, stream):
        """Inside a commit the block store already holds the block
        while history and state do not: a reader woken then would read
        the state of the block before (the join-by-snapshot race)."""
        ch = make_seam_channel(str(tmp_path / "applied"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        history_commit = ch.ledger.history_db.commit_block
        seen = []

        def inside_commit(block, codes, parsed):
            seen.append((ch.ledger.height,
                         ch.ledger.get_state(CC, "k1"),
                         ch.wait_for_height(block.header.number + 1,
                                            0.01)))
            return history_commit(block, codes, parsed)
        ch.ledger.history_db.commit_block = inside_commit
        ch.process_block(_parse(stream[1]))
        assert seen == [(2, None, False)]
        assert ch.wait_for_height(2, 0)
        assert ch.ledger.get_state(CC, "k1") == b"v"

    def test_wait_for_height_past_and_future(self, tmp_path, stream):
        ch, _ = _run_sequential(tmp_path, stream)
        assert ch.height == len(stream)
        assert all(ch.wait_for_height(h, 0)
                   for h in range(len(stream) + 1))
        assert not ch.wait_for_height(len(stream) + 1, 0.01)
        assert ch.wait_for_block(len(stream) - 1, 0)


class TestParity:
    def test_mixed_stream_bit_identical(self, tmp_path, stream):
        faults.clear()      # pins fallback/barrier counts
        seq_ch, seq_codes = _run_sequential(tmp_path, stream)

        pipe_ch = make_seam_channel(str(tmp_path / "pipe"))
        pipe_ch.ledger.initialize_from_genesis(_parse(stream[0]))
        committed = []
        pipeline = CommitPipeline(
            pipe_ch, depth=1,
            on_committed=lambda s, b, c: committed.append((s, c)))
        try:
            for i, raw in enumerate(stream[1:], start=1):
                pipeline.submit(i, raw=raw)
            pipeline.drain(timeout=30)
        finally:
            pipeline.stop()

        assert [c for _s, c in sorted(committed)] == seq_codes
        # TRANSACTIONS_FILTER bytes off the stored blocks
        assert _filters(pipe_ch.ledger, 6) == _filters(seq_ch.ledger, 6)
        # commit-hash chain: the strongest equality — every code byte
        # and data hash of every block matched
        assert pipe_ch.ledger.commit_hash == seq_ch.ledger.commit_hash
        assert pipe_ch.ledger.height == seq_ch.ledger.height == 6

        # the interesting verdicts actually happened:
        flat = [c for blk in seq_codes for c in blk]
        assert TVC.DUPLICATE_TXID in flat
        assert all(c == TVC.VALID for c in seq_codes[2])   # b3 post-VP
        assert all(c == TVC.VALID for c in seq_codes[4])   # b5 post-cfg
        # barriers fired for the vp update and the config block
        assert pipeline.stats["barriers"] >= 2
        assert pipeline.stats["validated_ahead"] == 5
        assert pipeline.stats["fallbacks"] == 0
        seq_ch.ledger.close()
        pipe_ch.ledger.close()

    def test_without_barrier_codes_would_differ(self, tmp_path, stream):
        """The control experiment: validating b3/b5 BEFORE their
        governing commits yields different codes — proof the barriers
        are load-bearing, not decorative."""
        ch = make_seam_channel(str(tmp_path / "ctl"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        # validate b3 against genesis state (no __policy committed)
        early = ch.validator.validate_ahead(_parse(stream[3]))
        assert TVC.ENDORSEMENT_POLICY_FAILURE in early.codes
        # validate b5 against mode A
        early5 = ch.validator.validate_ahead(_parse(stream[5]))
        assert TVC.ENDORSEMENT_POLICY_FAILURE in early5.codes
        ch.ledger.close()

    def test_overlap_is_measured(self, tmp_path, stream):
        """With a slowed commit, validate(N+1) demonstrably runs
        inside commit(N)'s window: overlap_ratio > 0."""
        faults.clear()      # pins committed/overlap stats
        ch = make_seam_channel(str(tmp_path / "ovl"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        orig = ch.commit_validated

        def slow_commit(block, codes, **kw):
            time.sleep(0.05)
            return orig(block, codes, **kw)
        ch.commit_validated = slow_commit
        # widen stage A too: with instant validation, a lagging commit
        # worker on a loaded 1-core box can make every validate window
        # miss every commit window (scheduling flake)
        orig_va = ch.validator.validate_ahead

        def slow_validate(block, known_txids=None):
            time.sleep(0.02)
            return orig_va(block, known_txids=known_txids)
        ch.validator.validate_ahead = slow_validate
        pipeline = CommitPipeline(ch, depth=1)
        try:
            for i, raw in enumerate(stream[1:], start=1):
                pipeline.submit(i, raw=raw)
            pipeline.drain(timeout=30)
        finally:
            pipeline.stop()
        assert pipeline.stats["committed"] == 5
        assert pipeline.overlap_ratio > 0.0
        ch.ledger.close()


class TestFaults:
    def test_stage_a_fault_falls_back_sequential(self, tmp_path,
                                                 stream):
        faults.clear()      # the test arms its own fault
        seq_ch, seq_codes = _run_sequential(tmp_path, stream)
        ch = make_seam_channel(str(tmp_path / "fault"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        faults.arm("commit.validate_ahead", mode="error", count=2)
        pipeline = CommitPipeline(ch, depth=1)
        try:
            for i, raw in enumerate(stream[1:], start=1):
                pipeline.submit(i, raw=raw)
            pipeline.drain(timeout=30)
        finally:
            pipeline.stop()
            faults.reset()
        assert pipeline.stats["fallbacks"] == 2
        assert ch.ledger.commit_hash == seq_ch.ledger.commit_hash
        assert _filters(ch.ledger, 6) == _filters(seq_ch.ledger, 6)
        seq_ch.ledger.close()
        ch.ledger.close()

    def test_barrier_fault_demotes_not_corrupts(self, tmp_path,
                                                stream):
        faults.clear()      # the test arms its own fault
        seq_ch, _ = _run_sequential(tmp_path, stream)
        ch = make_seam_channel(str(tmp_path / "bfault"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        faults.arm("commit.barrier", mode="error", count=1)
        pipeline = CommitPipeline(ch, depth=1)
        try:
            for i, raw in enumerate(stream[1:], start=1):
                pipeline.submit(i, raw=raw)
            pipeline.drain(timeout=30)
        finally:
            pipeline.stop()
            faults.reset()
        assert pipeline.stats["fallbacks"] >= 1
        assert ch.ledger.commit_hash == seq_ch.ledger.commit_hash
        seq_ch.ledger.close()
        ch.ledger.close()

    def test_forged_block_rejects_and_reset_recovers(self, tmp_path,
                                                     stream):
        faults.clear()      # pins the rejection path

        class RejectOnceMCS:
            def __init__(self):
                self.rejected = 0

            def verify_block(self, cid, seq, block):
                if seq == 2 and not self.rejected:
                    self.rejected += 1
                    raise BlockVerificationError("forged")

        ch = make_seam_channel(str(tmp_path / "rej"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        pipeline = CommitPipeline(ch, mcs=RejectOnceMCS(), depth=1)
        try:
            pipeline.submit(1, raw=stream[1])
            pipeline.submit(2, raw=stream[2])
            with pytest.raises(CommitPipelineError) as ei:
                pipeline.drain(timeout=30)
            assert ei.value.stage == "verify"
            assert ei.value.seq == 2
            # the sequential-retry recovery: reset to committed
            # height, re-feed from there
            pipeline.reset()
            assert pipeline.next_seq == ch.ledger.height
            for i in range(pipeline.next_seq, 6):
                pipeline.submit(i, raw=stream[i])
            pipeline.drain(timeout=30)
        finally:
            pipeline.stop()
        assert ch.ledger.height == 6
        ch.ledger.close()

    def test_depth_zero_refused(self, tmp_path, stream):
        ch = make_seam_channel(str(tmp_path / "d0"))
        with pytest.raises(ValueError, match="depth"):
            CommitPipeline(ch, depth=0)
        ch.ledger.close()


class TestCrash:
    def test_crash_between_validate_ahead_and_commit(self, tmp_path,
                                                     stream):
        """Kill the pipeline while commit(b1) is in flight and
        validate(b2) has already finished: NOTHING of b2 is published
        (no filter stamp, no durable bytes), and a reopened ledger
        replays both blocks to the same commit hash as the sequential
        twin."""
        faults.clear()      # pins stage timings around the crash
        seq_ch, _ = _run_sequential(tmp_path, stream)

        root = str(tmp_path / "crash")
        ch = make_seam_channel(root)
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        commit_entered = threading.Event()
        hold_commit = threading.Event()
        crashed = threading.Event()
        orig = ch.commit_validated

        def gated(block, codes, **kw):
            commit_entered.set()
            hold_commit.wait(10)
            if crashed.is_set():
                # the crash lands BEFORE anything durable happens
                raise RuntimeError("simulated crash before commit")
            return orig(block, codes, **kw)
        ch.commit_validated = gated

        pipeline = CommitPipeline(ch, depth=1)
        pipeline.submit(1, raw=stream[1])
        pipeline.submit(2, raw=stream[2])
        assert commit_entered.wait(10)
        deadline = time.monotonic() + 10
        spec = None
        while spec is None:
            assert time.monotonic() < deadline, \
                "validate-ahead of b2 never ran"
            with pipeline._cond:
                if pipeline._validated:
                    spec = pipeline._validated[0]
            time.sleep(0.01)
        # b2 validated while b1 uncommitted: no early side effects —
        # its in-memory block carries NO transactions filter and the
        # store has neither block
        assert pipeline.stats["validated_ahead"] == 2
        filt_idx = common.BlockMetadataIndex.TRANSACTIONS_FILTER
        assert len(spec.block.metadata.metadata) <= filt_idx or \
            not spec.block.metadata.metadata[filt_idx]
        assert ch.ledger.height == 1

        # crash: abandon the pipeline mid-commit, reopen from disk
        crashed.set()
        hold_commit.set()            # unblock the worker so stop joins
        pipeline.stop()
        ch.ledger.close()

        re_ch = make_seam_channel(root)    # same dir: real block store
        assert re_ch.ledger.height == 1    # nothing was committed
        for raw in stream[1:]:
            re_ch.process_block(_parse(raw))
        assert re_ch.ledger.commit_hash == seq_ch.ledger.commit_hash
        assert _filters(re_ch.ledger, 6) == _filters(seq_ch.ledger, 6)
        seq_ch.ledger.close()
        re_ch.ledger.close()


class TestDeliverClientPath:
    def test_deliverer_feeds_pipeline(self, tmp_path, stream):
        """The deliver-client ingest path: a stream endpoint feeding a
        pipelined channel commits everything, without the inline
        verify+process of the sequential branch."""
        from fabric_tpu.peer.deliverclient import Deliverer
        from fabric_tpu.protos import orderer as ordpb
        faults.reset()

        ch = make_seam_channel(str(tmp_path / "dlv"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        ch.commit_pipeline = CommitPipeline(ch, depth=1)

        served = threading.Event()

        class Endpoint:
            """Serves from the seek position like a real handler, so
            the reconnect loop (re-seek from the committed height)
            stays consistent with the pipeline's resets."""

            def __init__(self, raws):
                self._raws = raws     # raws[0] is block 1

            def handle(self, env):
                seek = ordpb.SeekInfo()
                seek.ParseFromString(pu.get_payload(env).data)
                start = seek.start.specified.number
                todo = self._raws[start - 1:]
                if not todo:
                    served.set()
                    time.sleep(0.02)   # tip: nothing new yet
                    return
                for raw in todo:
                    yield ordpb.DeliverResponse(block=_parse(raw))

        endpoint = Endpoint(stream[1:])
        deliverer = Deliverer(ch, FakeSigner(b"peer"),
                              lambda: endpoint, mcs=None)
        deliverer.start()
        try:
            assert served.wait(10)
            deadline = time.monotonic() + 10
            while ch.ledger.height < 6:
                assert time.monotonic() < deadline, \
                    f"stalled at height {ch.ledger.height}"
                time.sleep(0.02)
        finally:
            deliverer.stop()
            ch.commit_pipeline.stop()
        assert ch.ledger.height == 6
        ch.ledger.close()


class _FakeGChannel:
    on_block = on_state_request = on_state_response = None

    def publish_state_info(self, h):
        pass

    def heights(self):
        return {}

    def _tag_channel(self, msg):
        pass


class _FakeNode:
    def join_channel(self, cid):
        return _FakeGChannel()

    def gossip_block(self, cid, seq, raw):
        pass


class TestLeaderAdapterPath:
    def test_leader_runahead_feeds_pipeline(self, tmp_path, stream):
        """The leader's orderer intake: with a pipelined channel the
        adapter allows `depth` blocks of runahead (fetch+validate of
        N+1 proceeds while N commits) and the stream still lands
        fully, in order."""
        from fabric_tpu.gossip.service import _LeaderChannelAdapter
        from fabric_tpu.gossip.state import GossipStateProvider
        faults.clear()

        ch = make_seam_channel(str(tmp_path / "leader"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        ch.commit_pipeline = CommitPipeline(ch, depth=1)
        provider = GossipStateProvider(_FakeNode(), CHANNEL, ch, None,
                                       anti_entropy_interval_s=60)
        adapter = _LeaderChannelAdapter(ch, provider)
        provider.start()
        try:
            for i in range(1, 6):
                adapter.process_block(_parse(stream[i]))
            deadline = time.monotonic() + 15
            while ch.ledger.height < 6:
                assert time.monotonic() < deadline, \
                    f"stalled at height {ch.ledger.height}"
                time.sleep(0.02)
        finally:
            provider.stop()
            ch.commit_pipeline.stop()
        assert ch.ledger.height == 6
        ch.ledger.close()


class _RejectOnceMCS:
    """Forged-block simulation: rejects `bad_seq` exactly once."""

    def __init__(self, bad_seq):
        self.bad_seq = bad_seq
        self.rejected = 0

    def verify_block(self, cid, seq, block):
        if seq == self.bad_seq and not self.rejected:
            self.rejected += 1
            raise BlockVerificationError("forged")


class TestDeliverRejection:
    def test_forged_block_reconnects_immediately(self, tmp_path,
                                                 stream):
        """A forged block mid-stream must surface synchronously (via
        wait_validated) — tearing the stream for reconnect/failover —
        not idle unseen at the tip; the re-seek then heals."""
        from fabric_tpu.peer.deliverclient import Deliverer
        from fabric_tpu.protos import orderer as ordpb
        faults.clear()

        ch = make_seam_channel(str(tmp_path / "dlvrej"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        mcs = _RejectOnceMCS(bad_seq=3)
        ch.commit_pipeline = CommitPipeline(ch, mcs=mcs, depth=1)

        class Endpoint:
            def __init__(self, raws):
                self._raws = raws

            def handle(self, env):
                seek = ordpb.SeekInfo()
                seek.ParseFromString(pu.get_payload(env).data)
                start = seek.start.specified.number
                for raw in self._raws[start - 1:]:
                    yield ordpb.DeliverResponse(block=_parse(raw))
                time.sleep(0.02)

        deliverer = Deliverer(ch, FakeSigner(b"peer"),
                              lambda: Endpoint(stream[1:]), mcs=None,
                              retry_base_s=0.01, retry_max_s=0.05)
        deliverer.start()
        try:
            deadline = time.monotonic() + 15
            while ch.ledger.height < 6:
                assert time.monotonic() < deadline, \
                    f"stalled at height {ch.ledger.height}"
                time.sleep(0.02)
        finally:
            deliverer.stop()
            ch.commit_pipeline.stop()
        assert mcs.rejected == 1
        # the rejection tore the stream: at least one reconnect
        assert deliverer.reconnects >= 1
        assert ch.ledger.height == 6
        ch.ledger.close()


class TestGossipTipRejection:
    def test_rejection_at_tip_recovers_via_idle_probe(self, tmp_path,
                                                      stream):
        """A forged LAST block (nothing arriving after it) must not
        wedge: the feeder's idle tick probes the sticky error, rewinds
        the buffer, and an anti-entropy re-delivery heals."""
        from fabric_tpu.gossip.state import GossipStateProvider
        faults.clear()

        ch = make_seam_channel(str(tmp_path / "gtip"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        mcs = _RejectOnceMCS(bad_seq=5)
        ch.commit_pipeline = CommitPipeline(ch, mcs=mcs, depth=1)
        provider = GossipStateProvider(_FakeNode(), CHANNEL, ch, None,
                                       anti_entropy_interval_s=60)
        provider.start()
        try:
            for i in range(1, 6):
                provider.buffer.push(i, stream[i])
            # block 5 is rejected at the TIP — no newer block ever
            # arrives to shake the loop loose; only the feeder's idle
            # probe can rewind the buffer. Play anti-entropy: keep
            # re-delivering from the committed height (pushes below
            # the buffer's _next are dropped until the rewind lands).
            deadline = time.monotonic() + 20
            while ch.ledger.height < 6:
                assert time.monotonic() < deadline, \
                    f"wedged at height {ch.ledger.height}"
                for i in range(ch.ledger.height, 6):
                    provider.buffer.push(i, stream[i])
                time.sleep(0.05)
        finally:
            provider.stop()
            ch.commit_pipeline.stop()
        assert mcs.rejected == 1
        ch.ledger.close()


class TestGossipStatePath:
    def test_state_provider_commit_loop_uses_pipeline(self, tmp_path,
                                                      stream):
        """The gossip ingest path: the commit loop becomes the
        pipeline feeder; out-of-order arrival still commits in order
        and heights publish."""
        from fabric_tpu.gossip.state import GossipStateProvider
        faults.reset()

        class _FakeGChannel:
            on_block = None
            on_state_request = None
            on_state_response = None

            def publish_state_info(self, h):
                pass

            def heights(self):
                return {}

            def _tag_channel(self, msg):
                pass

        class _FakeNode:
            def join_channel(self, cid):
                return _FakeGChannel()

            def gossip_block(self, cid, seq, raw):
                pass

        ch = make_seam_channel(str(tmp_path / "gsp"))
        ch.ledger.initialize_from_genesis(_parse(stream[0]))
        ch.commit_pipeline = CommitPipeline(ch, depth=1)
        provider = GossipStateProvider(_FakeNode(), CHANNEL, ch, None,
                                       anti_entropy_interval_s=60)
        provider.start()
        try:
            # push out of order: 2..5 first, then 1 releases the run
            for i in (2, 3, 4, 5):
                provider.buffer.push(i, stream[i])
            provider.buffer.push(1, stream[1])
            deadline = time.monotonic() + 15
            while ch.ledger.height < 6:
                assert time.monotonic() < deadline, \
                    f"stalled at height {ch.ledger.height}"
                time.sleep(0.02)
        finally:
            provider.stop()
            ch.commit_pipeline.stop()
        assert ch.ledger.height == 6
        ch.ledger.close()
