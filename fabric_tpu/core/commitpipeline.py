"""Pipelined block intake: validate block N+1 while block N commits.

The peer's intake path was strictly sequential per block: pop one
block, verify + validate (device-bound), gather private data, commit
(host/IO-bound), then touch the next block — so the TPU idles during
every state-DB/block-store commit and the host idles during every
batched verify. `CommitPipeline` decouples the two:

  stage A (device)  mcs.verify_block + TxValidator.validate_ahead for
                    block N+1 — including protobuf parse, the tx-id
                    scan and ONE up-front extract_tx_rwset pass — on
                    the validate worker thread;
  stage B (host)    pvt-data gather + kvledger.commit_block for block
                    N on the commit worker thread.

This is the cross-block analog of the within-batch host<->device
overlap from round 6 (`BCCSP.TPU.PipelineChunk`), the same structure
hardware verification engines use to keep the cryptographic unit
saturated (arXiv:2112.02229) under the batching-vs-latency trade of
arXiv:2302.00418.

Correctness barriers (the interesting part) are explicit:

  * config blocks — validating past block N requires N's bundle
    (including the BlockValidation policy `verify_block` evaluates),
    so stage A drains — waits for the commit of N — before touching
    N+1 whenever an uncommitted predecessor is a config block;
  * validation-parameter updates — a predecessor whose VALID txs
    changed key-level endorsement parameters (statebased.BlockOverlay
    via record_valid), or that touched the `_lifecycle` namespace,
    must reach the state DB before later blocks resolve policies
    against it;
  * stage-A failure — any unexpected validate-ahead error (including
    an armed `commit.validate_ahead` / `commit.barrier` fault) demotes
    that block to the sequential path on the commit worker and
    barriers everything behind it. Only a genuine
    `BlockVerificationError` (forged/mismatched block) rejects.

Speculative validation publishes NO side effects early: the
TRANSACTIONS_FILTER stamp and the validation metrics for N+1 are
deferred (`TxValidator.publish_validation`) until N is durably
committed, and nothing of N+1 touches disk — a crash mid-pipeline
replays identically to the sequential path. Duplicate-txid detection
stays bit-identical: the tx-ids of validated-but-uncommitted
predecessors are threaded into `validate_ahead(known_txids=...)` so a
txid repeated across adjacent in-flight blocks is still caught.

Any error is sticky: the next `submit()`/`drain()` raises a
`CommitPipelineError`, the feeder calls `reset()` (which drops all
in-flight work and re-syncs to the committed ledger height) and
re-fetches from there — exactly the sequential retry semantics, with
at most `depth` extra blocks of re-fetch.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

from fabric_tpu.common import clustertrace, faults
from fabric_tpu.common import metrics as metrics_mod
from fabric_tpu.common import overload
from fabric_tpu.common import tracing
from fabric_tpu.common.hotpath import hot_path

logger = logging.getLogger("commitpipeline")


class CommitPipelineError(Exception):
    """A pipelined block failed. `seq` is the failing block, `stage`
    is "verify" | "validate" | "commit". The feeder's recovery is the
    sequential path's: reset + re-fetch from the committed height."""

    def __init__(self, seq: int, stage: str, cause: BaseException):
        super().__init__(f"block [{seq}] failed in pipeline stage "
                         f"{stage}: {cause}")
        self.seq = seq
        self.stage = stage
        self.cause = cause


class _Rejected(Exception):
    """Internal: a genuine block rejection (not a pipeline fault)."""

    def __init__(self, stage: str, cause: BaseException):
        super().__init__(str(cause))
        self.stage = stage
        self.cause = cause


class _Stale(Exception):
    """Internal: the pipeline was reset while this item was in
    flight; drop it without side effects."""


@dataclass
class _Item:
    seq: int
    epoch: int
    raw: Optional[bytes] = None
    block: object = None
    # stage-A products (None until validated)
    result: object = None        # txvalidator.ValidationResult
    rwsets: Optional[list] = None
    tx_ids: Optional[list] = None
    # sequential-fallback demotion (stage-A failure)
    fallback: bool = False
    verified: bool = False       # mcs.verify_block already passed
    # the block's own `peer.block` span, allocated at submit under
    # the feeder's ambient context (`parent_tctx`) and recorded once
    # the block is committed: the validate/commit spans on the two
    # worker threads are its children, as at Depth 0
    tctx: object = None
    parent_tctx: object = None
    t_submit: float = 0.0


class CommitPipeline:
    """Two-stage overlapped intake for one channel.

    `channel` duck-type: `.channel_id`, `.ledger` (block_store +
    height), `.validator` (validate_ahead/publish_validation),
    `.commit_validated(block, codes, rwsets=, tx_ids=)` and
    `.process_block(block)` (the sequential fallback) —
    fabric_tpu.peer.Channel satisfies it. `mcs` is optional (None
    skips block verification — the caller already verified)."""

    def __init__(self, channel, mcs=None, depth: int = 1,
                 metrics_provider=None,
                 on_committed: Optional[Callable] = None,
                 node_id: Optional[str] = None):
        if depth < 1:
            raise ValueError("CommitPipeline needs depth >= 1 "
                             "(0 = sequential: do not build one)")
        self.channel = channel
        self.depth = depth
        # cross-node trace attribution (round 18): the COMMITTING
        # node's identity — labels e2e_commit_seconds and the
        # validate/commit spans' track in the merged cluster trace
        self.node_id = node_id
        self._e2e_node = node_id or tracing.current_node() or "local"
        self._mcs = mcs
        self.on_committed = on_committed
        self._cond = threading.Condition()
        self._intake: list[_Item] = []     # submitted, not validated
        self._validated: list[_Item] = []  # validated, not committed
        self._committing: Optional[_Item] = None
        self._inflight = 0                 # submitted - committed
        self._epoch = 0                    # bumped by reset()
        self._next_seq = channel.ledger.height
        self._committed_through = channel.ledger.height - 1
        self._validated_through = channel.ledger.height - 1
        # validation of blocks AFTER _barrier_seq must wait until
        # _barrier_seq is committed; reason feeds the metric label
        self._barrier_seq: Optional[int] = None
        self._barrier_reason = ""
        self._error: Optional[CommitPipelineError] = None
        self._stop = threading.Event()
        # tx-ids of in-flight validated/committing blocks, for the
        # duplicate-txid check of later blocks; entries are dropped
        # only AFTER their block is durably committed (and therefore
        # visible through the ledger's own txid index)
        self._inflight_txids: dict[int, list[str]] = {}
        # overlap accounting: the commit-busy windows stage A
        # intersects — the currently-active commit plus the most
        # recently completed one
        self._commit_window: tuple[float, float] = (0.0, 0.0)
        self._commit_active_since: Optional[float] = None

        self.stats = {
            "submitted": 0, "validated_ahead": 0, "committed": 0,
            "fallbacks": 0, "barriers": 0, "sheds": 0,
            "validate_s": 0.0, "commit_s": 0.0, "overlap_s": 0.0,
        }
        self._last_shed_t: Optional[float] = None
        overload.register_stage(
            f"commit.pipeline.{channel.channel_id}", self)

        provider = metrics_provider or metrics_mod.DisabledProvider()
        cid = channel.channel_id
        self._m_depth = provider.new_gauge(
            metrics_mod.COMMIT_PIPELINE_DEPTH_OPTS).with_labels(
            "channel", cid)
        self._m_validate = provider.new_gauge(
            metrics_mod.COMMIT_PIPELINE_VALIDATE_SECONDS_OPTS
        ).with_labels("channel", cid)
        self._m_commit = provider.new_gauge(
            metrics_mod.COMMIT_PIPELINE_COMMIT_SECONDS_OPTS
        ).with_labels("channel", cid)
        self._m_overlap = provider.new_gauge(
            metrics_mod.COMMIT_PIPELINE_OVERLAP_RATIO_OPTS
        ).with_labels("channel", cid)
        self._m_barriers = provider.new_counter(
            metrics_mod.COMMIT_PIPELINE_BARRIER_TOTAL_OPTS)
        self._barrier_labels = ("channel", cid)
        self._m_depth.set(depth)

        self._validate_thread = threading.Thread(
            target=self._validate_loop,
            name=f"commit-pipeline-validate-{cid}", daemon=True)
        self._commit_thread = threading.Thread(
            target=self._commit_loop,
            name=f"commit-pipeline-commit-{cid}", daemon=True)
        self._validate_thread.start()
        self._commit_thread.start()

    # -- feeder API (the ingest thread) --

    @property
    def next_seq(self) -> int:
        with self._cond:
            return self._next_seq

    def overload_stats(self) -> dict:
        """Overload-registry protocol (common/overload.py): in-flight
        blocks are the stage's depth, deadline-expired backpressure
        waits its sheds."""
        with self._cond:
            return {
                "depth": self._inflight,
                "capacity": self.depth + 1,
                "sheds": self.stats["sheds"],
                "puts": self.stats["submitted"],
                "last_shed_t": self._last_shed_t,
            }

    def submit(self, seq: int, raw: Optional[bytes] = None,
               block=None, abort=None) -> None:
        """Enqueue the next in-sequence block (bytes or parsed).
        Blocks while more than `depth` blocks are in flight
        (backpressure); raises the pipeline's sticky error if a
        previous block failed. `abort` (an optional threading.Event,
        e.g. the feeder's own stop flag) breaks the backpressure wait
        so a stopping feeder is not held hostage by a slow commit.

        The backpressure wait is bounded (round 12) by the caller's
        ambient deadline budget, else `default_enqueue_budget_s()`:
        on expiry it raises `OverloadError` — NON-sticky and clean
        (nothing was enqueued, `next_seq` unchanged); the feeder
        simply retries the same block, keeping backpressure on the
        network without an unbounded wait."""
        if raw is None and block is None:
            raise ValueError("submit needs raw bytes or a parsed block")
        budget = overload.Deadline.remaining_or(
            overload.default_enqueue_budget_s())
        deadline = time.monotonic() + max(0.0, budget)
        with self._cond:
            self._raise_if_error()
            if seq != self._next_seq:
                raise CommitPipelineError(
                    seq, "verify",
                    ValueError(f"out-of-order submit: expected "
                               f"[{self._next_seq}]"))
            while self._inflight > self.depth and \
                    self._error is None and not self._stop.is_set() \
                    and not (abort is not None and abort.is_set()):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self.stats["sheds"] += 1
                    self._last_shed_t = time.monotonic()
                    tracing.note_shed(
                        f"commit.pipeline.{self.channel.channel_id}")
                    raise overload.OverloadError(
                        f"commit.pipeline.{self.channel.channel_id}",
                        f"backpressure wait for block [{seq}] "
                        f"exceeded the deadline budget")
                self._cond.wait(timeout=min(0.2, remaining))
            self._raise_if_error()
            if self._stop.is_set() or \
                    (abort is not None and abort.is_set()):
                raise CommitPipelineError(
                    seq, "verify", RuntimeError("pipeline stopped"))
            parent = tracing.capture()
            self._intake.append(_Item(
                seq=seq, epoch=self._epoch, raw=raw, block=block,
                tctx=tracing.child_context(parent) or parent,
                parent_tctx=parent, t_submit=time.perf_counter()))
            self._inflight += 1
            self._next_seq = seq + 1
            self.stats["submitted"] += 1
            self._cond.notify_all()

    def drain(self, timeout: Optional[float] = None,
              abort=None) -> None:
        """Wait until every submitted block is committed; raises the
        sticky error if any block failed. `abort` (an optional
        threading.Event) ends the wait early without error — for a
        feeder that is shutting down."""
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        with self._cond:
            while self._inflight > 0 and self._error is None and \
                    not self._stop.is_set() and \
                    not (abort is not None and abort.is_set()):
                remaining = None if deadline is None else \
                    deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"commit pipeline drain timed out with "
                        f"{self._inflight} block(s) in flight")
                self._cond.wait(timeout=0.2 if remaining is None
                                else min(0.2, remaining))
            self._raise_if_error()

    def reset(self) -> None:
        """Drop all in-flight work, clear the sticky error, and
        re-sync to the committed ledger height. Waits for an
        in-progress commit to finish first (a commit is durable work;
        it cannot be abandoned mid-write). Workers recognize items
        from the old epoch and discard them without side effects."""
        with self._cond:
            self._epoch += 1
            self._intake.clear()
            self._validated.clear()
            self._cond.notify_all()
            while self._committing is not None and \
                    not self._stop.is_set():
                self._cond.wait(timeout=0.2)
            self._inflight_txids.clear()
            self._error = None
            self._barrier_seq = None
            self._inflight = 0
            self._next_seq = self.channel.ledger.height
            self._committed_through = self._next_seq - 1
            self._validated_through = self._next_seq - 1
            self._cond.notify_all()

    def stop(self) -> None:
        """Abandon in-flight work and join the workers. Uncommitted
        blocks are simply not committed — crash-equivalent, which the
        sequential replay heals."""
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        for t in (self._validate_thread, self._commit_thread):
            t.join(timeout=5)

    def wait_validated(self, seq: int,
                       timeout: Optional[float] = None,
                       abort=None) -> None:
        """Block until stage A has handled block `seq` (validated, or
        demoted to the sequential fallback), raising the sticky error
        if it was rejected instead. A deliver-stream feeder calls this
        after each submit so a forged block from the orderer surfaces
        IMMEDIATELY — triggering reconnect + endpoint failover — as it
        did on the sequential path, instead of idling at the tip; the
        overlap is untouched (block N's commit still runs during this
        wait for validate(N+1))."""
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        with self._cond:
            while self._validated_through < seq and \
                    self._error is None and not self._stop.is_set() \
                    and not (abort is not None and abort.is_set()):
                remaining = None if deadline is None else \
                    deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"block [{seq}] not validated in time")
                self._cond.wait(timeout=0.2 if remaining is None
                                else min(0.2, remaining))
            self._raise_if_error()

    def check_error(self) -> None:
        """Non-blocking probe: raise the sticky error if a pipelined
        block failed, return immediately otherwise. Feeders call this
        on idle ticks so failures surface without draining (and
        therefore serializing) the pipeline."""
        with self._cond:
            self._raise_if_error()

    def _raise_if_error(self) -> None:
        if self._error is not None:
            raise self._error

    # -- stage A: validate ahead --

    def _validate_loop(self) -> None:
        tracing.set_node(self.node_id)
        while not self._stop.is_set():
            with self._cond:
                # a pending sticky error also parks the worker (the
                # feeder must reset() first) — without the second
                # clause this would busy-spin until then
                while (not self._intake or self._error is not None) \
                        and not self._stop.is_set():
                    self._cond.wait(timeout=0.2)
                if self._stop.is_set():
                    return
                item = self._intake.pop(0)
            reject: Optional[_Rejected] = None
            demoted: Optional[BaseException] = None
            try:
                self._validate_one(item)
            except _Stale:
                continue
            except _Rejected as e:
                reject = e
            except Exception as e:   # noqa: BLE001 — demote, never drop
                demoted = e
            with self._cond:
                if item.epoch != self._epoch or self._stop.is_set():
                    continue          # reset raced us: drop silently
                if reject is not None:
                    if self._error is None:
                        self._error = CommitPipelineError(
                            item.seq, reject.stage, reject.cause)
                    self._cond.notify_all()
                    continue
                if demoted is not None:
                    self._demote_locked(item, demoted)
                if self._error is None:
                    self._validated.append(item)
                    self._validated_through = item.seq
                    if item.tx_ids is not None:
                        self._inflight_txids[item.seq] = [
                            t for t in item.tx_ids if t]
                    self._cond.notify_all()

    def _demote_locked(self, item: _Item, cause: BaseException) -> None:
        """Stage-A failure → sequential fallback: the commit worker
        runs the plain verify+validate+commit path for this block, and
        everything behind it barriers until it lands."""
        logger.warning("[%s] validate-ahead of block [%d] failed (%s);"
                       " falling back to sequential",
                       self.channel.channel_id, item.seq, cause)
        item.fallback = True
        item.result = None
        item.rwsets = None
        item.tx_ids = None
        self.stats["fallbacks"] += 1
        self._barrier_seq = item.seq
        self._barrier_reason = "fallback"

    def _wait_barrier(self, item: _Item) -> None:
        """Drain the pipeline up to the pending barrier before
        validating `item`."""
        with self._cond:
            if item.epoch != self._epoch:
                raise _Stale()    # reset raced us: skip the (device-
                #                   bound) validation work entirely
            barrier = self._barrier_seq
            reason = self._barrier_reason
            if barrier is None or self._committed_through >= barrier:
                return
        # the armed chaos point: an error here demotes the block to
        # the sequential path (safe — stage B is ordered), a delay
        # models a slow predecessor commit
        faults.check("commit.barrier")
        self.stats["barriers"] += 1
        self._m_barriers.with_labels(*self._barrier_labels,
                                     "reason", reason).add(1)
        logger.debug("[%s] barrier before block [%d]: waiting for "
                     "commit of [%d] (%s)", self.channel.channel_id,
                     item.seq, barrier, reason)
        with self._cond:
            while self._committed_through < barrier and \
                    self._error is None and not self._stop.is_set() \
                    and item.epoch == self._epoch:
                self._cond.wait(timeout=0.2)
            if item.epoch != self._epoch:
                raise _Stale()
            if self._error is not None or self._stop.is_set():
                raise _Stale()

    @staticmethod
    def _parse_item(item: _Item) -> None:
        """Parse raw bytes into item.block (idempotent); a parse
        failure is a genuine rejection."""
        from fabric_tpu.protos import common
        if item.block is None:
            try:
                block = common.Block()
                block.ParseFromString(item.raw)
                item.block = block
            except Exception as e:
                raise _Rejected("verify", e) from e

    def _ensure_parsed_and_verified(self, item: _Item) -> None:
        """Parse (if needed) and run mcs.verify_block once, wrapping
        genuine rejections in _Rejected. Shared by stage A and the
        sequential-fallback path so rejection classification can
        never drift between them."""
        self._parse_item(item)
        if self._mcs is not None and not item.verified:
            from fabric_tpu.peer.mcs import BlockVerificationError
            try:
                self._mcs.verify_block(self.channel.channel_id,
                                       item.seq, item.block)
            except BlockVerificationError as e:
                raise _Rejected("verify", e) from e
        item.verified = True

    @hot_path
    def _validate_one(self, item: _Item) -> None:
        with tracing.span("commit.validate", parent=item.tctx,
                          seq=item.seq):
            self._validate_one_traced(item)

    @hot_path
    def _validate_one_traced(self, item: _Item) -> None:
        from fabric_tpu import protoutil as pu
        from fabric_tpu.ledger.kvledger import extract_tx_rwset

        faults.check("commit.validate_ahead")
        # parse WITHOUT verifying yet: verification must wait for the
        # barrier below (a config predecessor can change the
        # BlockValidation policy), but a parse failure rejects now
        self._parse_item(item)
        block = item.block

        # barrier BEFORE verify_block too: a config predecessor can
        # change the BlockValidation policy the verify evaluates
        self._wait_barrier(item)

        self._ensure_parsed_and_verified(item)

        t0 = time.perf_counter()
        with self._cond:
            known = [t for txids in self._inflight_txids.values()
                     for t in txids]

        n = len(block.data.data)
        with tracing.span("intake.txids", txs=n):
            tx_ids = self.channel.ledger.block_store.block_tx_ids(
                block)
        result = self.channel.validator.validate_ahead(
            block, known_txids=known)
        is_config = pu.is_config_block(block)
        rwsets = None
        barrier_reason = ""
        if is_config or block.header.number == 0:
            barrier_reason = "config"
        else:
            with tracing.span("intake.rwsets", txs=n):
                rwsets = [extract_tx_rwset(e) for e in block.data.data]
            if result.vp_dirty:
                barrier_reason = "vp_update"
            elif self._touches_lifecycle(rwsets, result.codes):
                barrier_reason = "lifecycle"
        t1 = time.perf_counter()

        item.result = result
        item.rwsets = rwsets
        item.tx_ids = tx_ids
        self.stats["validated_ahead"] += 1
        self._account_validate(t0, t1)
        if barrier_reason:
            with self._cond:
                if item.epoch == self._epoch:
                    self._barrier_seq = item.seq
                    self._barrier_reason = barrier_reason

    @staticmethod
    def _touches_lifecycle(rwsets, codes) -> bool:
        """Conservative: a VALID tx whose rwset mentions the
        `_lifecycle` namespace may change a chaincode definition later
        blocks validate under."""
        from fabric_tpu.core.scc import lifecycle as lc
        from fabric_tpu.protos import transaction as txpb
        for i, txrw in enumerate(rwsets):
            if txrw is None or \
                    codes[i] != txpb.TxValidationCode.VALID:
                continue
            for nsrw in txrw.ns_rwset:
                if nsrw.namespace == lc.NAMESPACE:
                    return True
        return False

    # -- stage B: ordered commit --

    def _commit_loop(self) -> None:
        tracing.set_node(self.node_id)
        while not self._stop.is_set():
            with self._cond:
                # park (don't spin) while a sticky error awaits reset
                while (not self._validated or
                       self._error is not None) and \
                        not self._stop.is_set():
                    self._cond.wait(timeout=0.2)
                if self._stop.is_set():
                    return
                item = self._validated.pop(0)
                self._committing = item
                self._commit_active_since = time.perf_counter()
            codes = None
            t0 = time.perf_counter()
            try:
                commit = tracing.span("commit.commit", parent=item.tctx,
                                      seq=item.seq,
                                      fallback=item.fallback)
                with commit, tracing.thread_io(commit):
                    if item.fallback:
                        codes = self._commit_fallback(item)
                    else:
                        # deferred validation side effects: the
                        # predecessor is durably committed NOW, so the
                        # TRANSACTIONS_FILTER stamp and validation
                        # metrics for this block are published
                        # sequentially-equivalently
                        self.channel.validator.publish_validation(
                            item.block, item.result)
                        codes = self.channel.commit_validated(
                            item.block, list(item.result.codes),
                            rwsets=item.rwsets, tx_ids=item.tx_ids)
            except _Rejected as e:
                self._fail_locked(item, e.stage, e.cause)
            except Exception as e:   # noqa: BLE001 — sticky, feeder retries
                logger.exception("[%s] pipelined commit of block [%d] "
                                 "failed", self.channel.channel_id,
                                 item.seq)
                self._fail_locked(item, "commit", e)
            t1 = time.perf_counter()
            if item.tctx is not item.parent_tctx:
                tracing.observe_span(
                    "peer.block", item.t_submit, t1,
                    parent=item.parent_tctx, ctx=item.tctx,
                    block=item.seq,
                    txs=len(item.block.data.data)
                    if item.block is not None else 0)
            with self._cond:
                self._committing = None
                self._commit_active_since = None
                self._commit_window = (t0, t1)
                if item.epoch == self._epoch:
                    self._inflight_txids.pop(item.seq, None)
                    if self._error is None and codes is not None:
                        self._committed_through = item.seq
                        self._inflight -= 1
                self._cond.notify_all()
            if codes is not None:
                self.stats["committed"] += 1
                self.stats["commit_s"] += t1 - t0
                self.stats["last_commit_s"] = t1 - t0
                # user-visible finality (round 18): first-ingress
                # birth -> durable commit on THIS node, feeding
                # e2e_commit_seconds{node=} and the SLO error budget.
                # No carrier/birth for this block's trace = no
                # observation (and tracing-off is a no-op).
                clustertrace.note_commit(item.tctx,
                                         node=self._e2e_node)
                # validate+commit wall for THIS block (fallbacks run
                # validation inside the commit window already): keeps
                # gossip's commit_duration histogram meaning the same
                # thing whether or not the pipeline is on
                self.stats["last_block_s"] = (t1 - t0) + (
                    item.result.duration_s
                    if not item.fallback and item.result is not None
                    else 0.0)
                self._m_commit.set(t1 - t0)
                if self.on_committed is not None:
                    try:
                        self.on_committed(item.seq, item.block, codes)
                    except Exception:   # noqa: BLE001
                        logger.exception("on_committed callback failed")

    def _fail_locked(self, item: _Item, stage: str,
                     cause: BaseException) -> None:
        with self._cond:
            if item.epoch == self._epoch and self._error is None:
                self._error = CommitPipelineError(item.seq, stage,
                                                  cause)
            self._cond.notify_all()

    def _commit_fallback(self, item: _Item) -> list[int]:
        """The sequential path for a demoted block: verify (if stage A
        never got there) + validate + commit, all on this worker, in
        order."""
        self._ensure_parsed_and_verified(item)
        return self.channel.process_block(item.block)

    # -- overlap accounting --

    def _account_validate(self, t0: float, t1: float) -> None:
        """How much of stage A's [t0,t1] ran while stage B was
        committing — the time the pipeline actually hid."""
        with self._cond:
            active = self._commit_active_since
            window = self._commit_window
        overlap = 0.0
        if active is not None:
            overlap += max(0.0, t1 - max(t0, active))
        cs, ce = window
        if ce > cs:
            overlap += max(0.0, min(t1, ce) - max(t0, cs))
        overlap = min(overlap, t1 - t0)
        self.stats["validate_s"] += t1 - t0
        self.stats["overlap_s"] += overlap
        self._m_validate.set(t1 - t0)
        if self.stats["validate_s"] > 0:
            self._m_overlap.set(
                self.stats["overlap_s"] / self.stats["validate_s"])

    @property
    def overlap_ratio(self) -> float:
        return (self.stats["overlap_s"] / self.stats["validate_s"]
                if self.stats["validate_s"] else 0.0)
