"""Process-wide fault-injection registry for chaos testing.

Liveness-critical paths carry NAMED fault points — hooks that are
no-ops in production (an unarmed `check()` is one dict lookup) but can
be armed by tests and chaos runs to raise, stall, or fail-N-times.
The well-known points:

    tpu.dispatch       every device batch dispatch (bccsp/tpu.py)
    tpu.compile        jit pipeline builds / AOT compiles
    tpu.table_persist  warm-table byte writers
    tpu.ed25519        the scheme router's Ed25519 device dispatch —
                       a fault serves the sub-batch on the host
                       reference path, bit-identical (bccsp/tpu.py)
    tpu.bls_aggregate  the staged BLS aggregate-verify path — a fault
                       serves the host reference pairing product
                       (bccsp/tpu.py verify_aggregate)
    tpu.device_lost    per-device point inside the sharded span feeder
                       and the quarantine probe (bccsp/tpu.py
                       _shard_put/_probe_device): checked with
                       arg=<full-mesh device index>, so chaos targets
                       chip k — an error there quarantines THAT chip
                       and the provider rebuilds a smaller mesh over
                       the survivors (common/devicehealth.py)
    tpu.device_straggler
                       same per-device seam, delay mode: the targeted
                       chip's transfer stream stalls, feeding the
                       straggler accounting that quarantines a chip
                       pacing the whole mesh (bccsp/tpu.py)
    raft.step          inbound raft messages (orderer raft chain loop)
    raft.wal_append    the raft WAL append seam (orderer/raft/
                       storage.py) — error mode drops the batch (the
                       chain demotes/retries), crash mode is the
                       crash-matrix kill point BEFORE the durable
                       write
    order.propose      the batched propose span of the ordering
                       admission window — a fault demotes the window
                       to per-block sequential proposes
                       (orderer/raft/chain.py)
    order.block_write  the block-write worker's span write
                       (orderer/raft/pipeline.py) — error mode is a
                       sticky stage failure (the chain demotes and
                       replays from the WAL), crash mode kills the
                       consenter between raft commit and the durable
                       block append
    net.drop           one matched message dropped by the network-
                       chaos layer (common/netchaos.py) — the arg
                       targets a link: an endpoint (either side),
                       `a>b` (directed) or `a|b|c` (either side in
                       the set)
    net.delay          one matched message held back delay_s seconds
                       (scheduled — the sender never blocks); arm
                       with mode `delay`
    net.dup            one matched message delivered twice
    net.reorder        one matched message held until <delay-field>
                       (default 4) later messages on its link passed
                       it — bounded reordering
    net.partition      installs a partition once per fire: the arg
                       names the cut group (`node2|node3` isolates
                       exactly that set from everyone else, both
                       directions); the delay field, when set, heals
                       it that many seconds later. Effects are
                       applied by any live NetChaos engine at its
                       next transport activity.
    deliver.stream     the peer's block-deliver stream
    cluster.pull       onboarding/catch-up block pulls from consenters
    cluster.verify     pulled-span verification (orderer/onboarding.py)
    onboarding.commit  committing a verified pulled block
    commit.validate_ahead  stage A of the commit pipeline — a fault
                       demotes the block to the sequential path
                       (core/commitpipeline.py)
    commit.barrier     the pipeline's drain-before-validate barrier
                       (config blocks, validation-parameter updates)

A new subsystem adds a `check()` call AND declares the point in
`KNOWN_POINTS` below — the canonical registry `tools/ftpu_lint.py`
checks every call-site literal against, and `arm()` warns on unknown
names so a typo'd FTPU_FAULTS entry is loud instead of inert (the
chaos suite would otherwise pass vacuously). Arbitrary names still
ARM (tests of the registry itself use made-up points); they just
warn.

Arming:
  - code:  `faults.arm("tpu.dispatch", mode="error", count=3)`
  - env:   FTPU_FAULTS="tpu.dispatch=error:3;deliver.stream=delay::0.2"
           parsed at import and re-applied by `reset()`, so a chaos CI
           pass (tools/chaos_check.sh) arms a whole pytest run while
           each test still starts from the same armed baseline.

Spec grammar: `point=mode[:count][:delay_s][:arg]`, `mode` in
{error, delay, crash}; empty count = unlimited. A `delay` fault sleeps
then proceeds (a stall, for deadline/breaker testing); an `error`
fault raises FaultInjected; a `crash` fault hard-kills the process
(`os._exit(137)`) at the k-th check, where k is the delay field
(`raft.wal_append=crash:1:3` dies at the 3rd WAL append) — the
crash-point recovery matrix arms these in subprocess children and
asserts bit-identical replay after restart. The optional 4th field
targets an ARGUMENT: the fault fires only when the call site's
`check(point, arg=...)` matches it (the per-device points pass the
full-mesh device index, so `tpu.device_lost=error:1::3` kills exactly
chip 3); a check without an arg never matches an arg-targeted arming.
Everything after the 3rd `:` is the arg verbatim, so endpoint args may
contain colons (`net.drop=error:5::orderer0.example.com:7050`).

Counts are consumed per fire; `fires(point)` reports how often a point
actually fired (armed or not, a check on an unarmed point counts
nothing — firing means the fault acted). Subsystems that implement a
fault's EFFECT themselves (the net.* points: common/netchaos.py turns
them into drops/delays/duplicates/reorders/partitions on its delivery
schedule) read the arming with `arming(point)` and book the fire with
`consume(point, arg=)` instead of `check()` — same count/fires
accounting, no raise, no sleep.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

logger = logging.getLogger("common.faults")

ENV_VAR = "FTPU_FAULTS"


class FaultInjected(RuntimeError):
    """Raised by an armed `error` fault point."""


# The canonical fault-point registry: every `faults.check("...")`
# call-site literal in the tree must appear here (enforced by
# tools/ftpu_lint.py's fault-point rule), and `arm()` warns when an
# unknown name is armed. Keep the docstring table above in sync.
KNOWN_POINTS = frozenset({
    "tpu.dispatch",
    "tpu.compile",
    "tpu.table_persist",
    "tpu.ed25519",
    "tpu.bls_aggregate",
    "tpu.device_lost",
    "tpu.device_straggler",
    "raft.step",
    "raft.wal_append",
    "order.propose",
    "order.block_write",
    "net.drop",
    "net.delay",
    "net.dup",
    "net.reorder",
    "net.partition",
    "deliver.stream",
    "cluster.pull",
    "cluster.verify",
    "onboarding.commit",
    "commit.validate_ahead",
    "commit.barrier",
})


@dataclass
class _Arming:
    mode: str                      # "error" | "delay" | "crash"
    count: Optional[int] = None    # remaining fires; None = unlimited
    delay_s: float = 0.0
    message: str = ""
    arg: Optional[str] = None      # fire only when check(arg=) matches
    skip: int = 0                  # crash mode: checks left before dying

    def snapshot(self) -> dict:
        return {"mode": self.mode, "count": self.count,
                "delay_s": self.delay_s, "arg": self.arg,
                "message": self.message}


class FaultRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._armed: dict[str, _Arming] = {}
        self._fires: dict[str, int] = {}

    # -- arming --

    def arm(self, point: str, mode: str = "error",
            count: Optional[int] = None, delay_s: float = 0.0,
            message: str = "", arg=None) -> None:
        if mode not in ("error", "delay", "crash"):
            raise ValueError(f"unknown fault mode {mode!r}")
        if point not in KNOWN_POINTS:
            logger.warning(
                "arming UNKNOWN fault point %r — no check() site "
                "declares it in KNOWN_POINTS (common/faults.py); a "
                "typo'd %s entry injects nothing", point, ENV_VAR)
        with self._lock:
            self._armed[point] = _Arming(
                mode=mode, count=count, delay_s=delay_s,
                message=message,
                arg=None if arg is None else str(arg),
                # crash mode: the delay field selects WHICH check dies
                # (k-th, 1-based; 0/1 = the first one)
                skip=max(0, int(delay_s) - 1) if mode == "crash"
                else 0)
        logger.info("fault point %s armed: mode=%s count=%s "
                    "delay=%.3fs arg=%s", point, mode, count, delay_s,
                    arg)

    def disarm(self, point: str) -> None:
        with self._lock:
            self._armed.pop(point, None)

    def clear(self) -> None:
        """Disarm everything, including env-armed faults."""
        with self._lock:
            self._armed.clear()
            self._fires.clear()

    def reset(self) -> None:
        """Back to the process baseline: clear, then re-apply the
        FTPU_FAULTS env arming (per-test isolation for chaos runs)."""
        self.clear()
        self.arm_from_env()

    def arm_from_env(self, spec: Optional[str] = None) -> None:
        spec = os.environ.get(ENV_VAR, "") if spec is None else spec
        if not spec:
            return
        for part in spec.replace(",", ";").split(";"):
            part = part.strip()
            if not part:
                continue
            try:
                point, _, rhs = part.partition("=")
                fields = rhs.split(":")
                mode = fields[0] or "error"
                count = (int(fields[1])
                         if len(fields) > 1 and fields[1] else None)
                delay = (float(fields[2])
                         if len(fields) > 2 and fields[2] else 0.0)
                # everything past the 3rd ':' is the arg verbatim —
                # endpoint args ("host:port") may contain colons
                arg = (":".join(fields[3:])
                       if len(fields) > 3 and fields[3] else None)
                self.arm(point.strip(), mode=mode, count=count,
                         delay_s=delay, message=f"env:{ENV_VAR}",
                         arg=arg)
            except (ValueError, IndexError):
                logger.warning("ignoring malformed %s entry %r",
                               ENV_VAR, part)

    # -- observation --

    def fires(self, point: str) -> int:
        with self._lock:
            return self._fires.get(point, 0)

    def armed(self, point: str) -> bool:
        with self._lock:
            return point in self._armed

    def arming(self, point: str) -> Optional[dict]:
        """Read-only snapshot of the current arming at `point` (mode,
        count, delay_s, arg, message), or None. For subsystems that
        interpret a fault's spec themselves (netchaos) — reading never
        consumes a fire."""
        with self._lock:
            a = self._armed.get(point)
            return None if a is None else a.snapshot()

    def consume(self, point: str, arg=None) -> Optional[dict]:
        """Book one fire at `point` WITHOUT acting (no raise, no
        sleep, no exit) and return the arming snapshot, or None when
        nothing armed / the arg doesn't match (same matching rule as
        `check`). The netchaos engine uses this to keep count/fires
        accounting canonical while applying the fault's effect on its
        own delivery schedule."""
        with self._lock:
            a = self._armed.get(point)
            if a is None:
                return None
            if a.arg is not None and (arg is None
                                      or str(arg) != a.arg):
                return None
            snap = a.snapshot()
            if a.count is not None:
                a.count -= 1
                if a.count <= 0:
                    del self._armed[point]
            self._fires[point] = self._fires.get(point, 0) + 1
            return snap

    # -- the hot-path hook --

    def check(self, point: str, arg=None) -> None:
        """Fire the fault armed at `point`, if any. Near-free when
        nothing is armed (the production state). `arg` is the call
        site's targeting argument (the per-device points pass the
        full-mesh device index); an arming with an arg fires ONLY on
        a matching check, and never on an arg-less one."""
        if not self._armed:
            return
        with self._lock:
            a = self._armed.get(point)
            if a is None:
                return
            if a.arg is not None and (arg is None
                                      or str(arg) != a.arg):
                return
            if a.mode == "crash" and a.skip > 0:
                a.skip -= 1    # not a fire: the k-th check dies
                return
            if a.count is not None:
                a.count -= 1
                if a.count <= 0:
                    del self._armed[point]
            self._fires[point] = self._fires.get(point, 0) + 1
            mode, delay_s, msg = a.mode, a.delay_s, a.message
            if a.arg is not None:
                msg = f"{msg};arg={a.arg}" if msg else f"arg={a.arg}"
        # act OUTSIDE the lock: a delay fault must not serialize every
        # other fault point behind its sleep
        if mode == "crash":
            # the crash-matrix kill: no cleanup, no atexit — the point
            # is to die exactly like a power loss at this seam
            logger.critical("injected CRASH at %s%s", point,
                            f" ({msg})" if msg else "")
            os._exit(137)
        if mode == "delay":
            # the sanitizer treats an injected stall like a device
            # dispatch: holding any tracked lock across it is a finding
            from fabric_tpu.common import lockcheck
            lockcheck.note_blocking(f"fault-delay:{point}")
            time.sleep(delay_s)
            return
        raise FaultInjected(
            f"injected fault at {point}" + (f" ({msg})" if msg else ""))


_registry = FaultRegistry()

# module-level convenience API (the registry is process-wide state,
# like the bccsp factory singleton)
arm = _registry.arm
disarm = _registry.disarm
clear = _registry.clear
reset = _registry.reset
arm_from_env = _registry.arm_from_env
fires = _registry.fires
armed = _registry.armed
arming = _registry.arming
consume = _registry.consume
check = _registry.check

# chaos runs arm the whole process via env before interpreter start
_registry.arm_from_env()
