"""Circuit breaker for liveness-critical accelerator dispatch.

`BCCSP.Default: TPU` promises bit-identical accept/reject with the sw
provider as the ONLY observable difference being speed — which means a
flaky, stalled, or absent accelerator must degrade to the host path,
never wedge the peer/orderer or change verdicts. FPGA verify engines
ship a CPU fallback for the same reason (arXiv:2112.02229); committee-
consensus deployments treat verification as liveness-critical
(arXiv:2302.00418).

States (the strings surfaced on /healthz and the breaker_state gauge):

    device    (closed)    dispatches go to the accelerator
    degraded  (open)      every dispatch is refused; callers serve the
                          bit-identical sw path; entered after
                          `trip_threshold` consecutive device failures
    probing   (half-open) cooldown elapsed: ONE probe dispatch is
                          admitted; success re-admits the device,
                          failure re-opens for another cooldown

A `deadline_ms` guard runs the dispatch on a watchdog thread: a stalled
device (wedged PCIe link, a compile that never returns) counts as a
failure after the deadline instead of blocking validation forever. The
abandoned call keeps running on its daemon thread and its eventual
result is discarded. One thread is spawned per guarded dispatch —
dispatches are BLOCK-granular (tens per second, not per-signature), so
the churn is noise next to the dispatch itself, and deadline_ms=0 (the
default) spawns none; revisit with a worker pool only if profiles ever
say otherwise.

Error classification: any Exception counts as a device failure except
types listed in `BreakerConfig.ignore` (caller bugs — e.g. TypeError
from malformed arguments — should surface, not trip the breaker).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field

logger = logging.getLogger("common.breaker")

DEVICE, DEGRADED, PROBING = "device", "degraded", "probing"

_STATE_CODES = {DEVICE: 0, PROBING: 1, DEGRADED: 2}


class CircuitOpen(RuntimeError):
    """Dispatch refused: the breaker is open (or the probe slot is
    taken). The caller serves its host fallback."""


class DeadlineExceeded(RuntimeError):
    """The guarded call outlived `deadline_ms`."""


@dataclass
class BreakerConfig:
    """`BCCSP.TPU.Fallback` in core.yaml (parsed by bccsp/factory.py)."""
    deadline_ms: float = 0.0      # 0 = no watchdog
    trip_threshold: int = 5       # consecutive failures before opening
    cooldown_s: float = 30.0      # open -> probing after this long
    probe_batch: int = 1024       # max lanes risked on a probe dispatch
    ignore: tuple = field(default_factory=tuple)  # exception types that
    #                                               never count


class CircuitBreaker:
    def __init__(self, config: BreakerConfig | None = None,
                 name: str = "tpu", clock=time.monotonic):
        self.config = config or BreakerConfig()
        self.name = name
        self._clock = clock
        self._lock = threading.Lock()
        self._state = DEVICE
        self._failures = 0           # consecutive
        self._open_until = 0.0
        self._probe_inflight = False
        self._probe_started = 0.0
        self._guards_inflight = 0    # guarded executions running now
        self.stats = {"trips": 0, "probes": 0, "deadline_timeouts": 0,
                      "failures": 0, "rejected": 0, "stale_probes": 0}

    # -- state --

    @property
    def state(self) -> str:
        """Current state; resolves cooldown expiry (degraded →
        probing) at observation time."""
        with self._lock:
            return self._state_locked()

    def _probe_timeout_s(self) -> float:
        return max(self.config.cooldown_s,
                   2 * self.config.deadline_ms / 1000.0, 1.0)

    def _state_locked(self) -> str:
        now = self._clock()
        if self._state == DEGRADED and now >= self._open_until:
            self._state = PROBING
            self._probe_inflight = False
            logger.info("%s breaker cooldown elapsed; probing the "
                        "device", self.name)
        elif self._state == PROBING and self._probe_inflight and \
                self._guards_inflight == 0 and \
                now - self._probe_started >= self._probe_timeout_s():
            # the probe's outcome was never reported (a caller dropped
            # its resolver): reclaim the slot by treating it as a
            # failed probe, otherwise the breaker wedges in `probing`
            # with the device benched forever. A probe still EXECUTING
            # inside guard() — e.g. paying a long first-dispatch
            # compile with no deadline configured — is not stale and
            # keeps the slot.
            self.stats["stale_probes"] += 1
            self._state = DEGRADED
            self._open_until = now + self.config.cooldown_s
            self._probe_inflight = False
            logger.warning(
                "%s breaker: probe outcome never reported after "
                "%.1fs; re-opening for %.1fs", self.name,
                self._probe_timeout_s(), self.config.cooldown_s)
        return self._state

    @property
    def state_code(self) -> int:
        return _STATE_CODES[self.state]

    # -- accounting --

    def admit(self) -> bool:
        """Raise CircuitOpen unless a dispatch may be tried now.
        Returns True when this dispatch IS the probe (the single
        half-open slot was acquired — released by the following
        success()/failure()), False for a normal closed-state
        dispatch. The probe decision is made HERE, atomically with the
        state resolution, so callers can bound the probe's size
        without racing the cooldown clock."""
        with self._lock:
            st = self._state_locked()
            if st == DEVICE:
                return False
            if st == PROBING and not self._probe_inflight:
                self._probe_inflight = True
                self._probe_started = self._clock()
                self.stats["probes"] += 1
                return True
            self.stats["rejected"] += 1
        raise CircuitOpen(f"{self.name} breaker {st}")

    def success(self) -> None:
        with self._lock:
            st = self._state_locked()
            if st == DEGRADED:
                # a stale in-flight dispatch (admitted before the
                # trip) resolving now must not force-close an OPEN
                # breaker — re-entry goes through cooldown + a bounded
                # probe, not through a straggler's luck
                return
            if st != DEVICE:
                logger.info("%s breaker: probe succeeded; device "
                            "re-admitted", self.name)
            self._state = DEVICE
            self._failures = 0
            self._probe_inflight = False

    def failure(self, exc: BaseException | None = None) -> None:
        if exc is not None and isinstance(exc, self.config.ignore):
            with self._lock:
                # the error doesn't count against the device, but a
                # held probe slot must not leak
                self._probe_inflight = False
            return
        tripped = 0
        with self._lock:
            self.stats["failures"] += 1
            st = self._state_locked()
            self._failures += 1
            if st == PROBING or \
                    self._failures >= self.config.trip_threshold:
                if st != DEGRADED:
                    self.stats["trips"] += 1
                    tripped = self._failures
                    logger.warning(
                        "%s breaker OPEN after %d consecutive device "
                        "failure(s) (%s); serving the sw path for "
                        "%.1fs", self.name, self._failures,
                        type(exc).__name__ if exc else "failure",
                        self.config.cooldown_s)
                self._state = DEGRADED
                self._open_until = (self._clock()
                                    + self.config.cooldown_s)
                self._probe_inflight = False
        if tripped:
            # flight-recorder landmark + automatic postmortem dump
            # (rate-limited, never raises) — OUTSIDE the breaker lock:
            # the dump does file I/O
            from fabric_tpu.common import tracing
            tracing.note_breaker_trip(self.name, failures=tripped)

    # -- guarded execution --

    @contextlib.contextmanager
    def execution(self):
        """Mark a device execution as live WITHOUT recording an
        outcome — for work done between admit() and a later guarded
        resolve (the prepared path's staging/compile window), so the
        stale-probe reclaim doesn't preempt it. A probe whose resolver
        is merely HELD (not executing) past the probe timeout is still
        treated as dropped; a late success()/failure() then
        self-corrects the state."""
        with self._lock:
            self._guards_inflight += 1
        try:
            yield
        finally:
            with self._lock:
                self._guards_inflight -= 1

    def guard(self, fn):
        """Run `fn()` under the deadline watchdog and record the
        outcome. No admission check — see run()."""
        deadline_s = self.config.deadline_ms / 1000.0
        # while a guarded execution runs, the probe slot is live (not
        # stale-reclaimable): a slow probe paying a first-dispatch
        # compile with no deadline configured must not be preempted
        with self._lock:
            self._guards_inflight += 1
        try:
            try:
                if deadline_s > 0:
                    from fabric_tpu.common import tracing
                    box: dict = {}
                    done = threading.Event()
                    tctx = tracing.capture()

                    def work():
                        try:
                            # the dispatch's spans stay under the
                            # caller's
                            with tracing.attached(tctx):
                                box["result"] = fn()
                        except BaseException as e:  # noqa: BLE001
                            box["error"] = e
                        finally:
                            done.set()

                    t = threading.Thread(
                        target=work, daemon=True,
                        name=f"{self.name}-breaker-dispatch")
                    t.start()
                    if not done.wait(deadline_s):
                        self.stats["deadline_timeouts"] += 1
                        exc = DeadlineExceeded(
                            f"{self.name} dispatch exceeded "
                            f"{self.config.deadline_ms:.0f}ms deadline")
                        self.failure(exc)
                        raise exc
                    if "error" in box:
                        raise box["error"]
                    result = box["result"]
                else:
                    result = fn()
            except DeadlineExceeded:
                raise
            except Exception as e:
                self.failure(e)
                raise
            self.success()
            return result
        finally:
            with self._lock:
                self._guards_inflight -= 1

    def run(self, fn):
        """Admission + guarded execution: raises CircuitOpen when the
        device must not be tried, otherwise runs fn() under the
        deadline and records the outcome."""
        self.admit()
        return self.guard(fn)
