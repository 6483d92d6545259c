"""Transaction-lifecycle tracing: correlated spans + a flight recorder.

Round 14. Every stage of the serving pipeline is batched, overlapped
and breaker-guarded (rounds 6-13), but the only timing evidence the
tree emitted was last-batch gauge snapshots and per-stage bench means:
no per-transaction causality across the five overlapped stages, no
tail distributions (a p99 convoy wait hides completely behind a mean),
and nothing at all to read after a run dies rc=124 or a chip gets
quarantined. The measurement-first papers in PAPERS.md
(arXiv:2302.00418, arXiv:2112.02229) find their wins by attributing
per-stage latency on the critical path; this module is that
instrument, in three pieces:

**Trace context** — `trace_id`/`span_id` carried down the calling
thread ambiently (the `overload.Deadline` pattern: nested stages
inherit correlation without threading parameters through every
signature), crossing thread handoffs explicitly via `capture()` at the
enqueue site and `attached(ctx)` / `span(parent=ctx)` at the worker.
A fresh trace opens per contiguous ingress run (the batch IS the
pipeline's unit of work; a single-envelope submitter gets its own
trace) and keeps one trace_id through order window -> propose ->
consensus -> block write -> validate -> commit.

**Spans** — `with span("stage.name", **attrs): ...` around every
pipeline seam (or the `@traced("stage.name")` decorator for whole-
function spans; `tools/ftpu_lint.py`'s span-coverage rule drives the
REQUIRED_SPANS registry to full coverage). A span records a monotonic
perf_counter pair plus its context; attrs are stored RAW and
formatted only at export, and error status is stamped from a
propagating exception — on `@hot_path` code the per-span cost is two
clock reads, one ring slot and one histogram observation. Every span
feeds a per-stage latency reservoir (`stage_quantiles()`: the bench's
p50/p99 stage fields) and, when a metrics provider is bound, the
canonical `trace_stage_seconds` histogram on `/metrics`. Where a
stretch also feeds a gauge, a histogram or a log line, `timed(name)`
is the span that hands its own clock readings back (`.t0`, `.t1`,
`.seconds`), so one pair of reads per boundary serves them all. Once
JAX is imported in the process (this module never imports it) every
span is also a `jax.profiler.TraceAnnotation`: a profiler capture
shows the span tree on a host line beside the device operations.
A generation-2 collection is a `runtime.gc` span (a `gc.callbacks`
hook, installed while tracing is enabled).

**Flight recorder** — a preallocated, lock-light, drop-oldest ring of
the most recent spans/events that is ALWAYS ON (`FTPU_TRACE=0` or
`Operations.Tracing.Enabled: false` opts out; disabled mode costs one
attribute read and allocates nothing). Exported as Chrome-trace-event
JSON (perfetto / chrome://tracing loadable, tid = pipeline stage) via
the `/debug/trace` operations endpoint (`snapshot()` is the in-process
read API, `dropped()` how many events the ring has overwritten), and
dumped to a file
automatically on breaker trips, device quarantines and shed bursts
(rate-limited) — the postmortem for the rc=124 class, where the only
prior evidence was an empty stdout tail.

Knobs: `Operations.Tracing.{Enabled,RingSize,SampleEvery,DumpDir}`
(node config) or env `FTPU_TRACE`, `FTPU_TRACE_RING`,
`FTPU_TRACE_SAMPLE`, `FTPU_TRACE_DUMP_DIR`, `FTPU_TRACE_DUMP_MIN_S`,
`FTPU_TRACE_SHED_BURST`. SampleEvery=N records every Nth span in the
ring (error spans and instant events always record; histograms always
observe) for hosts where even ring writes are too much.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import itertools
import json
import logging
import os
import re
import sys
import tempfile
import threading
import time
from typing import Optional

logger = logging.getLogger("common.tracing")

# export epoch: Chrome-trace `ts` is microseconds relative to this
_PC0 = time.perf_counter()

SHED_BURST_WINDOW_S = 10.0

_STAGE_RESERVOIR = 512   # per-stage duration reservoir (recent window)


def _env_int(name: str, default: int) -> int:
    try:
        v = int(os.environ.get(name, ""))
    except ValueError:
        return default
    return v if v > 0 else default


def _env_float(name: str, default: float) -> float:
    try:
        v = float(os.environ.get(name, ""))
    except ValueError:
        return default
    return v if v > 0 else default


class TraceContext:
    """One point in a trace: the correlation id shared by every span
    of a transaction's lifecycle (`trace_id`) and this span's own id.
    Immutable; cheap enough to stash in queue tuples at every thread
    handoff."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id}/{self.span_id})"


# ids: a per-process random prefix + counter — unique, collision-free
# across processes, and far cheaper than urandom per span
_ID_PREFIX = os.urandom(4).hex()
_id_seq = itertools.count(1)
_span_seq = itertools.count()    # sampling counter
_dump_seq = itertools.count(1)


def _next_id() -> str:
    return f"{_ID_PREFIX}{next(_id_seq):08x}"


class _State:
    """Module-wide mutable configuration + the recorder itself."""

    def __init__(self):
        self.enabled = os.environ.get("FTPU_TRACE", "1") != "0"
        self.sample_every = _env_int("FTPU_TRACE_SAMPLE", 1)
        self.ring: list = [None] * _env_int("FTPU_TRACE_RING", 4096)
        self.ring_idx = 0
        self.ring_lock = threading.Lock()
        self.stages: dict = {}           # stage -> _StageLat
        self.stage_lock = threading.Lock()
        self.hist = None                 # bound trace_stage_seconds
        self.annotation = None           # jax.profiler.TraceAnnotation
        self.gc_t0: Optional[float] = None
        self.dump_dir = os.environ.get("FTPU_TRACE_DUMP_DIR") or None
        self.dump_min_interval_s = _env_float("FTPU_TRACE_DUMP_MIN_S",
                                              10.0)
        self.last_dump_t: Optional[float] = None
        self.dump_lock = threading.Lock()
        self.shed_burst_n = _env_int("FTPU_TRACE_SHED_BURST", 32)
        self.shed_window_t0 = 0.0
        self.shed_window_n = 0
        self.shed_lock = threading.Lock()


_state = _State()
_tls = threading.local()

# node attribution (round 18): which LOGICAL node recorded an event.
# One process is normally one node (`FTPU_NODE_ID` / set_default_node
# at assembly), but the in-process multi-node rigs bind a node id per
# WORKER THREAD (cluster/gossip drain loops, the raft chain loop,
# commit-pipeline workers) so one shared ring still renders
# `node/stage` tracks per logical node.
_default_node: Optional[str] = os.environ.get("FTPU_NODE_ID") or None


def set_default_node(node: Optional[str]) -> None:
    """Process-level node identity (config/env; None clears)."""
    global _default_node
    _default_node = node or None


def set_node(node: Optional[str]) -> None:
    """Bind the CALLING THREAD to a logical node id (None unbinds —
    events fall back to the process default). Worker threads of the
    in-process multi-node rigs call this once at loop start."""
    _tls.node = node or None


def current_node() -> Optional[str]:
    n = getattr(_tls, "node", None)
    return n if n is not None else _default_node


def bound_node() -> Optional[str]:
    """The raw THREAD binding (no default fallback) — what a scoped
    rebind must save/restore."""
    return getattr(_tls, "node", None)


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def enabled() -> bool:
    return _state.enabled


def set_enabled(flag: bool) -> None:
    """Flip recording at runtime (the bench's overhead A/B uses this;
    nodes configure once at startup). Disabled mode is the
    zero-allocation fast path: span() returns a shared no-op."""
    _state.enabled = bool(flag)
    _sync_gc_hook()


def configure(enabled: Optional[bool] = None,
              ring_size: Optional[int] = None,
              sample_every: Optional[int] = None,
              dump_dir: Optional[str] = None,
              dump_min_interval_s: Optional[float] = None,
              shed_burst: Optional[int] = None) -> None:
    if enabled is not None:
        set_enabled(enabled)
    if ring_size is not None and ring_size > 0:
        with _state.ring_lock:
            _state.ring = [None] * int(ring_size)
            _state.ring_idx = 0
    if sample_every is not None and sample_every > 0:
        _state.sample_every = int(sample_every)
    if dump_dir is not None:
        _state.dump_dir = dump_dir or None
    if dump_min_interval_s is not None:
        _state.dump_min_interval_s = float(dump_min_interval_s)
    if shed_burst is not None and shed_burst > 0:
        _state.shed_burst_n = int(shed_burst)


def configure_from_config(cfg, metrics_provider=None) -> None:
    """Node-assembly entry: read `Operations.Tracing.*` (the
    viperutil Config both node assemblies carry; key lookup is
    case-insensitive so the peer's lowercase spelling works too) and
    optionally bind the metrics provider so span durations land in
    the canonical `trace_stage_seconds` histogram on /metrics."""
    try:
        ring = int(cfg.get("Operations.Tracing.RingSize", 0) or 0)
    except (TypeError, ValueError):
        ring = 0
    try:
        sample = int(cfg.get("Operations.Tracing.SampleEvery", 0) or 0)
    except (TypeError, ValueError):
        sample = 0
    # only flip `enabled` when the config actually SAYS something:
    # with the key absent, the env-derived state (FTPU_TRACE=0 is the
    # documented operator opt-out) must survive node startup
    en = None
    if cfg.get("Operations.Tracing.Enabled") is not None:
        en = cfg.get_bool("Operations.Tracing.Enabled", True)
    configure(
        enabled=en,
        ring_size=ring or None,
        sample_every=sample or None,
        dump_dir=cfg.get("Operations.Tracing.DumpDir"))
    # node identity for cross-node trace attribution (round 18):
    # config key only when PRESENT — the FTPU_NODE_ID env (or an
    # assembly's explicit set_default_node) survives otherwise
    node = cfg.get("Operations.Tracing.NodeID")
    if node:
        set_default_node(str(node))
    if metrics_provider is not None:
        bind_metrics(metrics_provider)


def bind_metrics(provider) -> None:
    """Attach a metrics provider: every span/stage observation also
    feeds the stage-labeled `trace_stage_seconds` histogram, so
    /metrics carries p50/p99-derivable distributions for each
    pipeline stage beside the existing last-batch gauges."""
    from fabric_tpu.common import metrics as metrics_mod
    try:
        _state.hist = provider.new_histogram(
            metrics_mod.TRACE_STAGE_SECONDS_OPTS)
    except Exception:
        logger.warning("trace_stage_seconds histogram unavailable",
                       exc_info=True)
    # round 18: the cross-node layer's e2e_commit_seconds/hop_seconds
    # histograms bind off the same provider (lazy import — the
    # cluster-trace module imports this one)
    try:
        from fabric_tpu.common import clustertrace
        clustertrace.bind_metrics(provider)
    except Exception:
        logger.warning("cluster-trace histograms unavailable",
                       exc_info=True)


def reset(enabled: Optional[bool] = None) -> None:
    """Test isolation: drop every recorded event and stage reading
    (ids keep counting — resets must not make them collide)."""
    with _state.ring_lock:
        _state.ring = [None] * len(_state.ring)
        _state.ring_idx = 0
    with _state.stage_lock:
        _state.stages.clear()
    with _state.shed_lock:
        _state.shed_window_t0 = 0.0
        _state.shed_window_n = 0
    with _state.dump_lock:
        _state.last_dump_t = None
    if enabled is not None:
        set_enabled(enabled)


# ---------------------------------------------------------------------------
# context propagation (the Deadline pattern, for correlation)
# ---------------------------------------------------------------------------

def new_context() -> TraceContext:
    """A fresh root context — assigned once per transaction at the
    ingress edge, then carried (explicitly across queues, ambiently
    within a thread) for the rest of its lifecycle."""
    return TraceContext(_next_id(), _next_id())


def capture() -> Optional[TraceContext]:
    """The calling thread's ambient context (None outside any span) —
    stash this in the queue tuple at a thread handoff."""
    return getattr(_tls, "ctx", None)


class _Attached:
    __slots__ = ("_ctx", "_prior")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx
        self._prior = None

    def __enter__(self) -> Optional[TraceContext]:
        self._prior = getattr(_tls, "ctx", None)
        if self._ctx is not None:
            _tls.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc) -> None:
        _tls.ctx = self._prior


def attached(ctx: Optional[TraceContext]) -> _Attached:
    """Install a captured context as the thread's ambient one for a
    block (None = no-op passthrough): the worker half of a queue
    handoff, so spans it opens correlate to the producer's trace."""
    return _Attached(ctx)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NoopSpan:
    """Disabled-mode span: a shared singleton — no allocation, no
    clock reads, no state."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


def _trace_annotation():
    """`jax.profiler.TraceAnnotation` once JAX is imported in this
    process, else None. This module never imports JAX (an orderer
    without it pays one dict lookup a span); with no profiler session
    active an annotation is one atomic check in C++."""
    ta = _state.annotation
    if ta is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            ta = _state.annotation = jax.profiler.TraceAnnotation
        except AttributeError:      # JAX is still being imported
            return None
    return ta


class _Span:
    __slots__ = ("name", "attrs", "_parent", "ctx", "_prior", "t0",
                 "t1", "_ann")

    def __init__(self, name: str, parent: Optional[TraceContext],
                 attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs
        self._parent = parent

    def set(self, **attrs) -> None:
        """Counts known only once the work is done (raw, like the
        attrs given at the opening)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> TraceContext:
        parent = self._parent
        if parent is None:
            parent = getattr(_tls, "ctx", None)
            self._parent = parent
        if parent is not None:
            ctx = TraceContext(parent.trace_id, _next_id())
        else:
            ctx = TraceContext(_next_id(), _next_id())
        self.ctx = ctx
        self._prior = getattr(_tls, "ctx", None)
        _tls.ctx = ctx
        # the same span on the profiler's timeline, beside the device
        # operations, whenever a capture is running
        ta = _trace_annotation()
        self._ann = ann = ta(self.name) if ta is not None else None
        if ann is not None:
            ann.__enter__()
        self.t0 = time.perf_counter()
        return ctx

    def __exit__(self, et, ev, tb) -> bool:
        self.t1 = t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        _tls.ctx = self._prior
        err = None
        if et is not None:
            # error status stamped from the propagating exception;
            # str(ev) is the ONE formatting cost and only on failures
            err = f"{et.__name__}: {ev}" if ev is not None \
                else et.__name__
        dur = t1 - self.t0
        _observe(self.name, dur)
        # sampled ring admission — error spans always record (they are
        # exactly what a postmortem reader is looking for)
        if err is not None or \
                next(_span_seq) % _state.sample_every == 0:
            parent = self._parent
            _record(("X", self.name, self.ctx.trace_id,
                     self.ctx.span_id,
                     parent.span_id if parent is not None else None,
                     self.t0, dur,
                     threading.current_thread().name,
                     self.attrs or None, err))
        return False


class _Clock:
    """Disabled-mode `timed()`: the two clock reads its caller's
    gauge, histogram or log line needs, and nothing of a span."""

    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        return False

    def set(self, **attrs) -> None:
        pass

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str, parent: Optional[TraceContext] = None, **attrs):
    """Open one lifecycle span: `with span("order.propose", n=3):`.
    Inherits the ambient context (or `parent`) for correlation,
    records a perf_counter pair + the attrs (raw — formatted only at
    export; `.set(k=v)` adds counts known only afterwards), stamps
    error status from a propagating exception, and feeds the stage
    latency reservoir/histogram. Returns a shared no-op when tracing
    is disabled."""
    if not _state.enabled:
        return _NOOP
    return _Span(name, parent, attrs or None)


def timed(name: str, **attrs):
    """A span that hands its own clock readings back (`.t0`, `.t1`,
    `.seconds`, valid once it has closed): for a stretch whose
    duration also feeds a gauge, a histogram or a log line, so that
    one pair of reads per boundary serves them all. Those readers
    exist with tracing disabled too, so this reads the clock either
    way; disabled, it records nothing and builds no span.

        t = tracing.timed("ledger.history")
        with t:
            ...
        hist.observe(t.seconds)
    """
    if not _state.enabled:
        return _Clock()
    return _Span(name, None, attrs or None)


def _thread_io() -> Optional[tuple[int, int, int]]:
    """(syscr, syscw, wchar) of the calling thread: the read and write
    system calls it has made and the bytes it has handed to the
    writes. None where the kernel keeps no such account. One `read`
    call, counted on the thread (`_tls.io_reads`) so that `thread_io`
    can take the tracer's own reads out of a span's `syscr`: the
    kernel books a read after it has filled the buffer, so each
    reading shows every read before it but its own."""
    try:
        fd = os.open("/proc/thread-self/io", os.O_RDONLY)
    except OSError:
        return None
    try:
        _tls.io_reads = getattr(_tls, "io_reads", 0) + 1
        f = os.read(fd, 512).split()
        fields = dict(zip(f[::2], f[1::2]))
        return (int(fields[b"syscr:"]), int(fields[b"syscw:"]),
                int(fields[b"wchar:"]))
    except (OSError, KeyError, ValueError):
        return None
    finally:
        os.close(fd)


@contextlib.contextmanager
def thread_io(sp, book=None):
    """Book on span `sp`, as `syscr` / `syscw`, the read and write
    system calls the calling thread makes inside the block:

        commit = tracing.span("commit.commit", block=num)
        with commit, tracing.thread_io(commit):
            ...

    What a stretch asks of the file system, where the time it takes
    there depends on the host (a network file system under sqlite).
    `syscr` leaves out the reads of `/proc` that this span and every
    `thread_io` nested in it made. `book(sp, syscr, syscw, wchar)`, if
    given, books the deltas instead (`wchar`: the bytes handed to the
    writes). Nothing is read with tracing disabled."""
    reads0 = getattr(_tls, "io_reads", 0)
    io0 = _thread_io() if _state.enabled else None
    try:
        yield
    finally:
        own = getattr(_tls, "io_reads", 0) - reads0
        io1 = _thread_io() if io0 is not None else None
        if io1 is not None:
            d = (io1[0] - io0[0] - own, io1[1] - io0[1], io1[2] - io0[2])
            if book is None:
                sp.set(syscr=d[0], syscw=d[1])
            else:
                book(sp, *d)


def traced(name: str):
    """Whole-function span decorator — the zero-churn spelling for
    the registered dispatch spans (REQUIRED_SPANS in
    tools/ftpu_lint.py): `@traced("tpu.shard_put")` above the def."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _state.enabled:
                return fn(*args, **kwargs)
            with _Span(name, None, None):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def child_context(parent: Optional[TraceContext] = None
                  ) -> Optional[TraceContext]:
    """A context allocated ahead of its span, for a stage that spans
    thread handoffs (the commit pipeline's block: submitted on one
    thread, validated and committed on two others): hand it to the
    workers as their spans' `parent`, then record the stage itself
    with `observe_span(..., parent=parent, ctx=<this>)` once it is
    over. None when tracing is disabled."""
    if not _state.enabled:
        return None
    return _child_of(parent if parent is not None else capture())


def _child_of(parent: Optional[TraceContext]) -> TraceContext:
    return TraceContext(parent.trace_id if parent is not None
                        else _next_id(), _next_id())


def observe_span(name: str, t0: float, t1: float,
                 parent: Optional[TraceContext] = None,
                 ctx: Optional[TraceContext] = None,
                 **attrs) -> Optional[TraceContext]:
    """Record an already-measured interval as a complete span (for
    stages whose timing is computed inline — the admission window's
    convoy wait, raft propose->commit consensus latency). `t0`/`t1`
    are perf_counter readings. `ctx` is the span's own context where
    `child_context` allocated it ahead. Returns the span's context."""
    if not _state.enabled:
        return None
    if ctx is None:
        if parent is None:
            parent = capture()
        ctx = _child_of(parent)
    dur = max(0.0, t1 - t0)
    _observe(name, dur)
    # same ring-admission sampling as span() exit — SampleEvery must
    # thin the inline-measured spans too, or the one span class it
    # cannot touch ends up owning the ring
    if next(_span_seq) % _state.sample_every == 0:
        _record(("X", name, ctx.trace_id, ctx.span_id,
                 parent.span_id if parent is not None else None,
                 t0, dur, threading.current_thread().name,
                 attrs or None, None))
    return ctx


def instant(name: str, **attrs) -> None:
    """An instant event in the recorder (breaker trip, quarantine,
    readmit, mesh rebuild, shed): zero duration, always recorded
    (never sampled out) — these are the landmarks a postmortem is
    read by."""
    if not _state.enabled:
        return
    ctx = capture()
    _record(("i", name,
             ctx.trace_id if ctx is not None else None, _next_id(),
             ctx.span_id if ctx is not None else None,
             time.perf_counter(), 0.0,
             threading.current_thread().name, attrs or None, None))


def observe_stage(stage: str, seconds: float) -> None:
    """Feed one duration into a stage's latency distribution without
    a ring event (per-device transfer/ready readings, convoy waits
    measured inline)."""
    if not _state.enabled:
        return
    _observe(stage, seconds)


# ---------------------------------------------------------------------------
# the collector: a full pass stops every thread, so it is a span
# ---------------------------------------------------------------------------

def _gc_hook(phase: str, info: dict) -> None:
    """`gc.callbacks` entry: one `runtime.gc` span per generation-2
    collection, under whatever span the collecting thread was in.
    Generations 0 and 1 run thousands of times a block and are not
    recorded."""
    if info["generation"] != 2:
        return
    if phase == "start":
        _state.gc_t0 = time.perf_counter()
    elif _state.gc_t0 is not None:
        t0, _state.gc_t0 = _state.gc_t0, None
        observe_span("runtime.gc", t0, time.perf_counter(),
                     collected=info["collected"])


def _sync_gc_hook() -> None:
    """The hook is installed exactly while tracing is enabled."""
    installed = _gc_hook in gc.callbacks
    if _state.enabled and not installed:
        gc.callbacks.append(_gc_hook)
    elif not _state.enabled and installed:
        gc.callbacks.remove(_gc_hook)


_sync_gc_hook()


# ---------------------------------------------------------------------------
# the ring + stage reservoirs
# ---------------------------------------------------------------------------

def _record(ev: tuple) -> None:
    # the 11th field is the recording thread's logical node (round 18)
    node = getattr(_tls, "node", None)
    if node is None:
        node = _default_node
    st = _state
    with st.ring_lock:
        ring = st.ring
        i = st.ring_idx
        st.ring_idx = i + 1
        ring[i % len(ring)] = ev + (node,)


class _StageLat:
    __slots__ = ("ring", "idx", "count", "sum", "hist", "child")

    def __init__(self):
        self.ring = [0.0] * _STAGE_RESERVOIR
        self.idx = 0
        self.count = 0
        self.sum = 0.0
        # the stage-labeled histogram child, cached per stage: the
        # with_labels allocation + label-key formatting must not run
        # once per span on the hot dispatch path
        self.hist = None        # the provider histogram it came from
        self.child = None


def _observe(stage: str, dur: float) -> None:
    st = _state
    hist = st.hist
    with st.stage_lock:
        sl = st.stages.get(stage)
        if sl is None:
            sl = st.stages[stage] = _StageLat()
        sl.ring[sl.idx % _STAGE_RESERVOIR] = dur
        sl.idx += 1
        sl.count += 1
        sl.sum += dur
        if hist is not None and sl.hist is not hist:
            # (re)bound provider: build this stage's child once
            try:
                sl.child = hist.with_labels("stage", stage)
                sl.hist = hist
            except Exception:
                logger.warning("trace_stage_seconds child bind "
                               "failed", exc_info=True)
                sl.child = None
                sl.hist = hist
        child = sl.child if hist is not None else None
    if child is not None:
        try:
            child.observe(dur)
        except Exception:
            logger.warning("trace_stage_seconds observe failed",
                           exc_info=True)
            st.hist = None     # never pay a failing path per span


def stage_quantiles() -> dict:
    """{stage: {"count", "mean_s", "p50_s", "p99_s"}} — mean/p50/p99
    all describe the SAME window, the stage's recent-duration
    reservoir (the last _STAGE_RESERVOIR observations); `count` alone
    is the all-time observation total. The bench's
    `*_p50_s`/`*_p99_s` stage-line fields read this; /metrics readers
    derive all-time distributions from the `trace_stage_seconds`
    histogram instead."""
    with _state.stage_lock:
        items = [(name, list(sl.ring[:min(sl.idx, _STAGE_RESERVOIR)]),
                  sl.count)
                 for name, sl in _state.stages.items()]
    out = {}
    for name, data, count in items:
        if not data:
            continue
        data.sort()
        out[name] = {
            "count": count,
            "mean_s": sum(data) / len(data),
            "p50_s": data[int(0.50 * (len(data) - 1))],
            "p99_s": data[int(0.99 * (len(data) - 1))],
        }
    return out


def stage_quantile(stage: str, which: str,
                   ndigits: Optional[int] = None) -> Optional[float]:
    """One reading (`which` in count/mean_s/p50_s/p99_s), optionally
    rounded, or None if the stage never observed."""
    q = stage_quantiles().get(stage)
    v = None if q is None else q.get(which)
    if v is None or ndigits is None:
        return v
    return round(v, ndigits)


# ---------------------------------------------------------------------------
# degradation landmarks (called from breaker / devicehealth / overload)
# ---------------------------------------------------------------------------

def note_breaker_trip(name: str, failures: int = 0) -> None:
    """A circuit breaker opened: instant event + automatic flight-
    recorder dump (the run's last N events are exactly the evidence
    for WHY the device path died). Never raises."""
    if not _state.enabled:
        return
    try:
        instant("breaker.trip", breaker=name, failures=failures)
        auto_dump("breaker_trip")
    except Exception:
        logger.warning("breaker-trip trace hook failed", exc_info=True)


def note_quarantine(device: int) -> None:
    if not _state.enabled:
        return
    try:
        instant("device.quarantine", device=device)
        auto_dump("device_quarantine")
    except Exception:
        logger.warning("quarantine trace hook failed", exc_info=True)


def note_readmit(device: int) -> None:
    if not _state.enabled:
        return
    try:
        instant("device.readmit", device=device)
    except Exception:
        logger.warning("readmit trace hook failed", exc_info=True)


def note_shed(stage: str) -> None:
    """One shed at an overload edge: instant event, plus a burst
    detector — `shed_burst_n` sheds inside SHED_BURST_WINDOW_S dumps
    the recorder once (rate-limited), capturing what the pipeline was
    doing while it drowned."""
    if not _state.enabled:
        return
    try:
        instant("overload.shed", stage=stage)
        now = time.monotonic()
        burst = False
        with _state.shed_lock:
            if now - _state.shed_window_t0 > SHED_BURST_WINDOW_S:
                _state.shed_window_t0 = now
                _state.shed_window_n = 0
            _state.shed_window_n += 1
            burst = _state.shed_window_n == _state.shed_burst_n
        if burst:
            auto_dump("shed_burst")
    except Exception:
        logger.warning("shed trace hook failed", exc_info=True)


# ---------------------------------------------------------------------------
# export: Chrome trace events + dump files
# ---------------------------------------------------------------------------

def snapshot() -> list:
    """The recorder's events, oldest first (raw tuples)."""
    with _state.ring_lock:
        ring = list(_state.ring)
        idx = _state.ring_idx
    n = len(ring)
    if idx <= n:
        events = ring[:idx]
    else:
        cut = idx % n
        events = ring[cut:] + ring[:cut]
    return [e for e in events if e is not None]


def dropped() -> int:
    """How many events the ring has overwritten since it was last
    sized or reset: a reader of `snapshot()` refuses a window that
    starts before the oldest event still held."""
    with _state.ring_lock:
        return max(0, _state.ring_idx - len(_state.ring))


def trace_stages(trace_id: str) -> list:
    """The distinct span/event names recorded under one trace_id,
    sorted — `bench_pipeline` asserts a probe transaction's lifecycle
    linkage with this."""
    return sorted({e[1] for e in snapshot() if e[2] == trace_id})


def trace_nodes(trace_id: str) -> list:
    """The distinct logical nodes that recorded events under one
    trace_id, sorted (round 18: the cross-node rigs assert a probe
    transaction's trace really crossed node boundaries with this)."""
    return sorted({e[10] for e in snapshot()
                   if e[2] == trace_id and e[10] is not None})


def _fmt_attr(v):
    return v if isinstance(v, (bool, int, float, str)) or v is None \
        else str(v)


def clock_anchor() -> dict:
    """One (monotonic, wall) clock pair plus the derived wall time of
    trace ts=0 — stamped into every export header so the cluster
    merger (common/clustertrace.py) can align per-node Chrome-trace
    timelines onto one wall axis and REPORT residual skew instead of
    hiding it."""
    pc = time.perf_counter()
    wall = time.time()
    return {"perf_counter": pc, "wall": wall,
            "epoch_wall_s": wall - (pc - _PC0)}


def chrome_trace(trace_id: Optional[str] = None) -> dict:
    """The recorder as a Chrome-trace-event document
    (chrome://tracing / perfetto loadable). tid = pipeline stage
    (the first dotted segment of the span name) — or `node/stage`
    when the event's recording thread carried a node binding (the
    cross-node view, round 18) — so the overlapped stages render as
    parallel tracks; per-span correlation ids + attrs ride in `args`.
    `trace_id` filters to one transaction's spans (the `?trace_id=`
    surface: pulling one probe must not ship the whole ring). Attrs
    were stored raw — THIS is where they are formatted."""
    pid = os.getpid()
    tids: dict = {}
    out = []
    for ph, name, tr, sp, par, t0, dur, tname, attrs, err, node in \
            snapshot():
        if trace_id is not None and tr != trace_id:
            continue
        group = name.split(".", 1)[0]
        tid = tids.setdefault((node, group), len(tids) + 1)
        args = {"trace_id": tr, "span_id": sp, "thread": tname}
        if par is not None:
            args["parent_span_id"] = par
        if node is not None:
            args["node"] = node
        if attrs:
            for k, v in attrs.items():
                args[k] = _fmt_attr(v)
        if err is not None:
            args["error"] = err
        rec = {"ph": ph, "name": name, "cat": group, "pid": pid,
               "tid": tid, "ts": round((t0 - _PC0) * 1e6, 1),
               "args": args}
        if ph == "X":
            rec["dur"] = round(dur * 1e6, 1)
        else:
            rec["s"] = "p"
        out.append(rec)
    meta = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": "fabric-tpu"}}]
    for (node, group), tid in sorted(tids.items(),
                                     key=lambda kv: kv[1]):
        label = f"{node}/{group}" if node is not None \
            else f"stage:{group}"
        meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": tid, "args": {"name": label}})
    return {"displayTimeUnit": "ms", "traceEvents": meta + out,
            "ftpu": {"pid": pid, "node_id": _default_node,
                     "clock": clock_anchor(),
                     **({"trace_id": trace_id}
                        if trace_id is not None else {})}}


def _dump_path(reason: str) -> str:
    d = _state.dump_dir or os.path.join(tempfile.gettempdir(),
                                        "ftpu_trace")
    os.makedirs(d, exist_ok=True)
    slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", reason)[:48] or "dump"
    return os.path.join(
        d, f"ftpu_trace_{os.getpid()}_{next(_dump_seq)}_{slug}.json")


def dump(reason: str = "manual", path: Optional[str] = None) -> str:
    """Write the recorder as a Chrome-trace JSON file and return the
    path. Default directory: `Operations.Tracing.DumpDir` /
    FTPU_TRACE_DUMP_DIR, else <tmp>/ftpu_trace. The document carries
    an `ftpu` header (reason, pid, wall time, stage quantiles) so a
    dump is a self-contained postmortem."""
    doc = chrome_trace()
    # extend (never replace) the export header: the clock anchor +
    # node id chrome_trace stamped are what the cluster merger aligns
    # dump FILES by
    doc["ftpu"].update({
        "reason": reason,
        "pid": os.getpid(),
        "wall_time": time.time(),
        "events": len(doc["traceEvents"]),
        "stage_quantiles": {
            k: {f: round(v, 6) if isinstance(v, float) else v
                for f, v in q.items()}
            for k, q in stage_quantiles().items()},
    })
    if path is None:
        path = _dump_path(reason)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    logger.warning("flight recorder dumped %d event(s) to %s (%s)",
                   len(doc["traceEvents"]), path, reason)
    return path


def auto_dump(reason: str) -> Optional[str]:
    """Rate-limited dump for automatic triggers (breaker trip, device
    quarantine, shed burst, bench watchdog): at most one file per
    `dump_min_interval_s`, written on a short-lived daemon thread —
    several triggers fire while their caller holds a stage lock or
    sits on a failure path, and the dump's file I/O must stall
    neither. Returns the path the dump WILL land at (None when
    rate-limited); `wait_dumps()` joins the writer for tests."""
    try:
        now = time.monotonic()
        with _state.dump_lock:
            last = _state.last_dump_t
            if last is not None and \
                    now - last < _state.dump_min_interval_s:
                return None
            _state.last_dump_t = now
        path = _dump_path(reason)

        def write():
            try:
                dump(reason, path=path)
            except Exception:
                logger.warning("flight-recorder auto dump failed "
                               "(%s)", reason, exc_info=True)

        t = threading.Thread(target=write, name="ftpu-trace-dump",
                             daemon=True)
        _dump_threads.append(t)
        del _dump_threads[:-4]      # keep only recent writers joinable
        t.start()
        return path
    except Exception:
        logger.warning("flight-recorder auto dump failed (%s)",
                       reason, exc_info=True)
        return None


_dump_threads: list = []


def wait_dumps(timeout: float = 10.0) -> None:
    """Join any in-flight async dump writers (tests / bench teardown)."""
    for t in list(_dump_threads):
        t.join(timeout)
