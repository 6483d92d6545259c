"""Device-cost observability: XLA compile & cache telemetry, per-chip
memory accounting and device-busy ratios (round 16).

The device side of the pipeline was blind before this layer: a cold
XLA compile is the 1436s-vs-88s restart cliff PR 6 measured, a
persistent-cache miss in steady state means an unplanned shape slipped
into serving, and HBM occupancy decides whether the next oversized
span OOMs — none of which was observable. Three instruments fix that:

  * ``CompileRecorder`` — the ONE seam every compiled-path build in
    `bccsp/tpu.py` goes through (``TPUProvider._jit``). Each first
    dispatch of a new argument shape (and each program prewarm names
    ahead of time, ``InstrumentedJit.aot`` — served from the store of
    compiled executables, common/execstore.py, without a trace where
    it holds the program) is timed, classified
    cache-hit vs cold (persistent-cache-dir delta + a wall-time
    threshold: a cold compile WRITES a new cache entry and takes
    seconds-to-minutes; a warm load does neither), annotated with
    XLA's lowering cost analysis (flops / bytes accessed, where the
    jax version exposes it), and recorded as a ``tpu.compile`` tracing
    span. A cold compile emits a ``compile.cold`` instant, and in
    steady state (after the first successful dispatch) auto-dumps the
    flight recorder — a steady-state cold compile is exactly the
    latency cliff an operator needs the timeline for.
  * ``device_memory()`` — per-device ``memory_stats()`` rows
    (bytes_in_use / peak / limit; devices without the API — CPU test
    meshes — simply report nothing), polled by
    ``profiling.publish_devicecost_stats`` into the
    ``bccsp_device_mem_{used,peak,limit}_bytes`` gauges, and read by
    the provider's `/healthz` HBM-headroom sub-state.
  * ``DeviceBusy`` — cumulative per-chip device-time fed from the
    same per-chip ready readings that feed the ``device.ready.d<k>``
    tracing stages; ``ratios()`` converts the window's accumulation
    into ``bccsp_device_busy_ratio`` (device-time over wall-time).

Everything here is wheel-free and clock-seamed for tests: the
recorder takes an injectable clock and cache-dir resolver, and the
whole layer imports jax lazily (a host without a device plugin still
imports and serves zeros).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Optional

from fabric_tpu.common import execstore, tracing

logger = logging.getLogger("common.devicecost")

# a first-shape dispatch slower than this is a compile even when the
# cache-dir probe is unavailable (threshold rule); a persistent-cache
# HIT is an mmap-and-load, far under a second even for the big comb
# programs (PR-6: cached 88s total vs cold 1436s across ~a dozen
# shapes)
COLD_COMPILE_THRESHOLD_S = float(
    os.environ.get("FTPU_DEVICECOST_COLD_S", "5.0"))

# minimum free fraction of any device's memory limit before /healthz
# components.bccsp grows the hbm_low sub-state — the "an oversized
# span is about to OOM" warning light
HBM_HEADROOM_FRAC = float(
    os.environ.get("FTPU_HBM_HEADROOM_FRAC", "0.10"))

# lowering cost analysis traces the program a second time (seconds on
# the big comb pipelines) — a once-per-shape cost, but disable-able
# for deadline-critical rigs
ANALYSIS_ENABLED = os.environ.get("FTPU_DEVICECOST_ANALYSIS",
                                  "1") == "1"

_EVENT_CAP = 256        # bounded per-compile event history
_UNRESOLVED = object()  # CompileRecorder.store before its first use

# JAX's own word on a compile request that found its program in the
# persistent cache (jax.monitoring). Where it speaks it beats the
# wall-time threshold: the 8,192-lane comb pipeline LOADS in ~9 s on
# a v5e (PR 23, chip run) — over the threshold, yet no compile.
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_jax_cache_hits = [0]
_listening = threading.Lock()


def _jax_cache_hit_count() -> int:
    """Persistent-cache hits JAX has reported in this process so far
    (the listener is registered on first use; 0 forever without jax)."""
    if _listening.acquire(blocking=False):      # once per process
        try:
            import jax.monitoring

            def on_event(event, **_kw):
                if event == _JAX_CACHE_HIT:
                    _jax_cache_hits[0] += 1
            jax.monitoring.register_event_listener(on_event)
        except Exception:           # noqa: BLE001 (jax-free callers)
            logger.debug("no jax.monitoring; threshold rule only")
    return _jax_cache_hits[0]


def _shape_key(args, static=()) -> tuple:
    """A compiled-program shape key: (shape, dtype) per argument —
    the same data XLA keys its own dispatch cache by. Non-array
    arguments degrade to their type name, and an argument at one of
    the program's `static` positions is keyed by its value."""
    return tuple(
        ("static", a) if i in static else
        (getattr(a, "shape", None),
         getattr(a, "dtype", None) if getattr(a, "dtype", None)
         is not None else type(a).__name__)
        for i, a in enumerate(args))


def _normalize_cost(ca) -> Optional[dict]:
    """One normalization of XLA's cost_analysis return shapes (dict
    in current jax, list-of-dict historically) into the two numbers
    the events carry — shared by the first-dispatch and AOT paths so
    they can never classify the same compile differently."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for k in ("flops", "bytes accessed"):
        if k in ca:
            out[k.replace(" ", "_")] = float(ca[k])
    return out or None


class DeviceBusy:
    """Cumulative per-device busy seconds -> windowed busy ratios.

    ``note(device, seconds)`` accumulates device-time (the per-chip
    ready lag of a sharded dispatch, or the whole-batch device stage
    on a single-chip provider); ``ratios()`` returns each device's
    busy-time share of the wall window since the previous ``ratios()``
    call, clamped to [0, 1] — the poller's cadence IS the window."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._busy: dict = {}       # device -> cumulative seconds
        self._last: dict = {}       # snapshot at the last ratios()
        self._last_t = clock()

    def note(self, device: int, seconds: float) -> None:
        if seconds <= 0:
            return
        with self._lock:
            self._busy[device] = self._busy.get(device, 0.0) + \
                float(seconds)

    def totals(self) -> dict:
        with self._lock:
            return dict(self._busy)

    def ratios(self) -> dict:
        """{device: busy_fraction} over the window since the last
        call. A device with no dispatches in the window reads 0.0 —
        idle, not absent."""
        with self._lock:
            now = self._clock()
            wall = now - self._last_t
            out: dict = {}
            if wall > 0:
                for d, total in self._busy.items():
                    delta = total - self._last.get(d, 0.0)
                    out[d] = round(min(1.0, max(0.0, delta / wall)), 4)
            self._last = dict(self._busy)
            self._last_t = now
            return out


class CompileRecorder:
    """The compile-seam bookkeeper (one per provider).

    Mirrors its counters into the provider's ``stats`` dict so they
    publish through the existing stats poller as the canonical
    ``bccsp_compile_{total,cache_hits,seconds}`` gauges:

      compile_total       programs compiled/loaded through the seam
      compile_cache_hits  persistent-compile-cache hits among them
      compile_cold_total  cold compiles (the expensive complement)
      compile_failures    builds/compiles that raised (armed
                          ``tpu.compile`` faults land here)
      compile_seconds     cumulative wall seconds inside the seam
      executable_store_hits    AOT requests served from the store of
                               compiled executables (no trace, no
                               lowering; each is also a cache hit)
      executable_store_misses  AOT requests the store had no entry
                               for: lowered, compiled or loaded from
                               the persistent cache, then written
      executable_store_errors  entries that could not be read, checked
                               or loaded (served as a miss and
                               replaced), and failed writes

    ``cache_dir`` may be a path, a zero-arg callable resolving one
    (``jaxenv.cache_dir`` — the persistent cache may be enabled after
    the provider is built), or None (threshold-only classification).
    ``store`` is the executable store behind the AOT seam; left unset
    it is ``ExecutableStore.beside_compile_cache`` of that directory,
    resolved at the first AOT request (tests hand one over a temporary
    directory).
    """

    def __init__(self, stats: Optional[dict] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 cache_dir=None,
                 cold_threshold_s: Optional[float] = None,
                 analysis: Optional[bool] = None,
                 store: Optional[execstore.ExecutableStore] = None):
        self.stats = stats if stats is not None else {}
        self.stats.setdefault("compile_total", 0)
        self.stats.setdefault("compile_cache_hits", 0)
        self.stats.setdefault("compile_cold_total", 0)
        self.stats.setdefault("compile_failures", 0)
        self.stats.setdefault("compile_seconds", 0.0)
        self.stats.setdefault("executable_store_hits", 0)
        self.stats.setdefault("executable_store_misses", 0)
        self.stats.setdefault("executable_store_errors", 0)
        self._store = _UNRESOLVED if store is None else store
        self._clock = clock
        self._cache_dir = cache_dir
        self.cold_threshold_s = (COLD_COMPILE_THRESHOLD_S
                                 if cold_threshold_s is None
                                 else float(cold_threshold_s))
        self.analysis = (ANALYSIS_ENABLED if analysis is None
                         else bool(analysis))
        self.events: list = []      # bounded per-compile records
        self._lock = threading.Lock()
        self._steady = False
        self.busy = DeviceBusy()

    # -- steady-state marker (set after the first successful
    #    dispatch: later cold compiles are serving-path cliffs) --

    @property
    def steady(self) -> bool:
        return self._steady

    def mark_steady(self) -> None:
        self._steady = True

    # -- persistent-cache probe --

    def _cache_dir_path(self) -> Optional[str]:
        d = self._cache_dir
        if callable(d):
            try:
                d = d()
            except Exception:       # noqa: BLE001
                return None
        return d if isinstance(d, str) and d else None

    def cache_entries(self) -> int:
        """Entry count of the persistent compile cache dir, or -1
        when there is none to probe. A cold compile WRITES an entry;
        a warm load only reads — the before/after delta is the
        hit-vs-miss signal the wall-time threshold backstops."""
        d = self._cache_dir_path()
        if not d:
            return -1
        try:
            with os.scandir(d) as it:
                return sum(1 for e in it if e.is_file())
        except OSError:
            return -1

    # -- the executable store behind the AOT seam --

    @property
    def store(self) -> Optional[execstore.ExecutableStore]:
        if self._store is _UNRESOLVED:
            try:
                self._store = \
                    execstore.ExecutableStore.beside_compile_cache(
                        self._cache_dir_path())
            except Exception:       # noqa: BLE001 (no backend: no store)
                logger.exception("no executable store")
                self._store = None
        return self._store

    @store.setter
    def store(self, store: Optional[execstore.ExecutableStore]) -> None:
        self._store = store

    def count_store(self, outcome: str) -> None:
        """``hits`` | ``misses`` | ``errors`` += 1."""
        with self._lock:
            self.stats["executable_store_" + outcome] += 1

    # -- recording --

    def note(self, kind: str, seconds: float, *, cache_hit: bool,
             key=None, cost: Optional[dict] = None,
             error: Optional[BaseException] = None,
             aot: bool = False, detail: Optional[dict] = None) -> None:
        """Book one pass through the seam. ``error`` records a failed
        build/compile (counter only — the caller re-raises and the
        enclosing ``tpu.compile`` span stamps error status).
        ``detail`` is what the ``tpu.compile`` span carries beside
        kind and aot (`run_compile`)."""
        detail = detail or {}
        ev = {"kind": kind, "seconds": round(float(seconds), 6),
              "cache_hit": bool(cache_hit) and error is None,
              "cold": error is None and not cache_hit,
              "source": detail.get("source"),
              "lower_s": detail.get("lower_s"),
              "load_s": detail.get("load_s"),
              "aot": aot, "steady": self._steady,
              "key": repr(key) if key is not None else None,
              "cost": cost or None,
              "error": repr(error) if error is not None else None}
        with self._lock:
            if error is not None:
                self.stats["compile_failures"] += 1
            else:
                self.stats["compile_total"] += 1
                self.stats["compile_seconds"] = round(
                    self.stats["compile_seconds"] + float(seconds), 6)
                if cache_hit:
                    self.stats["compile_cache_hits"] += 1
                else:
                    self.stats["compile_cold_total"] += 1
            self.events.append(ev)
            if len(self.events) > _EVENT_CAP:
                del self.events[:len(self.events) - _EVENT_CAP]
        if error is None and not cache_hit:
            tracing.instant("compile.cold", kind=kind,
                            seconds=round(float(seconds), 3),
                            steady=self._steady)
            if self._steady:
                # the 1436s-vs-88s cliff, live: a cold compile AFTER
                # the provider reached steady state means an
                # unplanned shape entered serving — dump the
                # timeline around it
                tracing.auto_dump("cold_compile")
            logger.info(
                "cold XLA compile: kind=%s %.1fs%s", kind,
                float(seconds),
                " (STEADY STATE — unplanned shape?)"
                if self._steady else "")

    def run_compile(self, kind: str, key, thunk, *,
                    cost: Optional[dict] = None, aot: bool = False,
                    detail: Optional[dict] = None):
        """THE classification path: run `thunk` (a first-shape
        dispatch or an AOT request) inside a ``tpu.compile`` span,
        time it, classify hit-vs-cold (JAX's cache-hit event, else
        cache-dir entry delta + wall threshold) and book the event. A
        raising thunk books a failure and re-raises.

        ``detail`` is the AOT seam's account of what the thunk did,
        filled in by the thunk and set on the span: ``lower_s`` and
        ``load_s`` (seconds of tracing-and-lowering, and of loading or
        compiling, apart), ``cost``, and ``source: "store"`` where the
        executable store served the request — no compile of any kind.
        Every span leaves with ``source`` = ``store`` | ``cache`` |
        ``cold``."""
        detail = {} if detail is None else detail
        before = self.cache_entries()
        hits0 = _jax_cache_hit_count()
        t0 = self._clock()
        sp = tracing.span("tpu.compile", kind=kind, aot=aot)
        try:
            with sp:
                out = thunk()
                dt = self._clock() - t0
                if detail.get("source") == "store":
                    hit = True
                else:
                    wrote = before >= 0 and self.cache_entries() > before
                    hit = (not wrote) and (
                        detail.get("load_s", dt) < self.cold_threshold_s
                        or _jax_cache_hit_count() > hits0)
                    detail["source"] = "cache" if hit else "cold"
                cost = detail.pop("cost", cost)
                sp.set(**detail)
        except BaseException as e:
            self.note(kind, self._clock() - t0, cache_hit=False,
                      key=key, cost=cost, error=e, aot=aot)
            raise
        self.note(kind, dt, cache_hit=hit, key=key, cost=cost,
                  aot=aot, detail=detail)
        return out

    def wrap(self, kind: str, jitted, *, params: Optional[dict] = None,
             static_argnums=()) -> "InstrumentedJit":
        """Instrument one jitted program — the return value of the
        provider's ``_jit`` seam. ``params`` (every static parameter of
        the builder that made the program) and ``static_argnums`` (as
        given to ``jax.jit``) are what the AOT seam keys the executable
        store by, beside the shapes."""
        return InstrumentedJit(self, kind, jitted, params=params,
                               static_argnums=static_argnums)


def _devices_of(shapes) -> list:
    """The devices an AOT request's executable runs on: those of the
    mesh its shapes are sharded over, else the default device (what
    ``lower()`` itself resolves a shape without a sharding to)."""
    for a in shapes:
        mesh = getattr(getattr(a, "sharding", None), "mesh", None)
        if mesh is not None:
            return list(mesh.devices.flat)
    import jax
    return jax.devices()[:1]


class InstrumentedJit:
    """A jitted callable whose first dispatch per argument shape (and
    each AOT request, `aot`) runs inside the compile seam. Steady
    dispatches of a seen shape pay one dict lookup: `_ready` maps a
    shape key to what serves it — the jitted function itself for a
    shape that arrived by dispatch, the compiled executable for one
    that `aot` registered, which is then called directly (jit never
    sees the shape, so the process never traces it)."""

    __slots__ = ("_rec", "_kind", "_fn", "_ready", "_lock", "_params",
                 "_static")

    def __init__(self, recorder: CompileRecorder, kind: str, jitted, *,
                 params: Optional[dict] = None, static_argnums=()):
        self._rec = recorder
        self._kind = kind
        self._fn = jitted
        self._ready: dict = {}
        self._lock = threading.Lock()
        self._params = dict(params or {})
        self._static = ((static_argnums,)
                        if isinstance(static_argnums, int)
                        else tuple(static_argnums))

    def __call__(self, *args):
        key = _shape_key(args, self._static)
        serve = self._ready.get(key)
        if serve is not None:
            return serve(*args)
        return self._compile_call(key, args)

    def _compile_call(self, key, args):
        """The instrumented first-dispatch path. The shape is CLAIMED
        before the call (concurrent first dispatches of one shape
        record once — jit serializes the actual compile anyway) and
        unclaimed on failure so a retry records again; measurement +
        hit/cold classification is the recorder's shared
        ``run_compile`` path, inside its ``tpu.compile`` span."""
        rec = self._rec
        with self._lock:
            first = key not in self._ready
            if first:
                self._ready[key] = self._fn
        if not first:
            return self._ready[key](*args)
        cost = self._cost_analysis(args)
        try:
            return rec.run_compile(self._kind, key,
                                   lambda: self._fn(*args),
                                   cost=cost)
        except BaseException:
            with self._lock:
                if self._ready.get(key) is self._fn:
                    del self._ready[key]
            raise

    def _cost_analysis(self, args) -> Optional[dict]:
        """XLA's lowering cost analysis for this shape (flops /
        bytes accessed), where the jax version exposes it. Traces the
        program once more — a once-per-shape cost on the (already
        seconds-to-minutes) compile path, never the dispatch path."""
        if not self._rec.analysis:
            return None
        try:
            return _normalize_cost(self._fn.lower(*args)
                                   .cost_analysis())
        except Exception:           # noqa: BLE001
            return None

    def aot(self, *shapes):
        """The AOT seam: make the executable for `shapes`
        (``jax.ShapeDtypeStruct``s, a static argument by its value)
        ready BEFORE the first dispatch, and register it so that the
        dispatch of that shape calls it directly.

        With an executable store (``CompileRecorder.store``) the
        request is first looked up there, by a key that needs no trace
        (common/execstore.py): a hit deserializes and loads the entry
        and nothing is traced or lowered in this process. A miss — and
        any entry that cannot be read, checked or loaded, which counts
        in ``executable_store_errors`` — does ``lower().compile()``
        (JAX's persistent cache, or a cold compile) and writes the
        entry for the next process; a failed write only logs and
        counts. Either way it is one ``tpu.compile`` span
        (``aot=True``; ``source``, ``lower_s``, ``load_s``) and one
        event. The executable is strict about what it is given: call
        the shape with arrays of other dtypes or shardings than
        `shapes` said and it raises."""
        key = _shape_key(shapes, self._static)
        ready = self._ready.get(key)
        if ready is not None and ready is not self._fn:
            return
        rec = self._rec
        detail: dict = {}
        self._ready[key] = rec.run_compile(
            self._kind, key, lambda: self._aot(shapes, detail),
            aot=True, detail=detail)

    def _aot(self, shapes, detail: dict):
        rec, store, static = self._rec, self._rec.store, self._static
        clock = rec._clock
        compiled = req = None
        if store is not None:
            t0 = clock()
            try:
                req = store.request(self._kind, self._params, shapes,
                                    static, _devices_of(shapes))
                compiled = store.load(req)
            except Exception:       # noqa: BLE001 (served as a miss)
                rec.count_store("errors")
                logger.exception("executable store: %s entry not "
                                 "served; compiling", self._kind)
            else:
                rec.count_store("misses" if compiled is None else "hits")
            if compiled is not None:
                detail.update(source="store", lower_s=0.0,
                              load_s=round(clock() - t0, 6))
        if compiled is None:
            t0 = clock()
            lowered = self._fn.lower(*shapes)
            t1 = clock()
            if rec.analysis:
                try:
                    detail["cost"] = _normalize_cost(
                        lowered.cost_analysis())
                except Exception:   # noqa: BLE001 (where jax has none)
                    detail["cost"] = None
            t2 = clock()
            compiled = lowered.compile()
            detail.update(lower_s=round(t1 - t0, 6),
                          load_s=round(clock() - t2, 6))
            if req is not None:
                t0 = clock()
                try:
                    size = store.save(req, compiled)
                    detail.update(store_bytes=size)
                except Exception:   # noqa: BLE001 (logs and counts)
                    rec.count_store("errors")
                    logger.exception("executable store: %s entry not "
                                     "written", self._kind)
                detail.update(store_write_s=round(clock() - t0, 6))
        if not static:
            return compiled
        return lambda *args: compiled(
            *[a for i, a in enumerate(args) if i not in static])

    def __getattr__(self, name):
        return getattr(self._fn, name)


# ---------------------------------------------------------------------------
# per-device memory accounting
# ---------------------------------------------------------------------------

# device-index -> "answers memory_stats()" capability, learned on the
# first poll: a CPU mesh answers None for every device, and a polling
# thread must not keep crossing into the runtime (including during
# interpreter shutdown) for devices that will never report
_mem_capable: dict = {}


def device_memory() -> list:
    """One row per local device exposing ``memory_stats()``:
    ``{"device", "kind", "bytes_in_use", "peak_bytes_in_use",
    "bytes_limit"}``. Devices without the API (CPU meshes) and hosts
    without jax report nothing — the gauges simply stay unset — and
    are not re-probed on later polls."""
    if _mem_capable and not any(_mem_capable.values()):
        return []           # fleet-wide no-stats-API: learned once
    try:
        import jax
        devs = jax.local_devices()
    except Exception:               # noqa: BLE001
        return []
    rows = []
    for i, d in enumerate(devs):
        if _mem_capable.get(i) is False:
            continue
        try:
            ms = d.memory_stats()
            # capability latches only on a CLEAN "no stats API"
            # answer (None on CPU meshes); a transient exception
            # (mesh rebuild, busy runtime) must not permanently
            # silence this chip's mem gauges and hbm_low warning
            _mem_capable[i] = bool(ms)
        except Exception:           # noqa: BLE001
            ms = None
        if not ms:
            continue
        in_use = int(ms.get("bytes_in_use", 0))
        rows.append({
            "device": i,
            "kind": getattr(d, "device_kind", str(d)),
            "bytes_in_use": in_use,
            "peak_bytes_in_use": int(
                ms.get("peak_bytes_in_use", in_use)),
            "bytes_limit": int(ms.get("bytes_limit", 0)),
        })
    return rows


def peak_memory_bytes(rows: Optional[list] = None) -> int:
    """The fleet's worst per-device peak occupancy (bench stage-line
    ``mem_peak_bytes``); 0 when no device reports memory stats."""
    rows = device_memory() if rows is None else rows
    return max((r.get("peak_bytes_in_use", 0) for r in rows),
               default=0)


def hbm_substate(rows: Optional[list] = None,
                 headroom_frac: Optional[float] = None
                 ) -> Optional[str]:
    """`hbm_low:d<k>:<free>%free` naming the tightest device when any
    device's free fraction drops under the headroom threshold, else
    None — the `/healthz components.bccsp` sub-state that shows an
    oversized span BEFORE it OOMs."""
    frac = HBM_HEADROOM_FRAC if headroom_frac is None \
        else float(headroom_frac)
    rows = device_memory() if rows is None else rows
    worst = None
    for r in rows:
        limit = r.get("bytes_limit") or 0
        if limit <= 0:
            continue
        free = 1.0 - (r.get("bytes_in_use", 0) / limit)
        if worst is None or free < worst[1]:
            worst = (r.get("device"), free)
    if worst is not None and worst[1] < frac:
        return f"hbm_low:d{worst[0]}:{max(0, int(worst[1] * 100))}%free"
    return None
