"""Profiling surfaces for the operations endpoint.

Rebuild of the reference's pprof wiring (`cmd/peer/main.go:10` imports
net/http/pprof; served on the operations listener when
`peer.profile.enabled`, `internal/peer/node/start.go:842-850`) —
adapted to this runtime:

  * `sample_profile(seconds)` — a sampling CPU profiler over
    `sys._current_frames()` (the pprof "profile" analog for Python:
    no instrumentation, safe on a live node);
  * `capture_jax_trace(out_dir, seconds)` — a JAX profiler capture
    producing an xplane trace of whatever runs on the devices during
    the window (SURVEY §5: the rebuild adds xplane capture on the
    compute path). View with TensorBoard / xprof.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import sys
import threading
import time

logger = logging.getLogger("common.profiling")

SAMPLE_HZ = 100

_poller_seq = itertools.count()


def _spawn_poller(name: str, poll_s: float, tick) -> threading.Thread:
    """One daemon poll loop, with a process-unique thread name and a
    deterministic per-poller interval jitter. Every publisher used to
    spawn with the same bare name and the same 5s period, so stacked
    pollers woke in phase — the sampling profiler (/debug/profile)
    read the synchronized sleep stacks as one aliased hot frame, and
    two providers' pollers were indistinguishable in a thread dump.
    The jitter staggers the periods (+3% per poller sequence —
    strictly DISTINCT periods, so no two pollers ever re-align; a
    modulo scheme would hand the 6th poller the 1st one's exact
    period back) and the `-<seq>` suffix makes each poller
    attributable."""
    seq = next(_poller_seq)
    interval = poll_s * (1.0 + 0.03 * seq)

    def loop():
        while True:
            tick()
            time.sleep(interval)

    t = threading.Thread(target=loop, name=f"{name}-{seq}",
                         daemon=True)
    t.start()
    return t


def sample_profile(seconds: float = 5.0, hz: int = SAMPLE_HZ) -> str:
    """Sample every thread's stack for `seconds`; returns a text
    report of the hottest stacks (collapsed, most-sampled first)."""
    interval = 1.0 / hz
    counts: collections.Counter = collections.Counter()
    nsamples = 0
    me = threading.get_ident()
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < 48:
                code = f.f_code
                stack.append(f"{os.path.basename(code.co_filename)}:"
                             f"{f.f_lineno}:{code.co_name}")
                f = f.f_back
            counts["; ".join(reversed(stack))] += 1
        nsamples += 1
        time.sleep(interval)
    lines = [f"# {nsamples} samples over {seconds:.1f}s at {hz} Hz"]
    for stack, n in counts.most_common(40):
        pct = 100.0 * n / max(1, nsamples)
        lines.append(f"{pct:5.1f}%  {n:6d}  {stack}")
    return "\n".join(lines) + "\n"


_trace_lock = threading.Lock()

# bounded /debug/jax/trace output: captures beyond this many are
# pruned oldest-first from the managed parent directory
JAX_TRACE_KEEP = int(os.environ.get("FTPU_JAX_TRACE_KEEP", "5"))


class ProfilerBusyError(RuntimeError):
    """A jax-trace capture is already running. The JAX profiler
    supports one live session per process; a second request must be
    REFUSED immediately (the ops endpoint maps this to 409) — the old
    behavior parked the second HTTP worker on the lock for the whole
    capture window."""


def capture_jax_trace(out_dir: str, seconds: float = 3.0) -> str:
    """Capture a JAX/xplane profiler trace of device activity for
    `seconds`; returns the trace directory. One live session per
    process: a concurrent call raises ProfilerBusyError immediately
    instead of queueing behind the full capture window."""
    import jax

    if not _trace_lock.acquire(blocking=False):
        raise ProfilerBusyError(
            "a jax trace capture is already running; retry after its "
            "window ends")
    try:
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        _trace_lock.release()
    return out_dir


def capture_jax_trace_bounded(seconds: float = 3.0,
                              parent_dir: str | None = None,
                              keep: int | None = None) -> str:
    """The ops-endpoint capture: a fresh per-capture directory under
    ONE managed parent, pruned to the newest `keep` captures after
    each run — /debug/jax/trace used to mkdtemp a new orphan
    directory per request, growing tmp without bound. Raises
    ProfilerBusyError like capture_jax_trace."""
    import tempfile

    parent = parent_dir or os.path.join(tempfile.gettempdir(),
                                        "ftpu_jax_trace")
    os.makedirs(parent, exist_ok=True)
    out = tempfile.mkdtemp(prefix="jax_trace_", dir=parent)
    try:
        capture_jax_trace(out, seconds)
    except ProfilerBusyError:
        try:
            os.rmdir(out)           # never leak the unused dir
        except OSError as e:
            logger.debug("could not remove unused trace dir %s: %s",
                         out, e)
        raise
    _prune_trace_dirs(parent, JAX_TRACE_KEEP if keep is None
                      else max(1, int(keep)))
    return out


def _prune_trace_dirs(parent: str, keep: int) -> None:
    """Delete all but the newest `keep` capture directories under
    `parent` (best-effort — a prune failure never fails the capture
    that triggered it)."""
    import shutil

    try:
        entries = [e for e in os.scandir(parent) if e.is_dir()]
    except OSError:
        return
    entries.sort(key=lambda e: e.stat().st_mtime, reverse=True)
    for e in entries[max(1, keep):]:
        shutil.rmtree(e.path, ignore_errors=True)


def publish_provider_stats(metrics_provider, csp, poll_s: float = 5.0):
    """Expose a BCCSP provider's `stats` counters as gauges
    (`bccsp_<name>`), refreshed by a daemon poller — the TPU
    path's perf-cliff counters (comb vs ladder dispatches, sw
    fallbacks, table cache bytes/evictions) become scrapeable instead
    of debugger-only. Returns the poller thread (daemon, running)."""
    from fabric_tpu.common import metrics as metrics_mod

    stats = getattr(csp, "stats", None)
    if not isinstance(stats, dict):
        return None
    # the pipeline stage timers have canonical declarations (help text,
    # gendoc rows) in common/metrics.py; every other stats key gets a
    # generic gauge named after it
    canonical = {
        "pipeline_host_s": metrics_mod.BCCSP_PIPELINE_HOST_SECONDS_OPTS,
        "pipeline_transfer_s":
            metrics_mod.BCCSP_PIPELINE_TRANSFER_SECONDS_OPTS,
        "pipeline_device_s":
            metrics_mod.BCCSP_PIPELINE_DEVICE_SECONDS_OPTS,
        "pipeline_overlap_ratio":
            metrics_mod.BCCSP_PIPELINE_OVERLAP_RATIO_OPTS,
        # sharded-dispatch scalars share their fqnames with the
        # canonical bccsp_shard_* declarations — the generic fallback
        # opts would collide in the registry with different help text
        "shard_devices": metrics_mod.BCCSP_SHARD_DEVICES_OPTS,
        "shard_dispatches": metrics_mod.BCCSP_SHARD_DISPATCHES_OPTS,
        "shard_skew_s": metrics_mod.BCCSP_SHARD_SKEW_SECONDS_OPTS,
        # the scalar quarantine/readmit aggregates share their STATS
        # key with the device-labeled bccsp_device_* series; their
        # canonical *_total names keep the registry fqnames disjoint
        # (the round-13 exclusion left these aggregates unpublished)
        "device_quarantines":
            metrics_mod.BCCSP_DEVICE_QUARANTINES_TOTAL_OPTS,
        "device_readmits":
            metrics_mod.BCCSP_DEVICE_READMITS_TOTAL_OPTS,
        # round-16 compile/cache telemetry (common/devicecost.py):
        # the canonical names operators alert on — cold compiles in
        # steady state are the minutes-long latency cliff
        "compile_total": metrics_mod.BCCSP_COMPILE_TOTAL_OPTS,
        "compile_cache_hits":
            metrics_mod.BCCSP_COMPILE_CACHE_HITS_OPTS,
        "compile_seconds": metrics_mod.BCCSP_COMPILE_SECONDS_OPTS,
        "executable_store_hits":
            metrics_mod.BCCSP_EXECUTABLE_STORE_HITS_OPTS,
        "executable_store_misses":
            metrics_mod.BCCSP_EXECUTABLE_STORE_MISSES_OPTS,
        "executable_store_errors":
            metrics_mod.BCCSP_EXECUTABLE_STORE_ERRORS_OPTS,
        # round-21 pairing engine: serving/demotion counters spanning
        # both device pairing paths (BLS12-381 aggregates, BN254
        # idemix products)
        "pairing_pairs": metrics_mod.BCCSP_PAIRING_PAIRS_OPTS,
        "pairing_batches": metrics_mod.BCCSP_PAIRING_BATCHES_OPTS,
        "pairing_fallbacks": metrics_mod.BCCSP_PAIRING_FALLBACKS_OPTS,
        # the prepared-block path's cumulative lane/byte counters (the
        # public reading of what the spans' attrs carry per call)
        "lanes_real": metrics_mod.BCCSP_LANES_REAL_OPTS,
        "lanes_padded": metrics_mod.BCCSP_LANES_PADDED_OPTS,
        "h2d_bytes": metrics_mod.BCCSP_H2D_BYTES_OPTS,
    }
    gauges = {
        name: metrics_provider.new_gauge(canonical.get(
            name, metrics_mod.GaugeOpts(
                namespace="bccsp", name=name,
                help="BCCSP provider runtime counter "
                     "(TPUProvider.stats)"))).with_labels()
        for name in stats
    }
    # the canonical degradation instruments (the names operators
    # alert on): breaker state gauge + trip counter, fed from the
    # provider's breaker rather than the stats dict so they track
    # state changes even between dispatches
    # per-device sharded-dispatch gauges (device label = mesh slot):
    # fed from the provider's shard_stats lists, refreshed per poll
    shard_stats = getattr(csp, "shard_stats", None)
    shard_gauges = None
    if isinstance(shard_stats, dict):
        try:
            shard_gauges = {
                "transfer_s": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_SHARD_TRANSFER_SECONDS_OPTS),
                "ready_s": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_SHARD_READY_SECONDS_OPTS),
                "lanes": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_SHARD_LANES_OPTS),
            }
        except Exception:
            shard_gauges = None
    # per-device health gauges (device label = FULL-mesh index): fed
    # from the provider's device_stats property — read fresh per poll
    # so cooldown-driven state changes (quarantined -> probing) show
    # without a dispatch
    device_stats = getattr(csp, "device_stats", None)
    device_gauges = None
    if isinstance(device_stats, dict):
        try:
            device_gauges = {
                "state": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_DEVICE_STATE_OPTS),
                "trips": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_DEVICE_TRIPS_OPTS),
                "quarantines": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_DEVICE_QUARANTINES_OPTS),
                "readmits": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_DEVICE_READMITS_OPTS),
            }
        except Exception:
            device_gauges = None
    # scheme-router gauges (scheme label = router partition key):
    # fed from the provider's scheme_stats dicts, refreshed per poll
    scheme_stats = getattr(csp, "scheme_stats", None)
    scheme_gauges = None
    if isinstance(scheme_stats, dict):
        try:
            scheme_gauges = {
                "lanes": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_SCHEME_LANES_OPTS),
                "sw_lanes": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_SCHEME_SW_LANES_OPTS),
                "dispatches": metrics_provider.new_gauge(
                    metrics_mod.BCCSP_SCHEME_DISPATCHES_OPTS),
            }
        except Exception:
            scheme_gauges = None
    # which backend serves: bccsp_device_info{platform,device_kind}
    device_info = getattr(csp, "device_info", None)
    if callable(device_info):
        try:
            d = device_info()
            metrics_provider.new_gauge(
                metrics_mod.BCCSP_DEVICE_INFO_OPTS).with_labels(
                    "platform", d["platform"],
                    "device_kind", d["device_kind"]).set(
                        float(d["count"]))
        except Exception as e:          # noqa: BLE001
            logger.warning("bccsp device info gauge publish failed: "
                           "%s", e)
    breaker = getattr(csp, "_breaker", None)
    fallback_state = fallback_trips = None
    if breaker is not None:
        try:
            fallback_state = metrics_provider.new_gauge(
                metrics_mod.BCCSP_FALLBACK_STATE_OPTS).with_labels()
            fallback_trips = metrics_provider.new_counter(
                metrics_mod.BCCSP_FALLBACK_TRIPS_OPTS).with_labels()
        except Exception:
            fallback_state = fallback_trips = None
    # the admission window (bccsp/admission.py) attaches itself to the
    # provider; its convoy wait becomes bccsp_admission_wait_s (the
    # window may appear AFTER this poller starts — re-probed per poll)
    try:
        admission_wait = metrics_provider.new_gauge(
            metrics_mod.BCCSP_ADMISSION_WAIT_SECONDS_OPTS).with_labels()
    except Exception:
        admission_wait = None

    state = {"last_trips": 0}
    warned: set = set()         # once per gauge, not once per poll

    def tick():
        if admission_wait is not None:
            win = getattr(csp, "__ftpu_admission_window__", None)
            if win is not None:
                try:
                    admission_wait.set(float(
                        win.stats.get("window_last_wait_s", 0.0)))
                except Exception as e:
                    if "admission" not in warned:
                        warned.add("admission")
                        logger.warning(
                            "bccsp admission gauge publish failed "
                            "(suppressing repeats): %s", e)
        for name, g in gauges.items():
            try:
                g.set(float(stats.get(name, 0)))
            except Exception as e:
                if name not in warned:
                    warned.add(name)
                    logger.warning("bccsp stats gauge %r publish "
                                   "failed (suppressing repeats): "
                                   "%s", name, e)
        if shard_gauges is not None:
            # re-read per poll: the provider replaces the dict
            # wholesale on each sharded batch
            cur = getattr(csp, "shard_stats", None)
            if isinstance(cur, dict):
                for name, g in shard_gauges.items():
                    try:
                        for d, v in enumerate(cur.get(name) or ()):
                            g.with_labels("device",
                                          str(d)).set(float(v))
                    except Exception as e:
                        if ("shard_" + name) not in warned:
                            warned.add("shard_" + name)
                            logger.warning(
                                "bccsp shard gauge %r publish "
                                "failed (suppressing repeats): %s",
                                name, e)
        if device_gauges is not None:
            cur = getattr(csp, "device_stats", None)
            if isinstance(cur, dict):
                for name, g in device_gauges.items():
                    try:
                        for d, v in enumerate(cur.get(name) or ()):
                            g.with_labels("device",
                                          str(d)).set(float(v))
                    except Exception as e:
                        if ("device_" + name) not in warned:
                            warned.add("device_" + name)
                            logger.warning(
                                "bccsp device gauge %r publish "
                                "failed (suppressing repeats): %s",
                                name, e)
        if scheme_gauges is not None:
            cur = getattr(csp, "scheme_stats", None)
            if isinstance(cur, dict):
                for name, g in scheme_gauges.items():
                    try:
                        for scheme, v in dict(
                                cur.get(name) or {}).items():
                            g.with_labels(
                                "scheme", str(scheme)).set(
                                    float(v))
                    except Exception as e:
                        if ("scheme_" + name) not in warned:
                            warned.add("scheme_" + name)
                            logger.warning(
                                "bccsp scheme gauge %r publish "
                                "failed (suppressing repeats): %s",
                                name, e)
        if fallback_state is not None:
            try:
                fallback_state.set(float(breaker.state_code))
                trips = breaker.stats["trips"]
                if trips > state["last_trips"]:
                    fallback_trips.add(trips - state["last_trips"])
                    state["last_trips"] = trips
            except Exception as e:
                if "breaker" not in warned:
                    warned.add("breaker")
                    logger.warning("bccsp breaker gauge publish "
                                   "failed (suppressing repeats): "
                                   "%s", e)

    return _spawn_poller("bccsp-stats", poll_s, tick)


def publish_overload_stats(metrics_provider, poll_s: float = 5.0):
    """Expose every registered overload stage (common/overload.py:
    shedding queues, the admission window, the write stage, the commit
    pipeline) as the canonical `overload_queue_{depth,capacity,
    max_depth,wait_s}` gauges and the `overload_sheds_total` counter,
    stage-labeled, refreshed by a daemon poller — the round-12
    overload surfaces an operator alerts on (sheds_total growing =
    load past capacity, shed cleanly). Returns the poller thread."""
    from fabric_tpu.common import metrics as metrics_mod
    from fabric_tpu.common import overload

    depth_g = metrics_provider.new_gauge(
        metrics_mod.OVERLOAD_QUEUE_DEPTH_OPTS)
    cap_g = metrics_provider.new_gauge(
        metrics_mod.OVERLOAD_QUEUE_CAPACITY_OPTS)
    max_g = metrics_provider.new_gauge(
        metrics_mod.OVERLOAD_QUEUE_MAX_DEPTH_OPTS)
    wait_g = metrics_provider.new_gauge(
        metrics_mod.OVERLOAD_PUT_WAIT_SECONDS_OPTS)
    sheds_c = metrics_provider.new_counter(
        metrics_mod.OVERLOAD_SHEDS_TOTAL_OPTS)
    rate_g = metrics_provider.new_gauge(
        metrics_mod.OVERLOAD_SHED_RATE_OPTS)

    last_sheds: dict = {}
    warned: set = set()

    def tick():
        for stage, s in overload.stage_stats().items():
            try:
                lbl = ("stage", stage)
                depth_g.with_labels(*lbl).set(
                    float(s.get("depth", 0)))
                cap_g.with_labels(*lbl).set(
                    float(s.get("capacity", 0)))
                if "max_depth" in s:
                    max_g.with_labels(*lbl).set(
                        float(s["max_depth"]))
                if "last_wait_s" in s:
                    wait_g.with_labels(*lbl).set(
                        float(s["last_wait_s"]))
                if "shed_rate" in s:
                    rate_g.with_labels(*lbl).set(
                        float(s["shed_rate"]))
                sheds = int(s.get("sheds", 0))
                if sheds > last_sheds.get(stage, 0):
                    sheds_c.with_labels(*lbl).add(
                        sheds - last_sheds.get(stage, 0))
                    last_sheds[stage] = sheds
            except Exception as e:
                if stage not in warned:
                    warned.add(stage)
                    logger.warning(
                        "overload gauge publish for %r failed "
                        "(suppressing repeats): %s", stage, e)

    return _spawn_poller("overload-stats", poll_s, tick)


def publish_order_stats(metrics_provider, registrar, poll_s: float = 5.0):
    """Expose every raft chain's ordering-pipeline readings as the
    canonical `orderer_batch_{fill,propose_s,consensus_s,write_s,
    overlap_ratio}` gauges (channel-labeled), refreshed by a daemon
    poller — the batched-ordering perf counters (admission-window
    fill, propose/consensus/write stage seconds, write-overlap ratio)
    become scrapeable beside the `bccsp_*` gauges. `registrar` must
    expose `channel_list()` + `get_chain(id)` (whose `.chain` may
    implement `order_pipeline_stats()`; chains that don't — solo,
    followers — are skipped). Returns the poller thread."""
    from fabric_tpu.common import metrics as metrics_mod

    if not hasattr(registrar, "channel_list"):
        return None
    gauges = {
        "fill": metrics_provider.new_gauge(
            metrics_mod.ORDERER_BATCH_FILL_OPTS),
        "propose_s": metrics_provider.new_gauge(
            metrics_mod.ORDERER_BATCH_PROPOSE_SECONDS_OPTS),
        "consensus_s": metrics_provider.new_gauge(
            metrics_mod.ORDERER_BATCH_CONSENSUS_SECONDS_OPTS),
        "write_s": metrics_provider.new_gauge(
            metrics_mod.ORDERER_BATCH_WRITE_SECONDS_OPTS),
        "overlap_ratio": metrics_provider.new_gauge(
            metrics_mod.ORDERER_BATCH_OVERLAP_RATIO_OPTS),
    }

    warned: set = set()         # once per channel, not once per poll

    def tick():
        for cid in registrar.channel_list():
            support = registrar.get_chain(cid)
            stats_fn = getattr(
                getattr(support, "chain", None),
                "order_pipeline_stats", None)
            if stats_fn is None:
                continue
            try:
                stats = stats_fn()
                for name, g in gauges.items():
                    g.with_labels("channel", cid).set(
                        float(stats.get(name, 0)))
            except Exception as e:
                if cid not in warned:
                    warned.add(cid)
                    logger.warning(
                        "orderer batch gauge publish for %r "
                        "failed (suppressing repeats): %s", cid, e)

    return _spawn_poller("orderer-batch-stats", poll_s, tick)


def publish_devicecost_stats(metrics_provider, csp,
                             poll_s: float = 5.0):
    """Expose the round-16 device-cost readings as gauges, refreshed
    by a daemon poller: per-device memory occupancy
    (`bccsp_device_mem_{used,peak,limit}_bytes`, from each device's
    memory_stats — devices without the API publish nothing) and
    per-device busy ratios (`bccsp_device_busy_ratio`, device-time
    over wall-time in the poll window, fed by the provider's
    CompileRecorder.busy accumulator). The compile/cache counters
    themselves ride publish_provider_stats (they live in the
    provider's stats dict). Returns the poller thread, or None when
    the gauges cannot be declared."""
    tick = devicecost_tick(metrics_provider, csp)
    if tick is None:
        return None
    return _spawn_poller("devicecost-stats", poll_s, tick)


def devicecost_tick(metrics_provider, csp):
    """Build the devicecost gauges and return the refresh callable
    (None when the gauges cannot be declared) — split from
    publish_devicecost_stats so tests drive one deterministic tick
    instead of leaking a fast poller that keeps crossing into the
    jax runtime for the rest of the session."""
    from fabric_tpu.common import devicecost as dc
    from fabric_tpu.common import metrics as metrics_mod

    try:
        mem_used = metrics_provider.new_gauge(
            metrics_mod.BCCSP_DEVICE_MEM_USED_BYTES_OPTS)
        mem_peak = metrics_provider.new_gauge(
            metrics_mod.BCCSP_DEVICE_MEM_PEAK_BYTES_OPTS)
        mem_limit = metrics_provider.new_gauge(
            metrics_mod.BCCSP_DEVICE_MEM_LIMIT_BYTES_OPTS)
        busy_g = metrics_provider.new_gauge(
            metrics_mod.BCCSP_DEVICE_BUSY_RATIO_OPTS)
    except Exception:
        logger.warning("devicecost gauges unavailable", exc_info=True)
        return None

    warned: set = set()

    def tick():
        try:
            rows = dc.device_memory()
        except Exception as e:      # noqa: BLE001
            rows = []
            if "mem" not in warned:
                warned.add("mem")
                logger.warning("device memory probe failed "
                               "(suppressing repeats): %s", e)
        for r in rows:
            try:
                lbl = ("device", str(r["device"]))
                mem_used.with_labels(*lbl).set(
                    float(r["bytes_in_use"]))
                mem_peak.with_labels(*lbl).set(
                    float(r["peak_bytes_in_use"]))
                mem_limit.with_labels(*lbl).set(
                    float(r["bytes_limit"]))
            except Exception as e:  # noqa: BLE001
                if "mem_gauge" not in warned:
                    warned.add("mem_gauge")
                    logger.warning("device memory gauge publish "
                                   "failed (suppressing repeats): "
                                   "%s", e)
        rec = getattr(csp, "device_cost", None)
        if rec is not None:
            try:
                for d, ratio in rec.busy.ratios().items():
                    busy_g.with_labels("device", str(d)).set(
                        float(ratio))
            except Exception as e:  # noqa: BLE001
                if "busy" not in warned:
                    warned.add("busy")
                    logger.warning("device busy-ratio publish failed "
                                   "(suppressing repeats): %s", e)

    return tick
