"""Round-16 device-cost observability tests.

Compile-seam counting (cold vs persistent-cache-hit, wheel-free via
recorder stubs with injected clocks/cache dirs), the provider `_jit`
seam + armed `tpu.compile` faults (error-status compile spans,
compile_failures), busy-ratio math, memory-gauge rendering, the
/healthz HBM-headroom sub-state, and the /debug/jax/trace busy/bounded
hardening.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from fabric_tpu.common import devicecost, faults, profiling, tracing
from fabric_tpu.common import metrics as metrics_mod
from fabric_tpu.common.devicecost import (
    CompileRecorder, DeviceBusy,
)

pytestmark = pytest.mark.chaos


@pytest.fixture()
def trace_env(tmp_path):
    """Isolated flight recorder with instant dumps (the test_tracing
    fixture shape)."""
    tracing.configure(enabled=True, ring_size=256, sample_every=1,
                      dump_dir=str(tmp_path),
                      dump_min_interval_s=0.0, shed_burst=32)
    tracing.reset()
    yield tmp_path
    tracing.wait_dumps()
    tracing.configure(enabled=True, ring_size=4096, sample_every=1,
                      dump_dir="", dump_min_interval_s=10.0,
                      shed_burst=32)
    tracing.reset()


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class _FakeLowered:
    """Quacks like jax.stages.Lowered for the AOT seam."""

    def __init__(self, jit, cost=None):
        self._jit = jit
        self._cost = cost

    def cost_analysis(self):
        return self._cost

    def compile(self):
        self._jit._run_once()

        def compiled(*args):
            self._jit.compiled_calls += 1
            return "out of the executable"
        return compiled


class _FakeJit:
    """Recorder stub for the wheel-free compile-seam tests: each
    'compile' advances the injected clock by the next scripted
    duration and optionally writes a persistent-cache entry."""

    def __init__(self, clock, durations, cache_dir=None,
                 writes=None, cost=None, raises=None):
        self.clock = clock
        self.durations = list(durations)
        self.cache_dir = cache_dir
        self.writes = list(writes or [])
        self.cost = cost
        self.raises = raises
        self.calls = 0
        self.compiled_calls = 0

    def _run_once(self):
        if self.raises is not None:
            raise self.raises
        self.calls += 1
        self.clock.advance(self.durations.pop(0)
                           if self.durations else 0.0)
        if self.writes and self.writes.pop(0) and self.cache_dir:
            with open(os.path.join(
                    self.cache_dir,
                    f"entry_{self.calls}.bin"), "wb") as f:
                f.write(b"x")

    def __call__(self, *args):
        self._run_once()
        return "out"

    def lower(self, *args):
        return _FakeLowered(self, cost=self.cost)


# ---------------------------------------------------------------------------
# the compile seam (CompileRecorder + InstrumentedJit)
# ---------------------------------------------------------------------------

class TestCompileSeam:
    def _recorder(self, tmp_path, **kw):
        kw.setdefault("analysis", False)
        clock = kw.pop("clock", _Clock())
        return CompileRecorder(clock=clock, cache_dir=str(tmp_path),
                               **kw), clock

    def test_cold_then_seen_shape_records_once(self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        fake = _FakeJit(clock, durations=[10.0, 0.0])
        fn = rec.wrap("comb", fake)
        a = np.zeros((8,), np.int32)
        assert fn(a) == "out"
        assert rec.stats["compile_total"] == 1
        assert rec.stats["compile_cold_total"] == 1
        assert rec.stats["compile_cache_hits"] == 0
        assert rec.stats["compile_seconds"] == pytest.approx(10.0)
        # seen shape: steady dispatch, no second event
        fn(a)
        assert rec.stats["compile_total"] == 1
        assert fake.calls == 2
        (ev,) = rec.events
        assert ev["kind"] == "comb" and ev["cold"] \
            and not ev["cache_hit"]

    def test_fast_load_without_cache_write_is_a_hit(self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        fn = rec.wrap("comb", _FakeJit(clock, durations=[0.05]))
        fn(np.zeros((8,), np.int32))
        assert rec.stats["compile_cache_hits"] == 1
        assert rec.stats["compile_cold_total"] == 0

    def test_slow_load_jax_calls_a_cache_hit_is_a_hit(self, tmp_path):
        # a GB-scale program takes ~9 s to LOAD from the persistent
        # cache on a v5e: over the wall threshold, but JAX's own
        # cache-hit event says no compile happened
        import jax.monitoring
        rec, clock = self._recorder(tmp_path)
        fake = _FakeJit(clock, durations=[9.0])
        run_once = fake._run_once

        def load_from_cache():
            run_once()
            jax.monitoring.record_event(
                "/jax/compilation_cache/cache_hits")
        fake._run_once = load_from_cache
        rec.wrap("comb", fake)(np.zeros((8,), np.int32))
        assert rec.stats["compile_cache_hits"] == 1
        assert rec.stats["compile_cold_total"] == 0

    def test_cache_dir_delta_beats_the_threshold(self, tmp_path):
        # a FAST compile that still wrote a cache entry is a MISS —
        # the delta rule catches what the wall-time threshold cannot
        rec, clock = self._recorder(tmp_path)
        fake = _FakeJit(clock, durations=[0.05],
                        cache_dir=str(tmp_path), writes=[True])
        fn = rec.wrap("comb", fake)
        fn(np.zeros((8,), np.int32))
        assert rec.stats["compile_cold_total"] == 1
        assert rec.stats["compile_cache_hits"] == 0

    def test_new_shape_records_its_own_compile(self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        fn = rec.wrap("comb", _FakeJit(clock, durations=[10.0, 0.01]))
        fn(np.zeros((8,), np.int32))
        fn(np.zeros((16,), np.int32))
        assert rec.stats["compile_total"] == 2
        assert rec.stats["compile_cold_total"] == 1
        assert rec.stats["compile_cache_hits"] == 1

    def test_aot_records_and_registers_the_executable(self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        fake = _FakeJit(clock, durations=[10.0, 0.01])
        fn = rec.wrap("comb_digest", fake)
        fn.aot(np.zeros((8,), np.int32))
        assert rec.stats["compile_total"] == 1
        assert rec.stats["compile_cold_total"] == 1
        assert rec.events[0]["aot"] is True
        assert rec.events[0]["source"] == "cold"
        # the shape is served by the executable the AOT request made:
        # its first real call never reaches jit and records nothing
        assert fn(np.zeros((8,), np.int32)) == "out of the executable"
        assert (fake.calls, fake.compiled_calls) == (1, 1)
        assert rec.stats["compile_total"] == 1
        # a shape nobody asked for ahead of time goes through jit
        assert fn(np.zeros((16,), np.int32)) == "out"
        assert rec.stats["compile_total"] == 2
        assert rec.stats["compile_cache_hits"] == 1
        assert rec.events[1]["source"] == "cache"
        # no store on a recorder nobody gave one (CPU backend)
        assert rec.store is None
        assert rec.stats["executable_store_misses"] == 0

    def test_aot_failure_counts_and_leaves_the_shape_to_jit(
            self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        boom = RuntimeError("XLA died")
        fake = _FakeJit(clock, durations=[0.01], raises=boom)
        fn = rec.wrap("comb_digest", fake)
        with pytest.raises(RuntimeError):
            fn.aot(np.zeros((8,), np.int32))
        assert rec.stats["compile_failures"] == 1
        assert rec.stats["compile_total"] == 0
        fake.raises = None
        assert fn(np.zeros((8,), np.int32)) == "out"
        assert rec.stats["compile_total"] == 1

    def test_aot_cost_analysis_rides_the_event(self, tmp_path):
        rec, clock = self._recorder(tmp_path, analysis=True)
        fake = _FakeJit(clock, durations=[10.0],
                        cost={"flops": 128.0, "bytes accessed": 64.0})
        rec.wrap("comb_digest", fake).aot(np.zeros((8,), np.int32))
        assert rec.events[0]["cost"] == {"flops": 128.0,
                                         "bytes_accessed": 64.0}

    def test_failure_counts_and_propagates(self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        boom = RuntimeError("XLA died")
        fn = rec.wrap("comb", _FakeJit(clock, durations=[],
                                       raises=boom))
        with pytest.raises(RuntimeError):
            fn(np.zeros((8,), np.int32))
        assert rec.stats["compile_failures"] == 1
        assert rec.stats["compile_total"] == 0
        assert rec.events[0]["error"] is not None

    def test_cost_analysis_captured_when_available(self, tmp_path):
        rec, clock = self._recorder(tmp_path, analysis=True)
        fake = _FakeJit(clock, durations=[10.0],
                        cost={"flops": 128.0, "bytes accessed": 64.0})
        fn = rec.wrap("comb", fake)
        fn(np.zeros((8,), np.int32))
        assert rec.events[0]["cost"] == {"flops": 128.0,
                                         "bytes_accessed": 64.0}

    def test_cold_instant_and_steady_auto_dump(self, tmp_path,
                                               trace_env):
        rec, clock = self._recorder(tmp_path)
        fn = rec.wrap("comb", _FakeJit(clock, durations=[10.0, 20.0]))
        fn(np.zeros((8,), np.int32))     # cold, but NOT steady yet
        evs = [e for e in tracing.snapshot()
               if e[1] == "compile.cold"]
        assert len(evs) == 1 and evs[0][8]["steady"] is False
        assert not list(trace_env.iterdir())     # no dump pre-steady
        rec.mark_steady()
        fn(np.zeros((32,), np.int32))    # the steady-state cliff
        tracing.wait_dumps()
        dumps = [p for p in trace_env.iterdir()
                 if "cold_compile" in p.name]
        assert dumps, list(trace_env.iterdir())
        doc = json.loads(dumps[0].read_text())
        assert doc["ftpu"]["reason"] == "cold_compile"

    def test_event_history_bounded(self, tmp_path):
        rec, clock = self._recorder(tmp_path)
        for i in range(devicecost._EVENT_CAP + 40):
            fn = rec.wrap("comb", _FakeJit(clock, durations=[0.01]))
            fn(np.zeros((8,), np.int32))
        assert len(rec.events) == devicecost._EVENT_CAP


class TestProviderJitSeam:
    """The provider-side integration: TPUProvider._jit is the one
    build seam — real jax.jit on a trivial fn (milliseconds on CPU),
    counters land in provider stats, armed tpu.compile faults become
    compile_failures + error-status tpu.compile spans."""

    def _prov(self):
        from fabric_tpu.bccsp.tpu import TPUProvider
        return TPUProvider(min_batch=4, use_g16=False)

    def test_jit_seam_counts_into_provider_stats(self):
        faults.clear()
        prov = self._prov()
        fn = prov._jit("probe", lambda x: x + 1)
        out = fn(np.arange(4, dtype=np.int32))
        assert np.asarray(out).tolist() == [1, 2, 3, 4]
        assert prov.stats["compile_total"] == 1
        fn(np.arange(4, dtype=np.int32))
        assert prov.stats["compile_total"] == 1     # seen shape
        assert prov.device_cost.events[0]["kind"] == "probe"

    def test_armed_compile_fault_books_failure_and_error_span(
            self, trace_env):
        faults.clear()
        prov = self._prov()
        faults.arm("tpu.compile", mode="error", count=1)
        try:
            with pytest.raises(faults.FaultInjected):
                prov._jit("probe", lambda x: x)
            assert prov.stats["compile_failures"] == 1
            assert prov.stats["compile_total"] == 0
            spans = [e for e in tracing.snapshot()
                     if e[1] == "tpu.compile" and e[9] is not None]
            assert spans, "no error-status tpu.compile span recorded"
            # the breaker interplay: a later build (fault consumed)
            # serves normally — degrade, don't wedge
            fn = prov._jit("probe", lambda x: x * 3)
            assert np.asarray(
                fn(np.arange(3, dtype=np.int32))).tolist() == \
                [0, 3, 6]
            assert prov.stats["compile_total"] == 1
        finally:
            faults.reset()

    def test_dispatch_marks_steady(self):
        faults.clear()
        prov = self._prov()
        assert prov.device_cost.steady is False
        with prov._dispatch_span():
            pass
        assert prov.device_cost.steady is True


# ---------------------------------------------------------------------------
# busy-ratio math
# ---------------------------------------------------------------------------

class TestBusyRatio:
    def test_windowed_ratio_and_reset(self):
        clock = _Clock()
        busy = DeviceBusy(clock=clock)
        busy.note(0, 0.5)
        busy.note(1, 0.25)
        clock.advance(1.0)
        assert busy.ratios() == {0: 0.5, 1: 0.25}
        # window reset: no new busy time, a later poll reads idle
        clock.advance(2.0)
        assert busy.ratios() == {0: 0.0, 1: 0.0}

    def test_ratio_clamped_to_one(self):
        clock = _Clock()
        busy = DeviceBusy(clock=clock)
        busy.note(3, 5.0)               # overlapping dispatches
        clock.advance(1.0)
        assert busy.ratios()[3] == 1.0

    def test_nonpositive_and_totals(self):
        busy = DeviceBusy(clock=_Clock())
        busy.note(0, 0.0)
        busy.note(0, -1.0)
        busy.note(2, 0.125)
        assert busy.totals() == {2: 0.125}

    def test_shard_ready_feeds_busy(self):
        """The provider's per-chip ready readings land in the busy
        accumulator keyed by FULL-mesh index."""
        from fabric_tpu.bccsp.tpu import TPUProvider
        prov = TPUProvider(min_batch=4, use_g16=False)
        prov.device_cost.busy.note(2, 0.25)
        assert prov.device_cost.busy.totals() == {2: 0.25}


# ---------------------------------------------------------------------------
# memory gauges + healthz headroom
# ---------------------------------------------------------------------------

def _fake_rows(used=900, limit=1000, peak=950, device=0):
    return [{"device": device, "kind": "fake-hbm",
             "bytes_in_use": used, "peak_bytes_in_use": peak,
             "bytes_limit": limit}]


class TestMemoryGauges:
    def test_devicecost_gauges_render(self, monkeypatch):
        rows = _fake_rows(used=100, peak=200, limit=1000) + \
            _fake_rows(used=50, peak=60, limit=1000, device=1)
        monkeypatch.setattr(devicecost, "device_memory",
                            lambda: rows)

        class _Rec:
            class busy:                  # noqa: N801 - stub namespace
                @staticmethod
                def ratios():
                    return {0: 0.5, 1: 0.0}

        class _Csp:
            device_cost = _Rec()

        provider = metrics_mod.PrometheusProvider()
        # one deterministic tick (the poller wraps this exact
        # callable — no leaked fast thread crossing into jax for the
        # rest of the session)
        tick = profiling.devicecost_tick(provider, _Csp())
        assert tick is not None
        tick()
        text = provider.render()
        assert 'bccsp_device_mem_used_bytes{device="0"} 100' in text
        assert 'bccsp_device_mem_peak_bytes{device="0"} 200' in text
        assert 'bccsp_device_mem_limit_bytes{device="1"} 1000' in text
        assert 'bccsp_device_busy_ratio{device="0"} 0.5' in text
        assert 'bccsp_device_busy_ratio{device="1"} 0' in text

    def test_compile_gauges_render_canonically(self):
        """The compile counters ride publish_provider_stats under
        their canonical fqnames (the both-node-assemblies wiring)."""
        from fabric_tpu.bccsp.tpu import TPUProvider
        faults.clear()
        prov = TPUProvider(min_batch=4, use_g16=False)
        fn = prov._jit("probe", lambda x: x + 1)
        fn(np.arange(4, dtype=np.int32))
        provider = metrics_mod.PrometheusProvider()
        t = profiling.publish_provider_stats(provider, prov,
                                             poll_s=0.01)
        assert t is not None
        deadline = time.monotonic() + 5.0
        text = ""
        while time.monotonic() < deadline:
            text = provider.render()
            if "bccsp_compile_total 1" in text:
                break
            time.sleep(0.02)
        assert "bccsp_compile_total 1" in text
        assert "bccsp_compile_cache_hits 1" in text
        assert "bccsp_compile_seconds" in text
        assert "bccsp_compile_cold_total 0" in text

    def test_device_memory_empty_without_stats_api(self):
        # CPU devices answer memory_stats() with None — no rows, no
        # gauges, no headroom sub-state
        devicecost._mem_capable.clear()
        assert devicecost.device_memory() == []
        assert devicecost.peak_memory_bytes([]) == 0
        # the capability is learned ONCE: a stats-less fleet stops
        # crossing into the runtime on later polls
        assert devicecost._mem_capable and \
            not any(devicecost._mem_capable.values())
        import jax
        assert len(devicecost._mem_capable) == len(jax.local_devices())

    def test_poller_spawns_and_returns_thread(self, monkeypatch):
        monkeypatch.setattr(devicecost, "device_memory", lambda: [])
        provider = metrics_mod.PrometheusProvider()

        class _Csp:
            device_cost = None

        t = profiling.publish_devicecost_stats(provider, _Csp(),
                                               poll_s=60.0)
        assert t is not None and t.daemon and t.is_alive()


class TestHbmHealth:
    def test_substate_names_tightest_device(self):
        rows = _fake_rows(used=950, limit=1000) + \
            _fake_rows(used=100, limit=1000, device=1)
        sub = devicecost.hbm_substate(rows, headroom_frac=0.10)
        assert sub == "hbm_low:d0:5%free"
        assert devicecost.hbm_substate(
            rows, headroom_frac=0.01) is None
        assert devicecost.hbm_substate([], 0.5) is None

    def test_zero_limit_rows_ignored(self):
        assert devicecost.hbm_substate(
            _fake_rows(used=5, limit=0), 0.5) is None

    def test_provider_health_grows_hbm_substate(self, monkeypatch):
        from fabric_tpu.bccsp.tpu import TPUProvider
        prov = TPUProvider(min_batch=4, use_g16=False)
        assert prov.health() == "device"
        monkeypatch.setattr(devicecost, "device_memory",
                            lambda: _fake_rows(used=990, limit=1000))
        assert prov.health() == "device;hbm_low:d0:1%free"


# ---------------------------------------------------------------------------
# /debug/jax/trace hardening (busy refusal + bounded output dirs)
# ---------------------------------------------------------------------------

class TestJaxTraceHardening:
    def test_concurrent_capture_refused_immediately(self):
        assert profiling._trace_lock.acquire(blocking=False)
        try:
            t0 = time.monotonic()
            with pytest.raises(profiling.ProfilerBusyError):
                profiling.capture_jax_trace("/tmp/unused", 5.0)
            assert time.monotonic() - t0 < 1.0, \
                "busy refusal must not wait out the capture window"
        finally:
            profiling._trace_lock.release()

    def test_bounded_keeps_last_n_dirs(self, tmp_path, monkeypatch):
        import jax
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        made = [profiling.capture_jax_trace_bounded(
            seconds=0.0, parent_dir=str(tmp_path), keep=2)
            for _ in range(4)]
        left = sorted(p.name for p in tmp_path.iterdir())
        assert len(left) == 2, left
        assert os.path.basename(made[-1]) in left

    def test_busy_bounded_does_not_leak_a_dir(self, tmp_path):
        assert profiling._trace_lock.acquire(blocking=False)
        try:
            with pytest.raises(profiling.ProfilerBusyError):
                profiling.capture_jax_trace_bounded(
                    seconds=0.0, parent_dir=str(tmp_path))
        finally:
            profiling._trace_lock.release()
        assert list(tmp_path.iterdir()) == []

    def test_ops_endpoint_replies_409_when_busy(self):
        from urllib.request import urlopen
        from urllib.error import HTTPError

        from fabric_tpu.node.operations import OperationsServer
        srv = OperationsServer(address="127.0.0.1:0",
                               profile_enabled=True)
        srv.start()
        try:
            assert profiling._trace_lock.acquire(blocking=False)
            try:
                with pytest.raises(HTTPError) as exc:
                    urlopen("http://%s/debug/jax/trace?seconds=0.1"
                            % srv.address, timeout=10)
                assert exc.value.code == 409
                body = json.loads(exc.value.read())
                assert "already running" in body["Error"]
            finally:
                profiling._trace_lock.release()
        finally:
            srv.stop()
