"""Round-14 lifecycle tracing: context propagation, the flight
recorder ring, Chrome-trace export, stage histograms, dump triggers
and the disabled-mode fast path (fabric_tpu/common/tracing.py).

The chaos gate (`tools/chaos_check.sh tracing`) re-runs this file
with tpu.dispatch / order.propose / tpu.device_lost armed via env —
armed faults must surface as error-status spans and parseable dumps,
never as broken tests.
"""

import json
import os
import threading
import time

import pytest

from fabric_tpu.common import faults, tracing


@pytest.fixture()
def trace_env(tmp_path):
    """Isolated recorder: small ring, instant dumps into tmp_path;
    restores the process defaults afterwards."""
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


def _events(name=None):
    evs = tracing.snapshot()
    return [e for e in evs if name is None or e[1] == name]


class TestContextPropagation:
    def test_nested_spans_share_trace_and_link_parent(self, trace_env):
        with tracing.span("order.window") as outer:
            with tracing.span("order.propose") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.span_id != outer.span_id
        ev = _events("order.propose")[0]
        # (ph, name, trace, span, parent, t0, dur, tname, attrs, err)
        assert ev[2] == outer.trace_id
        assert ev[4] == outer.span_id

    def test_ambient_is_thread_local_and_restored(self, trace_env):
        assert tracing.capture() is None
        with tracing.span("a") as ctx:
            assert tracing.capture() is ctx
        assert tracing.capture() is None

    def test_capture_attach_crosses_threads(self, trace_env):
        got = {}

        def worker(ctx):
            with tracing.attached(ctx):
                with tracing.span("commit.validate") as c:
                    got["trace"] = c.trace_id

        with tracing.span("ingress.batch") as ctx:
            handoff = tracing.capture()
        t = threading.Thread(target=worker, args=(handoff,))
        t.start()
        t.join()
        assert got["trace"] == ctx.trace_id
        assert sorted(tracing.trace_stages(ctx.trace_id)) == [
            "commit.validate", "ingress.batch"]

    def test_explicit_parent_beats_ambient(self, trace_env):
        root = tracing.new_context()
        with tracing.span("a"):
            with tracing.span("b", parent=root) as c:
                assert c.trace_id == root.trace_id

    def test_attached_none_is_passthrough(self, trace_env):
        with tracing.span("a") as ctx:
            with tracing.attached(None):
                assert tracing.capture() is ctx

    def test_observe_span_inherits_parent(self, trace_env):
        root = tracing.new_context()
        t0 = time.perf_counter()
        ctx = tracing.observe_span("order.consensus", t0, t0 + 0.25,
                                   parent=root, block=7)
        assert ctx.trace_id == root.trace_id
        ev = _events("order.consensus")[0]
        assert ev[6] == pytest.approx(0.25, abs=1e-6)
        assert ev[9] is None and ev[8] == {"block": 7}


class TestRing:
    def test_ring_bounds_and_drop_oldest(self, trace_env):
        tracing.configure(ring_size=8)
        for i in range(20):
            with tracing.span(f"s{i}"):
                pass
        names = [e[1] for e in tracing.snapshot()]
        assert names == [f"s{i}" for i in range(12, 20)]

    def test_ring_is_preallocated(self, trace_env):
        tracing.configure(ring_size=16)
        assert len(tracing._state.ring) == 16
        with tracing.span("one"):
            pass
        assert len(tracing._state.ring) == 16

    def test_sampling_thins_spans_but_not_errors(self, trace_env):
        tracing.configure(sample_every=4)
        try:
            for i in range(8):
                with tracing.span("sampled"):
                    pass
            assert len(_events("sampled")) == 2
            with pytest.raises(RuntimeError):
                with tracing.span("boom"):
                    raise RuntimeError("x")
            # error spans always record, whatever the sampling phase
            assert len(_events("boom")) == 1
        finally:
            tracing.configure(sample_every=1)

    def test_instants_always_record(self, trace_env):
        tracing.configure(sample_every=1000)
        try:
            tracing.instant("device.quarantine", device=3)
            assert len(_events("device.quarantine")) == 1
        finally:
            tracing.configure(sample_every=1)


class TestChromeTraceSchema:
    def test_export_round_trips_and_carries_correlation(self,
                                                       trace_env):
        with tracing.span("order.window", envelopes=5) as ctx:
            with tracing.span("order.propose"):
                pass
        tracing.instant("breaker.trip", breaker="bccsp.tpu")
        doc = json.loads(json.dumps(tracing.chrome_trace()))
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        spans = {e["name"]: e for e in evs if e["ph"] == "X"}
        inst = [e for e in evs if e["ph"] == "i"]
        # tid = pipeline stage, named via thread_name metadata
        tid_names = {e["args"]["name"] for e in meta
                     if e["name"] == "thread_name"}
        assert {"stage:order", "stage:breaker"} <= tid_names
        w = spans["order.window"]
        assert w["args"]["trace_id"] == ctx.trace_id
        assert w["args"]["envelopes"] == 5
        assert w["dur"] >= 0 and "ts" in w and "pid" in w
        p = spans["order.propose"]
        assert p["args"]["parent_span_id"] == ctx.span_id
        assert inst and inst[0]["args"]["breaker"] == "bccsp.tpu"
        assert spans["order.window"]["tid"] == p["tid"]

    def test_error_status_stamped_from_exception(self, trace_env):
        with pytest.raises(ValueError):
            with tracing.span("tpu.verify"):
                raise ValueError("device gone")
        ev = _events("tpu.verify")[0]
        assert ev[9] == "ValueError: device gone"
        doc = tracing.chrome_trace()
        args = [e for e in doc["traceEvents"]
                if e.get("name") == "tpu.verify"][0]["args"]
        assert args["error"] == "ValueError: device gone"

    def test_attrs_formatted_only_at_export(self, trace_env):
        class Lazy:
            formatted = 0

            def __str__(self):
                Lazy.formatted += 1
                return "lazy!"

        with tracing.span("a", obj=Lazy()):
            pass
        assert Lazy.formatted == 0          # stored raw on the span
        doc = tracing.chrome_trace()
        assert Lazy.formatted == 1          # formatted at export
        ev = [e for e in doc["traceEvents"] if e.get("name") == "a"][0]
        assert ev["args"]["obj"] == "lazy!"


class TestStageHistograms:
    def test_quantiles_over_known_data(self, trace_env):
        for ms in range(1, 101):
            tracing.observe_stage("bccsp.admission.wait", ms / 1000.0)
        q = tracing.stage_quantiles()["bccsp.admission.wait"]
        assert q["count"] == 100
        assert q["p50_s"] == pytest.approx(0.050, abs=0.002)
        assert q["p99_s"] == pytest.approx(0.100, abs=0.002)
        assert q["mean_s"] == pytest.approx(0.0505, abs=0.001)

    def test_span_exit_observes_its_stage(self, trace_env):
        with tracing.span("order.write"):
            pass
        assert tracing.stage_quantile("order.write", "count") == 1

    def test_bound_provider_histogram_renders(self, trace_env):
        from fabric_tpu.common import metrics as metrics_mod
        provider = metrics_mod.PrometheusProvider()
        tracing.bind_metrics(provider)
        try:
            with tracing.span("commit.commit"):
                pass
            tracing.observe_stage("device.transfer.d3", 0.002)
            text = provider.render()
            assert 'trace_stage_seconds_bucket{stage="commit.commit"' \
                in text
            assert 'stage="device.transfer.d3"' in text
            assert 'trace_stage_seconds_count{stage="commit.commit"}' \
                ' 1' in text
        finally:
            tracing._state.hist = None


class TestDumpTriggers:
    def test_breaker_trip_dumps_flight_recorder(self, trace_env):
        from fabric_tpu.common import breaker as breaker_mod
        with tracing.span("tpu.verify"):
            pass
        br = breaker_mod.CircuitBreaker(
            breaker_mod.BreakerConfig(trip_threshold=1),
            name="bccsp.tpu.test")
        br.failure(RuntimeError("dead device"))
        tracing.wait_dumps()
        dumps = [f for f in os.listdir(trace_env)
                 if "breaker_trip" in f]
        assert dumps, os.listdir(trace_env)
        doc = json.load(open(os.path.join(trace_env, dumps[0])))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "breaker.trip" in names and "tpu.verify" in names
        assert doc["ftpu"]["reason"] == "breaker_trip"

    def test_quarantine_dumps_and_readmit_marks(self, trace_env):
        from fabric_tpu.common import devicehealth as dh_mod
        dh = dh_mod.DeviceHealth(4, dh_mod.DeviceHealthConfig(
            trip_threshold=1, cooldown_s=0.0))
        dh.record_fault(2, RuntimeError("chip 2 gone"))
        tracing.wait_dumps()
        assert [f for f in os.listdir(trace_env)
                if "device_quarantine" in f]
        assert _events("device.quarantine")[0][8] == {"device": 2}
        for d in dh.probe_candidates():
            dh.probe_result(d, True)
        assert _events("device.readmit")

    def test_shed_burst_dumps_once(self, trace_env):
        tracing.configure(shed_burst=5)
        for _ in range(12):
            tracing.note_shed("raft.events.test")
        tracing.wait_dumps()
        dumps = [f for f in os.listdir(trace_env)
                 if "shed_burst" in f]
        assert len(dumps) >= 1
        assert len(_events("overload.shed")) == 12

    def test_auto_dump_rate_limited(self, trace_env):
        tracing.configure(dump_min_interval_s=3600.0)
        try:
            first = tracing.auto_dump("breaker_trip")
            second = tracing.auto_dump("breaker_trip")
            assert first is not None and second is None
        finally:
            tracing.configure(dump_min_interval_s=0.0)

    def test_dump_carries_stage_quantiles(self, trace_env):
        with tracing.span("order.propose"):
            pass
        path = tracing.dump("manual")
        doc = json.load(open(path))
        assert "order.propose" in doc["ftpu"]["stage_quantiles"]


class TestDisabledMode:
    def test_disabled_span_is_shared_noop(self, trace_env):
        tracing.set_enabled(False)
        try:
            # zero-allocation: every disabled span() is the SAME object
            assert tracing.span("a") is tracing.span("b")
            with tracing.span("a") as ctx:
                assert ctx is None
            tracing.instant("x")
            tracing.observe_stage("y", 1.0)
            tracing.note_shed("z")
            assert tracing.snapshot() == []
            assert tracing.stage_quantiles() == {}
        finally:
            tracing.set_enabled(True)

    def test_traced_decorator_disabled_calls_through(self, trace_env):
        calls = []

        @tracing.traced("tpu.dispatch")
        def fn(x):
            calls.append(x)
            return x * 2

        tracing.set_enabled(False)
        try:
            assert fn(3) == 6
            assert tracing.snapshot() == []
        finally:
            tracing.set_enabled(True)
        assert fn(4) == 8
        assert _events("tpu.dispatch")

    def test_reenable_records_again(self, trace_env):
        tracing.set_enabled(False)
        tracing.set_enabled(True)
        with tracing.span("back"):
            pass
        assert _events("back")


class TestDebugTraceEndpoint:
    def test_served_without_profile_enabled(self, trace_env):
        import urllib.request

        from fabric_tpu.node.operations import OperationsServer
        with tracing.span("ingress.batch"):
            pass
        srv = OperationsServer()       # profile_enabled=False
        srv.start()
        try:
            with urllib.request.urlopen(
                    f"http://{srv.address}/debug/trace",
                    timeout=30) as r:
                assert r.status == 200
                doc = json.loads(r.read())
            names = {e["name"] for e in doc["traceEvents"]}
            assert "ingress.batch" in names
        finally:
            srv.stop()


@pytest.mark.chaos
class TestChaosTracing:
    """Armed faults must land in the recorder as error-status spans
    and a parseable postmortem — the attribution evidence the chaos
    machinery itself never had."""

    def test_armed_dispatch_fault_stamps_error_span(self, trace_env):
        faults.clear()
        faults.arm("tpu.dispatch", mode="error", count=1)
        try:
            with pytest.raises(faults.FaultInjected):
                with tracing.span("tpu.dispatch"):
                    faults.check("tpu.dispatch")
        finally:
            faults.reset()
        ev = _events("tpu.dispatch")[0]
        assert ev[9] and "FaultInjected" in ev[9]
        # the export of an armed-fault run still round-trips
        doc = json.loads(json.dumps(tracing.chrome_trace()))
        errs = [e for e in doc["traceEvents"]
                if e.get("args", {}).get("error")]
        assert errs

    def test_order_pipeline_trace_links_lifecycle(self, trace_env,
                                                  tmp_path):
        """A real (tiny) ordered stream: whatever faults the chaos
        gate armed, one probe transaction's trace must link
        ingress -> order -> write -> validate -> commit, and the
        dumped file must parse."""
        import bench_pipeline
        out = bench_pipeline.order_pipeline_run(
            ntxs=24, window=8, block_txs=8,
            trace_path=str(tmp_path / "trace.json"))
        assert out["probe_trace_id"]
        linked = set((out["trace_linked_stages"] or "").split(","))
        for stage in ("ingress.batch", "order.window", "order.write",
                      "commit.validate", "commit.commit"):
            assert stage in linked, sorted(linked)
        doc = json.load(open(out["trace_file"]))
        assert doc["traceEvents"]
        for f in ("order_propose_p50_s", "order_write_p99_s",
                  "validate_p50_s", "commit_p99_s"):
            assert out[f] and out[f] > 0, (f, out[f])


# ---------------------------------------------------------------------------
# the block-intake span tree (PR 27): peer -> validation -> provider ->
# ledger, the same names at every pipeline depth, a fixed number of
# spans a block
# ---------------------------------------------------------------------------

# span -> its parent span, at Depth 0 (docs/metrics_reference.md,
# "Block-intake spans"; the stand-in provider opens no tpu.*)
INTAKE_TREE = {
    "peer.block": "test.deliver",
    "peer.verify_block": "test.deliver",
    "commit.validate": "peer.block",
    "validate.prep": "commit.validate",
    "validate.policy": "commit.validate",
    "validate.flags": "commit.validate",
    "intake.rwsets": "peer.block",
    "intake.txids": "peer.block",
    "commit.commit": "peer.block",
    "commit.pvt": "commit.commit",
    "ledger.mvcc": "commit.commit",
    "ledger.blockstore": "commit.commit",
    "blockstore.append": "ledger.blockstore",
    "blockstore.index": "ledger.blockstore",
    "ledger.settle": "ledger.blockstore",
    "ledger.history": "commit.commit",
    "ledger.state": "commit.commit",
    "commit.notify": "commit.commit",
}
# every commit of the ledger's store (`KVStore._writing`): one
# `kvdb.write` under each span that commits a keyspace, each with its
# `kvdb.commit`
STORE_TREE = {"kvdb.write": {"blockstore.index", "ledger.history",
                             "ledger.state"},
              "kvdb.commit": {"kvdb.write"}}
# Depth > 0: stage A verifies, scans and parses inside its own span
PIPELINED_TREE = dict(INTAKE_TREE, **{
    "peer.verify_block": "commit.validate",
    "intake.rwsets": "commit.validate",
    "intake.txids": "commit.validate"})

PROVIDER_SPANS = ("tpu.verify", "tpu.stage", "tpu.comb_digest",
                  "tpu.tables", "tpu.h2d", "tpu.enqueue", "tpu.wait",
                  "tpu.readback")


def _intake_run(monkeypatch, block_txs: int, depth: int = 0,
                enabled: bool = True):
    """A tiny chain through a real peer (the benchmark's harness with
    its stand-in provider), every hand-over under one `test.deliver`
    span. Returns the recorder's events."""
    pytest.importorskip("jax")
    from fabric_tpu import native
    if not native.available():
        pytest.skip("the native block-prep library cannot be built here")
    import argparse

    from benchmark import run, sut
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = manifest["workloads"][0]
    with open(os.path.join(root, manifest["configs"][0]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    config["orderer"]["BatchSize"]["MaxMessageCount"] = block_txs
    config["peer"]["CommitPipelineDepth"] = depth
    # nine blocks on supply, twice what 0.02 s hold at either size
    traffic["loop"] = {"kind": "closed",
                       "supply_tx_per_s": 400 * block_txs}
    traffic["warmup_blocks"] = 1
    traffic["transactions"].update(keys=4 * block_txs, tampered_share=0.1)

    def hand_over(self, block):
        with tracing.span("test.deliver"):
            if depth:
                pipeline = self.channel.commit_pipeline
                pipeline.submit(block.header.number, block=block)
                pipeline.drain(timeout=60)
            else:
                self.mcs.verify_block(sut.CHANNEL, block.header.number,
                                      block)
                self.channel.process_block(block)
    monkeypatch.setattr(sut.Intake, "hand_over", hand_over)
    tracing.configure(enabled=enabled, ring_size=8192, sample_every=1)
    tracing.reset()
    try:
        args = argparse.Namespace(
            workload=cell["name"], seed=2 ** 31 + 27, seconds=0.02,
            trace=0, control="", rehearse=True, workers=0)
        rc, result = run.execute(manifest, cell, config, traffic, args)
        assert rc == 4 and result["correct"] is True
        return tracing.snapshot()
    finally:
        tracing.configure(enabled=True, ring_size=4096)
        tracing.reset()


def _trees(events):
    """[{span name: [parent name, ...]}] per `test.deliver` root that
    held an endorser block, from the recorder's own parent ids."""
    by_id = {e[3]: e for e in events}
    out = {}
    for e in events:
        if e[0] != "X":
            continue
        parent = by_id.get(e[4])
        out.setdefault(e[2], {}).setdefault(e[1], []).append(
            parent[1] if parent is not None else None)
    return [t for t in out.values()
            if "test.deliver" in t and "intake.rwsets" in t]


class TestBlockIntakeTree:
    @pytest.mark.parametrize("depth, want", [(0, INTAKE_TREE),
                                             (1, PIPELINED_TREE)])
    def test_one_tree_per_block_same_names_at_every_depth(
            self, monkeypatch, depth, want):
        trees = _trees(_intake_run(monkeypatch, 8, depth=depth))
        assert len(trees) >= 3
        for tree in trees:
            # every span of the block hangs under the one trace, each
            # below the parent the registry names
            got = {name: set(parents) for name, parents in tree.items()
                   if name not in ("test.deliver", "runtime.gc")}
            assert got == dict({k: {v} for k, v in want.items()},
                               **STORE_TREE)

    def test_spans_per_block_do_not_grow_with_its_transactions(
            self, monkeypatch):
        def per_block(block_txs):
            trees = _trees(_intake_run(monkeypatch, block_txs))
            counts = {sum(len(p) for n, p in t.items()
                          if n != "runtime.gc") for t in trees}
            assert len(counts) == 1, counts
            return counts.pop()
        small, large = per_block(8), per_block(64)
        commits = len(STORE_TREE["kvdb.write"])
        assert small == large == len(INTAKE_TREE) + 1 + 2 * commits

    def test_disabled_intake_records_nothing(self, monkeypatch):
        assert _intake_run(monkeypatch, 8, enabled=False) == []

    def test_no_program_span_shadows_a_benchmark_annotation(self):
        """Both land in the same profiler trace: a shared name would
        put the program's span into the benchmark's own metrics."""
        from benchmark import tracered
        names = set(INTAKE_TREE) | set(STORE_TREE) | \
            set(PROVIDER_SPANS) | {"runtime.gc"}
        assert not names & set(tracered.HOST_SPANS)


class TestProviderSpans:
    """The prepared-block path with the device math stubbed (the
    tests/test_chaos.py idiom): real staging, transfer and readback."""

    @staticmethod
    def _provider(monkeypatch, **kw):
        import numpy as np

        from fabric_tpu.bccsp.tpu import TPUProvider
        tpu = TPUProvider(min_batch=4, use_g16=False, **kw)
        # a pool of a few hundred bytes: real slots, real pool writes
        monkeypatch.setattr(tpu, "_slab_rows", lambda: 8)
        monkeypatch.setattr(
            tpu, "_qtab_fn",
            lambda: lambda qx, qy: np.zeros((8, 3, 20), dtype=np.int32))
        monkeypatch.setattr(
            tpu, "_comb_pipeline_digest",
            lambda: lambda key_idx, q_flat, g16, r8, rpn8,
            w8, premask, digests: premask)
        return tpu

    @staticmethod
    def _prepared(n):
        import numpy as np

        from fabric_tpu.bccsp.sw import SWProvider
        from fabric_tpu.bccsp import bccsp as api
        key = SWProvider().key_gen(api.ECDSAKeyGenOpts(
            ephemeral=True))
        z = np.zeros((n, 32), dtype=np.uint8)
        der_ok = np.ones(n, dtype=bool)
        der_ok[1] = False
        return (z, z, z, z, der_ok, np.zeros(n, dtype=np.int32),
                [key], lambda i: b"")

    def test_one_provider_call_opens_the_registered_spans(
            self, trace_env, monkeypatch):
        pytest.importorskip("jax")
        tpu = self._provider(monkeypatch)
        for n in (8, 24):
            tracing.reset()
            before = dict(tpu.stats)
            with tracing.span("commit.validate") as root:
                resolve = tpu.verify_prepared_start(*self._prepared(n))
                with tracing.span("validate.flags"):
                    out = resolve()
            assert out == [i != 1 for i in range(n)]
            # (the pool write's first use is a compile: the compile
            # seam's own spans, tests/test_devicecost.py)
            evs = [e for e in tracing.snapshot()
                   if e[2] == root.trace_id and e[1] != "tpu.compile"]
            by_id = {e[3]: e[1] for e in evs}
            parents = {}
            for e in evs:
                parents.setdefault(e[1], set()).add(by_id.get(e[4]))
            # the same spans whatever the batch holds, and one
            # `tpu.table_build` a key it brings into the pool (each
            # batch here signs with a key of its own)
            assert sorted(e[1] for e in evs) == sorted(
                ["commit.validate", "validate.flags", "tpu.verify",
                 "tpu.verify", "tpu.stage", "tpu.comb_digest",
                 "tpu.tables", "tpu.table_build", "tpu.h2d",
                 "tpu.enqueue", "tpu.wait", "tpu.readback",
                 "tpu.readback"])
            assert parents["tpu.table_build"] == {"tpu.tables"}
            tables = _events("tpu.tables")[0][8]
            assert (tables["keys"], tables["hits"], tables["built"],
                    tables["evicted"]) == (1, 0, 1, 0)
            build = _events("tpu.table_build")[0][8]
            assert build["source"] == "build" and build["bytes"] > 0
            assert parents["tpu.stage"] == {"tpu.verify"}
            assert parents["tpu.comb_digest"] == {"tpu.verify"}
            for name in ("tpu.tables", "tpu.h2d", "tpu.enqueue"):
                assert parents[name] == {"tpu.comb_digest"}
            assert parents["tpu.wait"] == {"tpu.verify"}
            assert parents["tpu.verify"] == {"commit.validate",
                                             "validate.flags"}
            stage = _events("tpu.stage")[0][8]
            bucket = stage["bucket"]
            assert stage == {"lanes": n, "bucket": bucket, "keys": 1}
            h2d = _events("tpu.h2d")[0][8]
            assert h2d["chunk"] == 0 and h2d["bytes"] > 0
            # the counters the spans' attrs are booked beside
            assert tpu.stats["lanes_real"] - before["lanes_real"] == n
            assert tpu.stats["lanes_padded"] - before["lanes_padded"] \
                == bucket
            assert tpu.stats["h2d_bytes"] - before["h2d_bytes"] \
                == h2d["bytes"]
            # one set of clock reads feeds the span and the gauge
            assert tpu.stats["prepared_transfer_s"] == round(
                _events("tpu.h2d")[0][6], 6)

    def test_the_gauges_read_the_same_with_tracing_off(
            self, trace_env, monkeypatch):
        pytest.importorskip("jax")
        tpu = self._provider(monkeypatch)
        tracing.set_enabled(False)
        try:
            assert tpu.verify_prepared(*self._prepared(8)) == \
                [i != 1 for i in range(8)]
        finally:
            tracing.set_enabled(True)
        assert tracing.snapshot() == []
        assert tpu.stats["prepared_transfer_s"] > 0
        assert tpu.stats["prepared_device_s"] > 0
        assert tpu.stats["lanes_real"] == 8

    def test_jit_names_the_program_after_its_kind(self, monkeypatch):
        pytest.importorskip("jax")
        import jax.numpy as jnp

        from fabric_tpu.bccsp.tpu import TPUProvider
        tpu = TPUProvider(min_batch=4, use_g16=False)

        def fused(x, n):
            return x * n
        fn = tpu._jit("comb_digest", fused, static_argnums=1)
        assert int(fn(jnp.int32(3), 2)) == 6
        assert "jit_comb_digest" in fn._fn.lower(
            jnp.int32(3), 2).as_text()[:200]
        assert fused.__name__ == "fused"     # the caller's is untouched


class TestSpanReadings:
    def test_timed_hands_back_its_own_readings(self, trace_env):
        t = tracing.timed("ledger.history", rows=3)
        with t:
            time.sleep(0.002)
        ev = _events("ledger.history")[0]
        assert (ev[5], ev[6]) == (t.t0, t.seconds)
        assert t.seconds == t.t1 - t.t0 >= 0.002

    def test_timed_reads_the_clock_with_tracing_off(self, trace_env):
        tracing.set_enabled(False)
        try:
            t = tracing.timed("ledger.history")
            with t:
                time.sleep(0.002)
            t.set(rows=1)
            assert t.seconds >= 0.002
            assert not isinstance(t, type(tracing.span("x"))) and \
                not hasattr(t, "ctx")       # no span was built
            assert tracing.snapshot() == []
        finally:
            tracing.set_enabled(True)

    def test_set_adds_counts_known_afterwards(self, trace_env):
        sp = tracing.span("validate.prep", txs=5)
        with sp:
            sp.set(lanes=15)
        assert _events("validate.prep")[0][8] == {"txs": 5, "lanes": 15}

    def test_dropped_counts_what_the_ring_overwrote(self, trace_env):
        assert tracing.dropped() == 0
        for i in range(300):
            tracing.instant("tick", i=i)
        assert tracing.dropped() == 300 - 256
        tracing.reset()
        assert tracing.dropped() == 0

    def test_a_context_allocated_ahead_parents_its_children(
            self, trace_env):
        with tracing.span("deliver") as outer:
            ahead = tracing.child_context()
        with tracing.span("commit.validate", parent=ahead):
            pass
        tracing.observe_span("peer.block", 1.0, 2.0, parent=outer,
                             ctx=ahead, block=7)
        block = _events("peer.block")[0]
        assert (block[2], block[3], block[4]) == (
            outer.trace_id, ahead.span_id, outer.span_id)
        assert _events("commit.validate")[0][4] == ahead.span_id

    def test_full_collections_are_spans_young_ones_are_not(
            self, trace_env):
        import gc
        with tracing.span("peer.block") as ctx:
            gc.collect(0)
            gc.collect(1)
            gc.collect()
        evs = _events("runtime.gc")
        assert len(evs) == 1
        assert evs[0][4] == ctx.span_id and "collected" in evs[0][8]
        tracing.set_enabled(False)
        try:
            assert tracing._gc_hook not in gc.callbacks
        finally:
            tracing.set_enabled(True)
        assert gc.callbacks.count(tracing._gc_hook) == 1

    def test_spans_reach_the_profiler_once_jax_is_imported(
            self, trace_env, tmp_path):
        """A capture (`/debug/jax/trace`, or the benchmark's) shows the
        program's spans on a host line, by name."""
        jax = pytest.importorskip("jax")
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.span("peer.block", block=1):
                with tracing.span("ledger.state"):
                    time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        import glob
        path = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
        data = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for plane in data.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert {"peer.block", "ledger.state"} <= names
