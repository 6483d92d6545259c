"""The cell `big-block.catchup` (PR 38): the project's own north-star
block, 10,000 transactions cut by Fabric's `Orderer.BatchSize`, under
Smallbank on 100,000 accounts. Its files say what their source says,
count governs the cut, the traffic's MVCC conflicts are a deployment's
and not a keyspace too small for the block, the manifest lists the cell
where its readers have something to read, the reader of the gaps
between a batch's spans reads a hand-made trace exactly, and a
rehearsal of the cell reads `correct` — and not correct with the
controls in the program's place. No file of the benchmark is edited
for it: the cell is a configuration, a traffic file, a manifest entry,
its name in the lists of the metrics that are there, a metric file and
its reader.
"""

import argparse
import json
import os

import pytest

from benchmark import identities, run, synth, tracered
from benchmark.readers import pipeline, trace
from benchmark.run import BlockRecord
from fabric_tpu import native
from fabric_tpu.common import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "big-block.catchup"
SEED = 2 ** 31 + 38
GRPC_LIMIT = 100 << 20          # the message limit peer and orderer ship

needs_native = pytest.mark.skipif(
    not native.available(),
    reason="the native block-prep library cannot be built here")


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


def test_configuration_states_its_source_cut_and_guarantees(cell):
    manifest, entry, config, traffic = cell
    default = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "default-cut-2of3.json")))
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "big-block-10k-2of3", "catchup-big-block", 1)
    # everything but the block cutting is the default deployment's
    for group in ("channel", "chaincode", "bccsp", "peer", "ledger",
                  "guarantees", "chips"):
        assert config[group] == default[group], group
    batch = config["orderer"]["BatchSize"]
    assert batch["MaxMessageCount"] == 10000
    assert config["orderer"]["BatchTimeout"] == "2s"
    assert len(config["source"]) <= 200
    for word in ("BASELINE.json", "10k-tx", "configtx.yaml",
                 "MaxMessageCount 10000", "test-network"):
        assert word in config["source"], word
    assert "own target" in config["source_note"]
    assert config["reduced"] == ["ledger_preload"]
    assert config["ledger_preload"]["deployment"]
    assert config["ledger_preload"]["why_cut"]
    assumed = " ".join(config["assumed"])
    for word in ("2-of-3", "one submitting client", "PreferredMaxBytes",
                 "100,000 accounts", "49.6 %"):
        assert word in assumed, word
    assert traffic["transactions"]["keys"] == 100000
    # 30,000 signatures: 15 spans of 2,048, the last 1,328 real
    sigs = batch["MaxMessageCount"] * config["chaincode"]["signatures_per_tx"]
    assert (-(-sigs // 2048), sigs - 14 * 2048) == (15, 1328)


def test_the_traffic_is_the_default_mix_on_more_accounts(cell):
    traffic = cell[3]
    default = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "catchup-default-cut.json")))
    mix, base = traffic["transactions"], default["transactions"]
    assert {k: v for k, v in mix.items() if k not in ("keys", "assumed")} \
        == {k: v for k, v in base.items() if k not in ("keys", "assumed")}
    assert mix["assumed"][1:] == base["assumed"][1:]
    assert traffic["warmup_blocks"] == default["warmup_blocks"] == 2
    assert traffic["loop"]["kind"] == "closed"
    supply = traffic["loop"]["supply_tx_per_s"]
    assert supply % 500 == 0 and "1.5 x" in traffic["why"]
    assert synth.preload_blocks(mix, 10000) == 10


def test_count_governs_the_cut(cell, tmp_path):
    """10,000 envelopes as the harness builds them fit under
    PreferredMaxBytes, which is under AbsoluteMaxBytes, which is under
    the gRPC message limit."""
    _, _, config, traffic = cell
    batch = config["orderer"]["BatchSize"]
    material = identities.generate(str(tmp_path), SEED, 3)
    mix = dict(traffic["transactions"], keys=1000)
    plans = synth.plan_chain(SEED, 2, 1000, mix, len(material.orgs))
    envs = synth.build_envelopes((
        "benchchannel", config["chaincode"]["name"], material.client,
        [o.peer for o in material.orgs], plans[-1].txs[:64]))
    largest = max(len(e) for e in envs)
    assert 3000 < largest < 3600
    assert batch["MaxMessageCount"] * largest < batch["PreferredMaxBytes"] \
        < batch["AbsoluteMaxBytes"] <= GRPC_LIMIT


def test_the_mvcc_conflicts_are_a_deployments(cell):
    """Over 100,000 accounts 5-12 % of a 10,000-transaction block read
    an account an earlier valid transaction of the block wrote; over
    default-cut's 10,000 it would be about half."""
    _, _, config, traffic = cell
    mix = traffic["transactions"]
    block = config["orderer"]["BatchSize"]["MaxMessageCount"]
    pre = synth.preload_blocks(mix, block) + traffic["warmup_blocks"]
    plans = synth.plan_chain(SEED, pre + 6, block, mix,
                             config["channel"]["orgs"])
    share = sum(p.conflicts for p in plans[pre:]) / (6 * block)
    assert 0.05 <= share <= 0.12
    small = dict(mix, keys=10000)
    pre = synth.preload_blocks(small, block) + traffic["warmup_blocks"]
    plans = synth.plan_chain(SEED, pre + 2, block, small,
                             config["channel"]["orgs"])
    assert sum(p.conflicts for p in plans[pre:]) / (2 * block) > 0.4


def test_the_manifest_lists_the_cell_where_it_reads(cell):
    """The default cell's `.catchup` family reads this cell too, but for
    the readers that single the program out by its executions
    (`trace._program`): the parent of PR 38 books one dispatch for a
    block's 15 executions, so on the parent's side of the driver's
    traced run they raise `AmbiguousProgram` and the run prints no line.
    They can list the cell once this PR is the parent. The gap metric is
    read here. Membership only: later cells append to these lists."""
    manifest = cell[0]
    rate = [m for m in manifest["end_to_end"]
            if m["name"] == "commit_tx_per_s"][0]
    assert CELL in rate["workloads"] and rate["bound"] == 0.07
    family = [m for m in manifest["per_layer"]
              if m["name"].endswith(".catchup")
              and "default-cut.catchup" in m["workloads"]]
    assert len(family) >= 18
    assert [m["name"] for m in family if CELL not in m["workloads"]
            and _reader(m)[0] != "trace"] == []
    gap = {m["name"]: m for m in manifest["per_layer"]}[
        "comb_span_gap_ms.big"]
    assert CELL in gap.pop("workloads")
    assert gap == {"name": "comb_span_gap_ms.big", "unit": "ms",
                   "better": "lower", "source": "device_trace",
                   "layer": "provider seam", "moves": "commit_tx_per_s"}
    assert json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", "comb_span_gap_ms.big.json"))) == {
        "reader": "pipeline.span_gap_ms",
        "params": {"match": ["comb_digest", "jit_fused"]}}


# ---- the reader of the gaps between a batch's spans ------------------------

MS = 1e6                    # ns
T0 = 7000.0                 # the recorder's clock (perf_counter seconds)


@pytest.fixture()
def recorder():
    tracing.configure(enabled=True, ring_size=256, sample_every=1)
    tracing.reset()
    yield
    tracing.configure(enabled=True, ring_size=4096, sample_every=1)
    tracing.reset()


def _gap_ctx(executions, said=None):
    """Two traced blocks of 100 ms on both clocks; `executions` are
    (start ms, duration ms) on the trace's clock, where block 1 spans
    [1000, 1100) ms and block 2 [1200, 1300). The recorder holds one
    `tpu.comb_digest` a block, with the `chunks` of `said` (None: a
    program from before PR 38 names none)."""
    blocks = [(1000.0, 1100.0), (1200.0, 1300.0)]
    booked = [sum(1 for s, _ in executions if lo <= s < hi)
              for lo, hi in blocks]
    said = booked if said is None else said
    recs = []
    for k, (lo, hi) in enumerate(blocks):
        r = BlockRecord(k + 1, 10000)
        r.start, r.done = T0 + lo / 1e3, T0 + hi / 1e3
        r.traced, r.dispatches, r.lanes = True, booked[k], [30000]
        attrs = {} if said[k] is None else {"chunks": said[k]}
        tracing.observe_span("tpu.comb_digest", r.start + 0.001,
                             r.start + 0.002, **attrs)
        recs.append(r)
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules",
         "events": [["jit_comb_digest(7)", s * MS, d * MS]
                    for s, d in executions]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.block", lo * MS, (hi - lo) * MS]
            for lo, hi in blocks]}]}]}
    return {"records": recs, "trace": tr, "provider": None}


# three executions with gaps of 0.5 and 1.0 ms in block 1, one in
# block 2, one after the traced window
BATCHES = [(1010.0, 24.0), (1034.5, 24.0), (1059.5, 24.0),
           (1210.0, 24.0), (1350.0, 24.0)]
MATCH = ["comb_digest", "jit_fused"]


def test_the_gap_reader_reads_the_mean_gap_inside_blocks(recorder):
    ctx = _gap_ctx(BATCHES)
    assert tracered.program_times(ctx["trace"]) == {
        "jit_comb_digest(7)": [4, pytest.approx(0.096)]}
    assert pipeline.executions_said(ctx) == 4
    assert pipeline.span_gap_ms(ctx, MATCH) == pytest.approx(0.75)


def test_a_window_without_a_gap_reads_none(recorder):
    ctx = _gap_ctx([(1010.0, 24.0), (1210.0, 24.0)])
    assert pipeline.span_gap_ms(ctx, MATCH) is None


def test_a_program_that_names_no_spans_reads_none_not_an_error(recorder):
    """The parent of PR 38: one booking for a block of several
    executions, and no `chunks` on its spans."""
    ctx = _gap_ctx(BATCHES, said=[None, None])
    for r in ctx["records"]:
        r.dispatches = 1
    with pytest.raises(trace.AmbiguousProgram):
        trace.program_ms_per_execution(ctx, MATCH)
    assert pipeline.executions_said(ctx) is None
    assert pipeline.span_gap_ms(ctx, MATCH) is None


def _reader(metric):
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", metric["name"] + ".json")))
    return spec["reader"].rsplit(".", 1) + [spec.get("params", {})]


def test_the_cells_trace_readers_read_a_parents_trace(cell, recorder):
    """The driver runs the cell traced on the parent too, with this PR's
    manifest: every reader of the trace that the manifest lists for the
    cell gives a number or None there, never an error."""
    ctx = _gap_ctx(BATCHES, said=[None, None])
    for r in ctx["records"]:
        r.dispatches = 1
    listed = [m for m in cell[0]["per_layer"]
              if CELL in m.get("workloads", [CELL])]
    readers = [_reader(m) for m in listed]
    readers = [r for r in readers if r[0] in ("trace", "pipeline")]
    assert ["pipeline", "span_gap_ms"] in [r[:2] for r in readers]
    for module, fn, params in readers:
        getattr({"trace": trace, "pipeline": pipeline}[module], fn)(
            ctx, **params)


def test_spans_the_program_did_not_name_are_an_error(recorder):
    ctx = _gap_ctx(BATCHES, said=[2, 1])
    with pytest.raises(trace.AmbiguousProgram):
        pipeline.span_gap_ms(ctx, MATCH)


# ---- a rehearsal of the cell -----------------------------------------------

def _rehearse(cell, control=""):
    """The cell as its files give it, but for the accounts: 10,000
    instead of 100,000, because the 10 creating blocks of the full
    preload would be most of the test's time on this CPU; a window of
    one block."""
    manifest, entry, config, traffic = cell
    traffic = dict(traffic, transactions=dict(traffic["transactions"],
                                              keys=10000))
    args = argparse.Namespace(
        workload=CELL, seed=SEED, seconds=0.5, trace=0, control=control,
        rehearse=True, workers=2)
    rc, result = run.execute(manifest, entry, config, traffic, args)
    assert rc == 4, "a rehearsal is never a pass"
    return result


@needs_native
def test_a_rehearsal_of_the_cell_is_correct(cell):
    r = _rehearse(cell)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 1
    assert all(v["value"] == 0 for v in r["compared"].values())
    assert set(r["metrics"]) == {"commit_tx_per_s", "setup_s"}
    # one whole 10,000-transaction block re-verified by OpenSSL
    assert r["info"]["signatures_reverified"] >= 30000
    assert r["info"]["window_blocks"] == r["attempted"]
    assert 0 < r["info"]["supply_used_share"] <= 1


@needs_native
def test_the_controls_read_not_correct_in_the_cell(cell):
    r = _rehearse(cell, control="accept_high_s,skip_mvcc")
    assert r["correct"] is False
    for c in run.CONTROLS:
        assert r["compared"][f"control.{c}.flag_mismatches"]["value"] > 0
    # the program itself, in the same run, still reads sound
    assert r["compared"]["flag_mismatches"]["value"] == 0
