"""The benchmark's yardstick, checked without a chip and without JAX:
the trace reduction on a small recorded trace, the work arithmetic,
the synthesiser's determinism, the readers, and that every name in
BENCHMARK.json finds its file."""

import gzip
import importlib
import json
import os
import re
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

from benchmark import identities, reference, synth, tracered, work  # noqa: E402
from benchmark.readers import spans as span_readers  # noqa: E402
from benchmark.readers import trace as trace_readers  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.run import BlockRecord, blocks_needed, percentile  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# ---- trace reduction -------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(BENCH, "fixtures",
                                "trace_cut.json.gz"), "rt") as f:
        return json.load(f)


def synthetic_trace():
    """Two hand-overs; the device runs 30 of the first 100 us and 20 of
    the second, one program each."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_fused(123)", 1020.0, 30.0], ["jit_fused(123)", 1150.0, 20.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 1020.0, 10.0], ["fusion.2", 1030.0, 20.0],
                ["fusion.1", 1150.0, 20.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.block", 1000.0, 100.0], ["verify_block", 1000.0, 5.0],
            ["validate", 1005.0, 60.0], ["provider.call", 1010.0, 45.0],
            ["ledger.commit", 1065.0, 35.0],
            ["bench.block", 1100.0, 100.0], ["validate", 1100.0, 80.0],
            ["provider.call", 1140.0, 35.0], ["ledger.commit", 1180.0, 20.0],
        ]}]}]}


def test_busy_idle_and_window_on_a_synthetic_trace():
    t = synthetic_trace()
    assert tracered.traced_window(t) == (1000.0, 1200.0)
    busy, window = tracered.busy_and_window(t)
    assert busy == pytest.approx(50e-9) and window == pytest.approx(200e-9)


def test_program_and_op_times_on_a_synthetic_trace():
    t = synthetic_trace()
    assert tracered.program_times(t) == {
        "jit_fused(123)": [2, pytest.approx(50e-9)]}
    assert tracered.op_times(t)[0] == ["fusion.1", pytest.approx(30e-9)]


def test_gaps_go_to_the_innermost_host_annotation():
    gaps = dict(tracered.idle_gaps(synthetic_trace()))
    # 1000-1020 (mid 1010: provider.call), 1050-1150 (mid 1100:
    # validate of block 2), 1170-1200 (mid 1185: ledger.commit)
    assert gaps == {"between_ops": pytest.approx(150e-9)}


def test_long_gaps_are_shared_out_over_the_host_annotations():
    t = synthetic_trace()
    for p in t["planes"]:
        for line in p["lines"]:
            for ev in line["events"]:
                ev[1] *= 1e4
                ev[2] *= 1e4
    gaps = dict(tracered.idle_gaps(t))
    # the gaps 1000-1020, 1050-1150 and 1170-1200 (x 1e4), each stretch
    # to the innermost annotation over it
    assert gaps == {"provider.call": pytest.approx(30e-5),
                    "validate.host": pytest.approx(60e-5),
                    "ledger.commit": pytest.approx(55e-5),
                    "verify_block": pytest.approx(5e-5)}


def test_a_trace_without_device_operations_reads_nothing():
    t = synthetic_trace()
    t["planes"] = t["planes"][1:]
    assert tracered.busy_and_window(t) is None
    assert tracered.program_times(t) == {}
    assert tracered.idle_gaps(t) == []


def test_union_clips_and_merges():
    assert tracered.union_intervals([(0, 5), (3, 8), (20, 30), (9, 9)],
                                    2, 25) == [[2, 8], [20, 25]]


def test_recorded_trace_busy_is_inside_its_window(recorded):
    busy, window = tracered.busy_and_window(recorded)
    assert 0 < busy < window
    progs = tracered.program_times(recorded)
    assert progs and all(c > 0 and s > 0 for c, s in progs.values())
    assert sum(s for _, s in progs.values()) <= window * 1.0001


def test_recorded_trace_gaps_and_busy_fill_the_window(recorded):
    busy, window = tracered.busy_and_window(recorded)
    idle = sum(s for _, s in tracered.idle_gaps(recorded, top=100))
    assert busy + idle == pytest.approx(window, rel=1e-6)
    labels = {k for k, _ in tracered.idle_gaps(recorded, top=100)}
    assert labels <= {"provider.call", "validate.host", "ledger.commit", "between_ops",
                      "verify_block", "await_next_block", "other"}


def test_recorded_trace_names_the_pipeline_program(recorded):
    with open(os.path.join(BENCH, "metrics",
                           "comb_device_ms_per_span.catchup.json")) as f:
        match = json.load(f)["params"]["match"]
    assert any(m in name for m in match
               for name in tracered.program_times(recorded))


# ---- the work one verify needs ---------------------------------------------

def test_bytes_per_verify_arithmetic():
    assert work.TABLE_ROWS == 32 and work.TABLE_ROW_BYTES == 64
    assert work.OPERAND_BYTES == 133
    assert work.bytes_per_verify() == 133 + 32 * 64 == 2181
    assert work.int32_macs_per_verify() == (31 * 12 + 2) * 400
    assert work.hbm_floor_seconds(1500, 819e9) == pytest.approx(
        1500 * 2181 / 819e9)


def test_peaks_table_is_published_peaks_only():
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")


# ---- the synthesiser -------------------------------------------------------

with open(os.path.join(BENCH, "traffic", "catchup-default-cut.json")) as _f:
    TRAFFIC = json.load(_f)
MIX = dict(TRAFFIC["transactions"], keys=30, tampered_share=0.1)


def small_chain(seed: int, n_blocks: int = 2, block_txs: int = 24):
    d = tempfile.mkdtemp()
    mat = identities.generate(os.path.join(d, "crypto"), seed, 3)
    plans = synth.plan_chain(seed, n_blocks, block_txs, MIX, 3)
    peers = [o.peer for o in mat.orgs]
    orderer = synth.OrdererSigner(mat.orderer, seed)
    prev = reference.header_hash(0, b"", b"\x00" * 32)
    hashes, blocks = [], []
    for p in plans:
        envs = synth.build_envelopes(("benchchannel", "smallbank", mat.client,
                                      peers, p.txs))
        b = orderer.assemble(p.number, prev, envs)
        prev = reference.header_hash(p.number, prev, bytes(b.header.data_hash))
        hashes.append(prev)
        blocks.append(b)
    return mat, plans, blocks, hashes


@pytest.fixture(scope="module")
def chain():
    return small_chain(2 ** 31 + 12345)


def test_same_seed_same_block_hashes(chain):
    again = small_chain(2 ** 31 + 12345)
    assert again[3] == chain[3]
    assert [b.SerializeToString(deterministic=True) for b in again[2]] == \
        [b.SerializeToString(deterministic=True) for b in chain[2]]


def test_another_seed_another_chain(chain):
    assert small_chain(2 ** 31 + 12346)[3] != chain[3]


def test_reference_reads_back_what_the_plan_said(chain):
    mat, plans, blocks, _ = chain
    v = reference.Verifier(mat.trust_roots)
    orgs = [o.mspid for o in mat.orgs]
    for plan, block in zip(plans, blocks):
        for p, env in zip(plan.txs, block.data.data):
            t = reference.parse_tx(bytes(env))
            assert (tuple(t.reads), tuple(t.writes)) == (p.reads, p.writes)
            want = reference.ENDORSEMENT_POLICY_FAILURE if p.tamper else 0
            assert reference.signatures_verdict(
                t, v, "benchchannel", orgs, 2) == want


def test_every_tamper_kind_occurs_and_is_refused(chain):
    mat, plans, blocks, _ = chain
    kinds = {p.tamper[0] for plan in small_chain(7, 6, 40)[1]
             for p in plan.txs if p.tamper}
    assert kinds == set(synth.TAMPER_KINDS)


def test_high_s_is_accepted_only_by_the_control():
    mat, plans, blocks, _ = small_chain(7, 6, 40)
    orgs = [o.mspid for o in mat.orgs]
    sound = reference.Verifier(mat.trust_roots)
    control = reference.Verifier(mat.trust_roots, accept_high_s=True)
    seen = 0
    for plan, block in zip(plans, blocks):
        for p, env in zip(plan.txs, block.data.data):
            if p.tamper and p.tamper[0] == "high_s":
                t = reference.parse_tx(bytes(env))
                seen += 1
                assert reference.signatures_verdict(
                    t, sound, "benchchannel", orgs, 2) == 10
                assert reference.signatures_verdict(
                    t, control, "benchchannel", orgs, 2) == 0
    assert seen


def test_model_flags_a_read_of_a_key_written_earlier_in_the_block():
    m = reference.LedgerModel()
    flags = m.commit_block(1, [
        (0, (), (("a", b"1"),)),
        (0, (("a", None),), (("a", b"2"),)),      # reads what tx 0 wrote
        (10, (), (("b", b"9"),)),                 # refused upstream
        (0, (("b", None),), (("c", b"3"),))])     # b never written: fine
    assert list(flags) == [0, 11, 10, 0]
    assert m.value("a") == b"1" and m.value("b") is None
    assert m.version("c") == (1, 3)
    assert list(m.commit_block(2, [(0, (("a", (1, 0)),), ()),
                                   (0, (("a", (1, 1)),), ())])) == [0, 11]


def test_wire_reader_refuses_truncation():
    with pytest.raises((ValueError, IndexError)):
        reference.fields(b"\x0a\x05ab")


# ---- loops and readers -----------------------------------------------------

def test_percentile_is_the_smallest_value_covering_q():
    assert percentile(range(1, 101), 0.95) == 95
    assert percentile([5.0], 0.95) == 5.0
    assert percentile([1, 2, 3, 4], 0.5) == 2


def test_blocks_needed_covers_the_window():
    assert blocks_needed({"loop": {"kind": "closed", "supply_tx_per_s": 1000}},
                         500, 10) == 21
    assert blocks_needed({"loop": {"kind": "open", "interval_ms": 250}},
                         500, 10) == 41


def test_the_shipped_supply_is_321_blocks_a_window():
    assert TRAFFIC["loop"] == {"kind": "closed", "supply_tx_per_s": 4000}
    assert blocks_needed(TRAFFIC, 500, MANIFEST["run_seconds"]) == 321
    assert "1.5 x" in TRAFFIC["why"] and "1.5 x" in run.SUPPLY_RULE


class Clock:
    """`run`'s clock, moved only by the stand-in intake."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


class SteadyIntake:
    """Every hand-over takes `block_s` on the clock it is given."""

    def __init__(self, clock, block_s):
        self.clock, self.block_s, self.handed = clock, block_s, []

    def stats(self):
        return {"comb_batches": len(self.handed)}

    def hand_over(self, block):
        self.handed.append(block.header.number)
        self.clock.now += self.block_s


def backlog(n, n_tx=500):
    from types import SimpleNamespace as NS
    return [NS(header=NS(number=23 + i), data=NS(data=[b""] * n_tx))
            for i in range(n)]


CLOSED = {"kind": "closed", "supply_tx_per_s": 4000}


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(run, "time", c)
    return c


def test_a_window_that_outruns_its_backlog_raises_window_ran_dry(clock):
    # 4 blocks of 500 at 0.2 s a block are done 0.8 s into a 1 s window
    with pytest.raises(run.WindowRanDry) as e:
        run.run_window(SteadyIntake(clock, 0.2), backlog(4), CLOSED, 1.0,
                       source="benchmark/traffic/catchup-default-cut.json")
    assert isinstance(e.value, RuntimeError)
    msg = str(e.value)
    assert "the 4 blocks (2000 txs)" in msg and "0.80 s into a 1 s" in msg
    assert "2500.0 tx/s" in msg and run.SUPPLY_RULE in msg
    assert "benchmark/traffic/catchup-default-cut.json" in msg


@pytest.mark.parametrize("n_blocks, block_s, handed", [
    (6, 0.2, 5),      # one block to spare: the fifth ends the window
    (5, 0.2, 5),      # the last of the supply ends ON the window's end
    (4, 0.3, 4),      # the last of the supply CROSSES the end: a result
])
def test_a_window_that_its_backlog_outlasts_returns_normally(
        clock, n_blocks, block_s, handed):
    intake = SteadyIntake(clock, block_s)
    records, t0, t1 = run.run_window(intake, backlog(n_blocks), CLOSED, 1.0)
    assert len(records) == len(intake.handed) == handed
    assert t1 - t0 == pytest.approx(handed * block_s) and t1 - t0 >= 1.0
    assert all(r.served and r.dispatches == 1 for r in records)
    report = run.supply_used(n_blocks, len(records), "f.json")
    assert report == {"supply_blocks": n_blocks, "window_blocks": handed,
                      "supply_used_share": handed / n_blocks}


def test_a_window_near_the_end_of_its_backlog_warns(capsys):
    run.supply_used(20, 17, "traffic/x.json")      # 0.85: not past it
    assert "WARNING" not in capsys.readouterr().err
    assert run.supply_used(20, 18, "traffic/x.json")[
        "supply_used_share"] == pytest.approx(0.9)
    err = capsys.readouterr().err
    assert "WARNING" in err and "18 of the 20 blocks" in err
    assert "traffic/x.json" in err and run.SUPPLY_RULE in err


def records():
    out = []
    for i, lanes in enumerate((1500, 1500, 30720)):
        r = BlockRecord(i + 1, lanes // 3)
        r.due = r.start = float(i)
        r.done = i + 0.9
        r.spans = {"validate": [(i + 0.0, i + 0.5)],
                   "provider.call": [(i - 0.1, i - 0.05), (i + 0.1, i + 0.3)],
                   "ledger.commit": [(i + 0.5, i + 0.9)]}
        r.lanes = [1, lanes]
        out.append(r)
    return out


def test_span_readers():
    ctx = {"records": records()}
    txs = 500 + 500 + 10240
    assert span_readers.self_ms_per_ktx(
        ctx, "validate", "provider.call") == pytest.approx(
            3 * 0.3 * 1e3 / (txs / 1000))
    assert span_readers.ms_per_ktx(ctx, "ledger.commit") == pytest.approx(
        3 * 0.4 * 1e3 / (txs / 1000))
    assert span_readers.median_ms_per_block(
        ctx, "provider.call") == pytest.approx(250.0)
    assert span_readers.generator_lag_p95_ms(ctx) == 0.0
    assert span_readers.ms_per_ktx({"records": []}, "validate") is None


class Spanned:
    def _pipeline_span(self):
        return 100


def traced_ctx(dispatches=(1, 1)):
    recs = []
    for i, d in enumerate(dispatches):
        r = BlockRecord(i + 1, 10)
        r.done, r.traced, r.dispatches, r.lanes = 1.0, True, d, [1, 30]
        recs.append(r)
    return {"records": recs, "trace": synthetic_trace(),
            "provider": Spanned(), "device_kind": "TPU v5 lite"}


def test_trace_readers_tie_the_program_to_the_providers_counters():
    ctx = traced_ctx()
    assert trace_readers.program_ms_per_execution(
        ctx, ["comb_digest", "jit_fused"]) == pytest.approx(25e-6)
    assert trace_readers.lane_occupancy(ctx, "jit_fused") == pytest.approx(
        60 / 200)
    assert trace_readers.hbm_roofline_percent(
        ctx, "jit_fused") == pytest.approx(
            100 * work.hbm_floor_seconds(60, 819e9) / 50e-9)
    assert trace_readers.program_ms_per_execution(ctx, "qtab") is None


def test_a_second_program_of_the_same_name_is_an_error_not_a_sum():
    ctx = traced_ctx()
    ctx["trace"]["planes"][0]["lines"][0]["events"][1][0] = "jit_fused(456)"
    with pytest.raises(trace_readers.AmbiguousProgram):
        trace_readers.program_ms_per_execution(ctx, "jit_fused")


def test_executions_the_counters_did_not_book_are_an_error():
    with pytest.raises(trace_readers.AmbiguousProgram):
        trace_readers.lane_occupancy(traced_ctx((1, 2)), "jit_fused")


def test_a_provider_that_names_no_span_is_an_error():
    ctx = traced_ctx()
    ctx["provider"]._pipeline_span = lambda: None
    with pytest.raises(ValueError):
        trace_readers.lane_occupancy(ctx, "jit_fused")


def test_dispatches_by_the_providers_counters():
    from benchmark.run import dispatches
    before = {"comb_batches": 3, "pipeline_batches": 1, "pipeline_chunks": 4}
    whole = {"comb_batches": 4, "pipeline_batches": 1, "pipeline_chunks": 4}
    piped = {"comb_batches": 4, "pipeline_batches": 2, "pipeline_chunks": 8}
    assert dispatches(before, whole) == 1 and dispatches(before, piped) == 4
    assert dispatches(before, before) == 0


# ---- the generator's mixes are data ----------------------------------------

def test_every_account_is_created_before_the_operations_start():
    mix = dict(MIX, keys=300)
    assert synth.preload_blocks(mix, 50) == 6
    assert synth.preload_blocks(dict(MIX, keys=301), 50) == 7
    assert synth.preload_blocks(dict(MIX, preload=None), 50) == 0
    txs = [p for plan in synth.plan_chain(11, 7, 50, mix, 3)
           for p in plan.txs]
    for i, p in enumerate(txs[:300]):
        assert p.fn == "create_account" and p.reads == ()
        assert [k for k, _ in p.writes] == [synth.key_name(i)]
        assert json.loads(p.writes[0][1])["checking_balance"] == 10 ** 6
    assert all(p.fn != "create_account" and p.reads for p in txs[300:])


def test_a_chain_of_343_blocks_is_the_mix_the_traffic_file_states():
    """The guard that a longer plan is the same traffic: the 343 blocks
    a 40 s window at 4,000 tx/s plans (20 creating + 2 warm-up + 321),
    as shipped."""
    mix = TRAFFIC["transactions"]
    pre = synth.preload_blocks(mix, 500)
    n_blocks = pre + TRAFFIC["warmup_blocks"] + blocks_needed(TRAFFIC, 500, 40)
    assert (pre, n_blocks) == (20, 343)
    plans = synth.plan_chain(2 ** 31 + 35, n_blocks, 500, mix, 3)
    assert [p.number for p in plans] == list(range(1, 344))
    created = [p.args[0] for plan in plans[:pre] for p in plan.txs]
    assert created == [synth.key_name(i) for i in range(mix["keys"])]
    assert all(p.fn == "create_account" for plan in plans[:pre]
               for p in plan.txs)
    ops = plans[pre:]
    assert not any(p.fn == "create_account" for plan in ops for p in plan.txs)
    # the mix holds from the chain's head to its tail: by fifths
    for k in range(5):
        fifth = ops[k * len(ops) // 5:(k + 1) * len(ops) // 5]
        n = 500 * len(fifth)
        assert 0.03 <= sum(p.conflicts for p in fifth) / n <= 0.07
        assert 0.005 <= sum(1 for plan in fifth for p in plan.txs
                            if p.tamper) / n <= 0.015
        fns = [p.fn for plan in fifth for p in plan.txs]
        for fn in mix["functions"]:
            assert fns.count(fn) / n == pytest.approx(0.2, abs=0.02)
    assert all(0 < p.conflicts < 50 for p in ops)


def test_smallbank_calls_read_and_write_the_accounts_they_name():
    plans = synth.plan_chain(11, 10, 50, MIX, 3)[1:]
    seen = set()
    for plan in plans:
        for p in plan.txs:
            seen.add(p.fn)
            n = 2 if p.fn in ("send_payment", "amalgamate") else 1
            assert len(p.reads) == len(p.writes) == n
            assert [k for k, _ in p.reads] == [k for k, _ in p.writes]
            assert len({k for k, _ in p.writes}) == n
            for _, value in p.writes:
                rec = json.loads(value)
                assert set(rec) == {"customer_id", "customer_name",
                                    "checking_balance", "savings_balance"}
    assert seen == set(MIX["functions"])
    assert sum(p.conflicts for p in plans) > 0


def test_amalgamate_moves_everything_and_a_payment_moves_the_amount():
    mix = dict(MIX, keys=2, tampered_share=0.0, preload=None)
    for fn, want in (("amalgamate", lambda a, b, amt: (0, 0, 3 * 10 ** 6)),
                     ("send_payment",
                      lambda a, b, amt: (10 ** 6 - amt, 10 ** 6, 10 ** 6 + amt))):
        mix["functions"] = {fn: MIX["functions"][fn]}
        p = synth.plan_chain(5, 1, 1, mix, 3)[0].txs[0]
        recs = {k: json.loads(v) for k, v in p.writes}
        src, dst = recs[p.args[0]], recs[p.args[1]]
        assert (src["checking_balance"], src["savings_balance"],
                dst["checking_balance"]) == want(src, dst, int(p.args[2]))


@pytest.mark.parametrize("dist, hot_share", [({"kind": "uniform"}, 0.01),
                                             ({"kind": "zipf", "s": 0.99}, 0.19)])
def test_key_distributions(dist, hot_share):
    mix = dict(MIX, keys=100, key_distribution=dist, tampered_share=0.0,
               preload=None)
    plans = synth.plan_chain(3, 10, 100, mix, 3)
    first = [p.args[0] for plan in plans for p in plan.txs]
    share = first.count(synth.key_name(0)) / len(first)
    assert share == pytest.approx(hot_share, abs=0.03)
    with pytest.raises(ValueError):
        synth.plan_chain(3, 1, 1, dict(mix, key_distribution={"kind": "x"}), 3)


def test_a_blind_function_reads_nothing_and_pads_its_value():
    mix = dict(MIX, tampered_share=0.0, preload=None, functions={
        "put": {"weight": 1, "blind": True,
                "updates": [[0, "checking_balance", 1]]}},
        record=dict(MIX["record"], pad_to_bytes=1000))
    for p in synth.plan_chain(9, 1, 20, mix, 3)[0].txs:
        assert p.reads == () and len(p.writes) == 1
        assert len(p.writes[0][1]) == 1000
        json.loads(p.writes[0][1])


# ---- every name finds its file ---------------------------------------------

@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader_and_reports_in_cells_that_report_what_it_moves(
        metric):
    with open(os.path.join(BENCH, "metrics", metric["name"] + ".json")) as f:
        spec = json.load(f)
    module, fn = spec["reader"].rsplit(".", 1)
    assert callable(getattr(importlib.import_module(
        "benchmark.readers." + module), fn))
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    moved = e2e[metric["moves"]]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    assert NAME.match(metric["name"]) and " " not in metric["unit"]
    if metric["name"].split(".")[0].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_configuration_and_traffic(cell):
    cfg = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert config["name"] == cfg["name"] and config["source"] == cfg["source"]
    assert config["chips"] == cell["chips"]
    assert config["reduced"] == cfg["reduced"]
    assert all(k in config for k in cfg["reduced"])
    assert traffic["loop"]["kind"] in ("closed", "open")
    assert len(cell["why"]) <= 200 and NAME.match(cell["name"])
    assert cfg["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
