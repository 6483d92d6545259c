"""The readers over the program's own span tree
(`benchmark/readers/program.py`), on a synthetic recorder and a
synthetic trace: no chip, no peer."""

import pytest

from benchmark import tracered
from benchmark.readers import program
from benchmark.run import BlockRecord
from fabric_tpu.common import tracing

T0 = 5000.0                 # the recorder's clock (perf_counter seconds)
OFFSET_NS = 7.25e12         # what the trace's clock is ahead of it by
BLOCK_S = 0.100


@pytest.fixture()
def recorder():
    tracing.configure(enabled=True, ring_size=256, sample_every=1)
    tracing.reset()
    yield
    tracing.configure(enabled=True, ring_size=4096, sample_every=1)
    tracing.reset()


def span(name, t0, dur, parent=None, **attrs):
    return tracing.observe_span(name, t0, t0 + dur, parent=parent, **attrs)


def one_block(t, provider=True):
    """The spans of one 100 ms block handed over at `t`: 10 ms
    validation prep, the provider's host work, 30 ms of policy work, a
    20 ms wait, then a 30 ms commit of which the block store takes 12."""
    block = span("peer.block", t + 0.001, 0.098, txs=500)
    validate = span("commit.validate", t + 0.002, 0.066, parent=block)
    span("validate.prep", t + 0.002, 0.010, parent=validate)
    if provider:
        verify = span("tpu.verify", t + 0.012, 0.004, parent=validate)
        span("tpu.stage", t + 0.012, 0.001, parent=verify, lanes=1500,
             bucket=8192)
        comb = span("tpu.comb_digest", t + 0.013, 0.003, parent=verify)
        span("tpu.h2d", t + 0.013, 0.002, parent=comb, bytes=1 << 20)
        span("tpu.enqueue", t + 0.015, 0.001, parent=comb)
    span("validate.policy", t + 0.016, 0.030, parent=validate)
    flags = span("validate.flags", t + 0.046, 0.022, parent=validate)
    if provider:
        wait = span("tpu.verify", t + 0.046, 0.021, parent=flags)
        span("tpu.wait", t + 0.046, 0.020, parent=wait)
        span("tpu.readback", t + 0.066, 0.001, parent=wait, lanes=1500)
    commit = span("commit.commit", t + 0.068, 0.030, parent=block)
    span("ledger.mvcc", t + 0.068, 0.008, parent=commit)
    store = span("ledger.blockstore", t + 0.076, 0.012, parent=commit)
    span("blockstore.append", t + 0.076, 0.004, parent=store)
    span("blockstore.index", t + 0.080, 0.007, parent=store)
    span("ledger.state", t + 0.088, 0.010, parent=commit)


LEAVES = ["validate.prep", "tpu.stage", "tpu.h2d", "tpu.enqueue",
          "validate.policy", "validate.flags", "tpu.wait", "tpu.readback",
          "ledger.mvcc", "blockstore.append", "blockstore.index",
          "ledger.state"]


def context(n_blocks=3, provider=True, skew_s=None):
    """`n_blocks` traced hand-overs (and one before the profiler) with
    their spans in the recorder, and a trace whose clock runs
    OFFSET_NS ahead: the device is busy for the 20 ms of every
    `tpu.wait` and idle otherwise."""
    records, host, device = [], [], []
    for k in range(-1, n_blocks):
        t = T0 + k * BLOCK_S
        rec = BlockRecord(100 + k, 500)
        rec.start, rec.done = t, t + BLOCK_S
        rec.traced = k >= 0
        rec.spans["bench.block"] = [(t + 0.0005, t + 0.0995)]
        records.append(rec)
        one_block(t, provider)
        if k >= 0:
            skew = (skew_s or {}).get(k, 0.0)
            at = (t + skew) * 1e9 + OFFSET_NS
            host.append(["bench.block", at + 0.0005e9, 0.099e9])
            device.append(["fusion.1", at + 0.046e9, 0.020e9])
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host}]}]}
    return {"records": records, "trace": trace,
            "stats_before": {"lanes_real": 3000, "lanes_padded": 16384},
            "stats_after": {"lanes_real": 3000 + 1500 * n_blocks,
                            "lanes_padded": 16384 + 8192 * n_blocks}}


def test_span_time_per_thousand_transactions_and_lanes(recorder):
    ctx = context()
    assert program.span_ms_per_ktx(ctx, ["validate.prep"]) == \
        pytest.approx(10.0 / 0.5)
    assert program.span_ms_per_ktx(
        ctx, ["ledger.mvcc", "ledger.state"]) == pytest.approx(18.0 / 0.5)
    # lanes are the provider's own: the `tpu.stage` spans' attr
    assert program.span_ms_per_klane(
        ctx, ["tpu.stage", "tpu.h2d", "tpu.enqueue", "tpu.readback"]) == \
        pytest.approx(5.0 / 1.5)
    assert program.span_ms_per_klane(ctx, "tpu.wait") == \
        pytest.approx(20.0 / 1.5)


def test_only_the_blocks_handed_over_under_the_profiler_are_read(recorder):
    ctx = context()
    ctx["records"][0].n_tx = 10 ** 6     # the untraced one
    assert program.span_ms_per_ktx(ctx, ["validate.prep"]) == \
        pytest.approx(20.0)
    for r in ctx["records"]:
        r.traced = False
    assert program.span_ms_per_ktx(ctx, ["validate.prep"]) is None


def test_self_time_subtracts_the_children(recorder):
    ctx = context()
    # the block store's 12 ms hold 4 + 7 ms of children
    assert program.self_ms_per_ktx(ctx, "ledger.blockstore") == \
        pytest.approx(1.0 / 0.5)
    # peer.block 98 - (66 + 30); commit.validate 66 - (10 + 4 + 30 + 22);
    # commit.commit 30 - (8 + 12 + 10); tpu.verify (4 - 4) + (21 - 21)
    assert program.self_ms_per_ktx(
        ctx, ["peer.block", "commit.validate", "commit.commit",
              "tpu.verify"]) == pytest.approx((2.0 + 0.0 + 0.0 + 0.0) / 0.5)


def test_parents_of_a_tree_the_program_lacks_read_none(recorder):
    """The program before the block-intake tree records `tpu.verify`
    alone: its self time is no "intake time no leaf names"."""
    ctx = context()
    parents = ["peer.block", "tpu.verify"]
    assert program.self_ms_per_ktx(ctx, parents, needs="peer.block") == \
        pytest.approx(2.0 / 0.5)
    assert program.self_ms_per_ktx(ctx, parents, needs="no.such") is None
    assert program.self_ms_per_ktx(ctx, "tpu.verify") == \
        pytest.approx(0.0, abs=1e-6)


def test_a_run_with_no_provider_span_reads_none(recorder):
    ctx = context(provider=False)
    assert program.span_ms_per_klane(ctx, ["tpu.wait"]) is None
    assert program.span_ms_per_klane(ctx, ["validate.prep"]) is None
    assert program.span_ms_per_ktx(ctx, ["tpu.wait"]) is None
    assert program.span_ms_per_ktx(ctx, ["validate.prep"]) is not None


def test_a_recorder_that_is_off_or_empty_reads_none(recorder):
    ctx = context()
    tracing.set_enabled(False)
    try:
        assert program.span_ms_per_ktx(ctx, ["validate.prep"]) is None
        assert program.idle_unattributed_share(ctx, LEAVES) is None
    finally:
        tracing.set_enabled(True)
    tracing.reset()
    assert program.self_ms_per_ktx(ctx, "peer.block") is None


def test_a_wrapped_ring_is_an_error_not_a_number(recorder):
    ctx = context()
    # 256 later events push the traced blocks' first spans out
    for k in range(250):
        span("tick", T0 + 1.0 + k * 1e-3, 1e-4)
    assert tracing.dropped() > 0
    with pytest.raises(program.RingOverrun):
        program.span_ms_per_ktx(ctx, ["validate.prep"])


def test_a_ring_that_wrapped_before_the_traced_blocks_is_read(recorder):
    for k in range(300):
        span("tick", T0 - 10.0 + k * 1e-3, 1e-4)
    ctx = context(n_blocks=2)
    assert tracing.dropped() > 0
    assert program.span_ms_per_ktx(ctx, ["validate.prep"]) == \
        pytest.approx(20.0)


def test_counter_ratio_is_over_the_window(recorder):
    ctx = context()
    assert program.counter_ratio(ctx, "lanes_real", "lanes_padded") == \
        1500 / 8192
    assert program.counter_ratio(ctx, "lanes_real", "no_such") is None
    ctx["stats_after"]["lanes_padded"] = ctx["stats_before"]["lanes_padded"]
    assert program.counter_ratio(ctx, "lanes_real", "lanes_padded") is None


def test_the_clock_offset_is_recovered(recorder):
    ctx = context(n_blocks=5)
    assert program.clock_offset_ns(ctx) == pytest.approx(OFFSET_NS, abs=1e3)
    # 150 us on one block is inside the limit, and the median ignores it
    ctx = context(n_blocks=5, skew_s={2: 150e-6})
    assert program.clock_offset_ns(ctx) == pytest.approx(OFFSET_NS, abs=1e3)


def test_one_block_a_millisecond_off_is_an_error(recorder):
    ctx = context(n_blocks=5, skew_s={3: 1e-3})
    with pytest.raises(program.ClockMismatch, match="off the median"):
        program.idle_unattributed_share(ctx, LEAVES)


def test_blocks_that_do_not_pair_up_are_an_error(recorder):
    ctx = context(n_blocks=3)
    del ctx["trace"]["planes"][1]["lines"][0]["events"][0]
    with pytest.raises(program.ClockMismatch, match="annotations"):
        program.idle_unattributed_share(ctx, LEAVES)


def test_idle_no_leaf_covers(recorder):
    ctx = context(n_blocks=3)
    # a traced window of 3 x 100 ms less the bench.block margins (0.5 ms
    # at either end), the device busy 3 x 20 ms. Per block the leaves
    # leave uncovered: 2 ms before validate.prep, 1 ms of the block
    # store's own, 2 ms after ledger.state; tpu.wait lies under the
    # busy stretch
    idle = 299.0 - 60.0
    uncovered = 3 * (2.0 + 1.0 + 2.0) - 2 * 0.5
    assert program.idle_unattributed_share(ctx, LEAVES) == \
        pytest.approx(uncovered / idle, rel=1e-6)
    # fewer leaves, more idle time with no name
    fewer = [n for n in LEAVES if n != "validate.policy"]
    assert program.idle_unattributed_share(ctx, fewer) == \
        pytest.approx((uncovered + 3 * 30.0) / idle, rel=1e-6)
    # the same walk as tracered.idle_gaps
    assert sum(s for _, s in tracered.idle_gaps(ctx["trace"])) == \
        pytest.approx(idle / 1e3)


def test_a_trace_without_a_device_reads_none(recorder):
    ctx = context()
    ctx["trace"]["planes"] = ctx["trace"]["planes"][1:]
    assert program.idle_unattributed_share(ctx, LEAVES) is None
    ctx["trace"] = None
    assert program.idle_unattributed_share(ctx, LEAVES) is None
