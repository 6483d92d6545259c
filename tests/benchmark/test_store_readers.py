"""The readers of the store's commits (`benchmark/readers/store.py`) and
the two program readers the store's metrics name, on a synthetic
recorder: no chip, no peer."""

import json
import os

import pytest

from benchmark.readers import program, store
from benchmark.run import BlockRecord
from fabric_tpu.common import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
T0 = 6000.0                 # the recorder's clock (perf_counter seconds)
BLOCK_S = 0.100
PARENTS = ("blockstore.index", "ledger.history", "ledger.state")


@pytest.fixture()
def recorder():
    tracing.configure(enabled=True, ring_size=256, sample_every=1)
    tracing.reset()
    yield
    tracing.configure(enabled=True, ring_size=4096, sample_every=1)
    tracing.reset()


def span(name, t0, dur, parent=None, **attrs):
    return tracing.observe_span(name, t0, t0 + dur, parent=parent, **attrs)


def one_block(t, frames):
    """A 500-tx block's commit: under each of the three spans that
    commit a keyspace (10 ms each) a `kvdb.write` of 6 ms whose
    `kvdb.commit` takes 4, booking `frames[i]`; None books none."""
    commit = span("commit.commit", t + 0.060, 0.035)
    for i, name in enumerate(PARENTS):
        at = t + 0.062 + i * 0.010
        parent = span(name, at, 0.010, parent=commit)
        if frames is None:
            continue
        booked = {} if frames[i] is None else {"frames": frames[i]}
        write = span("kvdb.write", at + 0.002, 0.006, parent=parent,
                     ops=100, **booked)
        span("kvdb.commit", at + 0.004, 0.004, parent=write)


def context(frames, n_blocks=2):
    records = []
    for k in range(-1, n_blocks):
        t = T0 + k * BLOCK_S
        rec = BlockRecord(100 + k, 500)
        rec.start, rec.done = t, t + BLOCK_S
        rec.traced = k >= 0
        records.append(rec)
        one_block(t, frames)
    return {"records": records}


def metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    module, fn = spec["reader"].rsplit(".", 1)
    return getattr({"store": store, "program": program}[module], fn), \
        spec.get("params", {})


def test_attr_per_ktx_sums_the_traced_blocks(recorder):
    # the block before the profiler is not read: 2 blocks of 500 tx
    ctx = context([300, 500, 200])
    assert store.attr_per_ktx(ctx, "kvdb.write", "frames") == \
        pytest.approx(2 * 1000 / 1.0)
    assert store.attr_per_ktx(ctx, "kvdb.write", "ops") == \
        pytest.approx(2 * 300 / 1.0)
    # a store that books no frames (it checkpoints inline) adds none
    tracing.reset()
    ctx = context([300, None, 200])
    assert store.attr_per_ktx(ctx, "kvdb.write", "frames") == \
        pytest.approx(2 * 500 / 1.0)


def test_the_store_metrics_read_the_commits(recorder):
    ctx = context([300, 500, 200])
    read = {n: fn(ctx, **params) for n, (fn, params) in (
        (n, metric(n)) for n in (
            "ledger_wal_frames_per_ktx.catchup",
            "ledger_wal_write_ms_per_ktx.catchup",
            "ledger_kv_apply_ms_per_ktx.catchup"))}
    # per block: 1,000 frames, 3 x 4 ms writing them, 3 x 2 ms besides
    assert read == {
        "ledger_wal_frames_per_ktx.catchup": pytest.approx(2000.0),
        "ledger_wal_write_ms_per_ktx.catchup": pytest.approx(24.0),
        "ledger_kv_apply_ms_per_ktx.catchup": pytest.approx(12.0)}


@pytest.mark.parametrize("frames", [None, [None, None, None]],
                         ids=["no-store-spans", "no-span-with-frames"])
def test_none_where_there_is_nothing_to_read(recorder, frames):
    """A parent without the store's spans reads None in all three
    metrics, never an error; spans none of which books the attr (a
    kernel without a per-thread I/O account) make no sum."""
    ctx = context(frames)
    assert store.attr_per_ktx(ctx, "kvdb.write", "frames") is None
    if frames is None:
        for name in ("ledger_wal_frames_per_ktx.catchup",
                     "ledger_wal_write_ms_per_ktx.catchup",
                     "ledger_kv_apply_ms_per_ktx.catchup"):
            fn, params = metric(name)
            assert fn(ctx, **params) is None


def test_none_with_the_recorder_off(recorder):
    ctx = context([300, 500, 200])
    tracing.set_enabled(False)
    assert store.attr_per_ktx(ctx, "kvdb.write", "frames") is None
