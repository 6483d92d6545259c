"""The tracing session of a traced run (`benchmark/spans.py` `Tracer`):
the profiler's start follows the blocks, not the clock alone, and a
traced run that ends with no device window raises `NothingTraced`
instead of printing a line without `busy_s` / `window_s`. No chip: the
clock is driven by a stand-in intake, `jax.profiler.start_trace` /
`stop_trace` are stand-ins that note when they were called."""

from types import SimpleNamespace as NS

import pytest

from benchmark import run, spans, tracered

T0 = 100.0
CLOSED = {"kind": "closed", "supply_tx_per_s": 4000}


class Clock:
    def __init__(self):
        self.now = T0

    def perf_counter(self):
        return self.now


class PacedIntake:
    """Block `i` of the window takes `took(i, seconds into the window)`
    on the driven clock; the attributes a `Tracer` wraps are there and
    never called."""

    def __init__(self, clock, took):
        self.clock, self.took, self.begun = clock, took, []
        self.channel = NS(validator=NS(validate=None), commit_validated=None)
        self.mcs = NS(verify_block=None)
        self.csp = NS(verify_batch=None)

    def stats(self):
        return {"comb_batches": len(self.begun)}

    def hand_over(self, block):
        self.begun.append(self.clock.now)
        self.clock.now += self.took(len(self.begun) - 1,
                                    self.clock.now - T0)


def backlog(n):
    return [NS(header=NS(number=23 + i), data=NS(data=[b""] * 500))
            for i in range(n)]


@pytest.fixture
def session(monkeypatch, tmp_path):
    """drive(took, seconds) -> (tracer, intake, records, when the
    profiler was started and stopped on the driven clock)."""
    import jax
    clock = Clock()
    calls = {"start": [], "stop": []}
    monkeypatch.setattr(run, "time", clock)
    monkeypatch.setattr(spans, "time", clock)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls["start"].append(clock.now))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls["stop"].append(clock.now))

    def drive(took, seconds):
        intake = PacedIntake(clock, took)
        tracer = spans.Tracer(intake, str(tmp_path / "trace"),
                              min(run.TRACE_SECONDS, seconds),
                              run.TRACE_BLOCKS)
        records, t0, t1 = run.run_window(intake, backlog(400), CLOSED,
                                         seconds, tracer)
        assert t0 == T0
        return tracer, intake, records, calls
    return drive


def parents_boundary(begun, seconds):
    """Where the parent's rule starts the profiler: the first block
    boundary at or after the window's end less TRACE_SECONDS."""
    return next(b for b in begun if b >= T0 + seconds - run.TRACE_SECONDS)


def aging(i, into):
    return 0.2 + 0.1 * min(into, 40.0) / 40.0     # 0.2 s -> 0.3 s a block


def one_long_block(i, into):
    return 5.0 if 5.7 < into < 5.9 else 0.2


CASES = {
    # blocks, --seconds, traced blocks at least, the parent's boundary?
    "0.2s-blocks": (lambda i, into: 0.2, 40.0, 20, True),
    "2.75s-blocks": (lambda i, into: 2.75, 40.0, 2, False),
    "aging-0.2s-to-0.3s": (aging, 40.0, 13, True),
    "first-runs-8s-blocks": (lambda i, into: 8.0, 40.0, 2, False),
    "a-3s-window": (lambda i, into: 0.2, 3.0, 15, True),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_the_profiler_starts_at_a_boundary_that_leaves_it_blocks(
        session, case):
    took, seconds, at_least, as_parent = CASES[case]
    tracer, intake, records, calls = session(took, seconds)
    assert len(calls["start"]) == 1 and not calls["stop"]
    started = calls["start"][0]
    assert started in intake.begun, "the profiler starts at a boundary"
    traced = [r for r in records if r.traced]
    assert traced == records[-len(traced):] and traced[0].start == started
    assert tracer.traced_blocks == len(traced) >= at_least
    assert tracer.trace_lead_s == pytest.approx(T0 + seconds - started)
    longest = max(r.done - r.start for r in records)
    if as_parent:
        # at 0.2-0.3 s a block TRACE_SECONDS governs: the parent's rule
        # to the letter, the window's last 4 s to within a block
        assert started == parents_boundary(intake.begun, seconds)
        assert records[-1].done - started >= \
            min(run.TRACE_SECONDS, seconds) - longest
    else:
        # TRACE_BLOCKS governs: the last whole block and the one that
        # closes the window, both under the profiler
        assert tracer.lead_s() == pytest.approx(run.TRACE_BLOCKS * longest)
        assert traced[0].done <= T0 + seconds <= traced[-1].done
    assert all(r.done is not None for r in records)


def test_the_recent_time_of_a_block_is_a_median_of_the_last_few(session):
    # one stall of 3 s among 0.2 s blocks does not move the start
    def took(i, into):
        return 3.0 if i == 150 else 0.2
    tracer, intake, records, calls = session(took, 40.0)
    assert calls["start"][0] == parents_boundary(intake.begun, 40.0)
    assert tracer.lead_s() == run.TRACE_SECONDS


NOTHING = {
    # what the run meets -> what the error names
    "a-block-overruns-the-window": "no block began under the profiler",
    "an-empty-trace-directory": "left no .xplane.pb under",
    "a-trace-without-bench.block": "holds no bench.block annotation",
    "a-trace-without-device-operations": "no device operation ran",
}


@pytest.mark.parametrize("case", NOTHING, ids=list(NOTHING))
def test_a_traced_run_with_nothing_traced_raises_the_named_error(
        session, monkeypatch, case):
    overrun = case == "a-block-overruns-the-window"
    # a 10 s window: the block that begins 4.2 s before its end takes
    # 5 s, so no boundary falls in the last TRACE_SECONDS
    tracer, intake, records, calls = session(
        one_long_block if overrun else (lambda i, into: 0.2), 10.0)
    assert bool(calls["start"]) is not overrun
    if case.startswith("a-trace"):
        block = ["bench.block", 0.0, 2e8]
        planes = [{"name": "/host:CPU",
                   "lines": [{"name": "python3", "events": [
                       block if "device" in case
                       else ["ledger.commit", 0.0, 1e8]]}]}]
        if "bench.block" in case:       # the device ran, nothing says when
            planes.append({"name": "/device:TPU:0", "lines": [
                {"name": tracered.OPS_LINE, "events": [["op", 1e7, 1e7]]}]})
        monkeypatch.setattr(tracered, "find_xplane", lambda d: d)
        monkeypatch.setattr(tracered, "load_xplane",
                            lambda p: {"planes": planes})
    with pytest.raises(spans.NothingTraced) as e:
        tracer.finish()
    assert isinstance(e.value, RuntimeError)
    msg = str(e.value)
    assert NOTHING[case] in msg and "No result" in msg
    assert "TRACE_SECONDS = 4 s" in msg and "TRACE_BLOCKS = 2 x" in msg
    if overrun:
        assert not calls["stop"] and tracer.traced_blocks == 0
        assert "began 4.20 s before its end and took 5.00 s" in msg
    else:
        assert calls["stop"] and "[0.2, 0.2, 0.2, 0.2, 0.2] s" in msg


def test_a_rehearsal_needs_no_device_plane(session, monkeypatch):
    tracer, _, _, _ = session(lambda i, into: 0.2, 10.0)
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.block", 0.0, 2e8]]}]}]}
    monkeypatch.setattr(tracered, "find_xplane", lambda d: d)
    monkeypatch.setattr(tracered, "load_xplane", lambda p: trace)
    assert tracer.finish(need_device=False) is trace
    assert tracer.busy_and_window is None


def test_the_two_constants_of_the_rule():
    assert (run.TRACE_SECONDS, run.TRACE_BLOCKS) == (4.0, 2)
