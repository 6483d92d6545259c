"""Readers over the program's own span tree: the flight recorder of
`fabric_tpu.common.tracing`, read through `snapshot()` after the
window, and the provider's cumulative counters.

The recorder and the harness's `BlockRecord.start/done` are both
`time.perf_counter()`, so a span belongs to a block when it lies inside
`[start, done]`; only the blocks handed over under the profiler are
read, as `readers/trace.py` does. A reader that finds nothing to read
(the recorder off, a program without the span or the counter, a
rehearsal with no `tpu.*`) returns None; a recorder that no longer
holds the traced blocks whole is an error, never a number.
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import tracered

RESIDUAL_NS = 200_000.0     # a block's two clocks may disagree by this
# event tuple of tracing.snapshot(): (ph, name, trace, span, parent, t0,
# dur, thread, attrs, error, node)
_NAME, _SPAN, _PARENT, _T0, _DUR, _ATTRS = 1, 3, 4, 5, 6, 8


class RingOverrun(ValueError):
    """The recorder overwrote spans of the traced blocks."""


class ClockMismatch(ValueError):
    """The recorder's clock cannot be placed on the trace's."""


def _traced(ctx):
    return [r for r in ctx["records"] if r.traced and r.done is not None]


def _blocks(ctx):
    """[(record, [events inside it])] over the traced blocks, or None
    when the recorder is off or holds no span of them."""
    from fabric_tpu.common import tracing
    recs = _traced(ctx)
    if not recs or not tracing.enabled():
        return None
    held = [e for e in tracing.snapshot() if e[0] == "X"]
    if not held:
        return None
    oldest = held[0]        # spans are recorded as they end
    # (a program from before `dropped()` has none of the spans read
    # here either)
    dropped = getattr(tracing, "dropped", lambda: 0)()
    if dropped > 0 and oldest[_T0] + oldest[_DUR] > recs[0].start:
        raise RingOverrun(
            f"the flight recorder has overwritten {dropped} "
            f"events and the oldest it holds ended "
            f"{oldest[_T0] + oldest[_DUR] - recs[0].start:.3f}s after the "
            "first traced block began: raise FTPU_TRACE_RING")
    held.sort(key=lambda e: e[_T0])
    starts = [e[_T0] for e in held]
    out = []
    for r in recs:
        lo = bisect.bisect_left(starts, r.start)
        hi = bisect.bisect_right(starts, r.done)
        out.append((r, [e for e in held[lo:hi]
                        if e[_T0] + e[_DUR] <= r.done]))
    return out if any(evs for _, evs in out) else None


def _names(spans) -> tuple:
    return (spans,) if isinstance(spans, str) else tuple(spans)


def _seconds(blocks, names) -> tuple:
    """(seconds inside spans of `names`, how many such spans)."""
    hits = [e[_DUR] for _, evs in blocks for e in evs if e[_NAME] in names]
    return sum(hits), len(hits)


def span_ms_per_ktx(ctx, spans):
    """Wall time inside the named spans per 1,000 transactions."""
    blocks = _blocks(ctx)
    if blocks is None:
        return None
    t, n = _seconds(blocks, _names(spans))
    txs = sum(r.n_tx for r, _ in blocks)
    if not n or not txs:
        return None
    return t * 1e3 / (txs / 1000.0)


def span_ms_per_klane(ctx, spans):
    """Wall time inside the named spans per 1,000 real signatures, by
    the `tpu.stage` spans' own `lanes`."""
    blocks = _blocks(ctx)
    if blocks is None:
        return None
    t, n = _seconds(blocks, _names(spans))
    lanes = sum((e[_ATTRS] or {}).get("lanes", 0)
                for _, evs in blocks for e in evs
                if e[_NAME] == "tpu.stage")
    if not n or not lanes:
        return None
    return t * 1e3 / (lanes / 1000.0)


def self_ms_per_ktx(ctx, span, needs=None):
    """Time inside the named span(s) that none of their child spans
    covers (children by `parent` id), per 1,000 transactions. Nothing
    unless the `needs` span occurs: a sum over parents means something
    only in a program that has the tree they belong to."""
    blocks = _blocks(ctx)
    if blocks is None:
        return None
    names = _names(span)
    total, found, rooted = 0.0, 0, needs is None
    for _, evs in blocks:
        own = {e[_SPAN]: e[_DUR] for e in evs if e[_NAME] in names}
        found += len(own)
        for e in evs:
            rooted = rooted or e[_NAME] == needs
            if e[_PARENT] in own:
                own[e[_PARENT]] -= e[_DUR]
        total += sum(own.values())
    txs = sum(r.n_tx for r, _ in blocks)
    if not found or not rooted or not txs:
        return None
    return total * 1e3 / (txs / 1000.0)


def counter_ratio(ctx, num: str, den: str):
    """Growth of one provider counter over another's, over the window."""
    before, after = ctx["stats_before"], ctx["stats_after"]
    if num not in after or den not in after:
        return None
    d = after[den] - before.get(den, 0)
    if not d:
        return None
    return (after[num] - before.get(num, 0)) / d


def clock_offset_ns(ctx) -> float:
    """What to add to a `perf_counter` reading (in ns) to land on the
    trace's clock. `tracered.load_xplane` keeps only the benchmark's
    own annotations, so the program's do not reach `ctx["trace"]`; but
    each traced block has its `bench.block` interval on both clocks.
    The offset is the median of the per-block differences; blocks that
    do not pair up, or one that disagrees with the median by more than
    RESIDUAL_NS, are an error."""
    on_host = [r.spans["bench.block"][0][0] * 1e9 for r in _traced(ctx)
               if r.spans.get("bench.block")]
    on_trace = [s for name, s, _ in tracered.host_spans(ctx["trace"])
                if name == "bench.block"]
    if len(on_host) != len(on_trace) or not on_host:
        raise ClockMismatch(
            f"{len(on_host)} traced blocks on the host clock, "
            f"{len(on_trace)} bench.block annotations in the trace")
    diffs = [b - a for a, b in zip(on_host, on_trace)]
    offset = statistics.median(diffs)
    worst = max(abs(d - offset) for d in diffs)
    if worst > RESIDUAL_NS:
        raise ClockMismatch(
            f"a traced block lies {worst / 1e3:.1f} us off the median "
            f"offset between the recorder's clock and the trace's "
            f"(limit {RESIDUAL_NS / 1e3:.0f} us)")
    return offset


def idle_unattributed_share(ctx, leaves):
    """Share of the first chip's idle time in the traced window that no
    leaf span of the program covers. The gaps are `tracered.idle_gaps`'
    (between device operations, those under SHORT_GAP_NS left out); the
    recorder's spans are placed on the trace's clock by
    `clock_offset_ns`."""
    trace = ctx.get("trace")
    if not trace:
        return None
    win = tracered.traced_window(trace)
    planes = tracered.device_planes(trace)
    blocks = _blocks(ctx)
    if win is None or not planes or blocks is None:
        return None
    lo, hi = win
    busy = tracered.busy_intervals(planes[0], lo, hi)
    if not busy:
        return None
    names = _names(leaves)
    named_spans = [e for _, evs in blocks for e in evs
                   if e[_NAME] in names]
    if not named_spans:
        return None
    offset = clock_offset_ns(ctx)
    covered = tracered.union_intervals(
        ((e[_T0] * 1e9 + offset, (e[_T0] + e[_DUR]) * 1e9 + offset)
         for e in named_spans), lo, hi)
    ends = [b for _, b in covered]
    idle = named = 0.0
    cursor = lo
    for s, e in busy + [[hi, hi]]:
        if s - cursor >= tracered.SHORT_GAP_NS:
            idle += s - cursor
            k = bisect.bisect_right(ends, cursor)
            while k < len(covered) and covered[k][0] < s:
                named += min(s, covered[k][1]) - max(cursor, covered[k][0])
                k += 1
        cursor = max(cursor, e)
    return (idle - named) / idle if idle else None
