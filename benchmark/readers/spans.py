"""Readers over the benchmark's host-clock spans (traced runs)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The smallest value with at least q of the sample at or below."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def _done(ctx):
    return [r for r in ctx["records"] if r.done is not None]


def _total(rec, name: str) -> float:
    return sum(t1 - t0 for t0, t1 in rec.spans.get(name, ()))


def _nested(rec, name: str, outer: str) -> float:
    """Time of `name` spans that lie inside an `outer` span."""
    outers = rec.spans.get(outer, ())
    return sum(t1 - t0 for t0, t1 in rec.spans.get(name, ())
               if any(a <= t0 and t1 <= b for a, b in outers))


def self_ms_per_ktx(ctx, span: str, minus: str):
    """Self time of `span` (minus the `minus` spans inside it) per
    1,000 transactions."""
    recs = [r for r in _done(ctx) if span in r.spans]
    txs = sum(r.n_tx for r in recs)
    if not txs:
        return None
    t = sum(_total(r, span) - _nested(r, minus, span) for r in recs)
    return t * 1e3 / (txs / 1000.0)


def ms_per_ktx(ctx, span: str):
    recs = [r for r in _done(ctx) if span in r.spans]
    txs = sum(r.n_tx for r in recs)
    if not txs:
        return None
    return sum(_total(r, span) for r in recs) * 1e3 / (txs / 1000.0)


def ms_per_klane(ctx, span: str):
    """Wall time inside `span` per 1,000 real signatures handed to it."""
    recs = [r for r in _done(ctx) if span in r.spans]
    lanes = sum(sum(r.lanes) for r in recs)
    if not lanes:
        return None
    return sum(_total(r, span) for r in recs) * 1e3 / (lanes / 1000.0)


def median_ms_per_block(ctx, span: str):
    per = [_total(r, span) * 1e3 for r in _done(ctx) if span in r.spans]
    return statistics.median(per) if per else None


def generator_lag_p95_ms(ctx):
    """How late blocks were handed over against their due times, when
    the peer was free (the previous block done before this one's due)."""
    recs = _done(ctx)
    lags = []
    prev_done = None
    for r in recs:
        if prev_done is None or prev_done <= r.due:
            lags.append((r.start - r.due) * 1e3)
        prev_done = r.done
    return percentile(lags, 0.95) if lags else None


def block_latency_p50_ms(ctx):
    """Median of due time -> done over the blocks done: the steadier
    statistic beside the 95th percentile."""
    lat = [(r.done - r.due) * 1e3 for r in _done(ctx)]
    return statistics.median(lat) if lat else None
