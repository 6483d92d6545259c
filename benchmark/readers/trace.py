"""Readers over the reduced profiler trace (traced runs on a chip)."""

from __future__ import annotations

from benchmark import tracered, work


class AmbiguousProgram(ValueError):
    """The trace does not single out the provider's verify program."""


def _traced(ctx):
    return [r for r in ctx["records"] if r.traced and r.done is not None]


def _program(ctx, match):
    """(executions, device seconds) of the provider's verify program in
    the traced window. Its jitted function has a common name (`fused`),
    so a name alone could add another program in: of the names that
    hold one of `match` (the first alternative that any carries)
    exactly one whole name, identifier included, may have run, and it
    has to have run as often as the provider's own counters booked
    dispatches for the blocks handed over under the profiler. Anything
    else is an error, never a number."""
    trace = ctx.get("trace")
    if not trace:
        return None
    times = tracered.program_times(trace)
    hits = []
    for m in ([match] if isinstance(match, str) else match):
        hits = [(n, c, s) for n, (c, s) in times.items() if m in n]
        if hits:
            break
    if not hits:
        return None
    booked = sum(r.dispatches for r in _traced(ctx))
    chips = len(tracered.device_planes(trace))
    if len(hits) != 1 or hits[0][1] != booked * chips:
        raise AmbiguousProgram(
            f"programs matching {match!r} in the traced window: "
            f"{[(n, c) for n, c, _ in hits]}; the provider's counters "
            f"booked {booked} dispatches on {chips} chip(s)")
    return hits[0][1], hits[0][2]


def program_ms_per_execution(ctx, match):
    hit = _program(ctx, match)
    return hit[1] * 1e3 / hit[0] if hit and hit[0] else None


def _real_lanes(ctx, min_batch: int = 16) -> int:
    """Real signatures handed to the provider under the profiler (a
    call under MinBatch goes to sw by design)."""
    return sum(n for r in _traced(ctx) for n in r.lanes if n >= min_batch)


def lane_occupancy(ctx, match):
    """Real signatures / lanes the device ran: executions of the verify
    program in the trace x the provider's span (every execution runs
    one span's compiled shape)."""
    hit = _program(ctx, match)
    real = _real_lanes(ctx)
    if not hit or not hit[0] or not real:
        return None
    # the provider's own reckoning of a span; no file of the benchmark
    # states the number in its place
    span = ctx["provider"]._pipeline_span()
    if not span:
        raise ValueError("the provider names no pipeline span")
    return real / (hit[0] / len(tracered.device_planes(ctx["trace"])) * span)


def idle_share(ctx):
    trace = ctx.get("trace")
    bw = tracered.busy_and_window(trace) if trace else None
    if bw is None:
        return None
    return 1.0 - bw[0] / bw[1]


def hbm_roofline_percent(ctx, match):
    """Least time the chip could take for the signatures really
    verified in the traced window / device time of the program."""
    hit = _program(ctx, match)
    if not hit or not hit[1]:
        return None
    real = _real_lanes(ctx)
    if not real:
        return None
    peaks = work.load_peaks(ctx["device_kind"])
    floor = work.hbm_floor_seconds(real, peaks["hbm_bytes_per_s"])
    return 100.0 * floor / hit[1]
