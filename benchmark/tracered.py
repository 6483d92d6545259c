"""Reduction of a profiler trace to numbers: device busy and idle,
time per program, and idle gaps attributed to what the host was doing.

`load_xplane` (needs JAX) turns an `.xplane.pb` into a plain dict;
everything else is pure Python over that dict, so it is tested on the
small recorded trace in `benchmark/fixtures/`.

Trace dict: {"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}]}.
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_LINES = (OPS_LINE, MODULES_LINE)
SHORT_GAP_NS = 50_000.0     # 50 us
NAME_CHARS = 160            # an operation's name is its whole HLO line
# the benchmark's own host annotations, innermost first: each stretch of
# an idle gap goes to the innermost annotation that covers it
HOST_SPANS = ("provider.call", "validate", "ledger.commit", "verify_block",
              "await_next_block", "bench.block")
HOST_LABEL = {"validate": "validate.host", "bench.block": "other"}


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str) -> dict:
    """Device planes whole; of the host planes only the benchmark's
    annotations (the rest is large and unused)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = [[ev.name[:NAME_CHARS], float(ev.start_ns),
                       float(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name in HOST_SPANS]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def host_spans(trace: dict) -> list:
    """[(name, start_ns, end_ns)] of the benchmark's annotations."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith(DEVICE_PREFIX):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name in HOST_SPANS:
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda s: s[1])


def traced_window(trace: dict):
    """(start_ns, end_ns): from the first hand-over's start to the last
    hand-over's end that the trace holds."""
    blocks = [s for s in host_spans(trace) if s[0] == "bench.block"]
    if not blocks:
        return None
    return blocks[0][1], max(s[2] for s in blocks)


def union_intervals(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] pairs clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(plane: dict, lo: float, hi: float) -> list:
    line = _line(plane, OPS_LINE)
    lines = [line] if line is not None else [
        ln for ln in plane["lines"] if ln["name"] != "Steps"]
    return union_intervals(
        ((s, s + d) for ln in lines for _, s, d in ln["events"]), lo, hi)


def busy_and_window(trace: dict):
    """(busy_s averaged over the device planes, window_s) or None when
    the trace holds no device operation inside the window."""
    win = traced_window(trace)
    planes = device_planes(trace)
    if win is None or not planes:
        return None
    lo, hi = win
    busy = [sum(e - s for s, e in busy_intervals(p, lo, hi)) for p in planes]
    if not any(busy):
        return None
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def program_times(trace: dict) -> dict:
    """{program name: [executions, device seconds]} inside the window,
    summed over chips, from the modules line. The name is whole, the
    identifier XLA puts in brackets behind it included: two programs
    jitted from functions of one name stay apart."""
    win = traced_window(trace)
    out: dict = {}
    if win is None:
        return out
    lo, hi = win
    for p in device_planes(trace):
        line = _line(p, MODULES_LINE)
        if line is None:
            continue
        for name, s, d in line["events"]:
            if s < lo or s + d > hi:
                continue
            hit = out.setdefault(name, [0, 0.0])
            hit[0] += 1
            hit[1] += d / 1e9
    return out


def op_times(trace: dict, top: int = 10) -> list:
    """[[operation, device seconds]] that took most time in the window."""
    win = traced_window(trace)
    if win is None:
        return []
    lo, hi = win
    acc: dict = {}
    for p in device_planes(trace):
        line = _line(p, OPS_LINE) or _line(p, MODULES_LINE)
        if line is None:
            continue
        for name, s, d in line["events"]:
            if s >= lo and s + d <= hi:
                acc[name] = acc.get(name, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:top]]


def idle_gaps(trace: dict, top: int = 10) -> list:
    """[[what the host was doing, idle seconds]]: every gap between
    device operations of the first chip, shared out over the host
    annotations that cover it — each stretch of a gap goes to the
    innermost annotation over it, what none covers to `other` — and
    summed by annotation. Gaps under SHORT_GAP_NS lie between the
    operations of one program and are summed as `between_ops` without
    a look at the host."""
    win = traced_window(trace)
    planes = device_planes(trace)
    if win is None or not planes:
        return []
    lo, hi = win
    busy = busy_intervals(planes[0], lo, hi)
    if not busy:
        return []
    by_name: dict = {}
    for name, a, b in host_spans(trace):
        by_name.setdefault(name, []).append((a, b))
    starts = {n: [a for a, _ in v] for n, v in by_name.items()}
    acc: dict = {}

    def credit(label: str, ns: float) -> None:
        if ns > 0:
            acc[label] = acc.get(label, 0.0) + ns / 1e9

    def share_out(s: float, e: float) -> None:
        pieces = [(s, e)]
        for name in HOST_SPANS:
            spans = by_name.get(name)
            if not spans or not pieces:
                continue
            k = max(0, bisect.bisect_right(starts[name], s) - 1)
            while k < len(spans) and spans[k][0] < e:
                a, b = spans[k]
                rest = []
                for x, y in pieces:
                    credit(HOST_LABEL.get(name, name), min(y, b) - max(x, a))
                    if x < a:
                        rest.append((x, min(y, a)))
                    if y > b:
                        rest.append((max(x, b), y))
                pieces = rest
                k += 1
        for x, y in pieces:
            credit("other", y - x)

    cursor = lo
    for s, e in busy + [[hi, hi]]:
        if s - cursor >= SHORT_GAP_NS:
            share_out(cursor, s)
        else:
            credit("between_ops", s - cursor)
        cursor = max(cursor, e)
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:top]]
