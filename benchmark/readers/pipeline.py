"""Readers of a verify batch that runs as several executions of the
verify program (PR 38): a block of 10,000 transactions hands the
provider 30,000 signatures in one batch, which `bccsp/tpu.py`
`_dispatch_chunks` runs as 15 spans of 2,048 lanes, staging span k+1
while span k runs.

The program says how many executions a batch ran: the `chunks` of its
`tpu.comb_digest` span (PR 38). Where no such span carries it (a
program from before PR 38, the recorder off, a rehearsal) these
readers have nothing to read and return None. Where it does, the
trace has to agree with it and with the provider's counters
(`trace._program`), or the reading is an error, never a number.
"""

from __future__ import annotations

from benchmark import tracered
from benchmark.readers import program, trace


def executions_said(ctx):
    """Executions of the verify program that the program's recorder
    says the traced blocks' batches ran, or None."""
    blocks = program._blocks(ctx)
    if blocks is None:
        return None
    said = [(e[program._ATTRS] or {}).get("chunks")
            for _, evs in blocks for e in evs
            if e[program._NAME] == "tpu.comb_digest"]
    said = [c for c in said if c is not None]
    return sum(said) if said else None


def span_gap_ms(ctx, match):
    """Mean device idle, in ms, between consecutive executions of the
    verify program that start inside the same traced block's
    `bench.block` interval: what the chip waits for the host between
    two spans of one batch. A block of one execution has no gap, and a
    window with none reads None, never 0."""
    said = executions_said(ctx)
    if said is None:
        return None
    hit = trace._program(ctx, match)
    if not hit:
        return None
    tr = ctx["trace"]
    planes = tracered.device_planes(tr)
    if hit[0] != said * len(planes):
        raise trace.AmbiguousProgram(
            f"{hit[0]} executions of the verify program in the trace on "
            f"{len(planes)} chip(s); the program's tpu.comb_digest spans "
            f"say {said}")
    # the one whole name `trace._program` has just singled out: of the
    # first alternative that any name carries
    times = tracered.program_times(tr)
    name = next(n for m in ([match] if isinstance(match, str) else match)
                for n in times if m in n)
    blocks = [(s, e) for n, s, e in tracered.host_spans(tr)
              if n == "bench.block"]
    gaps = []
    for plane in planes:
        runs = sorted((s, s + d)
                      for line in plane["lines"]
                      if line["name"] == tracered.MODULES_LINE
                      for n, s, d in line["events"] if n == name)
        for lo, hi in blocks:
            inside = [r for r in runs if lo <= r[0] < hi]
            gaps += [max(0.0, b[0] - a[1])
                     for a, b in zip(inside, inside[1:])]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
