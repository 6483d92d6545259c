#!/usr/bin/env python3
"""Perf-regression ledger over the driver's BENCH_r*/MULTICHIP_r*
round history (round 16).

Five rounds of bench output already sit on disk with NO tooling that
reads them: r01/r02 parsed cleanly, r03's tail is a TRUNCATED final
line (the driver stored ``parsed: null``), r04 crashed mid-bench
(rc=1, traceback tail) and r05 timed out at interpreter start
(rc=124, nothing but a start-up warning). Those records are gone
from the tree (they predate twenty PRs of growth; the driver's
PERF_LEDGER.jsonl is the record now), but the shapes are what a
round file can look like. This tool turns such a history
into one machine-readable trajectory and gates the next round
against it:

  python tools/perf_ledger.py [--dir D] [--out F] [--pretty]
      Parse every BENCH_r*.json / MULTICHIP_r*.json round (the
      crashed/timed-out/truncated shapes are salvaged or carried as
      status rows, never fatal) and emit one trajectory JSON:
      per-round metric extractions plus per-metric series with
      best/last summaries.

  python tools/perf_ledger.py check --candidate F [--dir D]
      [--tolerance PCT] [--set metric=PCT] [--include-cpu]
      Compare a fresh bench aggregate (a JSON object file, or any
      bench stdout whose LAST JSON line is the aggregate) against the
      history's best-and-last per metric, with per-metric direction
      and tolerance from the registry below. Exits 1 with a
      named-regression report when the candidate is worse than the
      last good reading OR the historical best beyond tolerance;
      0 when clean; 2 on usage/empty-history errors. Candidates from
      a CPU parity rig (``on_tpu`` false) are skipped by default —
      comparing a wheel-free container's numbers against v5e rounds
      names nothing but the hardware.

``bench.py`` calls :func:`verdict` to stamp a ``ledger`` field on its
final aggregate line; ``tools/perf_check.sh`` runs both commands as a
CI gate beside static_check.

Stdlib-only and jax-free by design: the ledger must parse a history
of broken rounds on any machine, including the one whose TPU runtime
just hung.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

# canonical metric registry: direction ("up" = higher is better) and
# default tolerance (percent, vs both the last good reading and the
# historical best). Tolerances are deliberately loose where history
# shows noise (compile_s depends on the persistent-cache state of the
# box; warm_pass_s rides it).
METRICS: dict = {
    "value": ("up", 10.0),
    "vs_baseline": ("up", 15.0),
    "provider_sigs_per_s": ("up", 10.0),
    "e2e_pipelined_sigs_per_s": ("up", 15.0),
    "tpu_steady_s": ("down", 20.0),
    "compile_s": ("down", 75.0),
    "warm_pass_s": ("down", 75.0),
    "order_raft_s": ("down", 25.0),
    "order_tx_per_s": ("up", 25.0),
    "tpu_steady_scaling_x": ("up", 15.0),
    "commit_pipeline_overlap_ratio": ("up", 25.0),
    "tracing_overhead_pct": ("down", 2.0, "abs"),
    # round-20 fused Pallas tier: the fused A/B sub-stage's own
    # device number, the host SHA-256 slice it eliminates, and the
    # fused throughput (new metrics are absent from older rounds and
    # simply aren't gated until a device round books them)
    "fused_steady_s": ("down", 20.0),
    "fused_sigs_per_s": ("up", 20.0),
    "host_prep_s": ("down", 50.0),
    # round-21 BLS12-381 pairing engine: steady Miller-pair rate at
    # the widest aggregate and the shared-final-exp slice of that
    # pass (a share RISING past tolerance means the amortization the
    # batch structure exists for is eroding)
    "pairing_pairs_per_s": ("up", 20.0),
    "pairing_final_exp_share": ("down", 25.0),
}

# older rounds (pre-staged bench) spelled some metrics differently;
# both spellings land on one canonical series
ALIASES = {
    "provider_verify_batch_sigs_per_s": "provider_sigs_per_s",
    "compile_seconds": "compile_s",
}

_BENCH_RE = re.compile(r"BENCH_r(\d+)\.json$")
_MULTI_RE = re.compile(r"MULTICHIP_r(\d+)\.json$")


def _extract(obj, out: dict) -> None:
    """Pull every registry metric out of a (possibly nested) parsed
    object, breadth-first so a top-level reading wins over a nested
    one with the same name."""
    queue = [obj]
    while queue:
        cur = queue.pop(0)
        if not isinstance(cur, dict):
            continue
        for k, v in cur.items():
            canon = ALIASES.get(k, k)
            if canon in METRICS and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                out.setdefault(canon, float(v))
            elif isinstance(v, dict):
                queue.append(v)


def _salvage_tail(tail: str) -> dict:
    """Regex-extract registry metrics from a truncated/unparseable
    tail (the r03 shape: the final JSON line lost its head, but the
    '"name": number' pairs survive)."""
    out: dict = {}
    for name in list(METRICS) + list(ALIASES):
        m = re.search(r'"%s"\s*:\s*(-?\d+(?:\.\d+)?)'
                      % re.escape(name), tail or "")
        if m:
            out.setdefault(ALIASES.get(name, name),
                           float(m.group(1)))
    return out


def _last_line(text: str) -> str:
    lines = [ln.strip() for ln in (text or "").splitlines()
             if ln.strip()]
    return lines[-1] if lines else ""


def parse_bench_round(path: str) -> dict:
    """One BENCH_rNN.json driver capture -> a round entry. Crashed
    (rc!=0), timed-out (rc=124) and truncated-tail rounds are
    REPRESENTED, not fatal: status + error summary + whatever metrics
    the tail still names."""
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    m = _BENCH_RE.search(os.path.basename(path))
    n = d.get("n") if d.get("n") is not None else (
        int(m.group(1)) if m else None)
    rc = d.get("rc")
    entry: dict = {"round": n, "source": os.path.basename(path),
                   "rc": rc}
    metrics: dict = {}
    parsed = d.get("parsed")
    if isinstance(parsed, dict):
        _extract(parsed, metrics)
        entry["status"] = "ok" if rc == 0 else "error"
    else:
        metrics = _salvage_tail(d.get("tail") or "")
        if rc == 124:
            entry["status"] = "timeout"
            entry["error"] = ("rc=124 before any output — the "
                              "interpreter-start hang class"
                              if not metrics else "rc=124 mid-run")
        elif rc not in (0, None):
            entry["status"] = "crashed"
            entry["error"] = _last_line(d.get("tail") or "")[:200]
        else:
            entry["status"] = "salvaged" if metrics else "empty"
            if metrics:
                entry["note"] = ("parsed=null but the tail still "
                                 "names metrics (truncated final "
                                 "line)")
    entry["metrics"] = metrics
    return entry


def parse_multichip_round(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    m = _MULTI_RE.search(os.path.basename(path))
    return {"round": int(m.group(1)) if m else None,
            "rc": d.get("rc"), "ok": bool(d.get("ok")),
            "skipped": bool(d.get("skipped")),
            "n_devices": d.get("n_devices")}


def load_history(history_dir: str) -> list:
    """Every round in the directory, bench + multichip merged, in
    round order."""
    rounds: dict = {}
    for path in sorted(glob.glob(
            os.path.join(history_dir, "BENCH_r*.json"))):
        try:
            e = parse_bench_round(path)
        except (OSError, json.JSONDecodeError) as exc:
            e = {"round": None, "source": os.path.basename(path),
                 "status": "unreadable", "error": str(exc)[:200],
                 "metrics": {}}
        rounds.setdefault(e.get("round"), {}).update(e)
    for path in sorted(glob.glob(
            os.path.join(history_dir, "MULTICHIP_r*.json"))):
        try:
            mc = parse_multichip_round(path)
        except (OSError, json.JSONDecodeError) as exc:
            mc = {"round": None, "error": str(exc)[:200]}
        slot = rounds.setdefault(mc.get("round"),
                                 {"round": mc.get("round"),
                                  "metrics": {}})
        slot["multichip"] = {k: mc[k] for k in
                             ("rc", "ok", "skipped", "n_devices")
                             if k in mc}
    return [rounds[k] for k in sorted(rounds,
                                      key=lambda x: (x is None, x))]


def _tol(name: str):
    spec = METRICS[name]
    direction, tol = spec[0], spec[1]
    mode = spec[2] if len(spec) > 2 else "pct"
    return direction, tol, mode


def trajectory(history_dir: str) -> dict:
    """The whole history as one JSON document: round rows plus
    per-metric series with best/last summaries (what `check` gates
    against and what a scaling plot reads). Only rounds whose bench
    EXITED CLEANLY (rc=0 — full parses and the truncated-tail
    salvage class) feed the gating series: a crashed/timed-out
    round's tail can carry mid-run stage-line numbers (half the
    final aggregate), and booking those as best/last would gate the
    next healthy round against garbage. The broken rounds still
    appear as status rows with whatever their tails named."""
    rounds = load_history(history_dir)
    series: dict = {}
    for e in rounds:
        if e.get("status") not in ("ok", "salvaged"):
            continue
        for name, v in (e.get("metrics") or {}).items():
            series.setdefault(name, []).append(
                {"round": e.get("round"), "value": v})
    summary: dict = {}
    for name, pts in sorted(series.items()):
        direction, tol, mode = _tol(name)
        vals = [p["value"] for p in pts]
        summary[name] = {
            "direction": direction,
            "tolerance": tol,
            "tolerance_mode": mode,
            "best": max(vals) if direction == "up" else min(vals),
            "last": vals[-1],
            "points": pts,
        }
    return {
        "history_dir": os.path.abspath(history_dir),
        "rounds": rounds,
        "ok_rounds": [e.get("round") for e in rounds
                      if e.get("status") == "ok"],
        "broken_rounds": [
            {"round": e.get("round"), "status": e.get("status"),
             "error": e.get("error")}
            for e in rounds
            if e.get("status") in ("crashed", "timeout",
                                   "unreadable")],
        "metrics": summary,
    }


def load_candidate(path: str) -> dict:
    """A candidate aggregate: a JSON object file, or any text whose
    LAST parseable JSON line is the aggregate (raw bench stdout
    works). Returns the parsed object."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        obj = json.loads(text)
        if isinstance(obj, dict):
            return obj
    except json.JSONDecodeError:
        pass
    for ln in reversed([ln for ln in text.splitlines()
                        if ln.strip()]):
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            obj = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            return obj
    raise ValueError(f"no JSON object found in {path!r}")


def _allowed(ref: float, direction: str, tol: float,
             mode: str) -> float:
    if mode == "abs":
        return ref - tol if direction == "up" else ref + tol
    return ref * (1.0 - tol / 100.0) if direction == "up" \
        else ref * (1.0 + tol / 100.0)


def compare(candidate: dict, traj: dict,
            tolerance: float | None = None,
            metric_tolerances: dict | None = None) -> dict:
    """Candidate metrics vs the trajectory's best-and-last, per
    metric. Returns {"ok", "checked", "regressions", "skipped"}; a
    regression names the metric, the reference it failed against
    (last/best), both values and the allowed floor/ceiling."""
    cand_metrics: dict = {}
    _extract(candidate, cand_metrics)
    checked: dict = {}
    regressions: list = []
    for name, cv in sorted(cand_metrics.items()):
        s = (traj.get("metrics") or {}).get(name)
        if s is None:
            continue
        direction, tol, mode = _tol(name)
        if metric_tolerances and name in metric_tolerances:
            tol = float(metric_tolerances[name])
        elif tolerance is not None and mode != "abs":
            tol = float(tolerance)
        row = {"candidate": cv, "direction": direction,
               "tolerance": tol, "tolerance_mode": mode}
        for ref_name in ("last", "best"):
            ref = s[ref_name]
            allowed = _allowed(ref, direction, tol, mode)
            worse = cv < allowed if direction == "up" \
                else cv > allowed
            row[ref_name] = ref
            row[f"allowed_vs_{ref_name}"] = round(allowed, 6)
            if worse:
                regressions.append({
                    "metric": name, "reference": ref_name,
                    "candidate": cv, ref_name: ref,
                    "allowed": round(allowed, 6),
                    "direction": direction, "tolerance": tol,
                    "tolerance_mode": mode})
        checked[name] = row
    return {"ok": not regressions, "checked": checked,
            "regressions": regressions,
            "skipped": sorted(set(cand_metrics) - set(checked))}


def verdict(candidate: dict, history_dir: str) -> str:
    """The one-string summary bench.py stamps on its final aggregate
    line: 'ok(<n> metrics vs r<last>)', 'regressed:<m1>,<m2>',
    'skipped:cpu-rig' (a parity-rig candidate vs device-round
    history), or 'no_history'. Never raises."""
    try:
        traj = trajectory(history_dir)
        if not traj["metrics"]:
            return "no_history"
        if not candidate.get("on_tpu"):
            # the history rounds come from the driver's device box; a
            # wheel-free CPU parity rig regressing against them names
            # the hardware, not the code
            return "skipped:cpu-rig"
        res = compare(candidate, traj)
        if not res["checked"]:
            return "no_overlap"
        if res["ok"]:
            last_ok = (traj.get("ok_rounds") or
                       [r.get("round") for r in traj["rounds"]])
            return "ok(%d metrics vs r%s)" % (
                len(res["checked"]),
                last_ok[-1] if last_ok else "?")
        names = sorted({r["metric"] for r in res["regressions"]})
        return "regressed:" + ",".join(names)
    except Exception as e:          # noqa: BLE001
        return f"unavailable:{type(e).__name__}"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cmd_trajectory(args) -> int:
    traj = trajectory(args.dir)
    if not traj["rounds"]:
        print(f"perf_ledger: no BENCH_r*/MULTICHIP_r* rounds under "
              f"{args.dir!r}", file=sys.stderr)
        return 2
    doc = json.dumps(traj, indent=2 if args.pretty else None,
                     sort_keys=False)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(doc + "\n")
        print(f"perf_ledger: {len(traj['rounds'])} rounds, "
              f"{len(traj['metrics'])} metric series -> {args.out}")
    else:
        print(doc)
    return 0


def _cmd_check(args) -> int:
    history_dir = args.check_dir or args.dir
    pretty = (args.check_pretty if args.check_pretty is not None
              else args.pretty)
    try:
        candidate = load_candidate(args.candidate)
    except (OSError, ValueError) as e:
        print(f"perf_ledger: unreadable candidate: {e}",
              file=sys.stderr)
        return 2
    traj = trajectory(history_dir)
    if not traj["metrics"]:
        print(f"perf_ledger: no history under {history_dir!r} to "
              "check against", file=sys.stderr)
        return 2
    if not candidate.get("on_tpu") and not args.include_cpu:
        print(json.dumps({"ok": True, "skipped": "cpu-rig",
                          "note": "candidate is a CPU parity rig; "
                                  "pass --include-cpu to compare "
                                  "against device-round history "
                                  "anyway"}))
        return 0
    overrides = {}
    for spec in args.set or ():
        name, _, pct = spec.partition("=")
        try:
            overrides[name] = float(pct)
        except ValueError:
            print(f"perf_ledger: bad --set {spec!r} (want "
                  "metric=pct)", file=sys.stderr)
            return 2
    res = compare(candidate, traj, tolerance=args.tolerance,
                  metric_tolerances=overrides)
    print(json.dumps(res, indent=2 if pretty else None))
    if not res["checked"]:
        print("perf_ledger: candidate shares no registry metric "
              "with the history", file=sys.stderr)
        return 2
    if res["ok"]:
        return 0
    for r in res["regressions"]:
        print("perf_ledger: REGRESSION %s (%s): candidate=%s vs "
              "%s=%s allowed=%s" % (
                  r["metric"], r["reference"], r["candidate"],
                  r["reference"], r[r["reference"]], r["allowed"]),
              file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="perf-regression ledger over the BENCH_r*/"
                    "MULTICHIP_r* round history")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="history directory (default: the repo root)")
    ap.add_argument("--out", help="write the trajectory JSON here "
                                  "instead of stdout")
    ap.add_argument("--pretty", action="store_true")
    sub = ap.add_subparsers(dest="cmd")
    chk = sub.add_parser("check", help="gate a fresh bench aggregate "
                                       "against the history")
    chk.add_argument("--candidate", required=True,
                     help="aggregate JSON object file or raw bench "
                          "stdout (last JSON line wins)")
    # own dest: a subparser default for "dir" would CLOBBER a --dir
    # given before the subcommand (argparse applies subparser
    # defaults over already-parsed parent values)
    chk.add_argument("--dir", dest="check_dir", default=None)
    chk.add_argument("--tolerance", type=float, default=None,
                     help="override the default pct tolerance for "
                          "every metric")
    chk.add_argument("--set", action="append", metavar="METRIC=PCT",
                     help="per-metric tolerance override "
                          "(repeatable)")
    chk.add_argument("--include-cpu", action="store_true",
                     help="compare a CPU parity-rig candidate "
                          "against device-round history anyway")
    chk.add_argument("--pretty", dest="check_pretty",
                     action="store_true", default=None)
    args = ap.parse_args(argv)
    if args.cmd == "check":
        return _cmd_check(args)
    return _cmd_trajectory(args)


if __name__ == "__main__":
    raise SystemExit(main())
