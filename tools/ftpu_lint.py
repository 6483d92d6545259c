#!/usr/bin/env python3
"""ftpu_lint — project-invariant AST linter for the fabric_tpu tree.

The rebuild's correctness rests on stringly-typed seams nothing used
to cross-check: a typo'd `faults.check("commit.validate_head")` arms
nothing and the chaos suite passes vacuously; an undocumented
`CounterOpts` silently drifts out of `docs/metrics_reference.md`; an
`except Exception: pass` in a daemon loop hides real failures; a
stray `.item()` in an overlapped verify span stalls the device
pipeline. `go vet` caught the Go tree's equivalents — this is the
Python tree's equivalent, enforced by `tools/static_check.sh`.

Rules (each waivable per line with `# ftpu-lint: allow-<rule>(<reason>)`
on the flagged line or the line above; the reason is mandatory):

  fault-point    every `faults.check/arm/armed/disarm/fires("...")`
                 string literal must be declared in the canonical
                 `KNOWN_POINTS` registry in fabric_tpu/common/faults.py
                 (waiver: allow-fault-point)
  metric-drift   every statically-declared CounterOpts/GaugeOpts/
                 HistogramOpts must round-trip through
                 fabric_tpu/common/gendoc.py into
                 docs/metrics_reference.md (regenerate with
                 `python -m fabric_tpu.common.gendoc`)
  silent-swallow `except Exception/BaseException/bare: pass` is an
                 error — log at warning with context, or waive with
                 allow-swallow(<why swallowing is correct here>)
  host-sync      `.item()`, `float()`, `bool()`, `np.asarray` inside a
                 function decorated `@hot_path`
                 (fabric_tpu/common/hotpath.py) — host syncs that
                 stall the overlapped device spans; the deliberate
                 end-of-span materialization points carry
                 allow-host-sync waivers
  hot-path-coverage
                 the dispatch spans named in REQUIRED_HOT_PATHS (the
                 overlapped/sharded verify spans in bccsp/tpu.py, the
                 commit-pipeline validate worker) must exist and carry
                 the `@hot_path` decorator — dropping it silently
                 disarms the host-sync rule for exactly the code it
                 was written for (no waiver: the registry IS the
                 waiver; update it on a rename)
  unbounded-queue
                 creating an UNBOUNDED `queue.Queue()` (or an explicit
                 `maxsize=0`) is an error — unbounded inter-stage
                 queues are the overload failure mode round 12
                 removed (indefinite blocking or unbounded memory at
                 saturation). Use `common/overload.SheddingQueue`
                 (deadline-aware, shed-counting) or pass an explicit
                 positive bound with a Full policy; waive a deliberate
                 site with allow-unbounded-queue(<reason>)
  span-coverage  every function in the REQUIRED_SPANS registry (the
                 REQUIRED_HOT_PATHS dispatch spans plus the pipeline
                 stage workers) must open a lifecycle tracing span —
                 a `@traced("...")` decorator or a
                 span/timed/observe_span/observe_stage/instant call
                 (common/tracing.py). Dropping it silently blinds the
                 flight recorder and the per-stage histograms on
                 exactly the code they were written for (no waiver:
                 the registry IS the waiver; update it on a rename)

Usage:
  python tools/ftpu_lint.py [--root DIR] [--rules r1,r2] [files...]

Exit status: 0 clean, 1 findings, 2 usage/setup error.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from dataclasses import dataclass

ALL_RULES = ("fault-point", "metric-drift", "silent-swallow",
             "host-sync", "hot-path-coverage", "unbounded-queue",
             "span-coverage")

# The spans the host-sync rule exists FOR: every overlapped/sharded
# device-dispatch span. A span here without @hot_path is a finding —
# removing the decorator would silently disarm host-sync checking on
# the exact code paths where a stray host sync stalls the pipeline.
REQUIRED_HOT_PATHS = {
    "fabric_tpu/bccsp/tpu.py": (
        "_dispatch_arrays", "_verify_batch_pipelined",
        "_dispatch_comb_digest", "_dispatch_comb", "_shard_put",
        # round-11 scheme router: the Ed25519 device dispatch span
        "_dispatch_ed25519",
        # round-21 pairing engine: the batched BLS12-381
        # Miller-product dispatch span
        "_dispatch_bls_pairing",
        # round-13 elastic mesh: the degraded-mesh rebuild runs on
        # the dispatch path (admission hook, between batches) — a
        # host sync smuggled in here would stall every batch behind
        # the swap
        "_rebuild_mesh",
    ),
    "fabric_tpu/core/commitpipeline.py": ("_validate_one",),
    # round-10 ordering spans: the batched raft propose and the
    # ingress-verify admission window
    "fabric_tpu/orderer/raft/chain.py": ("_propose_batch",),
    "fabric_tpu/bccsp/admission.py": ("_dispatch_window",),
}

# The span-coverage registry (round 14): every dispatch span above
# must ALSO open a lifecycle tracing span, and so must the pipeline
# stage workers listed here — the per-stage latency histograms and
# the flight recorder are only as complete as this coverage. Like
# REQUIRED_HOT_PATHS, the registry is the waiver: renames update it.
REQUIRED_SPANS = {path: tuple(funcs)
                  for path, funcs in REQUIRED_HOT_PATHS.items()}
for _path, _funcs in {
    # registered pipeline stages: ingress batching, the order window,
    # the async block-write worker, commit-pipeline stage B, and the
    # round-15 network-chaos deferred-delivery worker (its flush
    # stage is the evidence a chaos soak's delays actually ran)
    "fabric_tpu/comm/services.py": ("broadcast_stream",),
    "fabric_tpu/orderer/raft/chain.py": ("_process_order_window",),
    "fabric_tpu/orderer/raft/pipeline.py": ("_write_loop",),
    "fabric_tpu/core/commitpipeline.py": ("_commit_loop",),
    "fabric_tpu/common/netchaos.py": ("_pump_loop",),
    # round-16 compile seam: the shared classification path (every
    # first-shape dispatch and AOT prewarm compile funnels through
    # it) must open its `tpu.compile` span — the compile telemetry
    # and the cold-compile postmortem dumps ride it
    "fabric_tpu/common/devicecost.py": ("run_compile",),
    # round-18 carrier EXTRACTION seams: every cross-node transport
    # drain (cluster consensus, cluster gRPC, gossip) and the deliver
    # feeder must resume the wire carrier (clustertrace.resumed) — a
    # new transport path that skips this silently drops propagation
    # and the cluster trace falls apart into per-node orphans
    "fabric_tpu/orderer/cluster.py": ("_drain", "handle_submit"),
    "fabric_tpu/comm/cluster_grpc.py": ("_drain", "handle_submit"),
    "fabric_tpu/gossip/transport.py": ("_drain",),
    "fabric_tpu/peer/deliverclient.py": ("_pull",),
    # note_commit records the e2e finality observation — rename it
    # and every commit seam goes blind at once (`resumed` is covered
    # transitively: it is itself a recognized span-opening call, so a
    # seam that drops it trips the entries above)
    "fabric_tpu/common/clustertrace.py": ("note_commit",),
    # the block-intake span tree (docs/metrics_reference.md, "Block-
    # intake spans"): the benchmark's per-layer metrics read these
    # spans by name from the flight recorder, so a rename that drops
    # one must fail here and not silently blind a metric
    "fabric_tpu/peer/peer.py": ("process_block", "commit_validated"),
    "fabric_tpu/ledger/kvledger.py": ("commit_block",),
    "fabric_tpu/ledger/blkstorage.py": ("add_block",),
    # every commit of a store (`kvdb.write` / `kvdb.commit`): what
    # the ledger-commit metrics split into statements and WAL frames
    "fabric_tpu/ledger/kvdb.py": ("_writing",),
    "fabric_tpu/core/fastvalidate.py": ("validate_fast",),
    "fabric_tpu/bccsp/tpu.py": ("_verify_prepared_device",
                                "_dispatch_chunks", "_key_slots",
                                "_admit_key"),
}.items():
    REQUIRED_SPANS[_path] = REQUIRED_SPANS.get(_path, ()) + _funcs

_WAIVER_RE = re.compile(
    r"#\s*ftpu-lint:\s*allow-([a-z-]+)\(\s*(.*?)\s*\)?\s*$")
_WAIVER_KINDS = ("swallow", "fault-point", "host-sync",
                 "unbounded-queue")

_FAULT_METHODS = {"check", "arm", "armed", "disarm", "fires",
                  # round 15: the read/consume accessors netchaos
                  # drives the net.* points through — a typo'd
                  # literal there is just as vacuous as one in check()
                  "arming", "consume"}
_HOST_SYNC_BUILTINS = {"float", "bool"}
_NP_NAMES = {"np", "numpy"}


@dataclass(frozen=True)
class Finding:
    path: str        # repo-relative
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class _Waivers:
    """Per-file `# ftpu-lint: allow-<rule>(reason)` comments, keyed by
    line. A waiver covers findings of its rule on its own line, or
    anywhere in the contiguous comment block directly above the
    flagged line (the reason may wrap onto following comment lines)."""

    def __init__(self, source: str):
        self._lines = source.splitlines()
        self._by_line: dict[int, tuple[str, str]] = {}
        self.malformed: list[tuple[int, str]] = []
        for i, text in enumerate(self._lines, start=1):
            m = _WAIVER_RE.search(text)
            if not m:
                continue
            rule, reason = m.group(1), m.group(2).strip()
            if rule not in _WAIVER_KINDS:
                self.malformed.append(
                    (i, f"unknown waiver `allow-{rule}` — known: "
                        + ", ".join(f"allow-{k}"
                                    for k in _WAIVER_KINDS)))
                continue
            if not reason:
                self.malformed.append(
                    (i, "ftpu-lint waiver without a reason — write "
                        "`# ftpu-lint: allow-<rule>(<why>)`"))
                continue
            self._by_line[i] = (rule, reason)

    def _is_comment_only(self, ln: int) -> bool:
        if not (1 <= ln <= len(self._lines)):
            return False
        return self._lines[ln - 1].lstrip().startswith("#")

    def covers(self, kind: str, *lines: int) -> bool:
        """`kind` is the waiver suffix (`allow-<kind>`): "swallow",
        "fault-point", "host-sync"."""
        for ln in lines:
            got = self._by_line.get(ln)
            if got and got[0] == kind:
                return True
            cand = ln - 1
            while self._is_comment_only(cand):
                got = self._by_line.get(cand)
                if got and got[0] == kind:
                    return True
                cand -= 1
        return False


def _repo_root_default() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_known_points(root: str):
    """AST-parse the canonical KNOWN_POINTS declaration out of
    fabric_tpu/common/faults.py (no import: the linter must stay
    runnable against any tree state). Returns (points, error)."""
    path = os.path.join(root, "fabric_tpu", "common", "faults.py")
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError) as e:
        return None, f"cannot parse {path}: {e}"
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "KNOWN_POINTS"
                   for t in node.targets):
            continue
        value = node.value
        if isinstance(value, ast.Call) and \
                isinstance(value.func, ast.Name) and \
                value.func.id in ("frozenset", "set") and value.args:
            value = value.args[0]
        try:
            return frozenset(ast.literal_eval(value)), None
        except (ValueError, SyntaxError) as e:
            return None, f"KNOWN_POINTS is not a literal set: {e}"
    return None, (f"{path} declares no KNOWN_POINTS registry "
                  f"(the fault-point rule's source of truth)")


# -- rule: fault-point --

def _fault_point_findings(rel, tree, waivers, known_points):
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in _FAULT_METHODS):
            continue
        base = func.value
        base_name = base.id if isinstance(base, ast.Name) else (
            base.attr if isinstance(base, ast.Attribute) else "")
        if base_name != "faults":
            continue
        point = None
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            point = node.args[0].value
        else:
            for kw in node.keywords:
                if kw.arg == "point" and \
                        isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, str):
                    point = kw.value.value
        if point is None:
            continue    # dynamic point name: the runtime warn covers it
        if point in known_points:
            continue
        if waivers.covers("fault-point", node.lineno):
            continue
        out.append(Finding(
            rel, node.lineno, "fault-point",
            f"fault point {point!r} is not declared in "
            f"fabric_tpu/common/faults.py KNOWN_POINTS — a typo here "
            f"arms nothing and chaos passes go vacuous"))
    return out


# -- rule: silent-swallow --

def _is_broad_exc(expr) -> bool:
    if expr is None:
        return True     # bare except
    if isinstance(expr, ast.Name):
        return expr.id in ("Exception", "BaseException")
    if isinstance(expr, ast.Tuple):
        return any(_is_broad_exc(e) for e in expr.elts)
    return False


def _swallow_findings(rel, tree, waivers):
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad_exc(node.type):
            continue
        body = node.body
        swallows = (len(body) == 1 and (
            isinstance(body[0], ast.Pass)
            or (isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and body[0].value.value is Ellipsis)))
        if not swallows:
            continue
        if waivers.covers("swallow", node.lineno, body[0].lineno):
            continue
        what = ast.unparse(node.type) if node.type is not None \
            else "<bare>"
        out.append(Finding(
            rel, node.lineno, "silent-swallow",
            f"`except {what}: pass` swallows failures silently — log "
            f"at warning with context or waive with "
            f"`# ftpu-lint: allow-swallow(<reason>)`"))
    return out


# -- rule: host-sync --

def _is_hot_path_decorator(dec) -> bool:
    target = dec.func if isinstance(dec, ast.Call) else dec
    if isinstance(target, ast.Name):
        return target.id == "hot_path"
    if isinstance(target, ast.Attribute):
        return target.attr == "hot_path"
    return False


def _host_sync_findings(rel, tree, waivers):
    out = []
    hot_funcs = [
        node for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and any(_is_hot_path_decorator(d) for d in node.decorator_list)
    ]
    for fn in hot_funcs:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            label = None
            if isinstance(func, ast.Attribute) and \
                    func.attr == "item" and not node.args:
                label = ".item()"
            elif isinstance(func, ast.Name) and \
                    func.id in _HOST_SYNC_BUILTINS:
                label = f"{func.id}()"
            elif isinstance(func, ast.Attribute) and \
                    func.attr == "asarray" and \
                    isinstance(func.value, ast.Name) and \
                    func.value.id in _NP_NAMES:
                label = f"{func.value.id}.asarray()"
            if label is None:
                continue
            if waivers.covers("host-sync", node.lineno):
                continue
            out.append(Finding(
                rel, node.lineno, "host-sync",
                f"{label} inside @hot_path `{fn.name}` forces a host "
                f"sync mid-span — hoist it out of the overlapped "
                f"region or waive the deliberate materialization "
                f"point with `# ftpu-lint: allow-host-sync(<reason>)`"))
    return out


# -- rule: hot-path-coverage --

def _hot_coverage_findings(rel, tree):
    want = REQUIRED_HOT_PATHS.get(rel.replace(os.sep, "/"))
    if not want:
        return []
    out = []
    fns: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(node.name, node)
    for name in want:
        fn = fns.get(name)
        if fn is None:
            out.append(Finding(
                rel, 1, "hot-path-coverage",
                f"required @hot_path span `{name}` no longer exists — "
                f"if it was renamed, update REQUIRED_HOT_PATHS in "
                f"tools/ftpu_lint.py so the host-sync rule keeps "
                f"covering it"))
        elif not any(_is_hot_path_decorator(d)
                     for d in fn.decorator_list):
            out.append(Finding(
                rel, fn.lineno, "hot-path-coverage",
                f"dispatch span `{name}` must carry @hot_path "
                f"(fabric_tpu/common/hotpath.py): without it the "
                f"host-sync rule is silently disarmed on the code it "
                f"was written for"))
    return out


# -- rule: span-coverage --

_SPAN_CALLS = {"span", "timed", "observe_span", "observe_stage",
               "instant",
               # round 18: the carrier-resume primitive opens the
               # hop.recv span — extraction seams satisfy span
               # coverage through it
               "resumed"}


def _is_traced_decorator(dec) -> bool:
    target = dec.func if isinstance(dec, ast.Call) else dec
    if isinstance(target, ast.Name):
        return target.id == "traced"
    if isinstance(target, ast.Attribute):
        return target.attr == "traced"
    return False


def _opens_span(fn) -> bool:
    """True when `fn` carries a @traced decorator or (anywhere in its
    body, nested closures included — broadcast_stream's span lives in
    its flush_run closure) calls span()/timed()/observe_span()/
    observe_stage()/instant() — plain or as tracing.<name>."""
    if any(_is_traced_decorator(d) for d in fn.decorator_list):
        return True
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = func.id if isinstance(func, ast.Name) else (
            func.attr if isinstance(func, ast.Attribute) else "")
        if name in _SPAN_CALLS:
            return True
    return False


def _span_coverage_findings(rel, tree):
    want = REQUIRED_SPANS.get(rel.replace(os.sep, "/"))
    if not want:
        return []
    out = []
    fns: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(node.name, node)
    for name in want:
        fn = fns.get(name)
        if fn is None:
            out.append(Finding(
                rel, 1, "span-coverage",
                f"required traced stage `{name}` no longer exists — "
                f"if it was renamed, update REQUIRED_SPANS in "
                f"tools/ftpu_lint.py so the lifecycle-tracing rule "
                f"keeps covering it"))
        elif not _opens_span(fn):
            out.append(Finding(
                rel, fn.lineno, "span-coverage",
                f"pipeline stage `{name}` opens no lifecycle tracing "
                f"span (common/tracing.py): add @traced(...) or a "
                f"span()/observe_span() call, or the flight recorder "
                f"and per-stage histograms go blind on exactly this "
                f"stage"))
    return out


# -- rule: unbounded-queue --

_QUEUE_CLASSES = {"Queue", "LifoQueue", "PriorityQueue"}


def _queue_aliases(tree):
    """(module aliases of `queue`, direct names of its classes) as
    imported by this file — resolution is import-based so a local
    class named Queue is never flagged."""
    mod_aliases: set = set()
    cls_names: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "queue":
                    mod_aliases.add(a.asname or "queue")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "queue":
                for a in node.names:
                    if a.name in _QUEUE_CLASSES:
                        cls_names.add(a.asname or a.name)
    return mod_aliases, cls_names


def _unbounded_queue_findings(rel, tree, waivers):
    mod_aliases, cls_names = _queue_aliases(tree)
    if not mod_aliases and not cls_names:
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        is_queue = (
            (isinstance(func, ast.Attribute)
             and func.attr in _QUEUE_CLASSES
             and isinstance(func.value, ast.Name)
             and func.value.id in mod_aliases)
            or (isinstance(func, ast.Name) and func.id in cls_names))
        if not is_queue:
            continue
        size = None
        if node.args:
            size = node.args[0]
        for kw in node.keywords:
            if kw.arg == "maxsize":
                size = kw.value
        unbounded = size is None or (
            isinstance(size, ast.Constant)
            and isinstance(size.value, (int, float))
            and size.value <= 0)
        # a non-constant maxsize expression counts as bounded: the
        # bound is the call site's contract (SheddingQueue rejects
        # non-positive bounds at runtime)
        if not unbounded:
            continue
        if waivers.covers("unbounded-queue", node.lineno):
            continue
        out.append(Finding(
            rel, node.lineno, "unbounded-queue",
            "unbounded queue.Queue() — at saturation this is "
            "indefinite blocking or unbounded memory, the round-12 "
            "overload failure mode; use common/overload.SheddingQueue "
            "(deadline-aware put + shed accounting) or an explicit "
            "positive maxsize with a Full policy, or waive a "
            "deliberate site with "
            "`# ftpu-lint: allow-unbounded-queue(<reason>)`"))
    return out


# -- rule: metric-drift --

def _metric_drift_findings(root):
    import importlib.util
    gendoc_path = os.path.join(root, "fabric_tpu", "common",
                               "gendoc.py")
    spec = importlib.util.spec_from_file_location("_ftpu_lint_gendoc",
                                                  gendoc_path)
    if spec is None or spec.loader is None:
        return [Finding(os.path.join("fabric_tpu", "common",
                                     "gendoc.py"), 1, "metric-drift",
                        "cannot load gendoc for the drift check")]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # dataclasses resolves __module__
    spec.loader.exec_module(mod)
    # delegate the comparison to gendoc's own --check so there is ONE
    # source of truth for what "stale" means (its diff output is
    # swallowed here — the finding points the user at the command)
    import contextlib
    import io
    with contextlib.redirect_stdout(io.StringIO()):
        rc = mod.main(["--check", "--root", root])
    if rc == 0:
        return []
    return [Finding(
        mod.DOC_RELPATH, 1, "metric-drift",
        "metrics reference is stale vs the declared *Opts literals — "
        "run `python -m fabric_tpu.common.gendoc --check` for the "
        "diff, regenerate with `python -m fabric_tpu.common.gendoc`")]


# -- driver --

def iter_source_files(root: str):
    pkg = os.path.join(root, "fabric_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def run_lint(root: str, rules=ALL_RULES, files=None) -> list:
    findings: list[Finding] = []
    known_points = frozenset()
    if "fault-point" in rules:
        known_points, err = load_known_points(root)
        if err is not None:
            findings.append(Finding(
                os.path.join("fabric_tpu", "common", "faults.py"), 1,
                "fault-point", err))
            known_points = frozenset()
    paths = list(files) if files else list(iter_source_files(root))
    for path in paths:
        rel = os.path.relpath(path, root)
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source)
        except (OSError, SyntaxError) as e:
            findings.append(Finding(rel, 1, "parse",
                                    f"cannot lint: {e}"))
            continue
        waivers = _Waivers(source)
        for ln, msg in waivers.malformed:
            findings.append(Finding(rel, ln, "waiver", msg))
        if "fault-point" in rules:
            findings += _fault_point_findings(rel, tree, waivers,
                                              known_points)
        if "silent-swallow" in rules:
            findings += _swallow_findings(rel, tree, waivers)
        if "host-sync" in rules:
            findings += _host_sync_findings(rel, tree, waivers)
        if "hot-path-coverage" in rules:
            findings += _hot_coverage_findings(rel, tree)
        if "span-coverage" in rules:
            findings += _span_coverage_findings(rel, tree)
        if "unbounded-queue" in rules:
            findings += _unbounded_queue_findings(rel, tree, waivers)
    if "metric-drift" in rules and not files:
        findings += _metric_drift_findings(root)
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="fabric_tpu project-invariant linter")
    parser.add_argument("--root", default=_repo_root_default(),
                        help="repo root (holds fabric_tpu/ and docs/)")
    parser.add_argument("--rules", default=",".join(ALL_RULES),
                        help=f"comma list from {ALL_RULES}")
    parser.add_argument("files", nargs="*",
                        help="limit per-file rules to these files "
                             "(metric-drift is tree-wide and skipped)")
    args = parser.parse_args(argv)
    rules = tuple(r.strip() for r in args.rules.split(",") if r.strip())
    unknown = [r for r in rules if r not in ALL_RULES]
    if unknown:
        print(f"ftpu_lint: unknown rule(s) {unknown}; "
              f"known: {ALL_RULES}", file=sys.stderr)
        return 2
    findings = run_lint(args.root, rules=rules,
                        files=args.files or None)
    for f in findings:
        print(f.render())
    if findings:
        print(f"ftpu_lint: {len(findings)} finding(s)")
        return 1
    nfiles = len(args.files) if args.files else \
        sum(1 for _ in iter_source_files(args.root))
    print(f"ftpu_lint: clean ({nfiles} files, "
          f"rules: {', '.join(rules)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
