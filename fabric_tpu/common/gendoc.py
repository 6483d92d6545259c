"""Metrics reference generator.

Rebuild of `common/metrics/gendoc/` (which AST-walks the Go tree for
`*Opts` literals and renders `docs/source/metrics_reference.rst`): this
walks the `fabric_tpu` package with `ast`, collects every
`CounterOpts/GaugeOpts/HistogramOpts(...)` call whose fields are
literals, and renders `docs/metrics_reference.md`.

Regeneration contract: after adding/changing ANY literal `*Opts(...)`
declaration, run `python -m fabric_tpu.common.gendoc` and commit the
doc. `--check` regenerates in memory and exits 1 with a unified diff
on any drift — enforced by `tests/test_observability.py`, by
`tools/ftpu_lint.py`'s metric-drift rule, and by the
`tools/static_check.sh` CI gate.

Dynamically-named instruments (e.g. the BCCSP provider-stats gauges,
whose names mirror `TPUProvider.stats` keys at runtime) cannot be
enumerated statically and are listed in the doc's epilogue instead.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass

_KINDS = {"CounterOpts": "counter", "GaugeOpts": "gauge",
          "HistogramOpts": "histogram"}

DOC_RELPATH = os.path.join("docs", "metrics_reference.md")

EPILOGUE = """\
## Dynamically-named instruments

- `bccsp_<stat>` — one gauge per `TPUProvider.stats` counter
  (comb/ladder dispatches, the key-table pool's lookups, hits,
  builds, evictions, resident keys and bytes, sw fallbacks …),
  published by
  `fabric_tpu/common/profiling.py publish_provider_stats`.
- `trace_stage_seconds{stage=<span>}` — the stage label is the span
  name. The block-intake spans, a fixed number per block and per
  provider call (registry and nesting: ARCHITECTURE.md, "Block-intake
  span tree"): `peer.verify_block`, `peer.block`, `commit.validate`,
  `validate.prep`, `validate.policy`, `validate.flags`, `tpu.verify`,
  `tpu.stage`, `tpu.comb_digest`, `tpu.tables`, `tpu.table_build`
  (one a key admitted to the pool, none in a steady block), `tpu.h2d`,
  `tpu.enqueue`, `tpu.wait`, `tpu.readback`, `intake.rwsets`,
  `intake.txids`, `commit.commit`, `commit.pvt`, `commit.notify`,
  `ledger.mvcc`, `ledger.blockstore`, `blockstore.append`,
  `blockstore.index`, `ledger.settle`, `ledger.history`,
  `ledger.state`, `kvdb.write` and its `kvdb.commit` (one each a
  commit of a store: three a block), `runtime.gc`; and on the ledger
  store's checkpoint thread, outside any block's tree,
  `ledger.checkpoint`. Once JAX is imported every span is also a
  `jax.profiler.TraceAnnotation`, so a `/debug/jax/trace` capture
  carries them on a host line beside the device operations.
"""


@dataclass(frozen=True)
class MetricDoc:
    kind: str
    namespace: str
    subsystem: str
    name: str
    help: str
    label_names: tuple
    file: str

    @property
    def fqname(self) -> str:
        return "_".join(p for p in (self.namespace, self.subsystem,
                                    self.name) if p)


def _literal(node):
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def collect(root: str) -> list[MetricDoc]:
    """Every statically-declared metric under `root`'s fabric_tpu
    package (tests and tools excluded), sorted by fq name. Distinct
    declarations sharing an fq name are all returned — collision
    detection is the caller's job (tests/test_observability.py)."""
    out = set()
    pkg = os.path.join(root, "fabric_tpu")
    for dirpath, _dirnames, filenames in os.walk(pkg):
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            with open(path, encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read())
                except SyntaxError:
                    continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                attr = func.attr if isinstance(func, ast.Attribute) \
                    else getattr(func, "id", "")
                kind = _KINDS.get(attr)
                if kind is None:
                    continue
                kw = {k.arg: _literal(k.value) for k in node.keywords}
                if not kw.get("name"):
                    continue   # dynamically named → epilogue
                out.add(MetricDoc(
                    kind=kind,
                    namespace=kw.get("namespace") or "",
                    subsystem=kw.get("subsystem") or "",
                    name=kw["name"],
                    help=(kw.get("help") or "").strip(),
                    label_names=tuple(kw.get("label_names") or ()),
                    file=rel))
    return sorted(out, key=lambda d: (d.fqname, d.file))


def generate(root: str) -> str:
    docs = collect(root)
    lines = [
        "# Metrics reference",
        "",
        "Every metric the framework can emit, generated from the "
        "source tree by",
        "`python -m fabric_tpu.common.gendoc` (the analog of the "
        "reference's",
        "`common/metrics/gendoc` → `docs/source/metrics_reference."
        "rst`). Metrics are",
        "exposed in Prometheus text format on the operations "
        "endpoint's `/metrics`",
        "(or pushed via statsd), per `operations.metrics.provider`.",
        "",
        "Do not edit by hand: after changing any literal "
        "`*Opts(...)` declaration,",
        "regenerate and commit — `gendoc --check` (run by "
        "`tools/static_check.sh`,",
        "the ftpu_lint metric-drift rule, and "
        "tests/test_observability.py) fails CI",
        "with a unified diff on any drift.",
        "",
    ]
    for kind, title in (("counter", "Counters"), ("gauge", "Gauges"),
                        ("histogram", "Histograms")):
        rows = [d for d in docs if d.kind == kind]
        if not rows:
            continue
        lines += [f"## {title}", "",
                  "| Name | Labels | Description | Declared in |",
                  "|---|---|---|---|"]
        for d in rows:
            labels = ", ".join(d.label_names) or "—"
            lines.append(f"| `{d.fqname}` | {labels} | {d.help} "
                         f"| `{d.file}` |")
        lines.append("")
    lines.append(EPILOGUE)
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--check", action="store_true",
                        help="exit 1 if the committed doc is stale")
    parser.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    args = parser.parse_args(argv)
    doc_path = os.path.join(args.root, DOC_RELPATH)
    rendered = generate(args.root)
    if args.check:
        try:
            with open(doc_path, encoding="utf-8") as f:
                current = f.read()
        except FileNotFoundError:
            current = ""
        if current != rendered:
            import difflib
            print(f"{doc_path} is stale: regenerate with "
                  f"python -m fabric_tpu.common.gendoc")
            for line in difflib.unified_diff(
                    current.splitlines(), rendered.splitlines(),
                    fromfile="committed", tofile="generated",
                    lineterm=""):
                print(line)
            return 1
        print(f"{doc_path} is current")
        return 0
    os.makedirs(os.path.dirname(doc_path), exist_ok=True)
    with open(doc_path, "w", encoding="utf-8") as f:
        f.write(rendered)
    print(f"wrote {doc_path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
