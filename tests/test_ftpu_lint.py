"""Project-invariant linter tests (ISSUE 5 tentpole, static half).

Two contracts: (1) the tree at HEAD is CLEAN — zero unwaived findings,
which is what lets tools/static_check.sh gate CI; (2) deliberately
seeded violations of every rule class (unknown fault point,
undocumented metric, bare swallow, host-sync in a @hot_path span) are
caught, and the `# ftpu-lint: allow-*` waiver grammar suppresses
exactly what it names. Plus the runtime half of the fault-point seam:
`Registry.arm()` warns on names outside KNOWN_POINTS.
"""

import importlib.util
import logging
import os
import shutil
import sys
import textwrap

import pytest

from fabric_tpu.common import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "_ftpu_lint_under_test",
        os.path.join(REPO, "tools", "ftpu_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lint():
    return _load_lint()


def _seed_tree(root) -> str:
    """A minimal lintable tree: the REAL faults.py/gendoc.py (so
    KNOWN_POINTS and the doc renderer are authentic), docs generated
    clean, no violations yet."""
    common = os.path.join(root, "fabric_tpu", "common")
    os.makedirs(common)
    open(os.path.join(root, "fabric_tpu", "__init__.py"), "w").close()
    open(os.path.join(common, "__init__.py"), "w").close()
    for fn in ("faults.py", "gendoc.py"):
        shutil.copy(os.path.join(REPO, "fabric_tpu", "common", fn),
                    os.path.join(common, fn))
    return root


def _regen_docs(root):
    spec = importlib.util.spec_from_file_location(
        "_seed_gendoc", os.path.join(root, "fabric_tpu", "common",
                                     "gendoc.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    doc = os.path.join(root, mod.DOC_RELPATH)
    os.makedirs(os.path.dirname(doc), exist_ok=True)
    with open(doc, "w", encoding="utf-8") as f:
        f.write(mod.generate(root))


class TestSeededViolations:
    @pytest.fixture()
    def seeded(self, tmp_path, lint):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)          # docs clean BEFORE the seed module
        seed = textwrap.dedent('''\
            from fabric_tpu.common import faults
            from fabric_tpu.common.hotpath import hot_path
            import numpy as np

            def CounterOpts(**kw):
                return kw

            SEEDED = CounterOpts(namespace="seeded",
                                 name="drift_total",
                                 help="undocumented on purpose")

            def poke():
                faults.check("commit.validate_head")   # the typo

            def swallow():
                try:
                    poke()
                except Exception:
                    pass

            @hot_path
            def hot(arr):
                dev = np.asarray(arr)
                return float(dev.item())
        ''')
        with open(os.path.join(root, "fabric_tpu", "seed.py"),
                  "w") as f:
            f.write(seed)
        return root

    def test_each_rule_class_caught(self, lint, seeded):
        findings = lint.run_lint(seeded)
        rules = {f.rule for f in findings}
        assert rules == {"fault-point", "silent-swallow", "host-sync",
                         "metric-drift"}
        fp = [f for f in findings if f.rule == "fault-point"]
        assert len(fp) == 1 and "commit.validate_head" in fp[0].message
        assert fp[0].path.endswith("seed.py")
        hs = [f for f in findings if f.rule == "host-sync"]
        # np.asarray, float(), .item() — all three sync idioms
        assert len(hs) == 3
        assert any(".item()" in f.message for f in hs)
        assert any("float()" in f.message for f in hs)
        assert any("np.asarray()" in f.message for f in hs)
        sw = [f for f in findings if f.rule == "silent-swallow"]
        assert len(sw) == 1
        md = [f for f in findings if f.rule == "metric-drift"]
        assert len(md) == 1 and "stale" in md[0].message

    def test_waivers_suppress_exactly_what_they_name(self, lint,
                                                     seeded):
        path = os.path.join(seeded, "fabric_tpu", "seed.py")
        with open(path) as f:
            src = f.read()
        src = src.replace(
            '    faults.check("commit.validate_head")   # the typo',
            '    # ftpu-lint: allow-fault-point(seeded test waiver)\n'
            '    faults.check("commit.validate_head")')
        src = src.replace(
            "    except Exception:\n        pass",
            "    # ftpu-lint: allow-swallow(seeded test waiver)\n"
            "    except Exception:\n        pass")
        src = src.replace(
            "    dev = np.asarray(arr)",
            "    # ftpu-lint: allow-host-sync(seeded test waiver)\n"
            "    dev = np.asarray(arr)")
        src = src.replace(
            "    return float(dev.item())",
            "    # ftpu-lint: allow-host-sync(seeded test waiver)\n"
            "    return float(dev.item())")
        with open(path, "w") as f:
            f.write(src)
        _regen_docs(seeded)        # clears the drift too
        assert lint.run_lint(seeded) == []

    def test_waiver_reason_is_mandatory(self, lint, seeded):
        path = os.path.join(seeded, "fabric_tpu", "seed.py")
        with open(path) as f:
            src = f.read()
        src = src.replace(
            "    except Exception:\n        pass",
            "    # ftpu-lint: allow-swallow()\n"
            "    except Exception:\n        pass")
        with open(path, "w") as f:
            f.write(src)
        findings = lint.run_lint(seeded)
        assert any(f.rule == "waiver" and "without a reason"
                   in f.message for f in findings)
        # and the reasonless waiver does NOT suppress the swallow
        assert any(f.rule == "silent-swallow" for f in findings)

    def test_waiver_reason_may_contain_parens(self, lint, seeded):
        path = os.path.join(seeded, "fabric_tpu", "seed.py")
        with open(path) as f:
            src = f.read()
        src = src.replace(
            "    except Exception:\n        pass",
            "    # ftpu-lint: allow-swallow(close() raises on a dead "
            "channel)\n"
            "    except Exception:\n        pass")
        with open(path, "w") as f:
            f.write(src)
        findings = lint.run_lint(seeded)
        assert not any(f.rule in ("silent-swallow", "waiver")
                       for f in findings)

    def test_unknown_waiver_rule_is_reported(self, lint, seeded):
        path = os.path.join(seeded, "fabric_tpu", "seed.py")
        with open(path) as f:
            src = f.read()
        src = src.replace(
            "    except Exception:\n        pass",
            "    # ftpu-lint: allow-swalow(typo'd rule name)\n"
            "    except Exception:\n        pass")
        with open(path, "w") as f:
            f.write(src)
        findings = lint.run_lint(seeded)
        assert any(f.rule == "waiver" and "unknown waiver"
                   in f.message for f in findings)
        assert any(f.rule == "silent-swallow" for f in findings)

    def test_missing_known_points_is_a_finding(self, lint, tmp_path):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        faults_py = os.path.join(root, "fabric_tpu", "common",
                                 "faults.py")
        with open(faults_py, "w") as f:
            f.write("ENV_VAR = 'FTPU_FAULTS'\n")
        findings = lint.run_lint(root)
        assert any(f.rule == "fault-point" and "KNOWN_POINTS"
                   in f.message for f in findings)

    def test_gendoc_check_prints_diff(self, seeded, capsys):
        spec = importlib.util.spec_from_file_location(
            "_seed_gendoc_chk",
            os.path.join(seeded, "fabric_tpu", "common", "gendoc.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        assert mod.main(["--check", "--root", seeded]) == 1
        out = capsys.readouterr().out
        assert "stale" in out
        assert "+| `seeded_drift_total`" in out
        # regenerated -> clean
        assert mod.main(["--root", seeded]) == 0
        assert mod.main(["--check", "--root", seeded]) == 0


class TestHotPathCoverage:
    """Round-9 rule: the overlapped/sharded dispatch spans named in
    REQUIRED_HOT_PATHS must exist and carry @hot_path — dropping the
    decorator would silently disarm the host-sync rule on exactly the
    code it was written for."""

    def _seed_tpu(self, root, body: str):
        bccsp = os.path.join(root, "fabric_tpu", "bccsp")
        os.makedirs(bccsp, exist_ok=True)
        open(os.path.join(bccsp, "__init__.py"), "w").close()
        with open(os.path.join(bccsp, "tpu.py"), "w") as f:
            f.write(body)

    def _all_spans(self, lint, decorate=True):
        dec = "@hot_path\n" if decorate else ""
        fns = "".join(
            f"{dec}def {name}(*a, **kw):\n    return None\n\n"
            for name in
            lint.REQUIRED_HOT_PATHS["fabric_tpu/bccsp/tpu.py"])
        return ("from fabric_tpu.common.hotpath import hot_path\n\n"
                + fns)

    def test_undecorated_span_is_a_finding(self, lint, tmp_path):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        self._seed_tpu(root, self._all_spans(lint, decorate=False))
        findings = [f for f in lint.run_lint(root)
                    if f.rule == "hot-path-coverage"]
        assert len(findings) == len(
            lint.REQUIRED_HOT_PATHS["fabric_tpu/bccsp/tpu.py"])
        assert any("_shard_put" in f.message for f in findings)
        assert all("@hot_path" in f.message for f in findings)

    def test_missing_span_reports_registry_drift(self, lint,
                                                 tmp_path):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        body = self._all_spans(lint).replace(
            "def _shard_put", "def _shard_put_renamed")
        self._seed_tpu(root, body)
        findings = [f for f in lint.run_lint(root)
                    if f.rule == "hot-path-coverage"]
        assert len(findings) == 1
        assert "_shard_put" in findings[0].message
        assert "REQUIRED_HOT_PATHS" in findings[0].message

    def test_decorated_spans_are_clean(self, lint, tmp_path):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        self._seed_tpu(root, self._all_spans(lint))
        assert [f for f in lint.run_lint(root)
                if f.rule == "hot-path-coverage"] == []

    def test_registry_names_the_sharded_feeder(self, lint):
        """The round-9 sharded span is registered — the satellite's
        point: new dispatch spans extend the coverage list."""
        assert "_shard_put" in \
            lint.REQUIRED_HOT_PATHS["fabric_tpu/bccsp/tpu.py"]


class TestSpanCoverage:
    """Round-14 rule: every REQUIRED_SPANS function (the hot-path
    dispatch spans plus the pipeline stage workers) must open a
    lifecycle tracing span — a @traced decorator or a span()/
    observe_span()/observe_stage()/instant() call; dropping it blinds
    the flight recorder on exactly that stage."""

    def _seed_tpu(self, root, body: str):
        bccsp = os.path.join(root, "fabric_tpu", "bccsp")
        os.makedirs(bccsp, exist_ok=True)
        open(os.path.join(bccsp, "__init__.py"), "w").close()
        with open(os.path.join(bccsp, "tpu.py"), "w") as f:
            f.write(body)

    def _spans(self, lint, spanned=True, how="traced"):
        names = lint.REQUIRED_SPANS["fabric_tpu/bccsp/tpu.py"]
        out = ["from fabric_tpu.common.hotpath import hot_path",
               "from fabric_tpu.common import tracing", ""]
        for name in names:
            out.append("@hot_path")
            if spanned and how == "traced":
                out.append(f'@tracing.traced("tpu.{name}")')
            out.append(f"def {name}(*a, **kw):")
            if spanned and how == "with":
                out.append(f'    with tracing.span("tpu.{name}"):')
                out.append("        return None")
            elif spanned and how == "nested":
                out.append("    def inner():")
                out.append(f'        tracing.observe_stage('
                           f'"tpu.{name}", 0.0)')
                out.append("    return inner()")
            else:
                out.append("    return None")
            out.append("")
        return "\n".join(out)

    def test_unspanned_stage_is_a_finding(self, lint, tmp_path):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        self._seed_tpu(root, self._spans(lint, spanned=False))
        findings = [f for f in lint.run_lint(root)
                    if f.rule == "span-coverage"]
        assert len(findings) == len(
            lint.REQUIRED_SPANS["fabric_tpu/bccsp/tpu.py"])
        assert any("_dispatch_arrays" in f.message for f in findings)
        assert all("tracing" in f.message for f in findings)

    @pytest.mark.parametrize("how", ["traced", "with", "nested"])
    def test_each_span_spelling_is_clean(self, lint, tmp_path, how):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        self._seed_tpu(root, self._spans(lint, how=how))
        assert [f for f in lint.run_lint(root)
                if f.rule == "span-coverage"] == []

    def test_missing_stage_reports_registry_drift(self, lint,
                                                  tmp_path):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        body = self._spans(lint).replace("def _shard_put",
                                        "def _shard_put_renamed")
        self._seed_tpu(root, body)
        findings = [f for f in lint.run_lint(root)
                    if f.rule == "span-coverage"]
        assert len(findings) == 1
        assert "_shard_put" in findings[0].message
        assert "REQUIRED_SPANS" in findings[0].message

    def test_registry_covers_hot_paths_and_stage_workers(self, lint):
        """REQUIRED_SPANS is a superset of REQUIRED_HOT_PATHS and
        names the pipeline stage workers — the registry IS the rule's
        coverage claim."""
        for path, funcs in lint.REQUIRED_HOT_PATHS.items():
            for fn in funcs:
                assert fn in lint.REQUIRED_SPANS.get(path, ()), \
                    (path, fn)
        assert "_write_loop" in \
            lint.REQUIRED_SPANS["fabric_tpu/orderer/raft/pipeline.py"]
        assert "_commit_loop" in \
            lint.REQUIRED_SPANS["fabric_tpu/core/commitpipeline.py"]
        assert "broadcast_stream" in \
            lint.REQUIRED_SPANS["fabric_tpu/comm/services.py"]
        assert "_process_order_window" in \
            lint.REQUIRED_SPANS["fabric_tpu/orderer/raft/chain.py"]


class TestBlockIntakeSpanRegistry:
    """PR 27: the benchmark's per-layer metrics read the block-intake
    spans by name, so the functions that open them are registered: a
    rename (or a dropped span) fails the lint instead of blinding a
    metric."""

    INTAKE = {
        "fabric_tpu/peer/peer.py": ("process_block", "commit_validated"),
        "fabric_tpu/ledger/kvledger.py": ("commit_block",),
        "fabric_tpu/ledger/blkstorage.py": ("add_block",),
        "fabric_tpu/core/fastvalidate.py": ("validate_fast",),
        "fabric_tpu/bccsp/tpu.py": ("_verify_prepared_device",),
    }

    @pytest.mark.parametrize("path", sorted(INTAKE))
    def test_registered(self, lint, path):
        for fn in self.INTAKE[path]:
            assert fn in lint.REQUIRED_SPANS[path], (path, fn)

    def _seed(self, root, body: str):
        ledger = os.path.join(root, "fabric_tpu", "ledger")
        os.makedirs(ledger, exist_ok=True)
        open(os.path.join(ledger, "__init__.py"), "w").close()
        with open(os.path.join(ledger, "kvledger.py"), "w") as f:
            f.write(textwrap.dedent(body))

    def _findings(self, lint, root):
        return [f for f in lint.run_lint(root, rules=("span-coverage",))
                if f.path.endswith("kvledger.py")]

    def test_a_timed_span_satisfies_the_rule(self, lint, tmp_path):
        root = _seed_tree(str(tmp_path))
        self._seed(root, '''\
            from fabric_tpu.common import tracing

            def commit_block(block):
                t = tracing.timed("ledger.mvcc")
                with t:
                    pass
                return t.seconds
            ''')
        assert self._findings(lint, root) == []

    def test_a_stage_that_lost_its_span_is_a_finding(self, lint,
                                                     tmp_path):
        root = _seed_tree(str(tmp_path))
        self._seed(root, '''\
            import time

            def commit_block(block):
                t0 = time.perf_counter()
                return time.perf_counter() - t0
            ''')
        findings = self._findings(lint, root)
        assert len(findings) == 1 and "commit_block" in findings[0].message

    def test_a_renamed_stage_reports_registry_drift(self, lint,
                                                    tmp_path):
        root = _seed_tree(str(tmp_path))
        self._seed(root, '''\
            from fabric_tpu.common import tracing

            def commit_block_v2(block):
                with tracing.span("ledger.mvcc"):
                    pass
            ''')
        findings = self._findings(lint, root)
        assert len(findings) == 1
        assert "REQUIRED_SPANS" in findings[0].message


class TestUnboundedQueueRule:
    """Round-12 rule: creating an unbounded queue.Queue anywhere in
    fabric_tpu/ is a finding — the overload-protection layer closed
    the unbounded-inter-stage-queue class and the linter keeps it
    closed."""

    def _run(self, lint, tmp_path, source):
        root = _seed_tree(str(tmp_path))
        _regen_docs(root)
        with open(os.path.join(root, "fabric_tpu", "qseed.py"),
                  "w") as f:
            f.write(textwrap.dedent(source))
        return [f for f in lint.run_lint(
            root, rules=("unbounded-queue",))
            if f.path.endswith("qseed.py")]

    def test_bare_queue_is_a_finding(self, lint, tmp_path):
        findings = self._run(lint, tmp_path, '''\
            import queue
            q = queue.Queue()
        ''')
        assert len(findings) == 1
        assert findings[0].rule == "unbounded-queue"
        assert "SheddingQueue" in findings[0].message

    def test_maxsize_zero_is_a_finding(self, lint, tmp_path):
        findings = self._run(lint, tmp_path, '''\
            import queue
            a = queue.Queue(maxsize=0)
            b = queue.Queue(0)
        ''')
        assert len(findings) == 2

    def test_from_import_and_alias_are_resolved(self, lint, tmp_path):
        findings = self._run(lint, tmp_path, '''\
            import queue as _q
            from queue import Queue, LifoQueue
            a = _q.Queue()
            b = Queue()
            c = LifoQueue()
        ''')
        assert len(findings) == 3

    def test_bounded_and_unrelated_are_clean(self, lint, tmp_path):
        findings = self._run(lint, tmp_path, '''\
            import queue

            class Queue:          # a local class, not queue.Queue
                pass

            def mk(n):
                return queue.Queue(maxsize=n)   # runtime-checked bound

            a = queue.Queue(maxsize=64)
            b = queue.Queue(16)
            c = Queue
        ''')
        assert findings == []

    def test_waiver_suppresses_with_reason(self, lint, tmp_path):
        findings = self._run(lint, tmp_path, '''\
            import queue
            # ftpu-lint: allow-unbounded-queue(bound enforced by the
            # wrapper class above this inner queue)
            a = queue.Queue()
            b = queue.Queue()     # unwaived: still a finding
        ''')
        assert len(findings) == 1
        assert findings[0].line == 5    # `b = ...`; `a` is waived

    def test_overload_module_owns_the_waived_exception(self, lint):
        """The tree's ONLY unbounded queue is SheddingQueue's inner
        one, waived with its reason (put_forced must exceed the
        bound)."""
        findings = [f for f in lint.run_lint(
            REPO, rules=("unbounded-queue",))]
        assert findings == []
        src = open(os.path.join(REPO, "fabric_tpu", "common",
                                "overload.py")).read()
        assert "allow-unbounded-queue(" in src


class TestTreeAtHead:
    def test_tree_is_clean(self, lint):
        findings = lint.run_lint(REPO)
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_cli_exit_zero_on_head(self, lint, capsys):
        assert lint.main(["--root", REPO]) == 0
        assert "clean" in capsys.readouterr().out

    def test_cli_rejects_unknown_rule(self, lint):
        assert lint.main(["--rules", "no-such-rule"]) == 2

    def test_known_points_match_docstring_table(self, lint):
        """The declaration list and the module docstring's point table
        must not drift from each other."""
        points, err = lint.load_known_points(REPO)
        assert err is None
        assert points == faults.KNOWN_POINTS
        for p in sorted(points):
            assert p in (faults.__doc__ or ""), \
                f"KNOWN_POINTS entry {p} missing from faults.py " \
                f"docstring table"


class TestArmWarnsOnUnknownPoint:
    def test_unknown_point_warns_but_still_arms(self, caplog):
        with caplog.at_level(logging.WARNING, logger="common.faults"):
            faults.arm("definitely.not.a.point", mode="error",
                       count=1)
        assert any("UNKNOWN fault point" in r.message
                   for r in caplog.records)
        assert faults.armed("definitely.not.a.point")
        with pytest.raises(faults.FaultInjected):
            faults.check("definitely.not.a.point")

    def test_known_point_arms_silently(self, caplog):
        with caplog.at_level(logging.WARNING, logger="common.faults"):
            faults.arm("tpu.dispatch", mode="error", count=1)
        assert not any("UNKNOWN fault point" in r.message
                       for r in caplog.records)

    def test_env_typo_is_loud(self, caplog):
        with caplog.at_level(logging.WARNING, logger="common.faults"):
            faults.arm_from_env("commit.validate_head=error:1")
        assert any("UNKNOWN fault point" in r.message
                   for r in caplog.records)
