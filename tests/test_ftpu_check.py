"""Whole-program static analyzer tests (ISSUE 20 tentpole).

Three contracts, mirroring test_ftpu_lint.py's shape:

(1) seeded violations of every interprocedural rule are caught —
    an unguarded device dispatch reachable from a public `verify*`
    entry (seam), wall-clock/set-iteration/traced-branch/environ
    reads inside a trace region (retrace), and the round-5 qtab bug
    shape: one attribute written from two thread roots with no
    common lock (lockset);
(2) the waiver grammar and the fingerprint baseline suppress exactly
    what they name, and nothing else;
(3) the tree at HEAD is CLEAN modulo the committed reasoned
    baseline — the property tools/static_check.sh gates on — and
    surgically reverting the qtab-cache lock fix (overrides, no
    checkout) makes the lockset rule fail again.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_check():
    spec = importlib.util.spec_from_file_location(
        "_ftpu_check_under_test",
        os.path.join(REPO, "tools", "ftpu_check.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def chk():
    return _load_check()


def _tree(root, files):
    """Materialize a tiny analyzable package: `files` maps paths
    relative to `fabric_tpu/` onto (dedented) source text."""
    pkg = os.path.join(str(root), "fabric_tpu")
    os.makedirs(pkg, exist_ok=True)
    open(os.path.join(pkg, "__init__.py"), "w").close()
    for rel, src in files.items():
        path = os.path.join(pkg, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            f.write(textwrap.dedent(src))
    return str(root)


def _fps(findings):
    return {f.fingerprint for f in findings}


# ---------------------------------------------------------------- seam

_DISPATCH_SRC = """\
    import jax


    class Prov:
        def __init__(self):
            self._fn = jax.jit(lambda x: x + 1)

        def verify_batch(self, items):
            return self._dispatch(items)

        def _dispatch(self, items):
            return self._fn(items)
    """


def test_seam_unguarded_dispatch_found(chk, tmp_path):
    """A jitted callable stored on self, invoked two hops below a
    public verify* entry with no seam anywhere on the path."""
    root = _tree(tmp_path, {"prov.py": _DISPATCH_SRC})
    # register the dispatcher so only the unguarded finding fires
    reg = {"fabric_tpu/prov.py": ("_dispatch",)}
    findings, _ = chk.run_check(root, rules=("seam",), registry=reg)
    assert _fps(findings) == {
        "seam:unguarded:fabric_tpu/prov.py::_dispatch"}
    (f,) = findings
    assert f.rule == "seam" and "self._fn" in f.message


def test_seam_guarded_path_is_clean(chk, tmp_path):
    """The same dispatch behind a fault-point seam at the entry:
    every path is dominated, no finding."""
    guarded = _DISPATCH_SRC.replace(
        "            return self._dispatch(items)",
        "            faults.check(\"pre-dispatch\")\n"
        "            return self._dispatch(items)")
    root = _tree(tmp_path, {"prov.py": "    import faults\n" + guarded})
    reg = {"fabric_tpu/prov.py": ("_dispatch",)}
    findings, _ = chk.run_check(root, rules=("seam",), registry=reg)
    assert findings == []


def test_seam_uncovered_dispatch_vs_registry(chk, tmp_path):
    """An empty registry: the discovered dispatcher is both unguarded
    and uncovered — the 'new path nobody registered' failure mode."""
    root = _tree(tmp_path, {"prov.py": _DISPATCH_SRC})
    findings, _ = chk.run_check(root, rules=("seam",), registry={})
    assert _fps(findings) == {
        "seam:unguarded:fabric_tpu/prov.py::_dispatch",
        "seam:uncovered:fabric_tpu/prov.py::_dispatch"}


def test_seam_stale_registry_entry(chk, tmp_path):
    """A registered function that reaches no dispatch site is drift
    in the other direction."""
    root = _tree(tmp_path, {"prov.py": _DISPATCH_SRC + """\

    def host_only(items):
        return sorted(items)
    """})
    reg = {"fabric_tpu/prov.py": ("_dispatch", "host_only")}
    findings, _ = chk.run_check(root, rules=("seam",), registry=reg)
    assert "seam:stale:fabric_tpu/prov.py::host_only" in _fps(findings)
    assert "seam:stale:fabric_tpu/prov.py::_dispatch" not in \
        _fps(findings)


# ------------------------------------------------------------- retrace

def test_retrace_hazards_in_trace_region(chk, tmp_path):
    """time.time, os.environ.get, set iteration and a Python branch
    on a jnp value — all inside a function handed to jax.jit."""
    root = _tree(tmp_path, {"kern.py": """\
    import os
    import time
    import jax
    import jax.numpy as jnp


    def kernel(x):
        t = time.time()
        mode = os.environ.get("FTPU_MODE")
        for k in set(mode or "ab"):
            t += ord(k)
        if jnp.sum(x):
            return x
        return x + t


    def build():
        return jax.jit(kernel)
    """})
    findings, _ = chk.run_check(root, rules=("retrace",))
    kinds = {fp.split(":")[1] for fp in _fps(findings)}
    assert kinds == {"clock", "environ", "set-iter", "traced-branch"}
    assert all(f.path == "fabric_tpu/kern.py" for f in findings)


def test_retrace_silent_outside_trace_region(chk, tmp_path):
    """The identical hazards in a function nothing jits: no finding
    — the rule is about trace regions, not a style ban."""
    root = _tree(tmp_path, {"host.py": """\
    import os
    import time


    def plumbing(x):
        t = time.time()
        for k in set(os.environ.get("P", "ab")):
            t += ord(k)
        return t
    """})
    findings, _ = chk.run_check(root, rules=("retrace",))
    assert findings == []


def test_retrace_unhashable_static_arg(chk, tmp_path):
    root = _tree(tmp_path, {"st.py": """\
    import jax


    def helper(x, shape):
        return x


    def run(x):
        f = jax.jit(helper, static_argnums=1)
        return f(x, [4, 4])
    """})
    findings, _ = chk.run_check(root, rules=("retrace",))
    fps = _fps(findings)
    assert any(fp.startswith("retrace:unhashable-static:") and
               ":run:f:1" in fp for fp in fps), fps


# ------------------------------------------------------------- lockset

_RACE_SRC = """\
    import threading


    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._entries = {}

        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()

        def _loop(self):
            self._entries["warm"] = 1

        def record(self, k, v):
            self._entries[k] = v
    """


def test_lockset_two_root_race_found(chk, tmp_path):
    """The qtab bug shape: `_entries` written from the restore thread
    AND the public API with no common lock."""
    root = _tree(tmp_path, {"cache.py": _RACE_SRC})
    findings, _ = chk.run_check(root, rules=("lockset",))
    assert _fps(findings) == {
        "lockset:fabric_tpu/cache.py::Cache._entries"}
    (f,) = findings
    assert "no common lock" in f.message


def test_lockset_common_lock_is_clean(chk, tmp_path):
    locked = _RACE_SRC.replace(
        '            self._entries["warm"] = 1',
        '            with self._lock:\n'
        '                self._entries["warm"] = 1').replace(
        "            self._entries[k] = v",
        "            with self._lock:\n"
        "                self._entries[k] = v")
    root = _tree(tmp_path, {"cache.py": locked})
    findings, _ = chk.run_check(root, rules=("lockset",))
    assert findings == []


def test_lockset_interprocedural_must_hold(chk, tmp_path):
    """The lock held at the CALL SITE, not lexically at the write:
    must-hold dataflow carries it down the call path."""
    src = _RACE_SRC.replace(
        '            self._entries["warm"] = 1',
        '            with self._lock:\n'
        '                self._store()\n\n'
        '        def _store(self):\n'
        '            self._entries["warm"] = 1').replace(
        "            self._entries[k] = v",
        "            with self._lock:\n"
        "                self._entries[k] = v")
    root = _tree(tmp_path, {"cache.py": src})
    findings, _ = chk.run_check(root, rules=("lockset",))
    assert findings == []


def test_lockset_class_waiver_covers_all_attrs(chk, tmp_path):
    """An actor-model annotation on the class line silences the rule
    for every attribute of that class."""
    waived = _RACE_SRC.replace(
        "    class Cache:",
        "    # ftpu-check: allow-lockset(fixture actor: single-writer"
        " by construction)\n    class Cache:")
    root = _tree(tmp_path, {"cache.py": waived})
    findings, _ = chk.run_check(root, rules=("lockset",))
    assert findings == []


def test_lockset_item_increment_gauge_policy(chk, tmp_path):
    """`self.stats[k] += n` is exempt by default (the documented
    GIL-gauge policy) and included under strict."""
    src = _RACE_SRC.replace(
        '            self._entries["warm"] = 1',
        '            self._entries["hits"] += 1').replace(
        "            self._entries[k] = v",
        "            self._entries[k] += v")
    root = _tree(tmp_path, {"cache.py": src})
    findings, _ = chk.run_check(root, rules=("lockset",))
    assert findings == []
    strict, _ = chk.run_check(root, rules=("lockset",), strict=True)
    assert _fps(strict) == {
        "lockset:fabric_tpu/cache.py::Cache._entries"}


# ------------------------------------------------------------- waivers

def test_waiver_suppresses_exactly_named_rule(chk, tmp_path):
    root = _tree(tmp_path, {"kern.py": """\
    import time
    import jax


    def kernel(x):
        # ftpu-check: allow-retrace(fixture: trace-time stamp wanted)
        t = time.time()
        return x + t


    def build():
        return jax.jit(kernel)
    """})
    findings, _ = chk.run_check(root, rules=("retrace",))
    assert findings == []


def test_waiver_wrong_rule_does_not_suppress(chk, tmp_path):
    root = _tree(tmp_path, {"kern.py": """\
    import time
    import jax


    def kernel(x):
        # ftpu-check: allow-lockset(wrong rule for this line)
        t = time.time()
        return x + t


    def build():
        return jax.jit(kernel)
    """})
    findings, _ = chk.run_check(root, rules=("retrace",))
    assert any(f.rule == "retrace" for f in findings)


def test_waiver_malformed_is_itself_a_finding(chk, tmp_path):
    root = _tree(tmp_path, {"m.py": """\
    # ftpu-check: allow-bogus(no such rule)
    # ftpu-check: allow-retrace()
    X = 1
    """})
    findings, _ = chk.run_check(root, rules=())
    msgs = [f.message for f in findings if f.rule == "waiver"]
    assert len(msgs) == 2
    assert any("unknown waiver" in m for m in msgs)
    assert any("without a reason" in m for m in msgs)


# ------------------------------------------------------------ baseline

def test_baseline_round_trip_preserves_reasons(chk, tmp_path):
    root = _tree(tmp_path, {"cache.py": _RACE_SRC})
    findings, _ = chk.run_check(root, rules=("lockset",))
    assert findings
    fp = findings[0].fingerprint
    bl = os.path.join(str(tmp_path), "baseline.json")

    chk.write_baseline(bl, findings, {})
    entries, err = chk.load_baseline(bl)
    assert err is None and set(entries) == {fp}
    assert entries[fp].startswith("TODO")

    # regeneration keeps the reviewed reason
    chk.write_baseline(bl, findings, {fp: "reviewed: fixture race"})
    entries, err = chk.load_baseline(bl)
    assert err is None
    assert entries[fp] == "reviewed: fixture race"

    # a reason-less entry is a setup error, not silently accepted
    with open(bl, "w", encoding="utf-8") as f:
        json.dump({"entries": [{"id": fp, "reason": ""}]}, f)
    entries, err = chk.load_baseline(bl)
    assert entries is None and "reason" in err


def test_missing_baseline_is_empty_not_error(chk, tmp_path):
    entries, err = chk.load_baseline(
        os.path.join(str(tmp_path), "nope.json"))
    assert entries == {} and err is None


# ----------------------------------------------- the real tree at HEAD

def test_hot_path_registry_loads_from_ftpu_lint(chk):
    reg, err = chk.load_hot_path_registry(REPO)
    assert err is None
    assert isinstance(reg, dict) and reg
    assert "fabric_tpu/bccsp/tpu.py" in reg


def test_clean_tree_gate(chk):
    """The committed tree passes the exact invocation
    tools/static_check.sh runs: zero new findings, zero stale
    baseline entries, whole tree analyzed."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ftpu_check.py"),
         "--root", REPO, "--json"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["findings"] == []
    assert out["stale_baseline"] == []
    assert out["functions_analyzed"] > 2000
    assert len(out["baselined"]) >= 1


def test_reverting_pool_lock_fails_gate(chk):
    """Surgically strip the key-table pool's locking from the live
    tree (overrides — no checkout) and the lockset rule must light up
    again on the pool's attributes (the restore thread and live
    batches both write them), over and above the committed
    baseline."""
    rel = "fabric_tpu/bccsp/tpu.py"
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        src = f.read()
    assert "with self._pool_lock:" in src
    reverted = src.replace("with self._pool_lock:",
                           "if True:  # unlocked")
    findings, _ = chk.run_check(REPO, rules=("lockset",),
                                overrides={rel: reverted})
    baseline, err = chk.load_baseline(
        os.path.join(REPO, "tools", "ftpu_check_baseline.json"))
    assert err is None
    new = {f.fingerprint for f in findings} - set(baseline)
    assert ("lockset:fabric_tpu/bccsp/tpu.py::"
            "TPUProvider._pool") in new, sorted(new)
    assert any("::TPUProvider._slot_of" in fp
               or "::TPUProvider._g16_rep" in fp for fp in new)
