"""The gate scripts and by-hand tools name only what exists.

`tools/static_check.sh` carried a gate that could not pass for nine
PRs (its script parsed a history that had been deleted) and nothing
said so. These checks read the scripts as text — they run none of
them — so a deleted test file, tool or subset shows up here first.
"""

import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = ("static_check.sh", "chaos_check.sh", "soak_check.sh")


def _script(name):
    with open(os.path.join(ROOT, "tools", name)) as f:
        return f.read()


def _code(text):
    """A script without its comments."""
    return "\n".join(line.split(" #")[0] for line in text.splitlines()
                     if not line.lstrip().startswith("#"))


@pytest.mark.parametrize("name", SCRIPTS)
def test_every_file_a_gate_script_runs_exists(name):
    paths = set(re.findall(
        r"(?<![\w/.])((?:tests|tools|fabric_tpu)/[\w/]+\.(?:py|sh)"
        r"|[\w]+\.py)\b", _code(_script(name))))
    assert paths, f"{name} names no file: the pattern has rotted"
    missing = sorted(p for p in paths
                     if not os.path.exists(os.path.join(ROOT, p)))
    assert not missing


def test_static_check_numbers_its_gates_as_its_header_lists_them():
    text = _script("static_check.sh")
    ran = re.findall(r'echo "== static_check (\d+)/(\d+):', text)
    total = len(ran)
    assert ran == [(str(i), str(total)) for i in range(1, total + 1)]
    listed = re.findall(r"^#\s+(\d+)\. ", text, flags=re.M)
    assert listed == [str(i) for i in range(1, total + 1)]


def test_chaos_check_usage_cases_and_functions_agree():
    text = _script("chaos_check.sh")
    usage = re.search(r"^# Usage: chaos_check.sh \[(.+)\]$", text,
                      flags=re.M).group(1).split("|")
    body = text[text.index('case "${1:-all}" in'):]
    cases = re.findall(r"^\s+([\w-]+)\) ", body, flags=re.M)
    assert sorted(usage) == sorted(cases)
    functions = set(re.findall(r"^(\w+)\(\) \{", text, flags=re.M))
    called = set(re.findall(r"\b([a-z_][a-z_0-9]*) ?;", body))
    assert called == functions - {"run"}


def test_chip_compile_lists_the_programs_the_provider_builds():
    spec = importlib.util.spec_from_file_location(
        "chip_compile_listing",
        os.path.join(ROOT, "tools", "chip_compile.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    table = mod.programs(2048, 4, None)
    assert sorted(table) == ["digest_q16", "digest_q8", "g16",
                             "pool_write", "qtab16", "qtab8"]
    for name, build in table.items():
        fn, shapes = build()
        assert callable(getattr(fn, "lower", None)), name
        assert shapes
