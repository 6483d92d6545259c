"""Test bootstrap: force an 8-device virtual CPU mesh before JAX imports.

Multi-chip sharding paths (fabric_tpu/parallel) are exercised on a virtual
8-device CPU backend so the suite runs anywhere; the benchmark on the chip is
benchmark/run.py, which does NOT import this.
"""

import os
import sys

# Force CPU even when the environment offers an accelerator: unit tests
# must be hermetic, and a chip belongs to one process at a time — six
# xdist workers cannot share it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax.config wins over the environment as long as it runs before
# backend initialization.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Round-8 lock-order sanitizer: when FTPU_LOCKCHECK is set, patch the
# threading lock factories BEFORE any fabric_tpu module creates its
# locks (tools/static_check.sh arms this for a fast threaded subset;
# FTPU_LOCKCHECK=raise fails at the detection point instead of at
# session end). jax was imported above on purpose — its internal
# locks stay untracked.
from fabric_tpu.common import lockcheck  # noqa: E402

lockcheck.install_from_env()

# Persistent compilation cache: the heavy differential tests jit the
# same pipelines on every run; caching makes re-runs minutes faster
# (keyed by HLO hash — safe across code edits).
from fabric_tpu.common import jaxenv  # noqa: E402

jaxenv.enable_compilation_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "integration: multi-process nwo integration tests")
    config.addinivalue_line(
        "markers", "slow: long-running crypto tests")
    config.addinivalue_line(
        "markers", "chaos: fault-injection robustness tests "
        "(fault points armed via fabric_tpu.common.faults; "
        "tools/chaos_check.sh re-runs subsets with FTPU_FAULTS set)")


import pytest  # noqa: E402


@pytest.hookimpl(wrapper=True)
def pytest_fixture_setup(fixturedef, request):
    """An optional-dependency gap is a SKIP, not an error: fixtures
    that hit the pure-python crypto fallback's honest limits (x509
    cert building, AES) report the missing wheel instead of erroring
    the whole test. Only genuine capability gaps convert — a typo'd
    `ec.`/`serialization.` attribute still fails loudly."""
    from fabric_tpu.bccsp import _crypto_compat as cc
    try:
        return (yield)
    except cc.MissingCryptographyError as e:
        if not cc.is_capability_gap(e):
            raise
        pytest.skip(f"optional dependency missing: {e}")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    """Scope-cached fixtures replay their original exception without
    re-entering pytest_fixture_setup — convert those too."""
    from fabric_tpu.bccsp import _crypto_compat as cc
    try:
        return (yield)
    except cc.MissingCryptographyError as e:
        if not cc.is_capability_gap(e):
            raise
        pytest.skip(f"optional dependency missing: {e}")


@pytest.fixture()
def require_cryptography():
    """Skip on hosts running the pure-python crypto fallback: these
    tests build real x509 certs (or AES), which only the optional
    `cryptography` wheel provides."""
    from fabric_tpu.bccsp._crypto_compat import HAVE_CRYPTOGRAPHY
    if not HAVE_CRYPTOGRAPHY:
        pytest.skip("needs the 'cryptography' wheel (x509/AES); the "
                    "pure-python backend covers P-256 ECDSA only")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Surface lock-order sanitizer findings (with both stacks) at the
    end of a FTPU_LOCKCHECK run."""
    san = lockcheck.sanitizer()
    if san is not None and san.violations():
        terminalreporter.write_sep("=", "lockcheck violations")
        terminalreporter.write_line(san.report())


def pytest_sessionfinish(session, exitstatus):
    """A sanitizer-armed run FAILS on recorded violations even when
    every test passed — that is the CI gate's contract."""
    san = lockcheck.sanitizer()
    if san is not None and san.violations() and session.exitstatus == 0:
        session.exitstatus = 3


@pytest.fixture(autouse=True)
def _fault_registry_isolation():
    """Each test starts from the process fault baseline: whatever
    FTPU_FAULTS armed (chaos runs), nothing otherwise — a test that
    arms or exhausts fault points cannot leak them into the next."""
    from fabric_tpu.common import faults
    faults.reset()
    yield
    faults.reset()
