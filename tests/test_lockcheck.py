"""Lock-order sanitizer tests (ISSUE 5 tentpole, runtime half).

The sanitizer itself must be trustworthy before its findings gate CI:
a forced A→B/B→A inversion is reported with BOTH acquisition stacks, a
lock held across a (stubbed) device dispatch or an injected-fault
stall is flagged, clean nesting and reentrant RLocks stay silent, and
the whole apparatus is a no-op when FTPU_LOCKCHECK is unset.

Tests use private `LockSanitizer` instances (never the env-installed
global) so deliberate violations cannot fail a sanitizer-armed CI run
of this very file.
"""

import hashlib
import threading

import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import faults, lockcheck
from fabric_tpu.common.lockcheck import LockOrderError, LockSanitizer

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



def _acquire_ab(lock_a, lock_b):
    with lock_a:
        with lock_b:
            pass


class TestInversionDetection:
    def test_ab_ba_inversion_reported_with_both_stacks(self):
        san = LockSanitizer()
        lock_a = san.lock()
        lock_b = san.lock()
        _acquire_ab(lock_a, lock_b)
        assert san.violations() == []      # one order alone is fine
        _acquire_ab(lock_b, lock_a)
        vs = san.violations()
        assert len(vs) == 1
        v = vs[0]
        assert v.kind == "order-inversion"
        report = v.render()
        # both creation sites named, and the acquiring frames of BOTH
        # orders present (the helper appears for this thread's edge
        # and for the recorded prior edge)
        assert "test_lockcheck.py" in report
        assert report.count("_acquire_ab") >= 2
        assert "while acquiring" in report
        assert "already acquired" in report

    def test_inversion_across_threads(self):
        san = LockSanitizer()
        lock_a = san.lock()
        lock_b = san.lock()
        t = threading.Thread(target=_acquire_ab,
                             args=(lock_a, lock_b))
        t.start()
        t.join()
        _acquire_ab(lock_b, lock_a)
        vs = san.violations()
        assert len(vs) == 1
        assert vs[0].kind == "order-inversion"

    def test_three_lock_cycle(self):
        # A→B, B→C, then C→A: no single pair inverts, the CYCLE does
        san = LockSanitizer()
        a = san.lock()
        b = san.lock()
        c = san.lock()          # three lines: three distinct classes
        _acquire_ab(a, b)
        _acquire_ab(b, c)
        assert san.violations() == []
        _acquire_ab(c, a)
        vs = san.violations()
        assert len(vs) == 1
        assert vs[0].kind == "order-inversion"

    def test_clean_nesting_passes(self):
        san = LockSanitizer()
        lock_a = san.lock()
        lock_b = san.lock()
        threads = [threading.Thread(target=_acquire_ab,
                                    args=(lock_a, lock_b))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _acquire_ab(lock_a, lock_b)
        assert san.violations() == []

    def test_inversion_deduplicated(self):
        san = LockSanitizer()
        lock_a = san.lock()
        lock_b = san.lock()
        _acquire_ab(lock_a, lock_b)
        _acquire_ab(lock_b, lock_a)
        _acquire_ab(lock_b, lock_a)
        assert len(san.violations()) == 1

    def test_reentrant_rlock_is_not_a_finding(self):
        san = LockSanitizer()
        r = san.rlock()
        with r:
            with r:
                san.note_blocking("probe")  # reentrancy: one held entry
        assert [v for v in san.violations()
                if v.kind == "order-inversion"] == []

    def test_same_class_nesting_skipped(self):
        # two instances from ONE creation line are one lock class:
        # nesting them is not an inversion finding (documented limit)
        san = LockSanitizer()
        locks = [san.lock() for _ in range(2)]
        _acquire_ab(locks[0], locks[1])
        _acquire_ab(locks[1], locks[0])
        assert san.violations() == []

    def test_raise_mode(self):
        san = LockSanitizer(raise_on_violation=True)
        lock_a = san.lock()
        lock_b = san.lock()
        _acquire_ab(lock_a, lock_b)
        with pytest.raises(LockOrderError):
            _acquire_ab(lock_b, lock_a)

    def test_allow_pair_waiver(self):
        san = LockSanitizer()
        lock_a = san.lock()
        lock_b = san.lock()
        san.allow_pair(lock_a._site, lock_b._site,
                       reason="test: documented benign pair")
        _acquire_ab(lock_a, lock_b)
        _acquire_ab(lock_b, lock_a)
        assert san.violations() == []
        with pytest.raises(ValueError):
            san.allow_pair("x", "y", reason="")


class TestHeldAcrossBlocking:
    def test_lock_held_across_blocking_span(self):
        san = LockSanitizer()
        lock = san.lock()
        with lock:
            san.note_blocking("tpu.dispatch")
        vs = san.violations()
        assert len(vs) == 1
        v = vs[0]
        assert v.kind == "held-across-blocking"
        assert "tpu.dispatch" in v.description
        report = v.render()
        assert "acquired at" in report
        assert "blocking span" in report
        assert "test_lockcheck.py" in report

    def test_cross_thread_release_evicts_holder_entry(self):
        # a plain Lock released by ANOTHER thread (handoff idiom) must
        # evict the owner's held entry, or the owner's next blocking
        # probe reports a lock it no longer holds
        san = LockSanitizer()
        handoff = san.lock()
        handoff.acquire()
        t = threading.Thread(target=handoff.release)
        t.start()
        t.join()
        san.note_blocking("tpu.dispatch")
        assert san.violations() == []

    def test_no_lock_held_is_clean(self):
        san = LockSanitizer()
        lock = san.lock()
        with lock:
            pass
        san.note_blocking("tpu.dispatch")
        assert san.violations() == []

    def test_allow_blocking_waiver(self):
        san = LockSanitizer()
        lock = san.lock()
        san.allow_blocking("tpu.dispatch", lock._site,
                           reason="test: prewarm holds this by design")
        with lock:
            san.note_blocking("tpu.dispatch")
        assert san.violations() == []

    def test_condition_wait_releases_bookkeeping(self):
        # Condition.wait goes through _release_save/_acquire_restore:
        # the held-set must empty during the wait and refill after, so
        # a blocking probe AFTER a wait still sees exactly one holder
        san = LockSanitizer()
        cond = san.condition()
        with cond:
            cond.wait(timeout=0.01)
            san.note_blocking("probe")
        vs = san.violations()
        assert len(vs) == 1        # held on re-acquire: flagged once
        san.clear()
        with cond:
            cond.wait(timeout=0.01)
        san.note_blocking("probe")
        assert san.violations() == []   # fully released afterwards

    def test_lock_held_across_stubbed_device_dispatch(self, monkeypatch):
        """End-to-end: the note_blocking hooks in bccsp/tpu.py fire on
        a real (device-stubbed) verify_batch, so holding a tracked
        lock across it is a finding tagged tpu.dispatch."""
        san = LockSanitizer()
        monkeypatch.setattr(lockcheck, "_SAN", san)
        sw = SWProvider()
        key = sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
        items = []
        for i in range(8):
            m = f"lockcheck {i}".encode()
            sig = sw.sign(key, hashlib.sha256(m).digest())
            items.append(VerifyItem(key=key.public_key(),
                                    signature=sig, message=m))
        tpu = TPUProvider(min_batch=4, use_g16=False)

        def fake_qtab_fn():
            return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                               dtype=np.int32)

        def fake_pipeline_digest():
            def run(key_idx, q_flat, g16, r8, rpn8, w8, premask,
                    digests):
                return np.asarray(premask)
            return run

        def fake_pipeline():
            def run(blocks, nblocks, key_idx, q_flat, g16, r, rpn, w,
                    premask, digests, has_digest):
                return np.asarray(premask)
            return run

        def fake_ladder():
            def run(blocks, nblocks, qx, qy, r, rpn, w, premask,
                    digests, has_digest):
                return np.asarray(premask)
            return run

        monkeypatch.setattr(tpu, "_slab_rows", lambda: SLAB_ROWS)

        monkeypatch.setattr(tpu, "_qtab_fn", fake_qtab_fn)
        monkeypatch.setattr(tpu, "_comb_pipeline_digest",
                            fake_pipeline_digest)
        monkeypatch.setattr(tpu, "_comb_pipeline", fake_pipeline)
        monkeypatch.setattr(tpu, "_pipeline", fake_ladder)
        caller_lock = san.lock()
        with caller_lock:
            out = tpu.verify_batch(items)
        assert out == [True] * len(items)
        vs = [v for v in san.violations()
              if v.kind == "held-across-blocking"]
        assert len(vs) == 1
        assert "tpu.dispatch" in vs[0].description
        # clean run afterwards: no lock held -> nothing new
        san.clear()
        assert tpu.verify_batch(items) == [True] * len(items)
        assert san.violations() == []

    def test_lock_held_across_injected_fault_sleep(self, monkeypatch):
        """faults.check delay mode routes through the sanitizer: an
        injected stall under a tracked lock is a finding."""
        san = LockSanitizer()
        monkeypatch.setattr(lockcheck, "_SAN", san)
        faults.arm("tpu.dispatch", mode="delay", count=1,
                   delay_s=0.01)
        lock = san.lock()
        with lock:
            faults.check("tpu.dispatch")
        vs = san.violations()
        assert len(vs) == 1
        assert vs[0].kind == "held-across-blocking"
        assert "fault-delay:tpu.dispatch" in vs[0].description


class TestNoOpWhenDisabled:
    def test_threading_untouched_without_install(self):
        if lockcheck.enabled():
            pytest.skip("global sanitizer armed (FTPU_LOCKCHECK run)")
        assert threading.Lock is lockcheck._orig_lock
        assert threading.RLock is lockcheck._orig_rlock
        assert threading.Condition is lockcheck._orig_condition

    def test_note_blocking_is_free_when_disabled(self):
        if lockcheck.enabled():
            pytest.skip("global sanitizer armed (FTPU_LOCKCHECK run)")
        # must not raise, record, or allocate a sanitizer
        lockcheck.note_blocking("tpu.dispatch")
        assert lockcheck.sanitizer() is None

    def test_install_from_env_off_values(self, monkeypatch):
        if lockcheck.enabled():
            pytest.skip("global sanitizer armed (FTPU_LOCKCHECK run)")
        for off in ("", "0", "false", "off"):
            monkeypatch.setenv(lockcheck.ENV_VAR, off)
            assert lockcheck.install_from_env() is None

    def test_install_uninstall_roundtrip(self):
        if lockcheck.enabled():
            pytest.skip("global sanitizer armed (FTPU_LOCKCHECK run)")
        try:
            san = lockcheck.install()
            assert lockcheck.enabled()
            lk = threading.Lock()
            assert isinstance(lk, lockcheck._TrackedLock)
            with lk:
                san.note_blocking("probe")
            assert len(san.violations()) == 1
        finally:
            lockcheck.uninstall()
        assert threading.Lock is lockcheck._orig_lock
        assert not lockcheck.enabled()


class TestReport:
    def test_clean_report(self):
        assert LockSanitizer().report() == "lockcheck: clean"

    def test_report_counts_and_renders(self):
        san = LockSanitizer()
        lock = san.lock()
        with lock:
            san.note_blocking("tpu.dispatch")
        rep = san.report()
        assert "1 violation(s)" in rep
        assert "held-across-blocking" in rep
