"""Chaos / graceful-degradation tests (ISSUE 1 tentpole).

`BCCSP.Default: TPU` must be invisible in verdicts: with faults armed
at every device dispatch point (forced errors, deadline stalls,
fail-N-then-recover) a mixed valid/invalid `verify_batch` stays
bit-identical to the SW provider, the breaker trips within
`TripThreshold` failures, refuses the device while open, and re-admits
it after cooldown via a bounded probe. The deliver client reconnects
with full-jitter backoff and resets after progress; a raft chain drops
a faulted step instead of crashing its loop.

Device math is replaced by the recorder-stub idiom from
tests/test_bccsp.py TestQ16TableCache (real staging + fault points +
breaker, no XLA compile), with the corpus chosen so that host
pre-validation (premask) IS the verdict; the `slow`-marked test at the
bottom runs the same scenario through the real compiled kernel.

All of these run green under JAX_PLATFORMS=cpu with no `cryptography`
wheel installed (the pure-python P-256 backend).
"""

import hashlib
import threading
import time
import urllib.request

import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, factory, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import breaker as breaker_mod
from fabric_tpu.common import faults
from fabric_tpu.common.breaker import (
    BreakerConfig,
    CircuitBreaker,
    CircuitOpen,
    DeadlineExceeded,
)

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

_SW = SWProvider()
_KEYS = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(3)]

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



class _StepClock:
    """Injectable monotonic clock for the breaker's clock seam
    (`CircuitBreaker(clock=)` / `DeviceHealth(clock=)`): cooldown
    transitions are driven by `advance()`, never by wall sleeps, so
    timing assertions cannot lose races on a loaded box."""

    def __init__(self):
        self._t = time.monotonic()

    def __call__(self) -> float:
        return self._t

    def advance(self, dt: float) -> None:
        self._t += dt


def _premask_pool(n_keys=2):
    """(VerifyItem, expected) pool whose verdicts are decided by host
    pre-validation alone (valid low-S sig -> True; malformed DER,
    high-S, out-of-range r -> False) so the recorder stub — which
    returns premask — is bit-exact with the sw oracle."""
    pool = []
    for i in range(8):
        k = _KEYS[i % n_keys]
        m = f"chaos payload {i}".encode() * (i % 3 + 1)
        sig = _SW.sign(k, hashlib.sha256(m).digest())
        pool.append((VerifyItem(key=k.public_key(), signature=sig,
                                message=m), True))
        r, s = utils.unmarshal_signature(sig)
        if i % 3 == 0:     # malformed DER
            pool.append((VerifyItem(key=k.public_key(),
                                    signature=sig[:-2], message=m),
                         False))
        elif i % 3 == 1:   # high-S twin
            pool.append((VerifyItem(
                key=k.public_key(),
                signature=utils.marshal_signature(r, utils.P256_N - s),
                message=m), False))
        else:              # r >= n
            pool.append((VerifyItem(
                key=k.public_key(),
                signature=utils.marshal_signature(utils.P256_N, 5),
                message=m), False))
    return pool


def _tile(pool, n):
    items = [pool[i % len(pool)][0] for i in range(n)]
    expected = [pool[i % len(pool)][1] for i in range(n)]
    return items, expected


def _stubbed_provider(monkeypatch, **kw):
    """TPUProvider with device math stubbed (returns premask), real
    staging/fault/breaker logic — the TestQ16TableCache idiom."""
    kw.setdefault("min_batch", 4)
    kw.setdefault("use_g16", False)
    tpu = TPUProvider(**kw)
    calls = {"premask": []}

    def fake_qtab_fn():
        return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                           dtype=np.int32)

    def fake_pipeline_digest():
        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            calls["premask"].append(np.asarray(premask).copy())
            return np.asarray(premask)
        return run

    def fake_pipeline():
        def run(blocks, nblocks, key_idx, q_flat, g16, r, rpn, w,
                premask, digests, has_digest):
            calls["premask"].append(np.asarray(premask).copy())
            return np.asarray(premask)
        return run

    def fake_ladder():
        def run(blocks, nblocks, qx, qy, r, rpn, w, premask, digests,
                has_digest):
            calls["premask"].append(np.asarray(premask).copy())
            return np.asarray(premask)
        return run

    monkeypatch.setattr(tpu, "_slab_rows", lambda: SLAB_ROWS)

    monkeypatch.setattr(tpu, "_qtab_fn", fake_qtab_fn)
    monkeypatch.setattr(tpu, "_comb_pipeline", fake_pipeline)
    monkeypatch.setattr(tpu, "_comb_pipeline_digest",
                        fake_pipeline_digest)
    # an all-dead batch has an empty key map and routes to the generic
    # ladder pipeline — stub that too (premask passthrough)
    monkeypatch.setattr(tpu, "_pipeline", fake_ladder)
    return tpu, calls


# ---------------------------------------------------------------------------
# the fault registry itself
# ---------------------------------------------------------------------------

class TestFaultRegistry:
    def test_unarmed_check_is_noop(self):
        faults.clear()
        faults.check("tpu.dispatch")
        assert faults.fires("tpu.dispatch") == 0

    def test_error_mode_counts_down(self):
        faults.clear()
        faults.arm("x.y", mode="error", count=2)
        for _ in range(2):
            with pytest.raises(faults.FaultInjected):
                faults.check("x.y")
        faults.check("x.y")            # exhausted -> disarmed
        assert faults.fires("x.y") == 2
        assert not faults.armed("x.y")

    def test_delay_mode_stalls_then_proceeds(self):
        faults.clear()
        faults.arm("x.y", mode="delay", count=1, delay_s=0.05)
        t0 = time.monotonic()
        faults.check("x.y")            # stalls, does not raise
        assert time.monotonic() - t0 >= 0.04
        faults.check("x.y")            # exhausted

    def test_env_spec_parsing(self):
        faults.clear()
        faults.arm_from_env("a.b=error:2; c.d=delay::0.01,e.f=error")
        assert faults.armed("a.b") and faults.armed("c.d") \
            and faults.armed("e.f")
        faults.arm_from_env("garbage==:::")   # must not raise

    def test_reset_restores_env_baseline(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "p.q=error:1")
        faults.reset()
        assert faults.armed("p.q")
        with pytest.raises(faults.FaultInjected):
            faults.check("p.q")
        faults.reset()                 # re-arms from env
        assert faults.armed("p.q")


# ---------------------------------------------------------------------------
# breaker state machine (no device)
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_trip_cooldown_probe_cycle(self):
        clock = [0.0]
        br = CircuitBreaker(BreakerConfig(trip_threshold=3,
                                          cooldown_s=10.0),
                            clock=lambda: clock[0])
        assert br.state == breaker_mod.DEVICE
        for _ in range(2):
            br.failure(RuntimeError("boom"))
        assert br.state == breaker_mod.DEVICE     # below threshold
        br.failure(RuntimeError("boom"))
        assert br.state == breaker_mod.DEGRADED
        assert br.stats["trips"] == 1
        with pytest.raises(CircuitOpen):
            br.run(lambda: "never")
        clock[0] = 10.5
        assert br.state == breaker_mod.PROBING
        assert br.run(lambda: "probe-ok") == "probe-ok"
        assert br.state == breaker_mod.DEVICE
        assert br.stats["probes"] == 1

    def test_probe_failure_reopens(self):
        clock = [0.0]
        br = CircuitBreaker(BreakerConfig(trip_threshold=1,
                                          cooldown_s=5.0),
                            clock=lambda: clock[0])
        br.failure(RuntimeError("boom"))
        clock[0] = 6.0
        with pytest.raises(RuntimeError):
            br.run(lambda: (_ for _ in ()).throw(RuntimeError("still")))
        assert br.state == breaker_mod.DEGRADED   # probe failed
        clock[0] = 12.0
        assert br.state == breaker_mod.PROBING

    def test_single_probe_slot(self):
        clock = [0.0]
        br = CircuitBreaker(BreakerConfig(trip_threshold=1,
                                          cooldown_s=1.0),
                            clock=lambda: clock[0])
        br.failure(RuntimeError("boom"))
        clock[0] = 2.0
        assert br.admit() is True      # takes the probe slot
        with pytest.raises(CircuitOpen):
            br.admit()                 # concurrent probe refused
        br.success()
        assert br.state == breaker_mod.DEVICE
        assert br.admit() is False     # closed-state admission

    def test_stale_probe_slot_reclaimed(self):
        """A caller that takes the probe slot and never reports the
        outcome (dropped resolver) must not wedge the breaker in
        'probing' forever — the slot is reclaimed as a failed probe."""
        clock = [0.0]
        br = CircuitBreaker(BreakerConfig(trip_threshold=1,
                                          cooldown_s=2.0),
                            clock=lambda: clock[0])
        br.failure(RuntimeError("boom"))
        clock[0] = 3.0
        br.admit()                     # probe slot taken, outcome lost
        clock[0] = 5.5                 # past the probe timeout
        assert br.state == breaker_mod.DEGRADED
        assert br.stats["stale_probes"] == 1
        clock[0] = 8.0                 # cooldown over: a NEW probe
        assert br.state == breaker_mod.PROBING
        assert br.run(lambda: "ok") == "ok"
        assert br.state == breaker_mod.DEVICE

    def test_running_probe_is_not_reclaimed(self):
        """A probe still EXECUTING (e.g. paying a long first-dispatch
        compile with no deadline) keeps its slot past the stale-probe
        timeout — only a DROPPED outcome is reclaimed."""
        clock = [0.0]
        br = CircuitBreaker(BreakerConfig(trip_threshold=1,
                                          cooldown_s=1.0),
                            clock=lambda: clock[0])
        br.failure(RuntimeError("boom"))
        clock[0] = 2.0

        def slow_probe():
            clock[0] = 60.0            # far past the probe timeout
            assert br.state == breaker_mod.PROBING
            return "ok"

        assert br.run(slow_probe) == "ok"
        assert br.state == breaker_mod.DEVICE
        assert br.stats["stale_probes"] == 0

    def test_deadline_guard(self):
        br = CircuitBreaker(BreakerConfig(deadline_ms=50,
                                          trip_threshold=2))
        with pytest.raises(DeadlineExceeded):
            br.guard(lambda: time.sleep(0.5))
        assert br.stats["deadline_timeouts"] == 1
        assert br.guard(lambda: 42) == 42         # fast call fine

    def test_stale_success_cannot_close_open_breaker(self):
        """An in-flight dispatch admitted BEFORE the trip that resolves
        successfully afterwards must not bypass cooldown + probe."""
        clock = [0.0]
        br = CircuitBreaker(BreakerConfig(trip_threshold=1,
                                          cooldown_s=10.0),
                            clock=lambda: clock[0])
        assert br.admit() is False     # healthy admission
        br.failure(RuntimeError("wedged"))
        assert br.state == breaker_mod.DEGRADED
        br.success()                   # the straggler resolves late
        assert br.state == breaker_mod.DEGRADED
        clock[0] = 11.0
        assert br.state == breaker_mod.PROBING

    def test_ignored_exceptions_do_not_count(self):
        br = CircuitBreaker(BreakerConfig(trip_threshold=1,
                                          ignore=(TypeError,)))
        with pytest.raises(TypeError):
            br.guard(lambda: (_ for _ in ()).throw(TypeError("caller")))
        assert br.state == breaker_mod.DEVICE


# ---------------------------------------------------------------------------
# TPU provider degradation (the acceptance scenario)
# ---------------------------------------------------------------------------

class TestTPUProviderDegradation:
    def test_forced_errors_10k_bit_identical_and_trips(self, monkeypatch):
        """Faults armed at EVERY device dispatch/compile/persist point:
        a 10k mixed batch is bit-identical to sw, the breaker trips
        within TripThreshold failures, and while open the device is
        never even attempted."""
        faults.clear()
        faults.arm("tpu.dispatch", mode="error")       # unlimited
        faults.arm("tpu.compile", mode="error")
        faults.arm("tpu.table_persist", mode="error")
        tpu, _ = _stubbed_provider(
            monkeypatch, min_batch=16,
            fallback=BreakerConfig(trip_threshold=3, cooldown_s=60.0,
                                   probe_batch=64))
        pool = _premask_pool()
        items, expected = _tile(pool, 10_000)
        # the sw oracle agrees with the pool verdicts by construction;
        # pin it on the unique pool to keep the wall clock sane
        assert _SW.verify_batch([it for it, _ in pool]) == \
            [e for _, e in pool]

        out = tpu.verify_batch(items)                  # failure 1
        assert out == expected
        small, small_exp = _tile(pool, 16)
        assert tpu.verify_batch(small) == small_exp    # failure 2
        assert tpu.health() != "degraded"
        assert tpu.verify_batch(small) == small_exp    # failure 3: trip
        assert tpu.health() == "degraded"
        assert tpu.stats["breaker_trips"] == 1
        assert tpu.stats["sw_fallbacks"] == 3

        # open breaker: the device is not attempted at all
        fires_before = faults.fires("tpu.dispatch")
        assert tpu.verify_batch(small) == small_exp
        assert faults.fires("tpu.dispatch") == fires_before
        assert tpu.stats["degraded_batches"] >= 1
        assert tpu.stats["breaker_state"] == 2

    def test_deadline_stall_trips_then_reprobes(self, monkeypatch):
        """Stalled dispatches (delay faults) exceed DeadlineMs, count
        as failures, trip the breaker; after CooldownS the next batch
        probes the device and re-admits it.

        Cooldown passage is driven through the breaker's monotonic
        CLOCK SEAM (a stepped fake), not wall sleeps: on a loaded box
        the old 0.2s margin lost races — more than the cooldown could
        elapse between the trip inside verify_batch and the health()
        assertion, reading `probing` where the test pinned
        `degraded`. The deadline watchdog itself still runs on wall
        time (the 1.0s injected stall vs the 300ms deadline leaves no
        meaningful race)."""
        faults.clear()
        # the deadline must measure the DISPATCH, not first-use costs:
        # warm the jax backend and the native-extension probe (a ~3s
        # one-time g++ attempt) before arming
        import jax.numpy as jnp
        jnp.zeros(1).block_until_ready()
        from fabric_tpu import native as native_mod
        native_mod.available()
        faults.arm("tpu.dispatch", mode="delay", count=2, delay_s=1.0)
        tpu, _ = _stubbed_provider(
            monkeypatch, min_batch=4,
            fallback=BreakerConfig(deadline_ms=300, trip_threshold=2,
                                   cooldown_s=0.2, probe_batch=64))
        clk = _StepClock()
        tpu._breaker._clock = clk
        items, expected = _tile(_premask_pool(), 16)
        assert tpu.verify_batch(items) == expected     # timeout 1
        assert tpu.verify_batch(items) == expected     # timeout 2: trip
        assert tpu.stats["breaker_deadline_timeouts"] == 2
        # deterministic: the breaker's clock has not moved since the
        # trip, so the cooldown CANNOT have elapsed yet
        assert tpu.health() == "degraded"
        clk.advance(0.25)
        assert tpu.health() == "probing"
        # fault budget exhausted: the probe dispatch succeeds
        assert tpu.verify_batch(items) == expected
        assert tpu.health() == "device"
        assert tpu.stats["breaker_probes"] == 1
        # drain the abandoned watchdog workers (each sleeps 1.0s in
        # the delay fault, then re-checks tpu.dispatch during staging)
        # so they cannot consume the NEXT test's armed fault budget
        time.sleep(1.1)

    def test_fail_n_then_recover_below_threshold(self, monkeypatch):
        faults.clear()
        faults.arm("tpu.dispatch", mode="error", count=2)
        tpu, calls = _stubbed_provider(
            monkeypatch, min_batch=4,
            fallback=BreakerConfig(trip_threshold=5))
        items, expected = _tile(_premask_pool(), 24)
        for _ in range(2):                             # transient faults
            assert tpu.verify_batch(items) == expected
        assert tpu.stats["sw_fallbacks"] == 2
        assert tpu.health() == "device"                # never tripped
        assert tpu.verify_batch(items) == expected     # device again
        assert calls["premask"], "device path did not run after recovery"

    def test_probe_risks_at_most_probe_batch_lanes(self, monkeypatch):
        faults.clear()
        faults.arm("tpu.dispatch", mode="error", count=1)
        tpu, _ = _stubbed_provider(
            monkeypatch, min_batch=4,
            fallback=BreakerConfig(trip_threshold=1, cooldown_s=0.4,
                                   probe_batch=8))
        items, expected = _tile(_premask_pool(), 32)
        assert tpu.verify_batch(items) == expected     # trip
        assert tpu.health() == "degraded"
        time.sleep(0.45)
        seen = []
        real = tpu._verify_batch_device

        def spy(batch):
            seen.append(len(batch))
            return real(batch)

        monkeypatch.setattr(tpu, "_verify_batch_device", spy)
        assert tpu.verify_batch(items) == expected     # probe + sw rest
        assert seen == [8]
        assert tpu.health() == "device"

    @staticmethod
    def _prepared_arrays(n, bad_lane=3):
        """Pre-staged operand arrays for verify_prepared (one key,
        lane `bad_lane` malformed)."""
        key = _KEYS[0]
        digests = np.zeros((n, 32), dtype=np.uint8)
        r = np.zeros((n, 32), dtype=np.uint8)
        rpn = np.zeros((n, 32), dtype=np.uint8)
        w = np.zeros((n, 32), dtype=np.uint8)
        der_ok = np.ones(n, dtype=bool)
        sigs = []
        P256_P = (1 << 256) - (1 << 224) + (1 << 192) + (1 << 96) - 1
        for i in range(n):
            m = f"prepared {i}".encode()
            dg = hashlib.sha256(m).digest()
            sig = _SW.sign(key, dg)
            ri, si = utils.unmarshal_signature(sig)
            wi = pow(si, -1, utils.P256_N)
            rpni = ri + utils.P256_N \
                if ri + utils.P256_N < P256_P else ri
            digests[i] = np.frombuffer(dg, np.uint8)
            r[i] = np.frombuffer(ri.to_bytes(32, "big"), np.uint8)
            rpn[i] = np.frombuffer(rpni.to_bytes(32, "big"), np.uint8)
            w[i] = np.frombuffer(wi.to_bytes(32, "big"), np.uint8)
            sigs.append(sig)
        sigs[bad_lane] = sigs[bad_lane][:-2]
        der_ok[bad_lane] = False
        expected = [i != bad_lane for i in range(n)]
        key_idx = np.zeros(n, dtype=np.int32)
        return digests, r, rpn, w, der_ok, key_idx, [key], sigs, \
            expected

    def test_prepared_path_degrades_bit_identically(self, monkeypatch):
        """verify_prepared under an open breaker rides
        _verify_prepared_sw with identical verdicts."""
        faults.clear()
        tpu, _ = _stubbed_provider(
            monkeypatch, min_batch=4,
            fallback=BreakerConfig(trip_threshold=1, cooldown_s=60.0))
        digests, r, rpn, w, der_ok, key_idx, keys, sigs, expected = \
            self._prepared_arrays(8)
        tpu._breaker.failure(RuntimeError("boom"))     # trip (thresh 1)
        assert tpu.health() == "degraded"
        out = tpu.verify_prepared(digests, r, rpn, w, der_ok, key_idx,
                                  keys, lambda i: sigs[i])
        assert out == expected
        assert tpu.stats["degraded_batches"] == 1

    def test_prepared_probe_is_bounded(self, monkeypatch):
        """In probing state the prepared path risks at most ProbeBatch
        lanes on the device; the rest verify on the host, and the
        merged verdicts stay bit-identical."""
        faults.clear()
        tpu, _ = _stubbed_provider(
            monkeypatch, min_batch=4,
            fallback=BreakerConfig(trip_threshold=1, cooldown_s=0.2,
                                   probe_batch=4))
        digests, r, rpn, w, der_ok, key_idx, keys, sigs, expected = \
            self._prepared_arrays(16)
        tpu._breaker.failure(RuntimeError("boom"))     # trip
        time.sleep(0.25)                               # -> probing
        seen = []
        real = tpu._verify_prepared_device

        def spy(dg, *args):
            seen.append(len(dg))
            return real(dg, *args)

        monkeypatch.setattr(tpu, "_verify_prepared_device", spy)
        out = tpu.verify_prepared(digests, r, rpn, w, der_ok, key_idx,
                                  keys, lambda i: sigs[i])
        assert out == expected
        assert seen == [4]                             # probe bounded
        assert tpu.health() == "device"

    @pytest.mark.parametrize("entry", ["verify_batch",
                                       "verify_prepared"])
    def test_failed_table_build_degrades_like_a_dispatch_failure(
            self, monkeypatch, entry):
        """A slab build (or a pool write) that fails degrades the batch
        exactly as a table failure always did: the sw provider's
        verdicts, one `sw_fallbacks`, the pool as it was — and the next
        batch builds and is served by the device."""
        faults.clear()
        tpu, calls = _stubbed_provider(
            monkeypatch, fallback=BreakerConfig(trip_threshold=5))
        real = tpu._build_slab
        state = {"fail": True}

        def flaky(kb):
            if state["fail"]:
                raise RuntimeError("table build failed")
            return real(kb)
        monkeypatch.setattr(tpu, "_build_slab", flaky)
        if entry == "verify_batch":
            items, expected = _tile(_premask_pool(), 24)
            keys = 2

            def verify():
                return tpu.verify_batch(items)
        else:
            digests, r, rpn, w, der_ok, key_idx, ks, sigs, expected = \
                self._prepared_arrays(8)
            keys = 1

            def verify():
                return tpu.verify_prepared(digests, r, rpn, w, der_ok,
                                           key_idx, ks,
                                           lambda i: sigs[i])

        assert verify() == expected
        assert tpu.stats["sw_fallbacks"] == 1
        assert tpu.stats["key_slots_resident"] == 0
        assert calls["premask"] == []          # nothing was dispatched
        state["fail"] = False
        assert verify() == expected
        assert tpu.stats["sw_fallbacks"] == 1
        assert tpu.stats["key_slots_resident"] == keys
        assert len(calls["premask"]) == 1

    def test_persist_fault_surfaces_in_counter(self, tmp_path):
        faults.clear()
        faults.arm("tpu.table_persist", mode="error", count=1)
        tpu = TPUProvider(min_batch=4, warm_keys_dir=str(tmp_path))
        tpu._persist_slab(b"\x01" * 64, np.zeros(4, dtype=np.int32))
        tpu.flush_warm_tables(timeout=5.0)
        assert tpu.stats["warm_table_persist_failures"] == 1
        assert not list(tmp_path.glob("slab*.npy"))

    def test_flush_warm_tables_total_deadline(self):
        """N stuck writers must cost ONE timeout, not N timeouts."""
        tpu = TPUProvider(min_batch=4)
        for _ in range(3):
            t = threading.Thread(target=time.sleep, args=(5.0,),
                                 daemon=True)
            t.start()
            tpu._persist_threads.append(t)
        t0 = time.monotonic()
        tpu.flush_warm_tables(timeout=0.4)
        assert time.monotonic() - t0 < 2.0
        assert len(tpu._persist_threads) == 3      # still alive, kept

    def test_fallback_config_reaches_breaker(self):
        opts = factory.FactoryOpts.from_config({
            "Default": "TPU",
            "TPU": {"Fallback": {"DeadlineMs": 250, "TripThreshold": 7,
                                 "CooldownS": 3, "ProbeBatch": 128}},
        })
        assert opts.tpu.fallback.deadline_ms == 250
        assert opts.tpu.fallback.trip_threshold == 7
        assert opts.tpu.fallback.cooldown_s == 3
        assert opts.tpu.fallback.probe_batch == 128
        csp = factory.new_bccsp(opts)
        assert isinstance(csp, TPUProvider)
        assert csp._breaker.config.trip_threshold == 7
        assert csp.health() == "device"

    def test_differential_under_ambient_faults(self, monkeypatch):
        """Whatever FTPU_FAULTS armed (nothing, errors, stalls): the
        provider's verdicts match the sw oracle bit for bit. This is
        the invariant tools/chaos_check.sh re-runs under env arming."""
        tpu, _ = _stubbed_provider(
            monkeypatch, min_batch=4,
            fallback=BreakerConfig(trip_threshold=2, cooldown_s=0.01,
                                   deadline_ms=500))
        pool = _premask_pool()
        items, expected = _tile(pool, 64)
        for _ in range(4):
            assert tpu.verify_batch(items) == expected


# ---------------------------------------------------------------------------
# /healthz surface
# ---------------------------------------------------------------------------

class TestHealthzSurface:
    def test_breaker_state_reported(self, monkeypatch):
        from fabric_tpu.node.operations import OperationsServer
        faults.clear()
        tpu, _ = _stubbed_provider(
            monkeypatch, fallback=BreakerConfig(trip_threshold=1,
                                                cooldown_s=60.0))
        srv = OperationsServer()
        srv.register_checker("bccsp", tpu.health)
        srv.start()
        try:
            def get():
                import json
                with urllib.request.urlopen(
                        f"http://{srv.address}/healthz",
                        timeout=10) as resp:
                    return resp.status, json.loads(resp.read())
            status, body = get()
            assert status == 200
            assert body["components"]["bccsp"] == "device"
            tpu._breaker.failure(RuntimeError("dead device"))
            status, body = get()
            assert status == 200       # degraded still SERVES
            assert body["components"]["bccsp"] == "degraded"
        finally:
            srv.stop()

    def test_canonical_fallback_instruments_published(self,
                                                      monkeypatch):
        """The documented bccsp_fallback_state / _trips_total series
        exist and move with the breaker (not just the dynamic
        bccsp_breaker_* stats gauges)."""
        from fabric_tpu.common import metrics as metrics_mod
        from fabric_tpu.common import profiling
        faults.clear()
        tpu, _ = _stubbed_provider(
            monkeypatch, fallback=BreakerConfig(trip_threshold=1,
                                                cooldown_s=60.0))
        provider = metrics_mod.PrometheusProvider()
        assert profiling.publish_provider_stats(
            provider, tpu, poll_s=0.05) is not None
        tpu._breaker.failure(RuntimeError("dead device"))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            text = provider.render()
            if "bccsp_fallback_state 2" in text:
                break
            time.sleep(0.02)
        assert "bccsp_fallback_state 2" in text, text
        assert "bccsp_fallback_trips_total 1" in text, text

    def test_failing_checker_still_503s(self):
        from fabric_tpu.node.operations import OperationsServer
        srv = OperationsServer()
        srv.register_checker("doomed", lambda: 1 / 0)
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{srv.address}/healthz", timeout=10)
            assert ei.value.code == 503
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# deliver client chaos
# ---------------------------------------------------------------------------

class _FakeSigner:
    def serialize(self):
        return b"test-signer"

    def sign(self, msg):
        return b"sig"


class _FakeLedger:
    def __init__(self):
        self.height = 0


class _FakeChannel:
    channel_id = "chaoschannel"

    def __init__(self):
        self.ledger = _FakeLedger()

    def process_block(self, block):
        self.ledger.height += 1


class _FakeMCS:
    def verify_block(self, channel_id, height, block):
        return None


class _FakeEndpoint:
    """Yields blocks forever; `die_after` ends the stream with an
    error after that many blocks per connection."""

    def __init__(self, die_after=None):
        self.die_after = die_after
        self.connections = 0

    def handle(self, env):
        from fabric_tpu.protos import common, orderer as ordpb
        self.connections += 1
        sent = 0
        while True:
            if self.die_after is not None and sent >= self.die_after:
                raise ConnectionError("stream torn down")
            blk = common.Block()
            blk.header.number = sent
            yield ordpb.DeliverResponse(block=blk)
            sent += 1


class TestDeliverChaos:
    def _deliverer(self, endpoint, **kw):
        from fabric_tpu.peer.deliverclient import Deliverer
        ch = _FakeChannel()
        d = Deliverer(ch, _FakeSigner(), lambda: endpoint, _FakeMCS(),
                      retry_base_s=0.005, retry_max_s=0.05, **kw)
        return d, ch

    def test_stream_faults_reconnect_and_count(self):
        faults.clear()
        faults.arm("deliver.stream", mode="error", count=3)
        d, ch = self._deliverer(_FakeEndpoint())
        d.start()
        try:
            deadline = time.monotonic() + 20
            while ch.ledger.height < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            d.stop()
        assert ch.ledger.height >= 3
        assert d.reconnects == 3
        assert d._backoff.failures == 0   # reset by processed blocks

    def test_backoff_resets_after_processed_block(self, monkeypatch):
        """One block per connection, then the stream dies: because the
        failure counter resets on progress, every outage backs off
        from the BASE delay — never pinned at retry_max_s."""
        import random as random_mod
        caps = []
        monkeypatch.setattr(
            random_mod, "uniform",
            lambda lo, hi: caps.append(hi) or 0.0)
        faults.clear()
        d, ch = self._deliverer(_FakeEndpoint(die_after=1))
        d.start()
        try:
            deadline = time.monotonic() + 20
            while ch.ledger.height < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            d.stop()
        assert ch.ledger.height >= 5
        assert len(caps) >= 4
        # failures reset after each delivered block: every cap is the
        # first-retry cap (base * 2), far below retry_max_s
        assert all(abs(c - 0.01) < 1e-9 for c in caps), caps

    def test_reconnect_counter_exported(self):
        from fabric_tpu.common import metrics as metrics_mod
        faults.clear()
        faults.arm("deliver.stream", mode="error", count=2)
        provider = metrics_mod.PrometheusProvider()
        d, ch = self._deliverer(_FakeEndpoint(),
                                metrics_provider=provider)
        d.start()
        try:
            deadline = time.monotonic() + 20
            while ch.ledger.height < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            d.stop()
        text = provider.render()
        assert 'deliver_client_reconnects{channel="chaoschannel"} 2' \
            in text, text


# ---------------------------------------------------------------------------
# raft chain chaos
# ---------------------------------------------------------------------------

class TestRaftStepChaos:
    def _bare_chain(self):
        """RaftChain with just the attrs _handle_event touches — the
        event-loop drop-don't-crash contract is what's under test."""
        from fabric_tpu.orderer.raft.chain import RaftChain

        class _Node:
            def __init__(self):
                self.stepped = []

            def step(self, msg):
                self.stepped.append(msg)

        class _Support:
            channel_id = "chaosraft"

        chain = RaftChain.__new__(RaftChain)
        chain.node = _Node()
        chain._peer_seen = {}
        chain._support = _Support()
        return chain

    def test_faulted_step_is_dropped_not_fatal(self):
        from fabric_tpu.protos import raft as rpb
        faults.clear()
        faults.arm("raft.step", mode="error", count=2)
        chain = self._bare_chain()
        msg = rpb.RaftMessage(from_=2, to=1, term=1)
        chain._handle_event(("step", msg), now=0.0)    # dropped
        chain._handle_event(("step", msg), now=0.0)    # dropped
        assert chain.node.stepped == []
        assert chain._peer_seen == {}
        chain._handle_event(("step", msg), now=1.0)    # recovers
        assert len(chain.node.stepped) == 1
        assert chain._peer_seen == {2: 1.0}
        assert faults.fires("raft.step") == 2

    def test_step_exception_does_not_leak(self):
        faults.clear()
        chain = self._bare_chain()

        def bad_step(msg):
            raise ValueError("corrupt message")

        chain.node.step = bad_step
        from fabric_tpu.protos import raft as rpb
        msg = rpb.RaftMessage(from_=3, to=1, term=1)
        chain._handle_event(("step", msg), now=0.0)    # swallowed


# ---------------------------------------------------------------------------
# the real compiled kernel (slow: ~minutes of XLA compile on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestRealDeviceRecovery:
    def test_fail_n_then_recover_on_real_kernel(self):
        """Same fail-N-then-recover scenario, real device math: after
        the transient faults the batch — including lanes only curve
        math can reject — is verified ON DEVICE, bit-identical to sw."""
        faults.clear()
        faults.arm("tpu.dispatch", mode="error", count=2)
        sw = SWProvider()
        keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
                for _ in range(2)]
        items, expected = [], []
        for i in range(12):
            k = keys[i % 2]
            m = f"real kernel {i}".encode()
            sig = sw.sign(k, hashlib.sha256(m).digest())
            ok = i % 4 != 2
            if not ok:
                m += b"!"           # tampered: premask passes, curve
                #                     math must reject
            items.append(VerifyItem(key=k.public_key(), signature=sig,
                                    message=m))
            expected.append(ok)
        tpu = TPUProvider(min_batch=4,
                          fallback=BreakerConfig(trip_threshold=5))
        assert tpu.verify_batch(items) == expected     # fault 1 -> sw
        assert tpu.verify_batch(items) == expected     # fault 2 -> sw
        assert tpu.stats["sw_fallbacks"] == 2
        out = tpu.verify_batch(items)                  # real device
        assert out == expected
        assert tpu.health() == "device"
        assert tpu.stats["comb_batches"] >= 1
