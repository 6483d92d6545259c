"""Elastic device-mesh fault tolerance (ISSUE 11 tentpole).

The contract under test: ONE chip failing or stalling mid-dispatch
must cost that one chip, never the fleet. A device-attributed fault
(`tpu.device_lost` armed against chip k, or a runtime error naming a
device) quarantines exactly that chip through its per-device breaker
(common/devicehealth.py), the provider rebuilds a smaller mesh over
the survivors and KEEPS dispatching on it — (N-1)/N device throughput
instead of the fleet-wide sw degrade — while every accept/reject
bitmap stays bit-identical to the sw oracle. After the cooldown a
bounded single-chip probe re-admits a recovered chip and the mesh
grows back. Stragglers (`tpu.device_straggler` delay faults inflating
one chip's transfer stream) quarantine through consecutive-strike
accounting fed by the `bccsp_shard_*` readings.

Device math uses the recorder-stub idiom (tests/test_shard_verify.py):
real staging, mesh placement, span feeding, fault points, per-device
breakers and mesh rebuilds — the jitted kernel is replaced by a
premask recorder so host pre-validation IS the verdict. The
slow-marked test at the bottom drives the same loss/rebuild scenario
through the real compiled q8 comb kernel.
"""

import hashlib
import logging
import time

import jax
import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, factory, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import devicehealth, faults
from fabric_tpu.common.devicehealth import (
    DeviceHealth,
    DeviceHealthConfig,
    DeviceLostError,
)
from fabric_tpu.parallel import batch_mesh
from tests.test_chaos import _StepClock

pytestmark = pytest.mark.chaos

_SW = SWProvider()
_KEYS = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(2)]

SPAN8 = 1024     # aligned_span granule for an 8-way mesh


_POOL: list = []

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



def _corpus(n):
    """Mixed valid/invalid lanes tiled from a 24-lane signed pool
    (pure-python signing is ~10ms/lane — per-lane signing made this
    module dominate tier-1): verdicts are decided by host
    pre-validation, so tiling loses no coverage."""
    if not _POOL:
        for i in range(24):
            k = _KEYS[i % 2]
            m = f"devhealth {i}".encode()
            sig = _SW.sign(k, hashlib.sha256(m).digest())
            if i % 3 == 2:
                r, s = utils.unmarshal_signature(sig)
                sig = (sig[:-2] if i % 2 else
                       utils.marshal_signature(r, utils.P256_N - s))
                _POOL.append((VerifyItem(key=k.public_key(),
                                         signature=sig, message=m),
                              False))
            else:
                _POOL.append((VerifyItem(key=k.public_key(),
                                         signature=sig, message=m),
                              True))
    items = [_POOL[i % len(_POOL)][0] for i in range(n)]
    expected = [_POOL[i % len(_POOL)][1] for i in range(n)]
    return items, expected


def _stubbed_provider(mesh=None, dh_config=None, **kw):
    kw.setdefault("min_batch", 1)
    kw.setdefault("use_g16", False)
    kw.setdefault("pipeline_chunk", SPAN8)
    tpu = TPUProvider(mesh=mesh, device_health=dh_config, **kw)
    calls = {"premask": [], "dispatches": 0}

    def fake_qtab_fn():
        return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                           dtype=np.int32)

    def fake_pipeline_digest():
        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            calls["premask"].append(np.asarray(premask).copy())
            calls["dispatches"] += 1
            return np.asarray(premask)
        return run

    def fake_ladder():
        def run(blocks, nblocks, qx, qy, r, rpn, w, premask, digests,
                has_digest):
            calls["dispatches"] += 1
            return np.asarray(premask)
        return run

    tpu._slab_rows = lambda: SLAB_ROWS

    tpu._qtab_fn = fake_qtab_fn
    tpu._comb_pipeline_digest = fake_pipeline_digest
    tpu._pipeline = fake_ladder
    return tpu, calls


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh from conftest")
    return batch_mesh(8)


def _wait_for(cond, timeout=10.0, what="condition"):
    """Poll for an async outcome (re-admission probes run on daemon
    threads off the hot path — admission never blocks on them)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# fault-point arg targeting (the chaos seam the device points ride)
# ---------------------------------------------------------------------------

class TestFaultArgTargeting:
    def test_armed_arg_fires_only_on_matching_check(self):
        faults.clear()
        faults.arm("tpu.device_lost", mode="error", count=None, arg=3)
        try:
            faults.check("tpu.device_lost", arg=1)   # no fire
            faults.check("tpu.device_lost")          # arg-less: no fire
            assert faults.fires("tpu.device_lost") == 0
            with pytest.raises(faults.FaultInjected):
                faults.check("tpu.device_lost", arg=3)
            assert faults.fires("tpu.device_lost") == 1
        finally:
            faults.clear()

    def test_env_grammar_fourth_field_targets_device(self):
        faults.clear()
        try:
            faults.arm_from_env("tpu.device_lost=error:1::5")
            faults.check("tpu.device_lost", arg=4)
            with pytest.raises(faults.FaultInjected):
                faults.check("tpu.device_lost", arg=5)
            # count=1 consumed
            faults.check("tpu.device_lost", arg=5)
        finally:
            faults.clear()

    def test_argless_arming_fires_for_any_device(self):
        faults.clear()
        faults.arm("tpu.device_lost", mode="error", count=2)
        try:
            with pytest.raises(faults.FaultInjected):
                faults.check("tpu.device_lost", arg=0)
            with pytest.raises(faults.FaultInjected):
                faults.check("tpu.device_lost", arg=7)
        finally:
            faults.clear()

    def test_new_points_in_known_registry(self):
        assert "tpu.device_lost" in faults.KNOWN_POINTS
        assert "tpu.device_straggler" in faults.KNOWN_POINTS


# ---------------------------------------------------------------------------
# the quarantine ring (unit)
# ---------------------------------------------------------------------------

class TestDeviceHealthRing:
    def test_fault_quarantines_then_probe_readmits(self):
        clk = _StepClock()
        dh = DeviceHealth(8, DeviceHealthConfig(cooldown_s=5.0),
                          clock=clk)
        assert dh.healthy() == list(range(8))
        assert dh.record_fault(3, RuntimeError("boom")) is True
        assert dh.healthy() == [0, 1, 2, 4, 5, 6, 7]
        assert dh.totals()["device_quarantines"] == 1
        # cooldown not elapsed: no probe slot offered
        assert dh.probe_candidates() == []
        clk.advance(5.1)
        assert dh.probe_candidates() == [3]
        # the slot is single-admission until the outcome reports
        assert dh.probe_candidates() == []
        dh.probe_result(3, True)
        assert dh.healthy() == list(range(8))
        assert dh.totals()["device_readmits"] == 1

    def test_failed_probe_reopens_cooldown(self):
        clk = _StepClock()
        dh = DeviceHealth(4, DeviceHealthConfig(cooldown_s=2.0),
                          clock=clk)
        dh.record_fault(1, RuntimeError("x"))
        clk.advance(2.1)
        assert dh.probe_candidates() == [1]
        dh.probe_result(1, False)
        assert dh.healthy() == [0, 2, 3]
        assert dh.probe_candidates() == []       # cooling down again
        clk.advance(2.1)
        assert dh.probe_candidates() == [1]
        dh.probe_result(1, True)
        assert dh.healthy() == [0, 1, 2, 3]

    def test_stale_reclaimed_probe_success_is_not_a_readmit(self):
        """A probe slower than the breaker's stale-probe reclaim
        window (max(cooldown_s, 1s)): a state poll reclaims the slot
        and re-opens the breaker; the probe's late success must NOT
        count a readmit — the chip never rejoined the mesh. Held
        under probe_execution() the same slow probe is NOT
        reclaimable and its success re-admits for real."""
        clk = _StepClock()
        dh = DeviceHealth(4, DeviceHealthConfig(cooldown_s=0.5),
                          clock=clk)
        dh.record_fault(2, RuntimeError("x"))
        clk.advance(0.6)
        assert dh.probe_candidates() == [2]
        # probe runs WITHOUT the execution marker and outlives the
        # reclaim window (max(0.5, 1.0) = 1.0s): a state poll
        # reclaims the slot
        clk.advance(1.1)
        assert dh.healthy() == [0, 1, 3]     # reclaim fired
        dh.probe_result(2, True)             # late success
        assert dh.totals()["device_readmits"] == 0
        assert 2 in dh.quarantined()
        # next round, probe held LIVE via probe_execution: the same
        # slow probe is not reclaimed and its success re-admits
        clk.advance(0.6)
        assert dh.probe_candidates() == [2]
        with dh.probe_execution(2):
            clk.advance(1.1)
            assert 2 not in dh.healthy()     # still just probing
            dh.probe_result(2, True)
        assert dh.totals()["device_readmits"] == 1
        assert dh.healthy() == [0, 1, 2, 3]

    def test_straggler_strikes_consecutive_then_reset(self):
        dh = DeviceHealth(4, DeviceHealthConfig(
            straggler_skew_s=0.1, straggler_strikes=3))
        idx = [0, 1, 2, 3]
        slow = [0.0, 0.0, 0.5, 0.0]      # device 2 over budget
        clean = [0.0] * 4
        assert dh.observe_shard(idx, slow, []) == []
        assert dh.observe_shard(idx, slow, []) == []
        # a clean batch resets the consecutive count
        assert dh.observe_shard(idx, clean, []) == []
        assert dh.observe_shard(idx, slow, []) == []
        assert dh.observe_shard(idx, slow, []) == []
        assert dh.observe_shard(idx, slow, []) == [2]
        assert dh.healthy() == [0, 1, 3]
        assert dh.totals()["device_quarantines"] == 1
        assert dh.totals()["device_straggler_strikes"] == 5

    def test_ready_lag_jump_localizes_straggler(self):
        """ready_s is sampled in mesh order (cumulative upper bound):
        a straggler at chip k steps the curve AT k — the jump, not
        the absolute value, attributes the strike."""
        dh = DeviceHealth(4, DeviceHealthConfig(
            straggler_skew_s=0.1, straggler_strikes=1))
        ready = [0.01, 0.02, 0.5, 0.5]   # the step is at device 2
        assert dh.observe_shard([0, 1, 2, 3], [], ready) == [2]
        assert dh.healthy() == [0, 1, 3]

    def test_correlated_stragglers_both_quarantine(self):
        """Two chips on one degrading link cross the strike budget in
        the SAME batch: both quarantine — neither escapes with its
        strikes silently reset."""
        dh = DeviceHealth(4, DeviceHealthConfig(
            straggler_skew_s=0.1, straggler_strikes=2))
        idx = [0, 1, 2, 3]
        slow2 = [0.0, 0.5, 0.0, 0.5]     # devices 1 and 3 over budget
        assert dh.observe_shard(idx, slow2, []) == []
        assert sorted(dh.observe_shard(idx, slow2, [])) == [1, 3]
        assert dh.healthy() == [0, 2]
        assert dh.totals()["device_quarantines"] == 2

    def test_skew_zero_disables_straggler_quarantine(self):
        dh = DeviceHealth(4, DeviceHealthConfig(
            straggler_skew_s=0.0, straggler_strikes=1))
        assert dh.observe_shard([0, 1, 2, 3],
                                [0.0, 9.0, 0.0, 0.0], []) == []
        assert dh.healthy() == [0, 1, 2, 3]

    def test_reattributed_fault_never_extends_cooldown(self):
        """Stale dispatches keep naming an already-benched chip (the
        total-loss shape): the extra faults must NOT re-arm its
        cooldown, or the chip never reaches its re-admission probe."""
        clk = _StepClock()
        dh = DeviceHealth(4, DeviceHealthConfig(cooldown_s=3.0),
                          clock=clk)
        dh.record_fault(1, RuntimeError("x"))
        clk.advance(2.9)
        # re-attribution just before cooldown expiry: ignored
        assert dh.record_fault(1, RuntimeError("again")) is False
        assert dh.attribute(RuntimeError("device 1 still dead")) == 1
        clk.advance(0.2)
        assert dh.probe_candidates() == [1]

    def test_attribute_parses_device_naming_errors(self):
        dh = DeviceHealth(8, DeviceHealthConfig())
        assert dh.attribute(RuntimeError("transfer to device 6 "
                                         "failed")) == 6
        assert dh.attribute(DeviceLostError(2, RuntimeError("x"))) == 2
        assert dh.attribute(RuntimeError("shape mismatch")) is None
        assert dh.attribute(RuntimeError("device 99 gone")) is None
        assert sorted(dh.quarantined()) == [2, 6]


# ---------------------------------------------------------------------------
# elastic mesh through the provider (recorder stubs, mesh8)
# ---------------------------------------------------------------------------

class TestElasticMeshDeviceLoss:
    def test_mid_dispatch_loss_shrinks_then_probe_regrows(self, mesh8):
        """The acceptance scenario at test scale: tpu.device_lost
        armed against chip 3 mid-run — the faulted batch serves sw
        BIT-IDENTICALLY, chip 3 is quarantined (never the whole
        breaker), the next batches dispatch on a 7-device mesh, and
        after the cooldown the re-admission probe restores all 8."""
        faults.clear()
        clk = _StepClock()
        tpu, calls = _stubbed_provider(
            mesh=mesh8,
            dh_config=DeviceHealthConfig(cooldown_s=30.0))
        tpu._devhealth.set_clock(clk)
        items, expected = _corpus(2048)
        oracle = _SW.verify_batch(items)
        assert expected == oracle

        faults.arm("tpu.device_lost", mode="error", count=1, arg=3)
        # batch 1: chip 3 dies mid-span-feed -> sw fallback, parity
        assert tpu.verify_batch(items) == oracle
        assert tpu.stats["sw_fallbacks"] == 1
        assert tpu.stats["device_quarantines"] == 1
        assert tpu._breaker.state == "device"      # fleet NOT benched
        assert tpu.stats["breaker_trips"] == 0
        assert tpu._mesh.size == 7
        assert tpu.stats["shard_devices"] == 7
        assert tpu.stats["mesh_devices_full"] == 8
        assert "degraded_mesh:7/8" in tpu.health()
        assert tpu.device_stats["state"][3] == 2   # quarantined
        # batches 2..4: DISPATCHED on the 7-device mesh (never full
        # sw while healthy chips remain)
        for _ in range(3):
            assert tpu.verify_batch(items) == oracle
        assert tpu.stats["pipeline_batches"] == 3
        assert tpu.stats["sw_fallbacks"] == 1      # no new fallbacks
        assert len(tpu.shard_stats["transfer_s"]) == 7
        # cooldown elapses -> the next admission KICKS chip 3's probe
        # (async — a wedged chip must never stall a batch); the fault
        # budget is exhausted so it succeeds, and a later admission
        # grows the mesh back
        clk.advance(30.1)
        assert tpu.verify_batch(items) == oracle
        _wait_for(lambda: tpu.stats["device_readmits"] == 1,
                  what="probe re-admission")
        assert tpu.verify_batch(items) == oracle
        assert tpu._mesh.size == 8
        assert tpu.health() == "device"
        assert tpu.device_stats["readmits"][3] == 1

    def test_probe_fails_while_fault_still_armed(self, mesh8):
        """An unlimited device_lost arming keeps the chip benched:
        every probe fails through the SAME fault point, the mesh
        stays at 7, and disarming finally re-admits."""
        faults.clear()
        clk = _StepClock()
        tpu, _ = _stubbed_provider(
            mesh=mesh8,
            dh_config=DeviceHealthConfig(cooldown_s=10.0,
                                         probe_timeout_s=2.0))
        tpu._devhealth.set_clock(clk)
        items, expected = _corpus(SPAN8 + 8)
        faults.arm("tpu.device_lost", mode="error", count=None, arg=5)
        assert tpu.verify_batch(items) == expected
        assert tpu._mesh.size == 7
        clk.advance(10.1)
        assert tpu.verify_batch(items) == expected   # kicks the probe
        # the async probe fails through the armed point: the chip
        # drops back to quarantined (state 2) and the mesh stays at 7
        _wait_for(lambda: tpu.device_stats["state"][5] == 2,
                  what="failed probe re-opening quarantine")
        assert tpu.verify_batch(items) == expected
        assert tpu._mesh.size == 7
        assert tpu.stats["device_readmits"] == 0
        faults.clear()
        clk.advance(10.1)
        assert tpu.verify_batch(items) == expected   # kicks the probe
        _wait_for(lambda: tpu.stats["device_readmits"] == 1,
                  what="probe re-admission after disarm")
        assert tpu.verify_batch(items) == expected
        assert tpu._mesh.size == 8

    def test_ten_k_lane_stream_bit_identical_across_loss(self, mesh8):
        """10k lanes streamed in batches with the chip loss landing
        mid-stream: every bitmap bit-identical to the sw oracle, and
        the provider never serves a full-sw batch after the rebuild."""
        faults.clear()
        tpu, _ = _stubbed_provider(
            mesh=mesh8, dh_config=DeviceHealthConfig(cooldown_s=300.0))
        items, expected = _corpus(10_000)
        oracle = _SW.verify_batch(items)
        assert expected == oracle
        batches = [(i, min(i + 2500, 10_000))
                   for i in range(0, 10_000, 2500)]
        out: list = []
        for bi, (lo, hi) in enumerate(batches):
            if bi == 1:     # the loss lands mid-stream
                faults.arm("tpu.device_lost", mode="error", count=1,
                           arg=6)
            out.extend(tpu.verify_batch(items[lo:hi]))
        assert out == oracle
        assert tpu.stats["device_quarantines"] == 1
        assert tpu._mesh.size == 7
        # exactly ONE batch fell back (the one that lost the chip);
        # everything after dispatched on the surviving mesh
        assert tpu.stats["sw_fallbacks"] == 1
        assert tpu.stats["pipeline_batches"] == len(batches) - 1
        assert tpu._breaker.state == "device"

    def test_whole_batch_digest_path_loses_chip_too(self, mesh8):
        """pipeline_chunk=0 (overlap off): the whole-batch sharded
        staging rides the same per-device fault seam and elastic
        rebuild."""
        faults.clear()
        tpu, _ = _stubbed_provider(
            mesh=mesh8, pipeline_chunk=0,
            dh_config=DeviceHealthConfig(cooldown_s=300.0))
        items, expected = _corpus(640)
        faults.arm("tpu.device_lost", mode="error", count=1, arg=0)
        assert tpu.verify_batch(items) == expected
        assert tpu._mesh.size == 7
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["sw_fallbacks"] == 1

    def test_key_pool_dropped_on_rebuild_and_filled_again(self, mesh8):
        """The key-table pool is REPLICATED over the serving mesh;
        after a mesh swap the old-mesh handle holds a replica on the
        benched chip (poisoned on real hardware). The rebuild drops
        the pool, and the next dispatch allocates it over the
        survivors and admits its keys again."""
        faults.clear()
        tpu, _ = _stubbed_provider(
            mesh=mesh8, dh_config=DeviceHealthConfig(cooldown_s=300.0))
        items, expected = _corpus(2048)
        assert tpu.verify_batch(items) == expected
        keys = tpu.stats["key_slots_resident"]
        assert keys >= 1 and len(tpu._pool.addressable_shards) == 8
        builds = tpu.stats["key_slot_builds"]
        faults.arm("tpu.device_lost", mode="error", count=1, arg=1)
        assert tpu.verify_batch(items) == expected   # loss + rebuild
        assert tpu._mesh.size == 7
        assert tpu._pool is None and not tpu._slot_of, \
            "old-mesh replicated pool survived the rebuild"
        assert tpu.stats["key_slots_resident"] == 0
        # the next dispatch fills a pool over the seven survivors
        assert tpu.verify_batch(items) == expected
        assert len(tpu._pool.addressable_shards) == 7
        assert tpu.stats["key_slots_resident"] == keys
        assert tpu.stats["key_slot_builds"] == builds + keys
        assert tpu.stats["pipeline_batches"] == 2

    def test_runtime_error_naming_a_device_attributes(self, mesh8):
        """A dispatch failure whose message names a chip (the real
        XLA/PJRT error shape) quarantines that chip even without the
        DeviceLostError wrapper."""
        faults.clear()
        tpu, calls = _stubbed_provider(
            mesh=mesh8, dh_config=DeviceHealthConfig(cooldown_s=300.0))

        real = tpu._comb_pipeline_digest
        state = {"failed": False}

        def failing_pipeline():
            inner = real()

            def run(*a):
                if not state["failed"]:
                    state["failed"] = True
                    raise RuntimeError(
                        "XLA:TPU compile permanent error on device 4:"
                        " core halted")
                return inner(*a)
            return run

        tpu._comb_pipeline_digest = failing_pipeline
        items, expected = _corpus(2048)
        assert tpu.verify_batch(items) == expected   # sw fallback
        assert tpu.stats["device_quarantines"] == 1
        assert tpu.device_stats["state"][4] == 2
        assert tpu._mesh.size == 7
        assert tpu.verify_batch(items) == expected   # 7-dev dispatch
        assert tpu.stats["pipeline_batches"] == 1

    def test_total_loss_serves_sw_until_a_probe_recovers(self, mesh8):
        """Every chip quarantined: batches serve sw OUTRIGHT (no
        doomed device dispatch paying transfer latency per batch —
        the provider breaker ignores device-attributed errors, so it
        could never degrade on its own), verdicts stay bit-identical,
        and recovered probes rebuild the mesh."""
        faults.clear()
        clk = _StepClock()
        tpu, calls = _stubbed_provider(
            mesh=mesh8, dh_config=DeviceHealthConfig(cooldown_s=5.0))
        tpu._devhealth.set_clock(clk)
        items, expected = _corpus(SPAN8 + 4)
        for d in range(8):
            tpu._devhealth.record_fault(d, RuntimeError("gone"))
        assert tpu._devhealth.healthy() == []
        assert tpu.verify_batch(items) == expected
        assert calls["dispatches"] == 0          # no doomed dispatch
        assert tpu.stats["degraded_batches"] == 1
        assert tpu.stats["sw_fallbacks"] == 0
        clk.advance(5.1)
        assert tpu.verify_batch(items) == expected  # kicks all probes
        _wait_for(lambda: tpu.stats["device_readmits"] == 8,
                  what="all 8 probes re-admitting")
        assert tpu.verify_batch(items) == expected
        # full mesh back, dispatching again
        assert tpu._mesh.size == 8
        assert calls["dispatches"] >= 1


class TestStragglerQuarantine:
    def test_straggler_delay_fault_trips_after_strikes(self, mesh8):
        """tpu.device_straggler (delay mode) inflates chip 2's
        per-device transfer stream; after StragglerStrikes struck
        batches the chip is quarantined and the mesh rebuilds —
        verdicts bit-identical throughout (the straggler only ever
        cost latency)."""
        faults.clear()
        tpu, _ = _stubbed_provider(
            mesh=mesh8,
            dh_config=DeviceHealthConfig(
                cooldown_s=300.0, straggler_skew_s=0.02,
                straggler_strikes=2))
        items, expected = _corpus(2048)
        faults.arm("tpu.device_straggler", mode="delay",
                   delay_s=0.01, arg=2)
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["device_straggler_strikes"] == 1
        assert tpu._mesh.size == 8        # one strike is not a verdict
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["device_quarantines"] == 1
        assert tpu.verify_batch(items) == expected
        assert tpu._mesh.size == 7
        assert "degraded_mesh:7/8" in tpu.health()
        # no sw fallback at any point: a straggler costs latency,
        # never the device path
        assert tpu.stats["sw_fallbacks"] == 0


# ---------------------------------------------------------------------------
# startup degrade + observability
# ---------------------------------------------------------------------------

class TestDegradedStartupHealth:
    def test_provider_reports_unmet_mesh_ask(self):
        tpu = TPUProvider(min_batch=4, use_g16=False,
                          mesh_requested=8)
        assert tpu.health() == "device;degraded_mesh:1/8"

    def test_factory_enumeration_failure_surfaces_on_health(
            self, monkeypatch):
        """_resolve_mesh blowing up (mid-flight libtpu upgrade, a
        chip another process holds) still degrades to single-device — but now as a
        /healthz fact, not just a log line."""
        import fabric_tpu.bccsp.factory as fmod

        def boom(n):
            raise RuntimeError("enumeration failed")
        monkeypatch.setattr(fmod, "_resolve_mesh",
                            lambda nd: (None, nd or "all"))
        prov = fmod.new_bccsp(fmod.FactoryOpts.from_config(
            {"Default": "TPU", "TPU": {"Devices": 4,
                                       "UseG16": False}}))
        assert prov.health() == "device;degraded_mesh:1/4"

    def test_resolve_mesh_reports_unmet_ask_on_failure(
            self, monkeypatch):
        import fabric_tpu.bccsp.factory as fmod

        class _BoomJax:
            def devices(self):
                raise RuntimeError("no backend")
        import sys
        monkeypatch.setitem(sys.modules, "jax", _BoomJax())
        mesh, unmet = fmod._resolve_mesh(4)
        assert mesh is None and unmet == 4
        mesh, unmet = fmod._resolve_mesh(None)
        assert mesh is None and unmet == "all"
        mesh, unmet = fmod._resolve_mesh(1)
        assert mesh is None and unmet is None   # 1 was the ask: met

    def test_devicehealth_config_parsed_from_core_yaml(self):
        opts = factory.FactoryOpts.from_config(
            {"Default": "TPU",
             "TPU": {"DeviceHealth": {"TripThreshold": 2,
                                      "CooldownS": 7.5,
                                      "StragglerSkewS": 0.5,
                                      "StragglerStrikes": 4,
                                      "ProbeTimeoutS": 1.5}}})
        dh = opts.tpu.device_health
        assert dh.trip_threshold == 2
        assert dh.cooldown_s == 7.5
        assert dh.straggler_skew_s == 0.5
        assert dh.straggler_strikes == 4
        assert dh.probe_timeout_s == 1.5


class TestDeviceGauges:
    def test_device_gauges_published_with_device_label(self, mesh8):
        """bccsp_device_{state,trips,quarantines,readmits} render on
        /metrics device-labeled, reading the provider's live
        device_stats property (state changes show without a
        dispatch)."""
        from fabric_tpu.common import metrics as m
        from fabric_tpu.common import profiling

        faults.clear()
        tpu, _ = _stubbed_provider(
            mesh=mesh8, dh_config=DeviceHealthConfig(cooldown_s=300.0))
        items, _ = _corpus(SPAN8 + 8)
        faults.arm("tpu.device_lost", mode="error", count=1, arg=3)
        tpu.verify_batch(items)
        assert tpu.stats["device_quarantines"] == 1
        provider = m.PrometheusProvider()
        t = profiling.publish_provider_stats(provider, tpu,
                                             poll_s=0.01)
        assert t is not None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            text = provider.render()
            if 'bccsp_device_state{device="3"} 2' in text:
                break
            time.sleep(0.02)
        text = provider.render()
        assert 'bccsp_device_state{device="3"} 2' in text
        assert 'bccsp_device_state{device="0"} 0' in text
        assert 'bccsp_device_quarantines{device="3"} 1' in text
        assert 'bccsp_device_trips{device="3"} 1' in text
        assert 'bccsp_device_readmits{device="3"} 0' in text
        # the scalar aggregates stay out of the generic gauge set
        # (fqname collision with the labeled series)
        assert "bccsp_device_quarantines 1" not in text
        # elastic-mesh scalars DO publish
        assert "bccsp_mesh_rebuilds 1" in text
        assert "bccsp_mesh_devices_full 8" in text

    def test_device_stats_property_no_mesh(self):
        tpu = TPUProvider(min_batch=4, use_g16=False)
        assert tpu.device_stats == {"state": [], "trips": [],
                                    "quarantines": [], "readmits": []}


# ---------------------------------------------------------------------------
# real kernel (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestElasticMeshRealKernel:
    def test_real_comb_loss_rebuild_parity(self, mesh8):
        """Full provider, REAL q8 comb kernel: chip 2 lost on the
        first sharded batch (sw fallback, parity), the rebuilt
        7-device mesh recompiles and dispatches the next batch with
        verdicts bit-identical to the sw oracle. Minutes of XLA
        compile — slow suite only; tier-1 covers the same plumbing
        with recorder stubs."""
        faults.clear()
        prov = TPUProvider(
            min_batch=16, use_g16=False, mesh=mesh8,
            pipeline_chunk=0, hash_on_host=True,
            device_health=DeviceHealthConfig(cooldown_s=3600.0))
        items, expected = _corpus(64)
        oracle = _SW.verify_batch(items)
        assert expected == oracle
        faults.arm("tpu.device_lost", mode="error", count=1, arg=2)
        assert prov.verify_batch(items) == oracle    # sw fallback
        assert prov.stats["device_quarantines"] == 1
        assert prov._mesh.size == 7
        assert prov.verify_batch(items) == oracle    # 7-dev kernel
        assert prov.stats["comb_batches"] >= 1
        assert prov.stats["shard_dispatches"] >= 1
        assert prov._breaker.state == "device"
