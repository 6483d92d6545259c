"""TPU BCCSP provider — batched verification on an accelerator mesh.

The rebuild's north star (BASELINE.json): where the reference's fastest
option is one `crypto/ecdsa.Verify` per goroutine
(`bccsp/sw/ecdsa.go:41-57` under the txvalidator pool), this provider
collects a whole block's signatures and runs ONE fixed-shape XLA program
(SHA-256 + P-256 double-scalar-mul) over the padded batch, sharded over
the batch axis of a device mesh.

Structure mirrors the `pkcs11` provider's containment
(`bccsp/pkcs11/pkcs11.go`): everything except `verify_batch` delegates to
an embedded `sw` provider; no layer above the factory knows TPUs exist.

Semantics: host-side pre-validation (strict DER, positivity, low-S) is
the SAME code path the sw provider uses (`sw.check_signature`), so the
two providers' accept/reject sets are structurally identical; the device
kernel then decides the curve equation exactly (integer limb arithmetic,
no floating point). Small batches and device failures fall back to sw —
a 3-signature block must not pay kernel-dispatch latency, and a sidecar
outage must degrade, not halt (SURVEY §7 step 3).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import logging
import os
import threading
from typing import Optional, Sequence

import numpy as np

from fabric_tpu.bccsp import bccsp as api
from fabric_tpu.bccsp import sw as swmod
from fabric_tpu.bccsp import utils
from fabric_tpu.common import breaker as breaker_mod
from fabric_tpu.common import devicecost
from fabric_tpu.common import devicehealth as devhealth_mod
from fabric_tpu.common import faults
from fabric_tpu.common import jaxenv
from fabric_tpu.common import lockcheck
from fabric_tpu.common import tracing
from fabric_tpu.common.devicehealth import DeviceLostError
from fabric_tpu.common.hotpath import hot_path

logger = logging.getLogger("bccsp.tpu")

P256_P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
N = utils.P256_N


def host_prep_scalars(pub, signature):
    """Pure-python per-lane signature prep — the byte-exact reference
    for native/batchprep.cpp (differential-tested): strict DER +
    low-S + scalar-range gates, then the device operand scalars.
    Returns (r, rpn, w) as 32-byte big-endian rows, or None when the
    lane is host-rejected. ONE implementation — the whole-batch path
    and the pipelined prep worker both call this; a policy change here
    cannot desynchronize them."""
    rs = swmod.check_signature(pub, signature)
    if rs is None:
        return None
    r, s = rs
    if r >= N or s >= N:
        # crypto/ecdsa.Verify rejects out-of-range scalars before any
        # curve math; mirror that on the host.
        return None
    rpn = r + N if r + N < P256_P else r
    w = pow(s, -1, N)
    return (r.to_bytes(32, "big"), rpn.to_bytes(32, "big"),
            w.to_bytes(32, "big"))


_DEVICE_INFO: dict = {}     # TPUProvider.device_info() memo

# lanes of one pipeline span on each device, where BCCSP.TPU.PipelineChunk
# is unset. A block Fabric really cuts (MaxMessageCount 500 x 3..4
# signatures) fits one such span three-quarters full; device time grows
# with the lanes (24 ms at 2,048, 39 at 4,096, 81 at 8,192 on a v5e:
# PERF.md, Findings PR 28), so a larger span only adds premasked lanes
# the host then waits for.
SPAN_LANES_PER_DEVICE = 2048

# granule of a span's lanes on each device: the TPU's vector registers
# are 128 lanes wide, and under a mesh every device takes an equal slice
LANE_ALIGN = 128


def aligned_span(lanes: int, mesh_size: int = 1) -> int:
    """Round a requested pipeline-chunk lane count to a multiple of
    LANE_ALIGN * mesh_size (floor, min one granule), so every span of
    every batch reuses one compiled shape that splits evenly over the
    mesh."""
    granule = LANE_ALIGN * max(1, mesh_size)
    return max(granule, (lanes // granule) * granule)


class TPUProvider(api.BCCSP):
    def __init__(self, keystore=None, min_batch: int = 16,
                 max_blocks: int = 64, mesh=None, max_keys: int = 32,
                 chunk: int = 32768,
                 pipeline_chunk: Optional[int] = None,
                 use_g16: Optional[bool] = None,
                 table_cache_bytes: int = 4000 << 20,
                 hash_on_host: bool = True,
                 warm_keys_dir: Optional[str] = None,
                 bucket_floor: int = 0,
                 fallback: Optional[breaker_mod.BreakerConfig] = None,
                 ed25519: bool = True,
                 bls_pairing: Optional[bool] = None,
                 device_health: Optional[
                     devhealth_mod.DeviceHealthConfig] = None,
                 mesh_requested=None):
        self._sw = swmod.SWProvider(keystore)
        # graceful degradation (BCCSP.TPU.Fallback): every device
        # dispatch runs behind this breaker; on trip the provider
        # serves the bit-identical sw path and re-probes the device
        # after a cooldown (see common/breaker.py). Under a mesh,
        # DeviceLostError is device-attributable: it quarantines ONE
        # chip (elastic rebuild below) and must NEVER count against
        # the whole accelerator path — an 8-chip box degrading to
        # 0-chip throughput on a 1-chip fault is the failure mode the
        # device-health layer exists to remove.
        fb = fallback or breaker_mod.BreakerConfig()
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            import dataclasses
            fb = dataclasses.replace(
                fb, ignore=tuple(fb.ignore) + (DeviceLostError,))
        self._breaker = breaker_mod.CircuitBreaker(fb,
                                                   name="bccsp.tpu")
        self._min_batch = min_batch
        # pad device batches up to this bucket (0 = off): a workload of
        # modest windows (e.g. the orderer's 512-envelope sig-filter
        # ingest) can pin itself to an already-AOT-compiled shape
        # instead of compiling its own — padded lanes are premasked
        # and near-free on device (BCCSP.TPU.BucketFloor)
        self._bucket_floor = bucket_floor
        self._max_blocks = max_blocks
        # hash message lanes on host (OpenSSL-class C SHA-256) and ship
        # 32-byte digests instead of padded SHA blocks: transfer drops
        # from O(message bytes) to 32 B/lane and the device runs pure
        # ECDSA. This also mirrors the reference's split —
        # `msp/identities.go:179` hashes via bccsp on CPU, only the
        # curve math is "hardware". Set HashOnHost: false (core.yaml)
        # to fuse SHA-256 into the device pipeline instead — the right
        # trade when the accelerator link is PCIe-fast and host cores
        # are the scarce resource.
        self._hash_on_host = hash_on_host
        # elastic device mesh: `_mesh` is the SERVING mesh (swapped
        # for a smaller one over the survivors when a chip is
        # quarantined, grown back on probe re-admission); `_mesh_full`
        # is the factory-built fleet and the stable device-index
        # space chaos/gauges/quarantine accounting all use.
        self._mesh = mesh
        self._mesh_full = mesh
        self._dev_all = (list(mesh.devices.flat)
                         if mesh is not None else [])
        self._dev_pos = {d: i for i, d in enumerate(self._dev_all)}
        # the factory's unmet multi-device ask (enumeration failure
        # degraded to single-device): surfaced on /healthz as
        # degraded_mesh:1/<requested> so operators SEE the silent
        # 1-chip startup degrade
        self._mesh_requested = mesh_requested
        self._devhealth = (
            devhealth_mod.DeviceHealth(len(self._dev_all),
                                       device_health)
            if len(self._dev_all) > 1 else None)
        self._mesh_lock = threading.Lock()   # serializes rebuilds
        # in-flight device dispatches, drained before a mesh swap so
        # no batch straddles two meshes; while a rebuild is draining,
        # NEW spans hold at the gate (otherwise sustained concurrent
        # load starves the drain and the swap lands mid-batch anyway)
        self._dispatch_cv = threading.Condition()
        self._dispatch_inflight = 0
        self._rebuild_pending = False
        self._probe_threads: dict = {}       # device -> live probe
        # per-batch rotation of the ready-probe sampling order: the
        # first-sampled chip's reading inflates every later one, so a
        # compute-slow chip PERMANENTLY first would never show a jump
        self._ready_rot = 0
        self._chunk = chunk         # double-buffer chunk size (sigs)
        # overlapped dispatch pipeline (BCCSP.TPU.PipelineChunk): a
        # device batch is split into spans of this many lanes; span
        # N's device execution overlaps span N+1's host prep (native
        # DER parse + limb packing on a worker thread) and its async
        # host->device transfer, so host cost hides behind device time
        # instead of adding to it (the FPGA-verify-engine shape,
        # arXiv:2112.02229). None (unset) = SPAN_LANES_PER_DEVICE on
        # each device of the serving mesh; a number is the total lanes
        # of a span, as given; 0 disables (whole-batch staging).
        self._pipeline_chunk = pipeline_chunk
        self._prep_pool = None      # lazy 1-worker host-prep executor
        # 16-bit windows on BOTH bases: the per-signature tree drops
        # from 64 to 32 points (measured 1.6x on the v5e) at the cost
        # of large resident device tables (~252 MB for G, ~252 MB a
        # key for Q). None = auto: on for TPU backends, off for CPU
        # meshes (where a table build takes minutes and HBM budgets
        # don't apply). One width serves the whole process.
        self._use_g16 = use_g16
        # the key-table pool (below, "the key-table pool"): MaxKeys
        # slots, as far as TableCacheMB and the device's free memory
        # hold them; WarmKeysDir keeps a file a resident key, so a
        # restarted node reads its tables back instead of building them
        self._max_keys = max_keys
        self._table_cache_bytes = table_cache_bytes
        self._warm_keys_dir = warm_keys_dir
        self._pool = None           # the resident device array
        self._capacity = None       # its slots, sized at first use
        # key bytes (x || y) -> slot, least recently used first
        self._slot_of: collections.OrderedDict = \
            collections.OrderedDict()
        self._restore_thread = None
        self._fn = None             # lazily-built generic jitted pipeline
        self._comb_fns = {}         # mesh-bound programs, by kind
        self._qtab_fns = {}         # single-device programs, by kind
        self._jit_lock = threading.Lock()   # prewarm thread vs first
        #                                     block: build each jit once
        # observability: perf-cliff counters surfaced via provider stats
        self.stats = {"comb_batches": 0, "ladder_batches": 0,
                      "host_hash_fallbacks": 0, "sw_fallbacks": 0,
                      "host_hashed_lanes": 0,
                      # the key-table pool: keys asked for by
                      # batches and those found resident; slabs built,
                      # read back from WarmKeysDir, evicted; keys
                      # resident now, slots and bytes of the pool
                      "key_slot_lookups": 0, "key_slot_hits": 0,
                      "key_slot_builds": 0, "key_slot_disk_loads": 0,
                      "key_slot_evictions": 0, "key_slots_resident": 0,
                      "key_slot_capacity": 0, "key_table_bytes": 0,
                      "nonp256_sw_lanes": 0,
                      "ed25519_batches": 0,
                      "bls_aggregate_checks": 0,
                      # round-21 pairing-engine counters: device
                      # Miller-product batches (BLS aggregate + BN254
                      # idemix), pairs they carried, and demotions to
                      # the host pairing (breaker/error only — the
                      # small-batch policy route is not a fallback)
                      "pairing_batches": 0, "pairing_pairs": 0,
                      "pairing_fallbacks": 0,
                      "pipeline_batches": 0, "pipeline_chunks": 0,
                      "pipeline_host_s": 0.0,
                      "pipeline_transfer_s": 0.0,
                      "pipeline_device_s": 0.0,
                      "pipeline_overlap_ratio": 0.0,
                      "prepared_transfer_s": 0.0,
                      "prepared_device_s": 0.0,
                      # cumulative, prepared-block path: signatures
                      # handed over, the lanes their padded buckets
                      # ran, and the operand bytes staged to the device
                      "lanes_real": 0, "lanes_padded": 0,
                      "h2d_bytes": 0,
                      "shard_devices": (getattr(mesh, "size", 1)
                                        if mesh is not None else 1),
                      "shard_dispatches": 0,
                      "shard_skew_s": 0.0,
                      # elastic-mesh counters (scalar aggregates; the
                      # per-device split rides the device_stats
                      # property as bccsp_device_* gauges)
                      "mesh_devices_full": (getattr(mesh, "size", 1)
                                            if mesh is not None
                                            else 1),
                      "mesh_rebuilds": 0,
                      "device_quarantines": 0,
                      "device_readmits": 0,
                      "device_straggler_strikes": 0,
                      # round-16 device-cost seam (compile & cache
                      # telemetry; common/devicecost.py — the
                      # canonical bccsp_compile_* gauges)
                      "compile_total": 0, "compile_cache_hits": 0,
                      "compile_cold_total": 0, "compile_failures": 0,
                      "compile_seconds": 0.0,
                      # the store of compiled executables behind the
                      # AOT seam (common/execstore.py): prewarm's
                      # requests served without a trace, those compiled
                      # and written, and entries or writes that failed
                      "executable_store_hits": 0,
                      "executable_store_misses": 0,
                      "executable_store_errors": 0,
                      "breaker_state": 0, "breaker_trips": 0,
                      "breaker_probes": 0,
                      "breaker_deadline_timeouts": 0,
                      "breaker_rejected_dispatches": 0,
                      "degraded_batches": 0,
                      "warm_table_persist_failures": 0,
                      "warm_restore_failures": 0,
                      # 1 once prewarm()'s compiles are in: a node's
                      # set-up is done and a later cold compile is an
                      # unplanned shape on the serving path
                      "prewarm_done": 0}
        # per-device stage observability for the sharded dispatch
        # (bccsp_shard_* gauges, published with a `device` label by
        # profiling.publish_provider_stats): one slot per mesh device,
        # refreshed per sharded batch. Empty lists while single-chip.
        self.shard_stats: dict = {"transfer_s": [], "ready_s": [],
                                  "lanes": []}
        # scheme-router observability (bccsp_scheme_* gauges, published
        # with a `scheme` label): cumulative lanes routed per scheme,
        # lanes that fell to the per-lane sw path, and device/aggregate
        # dispatches — the multi-scheme twin of nonp256_sw_lanes, which
        # stays as the scalar total for dashboard continuity
        self.scheme_stats: dict = {"lanes": {}, "sw_lanes": {},
                                   "dispatches": {}}
        # BCCSP.TPU.Ed25519: gate the Ed25519 device kernel (False =
        # Ed25519 lanes serve on the host reference path; verdicts are
        # identical either way)
        self._ed25519_enabled = ed25519
        # BCCSP.TPU.BLSPairing: gate the round-21 batched BLS12-381
        # Miller-product kernel (None = auto: real TPU backends only —
        # on CPU rigs the host reference pairing beats interpret-mode
        # XLA; FTPU_BLS_DEVICE=0/1 overrides). Verdicts are identical
        # either way (ops/bls12_381_kernel vs ops/bls12_381).
        self._bls_pairing = bls_pairing
        self._ed_tab = None         # replicated device B-comb table
        self._g16_rep = None        # mesh-replicated g16 cache
        self._persist_threads: list = []
        self._persist_slots = threading.BoundedSemaphore(2)
        # serializes warm-file removals (an evicted key's slab) with
        # the background table-byte writers' publish step, so a
        # concurrent eviction can never resurrect a reclaimed file
        self._warm_lock = threading.Lock()
        # round-16 device-cost recorder: every compiled-path build
        # rides the _jit seam below; counters mirror into self.stats
        # (bccsp_compile_* gauges) and per-chip busy time accumulates
        # for bccsp_device_busy_ratio. cache_dir resolves LAZILY —
        # the factory enables the persistent cache around provider
        # construction time
        self._devicecost = devicecost.CompileRecorder(
            stats=self.stats, cache_dir=jaxenv.cache_dir)
        # guards the pool and its bookkeeping (_pool, _slot_of,
        # _capacity): the background restore thread and concurrent
        # live batches mutate these together, and a batch holds it
        # from its slot lookup to its last enqueue (the pool write
        # donates the array). Deliberately SEPARATE from _warm_lock —
        # the slow warm-file I/O must never serialize dispatches — and
        # an RLock so helpers can nest.
        self._pool_lock = threading.RLock()
        try:
            d = self.device_info()
            logger.info("BCCSP TPU provider on platform=%s "
                        "device_kind=%r devices=%d", d["platform"],
                        d["device_kind"], d["count"])
        except Exception:           # noqa: BLE001
            # availability first (see factory._resolve_mesh): a backend
            # that cannot enumerate devices is the breaker's business
            logger.exception("BCCSP TPU provider: no JAX backend "
                             "could be enumerated")

    @staticmethod
    def device_info() -> dict:
        """The backend this process's JAX resolved, learned once:
        {"platform", "device_kind", "count"} as `jax.devices()` reports
        them. `health()` says `device` on ANY backend (the breaker is
        closed); this is what tells a TPU from a CPU — logged at
        construction, published as the `bccsp_device_info` gauge and
        the /healthz `bccsp_device` component."""
        if not _DEVICE_INFO:
            import jax
            devs = jax.devices()
            _DEVICE_INFO.update(
                platform=devs[0].platform,
                device_kind=getattr(devs[0], "device_kind", ""),
                count=len(devs))
        return dict(_DEVICE_INFO)

    @classmethod
    def _on_tpu(cls) -> bool:
        d = cls.device_info()
        return ("tpu" in d["platform"].lower()
                or "TPU" in d["device_kind"])

    def _g16_enabled(self) -> bool:
        """Resolve the use_g16 auto default: big resident tables are the
        right trade on a real TPU backend, not on CPU test meshes."""
        if self._use_g16 is None:
            # ftpu-check: allow-lockset(idempotent memo: concurrent
            # racers compute the same backend-derived value)
            self._use_g16 = self._on_tpu()
            logger.info("BCCSP TPU provider: use_g16 auto-resolved to %s",
                        self._use_g16)
        return self._use_g16

    def _bls_pairing_enabled(self) -> bool:
        """Resolve the BLS pairing-kernel knob (BCCSP.TPU.BLSPairing).

        FTPU_BLS_DEVICE=0/1 overrides for experiments and the pairing
        chaos/CI subsets; explicit knob next; auto default = real TPU
        backend only — on CPU rigs the exact host pairing is strictly
        faster than compiling the wide-limb Miller program.
        """
        import os
        env = os.environ.get("FTPU_BLS_DEVICE")
        if env is not None:
            return env != "0"
        if self._bls_pairing is not None:
            return self._bls_pairing
        return self._on_tpu()

    # -- everything non-batch delegates (pkcs11-style containment) --

    def key_gen(self, opts):
        return self._sw.key_gen(opts)

    def key_import(self, raw, opts):
        return self._sw.key_import(raw, opts)

    def get_key(self, ski):
        return self._sw.get_key(ski)

    def hash(self, msg, opts=None):
        return self._sw.hash(msg, opts)

    def sign(self, key, digest, opts=None):
        # Signing stays on CPU by design: secret keys + RNG never leave
        # the host (SURVEY §7 hard-parts list).
        return self._sw.sign(key, digest, opts)

    def verify(self, key, signature, digest, opts=None):
        return self._sw.verify(key, signature, digest, opts)

    def encrypt(self, key, plaintext, opts=None):
        return self._sw.encrypt(key, plaintext, opts)

    def decrypt(self, key, ciphertext, opts=None):
        return self._sw.decrypt(key, ciphertext, opts)

    # -- degradation surface --

    def health(self) -> str:
        """Breaker state for /healthz: 'device' | 'degraded' |
        'probing', with the elastic-mesh sub-state appended when the
        serving mesh is smaller than the fleet —
        'device;degraded_mesh:<k>/<n>' (k healthy of n chips; also
        '1/<requested>' when startup enumeration failed and the node
        silently serves single-device), and the round-16 HBM-headroom
        sub-state ('...;hbm_low:d<k>:<free>%free') when any chip's
        free memory drops under FTPU_HBM_HEADROOM_FRAC — an operator
        sees an oversized span BEFORE it OOMs. Verdicts are identical
        in every state; only the serving path (and therefore
        throughput) differs."""
        st = self._breaker.state
        parts = [p for p in (self._mesh_substate(),
                             self._hbm_substate()) if p]
        return ";".join([st] + parts) if parts else st

    def _mesh_substate(self) -> Optional[str]:
        """`degraded_mesh:<k>/<n>` when serving on fewer chips than
        the fleet (quarantine, or a failed startup enumeration), else
        None."""
        if self._mesh_full is None:
            if self._mesh_requested is not None:
                return f"degraded_mesh:1/{self._mesh_requested}"
            return None
        cur = self._mesh.size if self._mesh is not None else 1
        full = self._mesh_full.size
        if cur < full:
            return f"degraded_mesh:{cur}/{full}"
        return None

    def _hbm_substate(self) -> Optional[str]:
        """`hbm_low:d<k>:<free>%free` when any device's free memory
        fraction drops under the headroom threshold (devices without
        memory_stats — CPU meshes — never report), else None."""
        try:
            return devicecost.hbm_substate()
        except Exception:           # noqa: BLE001
            return None

    @property
    def device_stats(self) -> dict:
        """Per-device health rows (one slot per FULL-mesh device),
        read fresh per poll by profiling.publish_provider_stats and
        published as the device-labeled `bccsp_device_{state,trips,
        quarantines,readmits}` gauges. Empty lists while single-chip."""
        if self._devhealth is None:
            return {"state": [], "trips": [], "quarantines": [],
                    "readmits": []}
        return self._devhealth.snapshot()

    @property
    def device_cost(self) -> devicecost.CompileRecorder:
        """The round-16 compile/cache/busy recorder — read by
        profiling.publish_devicecost_stats and the bench's
        compile_s / mem_peak_bytes stage fields."""
        return self._devicecost

    def _jit(self, kind: str, fn, params: Optional[dict] = None,
             **jit_kw):
        """The ONE compiled-path build seam: every jitted program the
        provider serves (comb/digest/ladder/table builders, ed25519,
        pairing, g2msm) is built here, so the `tpu.compile` fault
        point, the compile-telemetry recorder and the `tpu.compile`
        tracing spans cover every path by construction. An armed
        fault (or a broken backend) books a compile_failures count
        and an error-status span, then propagates to the caller's
        breaker/fallback exactly as before.

        The program carries its `kind` as its name, so a device trace
        shows `jit_comb_digest(<id>)`, `jit_qtab16(<id>)`, ... and not
        one `jit_fused` for every program whose inner function happens
        to be called `fused`. (The name is part of the persistent
        compile cache's key.)

        `params` names every static parameter of the builder that made
        `fn` — what its closure holds beside the argument shapes. A
        program `prewarm` requests ahead of time is kept, compiled, in
        the executable store under them (`InstrumentedJit.aot`); the
        store cannot see a closure, so a parameter left out here is a
        wrong program served. The serving mesh is added here."""
        t0 = self._devicecost._clock()
        mesh = self._mesh
        params = dict(params or {}, mesh=None if mesh is None else (
            mesh.axis_names, [d.id for d in mesh.devices.flat]))

        @functools.wraps(fn)
        def program(*args, **kwargs):
            return fn(*args, **kwargs)
        program.__name__ = program.__qualname__ = kind
        try:
            with tracing.span("tpu.compile", kind=kind, build=True):
                faults.check("tpu.compile")
                import jax
                jitted = jax.jit(program, **jit_kw)
        except BaseException as e:
            self._devicecost.note(kind, self._devicecost._clock() - t0,
                                  cache_hit=False, error=e)
            raise
        return self._devicecost.wrap(
            kind, jitted, params=params,
            static_argnums=jit_kw.get("static_argnums", ()))

    def _sync_breaker_stats(self) -> None:
        b = self._breaker
        self.stats["breaker_state"] = b.state_code
        self.stats["breaker_trips"] = b.stats["trips"]
        self.stats["breaker_probes"] = b.stats["probes"]
        self.stats["breaker_deadline_timeouts"] = \
            b.stats["deadline_timeouts"]
        self.stats["breaker_rejected_dispatches"] = b.stats["rejected"]

    # -- elastic device mesh (fail-in-place; common/devicehealth.py) --

    @contextlib.contextmanager
    def _dispatch_span(self):
        """Mark one device dispatch live so a concurrent mesh rebuild
        drains it (waits for in-flight spans) before swapping the
        serving mesh out from under it. New spans HOLD at the gate
        while a rebuild is draining — without that, sustained
        concurrent verify load keeps `_dispatch_inflight` above zero
        forever and every rebuild burns its full drain deadline then
        swaps mid-batch anyway. The hold is bounded: the rebuild's
        drain wait is, and `_rebuild_pending` clears in its finally."""
        import time as _time
        with self._dispatch_cv:
            deadline = None
            while self._rebuild_pending:
                if deadline is None:
                    deadline = _time.monotonic() + 10.0
                if _time.monotonic() >= deadline:
                    break        # never wedge a dispatch on the gate
                self._dispatch_cv.wait(0.1)
            self._dispatch_inflight += 1
        try:
            # one `tpu.verify` span per breaker-guarded device
            # dispatch (whichever scheme path): the bench's verify
            # p50/p99 and the flight recorder's dispatch timeline
            with tracing.span("tpu.verify"):
                yield
            # first successful dispatch = steady state: from here a
            # cold compile is a serving-path latency cliff and the
            # recorder auto-dumps the timeline around it
            self._devicecost.mark_steady()
        finally:
            with self._dispatch_cv:
                self._dispatch_inflight -= 1
                self._dispatch_cv.notify_all()

    def _device_index(self, dev) -> int:
        """A device's FULL-mesh index — stable across rebuilds, the
        space chaos targeting / quarantine accounting / bccsp_device_*
        labels all share."""
        return self._dev_pos.get(dev, -1)

    def _attribute_device_failure(self, exc: BaseException
                                  ) -> Optional[int]:
        """Map a failed dispatch to ONE chip (DeviceLostError carries
        it; other runtime errors are matched when the message names a
        device) and quarantine it via its per-device breaker. Returns
        the struck full-mesh index, else None. Called from the sw-
        fallback handlers so the NEXT batch rebuilds and keeps
        (N-1)/N device throughput instead of serving sw fleet-wide."""
        if self._devhealth is None:
            return None
        d = self._devhealth.attribute(exc)
        if d is None:
            return None
        self.stats.update(self._devhealth.totals())
        # rebuild promptly (not lazily at the next admission): the
        # very next batch must dispatch on the surviving mesh
        self._maybe_probe_and_rebuild(probe=False)
        return d

    def _maybe_probe_and_rebuild(self,
                                 probe: bool = True
                                 ) -> Optional[list]:
        """Admission-time health hook: kick any due re-admission
        probes (ASYNCHRONOUSLY — a wedged chip's probe timeout must
        never stall a consensus-critical batch), then swap the
        serving mesh whenever healthy membership changed (shrink on
        quarantine, grow back on readmission). Returns the healthy
        full-mesh index list (None for a no-mesh provider): an EMPTY
        list tells the caller to serve sw outright instead of paying
        a doomed per-batch dispatch. Cheap when nothing changed (one
        list compare)."""
        dh = self._devhealth
        if dh is None:
            return None
        if probe:
            for d in dh.probe_candidates():
                self._spawn_probe(d)
        healthy = dh.healthy()
        cur = [self._device_index(d)
               for d in self._mesh.devices.flat] \
            if self._mesh is not None else []
        if healthy == cur or not healthy:
            # unchanged — or NOTHING healthy: keep the current mesh
            # object (an empty mesh cannot dispatch); callers see the
            # empty healthy list and serve sw until a probe recovers
            # a chip
            return healthy
        try:
            self._rebuild_mesh(healthy)
        except Exception:
            # a failed rebuild keeps the old mesh: dispatches on it
            # either work or fall to sw through the breaker — never
            # fail the caller's verify from the admission hook
            logger.exception("degraded-mesh rebuild failed; keeping "
                             "the current serving mesh")
        return healthy

    def _spawn_probe(self, d: int) -> None:
        """Run one chip's re-admission probe on a daemon thread; the
        caller's batch proceeds on the current mesh and a LATER
        admission grows the mesh once the outcome lands. The probe
        slot was already taken in probe_candidates(), so concurrent
        admissions cannot double-probe (the breaker's stale-probe
        reclaim backstops a thread that dies without reporting)."""
        dh = self._devhealth

        def work():
            ok = False
            try:
                # mark the probe LIVE on the chip's breaker: its wall
                # time (probe_timeout_s) may exceed the breaker's
                # stale-probe reclaim window, and a reclaim under a
                # merely-slow probe would turn its success into a
                # phantom readmit
                with dh.probe_execution(d):
                    ok = self._probe_device(d)
            finally:
                dh.probe_result(d, ok)
                if ok:
                    self.stats.update(dh.totals())
                self._probe_threads.pop(d, None)

        t = threading.Thread(target=work, daemon=True,
                             name=f"bccsp-device-probe-{d}")
        self._probe_threads[d] = t
        t.start()

    def _probe_device(self, d: int) -> bool:
        """One bounded single-chip probe: ship a tiny array to the
        quarantined device and run a trivial computation on it, on a
        watchdog thread so a wedged chip cannot stall admission. Goes
        through the SAME `tpu.device_lost` fault point as the span
        feeder (arg = full-mesh index) so chaos keeps a dead chip
        benched until it disarms."""
        timeout = (self._devhealth.config.probe_timeout_s
                   if self._devhealth else 5.0)
        box: dict = {}
        done = threading.Event()

        def work():
            try:
                faults.check("tpu.device_lost", arg=d)
                import jax
                import jax.numpy as jnp
                dev = self._dev_all[d]
                x = jax.device_put(np.arange(8, dtype=np.int32), dev)
                jax.block_until_ready(jnp.sum(x + 1))
                box["ok"] = True
            except BaseException as e:  # noqa: BLE001
                box["error"] = e
            finally:
                done.set()

        t = threading.Thread(target=work, daemon=True,
                             name=f"bccsp-device-probe-{d}")
        t.start()
        if not done.wait(timeout) or "error" in box:
            logger.warning(
                "device %d re-admission probe failed (%s); staying "
                "quarantined", d,
                box.get("error", f"no answer in {timeout:.1f}s"))
            return False
        return True

    @hot_path
    @tracing.traced("tpu.mesh_rebuild")
    def _rebuild_mesh(self, healthy: list) -> None:
        """Swap the serving mesh for one over `healthy` (full-mesh
        indices): drain in-flight dispatch spans (bounded — a wedged
        span must not hold the rebuild forever), drop every compiled
        program and replicated table handle bound to the old mesh,
        then install the new one. The key-table pool is dropped with
        them and filled again by the first dispatches (`_key_slots`);
        span/bucket floors re-derive per batch from the serving mesh
        size."""
        lockcheck.note_blocking("tpu.mesh_rebuild")
        import time as _time
        with self._mesh_lock:
            cur = [self._device_index(d)
                   for d in self._mesh.devices.flat] \
                if self._mesh is not None else []
            if healthy == cur:
                return              # another thread already rebuilt
            # gate NEW spans for the WHOLE drain+swap window: without
            # the gate sustained load starves the drain, and a span
            # admitted between drain and swap would recompile an
            # old-mesh program into the freshly-cleared fn cache
            with self._dispatch_cv:
                self._rebuild_pending = True
            try:
                deadline = _time.monotonic() + 5.0
                with self._dispatch_cv:
                    while self._dispatch_inflight > 0 and \
                            _time.monotonic() < deadline:
                        self._dispatch_cv.wait(
                            max(0.0, deadline - _time.monotonic()))
                    if self._dispatch_inflight > 0:
                        logger.warning(
                            "mesh rebuild proceeding with %d dispatch "
                            "span(s) still in flight after the drain "
                            "deadline (they serve sw on failure)",
                            self._dispatch_inflight)
                if len(healthy) == len(self._dev_all):
                    mesh = self._mesh_full
                else:
                    from fabric_tpu.parallel import batch_mesh
                    mesh = batch_mesh(
                        devices=[self._dev_all[i] for i in healthy])
                with self._jit_lock:
                    # every compiled shard_map program and replicated
                    # table handle embeds the old mesh — drop them;
                    # the jit cache rebuilds (persistent-cache-
                    # assisted) and the tables re-replicate on first
                    # dispatch
                    self._comb_fns.clear()
                    self._fn = None
                    self._ed_tab = None
                # the pool replicated over the OLD mesh holds a copy
                # on the benched chip: drop it, the first dispatch on
                # the new mesh admits its keys again (from WarmKeysDir
                # where their slabs are persisted, else rebuilt)
                self._drop_pool()
                self._mesh = mesh
            finally:
                with self._dispatch_cv:
                    self._rebuild_pending = False
                    self._dispatch_cv.notify_all()
            self.stats["shard_devices"] = mesh.size
            self.stats["mesh_rebuilds"] += 1
            tracing.instant("tpu.mesh_rebuild", devices=mesh.size,
                            full=len(self._dev_all))
            if mesh.size < len(self._dev_all):
                logger.warning(
                    "serving mesh REBUILT over %d/%d device(s) "
                    "(quarantined: %s) — keeping %d/%d device "
                    "throughput instead of the sw path",
                    mesh.size, len(self._dev_all),
                    self._devhealth.quarantined()
                    if self._devhealth else [],
                    mesh.size, len(self._dev_all))
            else:
                logger.info(
                    "serving mesh restored to the full %d device(s)",
                    mesh.size)

    # -- the batch path --

    def _bump_scheme(self, scheme: str, lanes: int = 0,
                     sw_lanes: int = 0, dispatches: int = 0) -> None:
        """One accounting point for the scheme router (bccsp_scheme_*
        gauges). Plain dict math — the GIL makes the += atomic enough
        for gauges, exactly like the scalar stats."""
        for key, n in (("lanes", lanes), ("sw_lanes", sw_lanes),
                       ("dispatches", dispatches)):
            if n:
                d = self.scheme_stats[key]
                d[scheme] = d.get(scheme, 0) + n

    def _sw_scatter(self, lanes, result, verify_fn,
                    scheme: str = "ecdsa-other") -> None:
        """THE consolidated non-device-lane bookkeeping (was four
        duplicated `nonp256_sw_lanes` sites): verify `lanes` through
        `verify_fn` (a callable taking the lane list and returning
        per-lane verdicts on the embedded sw provider) and scatter
        into `result`, accounting the scalar total and the per-scheme
        split in one place."""
        lanes = list(lanes)
        if not lanes:
            return
        self.stats["nonp256_sw_lanes"] += len(lanes)
        # sw_lanes only: these lanes were already counted under the
        # scheme that routed them here (router `lanes` partitions the
        # batch; `sw_lanes` records the detours within it)
        self._bump_scheme(scheme, sw_lanes=len(lanes))
        for i, v in zip(lanes, verify_fn(lanes)):
            result[i] = v

    @staticmethod
    def _lane_scheme(item) -> str:
        """Router partition key for one lane: which per-scheme
        sub-batch serves it. Everything the legacy P-256 staging
        already handles inline (P-256, non-P-256 ECDSA sw lanes, dead
        non-ECDSA keys) stays "p256" so that path remains bit-for-bit
        the pre-router pipeline."""
        key = item.key
        if getattr(key, "scheme", None) == "ed25519":
            return "ed25519"
        if getattr(key, "scheme", None) == "bls12381":
            return "bls"
        return "p256"

    def verify_batch(self, items: Sequence[api.VerifyItem]) -> list[bool]:
        """The scheme-dispatch router: partition lanes by (curve,
        hash) into per-scheme sub-batches — P-256 rides the existing
        comb/tree pipeline, Ed25519 the new batch kernel, BLS the
        per-lane pairing path (aggregates arrive via
        `verify_aggregate`), everything else the sw fallback — each
        behind the shared breaker/fallback. A pure-P-256 batch (the
        overwhelmingly common case) takes the legacy path with zero
        extra staging; every lane of a mixed batch is routed (none
        silently dropped), and the combined bitmap is bit-identical
        to all-sw."""
        if len(items) < self._min_batch:
            return self._sw.verify_batch(items)
        schemes = [self._lane_scheme(it) for it in items]
        if all(s == "p256" for s in schemes):
            return self._verify_batch_p256(items)
        by_scheme: dict[str, list[int]] = {}
        for i, s in enumerate(schemes):
            by_scheme.setdefault(s, []).append(i)
        result: list = [False] * len(items)
        for scheme, lanes in by_scheme.items():
            sub = [items[i] for i in lanes]
            if scheme == "p256":
                out = self._verify_batch_p256(sub)
            elif scheme == "ed25519":
                out = self._verify_batch_ed25519(sub)
            else:               # per-lane BLS verify on the host path
                out = self._sw.verify_batch(sub)
                self._bump_scheme(scheme, lanes=len(lanes),
                                  sw_lanes=len(lanes))
            for i, v in zip(lanes, out):
                result[i] = v
        return result

    def _verify_batch_p256(self, items: Sequence[api.VerifyItem]
                           ) -> list[bool]:
        """The pre-router batch path: P-256 device verify with inline
        sw lanes for non-P-256 ECDSA keys and dead lanes for
        everything unknown. Sub-batches from the router land here
        too, so the min-batch cutoff below still protects a mixed
        batch's small P-256 remainder from device-dispatch latency.

        Owns its own scheme accounting (like the Ed25519 path):
        `dispatches` bumps only after a device dispatch actually
        succeeded; sub-min-batch remainders, open-breaker degrades
        and guard fallbacks count as `sw_lanes` — so the gauges show
        the sw detours they document instead of a healthy device
        path."""
        self._bump_scheme("p256", lanes=len(items))
        if len(items) < self._min_batch:
            self._bump_scheme("p256", sw_lanes=len(items))
            return self._sw.verify_batch(items)
        # elastic-mesh health hook BEFORE admission: kick due chip
        # re-admission probes and apply any pending mesh shrink/grow,
        # so this batch stages against a coherent serving mesh. With
        # EVERY chip benched, serve sw outright — the provider
        # breaker ignores device-attributed errors, so a doomed
        # dispatch would just pay transfer latency per batch forever
        healthy = self._maybe_probe_and_rebuild()
        if healthy is not None and not healthy:
            self.stats["degraded_batches"] += 1
            self._bump_scheme("p256", sw_lanes=len(items))
            return self._sw.verify_batch(items)
        # admission FIRST: admit() resolves the breaker state and the
        # probe decision atomically, so a cooldown expiring between a
        # state peek and the dispatch can never send an un-split batch
        # to the suspect device as the probe
        try:
            is_probe = self._breaker.admit()
        except breaker_mod.CircuitOpen:
            self.stats["degraded_batches"] += 1
            self._sync_breaker_stats()
            self._bump_scheme("p256", sw_lanes=len(items))
            return self._sw.verify_batch(items)
        # probing: risk at most ProbeBatch lanes on the suspect device;
        # the rest of the batch verifies on the host path (results are
        # bit-identical either way, so the split is invisible)
        dev_items, probe_rest = items, None
        if is_probe:
            pb = self._breaker.config.probe_batch
            if pb and len(items) > max(pb, self._min_batch):
                cut = max(pb, self._min_batch)
                dev_items, probe_rest = items[:cut], items[cut:]
        try:
            with self._dispatch_span():
                out = self._breaker.guard(
                    lambda: self._verify_batch_device(dev_items))
        except Exception as e:
            self.stats["sw_fallbacks"] += 1
            self._sync_breaker_stats()
            self._bump_scheme("p256", sw_lanes=len(items))
            struck = self._attribute_device_failure(e)
            logger.exception(
                "TPU batch verify failed%s; falling back to sw for "
                "%d items",
                (f" (device {struck} quarantined)"
                 if struck is not None else ""), len(items))
            return self._sw.verify_batch(items)
        self._sync_breaker_stats()
        self._bump_scheme("p256", dispatches=1)
        if probe_rest is not None:
            self._bump_scheme("p256", sw_lanes=len(probe_rest))
            out = out + self._sw.verify_batch(probe_rest)
        return out

    def _verify_batch_device(self, items) -> list[bool]:
        # the tpu.dispatch fault point lives in the INNER dispatch
        # helpers (_dispatch_arrays/_dispatch_comb_digest, and the
        # overlapped pipeline's own check) — exactly one fire per
        # logical batch, whichever path staging takes
        if self._hash_on_host:
            out = self._verify_batch_pipelined(items)
            if out is not None:
                return out
        import jax.numpy as jnp

        from fabric_tpu.ops import limb, sha256

        n = len(items)
        bucket = self._bucket(n)

        premask = np.zeros(bucket, dtype=bool)
        r_b = np.zeros((bucket, 32), dtype=np.uint8)
        rpn_b = np.zeros((bucket, 32), dtype=np.uint8)
        w_b = np.zeros((bucket, 32), dtype=np.uint8)
        qx_b = np.zeros((bucket, 32), dtype=np.uint8)
        qy_b = np.zeros((bucket, 32), dtype=np.uint8)
        key_idx = np.zeros(bucket, dtype=np.int32)
        key_map: dict[bytes, int] = {}
        msgs: list[bytes] = []
        digests = np.zeros((bucket, 8), dtype=np.uint32)
        has_digest = np.zeros(bucket, dtype=bool)

        # host-side signature prep: the C++ extension parses/gates the
        # whole batch in one call (native/batchprep.cpp — strict DER,
        # low-S, range, w = s^-1 mod n); pure Python is the fallback
        # with byte-identical semantics (differential-tested)
        from fabric_tpu import native as native_mod
        native_out = None
        if native_mod.available():
            native_out = native_mod.batch_prep(
                [it.signature if isinstance(it.key.public_key(),
                                            swmod.ECDSAPublicKey)
                 else b"" for it in items])

        max_len = 0
        sw_lanes: list[int] = []    # non-P-256 ECDSA keys: per-lane sw
        for i, it in enumerate(items):
            pub = it.key.public_key()
            if not isinstance(pub, swmod.ECDSAPublicKey):
                msgs.append(b"")
                continue            # premask stays False -> reject
            if not pub.is_p256() or (it.digest is not None
                                     and len(it.digest) != 32):
                # the device kernels are P-256 over 32-byte digests;
                # other curves / digest sizes verify on the sw path
                # WITHOUT degrading the rest of the batch
                sw_lanes.append(i)
                msgs.append(b"")
                continue
            if native_out is not None:
                ok_i, r_all, rpn_all, w_all = native_out
                if not ok_i[i]:
                    msgs.append(b"")
                    continue
                premask[i] = True
                r_b[i] = r_all[i]
                rpn_b[i] = rpn_all[i]
                w_b[i] = w_all[i]
            else:
                prep = host_prep_scalars(pub, it.signature)
                if prep is None:
                    msgs.append(b"")
                    continue
                premask[i] = True
                r_b[i] = np.frombuffer(prep[0], np.uint8)
                rpn_b[i] = np.frombuffer(prep[1], np.uint8)
                w_b[i] = np.frombuffer(prep[2], np.uint8)
            qx_b[i] = pub.x_bytes()
            qy_b[i] = pub.y_bytes()
            kb = qx_b[i].tobytes() + qy_b[i].tobytes()
            key_idx[i] = key_map.setdefault(kb, len(key_map))
            if it.digest is not None:
                digests[i] = np.frombuffer(it.digest, dtype=">u4")
                has_digest[i] = True
                msgs.append(b"")
            else:
                msgs.append(it.message)
                max_len = max(max_len, len(it.message))

        msgs += [b""] * (bucket - n)
        if self._hash_on_host:
            # default path: host SHA-256 → 32-byte digest lanes (runs
            # for EVERY pending lane, including empty messages — an
            # empty message still hashes to SHA-256(b""), never to a
            # zero digest)
            hashed = 0
            for i in range(n):
                if premask[i] and not has_digest[i]:
                    digests[i] = np.frombuffer(
                        self._sw.hash(msgs[i]), dtype=">u4")
                    has_digest[i] = True
                    msgs[i] = b""
                    hashed += 1
            self.stats["host_hashed_lanes"] += hashed
            max_len = 0
        if max_len == 0 and bool(np.all(has_digest[:n] |
                                        ~premask[:n])):
            # every lane is a digest (or dead) lane: dispatch the
            # transfer-minimal digest pipeline — compact u8 scalars,
            # on-device limb conversion, no SHA stage at all
            if 0 < len(key_map) <= self._key_capacity():
                out = self._dispatch_comb_digest(
                    bucket, key_map, key_idx, r_b, rpn_b, w_b,
                    premask, digests)
                result = out[:n].tolist()
                self._sw_scatter(
                    sw_lanes, result,
                    lambda ls: self._sw.verify_batch(
                        [items[i] for i in ls]))
                return result
            blocks = np.zeros((bucket, 1, 16), dtype=np.uint32)
            nblocks = np.zeros(bucket, dtype=np.int32)
            r_l = limb.be_bytes_to_limbs(r_b)
            rpn_l = limb.be_bytes_to_limbs(rpn_b)
            w_l = limb.be_bytes_to_limbs(w_b)
            return self._finish_dispatch(
                bucket, key_map, key_idx, blocks, nblocks, r_l, rpn_l,
                w_l, premask, digests, has_digest, qx_b, qy_b, n,
                items, sw_lanes)
        nb = self._nb_bucket(max_len)
        if nb is None:
            # a message too large for the block budget: hash host-side and
            # turn every message lane into a digest lane so the nb=1 pack
            # below only ever sees empty messages
            self.stats["host_hash_fallbacks"] += 1
            logger.info("message of %d bytes exceeds the %d-block device "
                        "budget; hashing the batch host-side", max_len,
                        self._max_blocks)
            for i, m in enumerate(msgs[:n]):
                if premask[i] and not has_digest[i]:
                    digests[i] = np.frombuffer(
                        self._sw.hash(m), dtype=">u4")
                    has_digest[i] = True
                msgs[i] = b""
            nb = 1
        blocks, nblocks = sha256.pack_messages(msgs, nb)
        # digest-carrying lanes skip on-device hashing: zero their block
        # count and inject the digest after the hash stage via select
        nblocks = np.where(has_digest, 0, nblocks).astype(np.int32)

        r_l = limb.be_bytes_to_limbs(r_b)
        rpn_l = limb.be_bytes_to_limbs(rpn_b)
        w_l = limb.be_bytes_to_limbs(w_b)
        return self._finish_dispatch(
            bucket, key_map, key_idx, blocks, nblocks, r_l, rpn_l, w_l,
            premask, digests, has_digest, qx_b, qy_b, n, items,
            sw_lanes)

    @hot_path
    @tracing.traced("tpu.dispatch")
    def _dispatch_arrays(self, bucket, key_map, key_idx, blocks,
                         nblocks, r_l, rpn_l, w_l, premask, digests,
                         has_digest, qx_b, qy_b, async_out=False):
        """Array core shared by the item path and the prepared-block
        path: comb (the keys fit the pool) or generic ladder dispatch.
        With async_out the DISPATCH happens now and a thunk returning
        the materialized np result is returned (jax compute proceeds
        in the background while the caller works)."""
        lockcheck.note_blocking("tpu.dispatch")
        faults.check("tpu.dispatch")
        import jax.numpy as jnp

        from fabric_tpu.ops import limb

        if 0 < len(key_map) <= self._key_capacity():
            thunk = self._dispatch_comb(
                bucket, key_map, key_idx, blocks, nblocks, r_l, rpn_l,
                w_l, premask, digests, has_digest, async_out=True)
        else:
            self.stats["ladder_batches"] += 1
            qx_l = limb.be_bytes_to_limbs(qx_b)
            qy_l = limb.be_bytes_to_limbs(qy_b)
            args = (blocks, nblocks, qx_l, qy_l, r_l, rpn_l, w_l,
                    premask, digests, has_digest)
            # under a mesh the host arrays stay UNCOMMITTED so the
            # jit's NamedSharding in_shardings place each lane slice
            # on its device directly (a jnp.asarray here would commit
            # to device 0 and force a gather-then-scatter reshard)
            stage = ((lambda a: a) if self._mesh is not None
                     else jnp.asarray)
            # lanes are independent: above the span the bucket goes
            # span by span like the comb tiers, one lane shape
            chunk = self._mesh_chunk(bucket)
            fn = self._pipeline()
            outs = [fn(*(stage(a[lo:lo + chunk]) for a in args))
                    for lo in range(0, bucket, chunk)]
            thunk = lambda: np.concatenate(  # noqa: E731
                # ftpu-lint: allow-host-sync(the thunk IS the deliberate
                # materialization point, invoked after dispatch returns)
                [np.asarray(o) for o in outs])
        return thunk if async_out else thunk()

    def _finish_dispatch(self, bucket, key_map, key_idx, blocks,
                         nblocks, r_l, rpn_l, w_l, premask, digests,
                         has_digest, qx_b, qy_b, n, items, sw_lanes):
        out = self._dispatch_arrays(bucket, key_map, key_idx, blocks,
                                    nblocks, r_l, rpn_l, w_l, premask,
                                    digests, has_digest, qx_b, qy_b)
        result = out[:n].tolist()
        self._sw_scatter(
            sw_lanes, result,
            lambda ls: self._sw.verify_batch([items[i] for i in ls]))
        return result

    # -- the Ed25519 batch path (scheme router "ed25519" lanes) --

    def _verify_batch_ed25519(self, items) -> list[bool]:
        """Ed25519 sub-batch: host gates + SHA-512 challenge per lane
        (`ed25519_host.prep_verify` — the shared policy), then ONE
        device dispatch of the vmapped [S]B + [k](-A) == R kernel,
        behind the SAME breaker/fallback as the P-256 path. Small
        sub-batches, a disabled kernel (BCCSP.TPU.Ed25519: false) and
        device failures serve the host reference with bit-identical
        verdicts."""
        n = len(items)
        if n < self._min_batch or not self._ed25519_enabled:
            self._bump_scheme("ed25519", lanes=n, sw_lanes=n)
            return self._sw.verify_batch(items)
        healthy = self._maybe_probe_and_rebuild()
        if healthy is not None and not healthy:
            self.stats["degraded_batches"] += 1
            self._bump_scheme("ed25519", lanes=n, sw_lanes=n)
            return self._sw.verify_batch(items)
        try:
            is_probe = self._breaker.admit()
        except breaker_mod.CircuitOpen:
            self.stats["degraded_batches"] += 1
            self._sync_breaker_stats()
            self._bump_scheme("ed25519", lanes=n, sw_lanes=n)
            return self._sw.verify_batch(items)
        dev_items, probe_rest = items, None
        if is_probe:
            pb = self._breaker.config.probe_batch
            if pb and n > max(pb, self._min_batch):
                cut = max(pb, self._min_batch)
                dev_items, probe_rest = items[:cut], items[cut:]
        try:
            with self._dispatch_span():
                out = self._breaker.guard(
                    lambda: self._dispatch_ed25519(dev_items))
        except Exception as e:
            self.stats["sw_fallbacks"] += 1
            self._sync_breaker_stats()
            self._bump_scheme("ed25519", lanes=n, sw_lanes=n)
            struck = self._attribute_device_failure(e)
            logger.exception(
                "Ed25519 batch verify failed%s; falling back to sw "
                "for %d items",
                (f" (device {struck} quarantined)"
                 if struck is not None else ""), n)
            return self._sw.verify_batch(items)
        self._sync_breaker_stats()
        self._bump_scheme("ed25519", lanes=len(dev_items),
                          dispatches=1)
        if probe_rest is not None:
            self._bump_scheme("ed25519", lanes=len(probe_rest),
                              sw_lanes=len(probe_rest))
            out = out + self._sw.verify_batch(probe_rest)
        return out

    @hot_path
    @tracing.traced("tpu.ed25519")
    def _dispatch_ed25519(self, items) -> list[bool]:
        """The Ed25519 device span: host prep rows (gates + challenge
        already computed), bucket/chunk staging, sharded feed under a
        mesh, one compiled kernel per chunk shape."""
        lockcheck.note_blocking("tpu.ed25519")
        faults.check("tpu.ed25519")
        import jax

        from fabric_tpu.bccsp import ed25519_host as edh
        from fabric_tpu.ops import ed25519 as edo

        n = len(items)
        prep = []
        for it in items:
            pub = it.key.public_key()
            msg = it.message if it.message is not None else it.digest
            prep.append(None if msg is None else
                        edh.prep_verify(pub.bytes(), it.signature,
                                        msg))
        bucket = self._bucket(n)
        rows = edo.stage_rows(prep, bucket)
        tab = self._ed_table()
        fn = self._ed25519_pipeline()
        chunk = self._mesh_chunk(bucket)
        outs = []
        for lo in range(0, bucket, chunk):
            arrs = tuple(a[lo:lo + chunk] for a in rows)
            if self._mesh is not None:
                arrs = self._shard_put(arrs)
            else:
                arrs = tuple(jax.device_put(a) for a in arrs)
            outs.append(fn(tab, *arrs))
        self.stats["ed25519_batches"] += 1
        # ftpu-lint: allow-host-sync(end-of-batch materialization: the
        # sub-batch's single deliberate sync point)
        out = np.concatenate([np.asarray(o) for o in outs])
        return out[:n].tolist()

    def _ed25519_pipeline(self):
        """Jitted (optionally shard_mapped) Ed25519 batch kernel: the
        B-comb table rides replicated, per-lane operand rows sharded
        on the batch axis — the digest-pipeline discipline."""
        key = ("ed25519",)
        with self._jit_lock:
            if key not in self._comb_fns:
                from fabric_tpu.ops import ed25519 as edo
                fn = edo.verify_core
                if self._mesh is not None:
                    from jax.sharding import PartitionSpec as P
                    s = P("batch")
                    rep = P()
                    fn = jaxenv.shard_map(
                        fn, mesh=self._mesh,
                        in_specs=(rep, s, s, s, s, s, s, s),
                        out_specs=s)
                self._comb_fns[key] = self._jit("ed25519", fn)
            return self._comb_fns[key]

    def _ed_table(self):
        """The persisted fixed-base B-comb table as a device array,
        replicated across the mesh like q_flat/g16 (built through the
        same sidecar-verified cache seam — ops/ed25519.b_tables)."""
        with self._jit_lock:
            if self._ed_tab is None:
                import jax.numpy as jnp

                from fabric_tpu.ops import ed25519 as edo
                tab = jnp.asarray(edo.b_tables())
                if self._mesh is not None:
                    import jax
                    from jax.sharding import (
                        NamedSharding, PartitionSpec as P,
                    )
                    tab = jax.device_put(
                        tab, NamedSharding(self._mesh, P()))
                self._ed_tab = tab
            return self._ed_tab

    # -- BLS aggregate verify (orderer cluster/consenter identities) --

    def verify_aggregate(self, keys, messages, signature) -> bool:
        """BLS12-381 aggregate verify: structural/subgroup gates stage
        the pairing-product pair list (`ops/bls12_381.stage_pairs`),
        then every Miller product of the call runs as ONE fixed-shape
        batched device program with ONE shared final exponentiation
        (`ops/bls12_381_kernel`, the round-21 lift of ROADMAP item 4)
        behind the `tpu.bls_aggregate` fault point, the breaker and
        the _jit/compile-recorder seams. Small batches, a disabled
        kernel (auto: off on CPU rigs) and device failures serve the
        staged host path; any staged-path failure serves the host
        reference on the embedded sw provider — verdicts bit-identical
        on every route (the degrade-don't-halt contract)."""
        # materialize one-shot iterables up front: the staged loop
        # below consumes both, and the fault fallback needs them again
        keys = list(keys)
        msgs = list(messages)
        pks = []
        for k in keys:
            pub = k.public_key()
            if getattr(pub, "scheme", None) != "bls12381":
                raise TypeError("verify_aggregate requires BLS keys")
            pks.append(pub.point)
        # lanes counted ONCE per call, whichever path serves (the
        # router partition invariant); dispatches only after the
        # staged path actually produced the verdict
        self._bump_scheme("bls", lanes=len(pks))
        try:
            lockcheck.note_blocking("tpu.bls_aggregate")
            faults.check("tpu.bls_aggregate")
            from fabric_tpu.ops import bls12_381 as blsagg
            from fabric_tpu.ops import bls12_381_ref as bref
            try:
                sig = bref.g1_from_bytes(signature,
                                         subgroup_check=False)
            except ValueError:
                return False
            pairs = blsagg.stage_pairs(pks, msgs, sig)
            out = (False if pairs is None
                   else self._bls_pairing_check(pairs))
            self.stats["bls_aggregate_checks"] += 1
            self._bump_scheme("bls", dispatches=1)
            return out
        except Exception:
            self.stats["sw_fallbacks"] += 1
            self._bump_scheme("bls", sw_lanes=len(pks))
            logger.exception(
                "staged BLS aggregate verify failed; host reference "
                "fallback for %d keys", len(pks))
            # msgs, not messages: a one-shot iterable was already
            # consumed by the staged path above
            return self._sw.verify_aggregate(keys, msgs, signature)

    def _bls_pairing_check(self, pairs) -> bool:
        """Route ONE staged aggregate-verify pair list: the batched
        device kernel when the pair count clears the gate, the knob
        resolves on, the mesh is healthy and the breaker admits;
        otherwise the staged host path (`ops/bls12_381`). Verdicts
        are bit-identical on every route."""
        from fabric_tpu.ops import bls12_381 as blsagg

        def host() -> bool:
            return blsagg.check_products(blsagg.miller_products(pairs))

        n = len(pairs)
        if (not self._bls_pairing_enabled()
                or n < max(2, self._min_batch // 4)):
            return host()
        healthy = self._maybe_probe_and_rebuild()
        if healthy is not None and not healthy:
            self.stats["degraded_batches"] += 1
            self.stats["pairing_fallbacks"] += 1
            return host()
        try:
            self._breaker.admit()
        except breaker_mod.CircuitOpen:
            self.stats["degraded_batches"] += 1
            self.stats["pairing_fallbacks"] += 1
            self._sync_breaker_stats()
            return host()
        try:
            with self._dispatch_span():
                out = self._breaker.guard(
                    lambda: self._dispatch_bls_pairing(pairs))
        except Exception as e:
            self.stats["sw_fallbacks"] += 1
            self.stats["pairing_fallbacks"] += 1
            self._sync_breaker_stats()
            struck = self._attribute_device_failure(e)
            logger.exception(
                "device BLS pairing failed%s; staged host path for "
                "%d pairs",
                (f" (device {struck} quarantined)"
                 if struck is not None else ""), n)
            return host()
        self._sync_breaker_stats()
        return out

    @hot_path
    @tracing.traced("tpu.bls_pairing")
    def _dispatch_bls_pairing(self, pairs) -> bool:
        """The BLS pairing device span: pad the staged pairs to a
        power-of-two bucket (masked filler lanes contribute the Fp12
        identity), one compiled Miller-product program per bucket
        shape via the _jit/compile-recorder seam, ONE final
        exponentiation per call, one scalar verdict back."""
        import jax.numpy as jnp

        from fabric_tpu.ops import bls12_381_kernel as blsk

        n = len(pairs)
        bucket = 1
        while bucket < n:
            bucket *= 2
        staged = blsk.stage_pairs(pairs, pad_to=bucket)
        key = ("bls_pairing", bucket)
        # _jit_lock: same discipline as _qtab_fn/_q16_fn — the
        # jitted-fn cache is shared with the prewarm restore thread
        with self._jit_lock:
            if key not in self._qtab_fns:
                self._qtab_fns[key] = self._jit(
                    "bls_pairing",
                    lambda xP, yP, qx0, qx1, qy0, qy1, mask:
                    blsk.pairs_product_is_one(xP, yP, qx0, qx1, qy0,
                                              qy1, mask))
        # ftpu-lint: allow-host-sync(single scalar verdict: the
        # call's one deliberate materialization point)
        out = np.asarray(self._qtab_fns[key](
            *[jnp.asarray(a) for a in staged]))
        self.stats["pairing_batches"] += 1
        self.stats["pairing_pairs"] += n
        # ftpu-lint: allow-host-sync(scalar verdict of the already
        # materialized result array — no extra device round trip)
        return bool(out[0])

    # -- the overlapped dispatch pipeline (BCCSP.TPU.PipelineChunk) --

    def _pipeline_span(self) -> Optional[int]:
        """Lanes of one dispatch of the span pipeline, over all
        devices: the configured PipelineChunk as given or, unset,
        SPAN_LANES_PER_DEVICE for each device of the serving mesh
        (2,048 on one chip; 8,192 on a four-chip mesh, whose shard_map
        program so keeps 2,048 lanes a chip) — floored to the
        lane/mesh granule (aligned_span) and capped at Chunk. None
        when the overlapped pipeline is disabled — including when the
        mesh granule itself exceeds Chunk (the span must never break
        the per-dispatch staging cap)."""
        ndev = self._mesh.size if self._mesh is not None else 1
        pc = self._pipeline_chunk
        if pc is None:
            pc = SPAN_LANES_PER_DEVICE * ndev
        if pc <= 0:
            return None
        span = aligned_span(min(pc, self._chunk), ndev)
        return span if span <= self._chunk else None

    def _prep_executor(self):
        # ONE worker by design: host prep is the stage being hidden,
        # not parallelized — a second worker would only contend with
        # the main thread for the GIL during limb packing
        with self._jit_lock:
            if self._prep_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._prep_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="bccsp-prep")
            return self._prep_pool

    @hot_path
    @tracing.traced("tpu.pipeline")
    def _verify_batch_pipelined(self, items) -> Optional[list[bool]]:
        """Double-buffered verify: the batch is split into fixed
        PipelineChunk-lane spans; while span N executes on device,
        a worker thread runs span N+1's host prep (native batchprep
        DER parse + digest hashing + operand packing) and the main
        thread enqueues its async host->device transfer
        (jax.device_put) ahead of dispatch. Every span reuses ONE
        compiled shape (the tail span is padded and premasked), so
        chunk counts that do not divide the lane count cost nothing.

        Returns None when this batch should take the whole-batch
        staging path instead: pipeline disabled, fewer than two spans,
        or more distinct keys than the pool has slots (the generic
        ladder path keeps its own staging). Verdicts are bit-identical to the
        whole-batch path (pipeline-parity tested)."""
        import time as _time

        pc = self._pipeline_span()
        n = len(items)
        if pc is None or n <= pc:
            return None

        from fabric_tpu import native as native_mod

        # host signature gates FIRST, over the whole batch — exactly
        # the whole-batch path's order, so key MEMBERSHIP (and
        # therefore which slabs are built) is identical across the
        # two paths: a lane whose signature fails the DER/low-S/range
        # gates must not register its key. Native parses the batch in
        # one GIL-released C call (fast — the EXPENSIVE host half,
        # digest hashing + operand packing, stays in the per-span
        # worker below, overlapped with device execution).
        use_native = native_mod.available()
        native_out = None
        p256_lane = np.zeros(n, dtype=bool)
        sw_lanes: list[int] = []
        pubs: list = [None] * n
        for i, it in enumerate(items):
            pub = it.key.public_key()
            if not isinstance(pub, swmod.ECDSAPublicKey):
                continue            # dead lane -> False
            if not pub.is_p256() or (it.digest is not None
                                     and len(it.digest) != 32):
                sw_lanes.append(i)
                continue
            p256_lane[i] = True
            pubs[i] = pub
        if use_native:
            native_out = native_mod.batch_prep(
                [it.signature if p256_lane[i] else b""
                 for i, it in enumerate(items)])
        py_prep: list = [None] * n
        key_map: dict[bytes, int] = {}
        key_idx = np.zeros(n, dtype=np.int32)
        lane_ok = np.zeros(n, dtype=bool)
        for i in range(n):
            if not p256_lane[i]:
                continue
            if native_out is not None:
                if not native_out[0][i]:
                    continue
            else:
                py_prep[i] = host_prep_scalars(pubs[i],
                                               items[i].signature)
                if py_prep[i] is None:
                    continue
            lane_ok[i] = True
            kb = pubs[i].x_bytes().tobytes() + pubs[i].y_bytes().tobytes()
            key_idx[i] = key_map.setdefault(kb, len(key_map))
        if not (0 < len(key_map) <= self._key_capacity()):
            return None             # ladder/empty batches: legacy path

        lockcheck.note_blocking("tpu.dispatch")
        faults.check("tpu.dispatch")
        import jax

        nspans = (n + pc - 1) // pc

        def prep(ci: int):
            """Host stage for span ci (worker thread): digest hashing
            + operand packing into fresh pc-shaped arrays (the
            gate/scalar results were computed batch-wide above)."""
            t0 = _time.perf_counter()
            lo, hi = ci * pc, min((ci + 1) * pc, n)
            r8 = np.zeros((pc, 32), dtype=np.uint8)
            rpn8 = np.zeros((pc, 32), dtype=np.uint8)
            w8 = np.zeros((pc, 32), dtype=np.uint8)
            premask = np.zeros(pc, dtype=bool)
            dg = np.zeros((pc, 8), dtype=np.uint32)
            kidx = np.zeros(pc, dtype=np.int32)
            kidx[:hi - lo] = key_idx[lo:hi]
            premask[:hi - lo] = lane_ok[lo:hi]
            if native_out is not None:
                _, r_a, rpn_a, w_a = native_out
                r8[:hi - lo] = r_a[lo:hi]
                rpn8[:hi - lo] = rpn_a[lo:hi]
                w8[:hi - lo] = w_a[lo:hi]
            hashed = 0
            for j, i in enumerate(range(lo, hi)):
                if not lane_ok[i]:
                    continue
                it = items[i]
                if native_out is None:
                    p = py_prep[i]
                    r8[j] = np.frombuffer(p[0], np.uint8)
                    rpn8[j] = np.frombuffer(p[1], np.uint8)
                    w8[j] = np.frombuffer(p[2], np.uint8)
                if it.digest is not None:
                    dg[j] = np.frombuffer(it.digest, dtype=">u4")
                else:
                    dg[j] = np.frombuffer(self._sw.hash(it.message),
                                          dtype=">u4")
                    hashed += 1
            return ((kidx, r8, rpn8, w8, premask, dg),
                    (t0, _time.perf_counter()), hashed)

        ndev = self._mesh.size if self._mesh is not None else 1
        tdev = [0.0] * ndev

        def put(arrs):
            if self._mesh is not None:
                # sharded span feed: per-device transfer streams,
                # lanes dealt across the mesh (bccsp_shard_* gauges)
                return self._shard_put(arrs, tdev)
            return tuple(jax.device_put(a) for a in arrs)

        prep_pool = self._prep_executor()
        outs = []
        prep_ivs = []
        host_s = transfer_s = dispatch_s = 0.0
        hashed_total = 0
        t_disp0 = None
        # the pool lock from the slot lookup to the last enqueue: a
        # concurrent batch's table write donates the array in hand
        with self._pool_lock:
            key_idx, table, g16 = self._key_slots(key_map, key_idx)
            fn = self._comb_pipeline_digest()
            fut = prep_pool.submit(prep, 0)
            for ci in range(nspans):
                arrs, iv, hashed = fut.result()
                prep_ivs.append(iv)
                host_s += iv[1] - iv[0]
                hashed_total += hashed
                if ci + 1 < nspans:
                    fut = prep_pool.submit(prep, ci + 1)
                t0 = _time.perf_counter()
                dev = put(arrs)
                transfer_s += _time.perf_counter() - t0
                t0 = _time.perf_counter()
                if t_disp0 is None:
                    t_disp0 = t0
                outs.append(fn(dev[0], table, g16, *dev[1:]))
                dispatch_s += _time.perf_counter() - t0
        if self._mesh is not None:
            # per-device stage gauges BEFORE the gather: the final
            # span's shard readiness is the per-chip signal; the
            # np gather below would flatten it into one number
            self.stats["shard_dispatches"] += nspans
            self._record_shard_stats(outs[-1], tdev, pc, t_disp0)
        t0 = _time.perf_counter()
        # ftpu-lint: allow-host-sync(end-of-batch materialization: all
        # spans are dispatched, this is the single deliberate sync)
        flat = np.concatenate([np.asarray(o) for o in outs])
        t_done = _time.perf_counter()
        device_s = dispatch_s + (t_done - t0)

        self._book_comb(nspans)
        self.stats["pipeline_host_s"] = round(host_s, 6)
        self.stats["pipeline_transfer_s"] = round(transfer_s, 6)
        self.stats["pipeline_device_s"] = round(device_s, 6)
        if self._mesh is None:
            # single-chip providers have no per-shard ready probe;
            # the batch's device stage IS device 0's busy time
            self._devicecost.busy.note(0, device_s)
        # overlap = the host-prep time that ran INSIDE the device-busy
        # window [first dispatch, results materialized] — measured as
        # interval intersection, not main-thread wait time, because
        # with async dispatch the main thread parks on the prep future
        # while device work proceeds in the background. Span 0's prep
        # necessarily precedes the first dispatch, so a fully-hidden
        # pipeline tops out at (spans-1)/spans.
        overlap_s = sum(
            max(0.0, min(e, t_done) - max(s, t_disp0))
            for s, e in prep_ivs)
        self.stats["pipeline_overlap_ratio"] = round(
            overlap_s / host_s, 4) if host_s > 0 else 0.0
        self.stats["host_hashed_lanes"] += hashed_total

        result = flat[:n].tolist()
        self._sw_scatter(
            sw_lanes, result,
            lambda ls: self._sw.verify_batch([items[i] for i in ls]))
        return result

    # -- the prepared-block path (native host pipeline) --

    def verify_prepared(self, digests: np.ndarray, r: np.ndarray,
                        rpn: np.ndarray, w: np.ndarray,
                        der_ok: np.ndarray, key_idx: np.ndarray,
                        keys, get_sig) -> list[bool]:
        return self.verify_prepared_start(
            digests, r, rpn, w, der_ok, key_idx, keys, get_sig)()

    def verify_prepared_start(self, digests: np.ndarray, r: np.ndarray,
                              rpn: np.ndarray, w: np.ndarray,
                              der_ok: np.ndarray, key_idx: np.ndarray,
                              keys, get_sig):
        """Batched verify over pre-staged operand arrays.

        The host pipeline (native/blockprep.cpp via the TxValidator
        fast path) has already: hashed every lane to a 32-byte digest,
        DER-parsed + policy-gated each signature (der_ok), computed
        r/rpn/w big-endian scalars, and grouped lanes by key via
        `key_idx` into `keys` (bccsp Key objects, one per unique key).
        `get_sig(i)` returns lane i's DER bytes — only consulted on the
        sw paths (small batch, non-P256 key, device failure).

        Returns a RESOLVER: staging + the device dispatch happen now
        (jax dispatch is async), calling the resolver materializes the
        flags — so the caller's CPU work (policy preparation) overlaps
        device execution. `verify_prepared(...)` is the synchronous
        wrapper.

        Per-lane accept/reject is IDENTICAL to verify_batch over the
        equivalent VerifyItems (differential-tested); only the staging
        cost differs.
        """
        n = len(der_ok)
        if n == 0:
            return lambda: []
        pubs = []
        for k in keys:
            try:
                pub = k.public_key() if k is not None else None
            except Exception:
                pub = None
            pubs.append(pub if isinstance(pub, swmod.ECDSAPublicKey)
                        else None)
        if n < self._min_batch:
            out = self._verify_prepared_sw(
                range(n), digests, key_idx, keys, pubs, get_sig)
            return lambda: out

        def fallback():
            self.stats["sw_fallbacks"] += 1
            self._sync_breaker_stats()
            logger.exception("TPU prepared-batch verify failed; "
                             "falling back to sw for %d lanes", n)
            return self._verify_prepared_sw(
                range(n), digests, key_idx, keys, pubs, get_sig)

        # elastic-mesh health hook, then breaker admission: while
        # degraded every prepared batch rides the host path
        # (bit-identical verdicts); in probing state this batch IS
        # the probe — capped at ProbeBatch lanes, the rest on the
        # host path — and its resolve outcome decides re-entry. With
        # every chip benched, serve the host path outright.
        healthy = self._maybe_probe_and_rebuild()
        if healthy is not None and not healthy:
            self.stats["degraded_batches"] += 1
            out = self._verify_prepared_sw(
                range(n), digests, key_idx, keys, pubs, get_sig)
            return lambda: out
        try:
            is_probe = self._breaker.admit()
        except breaker_mod.CircuitOpen:
            self.stats["degraded_batches"] += 1
            self._sync_breaker_stats()
            out = self._verify_prepared_sw(
                range(n), digests, key_idx, keys, pubs, get_sig)
            return lambda: out

        cut = n
        if is_probe:
            pb = self._breaker.config.probe_batch
            if pb and n > max(pb, self._min_batch):
                cut = max(pb, self._min_batch)
        try:
            # staging may pay a first-dispatch compile: mark it live so
            # a probing breaker's stale-reclaim can't preempt it
            with self._dispatch_span(), self._breaker.execution():
                resolve = self._verify_prepared_device(
                    digests[:cut], r[:cut], rpn[:cut], w[:cut],
                    der_ok[:cut], key_idx[:cut], keys, pubs, get_sig)
        except Exception as e:
            self._breaker.failure(e)
            self._attribute_device_failure(e)
            out = fallback()
            return lambda: out

        def finish():
            try:
                # the guard runs the deadline watchdog and records the
                # device outcome (success closes a probing breaker)
                with self._dispatch_span():
                    out = self._breaker.guard(resolve)
            except Exception as e:
                self._attribute_device_failure(e)
                return fallback()
            self._sync_breaker_stats()
            if cut < n:
                out = out + self._verify_prepared_sw(
                    range(cut, n), digests, key_idx, keys, pubs,
                    get_sig)
            return out
        return finish

    def _verify_prepared_sw(self, lanes, digests, key_idx, keys, pubs,
                            get_sig) -> list[bool]:
        out = []
        for i in lanes:
            k = keys[key_idx[i]]
            if k is None:
                out.append(False)
                continue
            try:
                out.append(self._sw.verify(
                    k, get_sig(i), digests[i].tobytes()))
            except Exception:
                out.append(False)
        return out

    def _verify_prepared_device(self, digests, r, rpn, w, der_ok,
                                key_idx, keys, pubs, get_sig
                                ) -> list[bool]:
        from fabric_tpu.ops import limb

        n = len(der_ok)
        bucket = self._bucket(n)
        self.stats["lanes_real"] += n
        self.stats["lanes_padded"] += bucket
        # the host arrays the dispatch ships: one span, whatever n
        stage = tracing.span("tpu.stage", lanes=n, bucket=bucket)
        with stage:
            premask = np.zeros(bucket, dtype=bool)
            premask[:n] = der_ok.astype(bool)

            # per-key gating: lanes on a non-ECDSA key reject; lanes on
            # a non-P256 ECDSA key verify on the sw path without
            # degrading the batch (same contract as the item path)
            key_ok = np.array([p is not None and p.is_p256()
                               for p in pubs], dtype=bool)
            key_sw = np.array([p is not None and not p.is_p256()
                               for p in pubs], dtype=bool)
            lane_key = np.asarray(key_idx, dtype=np.int32)
            premask[:n] &= key_ok[lane_key]
            sw_lanes = np.nonzero(key_sw[lane_key])[0]

            key_map: dict[bytes, int] = {}
            # build the key table over P-256 keys only; dead lanes keep
            # slot 0 (masked out by premask)
            slot_of = np.zeros(len(keys), dtype=np.int32)
            kx = np.zeros((max(len(keys), 1), 32), dtype=np.uint8)
            ky = np.zeros((max(len(keys), 1), 32), dtype=np.uint8)
            for j, p in enumerate(pubs):
                if p is None or not p.is_p256():
                    continue
                xb = np.asarray(p.x_bytes(), dtype=np.uint8)
                yb = np.asarray(p.y_bytes(), dtype=np.uint8)
                kbytes = xb.tobytes() + yb.tobytes()
                slot_of[j] = key_map.setdefault(kbytes, len(key_map))
                kx[j] = xb
                ky[j] = yb
            lane_slot = np.zeros(bucket, dtype=np.int32)
            lane_slot[:n] = slot_of[lane_key]

            dg = np.zeros((bucket, 8), dtype=np.uint32)
            dg[:n] = np.ascontiguousarray(digests).view(">u4").reshape(
                n, 8)

            def pad8(a):
                out = np.zeros((bucket, 32), dtype=np.uint8)
                out[:n] = a
                return out

            comb = 0 < len(key_map) <= self._key_capacity()
            if comb:
                # transfer-minimal digest pipeline (the common case)
                scalars = (pad8(r), pad8(rpn), pad8(w))
            else:
                qx_b = np.zeros((bucket, 32), dtype=np.uint8)
                qy_b = np.zeros((bucket, 32), dtype=np.uint8)
                qx_b[:n] = kx[lane_key]
                qy_b[:n] = ky[lane_key]
                scalars = tuple(limb.be_bytes_to_limbs(pad8(a))
                                for a in (r, rpn, w))
            stage.set(keys=len(key_map))

        if comb:
            thunk = self._dispatch_comb_digest(
                bucket, key_map, lane_slot, *scalars, premask, dg,
                async_out=True)
        else:
            blocks = np.zeros((bucket, 1, 16), dtype=np.uint32)
            nblocks = np.zeros(bucket, dtype=np.int32)
            has_digest = np.ones(bucket, dtype=bool)
            thunk = self._dispatch_arrays(
                bucket, key_map, lane_slot, blocks, nblocks,
                *scalars, premask, dg, has_digest, qx_b, qy_b,
                async_out=True)

        def resolve() -> list[bool]:
            out = thunk()
            with tracing.span("tpu.readback", lanes=n):
                result = out[:n].tolist()
                self._sw_scatter(
                    sw_lanes.tolist(), result,
                    lambda ls: self._verify_prepared_sw(
                        ls, digests, key_idx, keys, pubs, get_sig))
            return result
        return resolve

    # -- the key-table pool --
    #
    # ONE resident device array holds the comb table of every P-256
    # key the provider serves, slot-major: a key's table is one
    # contiguous slab, row (slot * windows + window) * entries + w
    # (ops/comb.py), at one width for the life of the process (16-bit
    # windows where `_g16_enabled()`, ~252 MB a slab; 8-bit otherwise,
    # ~2 MB). A lane carries its key's slot, so ONE compiled program a
    # lane shape serves a batch of 3 keys and a batch of 25 alike, and
    # two blocks that share 24 of 25 keys share 24 slabs. A key is
    # admitted by building its slab (or reading it back from
    # WarmKeysDir) and writing it into a free slot, or the least
    # recently used one that the batch in hand does not use, IN PLACE:
    # the write donates the pool, so no second copy ever exists. That
    # donation is why `_pool_lock` is held from a batch's slot lookup
    # until its last dispatch is enqueued — a handle fetched before
    # another thread's write would be a deleted array — and why a
    # write is ordered after every execution already enqueued on it.

    def _slab_rows(self) -> int:
        from fabric_tpu.ops import comb
        return (comb.NWIN_G16 * comb.NENT_G16 if self._g16_enabled()
                else comb.NWIN * comb.NENT)

    def _slab_shape(self) -> tuple:
        from fabric_tpu.ops import limb
        return (self._slab_rows(), 3, limb.L)

    def _slab_bytes(self) -> int:
        """A slab's bytes as the DEVICE holds them — what the budget
        buys and the gauges report. The chip keeps a row's three
        20-limb coordinates in 24 words each (tiles of 8 limbs;
        `tools/chip_compile.py pool_write`): 301,989,888 B a key at
        16-bit windows, where the rows alone are 251,658,240."""
        rows, coords, limbs = self._slab_shape()
        if self._on_tpu():
            limbs = -(-limbs // 8) * 8
        return rows * coords * limbs * 4

    def _key_capacity(self) -> int:
        """Slots of the pool, sized once: MaxKeys, as far as
        TableCacheMB and half of a chip's memory hold that many slabs
        (every chip of a mesh holds the whole pool). A batch with more
        distinct keys goes to the ladder."""
        with self._pool_lock:
            if self._capacity is None:
                slab = self._slab_bytes()
                cap = min(self._max_keys,
                          self._table_cache_bytes // slab)
                # of the chip's memory, not of what is free just now:
                # prewarm's g16 thread allocates while this is read,
                # and the pool's shape is part of a program's key
                limit = [r["bytes_limit"]
                         for r in devicecost.device_memory()
                         if r["bytes_limit"]]
                if limit:
                    cap = min(cap, min(limit) // 2 // slab)
                self._capacity = max(0, int(cap))
                self.stats["key_slot_capacity"] = self._capacity
            return self._capacity

    def _replicated(self, arr):
        """`arr` on every chip of the serving mesh (as is without)."""
        if self._mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(arr, NamedSharding(self._mesh, P()))

    def _pool_array(self):
        """The pool, allocated whole at its first use (`_pool_lock`
        held): a program is compiled for its shape."""
        if self._pool is None:
            import jax.numpy as jnp
            rows = self._key_capacity() * self._slab_rows()
            where = None
            if self._mesh is not None:
                # on every chip at once: made on one chip and then
                # replicated, that chip would hold two pools a moment
                from jax.sharding import NamedSharding, PartitionSpec
                where = NamedSharding(self._mesh, PartitionSpec())
            self._pool = jnp.zeros((rows,) + self._slab_shape()[1:],
                                   dtype=jnp.int32, device=where)
            self.stats["key_table_bytes"] = \
                self._key_capacity() * self._slab_bytes()
        return self._pool

    def _drop_pool(self) -> None:
        """Forget the pool and every key in it (a mesh swap, a write
        that lost the donated array): the next batch allocates it anew
        and admits its keys again, from WarmKeysDir where they are."""
        with self._pool_lock:
            self._pool = None
            self._slot_of.clear()
            self._g16_rep = None
            self.stats["key_slots_resident"] = 0

    def _g16_table(self):
        """The 16-bit G table the pool's width calls for, or the empty
        stand-in of the 8-bit width (replicated under a mesh, once)."""
        if self._g16_rep is None:
            if self._g16_enabled():
                from fabric_tpu.ops import comb
                g16 = comb.g16_tables()
            else:
                import jax.numpy as jnp

                from fabric_tpu.ops import limb
                g16 = jnp.zeros((0, 3, limb.L), dtype=jnp.int32)
            self._g16_rep = self._replicated(g16)
        return self._g16_rep

    def _key_slots(self, key_map, key_idx):
        """Each lane's slot in the pool, every key of `key_map` resident
        when this returns: (lane slots, pool, g16). The caller holds
        `_pool_lock` until its dispatches are enqueued. One `tpu.tables`
        span a batch, one `tpu.table_build` under it a key admitted."""
        sp = tracing.span("tpu.tables", keys=len(key_map))
        with sp:
            slot_of, st = self._slot_of, self.stats
            slots = np.zeros(len(key_map), dtype=np.int32)
            missing = []
            for kb, j in key_map.items():
                slot = slot_of.get(kb)
                if slot is None:
                    missing.append(kb)
                else:
                    slot_of.move_to_end(kb)
                    slots[j] = slot
            evicted = 0
            for kb in missing:
                slots[key_map[kb]], ev = self._admit_key(kb, key_map)
                evicted += ev
            st["key_slot_lookups"] += len(key_map)
            st["key_slot_hits"] += len(key_map) - len(missing)
            sp.set(hits=len(key_map) - len(missing), built=len(missing),
                   evicted=evicted, q16=self._g16_enabled())
            return slots[key_idx], self._pool_array(), self._g16_table()

    def _admit_key(self, kb: bytes, keep=(), cold: bool = False,
                   slab=None):
        """Write `kb`'s slab into a free slot or, where none is left,
        the least recently used one whose key is not in `keep`
        (`_pool_lock` held). `cold` (a restore of persisted bytes no
        batch has asked for yet) takes free slots only and queues first
        for eviction. Returns (slot, keys evicted); (None, 0) where a
        cold key finds no room."""
        sp = tracing.span("tpu.table_build")
        with sp:
            pool = self._pool_array()
            source = "disk"
            if slab is None:
                slab = self._load_slab(kb)
            if slab is None:
                source = "build"
                slab = self._build_slab(kb)
            evicted = 0
            used = set(self._slot_of.values())
            slot = next((i for i in range(self._key_capacity())
                         if i not in used), None)
            if slot is None:
                if cold:
                    return None, 0
                victim = next(k for k in self._slot_of if k not in keep)
                slot = self._slot_of.pop(victim)
                self._drop_slab_file(victim)
                self.stats["key_slot_evictions"] += 1
                evicted = 1
            try:
                import jax
                self._pool = self._pool_write_fn()(
                    pool, self._replicated(slab),
                    np.int32(slot * self._slab_rows()))
                # the next admission waits for this write: a program's
                # outputs are allocated when it is enqueued, so the
                # builds of a wide channel's first block, enqueued one
                # behind the other, would hold a slab each beside the
                # pool
                jax.block_until_ready(self._pool)
            except BaseException:
                # the write donates the pool: where it failed after the
                # runtime took the array, nothing resident is readable
                # (before that, the slot simply stays free)
                if pool.is_deleted():
                    self._drop_pool()
                raise
            self._slot_of[kb] = slot
            if cold:
                self._slot_of.move_to_end(kb, last=False)
            if source == "build":
                self.stats["key_slot_builds"] += 1
                self._persist_slab(kb, slab)
            else:
                self.stats["key_slot_disk_loads"] += 1
            self.stats["key_slots_resident"] = len(self._slot_of)
            sp.set(slot=slot, bytes=self._slab_bytes(), source=source)
            return slot, evicted

    def _build_slab(self, kb: bytes):
        """One key's comb table at the pool's width, on the device."""
        import jax.numpy as jnp

        from fabric_tpu.ops import limb
        qk = np.frombuffer(kb, dtype=np.uint8).reshape(1, 64)
        slab = self._qtab_fn()(
            jnp.asarray(limb.be_bytes_to_limbs(qk[:, :32])),
            jnp.asarray(limb.be_bytes_to_limbs(qk[:, 32:])))
        if self._g16_enabled():
            slab = self._q16_fn()(slab)
        return slab

    # -- slab persistence (BCCSP.TPU.WarmKeysDir): the directory mirrors
    #    the pool, one file a resident key (`slab<width>_<key hex>.npy`,
    #    tmp + rename, a sha256 sidecar beside it), written in the
    #    background when a slab is built and removed when its key is
    #    evicted. A restarted node reads them back into slots (prewarm's
    #    restore thread, and any miss that gets there first) instead of
    #    building: the compile cache carries code, not data. A file
    #    that fails its sidecar or its shape is rebuilt, never combed
    #    against.

    def _slab_prefix(self) -> str:
        return f"slab{16 if self._g16_enabled() else 8}_"

    def _slab_path(self, kb: bytes) -> str:
        return os.path.join(self._warm_keys_dir,
                            f"{self._slab_prefix()}{kb.hex()}.npy")

    def _drop_slab_file(self, kb: bytes) -> None:
        if not self._warm_keys_dir:
            return
        from fabric_tpu.ops import comb
        try:
            with self._warm_lock:
                path = self._slab_path(kb)
                if os.path.exists(path):
                    os.remove(path)
                comb.drop_digest_sidecar(path)
        except Exception:
            logger.exception("could not remove an evicted key's "
                             "persisted table")

    def _persist_slab(self, kb: bytes, slab) -> None:
        """Write a built slab's bytes in a background thread: the
        serving path never waits for the disk, and for a copy to the
        host only where two slabs already wait for theirs."""
        if not self._warm_keys_dir:
            return
        # no more than two slabs wait for their copy to the host: the
        # builds of a wide channel's first block come one behind the
        # other, and each would leave its slab on the device beside the
        # pool until a writer got to it. After the copy the device
        # buffer is free and the bytes (252 MB a key) wait for the disk
        # in host memory.
        self._persist_slots.acquire()
        box = [slab]

        def work():
            try:
                try:
                    faults.check("tpu.table_persist")
                    arr = np.asarray(box.pop())
                finally:
                    self._persist_slots.release()
                from fabric_tpu.ops import comb
                os.makedirs(self._warm_keys_dir, exist_ok=True)
                path = self._slab_path(kb)
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    np.save(f, arr)
                    f.flush()
                    os.fsync(f.fileno())
                digest = comb.file_sha256(tmp)
                # publish under the warm lock: an eviction either sees
                # the file (and deletes it) or has already dropped the
                # key (and we delete our own write) — a reclaimed file
                # can never be resurrected
                with self._warm_lock:
                    os.replace(tmp, path)
                    # ftpu-check: allow-lockset(one dict lookup; taking
                    # _pool_lock under _warm_lock would invert the
                    # eviction's order)
                    if kb in self._slot_of:
                        comb.write_digest_sidecar(path, digest)
                    else:
                        os.remove(path)
            except Exception:
                # surfaced as bccsp_warm_table_persist_failures: a node
                # silently losing its warm bytes rebuilds every slab on
                # every restart, which operators must SEE
                self.stats["warm_table_persist_failures"] += 1
                logger.exception("could not persist a key's table bytes")

        t = threading.Thread(target=work, daemon=True,
                             name="key-table-persist")
        self._persist_threads.append(t)
        t.start()

    def flush_warm_tables(self, timeout: float = 120.0) -> None:
        """Join outstanding table-persist writers and the background
        restore (shutdown/bench). `timeout` bounds the TOTAL wait, not
        each join — N stuck writers must not turn shutdown into
        N x timeout."""
        import time as _time
        deadline = _time.monotonic() + timeout
        if self._restore_thread is not None:
            self._restore_thread.join(
                max(0.0, deadline - _time.monotonic()))
        for t in self._persist_threads:
            t.join(max(0.0, deadline - _time.monotonic()))
        stuck = [t for t in self._persist_threads if t.is_alive()]
        if stuck:
            logger.warning(
                "%d warm-table persist writer(s) still running after "
                "the %.0fs flush deadline; leaving them detached",
                len(stuck), timeout)
        self._persist_threads = stuck

    def _load_slab(self, kb: bytes):
        """`kb`'s persisted slab as a host array, or None (no
        WarmKeysDir, no file, or one that fails its checks)."""
        if not self._warm_keys_dir:
            return None
        from fabric_tpu.ops import comb
        path = self._slab_path(kb)
        try:
            if comb.verify_digest_sidecar(path) is False:
                logger.warning(
                    "persisted key table %s fails its sha256 sidecar "
                    "(disk corruption?); rebuilding", path)
                return None
            arr = np.load(path)
        except FileNotFoundError:
            return None
        except Exception:
            logger.exception("unreadable persisted key table; "
                             "rebuilding")
            return None
        if arr.dtype != np.int32 or arr.shape != self._slab_shape():
            logger.warning(
                "persisted key table %s is %s %s, want int32 %s; "
                "rebuilding", path, arr.dtype, arr.shape,
                self._slab_shape())
            return None
        return arr

    def _restore_slabs(self) -> int:
        """Read WarmKeysDir's slabs back into free slots, newest first
        (prewarm's background thread). The disk read runs off the pool
        lock; a batch that misses a key meanwhile admits it itself, as
        any miss, and the restore then finds it resident. Returns the
        keys restored."""
        if not self._warm_keys_dir:
            return 0
        prefix = self._slab_prefix()
        try:
            names = [n for n in os.listdir(self._warm_keys_dir)
                     if n.startswith(prefix) and n.endswith(".npy")]
        except OSError:
            return 0
        names.sort(key=lambda n: os.path.getmtime(
            os.path.join(self._warm_keys_dir, n)), reverse=True)
        restored = 0
        for name in names:
            try:
                kb = bytes.fromhex(name[len(prefix):-4])
                if len(kb) != 64 or kb in self._slot_of:
                    continue
                if self._key_capacity() <= len(self._slot_of):
                    break       # older keys stay on disk for a miss
                slab = self._load_slab(kb)
                if slab is None:
                    continue
                with self._pool_lock:
                    if kb not in self._slot_of and self._admit_key(
                            kb, cold=True, slab=slab)[0] is not None:
                        restored += 1
            except Exception:
                self.stats["warm_restore_failures"] += 1
                logger.exception("restoring one persisted key table "
                                 "failed")
        if restored:
            logger.info("restored %d key table(s) from persisted bytes",
                        restored)
        return restored

    @hot_path
    @tracing.traced("tpu.shard_put")
    def _shard_put(self, arrs, timings=None):
        """Round-robin span feeder for the sharded dispatch: deal each
        span's lanes contiguously across the mesh — device d takes the
        slice the batch NamedSharding assigns it — with one EXPLICIT
        per-device transfer stream per chip, then assemble the shards
        zero-copy into the global sharded array the shard_map program
        consumes. Versus one batched device_put this costs a few
        host-side slice views and buys per-device attribution: a chip
        whose H2D stream is slow shows up in `timings` (len-mesh list
        accumulating per-device transfer-enqueue seconds, surfaced as
        `bccsp_shard_transfer_s{device=…}`) instead of smearing into
        one opaque number."""
        import time as _time

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        s = NamedSharding(self._mesh, P("batch"))
        mesh_devs = list(self._mesh.devices.flat)
        out = []
        for a in arrs:
            imap = s.addressable_devices_indices_map(a.shape)
            shards = []
            for d, dev in enumerate(mesh_devs):
                gi = self._device_index(dev)
                t0 = _time.perf_counter()
                try:
                    # per-device fault seam (arg = FULL-mesh index, so
                    # chaos targets chip k whatever the serving mesh):
                    # device_lost errors here, device_straggler stalls
                    # this chip's transfer stream — feeding the
                    # quarantine accounting either way
                    faults.check("tpu.device_lost", arg=gi)
                    faults.check("tpu.device_straggler", arg=gi)
                    shards.append(jax.device_put(a[imap[dev]], dev))
                except Exception as e:
                    # a failed per-chip transfer IS device-attributed:
                    # quarantine THIS chip (the provider breaker
                    # ignores DeviceLostError — one bad chip must not
                    # bench the whole accelerator path)
                    raise DeviceLostError(gi, e) from e
                finally:
                    if timings is not None and d < len(timings):
                        timings[d] += _time.perf_counter() - t0
            out.append(jax.make_array_from_single_device_arrays(
                a.shape, s, shards))
        return tuple(out)

    def _record_shard_stats(self, last_out, tdev, span,
                            t_disp0) -> None:
        """Refresh the per-device shard gauges after a sharded batch:
        transfer-enqueue seconds per chip (from `_shard_put`), lanes
        per chip, and the per-device ready lag of the FINAL span's
        accept bitmap. Readiness is sampled by blocking shards in a
        per-batch ROTATING order, so device d's reading is max(its
        own, earlier-sampled devices') — an upper bound that still
        localizes a straggler chip as a step at its sampling
        position. The rotation matters: the first-sampled chip
        inflates every later reading equally, so a compute-slow chip
        PERMANENTLY sampled first would never show a jump (or skew)
        at all; rotating guarantees it has a measured predecessor on
        all but 1-in-N batches. Runs at the end-of-batch sync point,
        never inside an overlapped span."""
        import time as _time
        ndev = len(tdev)
        # lanes from the final span's REAL extent, not the nominal
        # chunk: a non-dividing bucket leaves a short tail chunk and
        # the gauge must report what each device actually processed
        shape = getattr(last_out, "shape", None)
        if shape:
            span = int(shape[0])
        mesh_devs = list(self._mesh.devices.flat)
        npos = min(ndev, len(mesh_devs))
        rot = self._ready_rot % npos if npos else 0
        self._ready_rot += 1
        order = list(range(rot, npos)) + list(range(0, rot))
        ready: list = []                 # mesh-position indexed
        sample_seq: list = []            # (position, reading) in order
        shards = getattr(last_out, "addressable_shards", None)
        if shards is not None and t_disp0 is not None:
            by_dev = {sh.device: sh for sh in shards}
            ready = [0.0] * npos
            for pos in order:
                dev = mesh_devs[pos]
                sh = by_dev.get(dev)
                if sh is not None:
                    try:
                        sh.data.block_until_ready()
                    except Exception:
                        logger.warning(
                            "shard ready probe failed on %s", dev,
                            exc_info=True)
                r = round(_time.perf_counter() - t_disp0, 6)
                ready[pos] = r
                sample_seq.append((pos, r))
        self.shard_stats = {
            "transfer_s": [round(t, 6) for t in tdev],
            "ready_s": ready,
            "lanes": [span // ndev] * ndev,
        }
        # per-chip tail distributions (round 14): the snapshot gauges
        # above show the LAST batch; these feed trace_stage_seconds so
        # a chip whose p99 transfer/ready drifts shows up long before
        # the straggler quarantine trips. Stage label carries the
        # FULL-mesh index — stable across rebuilds, like the gauges.
        for pos in range(npos):
            gi = self._device_index(mesh_devs[pos])
            tracing.observe_stage(f"device.transfer.d{gi}", tdev[pos])
            if ready:
                tracing.observe_stage(f"device.ready.d{gi}",
                                      ready[pos])
                # round-16 busy accounting: the same per-chip ready
                # reading feeds bccsp_device_busy_ratio (device-time
                # over wall-time, windowed by the stats poller)
                self._devicecost.busy.note(gi, ready[pos])
        self.stats["shard_devices"] = ndev
        self.stats["shard_skew_s"] = (
            round(max(ready) - min(ready), 6) if ready else 0.0)
        if self._devhealth is not None:
            # straggler accounting IN SAMPLING ORDER: per-chip
            # transfer time and the ready-lag jumps localize a chip
            # pacing the whole mesh; enough consecutive strikes
            # quarantine it (the NEXT batch's admission hook rebuilds
            # the mesh over the survivors)
            seq = sample_seq or [(pos, 0.0) for pos in order]
            full_idx = [self._device_index(mesh_devs[pos])
                        for pos, _ in seq]
            self._devhealth.observe_shard(
                full_idx,
                [tdev[pos] for pos, _ in seq],
                [r for _, r in seq] if sample_seq else [])
            self.stats.update(self._devhealth.totals())

    def _mesh_chunk(self, bucket: int) -> int:
        """Lanes of one dispatch of a `bucket`-lane batch: the bucket
        itself up to the pipeline span, the span above it (`_bucket`
        pads to whole spans there, so every chunked tier runs ONE
        lane shape whatever the batch size), Chunk at most. Under a
        mesh, slices stay divisible by the mesh size for shard_map."""
        chunk = min(bucket, self._chunk)
        if self._mesh is not None:
            m = self._mesh.size
            chunk = max(m, (chunk // m) * m)
        span = self._pipeline_span()
        if span is not None and chunk > span and bucket % span == 0:
            chunk = span
        return chunk

    def _book_comb(self, spans: int) -> None:
        """Book one batch of a comb program that ran `spans`
        executions of it, as counted by the loop that enqueued them.
        Every path books so, whatever cut the batch:
        `comb_batches` - `pipeline_batches` + `pipeline_chunks` is the
        executions (on each chip of a mesh), which the benchmark holds
        to the device trace."""
        self.stats["comb_batches"] += 1
        if spans > 1:
            self.stats["pipeline_batches"] += 1
            self.stats["pipeline_chunks"] += spans

    @hot_path
    def _dispatch_comb_digest(self, bucket, key_map, key_idx, r8, rpn8,
                              w8, premask, digests, async_out=False):
        """Digest-lane comb dispatch: compact u8 scalar operands, limb
        conversion ON DEVICE, no SHA stage (_comb_pipeline_digest) —
        the transfer-minimal shape for the host-hash default and the
        prepared-block fast path."""
        # above the span this is the overlapped item path's span
        # shape: one compiled program serves both paths
        chunk = self._mesh_chunk(bucket)
        sp = tracing.span("tpu.comb_digest")
        with sp:
            lockcheck.note_blocking("tpu.dispatch")
            faults.check("tpu.dispatch")
            # the pool lock from the slot lookup to the last enqueue: a
            # concurrent batch's table write donates the array in hand
            with self._pool_lock:
                key_idx, table, g16 = self._key_slots(key_map, key_idx)
                fn = self._comb_pipeline_digest()
                thunk, spans = self._dispatch_chunks(
                    bucket, chunk,
                    (key_idx, r8, rpn8, w8, premask, digests),
                    lambda c: fn(c[0], table, g16, *c[1:]))
            self._book_comb(spans)
            sp.set(chunks=spans)
            return thunk if async_out else thunk()

    @hot_path
    def _dispatch_chunks(self, bucket, chunk, operands, run):
        """The transfer-ahead double buffer of the prepared-block
        dispatches: chunk k+1's async device_put is enqueued BEFORE
        chunk k's dispatch, so the H2D copy rides under device
        execution instead of serializing with it (host prep already
        happened in native/blockprep.cpp). `run(staged)` enqueues the
        program on one chunk's staged operands. One `tpu.h2d` and one
        `tpu.enqueue` span a chunk; the thunk returned holds `tpu.wait`
        (the host blocked on the device, nothing else) and
        `tpu.readback`; it is returned with the executions enqueued.
        The spans' own clock readings feed the prepared_* gauges."""
        import jax

        ndev = self._mesh.size if self._mesh is not None else 1
        tdev = [0.0] * ndev
        transfer_s = dispatch_s = 0.0
        t_disp0 = None

        def stage(lo):
            nonlocal transfer_s
            arrs = tuple(a[lo:lo + chunk] for a in operands)
            nbytes = sum(a.nbytes for a in arrs)
            self.stats["h2d_bytes"] += nbytes
            h2d = tracing.timed("tpu.h2d", bytes=nbytes,
                                chunk=lo // chunk)
            with h2d:
                if self._mesh is not None:
                    staged = self._shard_put(arrs, tdev)
                else:
                    staged = tuple(jax.device_put(a) for a in arrs)
            transfer_s += h2d.seconds
            return staged

        outs = []
        nxt = stage(0)
        for lo in range(0, bucket, chunk):
            cur, nxt = nxt, None
            if lo + chunk < bucket:
                nxt = stage(lo + chunk)
            enqueue = tracing.timed("tpu.enqueue", chunk=lo // chunk)
            with enqueue:
                outs.append(run(cur))
            if t_disp0 is None:
                t_disp0 = enqueue.t0
            dispatch_s += enqueue.seconds
        # prepared_* (NOT pipeline_*): these gauges must not clobber
        # the overlapped item path's coherent host/transfer/device/
        # overlap snapshot with a different batch's numbers
        self.stats["prepared_transfer_s"] = round(transfer_s, 6)
        if self._mesh is not None:
            self.stats["shard_dispatches"] += len(outs)

        def thunk():
            wait = tracing.timed("tpu.wait", chunks=len(outs))
            with wait:
                if self._mesh is not None:
                    self._record_shard_stats(outs[-1], tdev, chunk,
                                             t_disp0)
                # ftpu-lint: allow-host-sync(the thunk IS the
                # deliberate materialization point, invoked after
                # dispatch returns; this is its wait)
                jax.block_until_ready(outs)
            readback = tracing.timed("tpu.readback", lanes=bucket)
            with readback:
                # ftpu-lint: allow-host-sync(the thunk IS the
                # deliberate materialization point, invoked after
                # dispatch returns)
                out = np.concatenate([np.asarray(o) for o in outs])
            self.stats["prepared_device_s"] = round(
                dispatch_s + readback.t1 - wait.t0, 6)
            return out
        return thunk, len(outs)

    @hot_path
    @tracing.traced("tpu.comb")
    def _dispatch_comb(self, bucket, key_map, key_idx, blocks, nblocks,
                       r_l, rpn_l, w_l, premask, digests, has_digest,
                       async_out=False):
        """Comb-method path: every key's table resident in the pool,
        then the batch is dispatched in chunks so host staging of chunk
        k+1 overlaps device execution of chunk k (jax dispatch is
        async)."""
        import jax.numpy as jnp

        chunk = self._mesh_chunk(bucket)
        outs = []
        stage = ((lambda a: a) if self._mesh is not None
                 else jnp.asarray)   # uncommitted under a mesh: the
        #                              shard_map jit deals lanes out
        with self._pool_lock:       # as `_dispatch_comb_digest` holds it
            key_idx, table, g16 = self._key_slots(key_map, key_idx)
            fn = self._comb_pipeline()
            for lo in range(0, bucket, chunk):
                hi = lo + chunk
                outs.append(fn(
                    stage(blocks[lo:hi]), stage(nblocks[lo:hi]),
                    stage(key_idx[lo:hi]), table, g16,
                    stage(r_l[lo:hi]), stage(rpn_l[lo:hi]),
                    stage(w_l[lo:hi]), stage(premask[lo:hi]),
                    stage(digests[lo:hi]),
                    stage(has_digest[lo:hi])))
        self._book_comb(len(outs))
        thunk = lambda: np.concatenate(  # noqa: E731
            # ftpu-lint: allow-host-sync(deliberate materialization)
            [np.asarray(o) for o in outs])
        return thunk if async_out else thunk()

    def _qtab_fn(self):
        """The 8-bit table builder at one key: a slab of the 8-bit
        pool, the input of `_q16_fn` for the 16-bit one."""
        with self._jit_lock:
            if "qtab" not in self._qtab_fns:
                from fabric_tpu.ops import comb
                self._qtab_fns["qtab"] = self._jit(
                    "qtab", comb.build_q_tables)
            return self._qtab_fns["qtab"]

    def _q16_fn(self):
        with self._jit_lock:
            if "qtab16" not in self._qtab_fns:
                from fabric_tpu.ops import comb
                self._qtab_fns["qtab16"] = self._jit(
                    "qtab16", comb.build_q16_tables)
            return self._qtab_fns["qtab16"]

    def _pool_write_fn(self):
        """slab -> its rows of the pool, in place (the pool is donated:
        a copy of a 6 GB array would double the peak)."""
        with self._jit_lock:
            if "pool_write" not in self._comb_fns:
                def pool_write(pool, slab, row0):
                    from jax import lax
                    return lax.dynamic_update_slice_in_dim(
                        pool, slab, row0, axis=0)

                self._comb_fns["pool_write"] = self._jit(
                    "pool_write", pool_write, donate_argnums=0)
            return self._comb_fns["pool_write"]

    def _comb_pipeline(self):
        """SHA-256 + comb in one program (HashOnHost: false), against
        the pool at its width."""
        with self._jit_lock:
            if "comb" not in self._comb_fns:
                from fabric_tpu.ops import comb, sha256

                q16 = self._g16_enabled()

                def fused(blocks, nblocks, key_idx, q_flat, g16, r, rpn, w,
                          premask, digests, has_digest):
                    import jax.numpy as jnp
                    hashed = sha256.sha256_blocks(blocks, nblocks)
                    words = jnp.where(has_digest[:, None], digests,
                                      hashed)
                    return comb.comb_verify_with_tables(
                        words, key_idx, q_flat, r, rpn, w, premask,
                        g16=g16 if q16 else None, q16=q16)

                if self._mesh is not None:
                    # shard_map, not GSPMD: as a per-shard program each
                    # chip combs its own batch slice against replicated
                    # tables — no collectives in the main path at all
                    from jax.sharding import PartitionSpec as P
                    s = P("batch")
                    rep = P()
                    fused = jaxenv.shard_map(
                        fused, mesh=self._mesh,
                        in_specs=(s, s, s, rep, rep, s, s, s, s, s, s),
                        out_specs=s)
                self._comb_fns["comb"] = self._jit("comb", fused,
                                                   {"q16": q16})
            return self._comb_fns["comb"]

    def _comb_pipeline_digest(self):
        """Digest-lane-only comb pipeline: no SHA stage, no block
        tensors, and the scalar operands arrive as 32-byte big-endian
        u8 rows converted to limbs ON DEVICE — the transfer-minimal
        shape the host-hash default and the prepared-block fast path
        dispatch (32+96 B/lane instead of ~346 B/lane; the difference
        is H2D bytes per span). The overlapped item path and the
        prepared-block path dispatch this SAME program at the same
        span shape, and a lane names its key by its slot in the pool:
        one compile a lane shape serves every batch, whatever its
        keys."""
        with self._jit_lock:
            if "digest" not in self._comb_fns:
                from fabric_tpu.ops import comb, limb

                q16 = self._g16_enabled()

                def fused(key_idx, q_flat, g16, r8, rpn8, w8, premask,
                          digests):
                    r = limb.be_bytes_to_limbs_jnp(r8)
                    rpn = limb.be_bytes_to_limbs_jnp(rpn8)
                    w = limb.be_bytes_to_limbs_jnp(w8)
                    return comb.comb_verify_with_tables(
                        digests, key_idx, q_flat, r, rpn, w, premask,
                        g16=g16 if q16 else None, q16=q16)

                if self._mesh is not None:
                    from jax.sharding import PartitionSpec as P
                    s = P("batch")
                    rep = P()
                    fused = jaxenv.shard_map(
                        fused, mesh=self._mesh,
                        in_specs=(s, rep, rep, s, s, s, s, s),
                        out_specs=s)
                self._comb_fns["digest"] = self._jit(
                    "comb_digest", fused, {"q16": q16})
            return self._comb_fns["digest"]

    def _pipeline(self):
        if self._fn is None:
            from fabric_tpu.ops import p256, sha256

            def fused(blocks, nblocks, qx, qy, r, rpn, w, premask,
                      digests, has_digest):
                import jax.numpy as jnp
                hashed = sha256.sha256_blocks(blocks, nblocks)
                words = jnp.where(has_digest[:, None], digests, hashed)
                return p256.verify_core(words, qx, qy, r, rpn, w, premask)

            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                s = NamedSharding(self._mesh, P("batch"))
                self._fn = self._jit("ladder", fused,
                                     in_shardings=(s,) * 10,
                                     out_shardings=s)
            else:
                self._fn = self._jit("ladder", fused)
        return self._fn

    def prewarm(self, buckets=None, msg_nbs=None,
                wait_restore: bool = False,
                bounded: bool = False) -> None:
        """Make ready what this provider will dispatch (and build the
        16-bit G table) BEFORE the node joins channels, so a cold peer
        does not stall its first blocks on device compilation. Each
        program goes through the AOT seam (`InstrumentedJit.aot`): a
        restarted peer LOADS it from the store of compiled executables
        beside the persistent compile cache and traces nothing; the
        first process after a change of code, JAX or device lowers and
        compiles (or loads from the persistent cache) and writes the
        store. Either way the executable is registered for its shape,
        and the first block's dispatch calls it directly.
        Persisted key tables are read back into the pool by a
        BACKGROUND thread that outlives this call (wait_restore=True
        joins it — tests): a batch that needs a key before its restore
        lands admits it itself, as any miss, so the node validates
        immediately like a reference peer. Safe to call on any
        backend; failures only log. `stats["prewarm_done"]` (gauge
        bccsp_prewarm_done) turns 1 when the compiles are in.

        The inventory does not depend on how many keys a channel has:
        the two table builders at one key, the pool write, and the
        digest pipeline against the pool at each lane shape a batch of
        `buckets` signatures dispatches (default: the smallest device
        bucket, which on a TPU is the one span shape every batch uses
        — see `_floor`); the SHA+comb programs only with HashOnHost
        off (never with bounded=True)."""
        import jax  # noqa: F401  (jax.ShapeDtypeStruct below)
        import numpy as _np

        from fabric_tpu.ops import comb
        if msg_nbs is None:
            # host-hash mode only ever ships nb=1 digest lanes; device-
            # hash mode also needs the typical proposal-payload shape
            msg_nbs = (1,) if self._hash_on_host else (1, 8)
        i32, u8 = _np.int32, _np.uint8
        lane = rep = None
        if self._mesh is not None:
            # a compiled executable takes only what it was compiled
            # for: say where `_shard_put` puts the lanes of a sharded
            # dispatch, and that the pool and g16 sit on every chip
            from jax.sharding import NamedSharding, PartitionSpec as P
            lane = NamedSharding(self._mesh, P("batch"))
            rep = NamedSharding(self._mesh, P())

        def sd(shape, dtype, sharding=None):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        try:
            q16 = self._g16_enabled()
            if q16 or self._warm_keys_dir:
                # the g16 G-table build AND the persisted key tables'
                # restore run in ONE background thread (g16 first —
                # any 16-bit dispatch needs it): H2D of GB-scale
                # tables must not hold up the compiles below
                def restore():
                    if q16:
                        comb.g16_tables()
                    self._restore_slabs()

                self._restore_thread = threading.Thread(
                    target=restore, daemon=True, name="qtab-restore")
                self._restore_thread.start()
            pc = self._pipeline_span()
            if buckets is None:
                buckets = (self._bucket(1),)
            # the lane shape a batch of `bucket` signatures dispatches
            lanes = sorted({pc if pc is not None and b > pc
                            else min(self._bucket(b), self._chunk)
                            for b in buckets})
            slots = self._key_capacity()
            slab_sd = sd(self._slab_shape(), i32, rep)
            pool_sd = sd((slots * self._slab_rows(),)
                         + self._slab_shape()[1:], i32, rep)
            g16_sd = sd((comb.NWIN_G16 * comb.NENT_G16 if q16 else 0,
                         3, 20), i32, rep)
            if lanes and slots:
                # the table builders run on one device, mesh or not
                self._qtab_fn().aot(sd((1, 20), i32), sd((1, 20), i32))
                if q16:
                    self._q16_fn().aot(
                        sd((comb.NWIN * comb.NENT, 3, 20), i32))
                self._pool_write_fn().aot(pool_sd, slab_sd, sd((), i32))
                logger.info("prewarmed the table builders and the pool "
                            "write: %d slots, q16=%s", slots, q16)
            for n in lanes if slots else ():
                self._comb_pipeline_digest().aot(
                    sd((n,), i32, lane), pool_sd, g16_sd,
                    sd((n, 32), u8, lane), sd((n, 32), u8, lane),
                    sd((n, 32), u8, lane), sd((n,), bool, lane),
                    sd((n, 8), _np.uint32, lane))
                logger.info("prewarmed digest comb pipeline lanes=%d "
                            "q16=%s", n, q16)
                if self._hash_on_host or bounded:
                    continue      # SHA+comb pipeline not used
                fn = self._comb_pipeline()
                for nb in msg_nbs:
                    fn.aot(
                        sd((n, nb, 16), _np.uint32, lane),
                        sd((n,), i32, lane), sd((n,), i32, lane),
                        pool_sd, g16_sd, sd((n, 20), i32, lane),
                        sd((n, 20), i32, lane),
                        sd((n, 20), i32, lane), sd((n,), bool, lane),
                        sd((n, 8), _np.uint32, lane),
                        sd((n,), bool, lane))
                    logger.info("prewarmed comb pipeline lanes=%d "
                                "nb=%d q16=%s", n, nb, q16)
            if wait_restore and self._restore_thread is not None:
                self._restore_thread.join()
        except Exception:
            logger.exception("prewarm failed (continuing; first block "
                             "will pay the compile)")
        finally:
            self.stats["prewarm_done"] = 1
            st = self.stats
            logger.info(
                "prewarm done: executable_store_hits=%d "
                "executable_store_misses=%d executable_store_errors=%d "
                "compile_cold_total=%d; %s",
                st["executable_store_hits"],
                st["executable_store_misses"],
                st["executable_store_errors"], st["compile_cold_total"],
                "; ".join("%s source=%s lower_s=%s load_s=%s" % (
                    e["kind"], e["source"], e["lower_s"], e["load_s"])
                    for e in self._devicecost.events if e["aot"]))

    # -- pairings (idemix stretch: BASELINE config 4) --

    def pairing_check_batch(self, products) -> list[bool]:
        """prod_j e(P_j, Q_j) == 1 per lane, on device.

        products: [[(P_int_affine, Q_twist_int_affine), ...] per lane]
        with a uniform term count. Small batches and device failures
        fall back to the exact host pairing (fabric_tpu/ops/bn254_ref)
        — same degrade-don't-halt contract as verify_batch. Reference
        consumer: `msp/idemix.go` credential verification (vendored
        IBM/idemix pairing checks).
        """
        from fabric_tpu.ops import bn254_ref as bref
        if len(products) < max(2, self._min_batch // 4):
            return self._pairing_host(products)
        try:
            from fabric_tpu.ops import bn254 as bdev
            nterms = len(products[0])
            n = len(products)
            bucket = 1
            while bucket < n:
                bucket *= 2
            # pad with a trivially-true product: e(inf...) is not
            # representable affine, so pad with a VALID identity
            # product e(P, Q) * e(P, -Q) using lane 0's first term
            p0, q0 = products[0][0]
            pad_lane = [(p0, q0), (p0, bref.g2_neg_tw(q0))]
            if nterms != 2:
                pad_lane = [(p0, q0)] * nterms  # caller beware; rare
            padded = list(products) + [pad_lane] * (bucket - n)
            if nterms != 2 and bucket != n:
                return self._pairing_host(products)
            staged = bdev.stage_pairing_products(padded)
            key = ("pairing", nterms, bucket)
            # _jit_lock: same discipline as _qtab_fn/_q16_fn — the
            # jitted-fn cache is shared with the prewarm restore thread
            with self._jit_lock:
                if key not in self._qtab_fns:
                    self._qtab_fns[key] = self._jit(
                        "pairing",
                        lambda xPs, yPs, Qs, Q1s, nQ2s:
                        bdev.pairing_product_is_one(xPs, yPs, Qs, Q1s,
                                                    nQ2s))
                fn = self._qtab_fns[key]
            out = np.asarray(fn(*staged))
            # round-21: pairing_* gauges span both device pairing
            # engines (BN254 idemix products here, BLS aggregates in
            # _dispatch_bls_pairing) — pairs counts Miller pairs served
            self.stats["pairing_batches"] += 1
            self.stats["pairing_pairs"] += n * nterms
            return out[:n].tolist()
        except Exception:
            self.stats["sw_fallbacks"] += 1
            self.stats["pairing_fallbacks"] += 1
            logger.exception("device pairing check failed; host fallback"
                             " for %d products", len(products))
            return self._pairing_host(products)

    def _pairing_host(self, products) -> list[bool]:
        # pkcs11-style containment: the exact host pairing lives on the
        # embedded sw provider; one implementation, not three
        return self._sw.pairing_check_batch(products)

    def g2_msm_batch(self, lanes) -> list:
        """Batched G2 multi-scalar multiplication on device: per lane,
        sum_t k_t * Q_t over the BN254 twist (affine int points / None;
        returns affine int points / None). One lax.scan of complete
        RCB double/add steps over the scalar bit columns
        (ops/bn254.py g2_msm_scan). Consumer: IdemixMSP PS
        presentation verification — every credential's Schnorr K~
        recombination and T~ subgroup check in one dispatch, where the
        reference verifies each credential's proof serially on CPU
        (vendored IBM/idemix). Small batches and device failures fall
        back to the host Strauss MSM (bn254_ref.g2_msm)."""
        from fabric_tpu.ops import bn254_ref as bref
        if len(lanes) < max(2, self._min_batch // 8):
            return [bref.g2_msm(lane) for lane in lanes]
        try:
            from fabric_tpu.ops import bn254 as bdev
            nterms = len(lanes[0])
            n = len(lanes)
            bucket = 1
            while bucket < n:
                bucket *= 2
            pad = [[(0, None)] * nterms] * (bucket - n)
            bits, q_flat = bdev.stage_g2_msm(list(lanes) + pad)
            key = ("g2msm", nterms, bucket)
            # _jit_lock: same discipline as _qtab_fn/_q16_fn — the
            # jitted-fn cache is shared with the prewarm restore thread
            with self._jit_lock:
                if key not in self._qtab_fns:
                    self._qtab_fns[key] = self._jit("g2msm",
                                                    bdev.g2_msm_scan)
                fn = self._qtab_fns[key]
            import jax.numpy as jnp
            out = fn(
                jnp.asarray(bits), *[jnp.asarray(a) for a in q_flat])
            return bdev.read_g2_msm(out)[:n]
        except Exception:    # noqa: BLE001
            self.stats["sw_fallbacks"] += 1
            logger.exception("device g2 msm failed; host fallback for "
                             "%d lanes", len(lanes))
            return [bref.g2_msm(lane) for lane in lanes]

    def bls_verify_batch(self, pk_tw, msgs, sig_points) -> list[bool]:
        """Issuer-credential BLS verify: e(sig, G2)·e(H(m), -pk) == 1
        per lane. `sig_points` entries may be None (malformed) — those
        lanes are False without touching the device."""
        from fabric_tpu.ops import bn254 as bdev
        idx = [i for i, s in enumerate(sig_points) if s is not None]
        out = [False] * len(msgs)
        if idx:
            prods = bdev.bls_products(
                pk_tw, [msgs[i] for i in idx],
                [sig_points[i] for i in idx])
            res = self.pairing_check_batch(prods)
            for i, v in zip(idx, res):
                out[i] = v
        return out

    def _floor(self) -> int:
        """BCCSP.TPU.BucketFloor; unset (0) resolves on a TPU backend
        to the pipeline span (2,048 lanes a device unless
        PipelineChunk says otherwise): every device batch up to the
        span pads to ONE lane shape, and larger ones go span by span
        (`_bucket`), so the provider costs one pipeline compile
        whatever the block size and the number of keys. The TPU compiler takes ~2 min per
        shape (tools/chip_compile.py) — a cliff per new bucket that
        padded, premasked lanes are cheap against. Not free: device
        time grows with the lanes (PERF.md, Findings PR 28), which is
        why the floor is no larger than a block Fabric really cuts.
        CPU backends keep the tight power-of-two buckets."""
        if self._bucket_floor:
            return self._bucket_floor
        return (self._pipeline_span() or 0) if self._on_tpu() else 0

    def _bucket(self, n: int) -> int:
        """Lanes a batch of `n` signatures is padded to: the next
        power of two from max(MinBatch, `_floor`) while that stays
        within the pipeline span; above the span the next whole
        number of spans (5,000 lanes over a 2,048-lane span are 3
        spans, not the 4 of 8,192), which `_mesh_chunk` then cuts
        into span-sized dispatches. With the floor at the span (a
        TPU) that is ceil(n / span) dispatches of one shape for
        every n."""
        floor = max(self._min_batch, self._floor())
        b = floor
        while b < n:
            b *= 2
        span = self._pipeline_span()
        if span is not None and b > span:
            b = max(floor, -(-n // span) * span)
        if self._mesh is not None:
            m = self._mesh.size
            b = ((b + m - 1) // m) * m
        return b

    def _nb_bucket(self, max_len: int) -> Optional[int]:
        """Power-of-two SHA block count covering max_len, else None."""
        from fabric_tpu.ops import sha256
        nb = 1
        while sha256.max_message_len(nb) < max_len:
            nb *= 2
            if nb > self._max_blocks:
                return None
        return nb
