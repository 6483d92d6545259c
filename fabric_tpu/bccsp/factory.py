"""BCCSP factory: config-driven provider selection + process singleton.

Rebuild of `bccsp/factory/` (`factory.go:17-55`, `nopkcs11.go:20-34`,
`swfactory.go:38`): `FactoryOpts{default: "SW"|"TPU", ...}` chooses the
provider; `get_default()` is the handle injected throughout the node
(reference injection sites: `cmd/peer/main.go:46`,
`internal/peer/node/start.go:289`). `BCCSP.Default: TPU` in core.yaml is
the only user-visible switch — no other layer imports the tpu module.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field
from typing import Optional

from fabric_tpu.bccsp.bccsp import BCCSP
from fabric_tpu.common.breaker import BreakerConfig
from fabric_tpu.common.devicehealth import DeviceHealthConfig

logger = logging.getLogger("bccsp.factory")

_lock = threading.Lock()
_default: Optional[BCCSP] = None


@dataclass
class SwOpts:
    hash_family: str = "SHA2"
    security: int = 256
    keystore_path: Optional[str] = None


@dataclass
class TpuOpts:
    min_batch: int = 16
    max_blocks: int = 64
    # BCCSP.TPU.Devices: batch-axis device-mesh size for the sharded
    # verify pipeline. None/0 (the default) = ALL local devices — a
    # box with 8 chips shards every big batch across all 8; 1 pins the
    # single-device path (bit-for-bit the pre-mesh pipeline, no mesh
    # object at all); N>1 uses the first N local devices.
    n_devices: Optional[int] = None
    # comb-path knobs (fabric_tpu/bccsp/tpu.py): these select the
    # flagship 16-bit-window configuration; use_g16=None auto-resolves
    # to True on TPU backends so `BCCSP.Default: TPU` in core.yaml
    # gets the measured kernel, not a degraded one.
    use_g16: Optional[bool] = None
    chunk: int = 32768
    # dispatch-pipeline chunk (BCCSP.TPU.PipelineChunk): a device batch
    # is padded to and split into spans of this many lanes (all devices
    # together) so stage N's device execution overlaps stage N+1's host
    # prep (native DER parse, limb packing) and host->device transfer.
    # None (unset) = 2,048 lanes for each device of the verify mesh
    # (tpu.SPAN_LANES_PER_DEVICE): one default-cut block's 1,500-2,000
    # signatures fill one span, and a four-chip mesh keeps 2,048 lanes a
    # chip. 0 disables the overlapped pipeline (whole-batch staging, the
    # pre-round-6 behavior).
    pipeline_chunk: Optional[int] = None
    # the key-table pool (bccsp/tpu.py, "the key-table pool"): one
    # resident device array of per-key comb tables. MaxKeys is its
    # capacity in slots — the most distinct P-256 keys a batch may
    # carry and still be served by the comb program (more go to the
    # ladder) — as far as TableCacheMB (the pool's byte budget, in
    # bytes as the device holds them: 4,000 MB hold 13 slabs of 16-bit
    # windows, 302 MB a key on the chip — the most that costs the comb
    # program no device time, PERF.md Findings PR 34; 8-bit windows
    # are 2.4 MB a key) and half of a chip's memory hold that many.
    # One capacity, sized once; neither is a limit of its own.
    max_keys: int = 32
    table_cache_bytes: int = 4000 << 20
    # True (default): hash message lanes on host, ship 32-byte digests
    # (reference-matching CPU hash; minimal device transfer). False:
    # fuse SHA-256 into the device pipeline (PCIe-attached hosts).
    hash_on_host: bool = True
    # directory where the provider keeps a file for every key's table
    # in the pool, so `prewarm()` reads them back BEFORE the first
    # block after a restart instead of building them (node assembly
    # defaults this under peer.fileSystemPath); None disables
    # persistence
    warm_keys_dir: Optional[str] = None
    # pad device batches up to this bucket (0 = off): pins modest
    # windows (e.g. orderer sig-filter ingest) to an AOT-compiled
    # shape; padded lanes are premasked
    bucket_floor: int = 0
    # BCCSP.TPU.Ed25519: the scheme router's Ed25519 device kernel.
    # False pins Ed25519 lanes to the host reference path (verdicts
    # identical — this is a serving-path knob, not a policy one)
    ed25519: bool = True
    # graceful degradation (BCCSP.TPU.Fallback): circuit breaker
    # around every device dispatch — on trip the provider serves the
    # bit-identical sw path and re-probes after CooldownS
    fallback: BreakerConfig = field(default_factory=BreakerConfig)
    # elastic fail-in-place (BCCSP.TPU.DeviceHealth): per-device
    # quarantine for the sharded mesh — a lost/straggling chip is
    # benched and the provider rebuilds a smaller mesh over the
    # survivors instead of tripping the whole accelerator path
    device_health: DeviceHealthConfig = field(
        default_factory=DeviceHealthConfig)


@dataclass
class FactoryOpts:
    default: str = "SW"
    sw: SwOpts = field(default_factory=SwOpts)
    tpu: TpuOpts = field(default_factory=TpuOpts)

    @classmethod
    def from_config(cls, cfg: dict) -> "FactoryOpts":
        """Build from a core.yaml-style `BCCSP:` mapping (reference:
        `sampleconfig/core.yaml:319-343` plus the new `TPU:` sibling)."""
        cfg = cfg or {}
        sw_cfg = cfg.get("SW") or {}
        tpu_cfg = cfg.get("TPU") or {}
        fks = sw_cfg.get("FileKeyStore") or {}
        fb_cfg = tpu_cfg.get("Fallback") or {}
        fb_defaults = BreakerConfig()
        dh_cfg = tpu_cfg.get("DeviceHealth") or {}
        dh_defaults = DeviceHealthConfig()
        return cls(
            default=(cfg.get("Default") or "SW").upper(),
            sw=SwOpts(
                hash_family=sw_cfg.get("Hash", "SHA2"),
                security=int(sw_cfg.get("Security", 256)),
                keystore_path=fks.get("KeyStore") or None,
            ),
            tpu=TpuOpts(
                min_batch=int(tpu_cfg.get("MinBatch", 16)),
                max_blocks=int(tpu_cfg.get("MaxBlocks", 64)),
                n_devices=(int(tpu_cfg["Devices"])
                           if tpu_cfg.get("Devices") is not None else None),
                use_g16=(bool(tpu_cfg["UseG16"])
                         if tpu_cfg.get("UseG16") is not None else None),
                chunk=int(tpu_cfg.get("Chunk", 32768)),
                pipeline_chunk=(int(tpu_cfg["PipelineChunk"])
                                if tpu_cfg.get("PipelineChunk") is not None
                                else None),
                max_keys=int(tpu_cfg.get("MaxKeys", 32)),
                table_cache_bytes=(
                    int(tpu_cfg.get("TableCacheMB", 4000)) << 20),
                hash_on_host=bool(tpu_cfg.get("HashOnHost", True)),
                warm_keys_dir=tpu_cfg.get("WarmKeysDir") or None,
                bucket_floor=int(tpu_cfg.get("BucketFloor", 0)),
                ed25519=bool(tpu_cfg.get("Ed25519", True)),
                fallback=BreakerConfig(
                    deadline_ms=float(fb_cfg.get(
                        "DeadlineMs", fb_defaults.deadline_ms)),
                    trip_threshold=int(fb_cfg.get(
                        "TripThreshold", fb_defaults.trip_threshold)),
                    cooldown_s=float(fb_cfg.get(
                        "CooldownS", fb_defaults.cooldown_s)),
                    probe_batch=int(fb_cfg.get(
                        "ProbeBatch", fb_defaults.probe_batch)),
                ),
                device_health=DeviceHealthConfig(
                    trip_threshold=int(dh_cfg.get(
                        "TripThreshold", dh_defaults.trip_threshold)),
                    cooldown_s=float(dh_cfg.get(
                        "CooldownS", dh_defaults.cooldown_s)),
                    straggler_skew_s=float(dh_cfg.get(
                        "StragglerSkewS",
                        dh_defaults.straggler_skew_s)),
                    straggler_strikes=int(dh_cfg.get(
                        "StragglerStrikes",
                        dh_defaults.straggler_strikes)),
                    probe_timeout_s=float(dh_cfg.get(
                        "ProbeTimeoutS",
                        dh_defaults.probe_timeout_s)),
                ),
            ),
        )


def _resolve_mesh(n_devices: Optional[int]):
    """BCCSP.TPU.Devices -> (mesh, requested) for the provider.

    None/0 = all local devices (the sharded flagship: every chip on
    the box combs its slice of the batch); 1 = no mesh, the
    single-device pipeline bit-for-bit; N>1 = the first N devices.
    Availability first: a backend that cannot even enumerate devices
    (mid-flight libtpu upgrade, a chip another process holds)
    degrades to the
    single-device path with a warning instead of failing provider
    construction — the breaker handles the rest at dispatch time.
    `requested` is the multi-device ask that was NOT satisfied (the
    explicit count, or "all" when enumeration itself failed): the
    provider surfaces it as the `degraded_mesh:1/<requested>` health
    sub-state so operators see the silent 1-chip degrade on /healthz,
    not just in logs. None when the ask was met (or was 1)."""
    try:
        nd = n_devices
        if nd == 1:
            return None, None
        import jax
        avail = len(jax.devices())
        if nd is None or nd <= 0:
            nd = avail
        elif nd > avail:
            # explicit over-ask (stale config on a smaller rig) serves
            # on every device there IS, loudly — silently dropping to
            # ONE device would cost ~avail x the configured throughput
            logger.warning(
                "BCCSP.TPU.Devices: %d exceeds the %d local "
                "device(s); clamping to %d", nd, avail, avail)
            nd = avail
        if nd <= 1:
            return None, None
        from fabric_tpu.parallel import batch_mesh
        return batch_mesh(nd), None
    except Exception:
        logger.exception(
            "could not build the %s-device verify mesh; serving on "
            "the single-device path (set BCCSP.TPU.Devices: 1 to "
            "silence)", n_devices if n_devices else "all")
        return None, (n_devices if n_devices and n_devices > 1
                      else "all")


def new_bccsp(opts: FactoryOpts) -> BCCSP:
    ks = None
    if opts.sw.keystore_path:
        from fabric_tpu.bccsp.keystore import FileKeyStore
        ks = FileKeyStore(opts.sw.keystore_path)
    if opts.default == "SW":
        from fabric_tpu.bccsp.sw import SWProvider
        return SWProvider(ks)
    if opts.default == "TPU":
        from fabric_tpu.bccsp.tpu import TPUProvider
        from fabric_tpu.common import jaxenv
        # compiled verify kernels are part of the node's warm state:
        # the persistent XLA cache lets a restart (or the next bench
        # process) skip the ~minutes compiles
        jaxenv.enable_compilation_cache()
        mesh, unmet = _resolve_mesh(opts.tpu.n_devices)
        return TPUProvider(ks, min_batch=opts.tpu.min_batch,
                           max_blocks=opts.tpu.max_blocks, mesh=mesh,
                           max_keys=opts.tpu.max_keys,
                           chunk=opts.tpu.chunk,
                           pipeline_chunk=opts.tpu.pipeline_chunk,
                           use_g16=opts.tpu.use_g16,
                           table_cache_bytes=opts.tpu.table_cache_bytes,
                           hash_on_host=opts.tpu.hash_on_host,
                           warm_keys_dir=opts.tpu.warm_keys_dir,
                           bucket_floor=opts.tpu.bucket_floor,
                           fallback=opts.tpu.fallback,
                           ed25519=opts.tpu.ed25519,
                           device_health=opts.tpu.device_health,
                           mesh_requested=unmet)
    raise ValueError(f"unknown BCCSP default {opts.default!r}")


def init_factories(opts: Optional[FactoryOpts] = None) -> BCCSP:
    """Initialize the process-wide default provider (idempotent, like
    `bccsp/factory/nopkcs11.go:29` InitFactories' sync.Once)."""
    global _default
    with _lock:
        if _default is None:
            _default = new_bccsp(opts or FactoryOpts())
        return _default


def get_default() -> BCCSP:
    """The singleton handle (reference: `factory.go:42` GetDefault, which
    lazily falls back to SW with a warning)."""
    global _default
    if _default is None:
        return init_factories()
    return _default


def _reset_for_tests() -> None:
    global _default
    with _lock:
        _default = None
