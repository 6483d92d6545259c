"""Metrics provider API: Counter / Gauge / Histogram with label currying.

Equivalent of the reference's ``common/metrics`` (go-kit style; see reference
``common/metrics/provider.go``): components receive a ``Provider`` and create
instruments from ``*Opts``; ``with_labels(...)`` returns a curried instrument.
Backends: ``PrometheusProvider`` (in-process registry rendered as Prometheus
text exposition on the operations endpoint, like the reference's
``/metrics``), ``StatsdProvider`` (UDP push with a flush loop, reference
``common/metrics/statsd`` + ``operations/system.go`` statsd wiring), and
``DisabledProvider`` (no-ops, reference ``common/metrics/disabled``).
"""

from __future__ import annotations

import math
import socket
import threading
from dataclasses import dataclass, field


@dataclass(frozen=True)
class CounterOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()


@dataclass(frozen=True)
class GaugeOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()


@dataclass(frozen=True)
class HistogramOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()
    buckets: tuple[float, ...] = (
        0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10,
    )


def _fqname(opts) -> str:
    return "_".join(p for p in (opts.namespace, opts.subsystem, opts.name) if p)


def _label_key(
    names: tuple[str, ...], label_values: tuple[str, ...]
) -> tuple[tuple[str, str], ...]:
    if len(label_values) % 2 != 0:
        raise ValueError("odd number of label values")
    given = dict(zip(label_values[::2], label_values[1::2]))
    return tuple((n, given.get(n, "")) for n in names)


# -- shared degradation instruments --
#
# One spelling for the graceful-degradation surfaces, whichever node
# assembly (peer or orderer) wires them: the TPU verify path's breaker
# state, and the robustness counters the chaos subsystem exposes.
# Components create them via `provider.new_*(OPTS)`; the registry
# dedupes by fully-qualified name.

BCCSP_FALLBACK_STATE_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="fallback", name="state",
    help="TPU verify path breaker state: 0 device, 1 probing, "
         "2 degraded (sw fallback serving).")

BCCSP_FALLBACK_TRIPS_OPTS = CounterOpts(
    namespace="bccsp", subsystem="fallback", name="trips_total",
    help="Circuit-breaker trips: the device was benched after "
         "consecutive dispatch failures or deadline stalls.")

BCCSP_PIPELINE_HOST_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pipeline", name="host_s",
    help="Host-prep seconds (DER parse, limb packing, digest hashing) "
         "spent staging the most recent overlapped verify batch.")

BCCSP_PIPELINE_TRANSFER_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pipeline", name="transfer_s",
    help="Host-to-device transfer-enqueue seconds for the most recent "
         "overlapped verify batch (async device_put ahead of "
         "dispatch).")

BCCSP_PIPELINE_DEVICE_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pipeline", name="device_s",
    help="Device dispatch + result-materialization seconds for the "
         "most recent overlapped verify batch.")

BCCSP_PIPELINE_OVERLAP_RATIO_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pipeline", name="overlap_ratio",
    help="Fraction of host-prep time hidden behind device execution "
         "in the most recent overlapped verify batch: 0 = fully "
         "serial, (chunks-1)/chunks = fully pipelined.")

BCCSP_DEVICE_INFO_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="info",
    help="Device count of the JAX backend the TPU verify provider "
         "runs on, labeled with the platform and device kind JAX "
         "reports (the breaker's `device` health state reads the same "
         "on a CPU backend; this series tells them apart).",
    label_names=("platform", "device_kind"))

BCCSP_SHARD_DEVICES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="shard", name="devices",
    help="Device-mesh size the TPU verify provider shards the batch "
         "axis over (BCCSP.TPU.Devices; 1 = single-device pipeline, "
         "no mesh).")

BCCSP_SHARD_DISPATCHES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="shard", name="dispatches",
    help="Sharded span/chunk dispatches issued to the device mesh "
         "since process start (each runs one per-shard comb program "
         "on every chip).")

BCCSP_SHARD_TRANSFER_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="shard", name="transfer_s",
    help="Per-device host-to-device transfer-enqueue seconds for the "
         "most recent sharded verify batch: the round-robin span "
         "feeder runs one explicit stream per chip, so a chip with a "
         "slow link stands out instead of smearing into one number.",
    label_names=("device",))

BCCSP_SHARD_READY_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="shard", name="ready_s",
    help="Per-device seconds from the batch's first span dispatch "
         "until that device's slice of the final span's accept bitmap "
         "was ready. Sampled in a per-batch rotating order (each "
         "reading is an upper bound given earlier-sampled devices); "
         "a straggler chip shows as a step at its sampling position — "
         "the rotation guarantees a chip is not permanently sampled "
         "first, where its slowness would inflate every reading "
         "equally and hide.", label_names=("device",))

BCCSP_SHARD_LANES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="shard", name="lanes",
    help="Signature lanes the most recent sharded span placed on each "
         "device (the batch axis is dealt contiguously across the "
         "mesh).", label_names=("device",))

BCCSP_SCHEME_LANES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="scheme", name="lanes",
    help="Signature lanes the scheme-dispatch router has routed to "
         "each per-scheme sub-batch path (p256 comb/tree pipeline, "
         "ed25519 batch kernel, bls pairing path) since process "
         "start.", label_names=("scheme",))

BCCSP_SCHEME_SW_LANES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="scheme", name="sw_lanes",
    help="Lanes per scheme that served on the per-lane sw/host path "
         "instead of a device kernel (non-P-256 ECDSA curves, "
         "sub-min-batch remainders, breaker fallbacks) — the "
         "per-scheme split of the nonp256_sw_lanes scalar.",
    label_names=("scheme",))

BCCSP_SCHEME_DISPATCHES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="scheme", name="dispatches",
    help="Device/aggregate dispatches the scheme router has issued "
         "per scheme (one per routed sub-batch; for bls, one per "
         "aggregate pairing check).", label_names=("scheme",))

BCCSP_PAIRING_PAIRS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pairing", name="pairs",
    help="Miller pairs served by the device pairing engines since "
         "process start — BLS12-381 aggregate-verify batches "
         "(round-21 wide-limb kernel, one shared final exponentiation "
         "per call) plus BN254 idemix pairing products.")

BCCSP_PAIRING_BATCHES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pairing", name="batches",
    help="Batched pairing programs dispatched to device (one per "
         "verify_aggregate call or idemix pairing_check_batch that "
         "cleared the small-batch gate).")

BCCSP_PAIRING_FALLBACKS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="pairing", name="fallbacks",
    help="Pairing dispatches demoted to the exact host path (breaker "
         "open, unhealthy mesh, armed fault or a device error) — "
         "verdicts stay bit-identical; small-batch POLICY routing to "
         "the host is deliberate and not counted here.")

BCCSP_LANES_REAL_OPTS = GaugeOpts(
    namespace="bccsp", name="lanes_real",
    help="Signatures handed to the prepared-block device path since "
         "process start (the `lanes` attr of the `tpu.stage` spans, "
         "summed).")

BCCSP_LANES_PADDED_OPTS = GaugeOpts(
    namespace="bccsp", name="lanes_padded",
    help="Lanes the device ran for those signatures since process "
         "start: each batch padded to its compiled bucket. "
         "lanes_real / lanes_padded is the share of device lanes that "
         "verified something.")

BCCSP_H2D_BYTES_OPTS = GaugeOpts(
    namespace="bccsp", name="h2d_bytes",
    help="Operand bytes the prepared-block dispatches staged to the "
         "device since process start (the `bytes` attr of the "
         "`tpu.h2d` spans, summed; resident tables not included).")

BCCSP_SHARD_SKEW_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="shard", name="skew_s",
    help="Ready-time spread (max - min) across mesh devices for the "
         "most recent sharded batch: persistent skew means one chip "
         "paces the whole mesh.")

BCCSP_DEVICE_STATE_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="state",
    help="Per-chip health state in the elastic verify mesh: 0 healthy "
         "(serving), 1 probing (cooldown elapsed, awaiting its "
         "re-admission probe), 2 quarantined (out of the mesh; the "
         "provider serves on the survivors). Device label = full-mesh "
         "index.", label_names=("device",))

BCCSP_DEVICE_TRIPS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="trips",
    help="Per-chip breaker trips: device-attributed dispatch/transfer "
         "failures or straggler-strike budgets that opened this "
         "chip's quarantine breaker since process start.",
    label_names=("device",))

BCCSP_DEVICE_QUARANTINES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="quarantines",
    help="Times this chip entered quarantine (benched out of the "
         "serving mesh) since process start — each one triggered a "
         "degraded-mesh rebuild over the surviving chips.",
    label_names=("device",))

BCCSP_DEVICE_READMITS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="readmits",
    help="Times this chip passed its re-admission probe and rejoined "
         "the serving mesh (the mesh grew back) since process start.",
    label_names=("device",))

BCCSP_DEVICE_QUARANTINES_TOTAL_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="quarantines_total",
    help="Chip quarantines across the whole mesh since process start "
         "— the scalar aggregate of the device-labeled "
         "bccsp_device_quarantines series, under its own canonical "
         "name so the generic provider-stats poller can publish it "
         "without colliding with the labeled gauge's fqname.")

BCCSP_DEVICE_READMITS_TOTAL_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="readmits_total",
    help="Probe re-admissions across the whole mesh since process "
         "start — the scalar aggregate of the device-labeled "
         "bccsp_device_readmits series (see "
         "bccsp_device_quarantines_total for why the name differs "
         "from the stats key).")

BCCSP_COMPILE_TOTAL_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="compile", name="total",
    help="XLA programs built through the provider's compile seam "
         "(common/devicecost.py) since process start: each first "
         "dispatch of a new argument shape and each AOT prewarm "
         "compile, whether a cold compile or a persistent-cache "
         "load.")

BCCSP_COMPILE_CACHE_HITS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="compile", name="cache_hits",
    help="Persistent-compile-cache hits among bccsp_compile_total "
         "(classified by cache-dir entry delta plus a wall-time "
         "threshold). total - cache_hits = cold compiles — the "
         "minutes-long restart cliff; a cold compile in steady state "
         "auto-dumps the flight recorder.")

BCCSP_COMPILE_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="compile", name="seconds",
    help="Cumulative wall seconds spent inside the compile seam "
         "(tracing + XLA compilation or cache load) since process "
         "start — the device-side cost the bench's compile_s stage "
         "field and the perf ledger track across rounds.")

BCCSP_EXECUTABLE_STORE_HITS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="executable_store", name="hits",
    help="Programs prewarm asked for ahead of time that were loaded "
         "from the store of compiled executables "
         "(common/execstore.py) since process start: nothing traced, "
         "lowered or compiled. A restarted peer reads its whole "
         "inventory here.")

BCCSP_EXECUTABLE_STORE_MISSES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="executable_store", name="misses",
    help="Programs prewarm asked for that the store held no entry "
         "for (first start after a change of code, JAX or device): "
         "lowered, loaded from the persistent compile cache or "
         "compiled cold, then written to the store.")

BCCSP_EXECUTABLE_STORE_ERRORS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="executable_store", name="errors",
    help="Store entries that could not be read, checked (sha256, "
         "request, input shapes) or loaded — served by compiling and "
         "replaced — plus entries that could not be written.")

BCCSP_DEVICE_MEM_USED_BYTES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="mem_used_bytes",
    help="Per-device bytes currently allocated (memory_stats "
         "bytes_in_use), polled by publish_devicecost_stats. Devices "
         "without the API (CPU meshes) publish nothing.",
    label_names=("device",))

BCCSP_DEVICE_MEM_PEAK_BYTES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="mem_peak_bytes",
    help="Per-device peak bytes allocated since process start "
         "(memory_stats peak_bytes_in_use) — the high-water mark an "
         "oversized span leaves behind.",
    label_names=("device",))

BCCSP_DEVICE_MEM_LIMIT_BYTES_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="mem_limit_bytes",
    help="Per-device memory capacity (memory_stats bytes_limit); "
         "used - limit headroom under FTPU_HBM_HEADROOM_FRAC also "
         "surfaces as the /healthz components.bccsp hbm_low "
         "sub-state.",
    label_names=("device",))

BCCSP_DEVICE_BUSY_RATIO_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="device", name="busy_ratio",
    help="Per-device device-time over wall-time in the last poll "
         "window, fed from the same per-chip ready readings as the "
         "device.ready.d<k> tracing stages — sustained low ratios "
         "on a big mesh mean the feeder (host prep/transfer), not "
         "the chips, is the bottleneck.",
    label_names=("device",))

TRACE_STAGE_SECONDS_OPTS = HistogramOpts(
    namespace="trace", subsystem="stage", name="seconds",
    help="Per-stage latency distributions from the lifecycle-tracing "
         "spans (common/tracing.py): ingress batches, admission-"
         "window convoy waits, order window/propose/consensus/write, "
         "commit-pipeline validate/commit, device dispatch and "
         "per-device transfer/ready — p50/p99-derivable tails beside "
         "the last-batch snapshot gauges. The stage label is the "
         "span name.",
    label_names=("stage",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10))

COMMIT_PIPELINE_DEPTH_OPTS = GaugeOpts(
    namespace="commit", subsystem="pipeline", name="depth",
    help="Configured commit-pipeline depth: how many blocks may be "
         "validated ahead of the block being committed "
         "(Peer.CommitPipeline.Depth; the gauge exists only when the "
         "pipeline is on).", label_names=("channel",))

COMMIT_PIPELINE_VALIDATE_SECONDS_OPTS = GaugeOpts(
    namespace="commit", subsystem="pipeline", name="validate_s",
    help="Stage-A seconds (block verify + batched validation + rwset "
         "extraction) for the most recent pipelined block.",
    label_names=("channel",))

COMMIT_PIPELINE_COMMIT_SECONDS_OPTS = GaugeOpts(
    namespace="commit", subsystem="pipeline", name="commit_s",
    help="Stage-B seconds (private-data gather + ledger commit) for "
         "the most recent pipelined block.", label_names=("channel",))

COMMIT_PIPELINE_OVERLAP_RATIO_OPTS = GaugeOpts(
    namespace="commit", subsystem="pipeline", name="overlap_ratio",
    help="Cumulative fraction of stage-A validation time hidden "
         "behind stage-B commits of earlier blocks: 0 = fully "
         "sequential intake, approaching 1 = validation fully hidden.",
    label_names=("channel",))

COMMIT_PIPELINE_BARRIER_TOTAL_OPTS = CounterOpts(
    namespace="commit", subsystem="pipeline", name="barrier_total",
    help="Times stage A drained the pipeline before validating a "
         "block, by reason: a config-block predecessor, a "
         "validation-parameter or _lifecycle update, or a "
         "sequential-fallback demotion.",
    label_names=("channel", "reason"))

ORDERER_BATCH_FILL_OPTS = GaugeOpts(
    namespace="orderer", subsystem="batch", name="fill",
    help="Envelopes carried by the most recent raft proposal cut from "
         "the ordering admission window (how full the batched propose "
         "path runs; 1 = the per-envelope floor).",
    label_names=("channel",))

ORDERER_BATCH_PROPOSE_SECONDS_OPTS = GaugeOpts(
    namespace="orderer", subsystem="batch", name="propose_s",
    help="Seconds the raft loop spent cutting and proposing the most "
         "recent admission window (msgprocessor revalidation, "
         "blockcutter pass, block assembly, one batched raft append).",
    label_names=("channel",))

ORDERER_BATCH_CONSENSUS_SECONDS_OPTS = GaugeOpts(
    namespace="orderer", subsystem="batch", name="consensus_s",
    help="Propose-to-commit seconds for the most recent block this "
         "leader proposed (raft replication + majority ack latency).",
    label_names=("channel",))

ORDERER_BATCH_WRITE_SECONDS_OPTS = GaugeOpts(
    namespace="orderer", subsystem="batch", name="write_s",
    help="Seconds the write stage spent signing and appending the "
         "most recent committed-block span (runs off the raft loop "
         "on the block-write worker).", label_names=("channel",))

ORDERER_BATCH_OVERLAP_RATIO_OPTS = GaugeOpts(
    namespace="orderer", subsystem="batch", name="overlap_ratio",
    help="Cumulative fraction of block-write time hidden behind the "
         "raft loop's cut/consensus work: 0 = fully sequential "
         "ordering, approaching 1 = writes fully hidden.",
    label_names=("channel",))

OVERLOAD_QUEUE_DEPTH_OPTS = GaugeOpts(
    namespace="overload", subsystem="queue", name="depth",
    help="Current depth of each registered inter-stage overload "
         "queue (broadcast ingress, raft events, write stage, commit "
         "pipeline, gossip inbox) — bounded by design; a depth "
         "pinned at capacity means the stage downstream is the "
         "bottleneck and sheds are imminent.",
    label_names=("stage",))

OVERLOAD_QUEUE_CAPACITY_OPTS = GaugeOpts(
    namespace="overload", subsystem="queue", name="capacity",
    help="Configured bound of each registered overload queue (0 = "
         "self-tuning, e.g. the admission window's convoy).",
    label_names=("stage",))

OVERLOAD_QUEUE_MAX_DEPTH_OPTS = GaugeOpts(
    namespace="overload", subsystem="queue", name="max_depth",
    help="High-water depth each overload queue has reached since "
         "process start — the soak rig's bounded-depth check reads "
         "this against capacity.", label_names=("stage",))

OVERLOAD_SHEDS_TOTAL_OPTS = CounterOpts(
    namespace="overload", name="sheds_total",
    help="Work items shed per stage: the stage could not accept the "
         "item within the caller's deadline budget and refused it "
         "retryably (broadcast clients see SERVICE_UNAVAILABLE). "
         "Sustained growth means the system is running past "
         "capacity and degrading GRACEFULLY — the alternative this "
         "counter replaced was an unbounded stall.",
    label_names=("stage",))

OVERLOAD_PUT_WAIT_SECONDS_OPTS = GaugeOpts(
    namespace="overload", subsystem="queue", name="wait_s",
    help="Seconds the most recent admission into each overload queue "
         "waited for space (backpressure before the shed horizon).",
    label_names=("stage",))

OVERLOAD_SHED_RATE_OPTS = GaugeOpts(
    namespace="overload", name="shed_rate",
    help="Sheds per second over each stage's trailing rolling window "
         "(overload.SHED_RATE_WINDOW_S): the burst-vs-steady reading "
         "the round-19 adaptive controller and /healthz act on — "
         "sheds_total answers 'has this stage ever shed', this "
         "gauge answers 'is it shedding NOW'.",
    label_names=("stage",))

ADAPTIVE_KNOB_VALUE_OPTS = GaugeOpts(
    namespace="adaptive", subsystem="knob", name="value",
    help="Current value of each serving knob registered with the "
         "round-19 adaptive admission controller (queue capacities, "
         "deadline budgets, the admission-window span), updated at "
         "each controller move — the live picture of how far the "
         "plane is tightened from its configured ceilings.",
    label_names=("knob",))

ADAPTIVE_ADJUSTMENTS_TOTAL_OPTS = CounterOpts(
    namespace="adaptive", name="adjustments_total",
    help="Knob moves the adaptive controller applied, by knob and "
         "direction (tighten = floor-ward under SLO-burn/saturation, "
         "relax = ceiling-ward in calm). A healthy controller moves "
         "in bounded runs; alternating tighten/relax growth is "
         "flapping and the hysteresis discipline failing.",
    label_names=("knob", "direction"))

ADAPTIVE_SIGNAL_OPTS = GaugeOpts(
    namespace="adaptive", name="signal",
    help="The adaptive controller's input vector as last sampled: "
         "slo_burn (error-budget burn rate), shed_rate (summed "
         "rolling per-stage sheds/s), queue_pressure (max "
         "depth/capacity), device_busy (max per-chip busy ratio), "
         "hbm_headroom (min per-chip free-memory fraction) — the "
         "evidence behind every adaptive.adjust instant.",
    label_names=("signal",))

BCCSP_ADMISSION_WAIT_SECONDS_OPTS = GaugeOpts(
    namespace="bccsp", subsystem="admission", name="wait_s",
    help="Seconds the most recent verify_batch caller spent in the "
         "admission window's convoy (queued behind an in-flight "
         "coalesced dispatch) before its own verdicts were taken or "
         "dispatched — the convoy latency the round-12 "
         "condition-variable rewrite made observable.")

NET_CHAOS_DROPPED_TOTAL_OPTS = CounterOpts(
    namespace="net", subsystem="chaos", name="dropped_total",
    help="Messages dropped by the network-chaos layer "
         "(common/netchaos.py): link-policy drop draws plus armed "
         "net.drop fault fires. Nonzero proves a chaos soak's claimed "
         "loss rate actually happened.")

NET_CHAOS_DUPLICATED_TOTAL_OPTS = CounterOpts(
    namespace="net", subsystem="chaos", name="duplicated_total",
    help="Messages delivered twice by the network-chaos layer "
         "(dup-rate policy draws plus armed net.dup fault fires) — "
         "the duplicate-safe step handling they exercise must keep "
         "commit streams bit-identical.")

NET_CHAOS_DELAYED_TOTAL_OPTS = CounterOpts(
    namespace="net", subsystem="chaos", name="delayed_total",
    help="Messages deferred by the network-chaos layer's scheduler "
         "(fixed/jittered link delay policies plus armed net.delay "
         "fault fires); the sender never blocks.")

NET_CHAOS_REORDERED_TOTAL_OPTS = CounterOpts(
    namespace="net", subsystem="chaos", name="reordered_total",
    help="Messages held back for bounded reordering (overtaken by up "
         "to the policy's reorder window of later messages on their "
         "link, or released at the hold deadline).")

NET_CHAOS_PARTITIONED_TOTAL_OPTS = CounterOpts(
    namespace="net", subsystem="chaos", name="partitioned_total",
    help="Messages cut by an installed chaos partition (symmetric or "
         "asymmetric link-set cuts, programmatic or armed via "
         "net.partition) before it healed.")

DELIVER_RECONNECTS_OPTS = CounterOpts(
    namespace="deliver", subsystem="client", name="reconnects",
    help="Deliver-stream reconnect attempts after a stream failure "
         "(full-jitter backoff between attempts).",
    label_names=("channel",))

E2E_COMMIT_SECONDS_OPTS = HistogramOpts(
    namespace="e2e", subsystem="commit", name="seconds",
    help="End-to-end commit latency: first-ingress birth stamp to "
         "durable commit on the labeled node (the user-visible "
         "finality number — common/clustertrace.py observes it at "
         "every commit-pipeline/gossip-state commit where the "
         "block's trace carrier is known). Birth rides the wire "
         "carrier, so re-relays and carrier-forwarded re-deliveries "
         "keep one identity; the rolling SLO error budget "
         "(Operations.SLO.CommitP99S -> /healthz components.slo) is "
         "fed from the same observations.",
    label_names=("node",),
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5,
             10, 30, 60))

HOP_SECONDS_OPTS = HistogramOpts(
    namespace="hop", name="seconds",
    help="Per-hop network latency observed at carrier EXTRACTION "
         "(send wall-stamp to receive), labeled by link (consensus "
         "`src>dst`, `deliver:<endpoint>`, `gossip:<src>`, "
         "`broadcast:client`). Cross-node readings include wall-"
         "clock skew: negative raws are clamped to 0 here but kept "
         "in the hop.recv span args as the cluster merger's "
         "residual-skew evidence.",
    label_names=("link",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5))

RPC_REJECTS_TOTAL_OPTS = CounterOpts(
    namespace="rpc", name="rejects_total",
    help="RPCs rejected at the gRPC edge by the per-service "
         "concurrency limiter (comm/interceptors.py "
         "ConcurrencyLimiter, RESOURCE_EXHAUSTED): shed work that "
         "never reached a pipeline queue, counted beside "
         "overload_sheds_total so the overload picture includes the "
         "transport edge; each rejection also leaves an `rpc.reject` "
         "instant in the flight recorder.",
    label_names=("service", "method"))


class Counter:
    def __init__(self, opts: CounterOpts):
        self.opts = opts
        self._lock = threading.Lock()
        self._values: dict[tuple, float] = {}
        self._labels: tuple[str, ...] = ()

    def with_labels(self, *label_values: str) -> "Counter":
        child = Counter.__new__(Counter)
        child.opts = self.opts
        child._lock = self._lock
        child._values = self._values
        child._labels = self._labels + label_values
        return child

    def add(self, delta: float = 1.0) -> None:
        key = _label_key(self.opts.label_names, self._labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta


class Gauge:
    def __init__(self, opts: GaugeOpts):
        self.opts = opts
        self._lock = threading.Lock()
        self._values: dict[tuple, float] = {}
        self._labels: tuple[str, ...] = ()

    def with_labels(self, *label_values: str) -> "Gauge":
        child = Gauge.__new__(Gauge)
        child.opts = self.opts
        child._lock = self._lock
        child._values = self._values
        child._labels = self._labels + label_values
        return child

    def set(self, value: float) -> None:
        key = _label_key(self.opts.label_names, self._labels)
        with self._lock:
            self._values[key] = value

    def add(self, delta: float) -> None:
        key = _label_key(self.opts.label_names, self._labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta


@dataclass
class _HistState:
    counts: list[int]
    total: int = 0
    sum: float = 0.0


class Histogram:
    def __init__(self, opts: HistogramOpts):
        self.opts = opts
        self._lock = threading.Lock()
        self._states: dict[tuple, _HistState] = {}
        self._labels: tuple[str, ...] = ()

    def with_labels(self, *label_values: str) -> "Histogram":
        child = Histogram.__new__(Histogram)
        child.opts = self.opts
        child._lock = self._lock
        child._states = self._states
        child._labels = self._labels + label_values
        return child

    def observe(self, value: float) -> None:
        key = _label_key(self.opts.label_names, self._labels)
        with self._lock:
            st = self._states.get(key)
            if st is None:
                st = _HistState(counts=[0] * len(self.opts.buckets))
                self._states[key] = st
            for i, ub in enumerate(self.opts.buckets):
                if value <= ub:
                    st.counts[i] += 1
            st.total += 1
            st.sum += value


class Provider:
    """Abstract provider; see PrometheusProvider / DisabledProvider."""

    def new_counter(self, opts: CounterOpts) -> Counter:
        raise NotImplementedError

    def new_gauge(self, opts: GaugeOpts) -> Gauge:
        raise NotImplementedError

    def new_histogram(self, opts: HistogramOpts) -> Histogram:
        raise NotImplementedError


class PrometheusProvider(Provider):
    """Registry-backed provider rendering Prometheus text exposition."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._instruments: dict[str, object] = {}

    def _register(self, name: str, inst):
        with self._lock:
            existing = self._instruments.get(name)
            if existing is not None:
                if type(existing) is not type(inst) or existing.opts != inst.opts:
                    raise ValueError(
                        f"metric {name} re-registered with different type or opts"
                    )
                return existing
            self._instruments[name] = inst
            return inst

    def new_counter(self, opts: CounterOpts) -> Counter:
        return self._register(_fqname(opts), Counter(opts))

    def new_gauge(self, opts: GaugeOpts) -> Gauge:
        return self._register(_fqname(opts), Gauge(opts))

    def new_histogram(self, opts: HistogramOpts) -> Histogram:
        return self._register(_fqname(opts), Histogram(opts))

    def render(self) -> str:
        """Prometheus text exposition format (for the /metrics endpoint)."""
        out: list[str] = []
        with self._lock:
            instruments = dict(self._instruments)
        for name, inst in sorted(instruments.items()):
            if isinstance(inst, Counter):
                out.append(f"# HELP {name} {inst.opts.help}")
                out.append(f"# TYPE {name} counter")
                with inst._lock:
                    values = dict(inst._values)
                for key, v in sorted(values.items()):
                    out.append(f"{name}{_render_labels(key)} {_fmt(v)}")
            elif isinstance(inst, Gauge):
                out.append(f"# HELP {name} {inst.opts.help}")
                out.append(f"# TYPE {name} gauge")
                with inst._lock:
                    values = dict(inst._values)
                for key, v in sorted(values.items()):
                    out.append(f"{name}{_render_labels(key)} {_fmt(v)}")
            elif isinstance(inst, Histogram):
                out.append(f"# HELP {name} {inst.opts.help}")
                out.append(f"# TYPE {name} histogram")
                with inst._lock:
                    states = {
                        k: _HistState(list(s.counts), s.total, s.sum)
                        for k, s in inst._states.items()
                    }
                for key, st in sorted(states.items()):
                    for ub, c in zip(inst.opts.buckets, st.counts):
                        lk = key + (("le", _fmt(ub)),)
                        out.append(f"{name}_bucket{_render_labels(lk)} {c}")
                    lk = key + (("le", "+Inf"),)
                    out.append(f"{name}_bucket{_render_labels(lk)} {st.total}")
                    out.append(f"{name}_sum{_render_labels(key)} {_fmt(st.sum)}")
                    out.append(f"{name}_count{_render_labels(key)} {st.total}")
        return "\n".join(out) + "\n"


def _escape_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _render_labels(key: tuple[tuple[str, str], ...]) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in key)
    return "{" + inner + "}"


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if float(v).is_integer():
        return str(int(v))
    return repr(float(v))


# ftpu-check: allow-lockset(_last_counts is flush-loop scratch; a manual
# flush racing the loop at worst double-counts one statsd delta)
class StatsdProvider(PrometheusProvider):
    """Statsd backend: instruments accumulate exactly like the registry
    provider; a flush loop (or explicit `flush()`) emits the current
    readings as statsd lines over UDP — `name.label1.label2:value|type`
    (counters `|c`, gauges `|g`, histogram observations summarized as
    `.sum`/`.count` gauges), matching the reference's go-kit statsd
    bridge's dotted-path naming (`common/metrics/statsd/provider.go`
    NewCounter/NewGauge/NewHistogram + operations/system.go flusher)."""

    def __init__(self, address: str = "127.0.0.1:8125",
                 prefix: str = "", flush_interval_s: float = 10.0):
        super().__init__()
        host, _, port = address.rpartition(":")
        self._addr = (host or "127.0.0.1", int(port))
        self._prefix = prefix
        self._interval = flush_interval_s
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._last_counts: dict[str, float] = {}

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop,
                                        name="statsd-flush", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self._interval)
            self._thread = None
        self.flush()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                self.flush()
            # ftpu-lint: allow-swallow(a statsd outage must never hurt
            # the node, and warning once per interval would spam for
            # the outage's whole duration; flush retries next tick)
            except Exception:
                pass

    def _path(self, name: str, key) -> str:
        parts = [self._prefix] if self._prefix else []
        parts.append(name)
        parts.extend(_escape_statsd(v) for _n, v in key)
        return ".".join(parts)

    def flush(self) -> list[str]:
        """Emit current readings; returns the lines (for tests)."""
        lines: list[str] = []
        # counter-total commits, parallel to lines: _last_counts is
        # only advanced AFTER a successful send, so a failed sendto
        # re-emits the delta on the next flush instead of losing it
        commits: list = []
        with self._lock:
            instruments = dict(self._instruments)
        for name, inst in sorted(instruments.items()):
            if isinstance(inst, Histogram):
                with inst._lock:
                    states = {k: (s.sum, s.total)
                              for k, s in inst._states.items()}
                for key, (s, n) in sorted(states.items()):
                    p = self._path(name, key)
                    lines.append(f"{p}.sum:{_fmt(s)}|g")
                    lines.append(f"{p}.count:{n}|g")
                    commits.extend([None, None])
                continue
            with inst._lock:
                values = dict(inst._values)
            for key, v in sorted(values.items()):
                p = self._path(name, key)
                if isinstance(inst, Counter):
                    # statsd counters are deltas; send the increment
                    delta = v - self._last_counts.get(p, 0.0)
                    if delta:
                        lines.append(f"{p}:{_fmt(delta)}|c")
                        commits.append((p, v))
                else:
                    lines.append(f"{p}:{_fmt(v)}|g")
                    commits.append(None)
        for line, commit in zip(lines, commits):
            try:
                self._sock.sendto(line.encode(), self._addr)
            except OSError:
                break
            if commit is not None:
                self._last_counts[commit[0]] = commit[1]
        return lines


def _escape_statsd(v: str) -> str:
    out = str(v).replace(".", "_").replace(":", "_").replace("|", "_")
    # empty label values must still occupy a path segment, or two
    # distinct label sets would merge into one statsd series (and the
    # counter delta bookkeeping would cross the streams)
    return out or "unknown"


def provider_from_config(which: str, statsd_address: str = "127.0.0.1:8125",
                         statsd_prefix: str = "",
                         statsd_interval_s: float = 10.0) -> Provider:
    """One provider-selection path for both node assemblies (the config
    key SPELLING differs between core.yaml and orderer.yaml; the
    semantics must not)."""
    if which == "statsd":
        p = StatsdProvider(address=statsd_address, prefix=statsd_prefix,
                           flush_interval_s=statsd_interval_s)
        p.start()
        return p
    if which == "prometheus":
        return PrometheusProvider()
    return DisabledProvider()


class _NoopInstrument:
    """True no-op: no locks, no state (reference common/metrics/disabled)."""

    def with_labels(self, *label_values: str) -> "_NoopInstrument":
        return self

    def add(self, delta: float = 1.0) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def observe(self, value: float) -> None:
        pass


class DisabledProvider(Provider):
    def __init__(self) -> None:
        self._noop = _NoopInstrument()

    def new_counter(self, opts: CounterOpts) -> Counter:
        return self._noop  # type: ignore[return-value]

    def new_gauge(self, opts: GaugeOpts) -> Gauge:
        return self._noop  # type: ignore[return-value]

    def new_histogram(self, opts: HistogramOpts) -> Histogram:
        return self._noop  # type: ignore[return-value]
