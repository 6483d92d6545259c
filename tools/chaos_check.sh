#!/usr/bin/env bash
# Chaos gate: re-run the bccsp / raft / deliver / onboarding test
# subsets with fault points ARMED via env (fabric_tpu/common/faults.py
# parses FTPU_FAULTS at interpreter start; the conftest fixture
# re-applies it per test).
#
# The claim under test: armed faults change WHICH path serves — never
# verdicts, never liveness. Tests that pin device-path internals clear
# the ambient arming themselves; everything else must stay green with
# errors and stalls injected at every named fault point.
#
# Spec grammar: point=mode[:count][:delay_s][:arg], mode in
# {error, delay}; the 4th field targets a check() argument (the
# per-device points pass the full-mesh chip index).
# Usage: chaos_check.sh [all|bccsp|raft|deliver|onboarding|commit|shard|order|schemes|overload|adaptive|mesh-health|tracing|net|devicecost|e2e-trace|pairing|static]
set -euo pipefail
cd "$(dirname "$0")/.."

PYTEST=(env JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow'
        -p no:cacheprovider -p no:randomly)

run() {
    local faults="$1"; shift
    echo "== chaos pass: FTPU_FAULTS='${faults}' $*"
    FTPU_FAULTS="$faults" "${PYTEST[@]}" "$@"
}

bccsp() {
    # transient device errors at every dispatch/compile/persist point —
    # breaker + sw fallback keep every verdict bit-identical
    run "tpu.dispatch=error:2;tpu.compile=error:1;tpu.table_persist=error:1" \
        tests/test_chaos.py tests/test_bucket_floor.py
    # stalls instead of errors
    run "tpu.dispatch=delay:2:0.05" \
        tests/test_chaos.py -k "Degradation or FaultRegistry"
}

raft() {
    # dropped step messages per test — elections/replication must
    # still converge (core tests drive the protocol; chain tests cover
    # the armed fault point)
    run "raft.step=error:3" tests/test_raft.py tests/test_chaos.py \
        -k Raft
}

deliver() {
    # torn streams force the reconnect/backoff path
    run "deliver.stream=error:2" tests/test_chaos.py -k Deliver
}

onboarding() {
    # the chain-replication fault points — dead sources at every pull,
    # corrupted spans at every verify, failing commits — catch-up must
    # still converge with nothing forged committed
    run "cluster.pull=error:2" tests/test_onboarding.py
    run "cluster.verify=error:2" tests/test_onboarding.py \
        -k "Replicator or Chaos"
    run "onboarding.commit=error:1" tests/test_onboarding.py \
        -k "Replicator or Chaos or Bootstrap"
    run "cluster.pull=delay:3:0.05;onboarding.commit=error:1" \
        tests/test_onboarding.py -k "Chaos"
}

commit() {
    # pipelined block intake under fire: stage-A faults demote blocks
    # to the sequential path, barrier faults must never corrupt —
    # codes, filters and commit hashes stay bit-identical throughout
    # only the feeder-path tests (GossipState/Deliver) keep the env
    # arming live — the parity/fault tests pin exact stats and clear
    # it, so selecting them here would make the pass vacuous
    run "commit.validate_ahead=error:2" tests/test_commit_pipeline.py
    run "commit.barrier=error:1" tests/test_commit_pipeline.py \
        -k "GossipState or Deliver"
    run "commit.validate_ahead=delay:3:0.05;commit.barrier=delay:2:0.05" \
        tests/test_commit_pipeline.py -k "Parity or GossipState or Deliver"
}

shard() {
    # sharded dispatch under fire: tpu.dispatch fires once per sharded
    # batch exactly like the single-chip path; breaker fallback must
    # keep every accept/reject bitmap bit-identical. The parity tests
    # pin stats and clear ambient arming; the multi-process case
    # inherits FTPU_FAULTS into its child (faulted sharded dispatches
    # serve sw — parity still binds), and TestShardedFaults arms the
    # point explicitly either way.
    run "tpu.dispatch=error:2" tests/test_shard_verify.py
    run "tpu.dispatch=delay:2:0.05" tests/test_shard_verify.py \
        -k "Faults or MultiProcess"
}

schemes() {
    # the round-11 scheme router under fire: armed tpu.ed25519 /
    # tpu.bls_aggregate faults must serve every lane on the host
    # reference path with BIT-IDENTICAL accept/reject bitmaps, then
    # re-enter the device path through the breaker. Router tests that
    # pin dispatch counts clear the ambient arming themselves.
    run "tpu.ed25519=error:2;tpu.bls_aggregate=error:2" \
        tests/test_scheme_router.py
    run "tpu.ed25519=delay:2:0.05;tpu.dispatch=error:1" \
        tests/test_scheme_router.py
}

order() {
    # the round-10 ordering pipeline under fire: failing batched
    # proposes demote the admission window to sequential per-block
    # proposes, dropped raft steps are healed by retransmission —
    # block streams stay bit-identical and no envelope is lost
    # (raft + broadcast ingest subsets, the new fault points armed)
    run "order.propose=error:2" tests/test_order_pipeline.py \
        tests/test_broadcast_batch.py
    run "order.propose=delay:2:0.02;raft.step=error:3" \
        tests/test_order_pipeline.py
    run "raft.step=error:2;order.propose=error:1" tests/test_raft.py \
        tests/test_chaos.py -k "Raft"
}

mesh_health() {
    # the round-13 elastic mesh under fire: chip 3 of the 8-device
    # conftest mesh killed / stalled mid-run — the provider must
    # quarantine exactly that chip, rebuild a smaller mesh over the
    # survivors (never dropping to full sw while healthy chips
    # remain), keep every accept/reject bitmap bit-identical to the
    # sw oracle, and grow the mesh back after a successful probe.
    # Device-health tests arm their own targeted faults on top of
    # (or after clearing) the ambient env arming; the shard subset
    # re-runs with a chip lost to prove the pre-elastic contracts
    # hold on a degraded mesh too.
    run "tpu.device_lost=error:1::3" \
        tests/test_device_health.py tests/test_shard_verify.py
    run "tpu.device_straggler=delay:2:0.05:2" \
        tests/test_device_health.py
    run "tpu.device_lost=error:2::5;tpu.dispatch=error:1" \
        tests/test_device_health.py tests/test_chaos.py \
        -k "Degradation or DeviceHealth or Elastic"
}

overload() {
    # the round-12 overload layer under fire: armed propose stalls +
    # device faults while the shed/deadline/backpressure semantics
    # are pinned — a shed must stay a clean retryable refusal, never
    # a half-applied state, whichever path serves
    run "order.propose=delay::0.02;tpu.dispatch=error:2" \
        tests/test_overload.py
    run "raft.step=error:3;order.propose=error:1" \
        tests/test_overload.py -k "Shed or Chain or Broadcast"
}

adaptive() {
    # the round-19 control plane under fire: armed propose stalls and
    # dropped raft steps perturb every signal the controller reads
    # (burn, sheds, depths) while the hysteresis/anti-flap/bounds
    # contract is pinned — noisy signals may change WHEN it moves,
    # never let it flap or leave a knob's declared bounds; the
    # proposal gate must keep shedding as clean retryable refusals
    run "order.propose=delay::0.02;raft.step=error:3" \
        tests/test_adaptive.py
    run "tpu.dispatch=error:2;order.propose=error:1" \
        tests/test_adaptive.py tests/test_overload.py -k \
        "Adaptive or Hysteresis or AntiFlap or Bounds or Gate or Shed"
}

tracing() {
    # the round-14 lifecycle tracer under fire: armed dispatch /
    # propose / per-device faults must surface as ERROR-STATUS spans
    # in the flight recorder, the auto-dumped postmortem file must
    # stay json.loads-parseable, and the Chrome-trace export must
    # round-trip — while every verdict/liveness contract of the
    # traced paths holds (the tests assert both)
    run "tpu.dispatch=error:1;order.propose=error:1" \
        tests/test_tracing.py
    run "tpu.device_lost=error:1::3;tpu.dispatch=delay:1:0.02" \
        tests/test_tracing.py
}

net() {
    # the round-15 network-chaos layer under its OWN fault points:
    # ambient net.* armings ride every NetChaos engine the suite
    # builds — drops/dups/reorders on live consensus links must
    # change delivery, never verdicts or convergence (tests that pin
    # exact schedules clear the ambient arming themselves). The raft/
    # order/gossip suites run alongside: engine-less tests prove the
    # armings are inert where no chaos transport exists.
    run "net.drop=error:4;net.dup=error:2" \
        tests/test_net_chaos.py tests/test_gossip.py
    run "net.reorder=error:3:4;net.delay=delay:2:0.02" \
        tests/test_net_chaos.py -k "Cluster or Parity or Policies or Gossip"
    run "net.partition=error:1:0.4:orderer0.example.com:7050;raft.step=error:2" \
        tests/test_net_chaos.py tests/test_raft.py \
        tests/test_order_pipeline.py
    # the new durable-seam points in ERROR mode: a failing block
    # write is a sticky stage failure -> demote + WAL replay, a
    # failing WAL append demotes / drops a block loudly — never a
    # wedge. Only the suites written for deposed-leader semantics run
    # armed (core-internals tests clear the ambient arming; stream-
    # completeness suites would read a dropped block as a failure).
    run "raft.wal_append=error:2;order.block_write=error:1" \
        tests/test_net_chaos.py \
        -k "DurableSeam or Policies or FaultGrammar or Unreachable or Rpc or Hardening"
}

devicecost() {
    # the round-16 device-cost layer under fire: armed tpu.compile
    # faults must surface as compile_failures counters and
    # error-status tpu.compile spans (the test suite pins both) while
    # the breaker/sw-fallback keeps every verdict bit-identical —
    # a failing compile degrades the serving path, never the answers
    run "tpu.compile=error:2" \
        tests/test_devicecost.py tests/test_chaos.py
    run "tpu.compile=error:1;tpu.dispatch=error:1" \
        tests/test_devicecost.py \
        -k "CompileSeam or ProviderJitSeam"
    run "tpu.compile=delay:1:0.05" \
        tests/test_devicecost.py tests/test_chaos.py \
        -k "Degradation or CompileSeam or ProviderJitSeam"
}

e2e_trace() {
    # the round-18 cross-node tracing layer under fire: net.drop /
    # net.reorder chaos on live links plus an armed order.propose —
    # wire carriers must SURVIVE (dup/reorder forward without
    # re-parenting, drops just lose hops), armed faults must surface
    # as error-status spans, and the merged cluster trace + e2e/SLO
    # contracts must hold throughout
    run "net.drop=error:3;net.reorder=error:2" \
        tests/test_cluster_trace.py
    run "net.dup=error:2;order.propose=error:1" \
        tests/test_cluster_trace.py \
        -k "Carrier or Chaos or Cluster or Resume"
}

pairing() {
    # the round-21 BLS12-381 pairing engine under fire: armed
    # tpu.bls_aggregate faults over the device-kernel suite must
    # serve every aggregate verdict on the host reference path
    # BIT-IDENTICALLY, then re-enter through the breaker; kernel
    # math tests prove the arming is inert below the provider seam.
    run "tpu.bls_aggregate=error:2" tests/test_bls12_381_device.py \
        tests/test_scheme_router.py -k "Aggregate or Bls or BLS"
    run "tpu.bls_aggregate=delay:1:0.05;tpu.compile=error:1" \
        tests/test_bls12_381_device.py
}

static() {
    # the round-8 static gate: project-invariant lint + metrics-doc
    # drift + the lock-order-sanitizer-armed threaded subset
    ./tools/static_check.sh
}

case "${1:-all}" in
    bccsp) bccsp ;;
    raft) raft ;;
    deliver) deliver ;;
    onboarding) onboarding ;;
    commit) commit ;;
    shard) shard ;;
    order) order ;;
    schemes) schemes ;;
    overload) overload ;;
    adaptive) adaptive ;;
    mesh-health) mesh_health ;;
    tracing) tracing ;;
    net) net ;;
    devicecost) devicecost ;;
    e2e-trace) e2e_trace ;;
    pairing) pairing ;;
    static) static ;;
    all) bccsp; raft; deliver; onboarding; commit; shard; order;
         schemes; overload; adaptive; mesh_health; tracing; net; devicecost;
         e2e_trace; pairing; static ;;
    *) echo "unknown subset: $1" >&2; exit 2 ;;
esac

echo "chaos_check: all passes green"
