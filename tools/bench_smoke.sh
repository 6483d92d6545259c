#!/usr/bin/env bash
# Bench smoke gate: run the STAGED bench on the CPU backend (with a
# forced 8-device host platform so the bounded multichip stage runs
# even on a 1-chip box) and assert the driver-parse contract that
# rounds 3-5 kept breaking — the process must finish inside its own
# deadlines (never rc=124 from outside), every stage must print its
# own JSON line, and the LAST stdout line must be ONE compact
# aggregate object.
#
# Per-stage deadlines are enforced by the orchestrator's subprocess
# timeouts, so a stage hung inside an XLA compile is killed and
# reported instead of eating the run. First run on a fresh machine
# pays the ~3-4 min compiles (stages may report deadline_hit — still
# green: the contract is "always parseable", not "always fast"); the
# persistent compilation cache under BENCH_WARM_DIR makes later runs
# take seconds. CI budget = total deadline + grace.
set -euo pipefail
cd "$(dirname "$0")/.."

DEADLINE="${BENCH_DEADLINE_S:-540}"
STAGE_DEADLINE="${BENCH_STAGE_DEADLINE_S:-240}"
WARM_DIR="${BENCH_WARM_DIR:-${HOME}/.cache/fabric_tpu_warmkeys}"
OUT="$(mktemp)"
SIDECAR="${BENCH_SIDECAR:-$(mktemp -u)/bench_detail.json}"
mkdir -p "$(dirname "$SIDECAR")"
trap 'rm -f "$OUT"' EXIT

# the bounded multichip stage: force an 8-device CPU host platform so
# core_alldev + the scaling line run everywhere (strip any caller
# forcing first)
FLAGS=""
for f in ${XLA_FLAGS:-}; do
    case "$f" in
        --xla_force_host_platform_device_count*) ;;
        *) FLAGS="$FLAGS $f" ;;
    esac
done
FLAGS="$FLAGS --xla_force_host_platform_device_count=8"

# grace on top of the self-deadline: the orchestrator must win this
# race. set +e around the pipeline — under set -e/pipefail a failing
# bench would abort the script before the rc attribution below runs
set +e
timeout -k 30 "$((${DEADLINE%.*} + 120))" \
    env JAX_PLATFORMS=cpu \
    XLA_FLAGS="$FLAGS" BENCH_SMOKE=1 \
    BENCH_DEADLINE_S="$DEADLINE" \
    BENCH_STAGE_DEADLINE_S="$STAGE_DEADLINE" \
    BENCH_WARM_DIR="$WARM_DIR" \
    BENCH_SIDECAR="$SIDECAR" \
    python bench.py | tee "$OUT"
rc=${PIPESTATUS[0]}
set -e
if [ "$rc" -ne 0 ]; then
    echo "bench_smoke: bench.py exited rc=$rc" >&2
    exit 1
fi

python - "$OUT" "$SIDECAR" <<'EOF'
import json, os, sys

# the documented operator opt-out: with FTPU_TRACE=0 the bench skips
# the tracing A/B and emits no tail/trace fields — the round-14
# asserts below must skip with it, not fail the harness
tracing_off = os.environ.get("FTPU_TRACE") == "0"

out_path, sidecar = sys.argv[1], sys.argv[2]
lines = [ln for ln in open(out_path).read().splitlines() if ln.strip()]
assert lines, "bench printed nothing"
json_lines = [json.loads(ln) for ln in lines
              if ln.startswith("{") and ln.endswith("}")]
assert json_lines, "no JSON lines at all"

final = json_lines[-1]           # the driver's parse, exactly
assert final.get("unit") == "sigs/s", final
assert "stage" not in final, "final line must be the aggregate"
assert len(lines[-1]) < 4096, f"final line not compact: {len(lines[-1])}B"
for v in final.values():
    assert not isinstance(v, (dict, list)), \
        f"nested container on the final line: {v!r}"

# every stage reported its own line
stages = {}
for obj in json_lines[:-1]:
    assert "stage" in obj, f"non-final JSON line without stage: {obj}"
    stages[obj["stage"]] = obj
for want in ("multichip", "full_pipeline"):
    assert want in stages, f"stage {want!r} never reported: {sorted(stages)}"
assert any(s.startswith("core") or s in ("provider_e2e", "kernel_steady")
           for s in stages), f"no core stage line: {sorted(stages)}"

if final.get("deadline_hit") or any(
        o.get("deadline_hit") or o.get("timeout") for o in stages.values()):
    # round-16: salvage lines keep the device-cost facts — a deadline
    # cut AFTER prewarm must still report what the compiles cost
    for o in stages.values():
        if o.get("deadline_hit") and "prewarm_s" in (
                o.get("completed_sections") or []):
            assert "compile_s" in o, \
                f"salvage line lost compile_s: {o}"
    print("bench_smoke: a deadline was hit (cold compile?) — "
          "all lines still parseable:", sorted(stages))
    sys.exit(0)

assert final.get("value"), final

# round-10 contract: the full_pipeline stage line reports the ordering
# bottleneck (wheel-free stub harness, so it runs on every host) —
# the driver reads the trend without a human opening sidecars
fp = stages.get("full_pipeline") or {}
if "skipped" not in fp and not fp.get("order_skipped"):
    # an explicit order_skipped (env opt-out / budget exhausted) is
    # fine; fields silently missing — or an errored section — is not
    assert fp.get("order_raft_s", 0) > 0, \
        f"full_pipeline lacks order_raft_s: {fp}"
    assert fp.get("order_vs_validate", 0) > 0, \
        f"full_pipeline lacks order_vs_validate: {fp}"
    # round-14 contract: the stage line carries per-stage tail
    # latencies (means hide the tail) and the lifecycle trace file,
    # whose Chrome-trace JSON must round-trip and link one
    # transaction's trace end to end
    for f in () if tracing_off else ("order_propose_p50_s", "order_propose_p99_s",
              "order_write_p50_s", "order_write_p99_s",
              "validate_p50_s", "commit_p99_s"):
        assert fp.get(f, 0) and fp[f] > 0, \
            f"full_pipeline lacks stage tail field {f!r}: {fp}"
    if not tracing_off:
        assert fp.get("trace_file"), \
            f"full_pipeline lacks trace_file: {fp}"
        trace = json.load(open(fp["trace_file"]))
        assert trace.get("traceEvents"), "trace file has no events"
        # round-18: the export header carries the clock anchor the
        # cluster merger aligns by
        assert (trace.get("ftpu") or {}).get("clock", {}).get(
            "epoch_wall_s"), "trace file lacks the clock anchor"
        linked = set((fp.get("trace_linked_stages") or "").split(","))
        for stage in ("ingress.batch", "order.window", "order.write",
                      "commit.validate", "commit.commit"):
            assert stage in linked, \
                f"probe trace does not link {stage!r}: {sorted(linked)}"
        # round-18: the probe's trace must CROSS nodes (orderer track
        # + the commit leg's peer track), and the stage line carries
        # the e2e finality tails (or the explicit skip marker)
        tnodes = [n for n in (fp.get("trace_nodes") or "").split(",")
                  if n]
        assert len(tnodes) >= 2, \
            f"probe trace did not cross nodes: {fp.get('trace_nodes')}"
        if "e2e_skipped" not in fp:
            assert fp.get("e2e_commit_p50_s", 0) > 0, \
                f"full_pipeline lacks e2e_commit_p50_s: {fp}"
            assert fp.get("e2e_commit_p99_s", 0) > 0, \
                f"full_pipeline lacks e2e_commit_p99_s: {fp}"
        print("bench_smoke: lifecycle trace", fp["trace_file"],
              "links", sorted(linked), "across", tnodes,
              "e2e_p99", fp.get("e2e_commit_p99_s",
                                fp.get("e2e_skipped")))

# round-15 contract: the full_pipeline line carries the bounded
# leader-kill failover facts (or an explicit skip marker) — fields
# silently missing from a section that claims to have run is the
# failure mode this guards
if "skipped" not in fp and not fp.get("failover_skipped"):
    assert not fp.get("failover_error"), \
        f"failover section failed: {fp['failover_error']}"
    assert fp.get("failover_reelect_s", 0) > 0, \
        f"full_pipeline lacks failover_reelect_s: {fp}"
    assert fp.get("failover_committed", 0) > 0, \
        f"full_pipeline lacks failover_committed: {fp}"
    assert fp.get("failover_exact_once") is True, \
        f"failover exactly-once contract not reported green: {fp}"
    assert fp.get("failover_leader_changes", 0) > 0, fp
    print("bench_smoke: failover re-elected in",
          fp["failover_reelect_s"], "s;",
          fp["failover_committed"], "committed exactly once under",
          fp.get("failover_chaos_dropped"), "dropped msgs")

# round-19 contract: the full_pipeline line carries the adaptive
# control-plane facts (or an explicit skip marker) — the max
# sustainable tx/s the closed loop held inside the p99 commit SLO,
# the static-baseline comparison, and the anti-flap verdict. The
# contract HERE is "fields parse and exactly-once held" — the strong
# claims (SLO held, adaptive beats static) belong to the soak gate,
# where the run is long enough to be a fair fight.
if "skipped" not in fp and not fp.get("adaptive_skipped"):
    assert not fp.get("adaptive_error"), \
        f"adaptive section failed: {fp['adaptive_error']}"
    assert fp.get("max_sustainable_tx_s", 0) > 0, \
        f"full_pipeline lacks max_sustainable_tx_s: {fp}"
    assert fp.get("adaptive_p99_s", 0) > 0, \
        f"full_pipeline lacks adaptive_p99_s: {fp}"
    assert fp.get("adaptive_slo_target_s", 0) > 0, fp
    for f in ("adaptive_slo_held", "adaptive_beats_static",
              "adaptive_no_flap"):
        assert isinstance(fp.get(f), bool), \
            f"full_pipeline lacks adaptive verdict field {f!r}: {fp}"
    assert fp.get("adaptive_exact_once") is True, \
        f"adaptive exactly-once contract not reported green: {fp}"
    print("bench_smoke: adaptive plane sustained",
          fp["max_sustainable_tx_s"], "tx/s at p99",
          fp.get("adaptive_p99_s"), "s (SLO",
          fp.get("adaptive_slo_target_s"), "s held:",
          fp.get("adaptive_slo_held"), ") vs static",
          fp.get("adaptive_static_tx_s"), "tx/s")

# round-14 contract: the core stage measures the tracing overhead
# A/B on its steady loop and reports the verify tail
pe = stages.get("provider_e2e") or {}
if pe and "skipped" not in pe and not tracing_off:
    assert "tracing_overhead_pct" in pe, \
        f"provider_e2e lacks tracing_overhead_pct: {pe}"
    assert pe.get("verify_p50_s", 0) > 0, \
        f"provider_e2e lacks verify_p50_s: {pe}"
    assert pe.get("verify_p99_s", 0) > 0, \
        f"provider_e2e lacks verify_p99_s: {pe}"
    print("bench_smoke: tracing overhead",
          pe["tracing_overhead_pct"], "% on the steady verify loop")

# round-16 contract: the core-family stage lines carry the
# device-cost facts (compile seconds, persistent-cache hits, peak
# device memory — 0s on backends without memory_stats, but the
# FIELDS must parse), and the final aggregate carries them plus the
# perf-ledger verdict string
for name in ("core", "provider_e2e"):
    obj = stages.get(name) or {}
    if not obj or "skipped" in obj:
        continue
    for f in ("compile_s", "compile_cache_hits", "mem_peak_bytes"):
        assert f in obj, f"{name} line lacks device-cost field {f!r}: {obj}"
        assert isinstance(obj[f], (int, float)), (name, f, obj[f])
    assert obj["compile_s"] >= 0 and obj["compile_cache_hits"] >= 0, obj
assert "ledger" in final and isinstance(final["ledger"], str), \
    f"final aggregate lacks the ledger verdict: {final}"
assert not final["ledger"].startswith("unavailable"), \
    f"perf ledger failed to run: {final['ledger']}"
for f in ("compile_s", "compile_cache_hits", "mem_peak_bytes"):
    assert f in final, f"final aggregate lacks {f!r}: {final}"
print("bench_smoke: device-cost fields",
      {f: final[f] for f in ("compile_s", "compile_cache_hits",
                             "mem_peak_bytes")},
      "ledger:", final["ledger"])

# round-11 contract: the core stage's ed25519 regime reports its own
# throughput line or an explicit skip marker (env opt-out / budget) —
# fields silently missing from a line that claims to have run is the
# failure mode this guards
ed = stages.get("ed25519") or {}
if ed and "skipped" not in ed and "ed25519_skipped" not in ed:
    assert ed.get("ed25519_sigs_per_s", 0) > 0, \
        f"ed25519 stage line lacks throughput: {ed}"
    print("bench_smoke: ed25519 regime", ed.get("ed25519_sigs_per_s"),
          "sigs/s over", ed.get("ed25519_batch"))

# round-20 contract: the core stage's fused A/B reports its own line
# or an explicit skip marker. On CPU rigs the marker MUST be there
# (the interpret-mode Mosaic compile is minutes — not a serving
# configuration), so its absence means the bench silently attempted
# a device kernel on the wrong backend. A run line must carry the
# A/B fields and zero host-hashed lanes (the whole point of the
# fused tier).
fv = stages.get("fused_verify") or {}
assert fv, f"no fused_verify stage line at all: {sorted(stages)}"
if "skipped" in fv or "fused_skipped" in fv:
    skip = fv.get("skipped") or fv.get("fused_skipped")
    assert skip in ("env", "cpu", "budget"), \
        f"fused_verify skip marker unrecognized: {fv}"
    if not final.get("on_tpu"):
        assert final.get("fused_skipped") == skip, \
            f"final aggregate lost the fused skip marker: {final}"
    print("bench_smoke: fused regime skipped:", skip)
else:
    assert fv.get("fused_sigs_per_s", 0) > 0, \
        f"fused_verify stage line lacks throughput: {fv}"
    assert fv.get("fused_steady_s", 0) > 0, fv
    assert fv.get("fused_host_hashed_lanes") == 0, \
        f"fused regime hashed lanes on host: {fv}"
    assert fv.get("hash_mode") == "device-fused", fv
    assert fv.get("host_prep_s", 0) > 0, \
        f"fused A/B lacks the host-hash baseline cost: {fv}"
    print("bench_smoke: fused regime", fv.get("fused_sigs_per_s"),
          "sigs/s (vs staged x", fv.get("fused_vs_staged"),
          "), host_prep_s", fv.get("host_prep_s"))

# round-21 contract: the core stage's pairing regime (BLS12-381
# batched Miller products behind verify_aggregate) reports its sweep
# line or an explicit skip marker. On CPU rigs the marker MUST be
# there (the 381-bit Miller scan compile is not a serving
# configuration off-device); a run line must carry the steady pair
# rate AND the shared-final-exp share — the amortization fact the
# whole regime exists to book.
pr = stages.get("pairing") or {}
assert pr, f"no pairing stage line at all: {sorted(stages)}"
if "skipped" in pr or "pairing_skipped" in pr:
    skip = pr.get("skipped") or pr.get("pairing_skipped")
    assert skip in ("env", "cpu", "budget"), \
        f"pairing skip marker unrecognized: {pr}"
    if not final.get("on_tpu"):
        assert final.get("pairing_skipped") == skip, \
            f"final aggregate lost the pairing skip marker: {final}"
    print("bench_smoke: pairing regime skipped:", skip)
else:
    assert pr.get("pairing_pairs_per_s", 0) > 0, \
        f"pairing stage line lacks throughput: {pr}"
    assert pr.get("pairing_steady_s", 0) > 0, pr
    share = pr.get("pairing_final_exp_share")
    assert share is not None and 0 < share < 1, \
        f"pairing line lacks a sane final-exp share: {pr}"
    assert pr.get("pairing_sweep"), \
        f"pairing line lacks the width sweep: {pr}"
    print("bench_smoke: pairing regime",
          pr.get("pairing_pairs_per_s"), "pairs/s,",
          "final-exp share", share)

detail = json.load(open(final["sidecar"]))
core1 = (detail.get("stage_detail") or {}).get("core_1dev") or {}
stats = core1.get("provider_stats") or {}
assert stats.get("pipeline_batches", 0) > 0, "pipeline path never ran"
assert stats.get("pipeline_overlap_ratio", 0) > 0, stats
mc = stages.get("multichip") or {}
if mc.get("ok"):
    # round-13 contract: the multichip line carries the device-health
    # facts (chips benched/re-admitted, final mesh size) so the
    # driver can tell a full-fleet scaling number from a
    # degraded-mesh salvage without opening sidecars
    for f in ("device_quarantines", "device_readmits",
              "final_mesh_devices"):
        assert f in mc and mc[f] is not None, \
            f"multichip line lacks device-health field {f!r}: {mc}"
    # round-14: the all-device verify tail rides the multichip line
    for f in () if tracing_off else ("verify_p50_s", "verify_p99_s"):
        assert mc.get(f) is not None and mc[f] > 0, \
            f"multichip line lacks verify tail field {f!r}: {mc}"
    if mc["device_quarantines"]:
        assert mc.get("device_health_note") or \
            mc["final_mesh_devices"] == mc.get("devices"), \
            f"degraded multichip run without a salvage note: {mc}"
    print("bench_smoke: multichip scaling",
          mc.get("tpu_steady_scaling_x"), "x over",
          mc.get("devices"), "devices; device_health",
          {f: mc[f] for f in ("device_quarantines", "device_readmits",
                              "final_mesh_devices")})
print("bench_smoke: ok —",
      {k: stats[k] for k in ("pipeline_batches", "pipeline_chunks",
                             "pipeline_overlap_ratio")},
      "value:", final.get("value"))
EOF
echo "bench_smoke: green"
