"""Headline benchmark: block-validation signature-verify throughput
THROUGH THE PRODUCT SEAM.

Reproduces BASELINE.json config 2/5 shape: a 10k-tx block with a 2-of-3
endorsement policy = 2 endorsement signatures + 1 creator signature per
tx → 30k independent ECDSA-P256 verifications over SHA-256 digests,
signed by 3 distinct org keys — the structural reality of a Fabric block.

Round-3 change (per the round-2 verdict): the measured thing IS the
shipped thing. The provider under test is constructed by the factory
from a core.yaml-style `BCCSP: {Default: TPU}` mapping — the same
object `peer node start` builds — and the workload flows through
`TPUProvider.verify_batch`. On a TPU backend that resolves to the
16/16-bit comb with per-key-set cached Q tables and the Pallas VMEM
tree kernel (fabric_tpu/ops/ptree.py).

Baseline ("bccsp/sw"): the reference verifies each signature on CPU in
a worker pool of size NumCPU (`core/peer/peer.go:501`,
`core/committer/txvalidator/v20/validator.go:180-237`). We measure
OpenSSL (`cryptography`) single-thread verify latency — the same
asm-optimized class as Go's crypto/ecdsa — and credit the baseline with
IDEAL linear scaling across every CPU core of this box.

Two TPU numbers are reported:
  * `value` / `tpu_steady_s` — the provider's OWN compiled pipeline and
    cached tables, timed on device-resident operands (the kernel
    number must not include host→device transfer; the end-to-end
    number below does). This is the same jitted callable and
    the same table objects `verify_batch` dispatches to — verified by
    identity, not similarity.
  * `provider_verify_batch_sigs_per_s` — honest wall clock of
    `TPUProvider.verify_batch(items)` end to end (host DER parse in
    C++, limb packing, per-device transfers, device, readback).

Round-9 structure: the default invocation is a jax-free STAGED
orchestrator — core (Devices=1), core (Devices=all), multichip
scaling, full_pipeline, each a child process under a hard parent-side
subprocess timeout, each printing its own JSON line as it finishes.
The LAST stdout line is always ONE compact aggregate object (the
driver's parse); full detail goes to the sidecar file, including the
measured device-scaling curve.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

# Smoke mode: a bounded, driver-parseable dry run — small block, small
# chunk, heavyweight sections off by default, one bounded-prewarm
# compile, and a HARD self-deadline (watchdog thread) so an external
# timeout (the round-5 rc=124) can never kill the process before it
# prints its one final JSON line.
#
# Bounded is the DEFAULT for a plain `python bench.py` (every round-5
# BENCH_r*.json came back rc=124/parsed:null from the unbounded run):
# FTPU_BENCH_FULL=1 opts into the full unbounded benchmark, and an
# explicit BENCH_SMOKE=0/1 overrides both.
_FULL = os.environ.get("FTPU_BENCH_FULL", "0") == "1"
SMOKE = os.environ.get("BENCH_SMOKE", "0" if _FULL else "1") == "1"

BLOCK_TXS = int(os.environ.get("BENCH_TXS", "512" if SMOKE else "10240"))
SIGS_PER_TX = 3
NKEYS = 3
MSG_LEN = 256          # typical proposal-response payload scale
CPU_SAMPLE = 60 if SMOKE else 300
TPU_ITERS = 3 if SMOKE else 5
CHUNK = int(os.environ.get("BENCH_CHUNK", "512" if SMOKE else "32768"))
# seconds from process start to the watchdog's forced final line;
# 0 disables. Round-6 change: FULL runs are BOUNDED too (BENCH_r05 /
# MULTICHIP_r05 went rc=124 with nothing printed) — an explicit
# BENCH_DEADLINE_S=0 is now the only unbounded mode.
DEADLINE_S = float(os.environ.get("BENCH_DEADLINE_S",
                                  "540" if SMOKE else "3600"))
# per-stage hard deadline: the orchestrator kills a stage child that
# exceeds it (works even when the child hangs inside a C extension or
# an XLA compile, which no in-process watchdog can preempt)
STAGE_DEADLINE_S = float(os.environ.get("BENCH_STAGE_DEADLINE_S",
                                        "240" if SMOKE else "1500"))
SIDECAR = os.environ.get("BENCH_SIDECAR", "bench_detail.json")

_T0 = time.monotonic()
_FINAL_EMITTED = threading.Event()
_FINAL_LOCK = threading.Lock()   # atomic test-and-set: the watchdog
#                                  and the normal exit path race here
_PARTIAL: dict = {}    # sections the watchdog can salvage


def _elapsed() -> float:
    return time.monotonic() - _T0


def _remaining() -> float:
    return float("inf") if not DEADLINE_S else DEADLINE_S - _elapsed()


def write_sidecar(detail: dict) -> str | None:
    """Full per-section detail goes to a JSON sidecar FILE; the final
    stdout line stays one compact object (the round-3 oversized tail
    made the driver's parse fail)."""
    try:
        tmp = SIDECAR + ".tmp"
        with open(tmp, "w") as f:
            json.dump(detail, f, indent=1)
        os.replace(tmp, SIDECAR)
        return SIDECAR
    except Exception:           # noqa: BLE001
        return None


def final_line(result: dict, detail: dict | None = None) -> str:
    """Build THE final stdout line: compact, flat-ish, no per-chunk
    arrays (those live in the sidecar). Exactly one of these is
    printed per process — the watchdog and the normal exit path race
    through _FINAL_EMITTED."""
    out = dict(result)
    if detail is not None:
        side = write_sidecar(detail)
        if side:
            out["sidecar"] = side
        stats = detail.get("provider_stats") or {}
        for k in ("pipeline_overlap_ratio", "pipeline_batches",
                  "pipeline_host_s", "pipeline_device_s"):
            if k in stats:
                out[k] = stats[k]
    out["smoke"] = SMOKE
    out["elapsed_s"] = round(_elapsed(), 1)
    return json.dumps(out, separators=(",", ":"))


def emit_final(result: dict, detail: dict | None = None) -> None:
    with _FINAL_LOCK:
        if _FINAL_EMITTED.is_set():
            return
        _FINAL_EMITTED.set()
    print(final_line(result, detail), flush=True)


def _start_watchdog() -> None:
    """At DEADLINE_S the bench prints whatever it has as its one final
    JSON line and exits 0 — a self-imposed deadline the driver's
    timeout never beats."""
    if not DEADLINE_S:
        return

    def fire():
        time.sleep(max(0.0, DEADLINE_S - _elapsed()))
        if _FINAL_EMITTED.is_set():
            return
        # reap live stage/restart children FIRST: os._exit alone would
        # orphan a bench child that still owns the single-owner TPU
        # chip, wedging the driver's next claim of the device
        _kill_children()
        trace_dump = None
        try:
            # the flight recorder is the rc=124 postmortem: dump what
            # the process was doing when the deadline fired
            from fabric_tpu.common import tracing
            trace_dump = tracing.dump("bench_watchdog")
        except Exception:       # noqa: BLE001
            pass
        res = {
            "metric": "block-validation sig-verify throughput "
                      "(smoke, self-deadline hit)",
            "value": _PARTIAL.get("value"),
            "unit": "sigs/s",
            "deadline_s": DEADLINE_S,
            "deadline_hit": True,
            "trace_dump": trace_dump,
            "completed_sections": sorted(_PARTIAL),
        }
        if _PARTIAL.get("stage"):
            # a stage child's salvage line keeps its stage tag (and
            # the device-count facts the orchestrator gates on) so the
            # relay still emits a line and multichip still runs
            res["stage"] = _PARTIAL["stage"]
            res["devices"] = _PARTIAL.get("devices")
            res["local_devices"] = _PARTIAL.get("local_devices")
            res["mesh_devices"] = _PARTIAL.get("mesh_devices")
            # round-14 salvage: the verify tail + measured tracing
            # overhead survive a deadline-cut core stage, so the
            # orchestrator's multichip line still carries them;
            # round-16 salvage: so do the device-cost facts (a
            # deadline hit DURING a cold compile is exactly when
            # compile_s matters)
            for k in ("verify_p50_s", "verify_p99_s",
                      "tracing_overhead_pct", "compile_s",
                      "compile_cache_hits", "mem_peak_bytes"):
                if k in _PARTIAL:
                    res[k] = _PARTIAL[k]
        emit_final(res, dict(_PARTIAL))
        os._exit(0)

    threading.Thread(target=fire, name="bench-deadline",
                     daemon=True).start()


# live children (stage/restart subprocesses) the deadline watchdog
# must reap before exiting
_CHILDREN_LOCK = threading.Lock()
_CHILDREN: set = set()


def _bounded_child(cmd, timeout, env=None):
    """`subprocess.run(capture_output=True, text=True)` twin that
    registers the child so the deadline watchdog can kill it. Returns
    (rc, stdout, stderr); on timeout kills the child and raises
    `subprocess.TimeoutExpired` carrying whatever stdout it printed."""
    import subprocess
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, env=env)
    with _CHILDREN_LOCK:
        _CHILDREN.add(p)
    try:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            raise subprocess.TimeoutExpired(cmd, timeout, output=out,
                                            stderr=err)
        return p.returncode, out, err
    finally:
        with _CHILDREN_LOCK:
            _CHILDREN.discard(p)


def _kill_children() -> None:
    with _CHILDREN_LOCK:
        live = list(_CHILDREN)
    for p in live:
        try:
            p.kill()
        except OSError:
            pass


def _ledger_verdict(candidate: dict) -> str:
    """tools/perf_ledger.verdict over the round history in this
    file's directory. Loaded by path (tools/ is not a package);
    any failure degrades to an 'unavailable:' marker — the ledger
    must never break the bench's final-line contract."""
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "ftpu_perf_ledger",
            os.path.join(REPO_ROOT, "tools", "perf_ledger.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.verdict(candidate, REPO_ROOT)
    except Exception as e:          # noqa: BLE001
        return f"unavailable:{type(e).__name__}"


def _devicecost_mod():
    """Lazy fabric_tpu.common.devicecost (round 16): jax-free to
    import, but the orchestrator stays import-light until a stage
    needs the memory/compile readings."""
    from fabric_tpu.common import devicecost
    return devicecost


def _have_openssl() -> bool:
    try:
        from fabric_tpu.bccsp._crypto_compat import HAVE_CRYPTOGRAPHY
        return bool(HAVE_CRYPTOGRAPHY)
    except Exception:           # noqa: BLE001
        try:
            import cryptography  # noqa: F401
            return True
        except ImportError:
            return False


def bench_idemix(prov) -> dict:
    """BASELINE config 4: idemix credential verification.

    The measurable surface is `IdemixMSP.validate_credentials_batch`
    (reference analog: `msp/idemix.go` credential verify via vendored
    IBM/idemix BN254 pairing checks). BLS-issued credentials resolve to
    ONE batched pairing-product dispatch (`csp.bls_verify_batch` →
    `pairing_check_batch` → device Miller loop + final exp); the host
    baseline is the exact integer pairing (`ops/bn254_ref`), the same
    arithmetic class as the reference's pure-Go IBM/mathlib.
    """
    import time as t

    from fabric_tpu.msp import msp as mapi
    from fabric_tpu.msp.idemix import (
        IdemixIssuer, IdemixMSP, idemix_msp_config,
    )

    n = int(os.environ.get("BENCH_IDEMIX_N", "256"))
    scheme = os.environ.get("BENCH_IDEMIX_SCHEME", "ps")
    issuer = IdemixIssuer(prov, scheme=scheme)
    msp = IdemixMSP(prov)
    msp.setup(idemix_msp_config("AnonZK", issuer))
    creds = issuer.issue("research", mapi.MSPRole.MEMBER, count=n)
    msp.add_credentials(creds)
    # every issued credential as a freshly-deserialized identity (the
    # "ps" default carries a zero-knowledge presentation per identity:
    # host Schnorr + ONE device pairing-product lane each)
    idents = []
    with msp._lock:
        signers = list(msp._signers)
    for s in signers:
        idents.append(msp.deserialize_identity(s.serialize()))

    t0 = t.perf_counter()
    ok = msp.validate_credentials_batch(idents)
    warm_s = t.perf_counter() - t0
    if not all(ok):
        raise RuntimeError("valid idemix credentials rejected")
    times = []
    for _ in range(3):
        t0 = t.perf_counter()
        ok = msp.validate_credentials_batch(idents)
        times.append(t.perf_counter() - t0)
    steady = min(times)

    # host baseline: exact integer pairing on a small sample
    from fabric_tpu.bccsp.sw import SWProvider
    sw_msp = IdemixMSP(SWProvider())
    sw_msp.setup(idemix_msp_config("AnonZK", issuer))
    sample = idents[:4]
    t0 = t.perf_counter()
    sample_ok = sw_msp.validate_credentials_batch(sample)
    host_per_cred = (t.perf_counter() - t0) / len(sample)
    if not all(sample_ok):
        raise RuntimeError("host pairing rejected valid credentials")
    ncpu = os.cpu_count() or 1
    host_ideal = ncpu / host_per_cred
    return {
        "creds": n,
        "scheme": scheme,
        "creds_per_s": round(n / steady, 1),
        "warm_s": round(warm_s, 2),
        "steady_s": round(steady, 4),
        "steady_phase_s": getattr(msp, "last_batch_timings", None),
        "host_single_thread_ms_per_cred":
            round(host_per_cred * 1e3, 1),
        "host_ideal_creds_per_s": round(host_ideal, 1),
        "vs_host_ideal": round((n / steady) / host_ideal, 2),
        "surface": "IdemixMSP.validate_credentials_batch -> "
                   "zero-knowledge PS presentations (host Schnorr + "
                   "BN254 pairing product on device)" if scheme == "ps"
                   else "IdemixMSP.validate_credentials_batch -> "
                   "bls_verify_batch (BN254 pairing product on "
                   "device)",
    }


def bench_blocksig(prov) -> dict:
    """BASELINE config 5: gossip identity + orderer block-signature
    verify at a simulated 10k tx/s load.

    At 10k tx/s with 500-tx blocks the peer sees 20 blocks/s, each
    needing ~1 orderer block-metadata signature plus a handful of
    gossip message-auth verifies — latency-critical 3-5 sig batches,
    NOT throughput batches. By design these ride the provider's small-
    batch fast path (CPU, no device round-trip: a 4-sig set must not
    wait on a 32k-lane pipeline — SURVEY §7 'a 3-sig policy on a 1-tx
    block must not wait for a batch'). Reported: per-set latency and
    the fraction of one core the whole 10k tx/s control-plane load
    consumes, alongside the device pipeline the data-plane (config
    2/3) uses.
    """
    import time as t

    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from fabric_tpu.bccsp import VerifyItem, utils as butils
    from fabric_tpu.bccsp.bccsp import ECDSAPublicKeyImportOpts

    sigs_per_set = 4          # 1 block sig + 3 gossip identity checks
    sets = 200
    priv = ec.generate_private_key(ec.SECP256R1())
    key = prov.key_import(priv.public_key(), ECDSAPublicKeyImportOpts())
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(sets):
        items = []
        for _ in range(sigs_per_set):
            m = rng.bytes(96)
            r, s = decode_dss_signature(
                priv.sign(m, ec.ECDSA(hashes.SHA256())))
            items.append(VerifyItem(
                key=key,
                signature=butils.marshal_signature(
                    r, butils.to_low_s(s)),
                message=m))
        batches.append(items)
    # warm
    warm_ok = prov.verify_batch(batches[0])
    if not all(warm_ok):
        raise RuntimeError("valid warm-up set rejected")
    lat = []
    t_all0 = t.perf_counter()
    for items in batches:
        t0 = t.perf_counter()
        out = prov.verify_batch(items)
        lat.append(t.perf_counter() - t0)
        if not all(out):
            raise RuntimeError("valid block-sig set rejected")
    total = t.perf_counter() - t_all0
    lat.sort()
    sets_per_s = sets / total
    blocks_per_s_at_10k = 10000 / 500.0

    # aggregated mode: the same 200 sets verified as ONE windowed
    # batch — the shape peer/mcs.py uses for gossip state-transfer
    # backlogs (many payload blocks' signatures at once). 800 lanes
    # clear MinBatch, so THIS blocksig configuration exercises the
    # device pipeline (round-3 verdict #7/#9).
    all_items = [it for items in batches for it in items]
    agg_warm = prov.verify_batch(all_items)
    if not all(agg_warm):
        raise RuntimeError("valid aggregated window rejected")
    agg_times = []
    for _ in range(3):
        t0 = t.perf_counter()
        prov.verify_batch(all_items)
        agg_times.append(t.perf_counter() - t0)
    agg_s = min(agg_times)
    return {
        "sigs_per_set": sigs_per_set,
        "sets": sets,
        "p50_latency_us": round(lat[len(lat) // 2] * 1e6, 1),
        "p99_latency_us": round(lat[int(len(lat) * 0.99) - 1] * 1e6,
                                1),
        "sets_per_s": round(sets_per_s, 1),
        "core_fraction_at_10k_tx_s":
            round(blocks_per_s_at_10k / sets_per_s, 4),
        "path": "small-batch fast path (latency-critical sets bypass "
                "the device pipeline by design)",
        "aggregated": {
            "window_sigs": len(all_items),
            "window_s": round(agg_s, 4),
            "sigs_per_s": round(len(all_items) / agg_s, 1),
            "amortized_us_per_set":
                round(agg_s / sets * 1e6, 1),
            "path": "device pipeline (windowed multi-set batch, the "
                    "gossip state-transfer backlog shape)",
        },
    }


def _signed_items(prov, privs, keys, n, rng, msg_len=96):
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from fabric_tpu.bccsp import VerifyItem, utils as butils

    items = []
    for i in range(n):
        m = rng.bytes(msg_len)
        k = i % len(privs)
        r, s = decode_dss_signature(
            privs[k].sign(m, ec.ECDSA(hashes.SHA256())))
        items.append(VerifyItem(
            key=keys[k],
            signature=butils.marshal_signature(r, butils.to_low_s(s)),
            message=m))
    return items


def bench_multikeyset() -> dict:
    """Round-3 verdict #5: the many-key-set regime. 8 channels' worth
    of distinct 4-key org sets interleave batches through ONE provider
    whose TableCacheMB holds a single 16-bit table — the adaptive
    policy must pin the resident set and serve the overflow on the
    8-bit path, with NO eviction thrash and the decision visible in
    provider stats (bccsp_q16_adaptive_skips)."""
    import time as t

    from cryptography.hazmat.primitives.asymmetric import ec

    from fabric_tpu.bccsp import factory
    from fabric_tpu.bccsp.bccsp import ECDSAPublicKeyImportOpts

    nsets = int(os.environ.get("BENCH_MK_SETS", "8"))
    per_batch = int(os.environ.get("BENCH_MK_BATCH", "4096"))
    rounds = int(os.environ.get("BENCH_MK_ROUNDS", "2"))
    prov = factory.new_bccsp(factory.FactoryOpts.from_config({
        "Default": "TPU",
        # one K=4 16-bit table is ~2 GB: budget fits exactly one set
        "TPU": {"MinBatch": 16, "TableCacheMB": 2560,
                "Chunk": CHUNK},
    }))
    rng = np.random.default_rng(99)
    sets = []
    for _ in range(nsets):
        privs = [ec.generate_private_key(ec.SECP256R1())
                 for _ in range(4)]
        keys = [prov.key_import(p.public_key(),
                                ECDSAPublicKeyImportOpts())
                for p in privs]
        sets.append(_signed_items(prov, privs, keys, per_batch, rng))
    # warm: first round pays the single q16 build + any compiles
    t0 = t.perf_counter()
    for items in sets:
        if not all(prov.verify_batch(items)):
            raise RuntimeError("valid multikeyset batch rejected")
    warm_s = t.perf_counter() - t0
    stats_after_warm = dict(prov.stats)
    t0 = t.perf_counter()
    n_done = 0
    for _ in range(rounds):
        for items in sets:
            out = prov.verify_batch(items)
            if not all(out):
                raise RuntimeError("valid multikeyset batch rejected")
            n_done += len(items)
    steady_s = t.perf_counter() - t0
    d = {k: prov.stats[k] - stats_after_warm[k]
         for k in ("q16_builds", "q16_evictions",
                   "q16_adaptive_skips")}
    return {
        "key_sets": nsets, "keys_per_set": 4,
        "sigs_per_batch": per_batch, "rounds": rounds,
        "warm_s": round(warm_s, 1),
        "steady_sigs_per_s": round(n_done / steady_s, 1),
        "q16_builds_warm": stats_after_warm["q16_builds"],
        "steady_deltas": d,
        "no_thrash": d["q16_builds"] == 0 and d["q16_evictions"] == 0,
        "policy": "adaptive: resident 16-bit set pinned, overflow "
                  "sets on the 8-bit path (TableCacheMB=2560)",
    }


def bench_crossover(prov) -> dict:
    """Round-3 verdict #9: sw-vs-device latency at small batch sizes,
    justifying (or retuning) MinBatch. The device side reuses the
    provider's cached tables/pipelines; each batch size pays one
    compile on first touch (persistent-cached across runs)."""
    import time as t

    from cryptography.hazmat.primitives.asymmetric import ec

    from fabric_tpu.bccsp.bccsp import ECDSAPublicKeyImportOpts

    sizes = [int(x) for x in os.environ.get(
        "BENCH_XOVER_SIZES", "4,16,64,256").split(",")]
    reps = int(os.environ.get("BENCH_XOVER_REPS", "15"))
    rng = np.random.default_rng(17)
    privs = [ec.generate_private_key(ec.SECP256R1()) for _ in range(3)]
    keys = [prov.key_import(p.public_key(), ECDSAPublicKeyImportOpts())
            for p in privs]
    out = {"sizes": {}, "min_batch": prov._min_batch}
    saved = prov._min_batch
    try:
        for n in sizes:
            items = _signed_items(prov, privs, keys, n, rng)
            prov._min_batch = 1 << 30     # force the sw path
            if not all(prov.verify_batch(items)):
                raise RuntimeError("sw crossover batch rejected")
            ts = []
            for _ in range(reps):
                t0 = t.perf_counter()
                prov.verify_batch(items)
                ts.append(t.perf_counter() - t0)
            sw_us = sorted(ts)[len(ts) // 2] * 1e6
            prov._min_batch = 1           # force the device path
            if not all(prov.verify_batch(items)):   # warm/compile
                raise RuntimeError("device crossover batch rejected")
            ts = []
            for _ in range(reps):
                t0 = t.perf_counter()
                prov.verify_batch(items)
                ts.append(t.perf_counter() - t0)
            dev_us = sorted(ts)[len(ts) // 2] * 1e6
            out["sizes"][str(n)] = {
                "sw_us": round(sw_us, 1),
                "device_us": round(dev_us, 1),
                "device_wins": bool(dev_us < sw_us),
            }
    finally:
        prov._min_batch = saved
    wins = [int(n) for n, v in out["sizes"].items()
            if v["device_wins"]]
    out["smallest_device_win"] = min(wins) if wins else None
    return out


BENCH_KEYS_PEM = "bench_keys.pem"


def _apply_platform():
    """Honor an explicit JAX_PLATFORMS through jax.config (which wins
    over anything else that picks a platform, as long as it runs
    before backend init). No-op when unset (runs on the chip)."""
    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)


def _load_bench_privs(warm_dir):
    """Bench-only org signing keys persisted beside the warm tables so
    a later process (the restart child, the next driver run) measures
    against the SAME key set the tables were built for."""
    from cryptography.hazmat.primitives import serialization
    path = os.path.join(warm_dir, BENCH_KEYS_PEM)
    try:
        blob = open(path, "rb").read()
    except FileNotFoundError:
        return None
    privs = []
    for chunk in blob.split(b"-----END PRIVATE KEY-----")[:-1]:
        privs.append(serialization.load_pem_private_key(
            chunk + b"-----END PRIVATE KEY-----", None))
    return privs or None


def _save_bench_privs(warm_dir, privs):
    from cryptography.hazmat.primitives import serialization
    os.makedirs(warm_dir, exist_ok=True)
    path = os.path.join(warm_dir, BENCH_KEYS_PEM)
    blob = b"".join(
        p.private_bytes(serialization.Encoding.PEM,
                        serialization.PrivateFormat.PKCS8,
                        serialization.NoEncryption())
        for p in privs)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


def _signed_batch(prov, privs, n, rng):
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from fabric_tpu.bccsp import VerifyItem, utils as butils
    from fabric_tpu.bccsp.bccsp import ECDSAPublicKeyImportOpts
    keys = [prov.key_import(p.public_key(), ECDSAPublicKeyImportOpts())
            for p in privs]
    items = []
    for i in range(n):
        m = rng.bytes(MSG_LEN)
        der = privs[i % len(privs)].sign(m, ec.ECDSA(hashes.SHA256()))
        r, s = decode_dss_signature(der)
        items.append(VerifyItem(
            key=keys[i % len(keys)],
            signature=butils.marshal_signature(r, butils.to_low_s(s)),
            message=m))
    return items


def _restart_child(mode, warm_dir):
    """Child-process half of the restart benchmark (one process = one
    TPU owner; the parent spawns these BEFORE initializing jax).

    populate: build + persist the Q tables for a fresh bench key set.
    restart:  the measured story — construct the provider from config,
              prewarm from persisted bytes, validate one CHUNK-sig
              batch; report seconds from construction to validated."""
    out = {"mode": mode}
    _apply_platform()
    from cryptography.hazmat.primitives.asymmetric import ec

    from fabric_tpu.bccsp import factory
    from fabric_tpu.common import jaxenv

    jaxenv.enable_compilation_cache()
    rng = np.random.default_rng(4321)

    if mode == "populate":
        privs = [ec.generate_private_key(ec.SECP256R1())
                 for _ in range(NKEYS)]
        _save_bench_privs(warm_dir, privs)
        prov = factory.new_bccsp(factory.FactoryOpts.from_config({
            "Default": "TPU",
            "TPU": {"MinBatch": 16, "Chunk": CHUNK,
                    "WarmKeysDir": warm_dir}}))
        prov.prewarm(buckets=(CHUNK,), wait_restore=True)
        items = _signed_batch(prov, privs, 4096, rng)
        t0 = time.perf_counter()
        ok = prov.verify_batch(items)
        out["cold_first_batch_s"] = round(time.perf_counter() - t0, 2)
        out["ok"] = bool(all(ok))
        prov.flush_warm_tables()
        out["q16_builds"] = prov.stats["q16_builds"]
    else:
        privs = _load_bench_privs(warm_dir)
        if privs is None:
            out["error"] = "no persisted bench keys"
            print(json.dumps(out))
            return
        # workload generation (signing) is not restart cost: presign
        # before the clock starts
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric.utils import (
            decode_dss_signature,
        )

        from fabric_tpu.bccsp import VerifyItem, utils as butils
        from fabric_tpu.bccsp.bccsp import ECDSAPublicKeyImportOpts
        pre = []
        for i in range(CHUNK):
            m = rng.bytes(MSG_LEN)
            der = privs[i % len(privs)].sign(
                m, ec.ECDSA(hashes.SHA256()))
            r, s = decode_dss_signature(der)
            pre.append((m, butils.marshal_signature(
                r, butils.to_low_s(s))))
        t0 = time.perf_counter()
        prov = factory.new_bccsp(factory.FactoryOpts.from_config({
            "Default": "TPU",
            "TPU": {"MinBatch": 16, "Chunk": CHUNK,
                    "WarmKeysDir": warm_dir}}))
        t_ctor = time.perf_counter()
        # prewarm phases timed so the restart cost is attributable:
        # g16 device build, then table restore (disk read + H2D)
        # OVERLAPPED with the AOT pipeline compiles inside prewarm()
        from fabric_tpu.ops import comb as _comb
        _comb.g16_tables()
        t_g16 = time.perf_counter()
        t_tabs = t_g16
        prov.prewarm(buckets=(CHUNK,))
        t_pw = time.perf_counter()
        keys = [prov.key_import(p.public_key(),
                                ECDSAPublicKeyImportOpts())
                for p in privs]
        items = [VerifyItem(key=keys[i % len(keys)], signature=sig,
                            message=m)
                 for i, (m, sig) in enumerate(pre)]
        ok = prov.verify_batch(items)
        t1 = time.perf_counter()
        served_8bit = prov.stats["q16_loading_skips"] > 0
        # time-to-flagship: when the background q16 restore lands and
        # a batch runs on the 16-bit path again
        prov.flush_warm_tables(timeout=1200)
        ok2 = prov.verify_batch(items)
        t2 = time.perf_counter()
        out.update({
            "ok": bool(all(ok)) and bool(all(ok2)),
            "restart_to_first_validated_s": round(t1 - t0, 2),
            "first_batch_path": ("8-bit (availability window: q16 "
                                 "restore still streaming)"
                                 if served_8bit else "16-bit"),
            "flagship_restored_s": round(t2 - t0, 2),
            "ctor_s": round(t_ctor - t0, 2),
            "g16_build_s": round(t_g16 - t_ctor, 2),
            "aot_s": round(t_pw - t_tabs, 2),
            "prewarm_s": round(t_pw - t_ctor, 2),
            "note": ("first-validated rides the 8-bit path while the "
                     "~GB q16 table streams back from disk to the "
                     "device (not re-measured on the v5e)"),
            "first_batch_s": round(t1 - t_pw, 2),
            "batch": CHUNK,
            "q16_disk_loads": prov.stats["q16_disk_loads"],
            "q8_disk_loads": prov.stats["q8_disk_loads"],
            "q16_loading_skips": prov.stats["q16_loading_skips"],
            "q16_builds": prov.stats["q16_builds"],
        })
    print(json.dumps(out))


def bench_restart(warm_dir, timeout: float = 1800.0) -> dict:
    """Parent half: spawn populate (only when the warm dir has no
    bench key set yet) then the measured restart child. Runs BEFORE
    the parent touches jax — on TPU rigs the chip is single-owner.
    `timeout` bounds the WHOLE stage: the restart child gets whatever
    the populate child left, so two sequential children can no longer
    spend 2x the stage budget."""
    import sys
    res = {}
    deadline = time.monotonic() + timeout
    have = (os.path.exists(os.path.join(warm_dir, BENCH_KEYS_PEM))
            and os.path.exists(os.path.join(warm_dir,
                                            "warm_keysets.json")))
    try:
        if not have:
            rc, out, err = _bounded_child(
                [sys.executable, os.path.abspath(__file__),
                 "--restart-child", "populate", warm_dir],
                max(1.0, deadline - time.monotonic()))
            if rc != 0:
                return {"error": "populate child failed",
                        "stderr": (err or "")[-800:]}
            res["populate"] = json.loads(out.strip().splitlines()[-1])
        rc, out, err = _bounded_child(
            [sys.executable, os.path.abspath(__file__),
             "--restart-child", "restart", warm_dir],
            max(1.0, deadline - time.monotonic()))
        if rc != 0:
            return {"error": "restart child failed",
                    "stderr": (err or "")[-800:]}
        res.update(json.loads(out.strip().splitlines()[-1]))
    except Exception as e:          # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}
    return res


# ---------------------------------------------------------------------------
# Staged bench (round 9): the default `python bench.py` is a jax-FREE
# orchestrator; every heavyweight measurement runs in a child process
# with its own hard deadline enforced by the PARENT's subprocess
# timeout — the only kind of watchdog that can preempt a child hung
# inside an XLA compile or a broken accelerator runtime (the BENCH_r05
# / MULTICHIP_r05 rc=124 class). Stages:
#   core@1dev      kernel-steady + provider-e2e, Devices: 1
#   core@alldev    the same, sharded over every local device
#   multichip      the scaling ratio between the two (curve in sidecar)
#   full_pipeline  endorse->order->validate->commit + secondary regimes
# Each stage prints its own JSON line the moment it ends; the LAST
# stdout line is still the one compact aggregate the driver parses.
# ---------------------------------------------------------------------------


def emit_stage(obj: dict) -> None:
    """Print one compact stage JSON line NOW: a stage that finished
    reports even if every later stage dies. Stage lines carry a
    "stage" key; the final aggregate line (emit_final) never does."""
    print(json.dumps(obj, separators=(",", ":")), flush=True)


def _flat(obj: dict) -> dict:
    return {k: v for k, v in obj.items()
            if not isinstance(v, (dict, list))}


def _devices_env() -> int:
    """BENCH_DEVICES: 0/absent = all local devices (the factory
    default), 1 = pinned single-device path, N = first N devices."""
    try:
        return int(os.environ.get("BENCH_DEVICES", "0"))
    except ValueError:
        return 0


def _tpu_config(warm_dir: str, devices: int,
                pipeline_chunk: int) -> dict:
    """The core.yaml-style BCCSP mapping every stage constructs its
    provider from — the SAME seam `peer node start` uses. Devices=0
    omits the knob so the factory's default (all local devices)
    applies."""
    tpu = {"MinBatch": 16, "Chunk": CHUNK,
           "PipelineChunk": pipeline_chunk,
           "WarmKeysDir": warm_dir}
    if devices:
        tpu["Devices"] = devices
    return {"Default": "TPU", "TPU": tpu}


def stage_core():
    """kernel-steady + provider-e2e at one device count (BENCH_DEVICES).

    Runs in its OWN process (one process = one device owner; the
    orchestrator spawns one per device count so the 1-device and
    all-device numbers come from identical fresh processes). Emits a
    stage line per sub-measurement and ONE final line; full detail
    goes to the BENCH_SIDECAR file."""
    _start_watchdog()
    devices = _devices_env()
    have_ssl = _have_openssl()
    warm_dir = os.environ.get(
        "BENCH_WARM_DIR",
        os.path.join(REPO_ROOT, ".cache", "warmkeys"))
    _apply_platform()
    import hashlib

    import jax
    import jax.numpy as jnp

    from fabric_tpu.bccsp import VerifyItem, factory, utils as butils
    from fabric_tpu.bccsp.bccsp import (
        ECDSAKeyGenOpts, ECDSAPublicKeyImportOpts,
    )
    from fabric_tpu.bccsp.sw import SWProvider
    from fabric_tpu.common import jaxenv

    jaxenv.enable_compilation_cache()
    local_devices = len(jax.devices())
    _PARTIAL["stage"] = "core"
    _PARTIAL["devices"] = devices or local_devices
    _PARTIAL["local_devices"] = local_devices
    rng = np.random.default_rng(1234)
    batch = BLOCK_TXS * SIGS_PER_TX

    pipeline_chunk = int(os.environ.get("BENCH_PIPELINE_CHUNK",
                                        str(min(8192, CHUNK))))
    prov = factory.new_bccsp(factory.FactoryOpts.from_config(
        _tpu_config(warm_dir, devices, pipeline_chunk)))
    mesh_devices = prov.stats["shard_devices"]
    _PARTIAL["mesh_devices"] = mesh_devices
    t0 = time.perf_counter()
    # wait_restore: the headline sections must measure the fully-warm
    # flagship path; the availability-first restore window is the
    # restart stage's measurement. Smoke runs pay ONE bounded compile.
    K_hdr = 1
    while K_hdr < NKEYS:
        K_hdr *= 2
    bucket_hdr = prov._bucket(batch)
    if SMOKE:
        prov.prewarm(buckets=(bucket_hdr,), key_counts=(K_hdr,),
                     wait_restore=True, bounded=True)
    else:
        prov.prewarm(buckets=(4096, CHUNK), wait_restore=True)
    prewarm_s = time.perf_counter() - t0
    _PARTIAL["prewarm_s"] = round(prewarm_s, 1)
    # earliest round-16 salvage point: prewarm just paid the compiles
    _PARTIAL["compile_s"] = round(
        prov.stats.get("compile_seconds", 0.0), 3)
    _PARTIAL["compile_cache_hits"] = \
        prov.stats.get("compile_cache_hits", 0)

    # --- workload: NKEYS org keys, `batch` signed messages. With
    # OpenSSL, reuse the persisted bench key set; without it (this
    # growth container), the pure-python sw backend signs ---
    privs = _load_bench_privs(warm_dir) if have_ssl else None
    sw_oracle = SWProvider()
    if have_ssl:
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec
        from cryptography.hazmat.primitives.asymmetric.utils import (
            decode_dss_signature,
        )
        if privs is None or len(privs) != NKEYS:
            privs = [ec.generate_private_key(ec.SECP256R1())
                     for _ in range(NKEYS)]
            try:
                _save_bench_privs(warm_dir, privs)
            except Exception:       # noqa: BLE001
                pass                 # read-only cache dir: still runs
        keys = [prov.key_import(p.public_key(),
                                ECDSAPublicKeyImportOpts())
                for p in privs]
        msgs = [rng.bytes(MSG_LEN) for _ in range(batch)]
        t0 = time.perf_counter()
        items = []
        for i, m in enumerate(msgs):
            der = privs[i % NKEYS].sign(m, ec.ECDSA(hashes.SHA256()))
            r, s = decode_dss_signature(der)
            # openssl may emit high-S; fabric's endorser signs low-S
            items.append(VerifyItem(
                key=keys[i % NKEYS],
                signature=butils.marshal_signature(
                    r, butils.to_low_s(s)),
                message=m))
        sign_s = time.perf_counter() - t0
    else:
        sw_keys = [sw_oracle.key_gen(ECDSAKeyGenOpts(ephemeral=True))
                   for _ in range(NKEYS)]
        keys = [k.public_key() for k in sw_keys]
        msgs = [rng.bytes(MSG_LEN) for _ in range(batch)]
        t0 = time.perf_counter()
        items = [VerifyItem(
            key=keys[i % NKEYS],
            signature=sw_oracle.sign(
                sw_keys[i % NKEYS], hashlib.sha256(m).digest()),
            message=m) for i, m in enumerate(msgs)]
        sign_s = time.perf_counter() - t0
    _PARTIAL["sign_s"] = round(sign_s, 1)

    # --- CPU baseline: single-thread verify, ideal-scaled to all
    #     cores ---
    sample = min(CPU_SAMPLE, batch)
    t0 = time.perf_counter()
    if have_ssl:
        for i in range(sample):
            privs[i % NKEYS].public_key().verify(
                items[i].signature, msgs[i],
                ec.ECDSA(hashes.SHA256()))
        baseline_impl = "openssl single-thread, ideal core scaling"
    else:
        for i in range(sample):
            if not sw_oracle.verify(keys[i % NKEYS],
                                    items[i].signature,
                                    hashlib.sha256(msgs[i]).digest()):
                raise SystemExit("baseline rejected a valid signature")
        baseline_impl = ("pure-python P-256 single-thread, ideal core "
                         "scaling (no OpenSSL wheel on this host)")
    cpu_per_sig = (time.perf_counter() - t0) / sample
    ncpu = os.cpu_count() or 1
    cpu_sigs_per_s = ncpu / cpu_per_sig          # ideal scaling credit
    _PARTIAL["cpu_ideal_sigs_per_s"] = round(cpu_sigs_per_s, 1)

    # --- provider-e2e sub-stage THROUGH THE SEAM: warm pass compiles
    #     the pipeline and builds/caches the per-key-set Q tables,
    #     then honest wall clock of verify_batch (host DER parse,
    #     limb packing, per-device transfer streams, device,
    #     readback) ---
    prewarmed_sets = prov.stats["q16_resident_sets"]
    t0 = time.perf_counter()
    out = prov.verify_batch(items)
    warm_s = time.perf_counter() - t0
    if not all(out):
        raise SystemExit("correctness failure: valid signatures "
                         "rejected")
    if prov.stats["comb_batches"] + prov.stats["fused_batches"] < 1:
        raise SystemExit("bench did not exercise a device verify "
                         "tier: %s" % prov.stats)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = prov.verify_batch(items)
        times.append(time.perf_counter() - t0)
    provider_s = min(times)
    if not all(out):
        raise SystemExit("correctness failure in steady provider pass")

    # --- round-14 tracing facts: verify tail latencies from the
    #     stage reservoirs, and a measured tracing-on vs tracing-off
    #     A/B on the SAME steady loop (the acceptance bar: the
    #     always-on recorder must cost <=2% on this stage) ---
    from fabric_tpu.common import tracing
    trace_fields: dict = {}
    provider_off_s = None
    if tracing.enabled():       # FTPU_TRACE=0 skips the A/B entirely
        tq = tracing.stage_quantiles().get("tpu.verify") or {}
        trace_fields["verify_p50_s"] = \
            round(tq["p50_s"], 6) if tq else None
        trace_fields["verify_p99_s"] = \
            round(tq["p99_s"], 6) if tq else None
        tracing.set_enabled(False)
        try:
            times_off = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = prov.verify_batch(items)
                times_off.append(time.perf_counter() - t0)
        finally:
            tracing.set_enabled(True)
        if not all(out):
            raise SystemExit("correctness failure in tracing-off "
                             "pass")
        provider_off_s = min(times_off)
        trace_fields["tracing_overhead_pct"] = round(
            (provider_s / provider_off_s - 1.0) * 100.0, 2)
    _PARTIAL.update(trace_fields)

    # --- round-16 device-cost facts: compile seconds / persistent-
    #     cache hits from the provider's compile seam, and the
    #     fleet's peak HBM occupancy (0 on backends without
    #     memory_stats) — refreshed again for the final line after
    #     the remaining sub-stages compile their shapes ---
    def devicecost_fields():
        return {
            "compile_s": round(
                prov.stats.get("compile_seconds", 0.0), 3),
            "compile_cache_hits":
                prov.stats.get("compile_cache_hits", 0),
            "mem_peak_bytes": _devicecost_mod().peak_memory_bytes(),
        }

    dc_fields = devicecost_fields()
    _PARTIAL.update(dc_fields)

    _PARTIAL["provider_verify_batch_sigs_per_s"] = \
        round(batch / provider_s, 1)
    _PARTIAL["value"] = _PARTIAL["provider_verify_batch_sigs_per_s"]
    _PARTIAL["provider_stats"] = dict(prov.stats)
    emit_stage({"stage": "provider_e2e",
                "devices": devices or local_devices,
                "mesh_devices": mesh_devices, "batch": batch,
                "sigs_per_s": round(batch / provider_s, 1),
                "seconds": round(provider_s, 4),
                "tracing_off_seconds": (round(provider_off_s, 4)
                                        if provider_off_s else None),
                **trace_fields,
                **dc_fields,
                "overlap_ratio":
                    prov.stats["pipeline_overlap_ratio"],
                "shard_skew_s": prov.stats["shard_skew_s"]})

    # --- kernel-steady sub-stage: the provider's OWN jitted pipeline
    #     + cached tables, operands staged once outside the timed loop
    #     (sharded across the mesh when one is configured — transfer
    #     jitter must not pollute the kernel number) ---
    tpu_s = None
    host_prep_s = None
    fused_fields: dict = {}
    if _remaining() <= 45:
        emit_stage({"stage": "kernel_steady", "skipped": "budget",
                    "devices": devices or local_devices})
        fused_fields["fused_skipped"] = "budget"
    else:
        from fabric_tpu import native

        bucket = prov._bucket(batch)   # the shape verify_batch compiled
        # host SHA-256 of every message lane — the serialized host
        # slice the round-20 fused kernel moves on device; timed so
        # the fused A/B below can report what it eliminates
        t0 = time.perf_counter()
        digests0 = np.zeros((bucket, 8), dtype=np.uint32)
        for i, m in enumerate(msgs):
            digests0[i] = np.frombuffer(
                hashlib.sha256(m).digest(), dtype=">u4")
        host_prep_s = time.perf_counter() - t0
        _PARTIAL["host_prep_s"] = round(host_prep_s, 4)
        prep = native.batch_prep([it.signature for it in items])
        if prep is not None:
            ok_n, r_b, rpn_b, w_b = prep
        else:
            # no native toolchain: stage with the pure-python prep
            from fabric_tpu.bccsp.tpu import host_prep_scalars
            ok_n = np.zeros(batch, dtype=bool)
            r_b = np.zeros((batch, 32), dtype=np.uint8)
            rpn_b = np.zeros((batch, 32), dtype=np.uint8)
            w_b = np.zeros((batch, 32), dtype=np.uint8)
            for i, it in enumerate(items):
                p = host_prep_scalars(it.key.public_key(),
                                      it.signature)
                if p is None:
                    continue
                ok_n[i] = True
                r_b[i] = np.frombuffer(p[0], np.uint8)
                rpn_b[i] = np.frombuffer(p[1], np.uint8)
                w_b[i] = np.frombuffer(p[2], np.uint8)
        assert ok_n.all()

        def padb(a):
            return np.pad(a, [(0, bucket - batch)] +
                          [(0, 0)] * (a.ndim - 1))

        r8 = padb(r_b)
        rpn8 = padb(rpn_b)
        w8 = padb(w_b)
        key_map: dict[bytes, int] = {}
        key_idx = np.zeros(bucket, dtype=np.int32)
        for i, it in enumerate(items):
            pub = it.key.public_key()
            kb = pub.x_bytes().tobytes() + pub.y_bytes().tobytes()
            key_idx[i] = key_map.setdefault(kb, len(key_map))
        # pristine first-appearance slots for the fused A/B below:
        # prepared_digest_pipeline returns a CANONICALLY REMAPPED
        # key_idx, and remapping an already-remapped array combs
        # lanes against the wrong keys
        key_idx0 = key_idx.copy()
        # the provider's SUPPORTED measurement surface: its own
        # compiled digest pipeline + resident tables, degrading to the
        # 8-bit path exactly as verify_batch would (the BENCH_r04
        # KeyError came from peeking at private caches instead)
        fn, key_idx, tabs = prov.prepared_digest_pipeline(key_map,
                                                          key_idx)
        q_flat, g16, q16_path, K = (tabs["q_flat"], tabs["g16"],
                                    tabs["q16"], tabs["K"])
        premask = np.zeros(bucket, dtype=bool)
        premask[:batch] = True

        chunk = prov._mesh_chunk(bucket)
        if prov._mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            _sh = NamedSharding(prov._mesh, P("batch"))

            def put(a):
                return jax.device_put(a, _sh)
        else:
            put = jnp.asarray
        staged = []
        for lo in range(0, bucket, chunk):
            hi = lo + chunk
            staged.append(tuple(put(a) for a in (
                key_idx[lo:hi], r8[lo:hi], rpn8[lo:hi], w8[lo:hi],
                premask[lo:hi], digests0[lo:hi])))
        jax.block_until_ready(staged)

        def run_chunks():
            outs = [fn(ch[0], q_flat, g16, *ch[1:]) for ch in staged]
            return np.concatenate([np.asarray(o) for o in outs])

        out = run_chunks()             # cache-hit: same shapes as warm
        if not out[:batch].all():
            raise SystemExit("correctness failure on device-resident "
                             "path")
        times = []
        for _ in range(TPU_ITERS):
            t0 = time.perf_counter()
            out = run_chunks()
            times.append(time.perf_counter() - t0)
        tpu_s = min(times)
        _PARTIAL["value"] = round(batch / tpu_s, 1)
        _PARTIAL["tpu_steady_s"] = round(tpu_s, 4)
        _PARTIAL["provider_stats"] = dict(prov.stats)
        emit_stage({"stage": "kernel_steady",
                    "devices": devices or local_devices,
                    "mesh_devices": mesh_devices, "batch": batch,
                    "sigs_per_s": round(batch / tpu_s, 1),
                    "seconds": round(tpu_s, 4),
                    "hash_mode": "host-digest",
                    "chunk": chunk, "q16": bool(q16_path)})

        # --- fused A/B sub-stage (round 20): the SAME corpus through
        #     the fused Pallas tier — raw padded message lanes in,
        #     device SHA-256 ahead of the comb, zero host hashing.
        #     `fused_vs_staged` is the per-iteration device ratio;
        #     `host_prep_s` above is the serialized host slice the
        #     fused path additionally eliminates. CPU rigs emit an
        #     explicit `fused_skipped: cpu` marker (the interpret-mode
        #     Mosaic compile is minutes, not a serving configuration)
        #     unless FTPU_FUSED=1 forces the A/B through interpret ---
        if os.environ.get("BENCH_FUSED", "1") != "1":
            fused_fields["fused_skipped"] = "env"
        elif (not type(prov)._on_tpu()
              and os.environ.get("FTPU_FUSED") != "1"):
            fused_fields["fused_skipped"] = "cpu"
        elif _remaining() <= 120:
            fused_fields["fused_skipped"] = "budget"
        else:
            from fabric_tpu.ops import sha256 as _sha
            t0 = time.perf_counter()
            f_nb = max(1, (max(len(m) for m in msgs) + 9 + 63) // 64)
            blocks, nblocks = _sha.pack_messages(
                list(msgs) + [b""] * (bucket - batch), f_nb)
            nblocks = nblocks.astype(np.int32)
            fused_pack_s = time.perf_counter() - t0
            ffn, fkey, ftabs = prov.prepared_fused_pipeline(
                key_map, key_idx0.copy())
            fq, fg = ftabs["q_flat"], ftabs["g16"]
            fdig = np.zeros((bucket, 8), dtype=np.uint32)
            fhd = np.zeros(bucket, dtype=bool)
            fstaged = []
            for lo in range(0, bucket, chunk):
                hi = lo + chunk
                fstaged.append(tuple(put(a) for a in (
                    blocks[lo:hi], nblocks[lo:hi], fkey[lo:hi],
                    r8[lo:hi], rpn8[lo:hi], w8[lo:hi],
                    premask[lo:hi], fdig[lo:hi], fhd[lo:hi])))
            jax.block_until_ready(fstaged)
            hh0 = prov.stats["host_hashed_lanes"]

            def run_fused():
                outs = [ffn(ch[0], ch[1], ch[2], fq, fg, *ch[3:])
                        for ch in fstaged]
                return np.concatenate([np.asarray(o) for o in outs])

            out = run_fused()              # compile + warm pass
            if not out[:batch].all():
                raise SystemExit("correctness failure on fused "
                                 "verify path")
            times = []
            for _ in range(TPU_ITERS):
                t0 = time.perf_counter()
                out = run_fused()
                times.append(time.perf_counter() - t0)
            fused_s = min(times)
            fused_fields = {
                "fused_batch": batch,
                "fused_steady_s": round(fused_s, 4),
                "fused_sigs_per_s": round(batch / fused_s, 1),
                "fused_pack_s": round(fused_pack_s, 4),
                "fused_vs_staged": (round(tpu_s / fused_s, 3)
                                    if tpu_s else None),
                "fused_host_hashed_lanes":
                    prov.stats["host_hashed_lanes"] - hh0,
            }
            _PARTIAL.update(fused_fields)
            emit_stage({"stage": "fused_verify",
                        "devices": devices or local_devices,
                        "mesh_devices": mesh_devices,
                        "hash_mode": "device-fused",
                        "host_prep_s": round(host_prep_s, 4),
                        "nb": f_nb, "chunk": chunk, **fused_fields})

    if "fused_skipped" in fused_fields:
        _PARTIAL["fused_skipped"] = fused_fields["fused_skipped"]
        emit_stage({"stage": "fused_verify",
                    "skipped": fused_fields["fused_skipped"]})

    # --- ed25519 regime: the scheme router's second device kernel
    #     (round 11). Own JSON fields on the stage/final lines; an
    #     explicit skip marker when the section doesn't run — the
    #     same contract as order_skipped, so bench_smoke can tell
    #     "opted out / out of budget" from "silently broken" ---
    ed_fields: dict = {}
    ed_batch = int(os.environ.get("BENCH_ED25519_BATCH",
                                  "128" if SMOKE else "1024"))
    if os.environ.get("BENCH_ED25519", "1") != "1":
        ed_fields["ed25519_skipped"] = "env"
    elif _remaining() <= 90:
        ed_fields["ed25519_skipped"] = "budget"
    else:
        from fabric_tpu.bccsp import ed25519_host as edh
        from fabric_tpu.bccsp._crypto_compat import ed25519_sign
        from fabric_tpu.bccsp.bccsp import Ed25519PublicKeyImportOpts
        seeds = [edh.generate_seed() for _ in range(NKEYS)]
        ed_keys = [prov.key_import(edh.public_from_seed(s),
                                   Ed25519PublicKeyImportOpts())
                   for s in seeds]
        t0 = time.perf_counter()
        ed_items = [VerifyItem(key=ed_keys[i % NKEYS],
                               signature=ed25519_sign(
                                   seeds[i % NKEYS], m),
                               message=m)
                    for i, m in enumerate(
                        rng.bytes(MSG_LEN) for _ in range(ed_batch))]
        ed_sign_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = prov.verify_batch(ed_items)       # compile + warm pass
        ed_warm_s = time.perf_counter() - t0
        if not all(out):
            raise SystemExit("correctness failure: valid ed25519 "
                             "signatures rejected")
        if not prov.stats["ed25519_batches"]:
            raise SystemExit("ed25519 regime never reached the "
                             "device kernel: %s" % prov.scheme_stats)
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            out = prov.verify_batch(ed_items)
            times.append(time.perf_counter() - t0)
        ed_s = min(times)
        if not all(out):
            raise SystemExit("correctness failure in steady ed25519 "
                             "pass")
        ed_fields = {
            "ed25519_batch": ed_batch,
            "ed25519_sigs_per_s": round(ed_batch / ed_s, 1),
            "ed25519_seconds": round(ed_s, 4),
            "ed25519_warm_s": round(ed_warm_s, 1),
        }
        _PARTIAL.update(ed_fields)
        emit_stage({"stage": "ed25519",
                    "devices": devices or local_devices,
                    "mesh_devices": mesh_devices, **ed_fields,
                    "sign_s": round(ed_sign_s, 2)})
    if "ed25519_skipped" in ed_fields:
        emit_stage({"stage": "ed25519",
                    "skipped": ed_fields["ed25519_skipped"]})

    # --- pairing regime (round 21): the BLS12-381 batched
    #     Miller-product kernel behind verify_aggregate. Aggregate-
    #     width sweep; pairing_pairs_per_s is the steady device rate
    #     at the widest width and pairing_final_exp_share the
    #     fraction of that pass spent in the ONE shared final
    #     exponentiation — the cost the batch amortizes, so the share
    #     should FALL as widths grow. CPU rigs skip with an explicit
    #     marker (the 381-bit Miller scan compile is not a serving
    #     configuration off-device) unless FTPU_BLS_DEVICE=1 forces
    #     the sweep through. ---
    pair_fields: dict = {}
    if os.environ.get("BENCH_PAIRING", "1") != "1":
        pair_fields["pairing_skipped"] = "env"
    elif (not type(prov)._on_tpu()
          and os.environ.get("FTPU_BLS_DEVICE") != "1"):
        pair_fields["pairing_skipped"] = "cpu"
    elif _remaining() <= 150:
        pair_fields["pairing_skipped"] = "budget"
    else:
        from fabric_tpu.bccsp.bccsp import BLSKeyGenOpts
        from fabric_tpu.bccsp.sw import bls_aggregate_signatures
        from fabric_tpu.ops import bls12_381_kernel as blsk
        sizes = [int(s) for s in os.environ.get(
            "BENCH_PAIRING_SIZES",
            "3,7" if SMOKE else "3,7,15,31").split(",")]
        bls_keys = [prov.key_gen(BLSKeyGenOpts(ephemeral=True))
                    for _ in range(min(4, max(sizes)))]
        pb0 = prov.stats["pairing_batches"]
        sweep = []
        for nk in sizes:
            msgs_a = [b"agg %d/%d" % (i, nk) for i in range(nk)]
            keys_a = [bls_keys[i % len(bls_keys)] for i in range(nk)]
            agg = bls_aggregate_signatures(
                [prov.sign(k, m) for k, m in zip(keys_a, msgs_a)])
            pubs = [k.public_key() for k in keys_a]
            t0 = time.perf_counter()
            ok = prov.verify_aggregate(pubs, msgs_a, agg)  # warm
            warm_s = time.perf_counter() - t0
            if ok is not True:
                raise SystemExit("correctness failure: valid BLS "
                                 "aggregate rejected (%d keys)" % nk)
            if prov.verify_aggregate(
                    pubs, msgs_a[:-1] + [b"forged"], agg) is not False:
                raise SystemExit("correctness failure: forged BLS "
                                 "aggregate accepted (%d keys)" % nk)
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                prov.verify_aggregate(pubs, msgs_a, agg)
                times.append(time.perf_counter() - t0)
            steady = min(times)
            npairs = nk + 1          # +1: the (agg_sig, -G2) pair
            sweep.append({"keys": nk, "pairs": npairs,
                          "steady_s": round(steady, 4),
                          "pairs_per_s": round(npairs / steady, 2),
                          "warm_s": round(warm_s, 1)})
            emit_stage({"stage": "pairing", **sweep[-1]})
        if prov.stats["pairing_batches"] == pb0:
            raise SystemExit("pairing regime never reached the "
                             "device kernel: %s" % dict(prov.stats))
        # final-exp share: ONE lane through the jitted register-
        # machine exponentiation, vs the widest full pass
        frng = np.random.default_rng(21)
        ints = [[[int.from_bytes(frng.bytes(47), "big")
                  for _ in range(2)] for _ in range(3)]
                for _ in range(2)]
        staged_f = tuple(tuple(
            (jnp.asarray(blsk.F.to_mont(c[0])[None, :]),
             jnp.asarray(blsk.F.to_mont(c[1])[None, :]))
            for c in half) for half in ints)
        fe = jax.jit(lambda f: blsk.gt_is_one(blsk.final_exp_batch(f)))
        jax.block_until_ready(fe(staged_f))          # compile + warm
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(fe(staged_f))
            times.append(time.perf_counter() - t0)
        fe_s = min(times)
        widest = sweep[-1]
        pair_fields = {
            "pairing_pairs": widest["pairs"],
            "pairing_steady_s": widest["steady_s"],
            "pairing_pairs_per_s": widest["pairs_per_s"],
            "pairing_final_exp_s": round(fe_s, 4),
            "pairing_final_exp_share": round(
                fe_s / widest["steady_s"], 3) if widest["steady_s"]
                else None,
            "pairing_sweep": sweep,
        }
        _PARTIAL.update({k: v for k, v in pair_fields.items()
                         if k != "pairing_sweep"})
        emit_stage({"stage": "pairing",
                    "devices": devices or local_devices,
                    "mesh_devices": mesh_devices, **pair_fields})
    if "pairing_skipped" in pair_fields:
        _PARTIAL["pairing_skipped"] = pair_fields["pairing_skipped"]
        emit_stage({"stage": "pairing",
                    "skipped": pair_fields["pairing_skipped"]})

    on_tpu = type(prov)._on_tpu()
    dc_fields = devicecost_fields()     # refreshed: all shapes built
    _PARTIAL.update(dc_fields)
    detail = {
        "batch": batch,
        "distinct_keys": NKEYS,
        "devices_requested": devices or "all",
        "local_devices": local_devices,
        "mesh_devices": mesh_devices,
        "kernel": ("fixed-base comb 16/16-bit windows + Pallas VMEM "
                   "tree (ops/comb.py + ops/ptree.py)" if on_tpu else
                   "comb 8-bit (CPU dry run)"),
        "seam": "factory.new_bccsp({'Default': 'TPU'}) -> "
                "TPUProvider.verify_batch; steady number uses the "
                "provider's own compiled pipeline + cached tables",
        "sharding": ("shard_map over a %d-device batch-axis mesh "
                     "(replicated tables, per-device transfer "
                     "streams)" % mesh_devices if mesh_devices > 1
                     else "single device (no mesh)"),
        "pipeline_chunk": pipeline_chunk,
        "tpu_steady_s": round(tpu_s, 4) if tpu_s else None,
        "hash_mode": ("device-fused" if prov._fused_enabled() else
                      "host SHA-256 -> 32B digest lanes (default)"
                      if prov._hash_on_host else
                      "fused device SHA-256"),
        "host_prep_s": (round(host_prep_s, 4)
                        if host_prep_s is not None else None),
        "fused": dict(fused_fields) or None,
        "tpu_block_tx_per_s": (round(BLOCK_TXS / tpu_s, 1)
                               if tpu_s else None),
        "provider_verify_batch_s": round(provider_s, 4),
        "provider_verify_batch_sigs_per_s":
            round(batch / provider_s, 1),
        "cpu_single_thread_us_per_sig": round(cpu_per_sig * 1e6, 1),
        "cpu_ideal_cores": ncpu,
        "cpu_ideal_sigs_per_s": round(cpu_sigs_per_s, 1),
        "cpu_baseline_impl": baseline_impl,
        "warm_pass_s": round(warm_s, 1),
        "prewarm_s": round(prewarm_s, 1),
        "prewarmed_key_sets": prewarmed_sets,
        "sign_s": round(sign_s, 2),
        "provider_stats": dict(prov.stats),
        "shard_stats": dict(prov.shard_stats),
        "scheme_stats": {k: dict(v)
                         for k, v in prov.scheme_stats.items()},
        "trace_stage_quantiles": tracing.stage_quantiles(),
        "compile_events": list(prov.device_cost.events),
        "device_memory": _devicecost_mod().device_memory(),
        "ed25519": dict(ed_fields) or None,
        "pairing": dict(pair_fields) or None,
        "devices": [str(d) for d in jax.devices()],
    }
    value = (round(batch / tpu_s, 1) if tpu_s
             else round(batch / provider_s, 1))
    emit_final({
        "stage": "core",
        "metric": "block-validation sig-verify throughput "
                  f"({BLOCK_TXS}-tx block, 2-of-3 P-256, via "
                  "TPUProvider)",
        "devices": devices or local_devices,
        "local_devices": local_devices,
        "mesh_devices": mesh_devices,
        # round-13 elastic mesh: chips benched / re-admitted during
        # the run and the mesh size the run FINISHED on — a degraded
        # run is a salvage (served on the survivors), not a zero
        "device_quarantines": prov.stats.get("device_quarantines", 0),
        "device_readmits": prov.stats.get("device_readmits", 0),
        "final_mesh_devices": prov.stats.get("shard_devices",
                                             mesh_devices),
        "value": value,
        "unit": "sigs/s",
        "vs_baseline": round(value / cpu_sigs_per_s, 3),
        "batch": batch,
        "provider_sigs_per_s": round(batch / provider_s, 1),
        "tpu_steady_s": round(tpu_s, 4) if tpu_s else None,
        "cpu_ideal_sigs_per_s": round(cpu_sigs_per_s, 1),
        "deadline_s": DEADLINE_S or None,
        "deadline_hit": False,
        "on_tpu": on_tpu,
        "host_prep_s": (round(host_prep_s, 4)
                        if host_prep_s is not None else None),
        **trace_fields,
        **dc_fields,
        **ed_fields,
        **fused_fields,
        **{k: v for k, v in pair_fields.items()
           if k != "pairing_sweep"},
    }, detail)


def stage_pipeline():
    """full-pipeline stage: the commit-pipeline overlap benchmark
    (wheel-free, runs in the bounded default) plus the secondary
    regimes — real endorse->order->validate->commit, idemix pairing
    verify, block-sig latency, many-key-set policy, sw/device
    crossover — each env-gated exactly as before."""
    _start_watchdog()
    have_ssl = _have_openssl()
    warm_dir = os.environ.get(
        "BENCH_WARM_DIR",
        os.path.join(REPO_ROOT, ".cache", "warmkeys"))
    aux_default = "0" if SMOKE else "1"

    def want(env: str, needs_ssl: bool = False,
             margin_s: float = 60.0) -> bool:
        if os.environ.get(env, aux_default) != "1":
            return False
        if needs_ssl and not have_ssl:
            return False
        return _remaining() > margin_s

    needs_prov = (want("BENCH_E2E", needs_ssl=True)
                  or want("BENCH_IDEMIX")
                  or want("BENCH_BLOCKSIG", needs_ssl=True)
                  or want("BENCH_CROSSOVER", needs_ssl=True))
    prov = None
    if needs_prov:
        _apply_platform()
        from fabric_tpu.bccsp import factory
        from fabric_tpu.common import jaxenv
        jaxenv.enable_compilation_cache()
        pipeline_chunk = int(os.environ.get(
            "BENCH_PIPELINE_CHUNK", str(min(8192, CHUNK))))
        prov = factory.new_bccsp(factory.FactoryOpts.from_config(
            _tpu_config(warm_dir, _devices_env(), pipeline_chunk)))
        prov.prewarm(buckets=(prov._bucket(BLOCK_TXS * SIGS_PER_TX),),
                     wait_restore=True, bounded=SMOKE)

    detail: dict = {}

    pipeline = None
    if want("BENCH_E2E", needs_ssl=True):
        try:
            import bench_pipeline
            pipeline = bench_pipeline.run(
                prov,
                ntxs=int(os.environ.get("BENCH_E2E_TXS",
                                        str(BLOCK_TXS))))
        except Exception as e:          # noqa: BLE001
            pipeline = {"error": f"{type(e).__name__}: {e}"}
        _PARTIAL["pipeline"] = pipeline
        detail["pipeline"] = pipeline

    commitpipe = None
    if os.environ.get("BENCH_COMMIT_PIPELINE", "1") == "1" and \
            _remaining() > 30:
        try:
            import bench_pipeline
            commitpipe = bench_pipeline.commit_pipeline_run(
                n_blocks=int(os.environ.get(
                    "BENCH_CP_BLOCKS", "6" if SMOKE else "16")),
                ntxs=int(os.environ.get(
                    "BENCH_CP_TXS", "24" if SMOKE else "96")))
        except Exception as e:          # noqa: BLE001
            commitpipe = {"error": f"{type(e).__name__}: {e}"}
        _PARTIAL["commit_pipeline"] = commitpipe
        detail["commit_pipeline"] = commitpipe

    # wheel-free (stub x509/MSP seam): runs by default, so every round
    # reports the ordering bottleneck beside peer validation; a skip
    # is recorded explicitly so the smoke gate can tell "didn't run"
    # from "ran but lost its fields"
    if os.environ.get("BENCH_ORDER_PIPELINE", "1") != "1":
        orderpipe = {"skipped": "BENCH_ORDER_PIPELINE!=1"}
    elif _remaining() <= 30:
        orderpipe = {"skipped": "time budget exhausted"}
    else:
        try:
            import bench_pipeline
            orderpipe = bench_pipeline.order_pipeline_run(
                prov,
                ntxs=int(os.environ.get(
                    "BENCH_ORDER_TXS", "192" if SMOKE else "1024")),
                window=int(os.environ.get("BENCH_ORDER_WINDOW", "64")),
                block_txs=int(os.environ.get(
                    "BENCH_ORDER_BLOCK_TXS", "64" if SMOKE else "256")))
        except Exception as e:          # noqa: BLE001
            orderpipe = {"error": f"{type(e).__name__}: {e}"}
    _PARTIAL["order_pipeline"] = orderpipe
    detail["order_pipeline"] = orderpipe

    # round-15: the bounded leader-kill failover soak (wheel-free,
    # chaos-wrapped 3-consenter cluster) — like the order section, a
    # skip is explicit so the smoke gate can tell "didn't run" from
    # "lost its fields"
    if os.environ.get("BENCH_FAILOVER", "1") != "1":
        failover = {"skipped": "BENCH_FAILOVER!=1"}
    elif _remaining() <= 60:
        failover = {"skipped": "time budget exhausted"}
    else:
        try:
            import bench_pipeline
            failover = bench_pipeline.failover_run(
                producers=int(os.environ.get(
                    "BENCH_FAILOVER_PRODUCERS", "2")),
                ntxs_per_producer=int(os.environ.get(
                    "BENCH_FAILOVER_TXS", "24" if SMOKE else "60")),
                block_txs=int(os.environ.get(
                    "BENCH_FAILOVER_BLOCK_TXS", "4")))
        except Exception as e:          # noqa: BLE001
            failover = {"error": f"{type(e).__name__}: {e}"}
    _PARTIAL["failover"] = failover
    detail["failover"] = failover

    # round-19: the adaptive admission control plane vs the same rig
    # with static knobs — closed-loop clients against a chaos-wrapped
    # 3-consenter + 2-peer cluster, reporting max sustainable tx/s at
    # the p99 commit SLO. Like the order/failover sections, a skip is
    # explicit so the smoke gate can tell "didn't run" from "ran but
    # lost its fields".
    if os.environ.get("BENCH_ADAPTIVE", "1") != "1":
        adaptrig = {"skipped": "BENCH_ADAPTIVE!=1"}
    elif _remaining() <= 90:
        adaptrig = {"skipped": "time budget exhausted"}
    else:
        # the rig builds its own controller; it refuses to run as a
        # vacuous static-vs-static comparison when the control plane
        # is globally disabled, so enable it for the section only
        prev_adaptive = os.environ.get("FTPU_ADAPTIVE")
        os.environ["FTPU_ADAPTIVE"] = "1"
        try:
            import bench_pipeline
            adaptrig = bench_pipeline.adaptive_serving_run(
                ntxs=int(os.environ.get(
                    "BENCH_ADAPTIVE_TXS", "240" if SMOKE else "2400")),
                invalid=int(os.environ.get(
                    "BENCH_ADAPTIVE_INVALID", "8" if SMOKE else "48")),
                slo_target_s=float(os.environ.get(
                    "BENCH_ADAPTIVE_SLO_S", "1.5")),
                deadline_s=max(60.0, _remaining() - 20))
        except Exception as e:          # noqa: BLE001
            adaptrig = {"error": f"{type(e).__name__}: {e}"}
        finally:
            if prev_adaptive is None:
                os.environ.pop("FTPU_ADAPTIVE", None)
            else:
                os.environ["FTPU_ADAPTIVE"] = prev_adaptive
    _PARTIAL["adaptive"] = adaptrig
    detail["adaptive"] = adaptrig

    idemix = None
    if want("BENCH_IDEMIX"):
        try:
            idemix = bench_idemix(prov)
        except Exception as e:          # noqa: BLE001
            idemix = {"error": f"{type(e).__name__}: {e}"}
        _PARTIAL["idemix"] = idemix
        detail["idemix"] = idemix

    blocksig = None
    if want("BENCH_BLOCKSIG", needs_ssl=True):
        try:
            blocksig = bench_blocksig(prov)
        except Exception as e:          # noqa: BLE001
            blocksig = {"error": f"{type(e).__name__}: {e}"}
        _PARTIAL["blocksig"] = blocksig
        detail["blocksig"] = blocksig

    multikeyset = None
    if want("BENCH_MULTIKEY", needs_ssl=True):
        try:
            multikeyset = bench_multikeyset()
        except Exception as e:          # noqa: BLE001
            multikeyset = {"error": f"{type(e).__name__}: {e}"}
        _PARTIAL["multikeyset"] = multikeyset
        detail["multikeyset"] = multikeyset

    crossover = None
    if want("BENCH_CROSSOVER", needs_ssl=True):
        try:
            crossover = bench_crossover(prov)
        except Exception as e:          # noqa: BLE001
            crossover = {"error": f"{type(e).__name__}: {e}"}
        _PARTIAL["crossover"] = crossover
        detail["crossover"] = crossover

    res = {"stage": "full_pipeline",
           "ok": not any(isinstance(v, dict) and "error" in v
                         for v in detail.values()),
           "sections": ",".join(sorted(detail)) or None,
           "deadline_hit": False}
    if commitpipe and "overlap_ratio" in commitpipe:
        res["commit_pipeline_overlap_ratio"] = \
            commitpipe["overlap_ratio"]
        res["commit_pipeline_speedup"] = commitpipe["speedup"]
        for k in ("cp_validate_p50_s", "cp_validate_p99_s",
                  "cp_commit_p50_s", "cp_commit_p99_s"):
            if commitpipe.get(k) is not None:
                res[k] = commitpipe[k]
    if orderpipe and "order_raft_s" in orderpipe:
        res["order_raft_s"] = orderpipe["order_raft_s"]
        res["order_tx_per_s"] = orderpipe["order_tx_per_s"]
        res["order_vs_validate"] = orderpipe["order_vs_validate"]
        # round-14 stage tails + the end-to-end lifecycle trace
        for k in ("order_window_p50_s", "order_window_p99_s",
                  "order_propose_p50_s", "order_propose_p99_s",
                  "order_consensus_p50_s", "order_consensus_p99_s",
                  "order_write_p50_s", "order_write_p99_s",
                  "validate_p50_s", "validate_p99_s",
                  "commit_p50_s", "commit_p99_s",
                  "trace_file", "probe_trace_id",
                  "trace_linked_stages",
                  # round-18: cross-node linkage + e2e finality tails
                  # (e2e_skipped is the explicit didn't-run marker)
                  "trace_nodes", "e2e_commit_p50_s",
                  "e2e_commit_p99_s", "e2e_skipped"):
            if orderpipe.get(k) is not None:
                res[k] = orderpipe[k]
    elif orderpipe and "skipped" in orderpipe:
        res["order_skipped"] = orderpipe["skipped"]
    if failover and "reelect_s" in failover:
        # round-15 failover facts on the stage line: how fast ordering
        # recovered from a leader kill under chaos, and that the
        # exactly-once/convergence contract held
        res["failover_reelect_s"] = failover["reelect_s"]
        res["failover_committed"] = failover["committed"]
        res["failover_leader_changes"] = failover["leader_changes"]
        res["failover_exact_once"] = \
            failover["accepted_commit_exact_once"]
        res["failover_chaos_dropped"] = failover["chaos_dropped"]
    elif failover and "skipped" in failover:
        res["failover_skipped"] = failover["skipped"]
    elif failover and "error" in failover:
        # surface the real exception on the stage line: the smoke
        # gate's "lacks failover_reelect_s" alone sends the
        # investigator to the wrong place
        res["failover_error"] = failover["error"]
    if adaptrig and "max_sustainable_tx_s" in adaptrig:
        # round-19 control-plane facts on the stage line: the serving
        # capacity the rig sustained INSIDE the SLO, whether the
        # closed loop beat the static baseline, and that the
        # anti-flap discipline held (phase details ride the sidecar)
        res["max_sustainable_tx_s"] = adaptrig["max_sustainable_tx_s"]
        res["adaptive_slo_held"] = adaptrig["slo_held"]
        res["adaptive_slo_target_s"] = adaptrig["slo_target_s"]
        res["adaptive_p99_s"] = \
            adaptrig["adaptive"]["commit_p99_s"]
        res["adaptive_static_tx_s"] = adaptrig["static"]["tx_s"]
        res["adaptive_beats_static"] = \
            adaptrig["adaptive_beats_static"]
        res["adaptive_no_flap"] = adaptrig["no_flap"]
        res["adaptive_controller_moves"] = \
            adaptrig["controller_moves"]
        res["adaptive_exact_once"] = \
            adaptrig["accepted_commit_exact_once"]
    elif adaptrig and "skipped" in adaptrig:
        res["adaptive_skipped"] = adaptrig["skipped"]
    elif adaptrig and "error" in adaptrig:
        res["adaptive_error"] = adaptrig["error"]
    if pipeline and "tpu_peer_block_s" in pipeline:
        res["e2e_tpu_peer_block_s"] = pipeline["tpu_peer_block_s"]
    emit_final(res, detail)


def _last_json_obj(text: str):
    for ln in reversed([line for line in (text or "").splitlines()
                        if line.strip()]):
        if ln.lstrip().startswith("{"):
            try:
                return json.loads(ln)
            except json.JSONDecodeError:
                continue
    return None


def _stage_lines(text: str) -> list:
    """Every JSON line with a "stage" key in a child's captured
    stdout — relayed onto the parent's stdout so sub-stage reports
    survive the capture."""
    out = []
    for ln in (text or "").splitlines():
        if not ln.lstrip().startswith("{"):
            continue
        try:
            obj = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "stage" in obj:
            out.append(obj)
    return out


def _run_stage(name: str, argv: list, env_extra: dict, budget: float):
    """Run one stage child under the parent's hard deadline. Returns
    (final_obj_or_None, child_stdout, error_line_or_None)."""
    import subprocess
    import sys
    env = dict(os.environ)
    env.update(env_extra)
    t0 = time.monotonic()
    try:
        rc, out, stderr = _bounded_child(
            [sys.executable, os.path.abspath(__file__)] + argv,
            budget, env=env)
    except subprocess.TimeoutExpired as e:
        out = e.stdout or ""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        return None, out, {
            "stage": name, "ok": False, "timeout": True,
            "budget_s": budget,
            "elapsed_s": round(time.monotonic() - t0, 1)}
    out = out or ""
    obj = _last_json_obj(out)
    if rc != 0 or obj is None:
        return obj, out, {
            "stage": name, "ok": False, "rc": rc,
            "stderr_tail": (stderr or "")[-400:],
            "elapsed_s": round(time.monotonic() - t0, 1)}
    return obj, out, None


def orchestrate():
    """The default `python bench.py`: a jax-free stage driver that
    ALWAYS prints one aggregate final line, whatever the stages do."""
    _start_watchdog()
    warm_dir = os.environ.get(
        "BENCH_WARM_DIR",
        os.path.join(REPO_ROOT, ".cache", "warmkeys"))
    have_ssl = _have_openssl()
    stages: dict = {}
    stage_detail: dict = {}

    def record(name, obj):
        stages[name] = obj or {}
        _PARTIAL.setdefault("stages", {})[name] = _flat(obj or {})

    def budget(floor: float = 45.0):
        return min(STAGE_DEADLINE_S or 1e9,
                   max(0.0, _remaining() - floor))

    # ---- restart stage (full mode + OpenSSL only, as before) ----
    if os.environ.get("BENCH_RESTART",
                      "0" if SMOKE else "1") == "1" and have_ssl:
        b = budget()
        if b > 60:
            res = bench_restart(warm_dir, timeout=b)
            res = {"stage": "restart",
                   "ok": "error" not in res, **res}
            emit_stage({"stage": "restart", **_flat(res)})
            record("restart", res)
            stage_detail["restart"] = res
        else:
            obj = {"stage": "restart", "skipped": "budget"}
            emit_stage(obj)
            record("restart", obj)

    def staged(name: str, argv: list, env: dict, b: float, side: str):
        """Run one child stage: relay its sub-stage lines, emit any
        error line, record its final object, load its sidecar — the
        one sequence every child stage (core_* and full_pipeline)
        goes through."""
        obj, out, err = _run_stage(name, argv, env, b)
        for line_obj in _stage_lines(out):
            emit_stage(line_obj)
        if err is not None:
            emit_stage(err)
        record(name, obj or err)
        try:
            with open(side) as f:
                stage_detail[name] = json.load(f)
        except Exception:           # noqa: BLE001
            stage_detail[name] = None
        return obj

    # ---- core stages: 1-device, then sharded over all devices ----
    def core_stage(name: str, devices: int):
        side = SIDECAR + f".{name}.json"
        b = budget()
        if b <= 60:
            obj = {"stage": name, "skipped": "budget"}
            emit_stage(obj)
            record(name, obj)
            return None
        env = {"BENCH_DEVICES": str(devices),
               "BENCH_SIDECAR": side,
               "BENCH_DEADLINE_S": str(max(45.0, b - 30.0))}
        return staged(name, ["--stage", "core"], env, b, side)

    core1 = core_stage("core_1dev", 1)
    local = (core1 or {}).get("local_devices") or 0
    coreN = None
    if os.environ.get("BENCH_MULTICHIP", "1") != "1":
        obj = {"stage": "multichip", "skipped": "BENCH_MULTICHIP=0"}
        emit_stage(obj)
        record("multichip", obj)
    elif local > 1:
        if not SMOKE:
            for tok in os.environ.get("BENCH_CURVE", "").split(","):
                tok = tok.strip()
                if tok.isdigit() and 1 < int(tok) < local:
                    core_stage(f"core_{tok}dev", int(tok))
        coreN = core_stage("core_alldev", 0)
        curve_d, curve_v, curve_p = [], [], []
        # numeric order, NOT name order: sorted names would interleave
        # core_16dev between core_1dev and core_2dev and hand any
        # scaling plot a non-monotonic device axis
        core_objs = [o for n, o in stages.items()
                     if n.startswith("core_") and (o or {}).get("value")]
        for obj in sorted(core_objs,
                          key=lambda o: o.get("mesh_devices") or 0):
            curve_d.append(obj.get("mesh_devices"))
            curve_v.append(obj.get("value"))
            curve_p.append(obj.get("provider_sigs_per_s"))
        mc = {"stage": "multichip",
              "ok": bool(core1 and coreN and (core1 or {}).get("value")
                         and (coreN or {}).get("value"))}
        if mc["ok"]:
            mc["devices"] = coreN.get("mesh_devices")
            mc["tpu_steady_scaling_x"] = round(
                coreN["value"] / core1["value"], 2)
            if coreN.get("provider_sigs_per_s") and \
                    core1.get("provider_sigs_per_s"):
                mc["provider_scaling_x"] = round(
                    coreN["provider_sigs_per_s"] /
                    core1["provider_sigs_per_s"], 2)
            # round-13 device-health facts for the driver: chips
            # benched/re-admitted during the all-device run and the
            # mesh size it finished on, plus an explicit salvage note
            # when the run completed degraded (its scaling number is
            # a survivors-mesh measurement, not a full-fleet one)
            quar = coreN.get("device_quarantines", 0) or 0
            readm = coreN.get("device_readmits", 0) or 0
            final_mesh = coreN.get("final_mesh_devices",
                                   coreN.get("mesh_devices"))
            mc["device_quarantines"] = quar
            mc["device_readmits"] = readm
            mc["final_mesh_devices"] = final_mesh
            # round-14: the all-device verify tail beside the scaling
            # ratio — a straggler chip shows here before it shows in
            # the mean
            mc["verify_p50_s"] = coreN.get("verify_p50_s")
            mc["verify_p99_s"] = coreN.get("verify_p99_s")
            if quar and final_mesh and \
                    final_mesh < (coreN.get("mesh_devices") or 0):
                mc["device_health_note"] = (
                    "degraded-mesh salvage: finished on "
                    f"{final_mesh}/{coreN.get('mesh_devices')} "
                    f"devices ({quar} quarantine(s), "
                    f"{readm} readmit(s))")
        emit_stage(mc)
        record("multichip", mc)
        # the measured scaling curve rides in the detail sidecar
        stage_detail["multichip_curve"] = {
            "devices": curve_d,
            "tpu_steady_sigs_per_s": curve_v,
            "provider_sigs_per_s": curve_p,
        }
    else:
        obj = {"stage": "multichip",
               "skipped": f"{local or 1} local device(s)"}
        emit_stage(obj)
        record("multichip", obj)

    # ---- full-pipeline stage ----
    run_pipe = (os.environ.get("BENCH_COMMIT_PIPELINE", "1") == "1"
                or not SMOKE)
    b = budget(floor=30.0)
    if run_pipe and b > 45:
        side = SIDECAR + ".pipeline.json"
        env = {"BENCH_SIDECAR": side,
               "BENCH_DEADLINE_S": str(max(40.0, b - 20.0))}
        staged("full_pipeline", ["--stage", "pipeline"], env, b, side)
    else:
        obj = {"stage": "full_pipeline",
               "skipped": "budget" if run_pipe else "off"}
        emit_stage(obj)
        record("full_pipeline", obj)

    # ---- aggregate final line (the one the driver parses) ----
    best = {}
    for cand in (stages.get("core_alldev"), stages.get("core_1dev")):
        if cand and cand.get("value"):
            best = cand
            break
    _PARTIAL["value"] = best.get("value")
    fp = stages.get("full_pipeline") or {}
    cp_flat = {k: fp[k] for k in ("commit_pipeline_overlap_ratio",
                                  "commit_pipeline_speedup")
               if k in fp}
    mc = stages.get("multichip") or {}
    ok_names = ",".join(sorted(
        n for n, o in stages.items()
        if o and (o.get("ok") or o.get("value") is not None)))
    bad_names = ",".join(sorted(
        n for n, o in stages.items()
        if o and o.get("ok") is False and "skipped" not in o))
    detail = {"stages": stages, "stage_detail": stage_detail}
    agg = {
        "metric": "block-validation sig-verify throughput "
                  f"({BLOCK_TXS}-tx block, 2-of-3 P-256, via "
                  "TPUProvider, staged)",
        **cp_flat,
        "value": best.get("value"),
        "unit": "sigs/s",
        "vs_baseline": best.get("vs_baseline"),
        "batch": best.get("batch"),
        "devices": best.get("mesh_devices"),
        "provider_sigs_per_s": best.get("provider_sigs_per_s"),
        "tpu_steady_s": best.get("tpu_steady_s"),
        "cpu_ideal_sigs_per_s": best.get("cpu_ideal_sigs_per_s"),
        "tpu_steady_scaling_x": mc.get("tpu_steady_scaling_x"),
        # round-16 device-cost facts from the winning core stage
        "compile_s": best.get("compile_s"),
        "compile_cache_hits": best.get("compile_cache_hits"),
        "mem_peak_bytes": best.get("mem_peak_bytes"),
        # round-20 fused-tier A/B from the winning core stage (skip
        # marker when the regime didn't run — CPU rig / env / budget)
        "fused_sigs_per_s": best.get("fused_sigs_per_s"),
        "fused_steady_s": best.get("fused_steady_s"),
        "fused_vs_staged": best.get("fused_vs_staged"),
        "fused_host_hashed_lanes": best.get("fused_host_hashed_lanes"),
        "fused_skipped": best.get("fused_skipped"),
        # round-21 pairing-engine sweep from the winning core stage
        # (same skip-marker contract: env / cpu / budget)
        "pairing_pairs_per_s": best.get("pairing_pairs_per_s"),
        "pairing_final_exp_share": best.get("pairing_final_exp_share"),
        "pairing_skipped": best.get("pairing_skipped"),
        "host_prep_s": best.get("host_prep_s"),
        "stages_ok": ok_names or None,
        "stages_failed": bad_names or None,
        "deadline_s": DEADLINE_S or None,
        "deadline_hit": False,
        "on_tpu": best.get("on_tpu"),
    }
    # round-16 perf ledger: gate this aggregate against the
    # BENCH_r*/MULTICHIP_r* round history beside this file. One
    # compact verdict string — 'ok(..)' / 'regressed:<metrics>' /
    # 'skipped:cpu-rig' / 'no_history' — so the driver (and
    # bench_smoke) reads the trend without opening the trajectory.
    agg["ledger"] = _ledger_verdict(agg)
    emit_final(agg, detail)


def main():
    """Back-compat alias: the staged orchestrator."""
    orchestrate()


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 3 and sys.argv[1] == "--restart-child":
        _restart_child(sys.argv[2], sys.argv[3])
    elif len(sys.argv) > 2 and sys.argv[1] == "--stage":
        if sys.argv[2] == "core":
            stage_core()
        elif sys.argv[2] == "pipeline":
            stage_pipeline()
        else:
            raise SystemExit(f"unknown stage {sys.argv[2]!r}")
    else:
        orchestrate()
