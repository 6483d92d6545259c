#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`configs[].file`) under a traffic mix (`benchmark/traffic/<traffic>.json`).
This process is the chip's only owner: it builds a `BCCSP.Default: TPU`
peer, synthesises a seeded chain in JAX-free worker processes while the
device warms up, hands blocks to the peer the way the deliver client
does, and prints one JSON result line. Without a TPU it prints none and
exits 3. `--rehearse` walks the same code on a CPU with a stand-in
provider at whatever size the files give and exits 4 without a result
line: a rehearsal is never a pass. `--control <names>` also puts the reference
with one guarantee broken in the program's place at the comparison:
`correct` then has to read false.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import identities, reference, sut, synth  # noqa: E402
from benchmark.readers.spans import percentile  # noqa: E402,F401

CONTROLS = ("accept_high_s", "skip_mvcc")
TRACE_SECONDS = 4.0          # the profiler takes the window's last seconds,
TRACE_BLOCKS = 2             # or this many blocks where they take longer
REFERENCE_SIGNATURES = 31000  # OpenSSL re-verifies about this many
STATE_READS = 200
SUPPLY_WARN_SHARE = 0.85     # of a closed loop's backlog, used by one window
SUPPLY_RULE = ("a benchmark PR sets its supply_tx_per_s to at least 1.5 x the "
               "newest accepted median of the fastest cell that reads it")
WORKERS = 8
JOB_TXS = 512


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_PROCESS:8.3f}] {msg}",
          file=sys.stderr, flush=True)


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return manifest, cell, config, traffic


def blocks_needed(traffic: dict, block_txs: int, seconds: float) -> int:
    loop = traffic["loop"]
    if loop["kind"] == "closed":
        return math.ceil(loop["supply_tx_per_s"] * seconds / block_txs) + 1
    return math.ceil(seconds * 1000.0 / loop["interval_ms"]) + 1


def host_snapshot() -> dict:
    """What this process held and burnt, for the earlier lines of a
    run: CPU seconds of all its threads (well above the wall time
    means threads spinning beside the loop), collector runs, threads,
    resident memory."""
    import gc
    snap = {"cpu_s": round(time.process_time(), 3),
            "gc_gen2": gc.get_stats()[2]["collections"]}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(("VmRSS", "Threads")):
                    k, v = line.split(":")
                    snap[k.lower()] = int(v.split()[0])
    except OSError:
        pass
    return snap


# ---- the measured window ---------------------------------------------------

class BlockRecord:
    __slots__ = ("number", "n_tx", "due", "start", "done", "served",
                 "dispatches", "spans", "lanes", "traced")

    def __init__(self, number, n_tx):
        self.number, self.n_tx = number, n_tx
        self.due = self.start = self.done = None
        self.served = True
        self.dispatches = 0      # device executions, by the provider's counters
        self.traced = False      # handed over under the profiler
        self.spans = {}          # name -> [(t0, t1), ...]
        self.lanes = []          # real signatures per provider call


def served_by_device(before: dict, after: dict) -> bool:
    if any(after.get(c, 0) != before.get(c, 0)
           for c in sut.FALLBACK_COUNTERS):
        return False
    return sum(after.get(c, 0) for c in sut.DISPATCH_COUNTERS) > \
        sum(before.get(c, 0) for c in sut.DISPATCH_COUNTERS)


def dispatches(before: dict, after: dict) -> int:
    """Executions of the verify program the provider's counters book
    between two readings: one for a whole-batch dispatch, one for each
    span of a pipelined one."""
    d = {c: after.get(c, 0) - before.get(c, 0)
         for c in ("comb_batches", "pipeline_batches", "pipeline_chunks")}
    return d["comb_batches"] - d["pipeline_batches"] + d["pipeline_chunks"]


class WindowRanDry(RuntimeError):
    """A closed loop handed in its last block before the window was
    over: without a backlog there is no sustained rate to report, and
    a window cut short would read a younger, faster chain."""

    def __init__(self, supplied: int, txs: int, elapsed_s: float,
                 seconds: float, source: str):
        super().__init__(
            f"the window ran dry: the {supplied} blocks ({txs} txs) that "
            f"{source} supplies were done {elapsed_s:.2f} s into a "
            f"{seconds:g} s window, {txs / elapsed_s:.1f} tx/s. No result: "
            f"{SUPPLY_RULE}")


def run_window(intake, blocks, loop: dict, seconds: float, tracer=None,
               source: str = "the traffic file"):
    """Hand `blocks` over until the window closes. Returns the records
    of the blocks handed in (or due), the window's start and end. A
    block that crosses the window's end closes it, the last of the
    supply too; `WindowRanDry` where that one is done before the end."""
    records = []
    closed = loop["kind"] == "closed"
    interval = None if closed else loop["interval_ms"] / 1000.0
    stats = intake.stats()
    t0 = time.perf_counter()
    i = 0
    while True:
        if i >= len(blocks):
            raise WindowRanDry(i, sum(r.n_tx for r in records),
                               time.perf_counter() - t0, seconds, source)
        block = blocks[i]
        rec = BlockRecord(block.header.number, len(block.data.data))
        due = time.perf_counter() if closed else t0 + i * interval
        if not closed:
            if due >= t0 + seconds:
                break
            wait = due - time.perf_counter()
            if wait > 0:
                if tracer is not None:
                    with tracer.span("await_next_block"):
                        time.sleep(wait)
                else:
                    time.sleep(wait)
        rec.due = due
        records.append(rec)
        if tracer is not None:
            tracer.before_block(rec, t0 + seconds)
        rec.start = time.perf_counter()
        if tracer is not None:
            with tracer.span("bench.block"):
                intake.hand_over(block)
        else:
            intake.hand_over(block)
        rec.done = time.perf_counter()
        after = intake.stats()
        rec.served = served_by_device(stats, after)
        rec.dispatches = dispatches(stats, after)
        stats = after
        i += 1
        if closed and rec.done - t0 >= seconds:
            break
    t1 = time.perf_counter()
    if not closed:
        # blocks due inside the window that were never handed in count
        # at their age when it closed
        while t0 + i * interval < t0 + seconds and i < len(blocks):
            rec = BlockRecord(blocks[i].header.number,
                              len(blocks[i].data.data))
            rec.due = t0 + i * interval
            records.append(rec)
            i += 1
    return records, t0, max(t1, t0 + (0 if closed else seconds))


def supply_used(supplied: int, handed: int, source: str) -> dict:
    """How near a closed loop came to the end of its backlog, for the
    result's `info`; past `SUPPLY_WARN_SHARE` a warning that names the
    file and its rule."""
    share = handed / supplied
    if share > SUPPLY_WARN_SHARE:
        log(f"WARNING: the window used {handed} of the {supplied} "
            f"blocks that {source} supplies ({share:.2f} of them, over "
            f"{SUPPLY_WARN_SHARE}); at 1.0 a run prints no result: "
            f"{SUPPLY_RULE}")
    return {"supply_blocks": supplied, "window_blocks": handed,
            "supply_used_share": share}


def end_to_end(manifest, cell_name: str, records, t0, t1, loop: dict,
               setup_s: float) -> dict:
    """The cell's end-to-end metrics, under the names BENCHMARK.json
    gives them: the set-up time, and the rate of a closed loop (unit
    tx/s) or the tail of an open one (unit ms)."""
    out = {}
    for m in manifest["end_to_end"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        if m["name"] == "setup_s":
            value = setup_s
        elif loop["kind"] == "closed" and m["unit"] == "tx/s":
            value = sum(r.n_tx for r in records) / (t1 - t0)
        elif loop["kind"] == "open" and m["unit"] == "ms":
            value = percentile(
                [((r.done if r.done is not None else t1) - r.due) * 1e3
                 for r in records], 0.95)
        else:
            raise ValueError(f"no way to measure {m['name']} in {cell_name}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---- correctness -----------------------------------------------------------

def compare(intake, plans, chain_hashes, records, material, config, seed,
            controls=()):
    """Every block reported done is read back from the peer and held to
    the plain reference. Returns ({name: [number, limit]}, info). Each
    of `controls` is the reference with one guarantee broken, put in
    the program's place: its flags and state are held to the sound
    reference the same way, under `control.<name>.*`."""
    import random
    rng = random.Random(seed ^ 0xC0FFEE)
    done = sorted(r.number for r in records if r.done is not None)
    last = done[-1] if done else 0
    by_number = {p.number: p for p in plans}
    block_txs = len(plans[0].txs)
    n_sample = max(1, REFERENCE_SIGNATURES // (3 * block_txs))
    sample = {last} | set(rng.sample(done, min(n_sample - 1, len(done))))
    orgs = [o.mspid for o in material.orgs]
    need = int(config["chaincode"]["endorsements_needed"])
    channel = sut.CHANNEL
    verifier = reference.Verifier(material.trust_roots)
    model = reference.LedgerModel()
    ctl_verifier = {c: reference.Verifier(
        material.trust_roots, accept_high_s=c == "accept_high_s")
        for c in controls}
    ctl_model = {c: reference.LedgerModel(skip_mvcc=c == "skip_mvcc")
                 for c in controls}
    ctl_flag_bad = dict.fromkeys(controls, 0)

    def mismatches(got: bytes, want: bytes) -> int:
        return sum(1 for a, b in zip(got, want) if a != b) + \
            abs(len(got) - len(want))

    missing = hash_bad = flag_bad = reverified = 0
    for number in range(1, last + 1):
        plan = by_number[number]
        got = intake.read_block(number)
        parsed = None
        if got is not None and number in sample:
            # the sample: what the peer stored, parsed and re-verified
            parsed = [reference.parse_tx(e) for e in got[2]]
            reverified += sum(1 + len(t.endorsements) for t in parsed)
            txs = [(reference.signatures_verdict(t, verifier, channel, orgs,
                                                 need),
                    tuple(t.reads), tuple(t.writes)) for t in parsed]
        else:
            txs = [(reference.ENDORSEMENT_POLICY_FAILURE if p.tamper
                    else reference.VALID, p.reads, p.writes)
                   for p in plan.txs]
        want = model.commit_block(number, txs)
        for c in controls:
            if parsed is not None:
                ctl = [(reference.signatures_verdict(
                    t, ctl_verifier[c], channel, orgs, need), r, w)
                    for t, (_, r, w) in zip(parsed, txs)]
            else:
                ctl = [(reference.VALID if (
                    c == "accept_high_s" and p.tamper
                    and p.tamper[0] == "high_s") else code, r, w)
                    for p, (code, r, w) in zip(plan.txs, txs)]
            ctl_flag_bad[c] += mismatches(
                ctl_model[c].commit_block(number, ctl), want)
        if got is None:
            missing += 1
            continue
        prev_hash, dhash, envs, flags = got
        if (prev_hash != chain_hashes[number - 1]
                or dhash != reference.data_hash(envs)
                or reference.header_hash(number, prev_hash, dhash)
                != chain_hashes[number]):
            hash_bad += 1
        flag_bad += mismatches(flags, want)

    first = [synth.key_name(i) for i in range(20)]
    written = sorted(model.state)
    keys = first + rng.sample(written, min(STATE_READS - len(first),
                                         len(written)))
    state_bad = sum(1 for k in keys
                    if intake.read_state(config["chaincode"]["name"], k)
                    != model.value(k))
    numbers = {"flag_mismatches": [flag_bad, 0],
               "hash_mismatches": [hash_bad, 0],
               "state_mismatches": [state_bad, 0],
               "blocks_missing": [missing, 0]}
    for c in controls:
        numbers[f"control.{c}.flag_mismatches"] = [ctl_flag_bad[c], 0]
        numbers[f"control.{c}.state_mismatches"] = [sum(
            1 for k in keys if ctl_model[c].value(k) != model.value(k)), 0]
    info = {"blocks_compared": last, "signatures_reverified": reverified,
            "blocks_reverified": len(sample), "state_reads": len(keys)}
    return numbers, info


# ---- per-layer metrics -----------------------------------------------------

def per_layer(manifest, cell_name: str, ctx: dict) -> dict:
    """Each metric of BENCHMARK.json that lists this cell: its file
    `metrics/<name>.json` names a reader and its parameters; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        module, fn = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(
            "benchmark.readers." + module), fn)
        value = reader(ctx, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---- main ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="comma-separated, of " + ", ".join(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--compile-only", action="store_true",
                    help="what a run starts in a child where the compile "
                         "cache is empty: compile the provider's programs "
                         "into it, measure nothing")
    ap.add_argument("--workers", type=int, default=WORKERS,
                    help="signing processes; 0 signs in this process")
    args = ap.parse_args(argv)

    unknown = set(args.control.split(",")) - set(CONTROLS) - {""}
    if unknown:
        ap.error(f"unknown control(s) {sorted(unknown)}")
    import fabric_tpu.protos  # noqa: F401  (no program, no run)
    manifest, cell, config, traffic = load_cell(args.workload)
    if args.compile_only:
        return compile_only(cell, config)
    rc, result = execute(manifest, cell, config, traffic, args)
    if rc == 0:
        print(json.dumps(result), flush=True)
    return rc


def compile_cache_dir() -> str:
    """`common/jaxenv.py`'s one rule, without importing JAX."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".cache", "xla")


def compile_cache_is_empty() -> bool:
    try:
        with os.scandir(compile_cache_dir()) as it:
            return not any(e.name.endswith("-cache") for e in it)
    except FileNotFoundError:
        return True


def compile_only(cell, config) -> int:
    """Fill the persistent compile cache with what `csp.prewarm`
    compiles, in a process of its own. A process that has compiled
    these programs itself then runs every host-side step of block
    intake at about a third of the speed (PERF.md, Findings PR 26), so
    the measuring process only ever loads them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        log(f"need {cell['chips']} TPU chip(s); JAX found {len(devices)} x "
            f"{devices[0].platform!r}")
        return 3
    csp = sut.new_provider(config["bccsp"])
    csp.prewarm(wait_restore=True)
    log("compile-only: " + json.dumps(
        {k: csp.stats[k] for k in sut.COMPILE_COUNTERS}))
    return 0


def execute(manifest, cell, config, traffic, args):
    """One run of `cell`. Returns (exit code, result): 0 with the
    result to print; 3 where there is no chip or no native block prep;
    4 with what a rehearsal would have printed."""
    block_txs = int(config["orderer"]["BatchSize"]["MaxMessageCount"])
    # the mix's preload, then its warm-up blocks, go through the timed
    # entry before the window opens
    warm = synth.preload_blocks(traffic["transactions"], block_txs) + \
        int(traffic["warmup_blocks"])
    n_blocks = warm + blocks_needed(traffic, block_txs, args.seconds)
    traffic_file = f"benchmark/traffic/{cell['traffic']}.json"
    phases = {}

    data_root = os.path.join(ROOT, ".cache", "bench-data")
    os.makedirs(data_root, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="run-", dir=data_root)
    pool = None
    intake = None
    try:
        # -- plan the chain and start the signers before JAX is touched
        t = time.perf_counter()
        material = identities.generate(os.path.join(data_dir, "crypto"),
                                       args.seed, int(config["channel"]["orgs"]))
        plans = synth.plan_chain(args.seed, n_blocks, block_txs,
                                 traffic["transactions"],
                                 len(material.orgs))
        phases["plan_s"] = time.perf_counter() - t
        log(f"planned {n_blocks} blocks x {block_txs} txs; MVCC conflicts "
            f"per block {[p.conflicts for p in plans[:8]]}..., total "
            f"{sum(p.conflicts for p in plans)}")
        peers = [o.peer for o in material.orgs]
        jobs = []
        for p in plans:
            for lo in range(0, block_txs, JOB_TXS):
                jobs.append((sut.CHANNEL, config["chaincode"]["name"],
                             material.client, peers, p.txs[lo:lo + JOB_TXS]))
        t_synth = time.perf_counter()
        if args.workers > 0:
            pool = multiprocessing.get_context("spawn").Pool(
                min(args.workers, max(1, (os.cpu_count() or 2) - 2)))
            pending = pool.map_async(synth.build_envelopes, jobs,
                                     chunksize=1)

        # -- the device
        if not args.rehearse and compile_cache_is_empty():
            import subprocess
            t = time.perf_counter()
            log(f"no compiled program under {compile_cache_dir()}: "
                "compiling in a child first")
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--compile-only",
                 "--workload", cell["name"], "--seed", str(args.seed),
                 "--seconds", "0"], stdout=sys.stderr).returncode
            phases["compile_child_s"] = time.perf_counter() - t
            if rc != 0:
                log(f"the compiling child exited {rc}: no result")
                return 3, None
        t = time.perf_counter()
        import jax
        devices = jax.devices()
        dev = devices[0]
        if not args.rehearse and (dev.platform != "tpu"
                                  or len(devices) < int(cell["chips"])):
            log(f"need {cell['chips']} TPU chip(s); JAX found "
                f"{len(devices)} x {dev.platform!r}: no result")
            return 3, None
        phases["jax_init_s"] = time.perf_counter() - t

        from fabric_tpu import native
        t = time.perf_counter()
        if args.rehearse:
            from benchmark.standin import StandInProvider
            csp = StandInProvider()
        else:
            csp = sut.new_provider(config["bccsp"])
        if not native.available():
            log("the native block-prep library did not build: the "
                "validator would take its per-transaction path, which is "
                "not the path a node runs: no result")
            return 3, None
        if hasattr(csp, "prewarm"):
            csp.prewarm(wait_restore=True)
        phases["provider_prewarm_s"] = time.perf_counter() - t

        t = time.perf_counter()
        genesis = synth.genesis_block(sut.CHANNEL, material, {
            "BatchTimeout": config["orderer"]["BatchTimeout"],
            **config["orderer"]["BatchSize"]}, args.seed)
        intake = sut.Intake(data_dir, material, genesis, config, csp)
        phases["peer_s"] = time.perf_counter() - t

        # -- collect the chain
        t = time.perf_counter()
        if pool is not None:
            built = pending.get()
            pool.close()
            pool.join()
            pool = None
        else:
            built = [synth.build_envelopes(j) for j in jobs]
        phases["synth_wait_s"] = time.perf_counter() - t
        phases["synth_total_s"] = time.perf_counter() - t_synth
        orderer = synth.OrdererSigner(material.orderer, args.seed)
        chain_hashes = {0: reference.header_hash(
            0, b"", bytes(genesis.header.data_hash))}
        blocks = []
        per_block = len(jobs) // n_blocks
        t = time.perf_counter()
        for k, p in enumerate(plans):
            envs = [e for part in built[k * per_block:(k + 1) * per_block]
                    for e in part]
            block = orderer.assemble(p.number, chain_hashes[p.number - 1],
                                     envs)
            chain_hashes[p.number] = reference.header_hash(
                p.number, chain_hashes[p.number - 1],
                bytes(block.header.data_hash))
            blocks.append(block)
        del built
        phases["assemble_s"] = time.perf_counter() - t

        # -- warm up the cell's own shapes through the timed entry
        t = time.perf_counter()
        for block in blocks[:warm]:
            intake.hand_over(block)
        flush = getattr(csp, "flush_warm_tables", None)
        if flush is not None:
            flush(300.0)
        phases["warmup_s"] = time.perf_counter() - t
        before = intake.stats()

        tracer = None
        if args.trace:
            from benchmark import spans
            tracer = spans.Tracer(intake, os.path.join(data_dir, "trace"),
                                  min(TRACE_SECONDS, args.seconds),
                                  TRACE_BLOCKS)
        # the chain, the plans and the peer's start-up objects are here
        # to stay: keep them out of the collector's later passes, so
        # that a pass inside the window walks the window's garbage only
        import gc
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_PROCESS
        phases["setup_s"] = setup_s
        log("phases " + json.dumps({k: round(v, 3)
                                    for k, v in phases.items()}))

        # -- the window
        host0 = host_snapshot()
        records, t0, t1 = run_window(intake, blocks[warm:], traffic["loop"],
                                     args.seconds, tracer, traffic_file)
        log("host before/after the window " + json.dumps(
            [host0, host_snapshot()]))
        took = [r.done - r.start for r in records if r.done is not None]
        fifths = [took[k * len(took) // 5:(k + 1) * len(took) // 5]
                  for k in range(5)]
        log("seconds per block over the window, by fifths: " + json.dumps(
            [round(sum(f) / len(f), 4) for f in fifths if f]))
        trace = None
        if tracer is not None:
            trace = tracer.finish(need_device=not args.rehearse)
            log(f"trace of the last {tracer.traced_blocks} blocks stopped "
                f"in {tracer.trace_stop_s:.1f}s, read in "
                f"{tracer.trace_load_s:.1f}s; the profiler started "
                f"{tracer.trace_lead_s:.2f}s before the window's end")
        after = intake.stats()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:int(cell["chips"])])
        cold = after.get("compile_cold_total", 0) - \
            before.get("compile_cold_total", 0)
        compiles = after.get("compile_total", 0) - \
            before.get("compile_total", 0)
        attempted = len(records)
        failed = sum(1 for r in records if r.done is None or not r.served)
        log(f"window {t1 - t0:.3f}s: {attempted} blocks, "
            f"{sum(r.n_tx for r in records if r.done is not None)} txs done, "
            f"{failed} not served by the device or not done; compiles in "
            f"the window {compiles} (cold {cold})")

        metrics = end_to_end(manifest, cell["name"], records, t0, t1,
                             traffic["loop"], setup_s)
        result = {"correct": False, "attempted": attempted,
                  "failed": failed}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        if args.trace:
            ctx = {"cell": cell, "config": config, "traffic": traffic,
                   "records": records, "window": (t0, t1),
                   "stats_before": before, "stats_after": after,
                   "trace": trace, "device_kind": dev.device_kind,
                   "provider": csp}
            from benchmark import tracered
            bw = tracer.busy_and_window
            if bw is not None:      # a rehearsal's CPU has no device plane
                device["busy_s"], device["window_s"] = bw
                result["breakdown"] = {
                    "device_ops": tracered.op_times(trace),
                    "idle_gaps": tracered.idle_gaps(trace)}
            e2e = metrics
            metrics = per_layer(manifest, cell["name"], ctx)
            log("end-to-end numbers of this traced run (not reported): "
                + json.dumps({k: v["value"] for k, v in e2e.items()}))

        # -- held to the plain reference, once the window has closed
        t = time.perf_counter()
        numbers, info = compare(intake, plans, chain_hashes, records,
                                material, config, args.seed,
                                tuple(c for c in args.control.split(",")
                                      if c))
        info["reference_s"] = round(time.perf_counter() - t, 3)
        if traffic["loop"]["kind"] == "closed":
            info.update(supply_used(n_blocks - warm, len(records),
                                    traffic_file))
        correct = all(n <= limit for n, limit in numbers.values())
        result.update(correct=correct, metrics=metrics, device=device)
        result["info"] = info
        result["compared"] = {k: {"value": v[0], "limit": v[1]}
                              for k, v in numbers.items()}
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()
        if intake is not None:
            try:
                intake.close()
            except Exception as e:     # noqa: BLE001 (reported, not hidden)
                log(f"closing the peer failed: {e!r}")
        shutil.rmtree(data_dir, ignore_errors=True)

    log("compared " + json.dumps(info))
    for name, (value, limit) in numbers.items():
        print(f"{name} {value} limit {limit}", file=sys.stderr, flush=True)
    if args.rehearse:
        log("rehearsal: no result line. Would have printed: "
            + json.dumps(result))
        return 4, result
    return 0, result


if __name__ == "__main__":
    sys.exit(main())
