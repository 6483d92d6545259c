#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that `BCCSP.Default: TPU` still
starts on the chip.

    python chip_smoke.py            # one chip: phases `seam`, `node`
    python chip_smoke.py --mesh     # four chips: phase `mesh` only

Phases (one JSON object per phase on stdout, then the result line):

  seam  one child process: the provider alone, through the factory
        seam with `{"Default": "TPU"}` and nothing else, on one
        real-size block (10,240 transactions x (2-of-3 endorsements +
        creator) = 30,720 signatures, ~256-byte messages, a seeded
        share tampered), and one 2,048-lane batch under as many
        distinct keys as the provider's table pool has slots (13 as
        shipped: the widest channel the comb program serves). Verdicts
        must equal the sw provider's
        lane for lane, and the counters must prove the DEVICE served
        them, from the same compiled program.
  node  real processes, the README's quick start: one sw orderer, one
        peer with `BCCSP: {Default: TPU}` (the chip's only owner), one
        sw peer as the plain reference; 1,500 `assetcc` puts at the
        orderer's default block cutting (500 / 2s). Both peers must
        hold the same chain, flags and state, and the TPU peer's
        /metrics must show device dispatches and no fallbacks.
  mesh  (--mesh only) the seam batch on `Devices: 1`, then on every
        local chip, in two children; verdicts identical, lanes on
        every chip.

This parent never touches JAX: a chip belongs to one process at a
time, so each phase that needs it runs in a child, one after another.
Any failed check fails the run; nothing is caught and stepped over.
Without a TPU the platform check fails and no result line is printed.
`--rehearse` walks the same code on whatever backend there is (tiny
`--txs/--node-txs` on a CPU) and then exits 4 WITHOUT a result line:
a rehearsal is never a pass (neither is a single `--phase`).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

FORBIDDEN_ENV = ("FTPU_BLS_DEVICE",)
FALLBACK_COUNTERS = ("sw_fallbacks", "host_hash_fallbacks",
                     "degraded_batches", "ladder_batches")
DISPATCH_COUNTERS = ("comb_batches", "pipeline_batches")
BLOCK_TXS = 500             # orderer/blockcutter.py MaxMessageCount
BATCH_TIMEOUT = "2s"        # orderer/blockcutter.py BatchTimeout
CHILD_TIMEOUT_S = 1100      # one JAX-owning child, cold compiles included
SETUP_TIMEOUT_S = 900       # a cold TPU peer's prewarm


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# phase `seam` (and the two `mesh` children) — runs in a CHILD process
# ---------------------------------------------------------------------------

WIDE_LANES = 2048       # the wide batch: one span


def make_items(seed: int, txs: int, n_endorsers: int = 3):
    """`n_endorsers` endorser keys + 1 creator key; per transaction a
    creator signature and 2 endorsements over ~256-byte messages. A
    seeded ~3% of lanes is tampered in each of the differential
    tests' ways (tests/test_bccsp.py `_corpus`). Keys, messages and
    tamper positions come from `seed`."""
    from cryptography.hazmat.primitives.asymmetric import ec

    from fabric_tpu.bccsp import utils
    from fabric_tpu.bccsp.bccsp import (ECDSAPrivateKeyImportOpts,
                                        VerifyItem)
    from fabric_tpu.bccsp.sw import SWProvider

    rng = random.Random(seed)
    sw = SWProvider()
    keys = [sw.key_import(
        ec.derive_private_key(rng.randrange(1, utils.P256_N),
                              ec.SECP256R1()),
        ECDSAPrivateKeyImportOpts()) for _ in range(n_endorsers + 1)]
    endorsers, creator = keys[:-1], keys[-1]
    items, tampered = [], 0
    for t in range(txs):
        signers = [creator] + rng.sample(endorsers, 2)
        for k in signers:
            msg = rng.randbytes(rng.randrange(232, 248))
            sig = sw.sign(k, hashlib.sha256(msg).digest())
            pub = k.public_key()
            roll = rng.random()
            if roll < 0.03:
                tampered += 1
                how = int(roll * 400) % 4
                if how == 0:        # bad signature: message changed
                    msg = msg + b"!"
                elif how == 1:      # wrong key
                    pub = keys[(keys.index(k) + 1)
                               % len(keys)].public_key()
                elif how == 2:      # high-S twin
                    r, s = utils.unmarshal_signature(sig)
                    sig = utils.marshal_signature(r, utils.P256_N - s)
                else:               # malformed DER
                    sig = sig[:-2]
            items.append(VerifyItem(key=pub, signature=sig, message=msg))
    return items, tampered


def seam_child(args) -> dict:
    set_env = [k for k in FORBIDDEN_ENV if k in os.environ]
    check(not set_env, f"{set_env} set: an inherited override would "
          "turn the kernels into their references")
    import jax

    from fabric_tpu import native
    from fabric_tpu.bccsp import factory
    from fabric_tpu.bccsp.sw import SWProvider
    from fabric_tpu.common import jaxenv
    from fabric_tpu.ops import comb

    dev = jax.devices()[0]
    out: dict = {"phase": args.child, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    if not args.rehearse:
        check(dev.platform == "tpu",
              f"JAX found no TPU (platform {dev.platform!r})")

    t0 = time.perf_counter()
    items, tampered = make_items(args.seed, args.txs)
    want = SWProvider().verify_batch(items)
    out.update(lanes=len(items), tampered=tampered,
               sw_accepts=sum(want),
               data_and_sw_s=round(time.perf_counter() - t0, 3),
               native_prep=native.available())

    cfg: dict = {"Default": "TPU"}
    if args.devices is not None:
        cfg["TPU"] = {"Devices": args.devices}
    if args.warm_keys_dir:
        cfg.setdefault("TPU", {})["WarmKeysDir"] = args.warm_keys_dir
    prov = factory.new_bccsp(factory.FactoryOpts.from_config(cfg))
    out["compile_cache"] = jaxenv.cache_dir()
    ndev = prov.stats["shard_devices"]

    # set-up, part 1: the 16-bit G table (its own jit, outside the
    # provider's compile seam)
    t0 = time.perf_counter()
    if prov._g16_enabled():
        jax.block_until_ready(comb.g16_tables())
    out["g16_build_s"] = round(time.perf_counter() - t0, 3)

    # set-up, part 2: what a node does before its first block —
    # prewarm's programs through the AOT seam: loaded from the store
    # of compiled executables on a second start on this machine,
    # lowered and compiled (or loaded from the persistent cache) and
    # written there on a first
    t0 = time.perf_counter()
    prov.prewarm(wait_restore=True)
    out["prewarm_s"] = round(time.perf_counter() - t0, 3)

    # set-up, part 3: the cold call (Q tables; a program prewarm did
    # not name would compile here)
    t0 = time.perf_counter()
    got = prov.verify_batch(items)
    out["cold_call_s"] = round(time.perf_counter() - t0, 3)
    check(got == want, "cold verdicts differ from sw")
    out["compiles"] = [
        {k: ev[k] for k in ("kind", "seconds", "cache_hit", "source",
                            "aot")}
        for ev in prov._devicecost.events]
    cold = dict(prov.stats)

    warm_s, served_by = [], []
    for _ in range(3):
        before = dict(prov.stats)
        t0 = time.perf_counter()
        got = prov.verify_batch(items)
        warm_s.append(round(time.perf_counter() - t0, 3))
        check(got == want, "warm verdicts differ from sw")
        moved = [c for c in DISPATCH_COUNTERS
                 if prov.stats[c] > before[c]]
        check(moved, "no device dispatch counter moved on a warm call")
        served_by.append(moved)
    # the wide batch: a key a slot of the pool, one span. Its cold call
    # admits the keys the block above did not bring (a slab build and a
    # pool write each, the block's own keys evicted for the last of
    # them); its warm calls find every key in its slot. The same
    # compiled program serves it: no compile from here on.
    wide, _ = make_items(args.seed + 1,
                         min(WIDE_LANES // 3, max(args.txs, 16)),
                         prov.stats["key_slot_capacity"] - 1)
    wide_want = SWProvider().verify_batch(wide)
    wide_s = []
    for _ in range(3):
        before = dict(prov.stats)
        t0 = time.perf_counter()
        got_wide = prov.verify_batch(wide)
        wide_s.append(round(time.perf_counter() - t0, 3))
        check(got_wide == wide_want, "wide-batch verdicts differ from sw")
        check(prov.stats["comb_batches"] > before["comb_batches"],
              "the wide batch did not go to the comb program")
    built = prov.stats["key_slot_builds"] - cold["key_slot_builds"]
    out["wide"] = {
        "lanes": len(wide),
        "keys": len({(it.key.x, it.key.y) for it in wide}),
        "cold_call_s": wide_s[0], "warm_call_s": wide_s[1:],
        "key_slot_builds": built,
        "slab_build_s": round((wide_s[0] - min(wide_s[1:]))
                              / max(built, 1), 3)}
    st = prov.stats
    out.update(
        warm_call_s=warm_s, served_by=served_by,
        warm_compiles=st["compile_total"] - cold["compile_total"],
        health=prov.health(),
        path={"q16": prov._g16_enabled(), "chunk": prov._chunk,
              "pipeline_span": prov._pipeline_span(),
              "bucket": prov._bucket(len(items))},
        stats={k: st[k] for k in FALLBACK_COUNTERS + DISPATCH_COUNTERS
               + ("key_slots_resident", "key_slot_capacity",
                  "key_slot_builds", "key_slot_evictions",
                  "key_slot_lookups", "key_slot_hits",
                  "key_table_bytes", "compile_total",
                  "compile_cold_total", "compile_cache_hits",
                  "executable_store_hits", "executable_store_misses",
                  "executable_store_errors",
                  "compile_seconds", "shard_devices",
                  "shard_dispatches", "host_hashed_lanes")},
        shard_lanes=list(prov.shard_stats.get("lanes") or []),
        verdict_sha256=hashlib.sha256(bytes(got)).hexdigest())
    ms = getattr(dev, "memory_stats", lambda: None)() or {}
    out["peak_bytes_in_use"] = ms.get("peak_bytes_in_use")
    if args.warm_keys_dir:
        t0 = time.perf_counter()
        prov.flush_warm_tables()
        out["warm_keys"] = {
            "flush_s": round(time.perf_counter() - t0, 3),
            "files": len([n for n in os.listdir(args.warm_keys_dir)
                          if n.endswith(".npy")]),
            "persist_failures": st["warm_table_persist_failures"]}

    for c in FALLBACK_COUNTERS:
        check(st[c] == 0, f"{c} = {st[c]}: a host path served lanes")
    check(out["health"] == "device", f"health() = {out['health']!r}")
    check(out["warm_compiles"] == 0,
          f"{out['warm_compiles']} compile(s) during the warm calls")
    check(st["executable_store_errors"] == 0,
          f"{st['executable_store_errors']} executable store entries "
          "not served or not written")
    check(args.rehearse or st["executable_store_hits"]
          + st["executable_store_misses"] > 0,
          "prewarm asked the executable store for nothing")
    check(st["key_slots_resident"] >= out["wide"]["keys"],
          f"{st['key_slots_resident']} keys resident after a batch of "
          f"{out['wide']['keys']}")
    # the first block's 4 keys and the wide batch's: least recently
    # used keys go only where the pool has no slot left
    evictions = max(0, 4 + out["wide"]["keys"] - st["key_slot_capacity"])
    check(st["key_slot_evictions"] == evictions,
          f"{st['key_slot_evictions']} keys evicted, {evictions} "
          "expected")
    if not args.rehearse:
        check(out["path"]["q16"], "q16 tables resolved off on a TPU")
    if ndev > 1:
        check(st["shard_dispatches"] > 0, "no sharded dispatch")
        check(len(out["shard_lanes"]) == ndev
              and all(x > 0 for x in out["shard_lanes"]),
              f"lanes per device {out['shard_lanes']}: not every chip "
              "took lanes")
        check("degraded_mesh" not in out["health"], out["health"])
    return out


# ---------------------------------------------------------------------------
# phase `node` — runs in the PARENT (gRPC + HTTP only, no JAX)
# ---------------------------------------------------------------------------

def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode()


def scrape(ops_port: int) -> dict:
    """/metrics as {series-with-labels: float}."""
    out = {}
    for line in _get(f"http://127.0.0.1:{ops_port}/metrics").splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            try:
                out[name] = float(val)
            except ValueError:
                pass
    return out


def wait_for(cond, timeout: float, what: str, step: float = 0.5):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            got = cond()
            if got:
                return got
        except Exception as e:          # noqa: BLE001 (retried, then raised)
            last = e
        time.sleep(step)
    raise CheckFailed(f"timed out after {timeout:.0f}s waiting for "
                      f"{what}" + (f" ({last!r})" if last else ""))


def maps_libtpu(pid: int) -> bool:
    """Whether process `pid` has libtpu mapped — a process that never
    loaded it cannot have initialised the TPU backend."""
    with open(f"/proc/{pid}/maps") as f:
        return "libtpu" in f.read()


def node_phase(args, root: str) -> dict:
    sys.path.insert(0, os.path.join(HERE, "tests"))
    import nwo

    from fabric_tpu import native
    from fabric_tpu.cmd.peer import _load_signer
    from fabric_tpu.comm import (BroadcastClient, DeliverClient,
                                 GatewayClient, channel_to)
    from fabric_tpu.peer.deliverclient import seek_envelope
    from fabric_tpu.protos import common
    from fabric_tpu.protoutil import protoutil as pu

    # the peers load the library the parent just built: with it their
    # block validation takes the native prep + prepared-array path
    out: dict = {"phase": "node", "txs": args.node_txs,
                 "native_prep": native.available()}
    t_phase = time.perf_counter()
    net = nwo.Network(os.path.join(root, "net"), n_orderers=1,
                      batch_timeout=BATCH_TIMEOUT,
                      max_message_count=BLOCK_TXS)
    tpu_peer, sw_peer = ("org1", 0), ("org2", 0)
    try:
        net.start_orderer(0)
        nwo.wait_http(f"http://127.0.0.1:{net.orderer_ports[0][1]}"
                      "/healthz")
        net.start_peer(*tpu_peer, bccsp={"Default": "TPU"})
        net.start_peer(*sw_peer)
        ops = {p: net.peer_ports[p][1] for p in (tpu_peer, sw_peer)}
        for p in ops:
            nwo.wait_http(f"http://127.0.0.1:{ops[p]}/healthz",
                          timeout=120)
        net.join_all()

        # set-up is done when the TPU peer's background prewarm says
        # so (stats["prewarm_done"] -> bccsp_prewarm_done): from then
        # on a compile is an unplanned shape on the serving path
        t0 = time.perf_counter()
        wait_for(lambda: scrape(ops[tpu_peer]).get(
            "bccsp_prewarm_done") == 1.0, SETUP_TIMEOUT_S,
            "the TPU peer's prewarm (bccsp_prewarm_done)", step=2.0)
        out["peer_setup_s"] = round(time.perf_counter() - t0, 1)
        health = json.loads(_get(
            f"http://127.0.0.1:{ops[tpu_peer]}/healthz"))["components"]
        out["tpu_peer_health"] = health
        if not args.rehearse:
            check(health.get("bccsp_device", "").startswith("tpu:"),
                  f"TPU peer runs on {health.get('bccsp_device')!r}")

        gw = {p: GatewayClient(
            channel_to(f"127.0.0.1:{net.peer_ports[p][0]}"),
            _load_signer(*net.peer_cli_identity(p[0])[1::2]),
            timeout_s=120.0) for p in (tpu_peer, sw_peer)}
        ch = net.channel

        def put(i: int):
            # both orgs endorse, by name: creator + 2 endorsements =
            # 3 signatures per transaction. Retried while gossip
            # membership (the gateway's endorser pool) settles.
            return wait_for(lambda: gw[sw_peer].endorse(
                ch, "assetcc", [b"put", f"k{i}".encode(),
                                str(i + args.seed).encode()],
                endorsing_organizations=("Org1MSP", "Org2MSP")),
                120, f"endorsement of put k{i} by both orgs", 1.0)

        # the first transaction goes alone and is retried: raft
        # election and gossip membership may still be settling (a
        # sub-MinBatch block, served by sw BY DESIGN — which is why
        # the counters below, not the verdicts, make this phase mean
        # something)
        def first():
            tx_id, env = put(0)
            gw[sw_peer].submit(ch, tx_id, env)
            return gw[sw_peer].commit_status(ch, tx_id, 60.0) == 0
        wait_for(first, 180, "the first transaction to commit", 2.0)
        warm = scrape(ops[tpu_peer])

        # endorse everything first (16 concurrent clients), then
        # submit it all at once, so the orderer cuts FULL blocks
        # instead of one per batch timeout...
        t0 = time.perf_counter()
        with ThreadPoolExecutor(16) as pool:
            prepared = list(pool.map(put, range(1, args.node_txs + 1)))
        out["endorse_s"] = round(time.perf_counter() - t0, 1)

        # ...as ONE Broadcast stream to the orderer (the ordering
        # service's own client API): per-transaction Submit calls
        # through a peer's gateway arrive at ~250/s here, which the
        # block cutter's 2 s timer beats to 500 now and then
        t0 = time.perf_counter()
        acks = BroadcastClient(
            channel_to(f"127.0.0.1:{net.orderer_ports[0][0]}"),
            timeout_s=300.0).process_messages(
                env for _, env in prepared)
        check(len(acks) == args.node_txs and all(
            a.status == common.Status.SUCCESS for a in acks),
            "the orderer refused envelopes")
        out["submit_s"] = round(time.perf_counter() - t0, 1)

        t0 = time.perf_counter()
        codes = [gw[sw_peer].commit_status(ch, tx_id, 300.0)
                 for tx_id, _ in prepared]
        check(all(c == 0 for c in codes),
              f"{sum(1 for c in codes if c)} transactions invalid "
              f"(validation codes {sorted(set(codes))})")

        def height(p):
            return int(scrape(ops[p]).get(
                f'ledger_blockchain_height{{channel="{ch}"}}', 0))
        want_h = height(sw_peer)
        wait_for(lambda: height(tpu_peer) >= want_h, 300,
                 "the TPU peer to reach the sw peer's height")
        out["commit_s"] = round(time.perf_counter() - t0, 1)
        out["height"] = want_h

        # same chain, same flags, same state on both peers
        chains = {}
        for p in (tpu_peer, sw_peer):
            signer = _load_signer(*net.peer_cli_identity(p[0])[1::2])
            dc = DeliverClient(
                channel_to(f"127.0.0.1:{net.peer_ports[p][0]}"))
            env = seek_envelope(ch, 0, signer, stop=want_h - 1)
            rows = []
            for resp in dc.handle(env):
                if resp.WhichOneof("type") != "block":
                    continue
                b = resp.block
                flags = bytes(b.metadata.metadata[
                    common.BlockMetadataIndex.TRANSACTIONS_FILTER])
                rows.append((b.header.number,
                             pu.block_header_hash(b.header).hex(),
                             flags.hex(), len(b.data.data)))
            chains[p] = rows
        check(len(chains[tpu_peer]) == want_h,
              f"TPU peer delivered {len(chains[tpu_peer])} blocks")
        check(chains[tpu_peer] == chains[sw_peer],
              "block hashes / validation flags differ between peers")
        sizes = [n for *_, n in chains[tpu_peer]]
        out["block_txs"] = sizes
        full = sum(1 for n in sizes if n == BLOCK_TXS)
        out["full_blocks"] = full
        check(full >= args.node_txs // BLOCK_TXS,
              f"{full} full blocks of {BLOCK_TXS}: the orderer cut "
              f"{sizes}")
        reads = {}
        for i in random.Random(args.seed).sample(
                range(args.node_txs + 1), min(5, args.node_txs)):
            vals = [gw[p].evaluate(ch, "assetcc",
                                   [b"get", f"k{i}".encode()]).payload
                    for p in (tpu_peer, sw_peer)]
            check(vals[0] == vals[1] == str(i + args.seed).encode(),
                  f"read k{i}: {vals}")
            reads[f"k{i}"] = vals[0].decode()
        out["reads"] = reads

        # which path served the TPU peer's blocks (the stats poller
        # publishes every 5 s: wait for the counters to land)
        def moved():
            m = scrape(ops[tpu_peer])
            d = sum(m.get(f"bccsp_{c}", 0) - warm.get(f"bccsp_{c}", 0)
                    for c in DISPATCH_COUNTERS)
            return m if d >= max(1, full) else None
        m = wait_for(moved, 30, "bccsp_* dispatch counters to move by "
                     "the number of full blocks", 1.0)
        out["tpu_peer_metrics"] = {
            k: v for k, v in m.items() if k.startswith((
                "bccsp_device_info", "bccsp_compile",
                "bccsp_executable_store", "bccsp_key_slot",
                "bccsp_prewarm")) or k in [
                f"bccsp_{c}" for c in FALLBACK_COUNTERS
                + DISPATCH_COUNTERS]}
        for c in FALLBACK_COUNTERS:
            check(m.get(f"bccsp_{c}") == 0, f"TPU peer bccsp_{c} = "
                  f"{m.get(f'bccsp_{c}')}")
        # a program prewarm made ready ahead of time is registered for
        # its shape, so its first dispatch compiles and loads nothing;
        # a COLD compile after set-up is an unplanned shape. On a CPU
        # rehearsal the tight power-of-two buckets make one (see
        # TPUProvider._floor), so only a real run checks it.
        out["cold_compiles_after_setup"] = int(
            m["bccsp_compile_cold_total"]
            - warm["bccsp_compile_cold_total"])
        out["cache_loads_after_setup"] = int(
            m["bccsp_compile_cache_hits"]
            - warm["bccsp_compile_cache_hits"])
        check(args.rehearse or out["cold_compiles_after_setup"] == 0,
              f"{out['cold_compiles_after_setup']} cold compile(s) on "
              "the serving path after set-up")
        check(m.get("bccsp_executable_store_errors") == 0,
              "TPU peer bccsp_executable_store_errors = "
              f"{m.get('bccsp_executable_store_errors')}")
        health = json.loads(_get(
            f"http://127.0.0.1:{ops[tpu_peer]}/healthz"))["components"]
        check(health.get("bccsp") == "device", f"healthz {health}")

        # exactly one process holds the accelerator
        holders = [name for name, node in net.nodes.items()
                   if maps_libtpu(node.proc.pid)]
        out["libtpu_mapped_by"] = holders
        if not args.rehearse:
            check(holders == ["peer_org1_0"],
                  f"processes that loaded libtpu: {holders}")
    except BaseException:
        for name, node in net.nodes.items():
            try:
                with open(node.log_path, "rb") as f:
                    tail = f.read()[-3000:].decode(errors="replace")
                print(f"--- {name} log tail ---\n{tail}",
                      file=sys.stderr)
            except OSError:
                pass
        raise
    finally:
        net.teardown()
        if args.logs_to:
            os.makedirs(args.logs_to, exist_ok=True)
            for node in net.nodes.values():
                shutil.copy(node.log_path, args.logs_to)
    alive = [n for n, node in net.nodes.items()
             if node.proc.poll() is None]
    check(not alive, f"still running after teardown: {alive}")
    out["phase_s"] = round(time.perf_counter() - t_phase, 1)
    return out


# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------

def run_child(args, name: str, devices=None) -> dict:
    """Run one JAX-owning phase as a child; relay its lines; return
    its phase object. A non-zero exit fails the run."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", name,
            "--seed", str(args.seed), "--txs", str(args.txs)]
    if devices is not None:
        argv += ["--devices", str(devices)]
    if args.rehearse:
        argv.append("--rehearse")
    if args.warm_keys_dir:
        argv += ["--warm-keys-dir", args.warm_keys_dir]
    t0 = time.perf_counter()
    proc = subprocess.run(argv, stdout=subprocess.PIPE, text=True,
                          timeout=CHILD_TIMEOUT_S)
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    last = (proc.stdout.strip().splitlines() or ["{}"])[-1]
    check(proc.returncode == 0, f"phase {name} exited "
          f"{proc.returncode}")
    obj = json.loads(last)
    obj["process_s"] = round(time.perf_counter() - t0, 1)
    return obj


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--txs", type=int, default=10240,
                    help="seam transactions (3 signatures each)")
    ap.add_argument("--node-txs", type=int, default=1500)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: run ONLY the mesh phase")
    ap.add_argument("--phase", choices=("seam", "node"),
                    help="run one phase only (no result line)")
    ap.add_argument("--rehearse", action="store_true",
                    help="skip the TPU checks; exits 4, never ok")
    ap.add_argument("--out", help="work directory (default: a "
                    "temporary one, removed afterwards)")
    ap.add_argument("--logs-to", help="copy the node logs here")
    ap.add_argument("--warm-keys-dir",
                    help="give the seam phase's provider this "
                         "BCCSP.TPU.WarmKeysDir (not part of the "
                         "bring-up check: it measures what persisting "
                         "every built slab costs)")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        out = {"phase": args.child, "ok": False}
        try:
            out = seam_child(args)
            out["ok"] = True
            return 0
        except CheckFailed as e:
            out["error"] = str(e)
            return 1
        finally:
            emit(out)

    # built from committed files: compile the host-prep library from
    # native/*.cpp BEFORE any child starts (a stale .so can ride along
    # in a copied tree, and three node processes must not race g++)
    from fabric_tpu import native
    native_ok = native._build()
    emit({"phase": "build", "native_batchprep_built": native_ok})

    root = args.out or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(root, exist_ok=True)
    device = None
    try:
        if args.mesh:
            one = run_child(args, "mesh_1dev", devices=1)
            allc = run_child(args, "mesh_alldev", devices=0)
            check(one["verdict_sha256"] == allc["verdict_sha256"],
                  "verdicts differ between Devices: 1 and the mesh")
            check(allc["stats"]["shard_devices"] == allc["device"]["count"]
                  and (args.rehearse or allc["device"]["count"] == 4),
                  f"mesh of {allc['stats']['shard_devices']} on "
                  f"{allc['device']['count']} devices")
            device = allc["device"]
        else:
            if args.phase in (None, "seam"):
                device = run_child(args, "seam")["device"]
            if args.phase in (None, "node"):
                emit(dict(node_phase(args, root), ok=True))
    finally:
        if not args.out:
            shutil.rmtree(root, ignore_errors=True)
    check("jax" not in sys.modules, "the parent imported JAX")
    if args.rehearse or args.phase:
        print("rehearsal / single phase: not a result", file=sys.stderr)
        return 4
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
