"""BASELINE config 3: block validation through the REAL tx pipeline.

Stands up an in-process 2-org network with a single-node etcdraft
orderer (real RaftChain: WAL, ready loop, block signing), endorses
`ntxs` transactions through the gateway (2 endorsements + 1 creator
signature each), orders them into one block, then times the peer-side
block pipeline — `Channel.process_block` = TxValidator (batched
verify) → pvt-data gather → kvledger commit — for BOTH a TPU-provider
peer and a sw-provider peer over the SAME ordered block.

Reference analog: `integration/e2e/e2e_test.go`; the timings mirror
"Validated block [n] in Tms" (`validator.go:262`) and the commit
breakdown (`kv_ledger.go:673-681`).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))


def run(tpu_csp, ntxs: int = 1024, endorsements: int = 2) -> dict:
    from fabric_tpu.bccsp.sw import SWProvider
    from fabric_tpu.core.chaincode import Chaincode, ChaincodeDefinition
    from fabric_tpu.core.chaincode import shim
    from fabric_tpu.internal import cryptogen
    from fabric_tpu.internal.configtxgen import (
        genesis_block,
        new_channel_group,
    )
    from fabric_tpu.msp import msp_config_from_dir
    from fabric_tpu.msp.mspimpl import X509MSP
    from fabric_tpu.orderer import raft as raft_mod
    from fabric_tpu.orderer.broadcast import BroadcastHandler
    from fabric_tpu.orderer.cluster import LocalClusterNetwork
    from fabric_tpu.orderer.multichannel import Registrar
    from fabric_tpu.peer import Peer
    from fabric_tpu.peer.gateway import Gateway
    from fabric_tpu.protos import transaction as txpb

    channel = "benchchannel"
    orderer_ep = "orderer0.example.com:7050"
    root = tempfile.mkdtemp(prefix="bench_e2e_")
    cdir = os.path.join(root, "crypto")
    # reuse crypto material across runs (beside the warm Q tables):
    # deterministic org keys mean the TPU-filtered orderer's persisted
    # tables match on the next run — restart-warm ordering instead of
    # a per-run table build
    warm_dir = os.environ.get(
        "BENCH_WARM_DIR",
        os.path.join(REPO_ROOT, ".cache", "warmkeys"))
    crypto_cache = os.path.join(warm_dir, "pipeline_crypto")
    import shutil
    if os.path.isdir(crypto_cache):
        shutil.copytree(crypto_cache, cdir)
        org1 = os.path.join(cdir, "peerOrganizations",
                            "org1.example.com")
        org2 = os.path.join(cdir, "peerOrganizations",
                            "org2.example.com")
        ordo = os.path.join(cdir, "ordererOrganizations",
                            "example.com")
    else:
        org1 = cryptogen.generate_org(cdir, "org1.example.com",
                                      n_peers=1, n_users=1)
        org2 = cryptogen.generate_org(cdir, "org2.example.com",
                                      n_peers=1, n_users=1)
        ordo = cryptogen.generate_org(cdir, "example.com",
                                      orderer_org=True)
        try:
            shutil.copytree(cdir, crypto_cache + ".tmp")
            os.replace(crypto_cache + ".tmp", crypto_cache)
        except Exception:                 # noqa: BLE001
            pass                          # cache miss next run; fine
    sw_csp = SWProvider()

    profile = {
        "Consortium": "SampleConsortium",
        "Capabilities": {"V2_0": True},
        "Application": {
            "Organizations": [
                {"Name": "Org1", "ID": "Org1MSP",
                 "MSPDir": os.path.join(org1, "msp")},
                {"Name": "Org2", "ID": "Org2MSP",
                 "MSPDir": os.path.join(org2, "msp")},
            ],
            "Capabilities": {"V2_0": True},
        },
        "Orderer": {
            "OrdererType": "etcdraft",
            "Addresses": [orderer_ep],
            # long timeout: submission of a full 10k-tx block takes
            # seconds; the cutter must cut on COUNT (one block), not
            # mid-submission timeouts
            "BatchTimeout": "30s",
            # bytes limits sized so MaxMessageCount governs: the point
            # is ONE ntxs-transaction block through the validator
            # (config 3's shape), not the blockcutter's byte policy
            "BatchSize": {"MaxMessageCount": ntxs,
                          "PreferredMaxBytes": 1 << 30,
                          "AbsoluteMaxBytes": 1 << 30},
            "Raft": {"Consenters": [
                {"Host": orderer_ep.split(":")[0], "Port": 7050}]},
            "Organizations": [
                {"Name": "OrdererOrg", "ID": "OrdererMSP",
                 "MSPDir": os.path.join(ordo, "msp"),
                 "OrdererEndpoints": [orderer_ep]}],
            "Capabilities": {"V2_0": True},
        },
    }
    genesis = genesis_block(channel, new_channel_group(profile))

    def local_msp(msp_dir, mspid):
        m = X509MSP(sw_csp)
        m.setup(msp_config_from_dir(msp_dir, mspid, csp=sw_csp))
        return m

    # ---- single-node raft ordering service ----
    net = LocalClusterNetwork()
    transport = net.register(orderer_ep)
    orderer_msp = local_msp(
        os.path.join(ordo, "orderers", "orderer0.example.com", "msp"),
        "OrdererMSP")
    # Two ordering services are measured: this one (sw filter — the
    # reference configuration) and, below, a TPU-filtered twin over
    # the same genesis. Both ride the WINDOWED ingest (one sig-filter
    # verify_batch + one consenter enqueue per 512-envelope window —
    # process_normal_msgs).
    registrar = Registrar(
        os.path.join(root, "orderer"),
        orderer_msp.get_default_signing_identity(), sw_csp,
        {"etcdraft": raft_mod.consenter(transport,
                                        tick_interval_s=0.03,
                                        election_tick=8)})
    registrar.join(genesis)
    broadcast = BroadcastHandler(registrar)

    class KV(Chaincode):
        def init(self, stub):
            return shim.success()

        def invoke(self, stub):
            fn, params = stub.get_function_and_parameters()
            stub.put_state(params[0], params[1].encode())
            return shim.success()

    # ---- two validating peers: TPU provider vs sw provider ----
    peers = {}
    for org_name, org_dir, mspid, csp in (
            ("org1", org1, "Org1MSP", tpu_csp),
            ("org2", org2, "Org2MSP", sw_csp)):
        msp = local_msp(
            os.path.join(org_dir, "peers",
                         f"peer0.{org_name}.example.com", "msp"), mspid)
        peer = Peer(os.path.join(root, f"peer_{org_name}"), msp, csp)
        peer.join_channel(genesis)
        peer.chaincode_support.register("bench", KV())
        peer.channel(channel).define_chaincode(
            ChaincodeDefinition(name="bench"))
        peers[org_name] = peer

    user_msp = local_msp(
        os.path.join(org1, "users", "User1@org1.example.com", "msp"),
        "Org1MSP")
    gw = Gateway(peers["org1"], broadcast,
                 user_msp.get_default_signing_identity())

    endorsing = list(peers.values())[:endorsements]

    print("pipeline: network up; endorsing", flush=True,
          file=sys.stderr)
    # ---- endorse everything first (CPU signing work, untimed) ----
    t0 = time.perf_counter()
    envs = [gw.endorse(channel, "bench",
                       [b"put", f"k{i}".encode(), f"v{i}".encode()],
                       endorsing_peers=endorsing)[0]
            for i in range(ntxs)]
    endorse_s = time.perf_counter() - t0

    print(f"pipeline: endorsed {ntxs} in {endorse_s:.1f}s; ordering",
          flush=True, file=sys.stderr)
    # ---- order through raft into one block ----
    # submission goes through the batched windowed ingest — the same
    # path the BroadcastStream gRPC handler drives (one sig-filter
    # verify_batch + one consenter enqueue per window)
    from fabric_tpu.protos import common as cpb

    def order_envs(bcast, reg, stall_s: float = 150.0):
        t0 = time.perf_counter()
        window = 512
        pos = 0
        deadline0 = time.monotonic() + 60
        while pos < len(envs):
            batch = envs[pos:pos + window]
            resps = bcast.process_messages(batch)
            ok = 0
            for resp in resps:
                if resp.status == cpb.Status.SUCCESS:
                    ok += 1
                elif resp.status == cpb.Status.SERVICE_UNAVAILABLE:
                    # raft still electing: retry the unaccepted tail
                    break
                else:
                    # permanent rejection (BAD_REQUEST/FORBIDDEN/...):
                    # retrying cannot help — fail fast with the info
                    raise RuntimeError(
                        f"broadcast rejected: {resp.status} "
                        f"{resp.info}")
            pos += ok
            if ok == 0:
                if time.monotonic() > deadline0:
                    raise RuntimeError("broadcast unavailable for 60s")
                time.sleep(0.05)
        ch = reg.get_chain(channel)
        deadline = time.monotonic() + stall_s
        while True:
            blks = [ch.ledger.block_store.get_block_by_number(n)
                    for n in range(1, ch.ledger.height)]
            done = (all(b is not None for b in blks) and
                    sum(len(b.data.data) for b in blks
                        if b is not None) >= ntxs)
            if done:
                return time.perf_counter() - t0, blks
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"ordering stalled at height {ch.ledger.height}")
            time.sleep(0.05)

    order_s, blocks = order_envs(broadcast, registrar)

    # ---- the SAME block ordered by a TPU-FILTERED orderer ----
    # a second single-node ordering service over the same genesis,
    # BCCSP = the TPU provider: the windowed sig filter verifies each
    # 512-envelope window on device. With crypto material and Q-table
    # bytes persisted across runs, its per-key-set table restores from
    # disk (warm restart) instead of rebuilding — the round-4 blocker.
    # Timed warm-included; round-4 kept the sw filter here and the
    # TPU-filter number was only a commit-message claim.
    order_tpu_s = None
    try:
        # the orderer's own provider pads every 512-envelope window to
        # the 4096-lane bucket the parent AOT-compiled: no fresh
        # device compiles inside the ordering timer (the padded lanes
        # are premasked; device time is ~flat in lane count here)
        from fabric_tpu.bccsp import factory as _bf
        orderer_csp = _bf.new_bccsp(_bf.FactoryOpts.from_config({
            "Default": "TPU",
            "TPU": {"MinBatch": 16, "BucketFloor": 4096,
                    "Chunk": 32768, "WarmKeysDir": warm_dir}}))
        net2 = LocalClusterNetwork()
        transport2 = net2.register(orderer_ep)
        registrar2 = Registrar(
            os.path.join(root, "orderer_tpu"),
            orderer_msp.get_default_signing_identity(), orderer_csp,
            {"etcdraft": raft_mod.consenter(transport2,
                                            tick_interval_s=0.03,
                                            election_tick=8)})
        registrar2.join(genesis)
        broadcast2 = BroadcastHandler(registrar2)
        # generous stall budget: a first-ever run may pay one K=1
        # pipeline compile + the creator-set table restore inside the
        # timer (both cached/persisted for every later run)
        order_tpu_s, _blocks2 = order_envs(broadcast2, registrar2,
                                           stall_s=900.0)
        registrar2.halt()
        transport2.close()
    except Exception as e:                # noqa: BLE001
        print(f"pipeline: tpu-filtered ordering failed: {e}",
              flush=True, file=sys.stderr)
    data_blocks = [b for b in blocks if b.data.data]
    nsigs = ntxs * (endorsements + 1)

    print(f"pipeline: ordered in {order_s:.1f}s; validating", flush=True,
          file=sys.stderr)
    # ---- peer-side pipeline: validate (repeatable) + commit (once) ----
    out: dict = {
        "ntxs": ntxs, "endorsements_per_tx": endorsements,
        "signatures": nsigs, "endorse_s": round(endorse_s, 2),
        "order_raft_s": round(order_s, 2),
        "order_tx_per_s": round(ntxs / order_s, 1),
        "blocks": len(data_blocks),
    }
    if order_tpu_s is not None:
        out["order_raft_tpu_filter_s"] = round(order_tpu_s, 2)
        out["order_tpu_filter_tx_per_s"] = round(ntxs / order_tpu_s, 1)
    for org_name, peer in peers.items():
        ch = peer.channel(channel)
        label = "tpu_peer" if org_name == "org1" else "sw_peer"
        # warm (compiles on the tpu peer), then best-of-3 validation
        for b in data_blocks:
            flags = ch.validator.validate(b)
            assert all(f == txpb.TxValidationCode.VALID for f in flags), \
                f"{label}: invalid flags {set(flags)}"
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for b in data_blocks:
                ch.validator.validate(b)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        t0 = time.perf_counter()
        for b in data_blocks:
            codes = ch.process_block(b)
            assert all(c == txpb.TxValidationCode.VALID for c in codes)
        commit_s = time.perf_counter() - t0
        out[label] = {
            "validate_s": round(best, 4),
            "validate_tx_per_s": round(ntxs / best, 1),
            "validate_sigs_per_s": round(nsigs / best, 1),
            "process_block_s": round(commit_s, 4),
            "commit_tx_per_s": round(ntxs / commit_s, 1),
        }
    registrar.halt()
    transport.close()
    return out


# ---------------------------------------------------------------------------
# Round-10 batched ordering: the wheel-free stub-seam harness.
#
# `run()` above exercises the REAL x509/MSP/channel-config stack and
# therefore needs the 'cryptography' wheel (cert generation); on hosts
# without it the ordering bottleneck would go unmeasured. The helpers
# below rebuild the same single-node etcdraft ordering service with
# ONLY those wheel-bound layers stubbed: real P-256 envelope
# signatures (pure-python backend), the real batched StandardChannel
# sig-filter over the provider's AdmissionWindow, the real
# blockcutter, RaftChain/RaftNode/WAL, BlockWriteStage and BlockWriter
# (signed blocks, batched self-verify). tests/test_order_pipeline.py
# drives the same harness deterministically.
# ---------------------------------------------------------------------------


def make_order_client(channel: str = "orderbench"):
    """Creator-side material for the stub ordering service: one REAL
    P-256 keypair, a protoutil-compatible signer, and an envelope
    factory. Pass the same client to twin services so an identical
    envelope stream can be replayed through both (bit-identity
    checks compare the resulting block streams)."""
    import hashlib
    import types

    from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem
    from fabric_tpu.bccsp.sw import SWProvider
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protoutil import protoutil as pu

    sw = SWProvider()
    key = sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
    pub = key.public_key()
    creator = b"order-bench-client"

    class _Signer:
        def serialize(self):
            return creator

        def sign(self, msg: bytes) -> bytes:
            return sw.sign(key, hashlib.sha256(msg).digest())

        def verify_item(self, msg: bytes, sig: bytes) -> VerifyItem:
            return VerifyItem(key=pub, signature=sig, message=msg)

    signer = _Signer()

    def envelope(i: int, payload: bytes = None) -> cpb.Envelope:
        ch = pu.make_channel_header(
            cpb.HeaderType.ENDORSER_TRANSACTION, channel,
            tx_id=f"obench{i}")
        sh = pu.create_signature_header(creator, pu.random_nonce())
        return pu.sign_or_panic(signer, pu.make_payload(
            ch, sh, payload if payload is not None
            else f"tx{i}".encode()))

    return types.SimpleNamespace(channel=channel, sw=sw, key=key,
                                 pub=pub, creator=creator,
                                 signer=signer, envelope=envelope)


def make_order_support(root: str, client=None, csp=None,
                       channel: str = "orderbench",
                       block_txs: int = 64,
                       batch_timeout_s: float = 30.0,
                       endpoints=("orderer0.example.com:7050",),
                       on_config=None):
    """A wheel-free `ChainSupport` twin: real OrdererLedger (block
    store + raft WAL keyspaces), real blockcutter, real BlockWriter
    (signed blocks, batched self-verify through `csp`), real
    StandardChannel whose batched sig-filter rides the provider's
    AdmissionWindow, and a real SignaturePolicy — only the
    x509/MSP/channel-config layers are replaced by a stub bundle whose
    consenter set is `endpoints`. A committed config block bumps the
    stub's config sequence (so later stale-seq envelopes exercise the
    batched revalidation path) and calls `on_config(support, block)` —
    the reconfiguration seam: mutate `support.orderer_config` there
    (e.g. rotate consenter certs). The returned support's `.chain` is
    None until a RaftChain is attached (see `make_order_service`)."""
    import hashlib
    import types

    from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem
    from fabric_tpu.bccsp.admission import AdmissionWindow
    from fabric_tpu.common.policies.cauthdsl import SignaturePolicy
    from fabric_tpu.orderer import blockcutter
    from fabric_tpu.orderer.blockwriter import BlockWriter
    from fabric_tpu.orderer.msgprocessor import StandardChannel
    from fabric_tpu.orderer.multichannel import OrdererLedger
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protos import configtx as ctxpb
    from fabric_tpu.protos import policies as polpb
    from fabric_tpu.protoutil import protoutil as pu

    if client is None:
        client = make_order_client(channel)
    sw = client.sw
    provider = csp if csp is not None else sw
    ingress = AdmissionWindow.shared(provider)

    # one orderer signing key per RIG, parked on the shared client:
    # snapshot catch-up verifies pulled-block signatures against the
    # block SOURCE, so every consenter of a multi-node bench cluster
    # must sign under the same (stub) orderer identity
    okey = getattr(client, "_bench_orderer_key", None)
    if okey is None:
        okey = sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
        try:
            client._bench_orderer_key = okey
        except Exception:
            pass
    opub = okey.public_key()

    class _OrdererSigner:
        def serialize(self):
            return b"order-bench-orderer"

        def sign(self, msg: bytes) -> bytes:
            return sw.sign(okey, hashlib.sha256(msg).digest())

        def verify_item(self, msg: bytes, sig: bytes) -> VerifyItem:
            return VerifyItem(key=opub, signature=sig, message=msg)

    class _Identity:
        def mspid(self):
            return "BenchMSP"

        def satisfies_principal(self, principal):
            return None

        def verify_item(self, msg: bytes, sig: bytes) -> VerifyItem:
            return VerifyItem(key=client.pub, signature=sig,
                              message=msg)

    class _Deserializer:
        def deserialize_identity(self, raw: bytes):
            if raw != client.creator:
                raise ValueError("unknown creator")
            return _Identity()

    def consensus_metadata(cert_suffix: bytes = b"") -> bytes:
        meta = ctxpb.ConsensusMetadata()
        for ep in endpoints:
            host, port = ep.rsplit(":", 1)
            c = meta.consenters.add()
            c.host, c.port = host, int(port)
            c.client_tls_cert = (b"stub-cert-" + ep.encode() +
                                 cert_suffix)
        return pu.marshal(meta)

    pol_env = polpb.SignaturePolicyEnvelope()
    pol_env.rule.signed_by = 0
    pol_env.identities.add()
    policy = SignaturePolicy(pol_env, _Deserializer(), ingress)

    class _PolicyManager:
        def get_policy(self, name):
            return policy

    orderer_cfg = types.SimpleNamespace(
        consensus_type="etcdraft",
        consensus_state=0,
        consensus_metadata=consensus_metadata(),
        consensus_metadata_fn=consensus_metadata,
        batch_size=types.SimpleNamespace(
            max_message_count=block_txs,
            absolute_max_bytes=1 << 30,
            preferred_max_bytes=1 << 20),
        batch_timeout_s=batch_timeout_s)
    bundle = types.SimpleNamespace(orderer=orderer_cfg,
                                   policy_manager=_PolicyManager())

    signer = _OrdererSigner()
    ledger = OrdererLedger(os.path.join(root, "ledger"))
    if ledger.height == 0:
        # deterministic stub genesis (twin services must agree on the
        # prev-hash of block 1): zeroed timestamp, empty nonce, no
        # signature — is_config_block only reads the channel header
        ch = pu.make_channel_header(cpb.HeaderType.CONFIG, channel)
        ch.timestamp = 0
        sh = pu.create_signature_header(signer.serialize(), b"")
        genesis = pu.new_block(0, b"")
        genesis.data.data.append(pu.marshal(cpb.Envelope(
            payload=pu.marshal(pu.make_payload(ch, sh,
                                               b"stub-genesis")))))
        genesis.header.data_hash = pu.block_data_hash(genesis.data)
        ledger.add_block(genesis)

    class _StubSupport:
        """ChainSupport duck-type over the stub bundle."""

        def __init__(self):
            self.channel_id = channel
            self.ledger = ledger
            self.signer = signer
            self.client = client
            self.orderer_config = orderer_cfg
            self.on_config = on_config
            self.chain = None
            self._sequence = 0
            self._last_config = 0
            self.cutter = blockcutter.Receiver(self._batch_config)
            self.writer = BlockWriter(
                ledger, signer,
                last_block=ledger.get_block(ledger.height - 1),
                csp=provider)
            self.ingress_csp = ingress
            self.processor = StandardChannel(channel, self)

        def bundle(self):
            return bundle

        def configtx_validator(self):
            return self   # duck-type: only .sequence() is consulted

        def sequence(self) -> int:
            return self._sequence

        @property
        def csp(self):
            return provider

        def _batch_config(self):
            bs = self.orderer_config.batch_size
            return blockcutter.BatchConfig(
                max_message_count=bs.max_message_count,
                absolute_max_bytes=bs.absolute_max_bytes,
                preferred_max_bytes=bs.preferred_max_bytes)

        @property
        def batch_timeout_s(self) -> float:
            return self.orderer_config.batch_timeout_s

        def write_block(self, block, consenter_metadata=b"") -> None:
            self.writer.write_block(
                block, consenter_metadata,
                last_config_number=self._last_config)

        def write_blocks(self, blocks,
                         consenter_metadata=b"") -> None:
            self.writer.write_blocks(
                blocks, consenter_metadata,
                last_config_number=self._last_config)

        def write_config_block(self, block,
                               consenter_metadata=b"") -> None:
            self.writer.write_block(
                block, consenter_metadata,
                last_config_number=block.header.number)
            self._last_config = block.header.number
            self._sequence += 1
            if self.on_config is not None:
                self.on_config(self, block)

        def verify_onboarded_span(self, blocks) -> tuple:
            """Snapshot catch-up verification over the stub MSP:
            numbering from the ledger tip, data-hash, prev-hash
            linkage, and every block signature against the rig's
            shared orderer identity in ONE batched dispatch (the stub
            has a single orderer principal, so the full policy
            re-derivation of the real ChainSupport collapses to
            that key)."""
            from fabric_tpu.orderer.onboarding import VerificationError
            height = self.ledger.height
            prev = None
            if height:
                prev = pu.block_header_hash(
                    self.ledger.get_block(height - 1).header)
            evals, items = [], []
            error = None
            for i, b in enumerate(blocks):
                number = height + i
                try:
                    if b.header.number != number:
                        raise VerificationError(
                            b.header.number,
                            f"out of order (expected {number})")
                    if b.header.data_hash != \
                            pu.block_data_hash(b.data):
                        raise VerificationError(
                            number, "data hash mismatch")
                    if prev is not None and \
                            b.header.previous_hash != prev:
                        raise VerificationError(
                            number, "previous-hash linkage broken")
                    lo, n = len(items), 0
                    if number > 0:
                        signed = pu.block_signature_set(b)
                        if not signed:
                            raise VerificationError(
                                number, "unsigned block")
                        for sd in signed:
                            if sd.identity != signer.serialize():
                                raise VerificationError(
                                    number, "unknown block signer")
                            items.append(signer.verify_item(
                                sd.data, sd.signature))
                        n = len(signed)
                except Exception as e:
                    error = e if isinstance(e, VerificationError) \
                        else VerificationError(number, str(e))
                    break
                evals.append((number, lo, n))
                prev = pu.block_header_hash(b.header)
            ok = provider.verify_batch(items) if items else []
            n_valid = 0
            for number, lo, n in evals:
                if not all(ok[lo:lo + n]):
                    error = VerificationError(
                        number, "block signature invalid")
                    break
                n_valid += 1
            return n_valid, error

        def commit_onboarded_block(self, block) -> None:
            """Commit one VERIFIED pulled block verbatim (it keeps the
            source's signatures) and resync the writer's tip."""
            if block.header.number != self.ledger.height:
                raise ValueError(
                    f"onboarding block {block.header.number} out of "
                    f"order (height {self.ledger.height})")
            self.ledger.add_block(block)
            self.writer.resync(block)
            if pu.is_config_block(block):
                self._last_config = block.header.number

        def close(self):
            self.ledger.close()

    return _StubSupport()


def make_order_service(root: str, client=None, csp=None,
                       channel: str = "orderbench",
                       block_txs: int = 64,
                       batch_timeout_s: float = 30.0,
                       endpoint: str = "orderer0.example.com:7050",
                       endpoints=None, net=None,
                       write_pipeline=None, start: bool = True,
                       tick_interval_s: float = 0.02,
                       election_tick: int = 8, on_config=None,
                       transport_wrap=None):
    """A raft ordering service over `make_order_support`: single-node
    by default, multi-consenter when `net` + `endpoints` are shared
    across calls. `start=False` leaves the ready loop unstarted so
    tests can drive the chain deterministically (tick/elect, feed
    `_process_order_window`, `_drain_ready`). `close(flush=False)` is
    crash-equivalent: the write stage is abandoned, committed-but-
    unwritten entries stay in the raft WAL and replay on the next
    service built over the same `root`."""
    import types

    from fabric_tpu.orderer.broadcast import BroadcastHandler
    from fabric_tpu.orderer.cluster import LocalClusterNetwork
    from fabric_tpu.orderer.raft.chain import RaftChain

    if net is None:
        net = LocalClusterNetwork()
    eps = tuple(endpoints) if endpoints else (endpoint,)
    support = make_order_support(
        root, client=client, csp=csp, channel=channel,
        block_txs=block_txs, batch_timeout_s=batch_timeout_s,
        endpoints=eps, on_config=on_config)
    transport = net.register(endpoint)
    if transport_wrap is not None:
        # round 15: the chaos seam — e.g. NetChaos.wrap_cluster puts
        # this consenter's outbound links under seeded network chaos
        transport = transport_wrap(transport)
    chain = RaftChain(support, transport,
                      tick_interval_s=tick_interval_s,
                      election_tick=election_tick,
                      write_pipeline=write_pipeline)
    support.chain = chain

    class _Registrar:
        def get_chain(self, cid):
            return support if cid == channel else None

    broadcast = BroadcastHandler(_Registrar())
    if start:
        chain.start()

    def close(flush: bool = True) -> None:
        try:
            if flush:
                chain.halt()
            else:
                # crash-sim: stop the loop without flushing the write
                # stage; its worker may be wedged mid-span — unwritten
                # blocks replay from the WAL at the next start
                chain._halted.set()
                try:
                    chain._events.put_nowait(None)
                except Exception:     # noqa: BLE001
                    pass
                if chain._thread is not None:
                    chain._thread.join(timeout=5)
        finally:
            try:
                transport.close()
            except Exception:         # noqa: BLE001
                pass
            support.close()

    return types.SimpleNamespace(support=support, chain=chain,
                                 transport=transport, net=net,
                                 broadcast=broadcast,
                                 client=support.client, close=close)


def _stage_tail(stage: str, which: str):
    """Rounded stage-quantile lookup shared by the bench rigs (the
    `*_p50_s`/`*_p99_s` stage-line fields)."""
    from fabric_tpu.common import tracing
    return tracing.stage_quantile(stage, which, ndigits=6)


def order_pipeline_run(csp=None, ntxs: int = 1024,
                       window: int = 256,
                       block_txs: int = 256,
                       trace_path: str = None) -> dict:
    """ISSUE 7 scenario: the batched raft ordering pipeline, wheel-free
    (stub x509/MSP seam, pure-python P-256 when the OpenSSL wheel is
    absent) so the bounded default bench can always report the
    ordering bottleneck. Stands up a REAL single-node etcdraft
    ordering service (WAL, ready loop, admission window, block-write
    stage, signed blocks), broadcasts `ntxs` creator-signed envelopes
    through the windowed ingest, and times `order_raft_s` from first
    submission to every block durable. The `order_vs_validate` ratio
    divides that by a peer-validation equivalent — ONE batched
    `verify_batch` over the same `ntxs` signatures on the same
    provider — so the driver sees how far ordering still trails
    validation (ROADMAP item 2's ~2x target), independent of how fast
    this host's crypto backend happens to be."""
    import shutil

    from fabric_tpu.bccsp import VerifyItem
    from fabric_tpu.common import clustertrace, tracing
    from fabric_tpu.protos import common as cpb

    root = tempfile.mkdtemp(prefix="bench_order_")
    svc = None
    commit_pipe = None
    try:
        # start from a clean recorder: this run's dump and stage
        # quantiles should describe THIS run, not earlier bench
        # sections sharing the process
        tracing.reset()
        clustertrace.reset()
        svc = make_order_service(root, csp=csp, block_txs=block_txs,
                                 batch_timeout_s=30.0)
        client = svc.client

        # ---- creator-signed envelopes (CPU signing, untimed):
        # `ntxs` for the timed run + one extra block's worth for the
        # untimed lifecycle PROBE below, so the timed denominator is
        # unchanged vs earlier rounds ----
        t0 = time.perf_counter()
        envs = [client.envelope(i) for i in range(ntxs + block_txs)]
        probe_envs, envs = envs[:block_txs], envs[block_txs:]
        sign_s = time.perf_counter() - t0

        # wait out the single-node election so the timed run measures
        # ordering, not retry sleeps
        deadline0 = time.monotonic() + 60
        while svc.chain.node.leader_id != svc.chain.node_id:
            if time.monotonic() > deadline0:
                raise RuntimeError("no raft leader after 60s")
            time.sleep(0.01)

        def pump(run, stop_deadline):
            """Broadcast `run` under per-window ingress spans (the
            broadcast_stream seam's round-14 shape: each window's
            trace context propagates into the order events)."""
            pos = 0
            while pos < len(run):
                with tracing.span("ingress.batch",
                                  envelopes=min(window,
                                                len(run) - pos)) as c:
                    if c is not None:
                        # first-ingress birth stamp (round 18): the
                        # e2e_commit_seconds observation at the
                        # commit leg measures from here
                        clustertrace.note_birth(c.trace_id)
                    resps = svc.broadcast.process_messages(
                        run[pos:pos + window])
                ok = 0
                for resp in resps:
                    if resp.status == cpb.Status.SUCCESS:
                        ok += 1
                    elif resp.status == \
                            cpb.Status.SERVICE_UNAVAILABLE:
                        break   # leadership wobble: retry tail
                    else:
                        raise RuntimeError(f"broadcast rejected: "
                                           f"{resp.status} "
                                           f"{resp.info}")
                pos += ok
                if ok == 0:
                    if time.monotonic() > stop_deadline:
                        raise RuntimeError(
                            "broadcast unavailable for 60s")
                    time.sleep(0.02)
            return c

        ledger = svc.support.ledger

        def wait_txs(want, deadline_s=600):
            deadline = time.monotonic() + deadline_s
            while True:
                blks = [ledger.get_block(n)
                        for n in range(1, ledger.height)]
                got = sum(len(b.data.data) for b in blks
                          if b is not None)
                if got >= want and all(b is not None for b in blks):
                    return blks
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"ordering stalled: {got}/{want} at height "
                        f"{ledger.height}")
                time.sleep(0.02)

        # ---- the lifecycle probe (untimed): ONE full block pushed
        # through ingress->order->write alone, so its trace_id links
        # a transaction end to end deterministically (the acceptance
        # trace); the commit pipeline below re-attaches the same
        # context for its validate/commit spans ----
        probe_ctx = pump(probe_envs, deadline0)
        wait_txs(len(probe_envs))
        probe_trace_id = probe_ctx.trace_id if probe_ctx else None

        # ---- the timed ordering run ----
        t0 = time.perf_counter()
        pump(envs, time.monotonic() + 60)
        blocks = wait_txs(len(probe_envs) + ntxs)
        order_s = time.perf_counter() - t0

        # ---- the peer-validation equivalent on the SAME provider ----
        provider = svc.support.csp
        items = [VerifyItem(key=client.pub, signature=e.signature,
                            message=e.payload) for e in envs]
        provider.verify_batch(items[:min(64, ntxs)])   # warm
        t0 = time.perf_counter()
        ok = provider.verify_batch(items)
        validate_s = max(time.perf_counter() - t0, 1e-9)
        if not all(ok):
            raise RuntimeError("validate-equivalent rejected lanes")

        # ---- validate+commit the ORDERED stream through the REAL
        # CommitPipeline (round 14): its commit.validate /
        # commit.commit spans complete the lifecycle — the probe
        # block submits under the probe's trace context, so one
        # trace_id now links ingress -> order.window -> order.propose
        # -> order.consensus -> order.write -> commit.validate ->
        # commit.commit in the dumped trace ----
        from fabric_tpu.core.commitpipeline import CommitPipeline
        from fabric_tpu.core.txvalidator import ValidationResult
        from fabric_tpu.protos import transaction as txpb
        from fabric_tpu.protoutil import protoutil as pu

        class _Validator:
            """Batched creator-signature verify per block on the same
            provider (the device-bound stage), deferred-publication
            contract matching the real TxValidator."""

            def validate_ahead(self, block, known_txids=None):
                v0 = time.perf_counter()
                vitems = []
                for env_bytes in block.data.data:
                    env = pu.unmarshal_envelope(env_bytes)
                    vitems.append(VerifyItem(key=client.pub,
                                             signature=env.signature,
                                             message=env.payload))
                vok = provider.verify_batch(vitems)
                codes = [txpb.TxValidationCode.VALID if o else
                         txpb.TxValidationCode.BAD_CREATOR_SIGNATURE
                         for o in vok]
                return ValidationResult(
                    codes=codes, n_items=len(vitems),
                    duration_s=time.perf_counter() - v0)

            def publish_validation(self, block, result):
                while len(block.metadata.metadata) <= \
                        cpb.BlockMetadataIndex.TRANSACTIONS_FILTER:
                    block.metadata.metadata.append(b"")
                block.metadata.metadata[
                    cpb.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
                    bytes(result.codes)

            def validate(self, block):
                result = self.validate_ahead(block)
                self.publish_validation(block, result)
                return result.codes

        class _BlockStore:
            @staticmethod
            def block_tx_ids(block):
                out = []
                for env_bytes in block.data.data:
                    try:
                        env = pu.unmarshal_envelope(env_bytes)
                        payload = pu.get_payload(env)
                        out.append(pu.get_channel_header(
                            payload).tx_id)
                    except Exception:       # noqa: BLE001
                        out.append("")
                return out

        class _PeerLedger:
            def __init__(self):
                self.height = 1             # "genesis committed"
                self.block_store = _BlockStore()

        class _PeerChan:
            channel_id = client.channel

            def __init__(self):
                self.ledger = _PeerLedger()
                self.validator = _Validator()
                self.committed: list = []

            def commit_validated(self, block, codes, rwsets=None,
                                 tx_ids=None):
                if not all(c == txpb.TxValidationCode.VALID
                           for c in codes):
                    raise RuntimeError(
                        f"ordered block [{block.header.number}] "
                        f"failed creator-signature validation")
                self.committed.append(block.header.number)
                self.ledger.height = block.header.number + 1
                return list(codes)

            def process_block(self, block):
                codes = self.validator.validate(block)
                return self.commit_validated(block, codes)

        chan = _PeerChan()
        # round 18: the commit leg IS the peer node of this rig —
        # naming it gives the probe trace a second node track (the
        # orderer's chain loop already records under its endpoint)
        commit_pipe = CommitPipeline(
            chan, depth=1, node_id="peer0.example.com:7051")
        t0 = time.perf_counter()
        for i, blk in enumerate(blocks, start=1):
            # every block submits under the carrier the block writer
            # registered (round 18 — the deliver-feeder shape); the
            # probe block's carrier descends from the probe ingress
            # span, so its validate/commit spans keep the lifecycle
            # trace_id exactly as before
            carrier = clustertrace.block_carrier(client.channel,
                                                 blk.header.number)
            if carrier is None and blk.header.number == 1:
                with tracing.attached(probe_ctx):
                    commit_pipe.submit(i, block=blk)
            else:
                with clustertrace.resumed(
                        carrier, link="deliver:orderbench",
                        node="peer0.example.com:7051"):
                    commit_pipe.submit(i, block=blk)
        commit_pipe.drain(timeout=600)
        commit_leg_s = time.perf_counter() - t0
        if len(chan.committed) != len(blocks):
            raise RuntimeError(
                f"commit leg short: {len(chan.committed)}/"
                f"{len(blocks)} blocks")

        # ---- stage tails + the lifecycle trace dump ----
        pq = _stage_tail

        if trace_path is None:
            trace_path = os.environ.get("BENCH_TRACE_SIDECAR",
                                        "bench_trace.json")
        trace_file = None
        linked = []
        if trace_path:
            try:
                trace_file = tracing.dump("bench_full_pipeline",
                                          path=trace_path)
            except Exception:               # noqa: BLE001
                trace_file = None
        nodes: list = []
        if probe_trace_id:
            linked = tracing.trace_stages(probe_trace_id)
            # round-18 contract: the probe's trace must CROSS nodes —
            # the orderer's chain-loop track plus the commit leg's
            # peer track at minimum
            nodes = tracing.trace_nodes(probe_trace_id)
            assert len(nodes) >= 2, \
                f"probe trace stayed on one node: {nodes}"

        # round-18 e2e finality tails (birth -> commit on the peer
        # leg); an explicit marker when tracing is off or nothing
        # carried a birth, so the smoke gate can tell "didn't run"
        # from "lost its fields"
        e2e_p50 = _stage_tail("e2e.commit", "p50_s")
        e2e_p99 = _stage_tail("e2e.commit", "p99_s")

        stats = svc.chain.order_pipeline_stats()
        win = getattr(svc.support.ingress_csp, "stats", {})
        return {
            "ntxs": ntxs, "window": window, "block_txs": block_txs,
            "blocks": len(blocks) - 1,      # probe block excluded
            "sign_s": round(sign_s, 2),
            "order_raft_s": round(order_s, 3),
            "order_tx_per_s": round(ntxs / order_s, 1),
            "validate_equiv_s": round(validate_s, 4),
            "order_vs_validate": round(order_s / validate_s, 2),
            "commit_leg_s": round(commit_leg_s, 3),
            "batch_fill": stats.get("fill"),
            "windows": stats.get("windows"),
            "blocks_proposed": stats.get("blocks_proposed"),
            "blocks_written": stats.get("blocks_written"),
            "write_overlap_ratio": round(
                stats.get("overlap_ratio") or 0.0, 4),
            "steps_coalesced": stats.get("steps_coalesced"),
            "demotions": stats.get("demotions"),
            "ingress_window_dispatches": win.get("window_dispatches"),
            "ingress_window_callers": win.get("window_callers"),
            "filter_backend": type(provider).__name__,
            # round-14 per-stage tails (the means above hide these)
            "order_window_p50_s": pq("order.window", "p50_s"),
            "order_window_p99_s": pq("order.window", "p99_s"),
            "order_propose_p50_s": pq("order.propose", "p50_s"),
            "order_propose_p99_s": pq("order.propose", "p99_s"),
            "order_consensus_p50_s": pq("order.consensus", "p50_s"),
            "order_consensus_p99_s": pq("order.consensus", "p99_s"),
            "order_write_p50_s": pq("order.write", "p50_s"),
            "order_write_p99_s": pq("order.write", "p99_s"),
            "validate_p50_s": pq("commit.validate", "p50_s"),
            "validate_p99_s": pq("commit.validate", "p99_s"),
            "commit_p50_s": pq("commit.commit", "p50_s"),
            "commit_p99_s": pq("commit.commit", "p99_s"),
            "trace_file": trace_file,
            "probe_trace_id": probe_trace_id,
            "trace_linked_stages": ",".join(linked) or None,
            "trace_nodes": ",".join(nodes) or None,
            **({"e2e_commit_p50_s": e2e_p50,
                "e2e_commit_p99_s": e2e_p99}
               if e2e_p50 is not None else
               {"e2e_skipped": "tracing off or no birth-stamped "
                               "commits"}),
        }
    finally:
        if commit_pipe is not None:
            try:
                commit_pipe.stop()
            except Exception:             # noqa: BLE001
                pass
        if svc is not None:
            try:
                svc.close(flush=True)
            except Exception:         # noqa: BLE001
                pass
        shutil.rmtree(root, ignore_errors=True)


def cluster_trace_run(consenters: int = 3, ntxs: int = 24,
                      block_txs: int = 8, window: int = 12,
                      slo_target_s: float = 1.0,
                      deadline_s: float = 120.0) -> dict:
    """ISSUE 15 acceptance rig: a wheel-free in-process 3-consenter +
    2-peer run that produces ONE merged Chrome trace in which a single
    probe transaction's trace_id links ingress -> raft consensus hops
    -> block write -> gossip/deliver -> commit.validate/commit.commit
    on BOTH peers.

    Topology: `consenters` raft orderers over one LocalClusterNetwork
    (wire carriers framed into consensus/submit payloads); peer0 feeds
    its CommitPipeline from a REAL `common/deliver.DeliverHandler`
    block stream off the leader; peer1 receives the same blocks over
    the gossip `LocalNetwork` (a relay reads a FOLLOWER's deliver
    stream and re-gossips under the resumed carrier). Two
    OperationsServers front the shared recorder; the merge is pulled
    over HTTP via `/debug/trace/cluster?trace_id=` (peer fetch + clock
    alignment + span-id dedup all exercised), and
    `e2e_commit_seconds`/`hop_seconds` + `components.slo` are read off
    the REAL /metrics and /healthz surfaces."""
    import shutil
    import threading
    import types
    import urllib.request

    from fabric_tpu.common import clustertrace, tracing
    from fabric_tpu.common import metrics as metrics_mod
    from fabric_tpu.common.deliver import DeliverHandler
    from fabric_tpu.core.commitpipeline import CommitPipeline
    from fabric_tpu.core.txvalidator import ValidationResult
    from fabric_tpu.gossip.transport import LocalNetwork
    from fabric_tpu.node.operations import OperationsServer
    from fabric_tpu.orderer.cluster import LocalClusterNetwork
    from fabric_tpu.peer.deliverclient import seek_envelope
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protos import transaction as txpb
    from fabric_tpu.protoutil import protoutil as pu

    if not tracing.enabled():
        return {"skipped": "FTPU_TRACE=0"}

    root = tempfile.mkdtemp(prefix="bench_ctrace_")
    t_run0 = time.perf_counter()
    deadline = time.monotonic() + deadline_s
    eps = [f"orderer{i}.example.com:{7050 + i}"
           for i in range(consenters)]
    peer_eps = ["peer0.example.com:7051", "peer1.example.com:7052"]
    svcs: dict = {}
    pipes: list = []
    ops_servers: list = []
    gossip_net = None
    try:
        tracing.reset()
        clustertrace.reset()
        provider = metrics_mod.PrometheusProvider()
        tracing.bind_metrics(provider)   # + e2e/hop histograms
        clustertrace.configure_slo(slo_target_s)

        net = LocalClusterNetwork()
        client = make_order_client()
        for i, ep in enumerate(eps):
            svcs[ep] = make_order_service(
                os.path.join(root, f"o{i}"), client=client,
                endpoint=ep, endpoints=eps, net=net,
                block_txs=block_txs, batch_timeout_s=0.1,
                tick_interval_s=0.01, election_tick=8)

        def leader_ep():
            from fabric_tpu.orderer.raft.core import LEADER
            for ep, s in svcs.items():
                if s.chain.node.state == LEADER:
                    return ep
            return None

        while leader_ep() is None:
            if time.monotonic() > deadline:
                raise RuntimeError("no raft leader")
            time.sleep(0.005)
        lead = svcs[leader_ep()]

        # ---- the probe block + steady traffic, birth-stamped ----
        envs = [client.envelope(i) for i in range(block_txs + ntxs)]
        probe_envs, rest = envs[:block_txs], envs[block_txs:]

        def pump(run):
            pos = 0
            ctx = None
            while pos < len(run):
                with tracing.span(
                        "ingress.batch",
                        envelopes=min(window, len(run) - pos)) as c:
                    if c is not None:
                        clustertrace.note_birth(c.trace_id)
                        ctx = c
                    resps = lead.broadcast.process_messages(
                        run[pos:pos + window])
                ok = sum(1 for r in resps
                         if r.status == cpb.Status.SUCCESS)
                pos += ok
                if ok == 0:
                    if time.monotonic() > deadline:
                        raise RuntimeError("broadcast stalled")
                    time.sleep(0.02)
            return ctx

        probe_ctx = pump(probe_envs)
        probe_trace_id = probe_ctx.trace_id
        pump(rest)

        # every consenter durably holds every block
        want_txs = len(envs)
        while True:
            heights = [s.support.ledger.height for s in svcs.values()]
            got = 0
            if len(set(heights)) == 1 and heights[0] > 1:
                blks = [lead.support.ledger.get_block(n)
                        for n in range(1, heights[0])]
                if all(b is not None for b in blks):
                    got = sum(len(b.data.data) for b in blks)
                    if got >= want_txs:
                        break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"cluster never converged: {heights} ({got}/"
                    f"{want_txs} txs)")
            time.sleep(0.02)
        height = heights[0]

        # ---- the two peers ----
        class _Validator:
            def validate_ahead(self, block, known_txids=None):
                v0 = time.perf_counter()
                n = len(block.data.data)
                return ValidationResult(
                    codes=[txpb.TxValidationCode.VALID] * n,
                    n_items=n,
                    duration_s=time.perf_counter() - v0)

            def publish_validation(self, block, result):
                while len(block.metadata.metadata) <= \
                        cpb.BlockMetadataIndex.TRANSACTIONS_FILTER:
                    block.metadata.metadata.append(b"")
                block.metadata.metadata[
                    cpb.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
                    bytes(result.codes)

            def validate(self, block):
                result = self.validate_ahead(block)
                self.publish_validation(block, result)
                return result.codes

        class _BlockStore:
            @staticmethod
            def block_tx_ids(block):
                return [""] * len(block.data.data)

        class _PeerChan:
            channel_id = client.channel

            def __init__(self):
                self.ledger = types.SimpleNamespace(
                    height=1, block_store=_BlockStore())
                self.validator = _Validator()
                self.committed: list = []

            def commit_validated(self, block, codes, rwsets=None,
                                 tx_ids=None):
                self.committed.append(block.header.number)
                self.ledger.height = block.header.number + 1
                return list(codes)

            def process_block(self, block):
                codes = self.validator.validate(block)
                return self.commit_validated(block, codes)

        chans = [_PeerChan() for _ in peer_eps]
        pipes = [CommitPipeline(chan, depth=1, node_id=pep)
                 for chan, pep in zip(chans, peer_eps)]

        # peer0: the REAL DeliverHandler block stream off the leader
        deliver = DeliverHandler(
            lambda cid: lead.support
            if cid == client.channel else None)
        seek = seek_envelope(client.channel, 1, client.signer,
                             stop=height - 1)
        errors: list = []

        def deliver_feeder():
            try:
                for resp in deliver.handle(seek):
                    if resp.WhichOneof("type") != "block":
                        break
                    blk = resp.block
                    carrier = clustertrace.block_carrier(
                        client.channel, blk.header.number)
                    with clustertrace.resumed(
                            carrier,
                            link=f"deliver:{lead.transport.endpoint}",
                            node=peer_eps[0]):
                        pipes[0].submit(blk.header.number, block=blk)
            except Exception as e:   # noqa: BLE001 — surfaced below
                errors.append(f"deliver feeder: {e}")

        # peer1: blocks re-gossiped over the gossip fabric by a relay
        # reading a FOLLOWER's deliver stream (carrier captured at the
        # relay's resumed ambient, re-extracted at peer1's transport
        # drain)
        gossip_net = LocalNetwork()
        relay_t = gossip_net.register("relay.example.com:7060")
        peer1_t = gossip_net.register(peer_eps[1])

        def on_gossip(sender, raw):
            # runs on peer1's drain thread UNDER the resumed carrier
            blk = cpb.Block()
            blk.ParseFromString(raw)
            clustertrace.register_block(client.channel,
                                        blk.header.number)
            with clustertrace.resumed(
                    clustertrace.block_carrier(client.channel,
                                               blk.header.number),
                    link=f"gossip:{sender}", node=peer_eps[1]):
                pipes[1].submit(blk.header.number, block=blk)

        peer1_t.set_handler(on_gossip)
        follower = next(s for ep, s in svcs.items()
                        if s is not lead)
        fol_deliver = DeliverHandler(
            lambda cid: follower.support
            if cid == client.channel else None)

        def gossip_relay():
            try:
                for resp in fol_deliver.handle(seek):
                    if resp.WhichOneof("type") != "block":
                        break
                    blk = resp.block
                    carrier = clustertrace.block_carrier(
                        client.channel, blk.header.number)
                    with clustertrace.resumed(
                            carrier, link="deliver:follower",
                            node="relay.example.com:7060"):
                        relay_t.send(peer_eps[1],
                                     blk.SerializeToString())
            except Exception as e:   # noqa: BLE001 — surfaced below
                errors.append(f"gossip relay: {e}")

        threads = [threading.Thread(target=deliver_feeder,
                                    name="ctrace-deliver"),
                   threading.Thread(target=gossip_relay,
                                    name="ctrace-relay")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(5.0, deadline - time.monotonic()))
        if errors:
            raise RuntimeError("; ".join(errors))
        # the gossip leg submits from peer1's ASYNC drain thread:
        # pipeline.drain() only covers already-submitted blocks, so
        # wait for every commit to actually land before asserting
        while not all(len(c.committed) >= height - 1
                      for c in chans):
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"peer commits stalled: "
                    f"{[len(c.committed) for c in chans]}/"
                    f"{height - 1}")
            time.sleep(0.01)
        for p in pipes:
            p.drain(timeout=max(5.0, deadline - time.monotonic()))
        for chan in chans:
            assert len(chan.committed) == height - 1, \
                (chan.committed, height)

        # ---- the operations surfaces ----
        ops_a = OperationsServer(metrics_provider=provider)
        ops_a.register_checker("slo", clustertrace.slo_health)
        ops_b = OperationsServer()
        ops_a.set_trace_peers([ops_b.address])
        ops_a.start()
        ops_b.start()
        ops_servers = [ops_a, ops_b]

        def get_json(addr, path):
            with urllib.request.urlopen(f"http://{addr}{path}",
                                        timeout=10) as r:
                return json.load(r)

        merged = get_json(ops_a.address,
                          f"/debug/trace/cluster?trace_id="
                          f"{probe_trace_id}")
        probe_events = [e for e in merged["traceEvents"]
                        if e.get("ph") != "M"]
        assert probe_events, "merged cluster trace is empty"
        assert all(e["args"]["trace_id"] == probe_trace_id
                   for e in probe_events), "trace_id filter leaked"
        stages = {e["name"] for e in probe_events}
        for want in ("ingress.batch", "hop.recv", "order.write",
                     "commit.validate", "commit.commit"):
            assert want in stages, \
                f"probe trace lacks {want!r}: {sorted(stages)}"
        nodes = {e["args"].get("node") for e in probe_events} - {None}
        commit_nodes = {e["args"].get("node") for e in probe_events
                        if e["name"] == "commit.commit"}
        assert set(peer_eps) <= commit_nodes, \
            f"probe did not commit on both peers: {commit_nodes}"
        hop_nodes = {e["args"].get("node") for e in probe_events
                     if e["name"] == "hop.recv"} - {None}
        assert any(n in hop_nodes for n in eps), \
            f"no consensus hop resumed on a consenter: {hop_nodes}"

        with urllib.request.urlopen(
                f"http://{ops_a.address}/metrics", timeout=10) as r:
            metrics_text = r.read().decode()
        assert "e2e_commit_seconds" in metrics_text, \
            "e2e_commit_seconds not rendered on /metrics"
        assert "hop_seconds" in metrics_text, \
            "hop_seconds not rendered on /metrics"
        healthz = get_json(ops_a.address, "/healthz")
        slo_state = (healthz.get("components") or {}).get("slo")
        assert slo_state is not None, healthz

        pq = _stage_tail
        return {
            "consenters": consenters,
            "peers": len(peer_eps),
            "ntxs": want_txs,
            "blocks": height - 1,
            "probe_trace_id": probe_trace_id,
            "merged_events": len(probe_events),
            "trace_nodes": ",".join(sorted(nodes)),
            "commit_nodes": ",".join(sorted(commit_nodes)),
            "linked_stages": ",".join(sorted(stages)),
            "residual_skew_s": merged["ftpu"]["cluster"][
                "residual_skew_s_observed"],
            "e2e_commit_p50_s": pq("e2e.commit", "p50_s"),
            "e2e_commit_p99_s": pq("e2e.commit", "p99_s"),
            "slo_health": slo_state,
            "slo_target_s": slo_target_s,
            "run_s": round(time.perf_counter() - t_run0, 2),
        }
    finally:
        for p in pipes:
            try:
                p.stop()
            except Exception:         # noqa: BLE001
                pass
        for s in svcs.values():
            try:
                s.close(flush=True)
            except Exception:         # noqa: BLE001
                pass
        if gossip_net is not None:
            for ep in list(gossip_net.endpoints()):
                try:
                    gossip_net._nodes[ep].close()
                except Exception:     # noqa: BLE001
                    pass
        for o in ops_servers:
            try:
                o.stop()
            except Exception:         # noqa: BLE001
                pass
        clustertrace.configure_slo(None)
        shutil.rmtree(root, ignore_errors=True)


def overload_run(producers: int = 4, ntxs_per_producer: int = 300,
                 window: int = 24, block_txs: int = 32,
                 budget_s: float = 0.35,
                 events_cap: int = 48) -> dict:
    """ISSUE 9 soak scenario: drive the REAL single-node raft ordering
    service (threaded ready loop, admission window, write stage,
    signed blocks) with MORE offered load than it can drain —
    `producers` threads each broadcasting creator-signed envelopes
    through `BroadcastHandler.process_messages` under a tight ambient
    `Deadline` (`budget_s`) against a deliberately small raft event
    queue (`events_cap` windows) — and assert the round-12 overload
    contract:

      * bounded: every registered overload queue's max_depth stayed
        within its capacity (no unbounded growth anywhere);
      * shed, not stalled: over-capacity load was refused as clean
        per-envelope SERVICE_UNAVAILABLE, counted per stage, and no
        producer ever blocked past its deadline budget;
      * nothing half-applied: every ACCEPTED (SUCCESS) envelope
        commits exactly once, every committed envelope was accepted,
        and the committed stream replayed through a fresh SEQUENTIAL
        (write_pipeline=False) oracle service is bit-identical;
      * live throughout: the ledger kept advancing and the run
        finished inside its wall budget (the soak script adds
        FTPU_LOCKCHECK=1 on top for the no-deadlock claim).

    Chaos faults ride in from FTPU_FAULTS exactly like every other
    regime (tools/soak_check.sh arms order.propose delays + raft.step
    errors), so shed accounting and demotion machinery are exercised
    TOGETHER."""
    import shutil
    import threading

    from fabric_tpu.common import overload
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protoutil.protoutil import marshal as pu_marshal

    os.environ["FTPU_RAFT_EVENTS_CAP"] = str(events_cap)
    root = tempfile.mkdtemp(prefix="bench_overload_")
    svc = None
    oracle = None
    try:
        svc = make_order_service(os.path.join(root, "hot"),
                                 block_txs=block_txs,
                                 batch_timeout_s=0.2)
        client = svc.client

        deadline0 = time.monotonic() + 60
        while svc.chain.node.leader_id != svc.chain.node_id:
            if time.monotonic() > deadline0:
                raise RuntimeError("no raft leader after 60s")
            time.sleep(0.01)

        # pre-sign everything (CPU signing is untimed setup)
        all_envs = [[client.envelope(p * 1_000_000 + i)
                     for i in range(ntxs_per_producer)]
                    for p in range(producers)]

        accepted: list[list[bytes]] = [[] for _ in range(producers)]
        shed_counts = [0] * producers
        max_call_s = [0.0] * producers
        errors: list = []

        def producer(p: int) -> None:
            envs = all_envs[p]
            pos = 0
            while pos < len(envs):
                batch = envs[pos:pos + window]
                pos += len(batch)
                t0 = time.perf_counter()
                try:
                    with overload.Deadline.after(budget_s).applied():
                        resps = svc.broadcast.process_messages(batch)
                except Exception as e:      # noqa: BLE001
                    errors.append(f"producer {p}: {e!r}")
                    return
                dt = time.perf_counter() - t0
                if dt > max_call_s[p]:
                    max_call_s[p] = dt
                for env, resp in zip(batch, resps):
                    if resp.status == cpb.Status.SUCCESS:
                        accepted[p].append(pu_marshal(env))
                    elif resp.status == \
                            cpb.Status.SERVICE_UNAVAILABLE:
                        shed_counts[p] += 1
                    else:
                        errors.append(
                            f"producer {p}: unexpected status "
                            f"{resp.status} {resp.info}")
                        return

        t_run0 = time.perf_counter()
        threads = [threading.Thread(target=producer, args=(p,),
                                    name=f"overload-producer-{p}")
                   for p in range(producers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        offered_s = time.perf_counter() - t_run0
        if errors:
            raise RuntimeError("; ".join(errors[:3]))

        n_accepted = sum(len(a) for a in accepted)
        n_shed = sum(shed_counts)
        n_offered = producers * ntxs_per_producer

        # ---- drain: every accepted envelope must land ----
        # incremental read (high-water block cursor): re-reading the
        # whole ledger every poll tick is O(blocks^2) and starves the
        # single-core pipeline being drained
        ledger = svc.support.ledger
        accepted_set = {e for a in accepted for e in a}
        drain_deadline = time.monotonic() + 300
        committed: list = []
        next_block = 1
        while True:
            while next_block < ledger.height:
                b = ledger.get_block(next_block)
                if b is None:       # still in the write stage
                    break
                committed.extend(bytes(d) for d in b.data.data)
                next_block += 1
            if len(committed) >= n_accepted:
                break
            if time.monotonic() > drain_deadline:
                raise RuntimeError(
                    f"overload drain stalled: {len(committed)}/"
                    f"{n_accepted} committed")
            time.sleep(0.05)
        n_blocks = next_block - 1
        drain_s = time.perf_counter() - t_run0 - offered_s

        # exactly-once: accepted == committed as multisets (and since
        # accepted envelopes are globally unique, set+len suffice)
        assert len(committed) == n_accepted, \
            (len(committed), n_accepted)
        assert set(committed) == accepted_set, \
            "committed stream diverged from the accepted set"

        # snapshot the overload stages NOW: the oracle service below
        # re-registers same-named queues (raft.events.<channel>) and
        # would shadow the hot run's readings
        stages = overload.stage_stats()

        # ---- sequential-oracle replay, bit-identical ----
        # SAME client (keys + creator): the oracle must accept the
        # exact committed bytes, and a fresh client's sig filter
        # would rightly reject them
        oracle = make_order_service(os.path.join(root, "oracle"),
                                    client=client,
                                    block_txs=block_txs,
                                    batch_timeout_s=0.2,
                                    write_pipeline=False,
                                    endpoint="oracle0.example.com:7050",
                                    endpoints=(
                                        "oracle0.example.com:7050",))
        odl = time.monotonic() + 60
        while oracle.chain.node.leader_id != oracle.chain.node_id:
            if time.monotonic() > odl:
                raise RuntimeError("oracle: no raft leader")
            time.sleep(0.01)
        pos = 0
        committed_envs = [cpb.Envelope.FromString(raw)
                          for raw in committed]
        while pos < len(committed_envs):
            resps = oracle.broadcast.process_messages(
                committed_envs[pos:pos + window])
            ok = sum(1 for r in resps
                     if r.status == cpb.Status.SUCCESS)
            if ok == 0:
                raise RuntimeError("oracle rejected the committed "
                                   "stream")
            pos += ok
        olg = oracle.support.ledger
        odeadline = time.monotonic() + 300
        ocommitted: list = []
        onext = 1
        while True:
            while onext < olg.height:
                b = olg.get_block(onext)
                if b is None:
                    break
                ocommitted.extend(bytes(d) for d in b.data.data)
                onext += 1
            if len(ocommitted) >= len(committed):
                break
            if time.monotonic() > odeadline:
                raise RuntimeError("oracle drain stalled")
            time.sleep(0.05)
        assert ocommitted == committed, \
            "sequential-oracle envelope stream diverged bit-wise"

        # the oracle's creator signed the SAME key: its envelopes ARE
        # the committed bytes, so equality above is bit-identity of
        # everything the overloaded path committed

        # ---- bounded-depth + per-stage shed accounting ----
        depth_violations = {
            name: s for name, s in stages.items()
            if s.get("capacity", 0) > 0
            and s.get("max_depth", 0) > s["capacity"]}
        assert not depth_violations, \
            f"queue depth exceeded its bound: {depth_violations}"
        stage_sheds = {name: int(s.get("sheds", 0))
                       for name, s in stages.items()
                       if s.get("sheds")}

        opstats = svc.chain.order_pipeline_stats()
        committed_rate = (len(committed) /
                          max(offered_s + drain_s, 1e-9))
        offered_rate = n_offered / max(offered_s, 1e-9)
        return {
            "producers": producers,
            "offered": n_offered,
            "accepted": n_accepted,
            "client_shed": n_shed,
            "offered_per_s": round(offered_rate, 1),
            "committed_per_s": round(committed_rate, 1),
            "overcapacity_ratio": round(
                offered_rate / max(committed_rate, 1e-9), 2),
            "max_producer_call_s": round(max(max_call_s), 3),
            "budget_s": budget_s,
            "events_cap": events_cap,
            "stage_sheds": stage_sheds,
            "queue_max_depths": {
                name: s.get("max_depth", 0)
                for name, s in stages.items()
                if s.get("capacity", 0) > 0},
            "demotions": opstats.get("demotions"),
            "blocks": n_blocks,
            "accepted_commit_exact_once": True,
            "oracle_bit_identical": True,
            "run_s": round(offered_s + drain_s, 2),
        }
    finally:
        os.environ.pop("FTPU_RAFT_EVENTS_CAP", None)
        for s in (svc, oracle):
            if s is not None:
                try:
                    s.close(flush=True)
                except Exception:     # noqa: BLE001
                    pass
        shutil.rmtree(root, ignore_errors=True)


def _scheme_mix_run(n_items: int = 96, n_keys: int = 24,
                    hot_keys: int = 4, hot_frac: float = 0.8,
                    ed_items: int = 24, bls_items: int = 4,
                    invalid_frac: float = 0.1,
                    seed: int = 5) -> dict:
    """The Caliper-style scenario-mix side workload of the round-19
    serving rig: ONE mixed batch through a fresh `AdmissionWindow` —
    P-256 endorsement checks under a hot-key vs long-tail key
    distribution (`hot_frac` of items signed by `hot_keys` keys, the
    rest spread over the tail), an Ed25519 MSP slice, a (small — the
    wheel-free pairing costs ~0.25s/verify) BLS consenter slice, and
    an adversarial invalid-signature mix. Every valid item must
    verify, every corrupted one must be refused — the mixed batch
    exercises the window's scheme router + span splitter exactly the
    way a mixed-tenant serving plane would."""
    import hashlib
    import random

    from fabric_tpu.bccsp import (BLSKeyGenOpts, ECDSAKeyGenOpts,
                                  Ed25519KeyGenOpts, VerifyItem)
    from fabric_tpu.bccsp.admission import AdmissionWindow
    from fabric_tpu.bccsp.sw import SWProvider

    rng = random.Random(seed)
    sw = SWProvider()
    window = AdmissionWindow.shared(sw)
    ec_keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
               for _ in range(n_keys)]
    ed_keys = [sw.key_gen(Ed25519KeyGenOpts(ephemeral=True))
               for _ in range(max(2, hot_keys))]
    bls_keys = [sw.key_gen(BLSKeyGenOpts(ephemeral=True))
                for _ in range(2)]

    items, want, schemes = [], [], []
    key_picks = {"hot": 0, "tail": 0}
    t_sign0 = time.perf_counter()
    for i in range(n_items + ed_items + bls_items):
        msg = f"scheme-mix item {i}".encode()
        if i < n_items:
            if rng.random() < hot_frac:
                key = ec_keys[rng.randrange(hot_keys)]
                key_picks["hot"] += 1
            else:
                key = ec_keys[hot_keys +
                              rng.randrange(n_keys - hot_keys)]
                key_picks["tail"] += 1
            sig = sw.sign(key, hashlib.sha256(msg).digest())
            schemes.append("p256")
        elif i < n_items + ed_items:
            key = ed_keys[rng.randrange(len(ed_keys))]
            sig = sw.sign(key, msg)   # message-based scheme
            schemes.append("ed25519")
        else:
            key = bls_keys[rng.randrange(len(bls_keys))]
            sig = sw.sign(key, msg)
            schemes.append("bls12381")
        ok = rng.random() >= invalid_frac
        if not ok:
            # wrong-message signature: well-formed, must verify False
            bad = msg + b"#tampered"
            if schemes[-1] == "p256":
                sig = sw.sign(key, hashlib.sha256(bad).digest())
            else:
                sig = sw.sign(key, bad)
        items.append(VerifyItem(key=key.public_key(), signature=sig,
                                message=msg))
        want.append(ok)
    sign_s = time.perf_counter() - t_sign0

    t0 = time.perf_counter()
    got = window.verify_batch(items)
    verify_s = time.perf_counter() - t0
    mismatches = [i for i, (g, w) in enumerate(zip(got, want))
                  if bool(g) != w]
    assert not mismatches, \
        (f"scheme-mix verdict mismatch at {mismatches[:5]} "
         f"(schemes {[schemes[i] for i in mismatches[:5]]})")
    return {
        "items": len(items),
        "schemes": {s: schemes.count(s)
                    for s in ("p256", "ed25519", "bls12381")},
        "key_distribution": key_picks,
        "invalid_refused": sum(1 for w in want if not w),
        "sign_s": round(sign_s, 3),
        "verify_s": round(verify_s, 3),
        "verify_per_s": round(len(items) / max(verify_s, 1e-9), 1),
        "q16_table_cache": "skipped (sw provider)",
        "all_verdicts_exact": True,
    }


def adaptive_serving_run(consenters: int = 3, workers: int = 6,
                         ntxs: int = 2400, invalid: int = 48,
                         clients: int = 20000,
                         block_txs: int = 1,
                         slo_target_s: float = 1.5,
                         events_cap: int = 256,
                         interval_s: float = 0.25,
                         warmup_frac: float = 0.25,
                         seed: int = 11,
                         drop_rate: float = 0.02,
                         dup_rate: float = 0.01,
                         reorder_rate: float = 0.02,
                         reorder_window: int = 4,
                         flap_ceiling: int = 6,
                         adjust_ceiling: int = 250,
                         scheme_mix: bool = True,
                         deadline_s: float = 600.0) -> dict:
    """ISSUE 19 acceptance rig: the closed-loop serving benchmark that
    pits the ADAPTIVE admission control plane against the same rig
    with static knobs, and reports **max sustainable tx/s at a held
    p99 commit SLO**.

    Topology per phase (built fresh twice, identical except for the
    controller): a 3-consenter raft ordering cluster with every
    inter-consenter link under seeded network chaos, plus two peers
    fed post-load from DISTINCT consenters (peer0 off the leader's
    deliver stream, peer1 off a follower's) through real
    CommitPipelines. `workers` closed-loop clients — multiplexing
    `clients` simulated client identities (the tx payload carries the
    client id) — submit pre-signed P-256 envelopes one at a time
    under the live ingress deadline budget, with `invalid`
    corrupted-signature envelopes interleaved (they must be refused,
    never committed). `block_txs=1` makes the signed-block writer the
    genuine serving bottleneck (~5ms sign+self-verify per block on
    the wheel-free provider), so offered load really does exceed
    drain capacity and the static phase exhibits bufferbloat: the
    raft events queue absorbs the excess and commit p99 blows through
    the SLO. A watcher thread stamps every commit against its submit
    time and feeds `clustertrace.slo()` live — the burn signal the
    controller (adaptive phase only) closes the loop on, shrinking
    queue capacities and deadline budgets until latency is bounded by
    shallow queues instead of deep ones.

    Methodology (Caliper-style): per phase, p99 and throughput are
    computed over the steady window — commits whose SUBMIT fell after
    `warmup_frac` of the load wall (the warmup covers the
    controller's reaction time in the adaptive phase and the
    queue-growth ramp in the static one); `slo_held` is steady-window
    p99 <= target; `max_sustainable_tx_s` is the adaptive phase's
    steady-window committed rate. `adaptive_beats_static` per the
    acceptance bar: the adaptive phase holds the SLO AND (the static
    phase burns it OR adaptive sustained more tx/s). Controller
    adjustments are bounded: reversals <= `flap_ceiling`, total moves
    <= `adjust_ceiling`. The adaptive phase's committed stream must
    replay bit-identically through a fresh sequential oracle, and
    accepted == committed exactly-once in BOTH phases."""
    import gc
    import shutil
    import threading
    import types

    from fabric_tpu.common import (adaptive, clustertrace, netchaos,
                                   overload, tracing)
    from fabric_tpu.common import metrics as metrics_mod
    from fabric_tpu.common.deliver import DeliverHandler
    from fabric_tpu.core.commitpipeline import CommitPipeline
    from fabric_tpu.core.txvalidator import ValidationResult
    from fabric_tpu.orderer.cluster import LocalClusterNetwork
    from fabric_tpu.peer.deliverclient import seek_envelope
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protos import transaction as txpb
    from fabric_tpu.protoutil.protoutil import marshal as pu_marshal

    if not adaptive.enabled():
        return {"skipped": "FTPU_ADAPTIVE disabled"}

    root = tempfile.mkdtemp(prefix="bench_adaptive_")
    t_run0 = time.perf_counter()
    deadline = time.monotonic() + deadline_s
    peer_eps = ["peer0.example.com:7051", "peer1.example.com:7052"]
    client = make_order_client(channel="adaptbench")

    # ---- pre-signed envelope pool (untimed setup, shared by both
    # phases — each phase runs over a fresh ledger, so identical tx
    # ids never meet). The payload carries the simulated client id:
    # `workers` threads multiplex `clients` logical clients, the
    # closed-loop Caliper shape.
    pool = []                     # (envelope, marshalled, valid)
    for i in range(ntxs):
        env = client.envelope(
            i, payload=f"c{i % clients}:tx{i}".encode())
        pool.append((env, pu_marshal(env), True))
    inv_step = max(1, ntxs // max(1, invalid))
    for j in range(invalid):
        env = client.envelope(
            ntxs + j, payload=f"c{j % clients}:bad{j}".encode())
        # adversarial mix: a WELL-FORMED signature over the wrong
        # bytes — it must fail verification cleanly (a malformed
        # encoding would test the parser, not the policy)
        env.signature = client.signer.sign(
            env.payload + b"#tampered")
        # interleave the adversarial mix evenly through the stream
        pool.insert(min(len(pool), j * inv_step + inv_step // 2),
                    (env, pu_marshal(env), False))
    invalid_raws = {raw for _e, raw, ok in pool if not ok}

    class _Validator:
        def validate_ahead(self, block, known_txids=None):
            v0 = time.perf_counter()
            n = len(block.data.data)
            return ValidationResult(
                codes=[txpb.TxValidationCode.VALID] * n,
                n_items=n,
                duration_s=time.perf_counter() - v0)

        def publish_validation(self, block, result):
            while len(block.metadata.metadata) <= \
                    cpb.BlockMetadataIndex.TRANSACTIONS_FILTER:
                block.metadata.metadata.append(b"")
            block.metadata.metadata[
                cpb.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
                bytes(result.codes)

        def validate(self, block):
            result = self.validate_ahead(block)
            self.publish_validation(block, result)
            return result.codes

    class _BlockStore:
        @staticmethod
        def block_tx_ids(block):
            return [""] * len(block.data.data)

    class _PeerChan:
        channel_id = client.channel

        def __init__(self):
            self.ledger = types.SimpleNamespace(
                height=1, block_store=_BlockStore())
            self.validator = _Validator()
            self.committed: list = []

        def commit_validated(self, block, codes, rwsets=None,
                             tx_ids=None):
            self.committed.append(block.header.number)
            self.ledger.height = block.header.number + 1
            return list(codes)

        def process_block(self, block):
            codes = self.validator.validate(block)
            return self.commit_validated(block, codes)

    def run_phase(name: str, with_controller: bool) -> dict:
        eps = [f"orderer{i}.{name}.example.com:{7050 + i}"
               for i in range(consenters)]
        tracing.reset()
        clustertrace.reset()
        adaptive.reset()
        gc.collect()
        provider = metrics_mod.PrometheusProvider()
        tracing.bind_metrics(provider)
        clustertrace.configure_slo(slo_target_s)
        chaos = netchaos.NetChaos(seed=seed)
        chaos.set_policy(netchaos.LinkPolicy(
            drop_rate=drop_rate, dup_rate=dup_rate,
            reorder_rate=reorder_rate,
            reorder_window=reorder_window))
        net = LocalClusterNetwork()
        svcs: dict = {}
        pipes: list = []
        ctl = None
        os.environ["FTPU_RAFT_EVENTS_CAP"] = str(events_cap)
        try:
            for i, ep in enumerate(eps):
                svcs[ep] = make_order_service(
                    os.path.join(root, name, f"o{i}"),
                    client=client, channel=client.channel,
                    endpoint=ep, endpoints=eps,
                    net=net, block_txs=block_txs,
                    batch_timeout_s=0.1,
                    # the leader's loop stalls up to ~events_cap x
                    # 5ms in writer backpressure under overload; the
                    # election timeout must ride it out or a healthy
                    # leader gets deposed mid-burn
                    tick_interval_s=0.02, election_tick=200,
                    transport_wrap=chaos.wrap_cluster)
        finally:
            os.environ.pop("FTPU_RAFT_EVENTS_CAP", None)
        try:
            def leader_ep():
                from fabric_tpu.orderer.raft.core import LEADER
                for ep, s in svcs.items():
                    if s.chain.node.state == LEADER:
                        return ep
                return None

            while leader_ep() is None:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"{name}: no raft leader")
                time.sleep(0.005)
            lead = svcs[leader_ep()]

            if with_controller:
                # the shared AdmissionWindow is cached per provider
                # and registered its span knob when the STATIC phase
                # built it; adaptive.reset() cleared the registry, so
                # re-park the knob for this phase's controller
                from fabric_tpu.bccsp.admission import \
                    AdmissionWindow
                win = AdmissionWindow.shared(client.sw)
                if "bccsp.admission.span" not in adaptive.knobs():
                    adaptive.register_attr_knob(
                        win, "max_window_items",
                        "bccsp.admission.span",
                        floor=16, ceiling=win._SPAN_CAP)
                ctl = adaptive.start_controller(
                    metrics_provider=provider,
                    interval_s=interval_s)
                if ctl is None:
                    raise RuntimeError(
                        "adaptive controller failed to start")

            # ---- closed-loop load ----
            slices = [pool[w::workers] for w in range(workers)]
            submit_t: dict = {}
            sub_lock = threading.Lock()
            accepted: list = [[] for _ in range(workers)]
            shed = [0] * workers
            rejected = [0] * workers
            errors: list = []
            committed: list = []
            n_target = [None]      # set once workers finish
            stop_watch = threading.Event()
            lat: list = []         # (submit_t, commit_t, latency_s)

            def worker(w: int) -> None:
                for env, raw, _ok in slices[w]:
                    now = time.perf_counter()
                    with sub_lock:
                        submit_t[raw] = now
                    try:
                        budget = overload.ingress_budget_s()
                        with overload.Deadline.after(
                                budget).applied():
                            resp = lead.broadcast.process_messages(
                                [env])[0]
                    except Exception as e:  # noqa: BLE001
                        errors.append(f"{name} worker {w}: {e!r}")
                        return
                    if resp.status == cpb.Status.SUCCESS:
                        accepted[w].append(raw)
                    else:
                        with sub_lock:
                            submit_t.pop(raw, None)
                        if resp.status == \
                                cpb.Status.SERVICE_UNAVAILABLE:
                            shed[w] += 1
                        else:
                            rejected[w] += 1

            def watcher() -> None:
                ledger = lead.support.ledger
                next_block = 1
                slo = clustertrace.slo()
                while True:
                    advanced = True
                    while advanced:
                        advanced = False
                        while next_block < ledger.height:
                            b = ledger.get_block(next_block)
                            if b is None:
                                break
                            now = time.perf_counter()
                            for d in b.data.data:
                                raw = bytes(d)
                                with sub_lock:
                                    st = submit_t.get(raw)
                                if st is not None:
                                    lsec = now - st
                                    slo.observe(lsec)
                                    lat.append((st, now, lsec))
                                committed.append(raw)
                            next_block += 1
                            advanced = True
                    if stop_watch.is_set():
                        return
                    if n_target[0] is not None and \
                            len(committed) >= n_target[0]:
                        return
                    time.sleep(0.02)

            t_load0 = time.perf_counter()
            wthreads = [threading.Thread(
                target=worker, args=(w,),
                name=f"adaptive-client-{w}")
                for w in range(workers)]
            watch = threading.Thread(target=watcher,
                                     name="adaptive-watcher")
            watch.start()
            for t in wthreads:
                t.start()
            for t in wthreads:
                t.join(timeout=max(5.0,
                                   deadline - time.monotonic()))
            if errors:
                raise RuntimeError("; ".join(errors[:3]))
            n_accepted = sum(len(a) for a in accepted)
            n_target[0] = n_accepted
            watch.join(timeout=max(5.0,
                                   deadline - time.monotonic()))
            if watch.is_alive():
                stop_watch.set()
                watch.join(timeout=5.0)
                raise RuntimeError(
                    f"{name}: drain stalled at "
                    f"{len(committed)}/{n_accepted}")
            load_s = time.perf_counter() - t_load0

            # ---- exactly-once + adversarial-mix accounting ----
            accepted_set = {raw for a in accepted for raw in a}
            assert len(committed) == n_accepted, \
                (name, len(committed), n_accepted)
            assert set(committed) == accepted_set, \
                f"{name}: committed stream diverged from accepted"
            assert not (invalid_raws & set(committed)), \
                f"{name}: an invalid-signature envelope committed"
            n_rejected = sum(rejected)
            assert n_rejected <= invalid, (name, n_rejected)

            # ---- steady-window latency + throughput ----
            cut = t_load0 + warmup_frac * load_s
            steady = [x for x in lat if x[0] >= cut] or lat
            lats = sorted(x[2] for x in steady)
            p99 = lats[int(0.99 * (len(lats) - 1))] if lats else 0.0
            p50 = lats[len(lats) // 2] if lats else 0.0
            span0 = min(x[0] for x in steady) if steady else cut
            span1 = max(x[1] for x in steady) if steady else cut
            tx_s = len(steady) / max(span1 - span0, 1e-9)

            stages = overload.stage_stats()
            stage_sheds = {n: int(s.get("sheds", 0))
                           for n, s in stages.items()
                           if s.get("sheds")}
            # the raft events queues carry a FORCED control-plane
            # lane (consensus steps, bounded at 4x the data-plane
            # capacity) — their depth bound is 5x; everything else
            # must honor its configured capacity exactly
            depth_violations = {
                n: s for n, s in stages.items()
                if s.get("capacity", 0) > 0
                and s.get("max_depth", 0) > s["capacity"] *
                (5 if s.get("forced") else 1)}
            assert not depth_violations, \
                f"{name}: depth bound broken: {depth_violations}"

            # ---- both peers commit the full chain, fed from
            # DISTINCT consenters ----
            while True:
                heights = [s.support.ledger.height
                           for s in svcs.values()]
                if len(set(heights)) == 1:
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"{name}: consenters never converged "
                        f"{heights}")
                time.sleep(0.02)
            height = heights[0]
            chans = [_PeerChan() for _ in peer_eps]
            pipes = [CommitPipeline(chan, depth=1, node_id=pep)
                     for chan, pep in zip(chans, peer_eps)]
            follower = next(s for s in svcs.values()
                            if s is not lead)
            feed_errors: list = []

            def feed(src, pipe, pep):
                try:
                    handler = DeliverHandler(
                        lambda cid: src.support
                        if cid == client.channel else None)
                    seek = seek_envelope(client.channel, 1,
                                         client.signer,
                                         stop=height - 1)
                    for resp in handler.handle(seek):
                        if resp.WhichOneof("type") != "block":
                            break
                        blk = resp.block
                        carrier = clustertrace.block_carrier(
                            client.channel, blk.header.number)
                        with clustertrace.resumed(
                                carrier,
                                link=f"deliver:"
                                     f"{src.transport.endpoint}",
                                node=pep):
                            pipe.submit(blk.header.number,
                                        block=blk)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    feed_errors.append(f"{pep}: {e}")

            fthreads = [
                threading.Thread(target=feed,
                                 args=(src, pipe, pep),
                                 name=f"adaptive-feed-{pep}")
                for src, pipe, pep in zip((lead, follower), pipes,
                                          peer_eps)]
            for t in fthreads:
                t.start()
            for t in fthreads:
                t.join(timeout=max(5.0,
                                   deadline - time.monotonic()))
            if feed_errors:
                raise RuntimeError("; ".join(feed_errors))
            for p in pipes:
                p.drain(timeout=max(5.0,
                                    deadline - time.monotonic()))
            for chan in chans:
                assert len(chan.committed) == height - 1, \
                    (name, len(chan.committed), height - 1)

            out = {
                "offered": len(pool),
                "accepted": n_accepted,
                "shed": sum(shed),
                "rejected_invalid": n_rejected,
                "committed": len(committed),
                "blocks": height - 1,
                "peer_commits": [len(c.committed) for c in chans],
                "load_s": round(load_s, 2),
                "steady_obs": len(steady),
                "commit_p50_s": round(p50, 3),
                "commit_p99_s": round(p99, 3),
                "tx_s": round(tx_s, 1),
                "slo_held": bool(p99 <= slo_target_s),
                "slo_over_target": clustertrace.slo().stats[
                    "over_target"],
                "stage_sheds": stage_sheds,
                "chaos": {k: chaos.stats[k]
                          for k in ("sent", "dropped", "duplicated",
                                    "reordered")},
            }
            if ctl is not None:
                ctl_stats = dict(ctl.stats)
                out["controller"] = ctl_stats
                out["knobs_final"] = {
                    n: k.value()
                    for n, k in sorted(adaptive.knobs().items())}
                rendered = provider.render() \
                    if hasattr(provider, "render") else ""
                out["adaptive_metrics_rendered"] = bool(
                    ctl_stats.get("moves", 0) == 0 or
                    "adaptive_knob_value" in rendered)
            return out, committed
        finally:
            stop_w = locals().get("stop_watch")
            if stop_w is not None:
                stop_w.set()
            if ctl is not None:
                adaptive.stop_controller()
            for p in pipes:
                try:
                    p.stop()
                except Exception:     # noqa: BLE001
                    pass
            for s in svcs.values():
                try:
                    s.close(flush=True)
                except Exception:     # noqa: BLE001
                    pass
            chaos.close()
            clustertrace.configure_slo(None)

    oracle = None
    try:
        static_res, _static_committed = run_phase("static", False)
        adaptive_res, committed = run_phase("adaptive", True)

        # ---- sequential-oracle replay of the ADAPTIVE phase's
        # committed stream (same client: the oracle must accept the
        # exact committed bytes) ----
        oracle = make_order_service(
            os.path.join(root, "oracle"), client=client,
            channel=client.channel,
            block_txs=64, batch_timeout_s=0.2,
            write_pipeline=False,
            endpoint="oracle0.example.com:7050",
            endpoints=("oracle0.example.com:7050",))
        odl = time.monotonic() + 60
        while oracle.chain.node.leader_id != oracle.chain.node_id:
            if time.monotonic() > odl:
                raise RuntimeError("oracle: no raft leader")
            time.sleep(0.01)
        committed_envs = [cpb.Envelope.FromString(raw)
                          for raw in committed]
        pos = 0
        while pos < len(committed_envs):
            resps = oracle.broadcast.process_messages(
                committed_envs[pos:pos + 64])
            ok = sum(1 for r in resps
                     if r.status == cpb.Status.SUCCESS)
            if ok == 0:
                raise RuntimeError(
                    "oracle rejected the committed stream")
            pos += ok
        olg = oracle.support.ledger
        ocommitted: list = []
        onext = 1
        while len(ocommitted) < len(committed):
            while onext < olg.height:
                b = olg.get_block(onext)
                if b is None:
                    break
                ocommitted.extend(bytes(d) for d in b.data.data)
                onext += 1
            if time.monotonic() > deadline:
                raise RuntimeError("oracle drain stalled")
            time.sleep(0.02)
        assert ocommitted == committed, \
            "sequential-oracle envelope stream diverged bit-wise"

        ctl_stats = adaptive_res.get("controller", {})
        moves = int(ctl_stats.get("moves", 0))
        reversals = int(ctl_stats.get("reversals", 0))
        no_flap = (reversals <= flap_ceiling and
                   moves <= adjust_ceiling)
        beats = bool(
            adaptive_res["slo_held"] and
            (not static_res["slo_held"] or
             adaptive_res["tx_s"] > static_res["tx_s"]))
        res = {
            "consenters": consenters,
            "peers": len(peer_eps),
            "workers": workers,
            "clients_simulated": clients,
            "ntxs_per_phase": len(pool),
            "invalid_per_phase": invalid,
            "block_txs": block_txs,
            "events_cap": events_cap,
            "slo_target_s": slo_target_s,
            "warmup_frac": warmup_frac,
            "static": static_res,
            "adaptive": adaptive_res,
            "max_sustainable_tx_s": adaptive_res["tx_s"],
            "slo_held": adaptive_res["slo_held"],
            "adaptive_beats_static": beats,
            "controller_moves": moves,
            "controller_reversals": reversals,
            "flap_ceiling": flap_ceiling,
            "adjust_ceiling": adjust_ceiling,
            "no_flap": no_flap,
            "accepted_commit_exact_once": True,
            "oracle_bit_identical": True,
        }
        if scheme_mix:
            try:
                res["scheme_mix"] = _scheme_mix_run()
            except Exception as e:    # noqa: BLE001
                res["scheme_mix"] = {
                    "error": f"{type(e).__name__}: {e}"}
        res["run_s"] = round(time.perf_counter() - t_run0, 2)
        return res
    finally:
        if oracle is not None:
            try:
                oracle.close(flush=True)
            except Exception:         # noqa: BLE001
                pass
        from fabric_tpu.common import adaptive as _ad
        _ad.reset()
        shutil.rmtree(root, ignore_errors=True)


def failover_run(consenters: int = 3, producers: int = 2,
                 ntxs_per_producer: int = 60, window: int = 12,
                 block_txs: int = 8, seed: int = 7,
                 drop_rate: float = 0.10, dup_rate: float = 0.05,
                 reorder_rate: float = 0.10, reorder_window: int = 4,
                 kill_after: float = 0.35,
                 partition_s: float = 0.3,
                 reelect_bound_s: float = 30.0) -> dict:
    """ISSUE 13 soak: a 3-consenter raft ordering cluster with every
    inter-consenter link under seeded network chaos (drop + duplicate
    + bounded reorder, `common/netchaos.py`), the LEADER killed
    crash-equivalently mid-load, and — after re-election — one
    surviving follower partitioned and healed. The claims:

      * ordering recovers within a bounded re-election window
        (`failover_reelect_s` < `reelect_bound_s`), attributable via
        `raft.leader_change` tracing instants and a parseable
        flight-recorder auto-dump;
      * the survivors' committed block streams are BYTE-IDENTICAL
        (numbers, prev-hash linkage, data hashes, envelope bytes);
      * exactly-once: no envelope commits twice, and every ACCEPTED
        (SUCCESS-acked) envelope commits — acks lost with the dead
        leader are reconciled by resubmission AFTER quiescence, the
        real client protocol;
      * the committed stream replays bit-identically through a fresh
        sequential oracle service (the PR-9 oracle-replay check).

    Chaos decisions are seeded (`seed`) so a failing run reproduces."""
    import shutil
    import threading

    from fabric_tpu.common import netchaos, tracing
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protoutil.protoutil import marshal as pu_marshal

    from fabric_tpu.common import clustertrace

    root = tempfile.mkdtemp(prefix="bench_failover_")
    dump_dir = os.path.join(root, "traces")
    chaos = netchaos.NetChaos(seed=seed)
    chaos.set_policy(netchaos.LinkPolicy(
        drop_rate=drop_rate, dup_rate=dup_rate,
        reorder_rate=reorder_rate, reorder_window=reorder_window))
    eps = [f"orderer{i}.example.com:{7050 + i}"
           for i in range(consenters)]
    svcs: dict = {}
    oracle = None
    t_run0 = time.perf_counter()
    try:
        tracing.reset()
        # the birth/block-carrier registries are keyed by (channel,
        # number) on the SHARED default channel: an earlier bench
        # section's first-wins registrations would otherwise shadow
        # this one's
        clustertrace.reset()
        tracing.configure(dump_dir=dump_dir)
        from fabric_tpu.orderer.cluster import LocalClusterNetwork
        net = LocalClusterNetwork()
        client = make_order_client()
        for i, ep in enumerate(eps):
            svcs[ep] = make_order_service(
                os.path.join(root, f"o{i}"), client=client,
                endpoint=ep, endpoints=eps, net=net,
                block_txs=block_txs, batch_timeout_s=0.1,
                tick_interval_s=0.01, election_tick=8,
                transport_wrap=chaos.wrap_cluster)
        alive = dict(svcs)

        def current_leader(services=None):
            from fabric_tpu.orderer.raft.core import LEADER
            for ep, s in (services or alive).items():
                if s.chain.node.state == LEADER:
                    return ep
            return None

        def wait_leader(bound_s, services=None):
            deadline = time.monotonic() + bound_s
            while time.monotonic() < deadline:
                ep = current_leader(services)
                if ep is not None:
                    return ep
                time.sleep(0.005)
            raise RuntimeError(f"no raft leader inside {bound_s}s")

        wait_leader(60.0)

        # pre-sign every envelope (untimed CPU setup); globally unique
        all_envs = [[client.envelope(p * 1_000_000 + i)
                     for i in range(ntxs_per_producer)]
                    for p in range(producers)]
        n_offered = producers * ntxs_per_producer

        accepted_lock = threading.Lock()
        accepted: set = set()          # marshaled envelope bytes
        unknown: set = set()           # outcome lost with a dying node
        shed = [0]
        errors: list = []

        def producer(p: int) -> None:
            envs = all_envs[p]
            pos = 0
            rotation = 0
            deadline = time.monotonic() + 180
            while pos < len(envs):
                if time.monotonic() > deadline:
                    errors.append(f"producer {p}: offered-load "
                                  f"deadline at {pos}/{len(envs)}")
                    return
                targets = list(alive.values())
                svc = targets[(p + rotation) % len(targets)]
                batch = envs[pos:pos + window]
                try:
                    resps = svc.broadcast.process_messages(batch)
                except Exception:   # noqa: BLE001 — a dying node mid-call:
                    # outcome UNKNOWN (it may have enqueued a prefix);
                    # reconciliation decides after quiescence
                    with accepted_lock:
                        unknown.update(pu_marshal(e) for e in batch)
                    pos += len(batch)
                    rotation += 1
                    continue
                ok = 0
                for resp in resps:
                    if resp.status == cpb.Status.SUCCESS:
                        ok += 1
                    elif resp.status == cpb.Status.SERVICE_UNAVAILABLE:
                        shed[0] += 1
                        break       # election wobble: retry the tail
                    else:
                        errors.append(f"producer {p}: {resp.status} "
                                      f"{resp.info}")
                        return
                with accepted_lock:
                    accepted.update(pu_marshal(e)
                                    for e in batch[:ok])
                pos += ok
                if ok == 0:
                    rotation += 1
                    time.sleep(0.02)

        threads = [threading.Thread(target=producer, args=(p,),
                                    name=f"failover-producer-{p}")
                   for p in range(producers)]
        for t in threads:
            t.start()

        # ---- the kill: wait for part of the load, then crash the
        # leader (no flush — its unwritten blocks die with it) ----
        kill_threshold = int(kill_after * n_offered)
        deadline = time.monotonic() + 120
        while True:
            with accepted_lock:
                n_acc = len(accepted)
            if n_acc >= kill_threshold:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"load never reached the kill threshold "
                    f"({n_acc}/{kill_threshold}; errors={errors[:2]})")
            time.sleep(0.005)
        victim_ep = wait_leader(30.0)
        victim = alive[victim_ep]
        # rebind (never mutate) the shared dict: producer threads are
        # mid-iteration over it without a lock, and a pop() here would
        # kill one with 'dictionary changed size' OUTSIDE its
        # try/except — silently weakening the offered load
        alive = {ep: s for ep, s in alive.items()
                 if ep != victim_ep}
        t_kill = time.monotonic()
        victim.close(flush=False)
        new_leader_ep = wait_leader(reelect_bound_s, services=alive)
        reelect_s = time.monotonic() - t_kill

        # ---- one partition-and-heal on a surviving follower ----
        follower_eps = [ep for ep in alive if ep != new_leader_ep]
        if follower_eps and partition_s > 0:
            chaos.partition([follower_eps[0]],
                            heal_after_s=partition_s)

        for t in threads:
            t.join(timeout=240)
        if errors:
            raise RuntimeError("; ".join(errors[:3]))

        # ---- quiesce: survivor streams equal and stable ----
        def read_stream(svc, timeout_s: float = 10.0):
            """Fully-readable committed stream: `height` can advance
            a beat before the row is visible to this reader thread
            (async write stage) — retry until every block reads."""
            lg = svc.support.ledger
            rd = time.monotonic() + timeout_s
            while True:
                h = lg.height
                out = []
                for n in range(h):
                    b = lg.get_block(n)
                    if b is None:
                        break
                    out.append(b)
                if len(out) == h or time.monotonic() > rd:
                    return out
                time.sleep(0.01)

        def survivor_streams():
            return {ep: read_stream(s) for ep, s in alive.items()}

        # stability is detected on the CHEAP height signal (monotonic;
        # a full read_stream per 50ms poll would proto-decode every
        # block of every survivor hundreds of times) — the full
        # visibility-retrying reads happen once afterwards
        deadline = time.monotonic() + 240
        stable_since = None
        last_sig = None
        while True:
            sig = tuple(s.support.ledger.height
                        for s in alive.values())
            now = time.monotonic()
            if sig != last_sig or len(set(sig)) != 1:
                last_sig, stable_since = sig, now
            elif now - stable_since >= 1.0:
                break
            if now > deadline:
                raise RuntimeError(f"survivors never quiesced: {sig}")
            time.sleep(0.05)

        # ---- reconcile: resubmit accepted/unknown envelopes the dead
        # leader lost, then re-quiesce ----
        def committed_envs():
            streams = survivor_streams()
            ref = streams[new_leader_ep]
            return [bytes(d) for b in ref[1:] for d in b.data.data]

        committed = committed_envs()
        cset = set(committed)
        with accepted_lock:
            tracked = set(accepted) | set(unknown)
        missing = (set(accepted) - cset) | (set(unknown) - cset)
        resubmitted = len(missing)
        if missing:
            leader_svc = alive[wait_leader(30.0, services=alive)]
            todo = [cpb.Envelope.FromString(raw)
                    for raw in sorted(missing)]
            pos = 0
            deadline = time.monotonic() + 120
            while pos < len(todo):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"reconciliation stalled at {pos}/{len(todo)}")
                resps = leader_svc.broadcast.process_messages(
                    todo[pos:pos + window])
                ok = sum(1 for r in resps
                         if r.status == cpb.Status.SUCCESS)
                pos += ok
                if ok == 0:
                    time.sleep(0.02)
            with accepted_lock:
                accepted.update(pu_marshal(e) for e in todo)
            deadline = time.monotonic() + 240
            last_hs = None
            while True:
                hs = tuple(s.support.ledger.height
                           for s in alive.values())
                if hs != last_hs:
                    # re-read (and re-decode) the chain only when the
                    # cheap height signal moved
                    committed = committed_envs()
                    last_hs = hs
                if set(committed) >= set(accepted) and \
                        len(set(hs)) == 1:
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError("resubmitted envelopes never "
                                       "all committed")
                time.sleep(0.05)

        # ---- the contract ----
        dup_count = len(committed) - len(set(committed))
        assert dup_count == 0, \
            f"{dup_count} envelope(s) committed more than once"
        with accepted_lock:
            lost = set(accepted) - set(committed)
        assert not lost, f"{len(lost)} accepted envelope(s) lost"
        stray = set(committed) - tracked - set(accepted)
        assert not stray, \
            f"{len(stray)} committed envelope(s) never offered"

        streams = survivor_streams()
        ref_ep, ref = next(iter(streams.items()))
        for ep, st in streams.items():
            assert len(st) == len(ref), (ep, len(st), len(ref))
            for x, y in zip(ref, st):
                assert (x.header.number == y.header.number and
                        x.header.previous_hash ==
                        y.header.previous_hash and
                        x.header.data_hash == y.header.data_hash and
                        list(x.data.data) == list(y.data.data)), \
                    f"survivor streams diverge at block " \
                    f"{x.header.number} ({ref_ep} vs {ep})"

        # ---- failover attribution: instants + parseable auto-dump ----
        leader_changes = sum(
            1 for e in tracing.snapshot()
            if e[0] == "i" and e[1] == "raft.leader_change")
        assert leader_changes >= consenters + 1, leader_changes

        # round-18 contract: with wire-carrier propagation the
        # ordering traces CROSS consenters — the leader's windows
        # must show resumed consensus hops on other nodes' tracks
        # even under chaos (dup/reorder forward carriers, drops just
        # lose hops)
        multi_node_traces = 0
        if tracing.enabled():
            trace_node_sets: dict = {}
            for e in tracing.snapshot():
                if e[2] is not None and e[10] is not None:
                    trace_node_sets.setdefault(e[2], set()).add(e[10])
            multi_node_traces = sum(
                1 for s in trace_node_sets.values() if len(s) >= 2)
            assert multi_node_traces > 0, \
                "no trace crossed a consenter boundary"
        tracing.wait_dumps()
        dump_path = None
        if os.path.isdir(dump_dir):
            dumps = sorted(
                f for f in os.listdir(dump_dir)
                if "leader_change" in f and f.endswith(".json"))
            if dumps:
                dump_path = os.path.join(dump_dir, dumps[-1])
                with open(dump_path, encoding="utf-8") as f:
                    doc = json.load(f)
                assert doc.get("traceEvents"), "empty failover dump"
        assert dump_path is not None, \
            "no leader_change flight-recorder dump was written"

        # ---- sequential-oracle replay, bit-identical ----
        oracle = make_order_service(
            os.path.join(root, "oracle"), client=client,
            block_txs=block_txs, batch_timeout_s=0.1,
            write_pipeline=False,
            endpoint="oracle0.example.com:7050",
            endpoints=("oracle0.example.com:7050",))
        odl = time.monotonic() + 60
        while oracle.chain.node.leader_id != oracle.chain.node_id:
            if time.monotonic() > odl:
                raise RuntimeError("oracle: no raft leader")
            time.sleep(0.01)
        committed_objs = [cpb.Envelope.FromString(raw)
                          for raw in committed]
        pos = 0
        odl = time.monotonic() + 240
        while pos < len(committed_objs):
            resps = oracle.broadcast.process_messages(
                committed_objs[pos:pos + window])
            ok = sum(1 for r in resps
                     if r.status == cpb.Status.SUCCESS)
            if ok == 0 and time.monotonic() > odl:
                raise RuntimeError("oracle rejected the committed "
                                   "stream")
            pos += ok
            if ok == 0:
                time.sleep(0.02)
        olg = oracle.support.ledger
        ocommitted: list = []
        onext = 1
        odl = time.monotonic() + 240
        while len(ocommitted) < len(committed):
            while onext < olg.height:
                b = olg.get_block(onext)
                if b is None:
                    break
                ocommitted.extend(bytes(d) for d in b.data.data)
                onext += 1
            if time.monotonic() > odl:
                raise RuntimeError("oracle drain stalled")
            time.sleep(0.02)
        assert ocommitted == committed, \
            "oracle envelope stream diverged bit-wise"

        with accepted_lock:
            n_accepted = len(accepted)
        return {
            "consenters": consenters,
            "offered": n_offered,
            "accepted": n_accepted,
            "unknown_outcome": len(unknown),
            "client_shed": shed[0],
            "resubmitted": resubmitted,
            "committed": len(committed),
            "duplicates": 0,
            "reelect_s": round(reelect_s, 3),
            "reelect_bound_s": reelect_bound_s,
            "leader_changes": leader_changes,
            "killed_leader": victim_ep,
            "survivor_streams_identical": True,
            "accepted_commit_exact_once": True,
            "oracle_bit_identical": True,
            "multi_node_traces": multi_node_traces,
            "trace_dump": dump_path,
            "chaos_dropped": chaos.stats["dropped"],
            "chaos_duplicated": chaos.stats["duplicated"],
            "chaos_reordered": chaos.stats["reordered"],
            "chaos_partitioned": chaos.stats["partitioned"],
            "chaos_heals": chaos.stats["heals"],
            "run_s": round(time.perf_counter() - t_run0, 2),
        }
    finally:
        for s in list(svcs.values()) + ([oracle] if oracle else []):
            try:
                s.close(flush=False)
            except Exception:     # noqa: BLE001
                pass
        chaos.close()
        tracing.configure(
            dump_dir=os.environ.get("FTPU_TRACE_DUMP_DIR", ""))
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Round-15 crash-point recovery matrix: subprocess children.
#
# The harness (tests/test_net_chaos.py) runs these as KILLED AND
# RESTARTED real processes: run 1 arms a `crash`-mode fault at one
# durable-write seam (raft.wal_append / order.block_write /
# onboarding.commit) via FTPU_FAULTS and dies mid-stream (os._exit
# 137, a power loss at the seam); run 2 reopens the same root, replays
# from the WAL/ledger, reports the replayed stream's per-block digests,
# pumps whatever payloads are still missing, and asserts exactly-once;
# run 3 reopens again and must report the IDENTICAL digests (restart
# replay is deterministic and bit-identical).
# ---------------------------------------------------------------------------


def _block_digest(block) -> str:
    """Digest over EVERYTHING durable — header, envelope bytes AND
    metadata (a restart replays stored bytes, it never re-signs, so
    bit-identity across reopen includes each block's signature)."""
    import hashlib

    h = hashlib.sha256()
    h.update(block.header.number.to_bytes(8, "big"))
    h.update(bytes(block.header.previous_hash))
    h.update(bytes(block.header.data_hash))
    for d in block.data.data:
        h.update(len(d).to_bytes(4, "big"))
        h.update(bytes(d))
    for m in block.metadata.metadata:
        h.update(len(m).to_bytes(4, "big"))
        h.update(bytes(m))
    return h.hexdigest()


def crash_matrix_order_child(root: str, ntxs: int = 16,
                             block_txs: int = 4) -> dict:
    """One crash-matrix cell over the raft ordering service: open (or
    reopen) the service at `root`, report the REPLAYED stream, then
    pump every payload of range(ntxs) not yet committed — one block's
    worth at a time, waiting each out, so the WAL-append / block-write
    seams are crossed once per batch and an armed crash fault lands
    mid-stream deterministically."""
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protoutil import protoutil as pu

    svc = make_order_service(root, block_txs=block_txs,
                             batch_timeout_s=0.05,
                             tick_interval_s=0.01)
    try:
        ledger = svc.support.ledger
        client = svc.client

        def stream():
            # a block can be committed-but-mid-append in the write
            # stage: read the contiguous written prefix only
            out = []
            for n in range(ledger.height):
                b = ledger.get_block(n)
                if b is None:
                    break
                out.append(b)
            return out

        def payload_counts():
            counts: dict = {}
            for b in stream()[1:]:
                for raw in b.data.data:
                    env = pu.unmarshal_envelope(bytes(raw))
                    data = bytes(pu.get_payload(env).data)
                    counts[data] = counts.get(data, 0) + 1
            return counts

        replay_digests = [_block_digest(b) for b in stream()]

        deadline = time.monotonic() + 60
        while svc.chain.node.leader_id != svc.chain.node_id:
            if time.monotonic() > deadline:
                raise RuntimeError("no raft leader after 60s")
            time.sleep(0.005)

        want = {f"tx{i}".encode(): i for i in range(ntxs)}
        have = payload_counts()
        missing = [i for data, i in sorted(want.items(),
                                           key=lambda kv: kv[1])
                   if data not in have]
        pumped = 0
        for lo in range(0, len(missing), block_txs):
            batch = [client.envelope(i)
                     for i in missing[lo:lo + block_txs]]
            pos = 0
            deadline = time.monotonic() + 60
            while pos < len(batch):
                resps = svc.broadcast.process_messages(batch[pos:])
                pos += sum(1 for r in resps
                           if r.status == cpb.Status.SUCCESS)
                if time.monotonic() > deadline:
                    raise RuntimeError("pump stalled")
                if pos < len(batch):
                    time.sleep(0.01)
            pumped += len(batch)
            # wait THIS batch durable before the next: one admission
            # window -> one WAL append -> one block write per batch
            deadline = time.monotonic() + 60
            while sum(payload_counts().get(
                    f"tx{i}".encode(), 0)
                    for i in missing[lo:lo + block_txs]) < len(batch):
                if time.monotonic() > deadline:
                    raise RuntimeError("batch never committed")
                time.sleep(0.01)

        counts = payload_counts()
        exact_once = (sorted(counts) == sorted(want) and
                      all(v == 1 for v in counts.values()))
        final = stream()
        return {
            "replay_height": len(replay_digests),
            "replay_digests": replay_digests,
            "height": len(final),
            "block_digests": [_block_digest(b) for b in final],
            "payloads_exact_once": exact_once,
            "pumped": pumped,
            "ntxs": ntxs,
        }
    finally:
        svc.close(flush=True)


def crash_matrix_onboard_child(root: str, nblocks: int = 9) -> dict:
    """The onboarding-commit crash-matrix cell: replicate a
    deterministic stub-signed chain (the test_onboarding seam shape)
    into a DURABLE OrdererLedger through the real ChainReplicator —
    `onboarding.commit=crash:1:k` kills the process at the k-th
    commit; the rerun must resume from the durable prefix and finish
    with a replica bit-identical to the source."""
    import hashlib
    from types import SimpleNamespace

    from fabric_tpu.orderer import onboarding as onb
    from fabric_tpu.orderer.multichannel import OrdererLedger
    from fabric_tpu.common.backoff import FullJitterBackoff
    from fabric_tpu.protos import common as cpb
    from fabric_tpu.protos import configtx as ctxpb
    from fabric_tpu.protoutil import protoutil as pu

    channel = "crashonb"
    signer = b"orderer-a"

    def sign(ident: bytes, msg: bytes) -> bytes:
        return hashlib.sha256(b"stubsig|" + ident + b"|" + msg) \
            .digest()

    class _Csp:
        def verify_batch(self, items):
            return [sig == sign(ident, msg)
                    for ident, msg, sig in items]

    class _Prepared:
        def __init__(self, signed):
            self.items = [(sd.identity, sd.data, sd.signature)
                          for sd in signed]
            self._signed = signed

        def finish(self, ok):
            for sd, o in zip(self._signed, ok):
                if o and sd.identity == signer:
                    return
            raise RuntimeError("no valid orderer signature")

    class _Policy:
        def prepare(self, signed):
            return _Prepared(signed)

    meta = ctxpb.ConsensusMetadata()
    c = meta.consenters.add()
    c.host, c.port = "src.example.com", 7050
    bundle = SimpleNamespace(
        csp=_Csp(),
        policy_manager=SimpleNamespace(
            get_policy=lambda path: _Policy()),
        orderer=SimpleNamespace(
            consensus_metadata=meta.SerializeToString(
                deterministic=True)))

    # deterministic source chain: both the crashed and the resumed
    # child regenerate the identical bytes
    blocks = []
    prev = b""
    for i in range(nblocks):
        block = pu.new_block(i, prev)
        block.data.data.append(b"onb-payload-%d" % i)
        block.header.data_hash = pu.block_data_hash(block.data)
        md = cpb.Metadata()
        md.value = pu.encode_last_config(0)
        if i > 0:
            ms = md.signatures.add()
            ms.signature_header = pu.marshal(
                pu.create_signature_header(signer, b"n" * 24))
            ms.signature = sign(
                signer, md.value + ms.signature_header +
                pu.block_header_bytes(block.header))
        block.metadata.metadata[
            cpb.BlockMetadataIndex.SIGNATURES] = pu.marshal(md)
        blocks.append(block)
        prev = pu.block_header_hash(block.header)

    class _Transport:
        endpoint = "joiner.example.com:0"

        def pull_blocks(self, ep, cid, start, end):
            return [b for b in blocks
                    if start <= b.header.number < end]

    ledger = OrdererLedger(os.path.join(root, "replica"))
    try:
        class _LedgerSink:
            def height(self):
                return ledger.height

            def tip_hash(self):
                if ledger.height == 0:
                    return None
                return pu.block_header_hash(
                    ledger.get_block(ledger.height - 1).header)

            def verify(self, span):
                n, bundle_after, err = onb.verify_block_span(
                    channel, span, self.height(), self.tip_hash(),
                    bundle)
                return n, err

            def commit(self, block):
                ledger.add_block(block)

        replay_digests = [_block_digest(ledger.get_block(n))
                          for n in range(ledger.height)]
        rep = onb.ChainReplicator(
            channel, _Transport(),
            consenters_fn=lambda: ["src.example.com:7050"],
            sink=_LedgerSink(), batch=3,
            backoff=FullJitterBackoff(0.001, 0.01))
        rep.run(target_height=nblocks, max_wall_s=60.0)

        replica = [ledger.get_block(n) for n in range(ledger.height)]
        source_digests = [_block_digest(b) for b in blocks]
        replica_digests = [_block_digest(b) for b in replica]
        return {
            "replay_height": len(replay_digests),
            "replay_digests": replay_digests,
            "height": len(replica),
            "block_digests": replica_digests,
            "source_digests": source_digests,
            "matches_source": replica_digests == source_digests,
            "replay_is_source_prefix": replay_digests ==
            source_digests[:len(replay_digests)],
        }
    finally:
        ledger.close()


def _have_openssl_cp() -> bool:
    try:
        from fabric_tpu.bccsp._crypto_compat import HAVE_CRYPTOGRAPHY
        return bool(HAVE_CRYPTOGRAPHY)
    except Exception:                     # noqa: BLE001
        return False


def commit_pipeline_run(n_blocks: int = 6, ntxs: int = 24) -> dict:
    """ISSUE 4 scenario: sequential vs depth-1 overlapped intake on a
    synthetic multi-block stream — REAL per-tx signature verification
    (stage A, batched through the BCCSP seam; pure-python P-256 when
    the OpenSSL wheel is absent) against REAL KVLedger commits (stage
    B), wheel-free so the bounded default bench can always run it.
    Reports both wall clocks and the pipeline's measured overlap."""
    import hashlib
    import tempfile

    from fabric_tpu import protoutil as pu
    from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem
    from fabric_tpu.bccsp.sw import SWProvider
    from fabric_tpu.core.commitpipeline import CommitPipeline
    from fabric_tpu.core.committer import LedgerCommitter
    from fabric_tpu.core.txvalidator import ValidationResult
    from fabric_tpu.ledger import KVLedger
    from fabric_tpu.ledger.kvdb import DBHandle, KVStore
    from fabric_tpu.ledger.kvledger import extract_tx_rwset
    from fabric_tpu.ledger.statedb import StateDB
    from fabric_tpu.ledger.txmgr import TxSimulator
    from fabric_tpu.protos import common as cpb, proposal as proppb
    from fabric_tpu.protos import transaction as txpb

    from fabric_tpu.common import tracing

    channel = "cpbench"
    root = tempfile.mkdtemp(prefix="bench_cp_")
    seq = piped = pipeline = None
    scratch_kv = None
    try:
        # clean stage reservoirs + carrier registries: this run's
        # validate/commit tails must describe THIS rig, not earlier
        # bench sections
        tracing.reset()
        from fabric_tpu.common import clustertrace
        clustertrace.reset()
        sw = SWProvider()
        key = sw.key_gen(ECDSAKeyGenOpts(ephemeral=True))
        pub = key.public_key()

        class Signer:
            def serialize(self):
                return b"bench-client"

            def sign(self, msg):
                return sw.sign(key, hashlib.sha256(msg).digest())

        # ---- build the stream once (signing is untimed setup) ----
        scratch_kv = KVStore(os.path.join(root, "scratch.db"))
        scratch = StateDB(DBHandle(scratch_kv, "s"))

        def tx_env(i):
            sim = TxSimulator(scratch, "sim")
            sim.put_state("bench", f"k{i}", f"v{i}".encode())
            results = pu.marshal(sim.get_tx_simulation_results())
            prop, _tx_id = pu.create_proposal(channel, "bench",
                                              [b"invoke"],
                                              creator=b"bench-client")
            presp = pu.create_proposal_response(
                pu.marshal(prop), results, b"", proppb.Response(status=200),
                proppb.ChaincodeID(name="bench"), Signer())
            return pu.marshal(pu.create_signed_tx(prop, [presp], Signer()))

        ch_hdr = pu.make_channel_header(cpb.HeaderType.CONFIG, channel)
        sh = pu.create_signature_header(b"orderer", pu.random_nonce())
        genesis = pu.new_block(0, b"")
        genesis.data.data.append(pu.marshal(cpb.Envelope(
            payload=pu.marshal(pu.make_payload(ch_hdr, sh, b"cfg")))))
        genesis.header.data_hash = pu.block_data_hash(genesis.data)
        blocks = [genesis]
        n = 0
        for _ in range(n_blocks):
            blk = pu.new_block(blocks[-1].header.number + 1,
                               pu.block_header_hash(blocks[-1].header))
            for _t in range(ntxs):
                blk.data.data.append(tx_env(n))
                n += 1
            blk.header.data_hash = pu.block_data_hash(blk.data)
            blocks.append(blk)
        stream = [b.SerializeToString() for b in blocks]

        class Validator:
            """One batched signature verify per block (the device-bound
            stage); verdicts + deferred-publication contract match the
            real TxValidator."""

            def validate_ahead(self, block, known_txids=None):
                t0 = time.perf_counter()
                items = []
                for env_bytes in block.data.data:
                    env = pu.unmarshal_envelope(env_bytes)
                    items.append(VerifyItem(key=pub,
                                            signature=env.signature,
                                            message=env.payload))
                ok = sw.verify_batch(items) if block.header.number else \
                    [True] * len(items)
                codes = [txpb.TxValidationCode.VALID if o else
                         txpb.TxValidationCode.BAD_CREATOR_SIGNATURE
                         for o in ok]
                return ValidationResult(
                    codes=codes, n_items=len(items),
                    duration_s=time.perf_counter() - t0)

            def publish_validation(self, block, result):
                while len(block.metadata.metadata) <= \
                        cpb.BlockMetadataIndex.TRANSACTIONS_FILTER:
                    block.metadata.metadata.append(b"")
                block.metadata.metadata[
                    cpb.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
                    bytes(result.codes)

            def validate(self, block):
                result = self.validate_ahead(block)
                self.publish_validation(block, result)
                return result.codes

        class Chan:
            def __init__(self, name):
                self.ledger = KVLedger(channel, os.path.join(root, name))
                self.channel_id = channel
                self.validator = Validator()
                self.committer = LedgerCommitter(self.ledger)

            def commit_validated(self, block, codes, rwsets=None,
                                 tx_ids=None):
                return self.committer.commit(block, codes, rwsets=rwsets)

            def process_block(self, block):
                codes = self.validator.validate(block)
                rwsets = [extract_tx_rwset(e) for e in block.data.data]
                return self.commit_validated(block, codes, rwsets=rwsets)

        def parse(raw):
            blk = cpb.Block()
            blk.ParseFromString(raw)
            return blk

        # ---- sequential twin ----
        seq = Chan("seq")
        seq.ledger.initialize_from_genesis(parse(stream[0]))
        t0 = time.perf_counter()
        for raw in stream[1:]:
            seq.process_block(parse(raw))
        sequential_s = time.perf_counter() - t0

        # ---- depth-1 overlapped twin ----
        piped = Chan("piped")
        piped.ledger.initialize_from_genesis(parse(stream[0]))
        pipeline = CommitPipeline(piped, depth=1)
        t0 = time.perf_counter()
        try:
            for i, raw in enumerate(stream[1:], start=1):
                pipeline.submit(i, raw=raw)
            pipeline.drain(timeout=600)
        finally:
            stats = dict(pipeline.stats)
            overlap = pipeline.overlap_ratio
        pipelined_s = time.perf_counter() - t0

        assert piped.ledger.commit_hash == seq.ledger.commit_hash, \
            "pipelined commit hash diverged from sequential"
        pq = _stage_tail

        return {
            "blocks": n_blocks, "txs_per_block": ntxs,
            "sequential_s": round(sequential_s, 4),
            "pipelined_s": round(pipelined_s, 4),
            # round-14 per-block stage tails from the pipelined twin
            "cp_validate_p50_s": pq("commit.validate", "p50_s"),
            "cp_validate_p99_s": pq("commit.validate", "p99_s"),
            "cp_commit_p50_s": pq("commit.commit", "p50_s"),
            "cp_commit_p99_s": pq("commit.commit", "p99_s"),
            "speedup": round(sequential_s / pipelined_s, 3)
            if pipelined_s else None,
            "overlap_ratio": round(overlap, 4),
            "validate_s": round(stats["validate_s"], 4),
            "commit_s": round(stats["commit_s"], 4),
            "barriers": stats["barriers"],
            "fallbacks": stats["fallbacks"],
            "commit_hash_match": True,
            # on wheel-less 1-core hosts stage A is pure-python P-256
            # and HOLDS the GIL, so measured overlap shows as
            # contention, not speedup; device/native stage A (TPU comb
            # kernel, native DER parse) releases it and the same
            # overlap buys wall clock
            "stage_a_backend": "sw-pure-python"
            if not _have_openssl_cp() else "sw-openssl",
        }
    finally:
        # this runs on EVERY default bench invocation now: close both
        # twins and drop the temp trees even when an assert fires
        import shutil
        if pipeline is not None:
            pipeline.stop()
        for chan in (seq, piped):
            if chan is not None:
                try:
                    chan.ledger.close()
                except Exception:     # noqa: BLE001
                    pass
        try:
            scratch_kv.close()
        except Exception:             # noqa: BLE001
            pass
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    if len(sys.argv) > 1 and sys.argv[1] == "failover":
        # the round-15 leader-kill soak (tools/soak_check.sh): same
        # lockcheck discipline as the overload regime
        from fabric_tpu.common import lockcheck
        if os.environ.get(lockcheck.ENV_VAR):
            lockcheck.install(
                raise_on_violation=os.environ.get(
                    lockcheck.ENV_VAR) == "raise")
        out = failover_run(
            producers=int(os.environ.get("SOAK_PRODUCERS", "2")),
            ntxs_per_producer=int(os.environ.get("SOAK_TXS", "60")),
            seed=int(os.environ.get("SOAK_SEED", "7")),
            drop_rate=float(os.environ.get("SOAK_DROP_RATE", "0.10")),
            reelect_bound_s=float(os.environ.get(
                "SOAK_REELECT_BOUND_S", "30")))
        san = lockcheck.sanitizer()
        out["lockcheck_violations"] = (
            len(san.violations()) if san is not None else None)
        print(json.dumps(out))
        if san is not None and san.violations():
            print(san.report(), file=sys.stderr)
            sys.exit(3)
        sys.exit(0)

    if len(sys.argv) > 1 and sys.argv[1] == "clustertrace":
        # the round-18 cross-node tracing acceptance rig: 3 consenters
        # + 2 peers, ONE merged Chrome trace over /debug/trace/cluster
        out = cluster_trace_run(
            ntxs=int(os.environ.get("CTRACE_TXS", "24")),
            block_txs=int(os.environ.get("CTRACE_BLOCK_TXS", "8")),
            slo_target_s=float(os.environ.get("CTRACE_SLO_S", "1.0")))
        print(json.dumps(out))
        sys.exit(0)

    if len(sys.argv) > 1 and sys.argv[1] == "crashchild":
        # one crash-matrix cell (tests/test_net_chaos.py drives this
        # as a killed-and-restarted subprocess; the crash fault itself
        # rides in via FTPU_FAULTS)
        mode, root = sys.argv[2], sys.argv[3]
        if mode == "order":
            out = crash_matrix_order_child(
                root,
                ntxs=int(os.environ.get("CRASH_NTXS", "16")),
                block_txs=int(os.environ.get("CRASH_BLOCK_TXS", "4")))
        elif mode == "onboard":
            out = crash_matrix_onboard_child(
                root,
                nblocks=int(os.environ.get("CRASH_NBLOCKS", "9")))
        else:
            print(f"unknown crashchild mode {mode!r}",
                  file=sys.stderr)
            sys.exit(2)
        print(json.dumps(out))
        sys.exit(0)

    if len(sys.argv) > 1 and sys.argv[1] == "adaptive":
        # the round-19 closed-loop serving soak (tools/soak_check.sh):
        # adaptive-vs-static phases, max sustainable tx/s at a held
        # p99 commit SLO. Same lockcheck discipline as the other
        # regimes — armed BEFORE the fabric_tpu imports.
        from fabric_tpu.common import lockcheck
        if os.environ.get(lockcheck.ENV_VAR):
            lockcheck.install(
                raise_on_violation=os.environ.get(
                    lockcheck.ENV_VAR) == "raise")
        out = adaptive_serving_run(
            workers=int(os.environ.get("SOAK_WORKERS", "6")),
            ntxs=int(os.environ.get("SOAK_TXS", "2400")),
            invalid=int(os.environ.get("SOAK_INVALID", "48")),
            slo_target_s=float(os.environ.get("SOAK_SLO_S", "1.5")),
            events_cap=int(os.environ.get("SOAK_EVENTS_CAP", "256")),
            interval_s=float(os.environ.get(
                "SOAK_ADAPT_INTERVAL_S", "0.25")),
            seed=int(os.environ.get("SOAK_SEED", "11")),
            drop_rate=float(os.environ.get("SOAK_DROP_RATE", "0.02")))
        san = lockcheck.sanitizer()
        out["lockcheck_violations"] = (
            len(san.violations()) if san is not None else None)
        print(json.dumps(out))
        if san is not None and san.violations():
            print(san.report(), file=sys.stderr)
            sys.exit(3)
        sys.exit(0)

    if len(sys.argv) > 1 and sys.argv[1] == "overload":
        # the round-12 soak regime (tools/soak_check.sh): arm the
        # lock-order sanitizer FIRST when requested — locks are
        # tracked from creation, so the patch must precede the
        # fabric_tpu imports the run pulls in
        from fabric_tpu.common import lockcheck
        if os.environ.get(lockcheck.ENV_VAR):
            lockcheck.install(
                raise_on_violation=os.environ.get(
                    lockcheck.ENV_VAR) == "raise")
        out = overload_run(
            producers=int(os.environ.get("SOAK_PRODUCERS", "4")),
            ntxs_per_producer=int(os.environ.get("SOAK_TXS", "300")),
            budget_s=float(os.environ.get("SOAK_BUDGET_S", "0.35")),
            events_cap=int(os.environ.get("SOAK_EVENTS_CAP", "48")))
        san = lockcheck.sanitizer()
        out["lockcheck_violations"] = (
            len(san.violations()) if san is not None else None)
        print(json.dumps(out))
        if san is not None and san.violations():
            print(san.report(), file=sys.stderr)
            sys.exit(3)
        sys.exit(0)

    from fabric_tpu.bccsp import factory
    from fabric_tpu.common import jaxenv

    jaxenv.enable_compilation_cache()
    prov = factory.new_bccsp(factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"MinBatch": 16}}))
    print(json.dumps(run(prov, ntxs=int(
        os.environ.get("BENCH_E2E_TXS", "1024")))))
