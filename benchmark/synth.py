"""The one general traffic generator: a seeded chain of signed blocks.

`plan_chain` decides every transaction from the seed (function, keys,
values, endorsers, tampering) and what the chaincode would have read
and written against the state at the end of the previous block, all by
the parameters of the traffic file's `transactions` group; worker
processes that never import JAX (`build_envelopes`) turn plans into
real envelopes — X.509 creator, two endorsements, deterministic
low-S ECDSA — and `assemble_block` chains and signs them as the
orderer would. No chaincode runs and no RPC is made. Same seed, same
bytes.
"""

from __future__ import annotations

import hashlib
import json
from typing import NamedTuple, Optional, Sequence

import numpy as np
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature, encode_dss_signature)
from cryptography.hazmat.primitives.serialization import load_pem_private_key

from benchmark import reference as ref

TAMPER_KINDS = ("message", "wrong_key", "high_s", "bad_der")
_BASE_TS = 1_700_000_000_000_000_000     # fixed: timestamps are from the seed
_DET = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)


class TxPlan(NamedTuple):
    fn: str                   # a name of the mix's `functions`
    args: tuple               # chaincode arguments after the function name
    reads: tuple              # ((key, (block, tx) | None), ...)
    writes: tuple             # ((key, value bytes), ...)
    endorsers: tuple          # two org indexes, in signing order
    tamper: Optional[tuple]   # (kind, endorsement slot) or None
    nonce: bytes
    ts: int


class BlockPlan(NamedTuple):
    number: int
    txs: tuple                # TxPlan, in block order
    flags: bytes              # validation codes the model expects
    conflicts: int            # MVCC conflicts among them


def key_name(i: int) -> str:
    return f"acct{i:06d}"


def _key_cdf(n: int, dist: dict) -> np.ndarray:
    """Cumulative popularity of the `n` keys, by rank: `uniform`, or
    `zipf` with exponent `s`."""
    if dist["kind"] == "uniform":
        w = np.ones(n, dtype=np.float64)
    elif dist["kind"] == "zipf":
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(dist["s"])
    else:
        raise ValueError(f"no key distribution {dist['kind']!r}")
    return np.cumsum(w / w.sum())


def _record(mix: dict, key: str, old: Optional[bytes]) -> dict:
    """The record under `key` as the chaincode reads it: what is
    stored, else what the mix's creating call would have stored."""
    if old is not None:
        return json.loads(old)
    rec = {"customer_id": key, "customer_name": "name" + key[4:]}
    rec.update(dict.fromkeys(mix["record"]["fields"],
                             int(mix["record"]["initial"])))
    return rec


def _stored(mix: dict, rec: dict) -> bytes:
    return json.dumps(rec, separators=(",", ":")).ljust(
        int(mix["record"].get("pad_to_bytes", 0))).encode()


def preload_blocks(mix: dict, block_txs: int) -> int:
    """Blocks at the head of the chain that the mix's `preload` call
    fills: one call a key, in key order."""
    return -(-int(mix["keys"]) // block_txs) if mix.get("preload") else 0


def plan_chain(seed: int, n_blocks: int, block_txs: int, mix: dict,
               n_orgs: int, first_block: int = 1) -> list:
    """Plan `n_blocks` blocks of `block_txs` transactions. `mix` is the
    traffic file's `transactions` group. Where it has a `preload`, the
    chain's first transactions are that call, once for every key in
    key order: a blind write of the opening record. After them its
    `functions` are drawn by `weight`; each touches as many distinct
    keys as its `updates` name slots, drawn from `key_distribution`,
    reads each (unless `blind`) and writes each record back with
    `field += coefficient x amount` (`drain` empties a field into a
    pot, `pot` adds the pot)."""
    rng = np.random.default_rng([seed, 0x5EED])
    n = n_blocks * block_txs
    names = sorted(mix["functions"])
    fns = [mix["functions"][f] for f in names]
    weights = np.array([float(f["weight"]) for f in fns])
    which = np.searchsorted(np.cumsum(weights / weights.sum()), rng.random(n))
    slots = [1 + max(u[0] for u in f["updates"]) for f in fns]
    n_keys = int(mix["keys"])
    cdf = _key_cdf(n_keys, mix["key_distribution"])
    drawn = np.minimum(np.searchsorted(cdf, rng.random((n, max(slots)))),
                       n_keys - 1)
    amounts = rng.integers(1, int(mix["amount_max"]) + 1, n)
    pairs = [(a, b) for a in range(n_orgs) for b in range(n_orgs) if a != b]
    pair_idx = rng.integers(0, len(pairs), n)
    tampered = rng.random(n) < float(mix["tampered_share"])
    kinds = list(mix["tamper_kinds"])
    tamper_kind = rng.integers(0, len(kinds), n)
    tamper_slot = rng.integers(0, 2, n)
    nonces = rng.bytes(24 * n)

    n_pre = n_keys if mix.get("preload") else 0
    model = ref.LedgerModel()
    model.height = first_block
    out = []
    t = 0
    for b in range(n_blocks):
        number = first_block + b
        txs = []
        for _ in range(block_txs):
            if t < n_pre:
                fn, keys = mix["preload"]["function"], [key_name(t)]
                args = (keys[0], str(mix["record"]["initial"]))
                reads, recs = (), [_record(mix, keys[0], None)]
            else:
                f = fns[int(which[t])]
                fn = names[int(which[t])]
                ids = []
                for k in drawn[t, :slots[int(which[t])]]:
                    k = int(k)
                    while k in ids:         # a call names distinct keys
                        k = (k + 1) % n_keys
                    ids.append(k)
                keys = [key_name(k) for k in ids]
                recs = [_record(mix, k, model.value(k)) for k in keys]
                amount, pot = int(amounts[t]), 0
                for slot, field, how in f["updates"]:
                    if how == "drain":
                        pot += recs[slot][field]
                        recs[slot][field] = 0
                    elif how == "pot":
                        recs[slot][field] += pot
                    else:
                        recs[slot][field] += int(how) * amount
                args = (*keys, str(amount))
                reads = () if f.get("blind") else tuple(sorted(
                    (k, model.version(k)) for k in keys))
            writes = tuple(sorted((k, _stored(mix, r))
                                  for k, r in zip(keys, recs)))
            tamper = ((kinds[int(tamper_kind[t])], int(tamper_slot[t]))
                      if tampered[t] else None)
            txs.append(TxPlan(fn, args, reads, writes,
                              pairs[int(pair_idx[t])], tamper,
                              nonces[24 * t:24 * t + 24], _BASE_TS + t))
            t += 1
        flags = model.commit_block(number, [
            (ref.ENDORSEMENT_POLICY_FAILURE if p.tamper else ref.VALID,
             p.reads, p.writes) for p in txs])
        out.append(BlockPlan(number, tuple(txs), flags,
                             flags.count(ref.MVCC_READ_CONFLICT)))
    return out


# ---- envelopes (worker processes; JAX-free) --------------------------------

def _low_s(der: bytes) -> bytes:
    r, s = decode_dss_signature(der)
    return encode_dss_signature(r, ref.P256_N - s) if s > ref.HALF_N else der


def _high_s(der: bytes) -> bytes:
    r, s = decode_dss_signature(der)
    return encode_dss_signature(r, ref.P256_N - s) if s <= ref.HALF_N else der


def sign(key, message: bytes) -> bytes:
    """Deterministic (RFC 6979) low-S ECDSA over SHA-256(message), DER."""
    return _low_s(key.sign(message, _DET))


def build_envelopes(job: tuple) -> list:
    """(channel, chaincode name, client Signer, org-peer Signers,
    TxPlans) -> marshaled
    ENDORSER_TRANSACTION envelopes, as the gateway would have produced
    them from the endorsers' proposal responses."""
    from fabric_tpu.protos import common, proposal as pb, rwset as rwpb
    from fabric_tpu.protos import transaction as txpb

    channel, namespace, client, peers, plans = job
    ckey = load_pem_private_key(client.key_pem, None)
    pkeys = [load_pem_private_key(p.key_pem, None) for p in peers]
    ext = pb.ChaincodeHeaderExtension()
    ext.chaincode_id.name = namespace
    ext_bytes = ext.SerializeToString(deterministic=True)
    ok = pb.Response(status=200)
    out = []
    for p in plans:
        tx_id = hashlib.sha256(p.nonce + client.serialized).hexdigest()
        ch = common.ChannelHeader(
            type=common.HeaderType.ENDORSER_TRANSACTION, version=0,
            timestamp=p.ts, channel_id=channel, tx_id=tx_id, epoch=0,
            extension=ext_bytes)
        sh = common.SignatureHeader(creator=client.serialized, nonce=p.nonce)
        hdr = common.Header(
            channel_header=ch.SerializeToString(deterministic=True),
            signature_header=sh.SerializeToString(deterministic=True))
        spec = pb.ChaincodeInvocationSpec()
        spec.chaincode_spec.type = pb.ChaincodeSpec.PYTHON
        spec.chaincode_spec.chaincode_id.name = namespace
        spec.chaincode_spec.input.args.extend(
            [p.fn.encode()] + [a.encode() for a in p.args])
        ccpp = pb.ChaincodeProposalPayload(
            input=spec.SerializeToString(deterministic=True))
        ccpp_bytes = ccpp.SerializeToString(deterministic=True)
        prop = pb.Proposal(header=hdr.SerializeToString(deterministic=True),
                           payload=ccpp_bytes)
        kv = rwpb.KVRWSet()
        for key, ver in p.reads:
            kr = kv.reads.add(key=key)
            if ver is not None:
                kr.version.block_num, kr.version.tx_num = ver
        for key, value in p.writes:
            kv.writes.add(key=key, value=value)
        txrw = rwpb.TxReadWriteSet(data_model=rwpb.TxReadWriteSet.KV)
        txrw.ns_rwset.add(namespace=namespace,
                          rwset=kv.SerializeToString(deterministic=True))
        action = pb.ChaincodeAction(
            results=txrw.SerializeToString(deterministic=True), response=ok)
        action.chaincode_id.name = namespace
        prp = pb.ProposalResponsePayload(
            proposal_hash=hashlib.sha256(
                prop.SerializeToString(deterministic=True)).digest(),
            extension=action.SerializeToString(deterministic=True))
        prp_bytes = prp.SerializeToString(deterministic=True)

        cap = txpb.ChaincodeActionPayload(chaincode_proposal_payload=ccpp_bytes)
        cap.action.proposal_response_payload = prp_bytes
        for slot, org in enumerate(p.endorsers):
            endorser = peers[org].serialized
            msg = prp_bytes + endorser
            kind = p.tamper[0] if p.tamper and p.tamper[1] == slot else None
            sig = sign(pkeys[org], msg + b"!" if kind == "message" else msg)
            if kind == "wrong_key":
                other = [o for o in range(len(peers))
                         if o not in p.endorsers]
                endorser = peers[other[0] if other
                                 else p.endorsers[1 - slot]].serialized
            elif kind == "high_s":
                sig = _high_s(sig)
            elif kind == "bad_der":
                sig = sig[:-2]
            cap.action.endorsements.add(endorser=endorser, signature=sig)
        tx = txpb.Transaction()
        tx.actions.add(header=hdr.signature_header,
                       payload=cap.SerializeToString(deterministic=True))
        payload = common.Payload(
            header=hdr, data=tx.SerializeToString(deterministic=True))
        env = common.Envelope(
            payload=payload.SerializeToString(deterministic=True))
        env.signature = sign(ckey, env.payload)
        out.append(env.SerializeToString(deterministic=True))
    return out


# ---- blocks (parent process) -----------------------------------------------

class OrdererSigner:
    def __init__(self, signer, seed: int):
        self._key = load_pem_private_key(signer.key_pem, None)
        self._creator = signer.serialized
        self._seed = seed

    def assemble(self, number: int, previous_hash: bytes,
                 envelopes: Sequence[bytes]):
        """One chained block with the orderer's signature over
        (metadata value || signature header || header bytes), the
        image `BlockValidation` is evaluated over."""
        from fabric_tpu.protos import common
        block = common.Block()
        block.header.number = number
        block.header.previous_hash = previous_hash
        block.data.data.extend(envelopes)
        block.header.data_hash = ref.data_hash(envelopes)
        nonce = hashlib.sha256(
            f"ftpu-bench/{self._seed}/block/{number}".encode()).digest()[:24]
        md = common.Metadata(
            value=common.OrdererBlockMetadata(
                last_config_index=0).SerializeToString(deterministic=True))
        ms = md.signatures.add()
        ms.signature_header = common.SignatureHeader(
            creator=self._creator, nonce=nonce).SerializeToString(
                deterministic=True)
        ms.signature = sign(self._key, md.value + ms.signature_header
                            + ref.header_bytes(number, previous_hash,
                                               block.header.data_hash))
        for _ in range(5):
            block.metadata.metadata.append(b"")
        block.metadata.metadata[common.BlockMetadataIndex.SIGNATURES] = \
            md.SerializeToString(deterministic=True)
        block.metadata.metadata[
            common.BlockMetadataIndex.TRANSACTIONS_FILTER] = \
            bytes(len(envelopes))
        return block


def genesis_block(channel: str, material, batch: dict, seed: int):
    """Block 0: the program's own channel-config encoder over the
    seeded MSP directories, wrapped with a seeded nonce and a fixed
    timestamp (the program's wrapper uses the clock and os.urandom)."""
    from fabric_tpu.internal.configtxgen import new_channel_group
    from fabric_tpu.protos import common, configtx as ctxpb

    endpoint = "orderer0.example.com:7050"
    profile = {
        "Consortium": "SampleConsortium",
        "Capabilities": {"V2_0": True},
        "Application": {
            "Organizations": [
                {"Name": o.name, "ID": o.mspid, "MSPDir": o.msp_dir}
                for o in material.orgs],
            "Capabilities": {"V2_0": True},
        },
        "Orderer": {
            "OrdererType": "etcdraft",
            "Addresses": [endpoint],
            "BatchTimeout": batch["BatchTimeout"],
            "BatchSize": {k: batch[k] for k in (
                "MaxMessageCount", "PreferredMaxBytes", "AbsoluteMaxBytes")},
            "Raft": {"Consenters": [{"Host": endpoint.split(":")[0],
                                     "Port": 7050}]},
            "Organizations": [
                {"Name": "OrdererOrg", "ID": material.orderer_mspid,
                 "MSPDir": material.orderer_org_msp_dir,
                 "OrdererEndpoints": [endpoint]}],
            "Capabilities": {"V2_0": True},
        },
    }
    cenv = ctxpb.ConfigEnvelope()
    cenv.config.sequence = 0
    cenv.config.channel_group.CopyFrom(new_channel_group(profile))
    ch = common.ChannelHeader(type=common.HeaderType.CONFIG,
                              timestamp=_BASE_TS, channel_id=channel)
    sh = common.SignatureHeader(creator=b"", nonce=hashlib.sha256(
        f"ftpu-bench/{seed}/genesis".encode()).digest()[:24])
    payload = common.Payload(data=cenv.SerializeToString(deterministic=True))
    payload.header.channel_header = ch.SerializeToString(deterministic=True)
    payload.header.signature_header = sh.SerializeToString(deterministic=True)
    env = common.Envelope(
        payload=payload.SerializeToString(deterministic=True))
    block = common.Block()
    block.header.number = 0
    block.data.data.append(env.SerializeToString(deterministic=True))
    block.header.data_hash = ref.data_hash(block.data.data)
    for _ in range(5):
        block.metadata.metadata.append(b"")
    block.metadata.metadata[common.BlockMetadataIndex.SIGNATURES] = \
        common.Metadata(value=common.OrdererBlockMetadata(
            last_config_index=0).SerializeToString(
                deterministic=True)).SerializeToString(deterministic=True)
    return block
