"""Differential tests: fast validation path vs the reference path.

The fast path (fabric_tpu/core/fastvalidate.py + native/blockprep.cpp)
must produce byte-identical TRANSACTIONS_FILTER codes to
`TxValidator._validate_reference_path` on every input — well-formed
blocks, tampered blocks, adversarial mutations, custom plugins,
key-level validation parameters. Crypto is routed through the
provider's sw path (MinBatch above the block size) so these tests pin
the HOST pipeline; the device kernel equivalence is pinned by
tests/test_tpu_seam.py and the comb differential suites.
"""

import copy
import os
import random

import numpy as np
import pytest

from fabric_tpu.bccsp import factory
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.core.chaincode import Chaincode, ChaincodeDefinition, shim
from fabric_tpu.core.txvalidator import TxValidator
from fabric_tpu.internal import cryptogen
from fabric_tpu.internal.configtxgen import genesis_block, new_channel_group
from fabric_tpu.msp import msp_config_from_dir
from fabric_tpu.msp.mspimpl import X509MSP
from fabric_tpu.peer import Peer
from fabric_tpu.peer.gateway import Gateway
from fabric_tpu.protos import common as cpb, transaction as txpb
from fabric_tpu.protoutil import protoutil as pu

CHANNEL = "fastchannel"
TVC = txpb.TxValidationCode


class KV(Chaincode):
    def init(self, stub):
        return shim.success()

    def invoke(self, stub):
        fn, params = stub.get_function_and_parameters()
        stub.put_state(params[0], params[1].encode())
        return shim.success()


@pytest.fixture(scope="module")
def net(tmp_path_factory):
    root = tmp_path_factory.mktemp("fastval")
    cdir = str(root / "crypto")
    org1 = cryptogen.generate_org(cdir, "org1.example.com", n_peers=1,
                                  n_users=1)
    org2 = cryptogen.generate_org(cdir, "org2.example.com", n_peers=1,
                                  n_users=1)
    sw = SWProvider()
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
            "OrdererType": "solo",
            "Addresses": ["orderer0:7050"],
            "BatchTimeout": "1s",
            "BatchSize": {"MaxMessageCount": 512,
                          "PreferredMaxBytes": 1 << 30,
                          "AbsoluteMaxBytes": 1 << 30},
            "Organizations": [],
            "Capabilities": {"V2_0": True},
        },
    }
    genesis = genesis_block(CHANNEL, new_channel_group(profile))

    def local_msp(msp_dir, mspid):
        m = X509MSP(sw)
        m.setup(msp_config_from_dir(msp_dir, mspid, csp=sw))
        return m

    peers = {}
    for org_name, org_dir, mspid in (("org1", org1, "Org1MSP"),
                                     ("org2", org2, "Org2MSP")):
        msp = local_msp(
            os.path.join(org_dir, "peers",
                         f"peer0.{org_name}.example.com", "msp"), mspid)
        p = Peer(str(root / f"peer_{org_name}"), msp, sw)
        p.join_channel(genesis)
        p.chaincode_support.register("fastcc", KV())
        p.channel(CHANNEL).define_chaincode(
            ChaincodeDefinition(name="fastcc"))
        peers[org_name] = p

    user = local_msp(
        os.path.join(org1, "users", "User1@org1.example.com", "msp"),
        "Org1MSP")
    gw = Gateway(peers["org1"], None,
                 user.get_default_signing_identity())

    def make_block(ntxs: int, num: int = 1) -> cpb.Block:
        envs = [gw.endorse(CHANNEL, "fastcc",
                           [b"put", f"k{num}_{i}".encode(),
                            f"v{i}".encode()],
                           endorsing_peers=list(peers.values()))[0]
                for i in range(ntxs)]
        block = pu.new_block(num, b"\x00" * 32)
        for env in envs:
            block.data.data.append(pu.marshal(env))
        block.header.data_hash = pu.block_data_hash(block.data)
        while len(block.metadata.metadata) <= \
                cpb.BlockMetadataIndex.TRANSACTIONS_FILTER:
            block.metadata.metadata.append(b"")
        return block

    return peers, gw, make_block


def _validators(net):
    """(reference sw validator, fast-path validator) over the SAME
    ledger. MinBatch above any test block keeps the provider's crypto
    on the sw route — identical accept/reject, no XLA compiles."""
    peers, _, _ = net
    ch = peers["org1"].channel(CHANNEL)
    tpu = factory.new_bccsp(factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"MinBatch": 1 << 20}}))
    fast = TxValidator(
        CHANNEL, ch.ledger, ch.validator._bundle_source, tpu,
        cc_definition=ch.validator._cc_definition,
        configtx_validator_source=ch.validator._configtx_validator_source)
    return ch.validator, fast


def _diff(ref_v, fast_v, block):
    fast = fast_v.validate(copy.deepcopy(block))
    os.environ["FTPU_FAST_VALIDATE"] = "0"
    try:
        ref = fast_v.validate(copy.deepcopy(block))
    finally:
        os.environ["FTPU_FAST_VALIDATE"] = "1"
    assert fast == ref, [
        (i, TVC.Name(a), TVC.Name(b))
        for i, (a, b) in enumerate(zip(fast, ref)) if a != b][:8]
    sw_ref = ref_v.validate(copy.deepcopy(block))
    assert fast == sw_ref
    return fast


def test_valid_block_matches(net):
    ref_v, fast_v = _validators(net)
    _, _, make_block = net
    block = make_block(48)
    codes = _diff(ref_v, fast_v, block)
    assert set(codes) == {TVC.VALID}


def test_tampered_block_matches(net):
    ref_v, fast_v = _validators(net)
    _, _, make_block = net
    block = make_block(24, num=2)
    # bad creator signature
    env = pu.unmarshal_envelope(block.data.data[3])
    block.data.data[3] = cpb.Envelope(
        payload=env.payload,
        signature=b"\x30\x06\x02\x01\x01\x02\x01\x01"
    ).SerializeToString()
    # duplicate txid
    block.data.data[7] = block.data.data[5]
    # garbage / truncation / empty
    block.data.data[9] = b"\xff\xff\xff"
    block.data.data[11] = block.data.data[11][:40]
    block.data.data[13] = b""
    codes = _diff(ref_v, fast_v, block)
    assert codes[3] == TVC.BAD_CREATOR_SIGNATURE
    assert codes[7] == TVC.DUPLICATE_TXID
    assert codes[5] == TVC.VALID


def test_mutation_sweep_matches(net):
    """Random byte mutations over well-formed envelopes: the fast and
    reference paths must agree on every verdict (the fast parser may
    route to Python, never diverge)."""
    ref_v, fast_v = _validators(net)
    _, _, make_block = net
    base = make_block(8, num=3)
    rng = random.Random(42)
    for trial in range(24):
        block = copy.deepcopy(base)
        block.header.number = 100 + trial
        for _ in range(3):
            ti = rng.randrange(len(block.data.data))
            raw = bytearray(block.data.data[ti])
            if not raw:
                continue
            op = rng.random()
            if op < 0.4:
                raw[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
            elif op < 0.7:
                del raw[rng.randrange(len(raw))]
            else:
                raw.insert(rng.randrange(len(raw)),
                           rng.randrange(256))
            block.data.data[ti] = bytes(raw)
        _diff(ref_v, fast_v, block)


def test_unknown_fields_route_to_python(net):
    """An envelope with an unknown (but upb-legal) field parses fine in
    the reference path; the native parser must hand it over rather
    than guess."""
    from fabric_tpu import native
    ref_v, fast_v = _validators(net)
    _, _, make_block = net
    block = make_block(4, num=4)
    # append unknown field 7 (varint) to the envelope — upb keeps it
    block.data.data[1] = block.data.data[1] + b"\x38\x01"
    bp = native.block_prep(list(block.data.data), CHANNEL)
    assert bp.status[1] == native.BP_NEEDS_PYTHON
    codes = _diff(ref_v, fast_v, block)
    assert codes[1] == TVC.VALID      # unknown fields are legal


def test_custom_plugin_reroutes(net):
    ref_v, fast_v = _validators(net)
    peers, _, make_block = net
    from fabric_tpu.core import handlers
    calls = []

    def plugin(validator, bundle, cc_name, endorsement_sd, write_info):
        calls.append(cc_name)
        return validator.builtin_vscc_prepare(
            bundle, cc_name, endorsement_sd, write_info)

    handlers.validation_plugins.register("testplugin", plugin)
    ch = peers["org1"].channel(CHANNEL)
    try:
        ch.define_chaincode(ChaincodeDefinition(
            name="fastcc", validation_plugin="testplugin"))
        block = make_block(6, num=5)
        codes = _diff(ref_v, fast_v, block)
        assert set(codes) == {TVC.VALID}
        assert calls  # the plugin actually ran (via the reroute)
    finally:
        ch.define_chaincode(ChaincodeDefinition(name="fastcc"))


def test_key_level_vp_escalation(net):
    """Committed VALIDATION_PARAMETER metadata on a written key must
    pull the tx off the plain shortcut into the full key-level path —
    and the verdicts must still match the reference exactly."""
    ref_v, fast_v = _validators(net)
    peers, _, make_block = net
    from fabric_tpu.ledger import statedb as sdb
    from fabric_tpu.ledger.txmgr import serialize_metadata
    from fabric_tpu.common.policies import policydsl

    block = make_block(6, num=6)
    # find a key this block writes and pin it to an org2-only policy
    vp = policydsl.from_string("AND('Org2MSP.member')")
    md = serialize_metadata(
        {shim.VALIDATION_PARAMETER: vp.SerializeToString()})
    ledger = peers["org1"].channel(CHANNEL).ledger
    batch = sdb.UpdateBatch()
    batch.put("fastcc", "k6_2", b"seed", sdb.Height(0, 0), md)
    ledger.state_db.apply_writes_only(batch)

    codes = _diff(ref_v, fast_v, block)
    # both endorsers signed, so the org2-only key policy is satisfied
    assert set(codes) == {TVC.VALID}

    # now a policy nobody in this network can satisfy
    vp_bad = policydsl.from_string("AND('NoSuchMSP.member')")
    md_bad = serialize_metadata(
        {shim.VALIDATION_PARAMETER: vp_bad.SerializeToString()})
    batch2 = sdb.UpdateBatch()
    batch2.put("fastcc", "k6_2", b"seed", sdb.Height(0, 0), md_bad)
    ledger.state_db.apply_writes_only(batch2)
    codes2 = _diff(ref_v, fast_v, block)
    assert codes2[2] == TVC.ENDORSEMENT_POLICY_FAILURE
    assert codes2[0] == TVC.VALID


def test_extract_failure_still_claims_txid(net):
    """A tx with an empty proposal-response payload fails extraction
    (INVALID_ENDORSER_TRANSACTION) but — in reference order — only
    AFTER its valid creator claimed the txid, so a later tx reusing
    that txid is a duplicate. The native path must preserve both the
    code and the claim."""
    from fabric_tpu import native
    from fabric_tpu.protos import transaction as txpb2

    ref_v, fast_v = _validators(net)
    _, _, make_block = net
    block = make_block(4, num=8)
    # strip tx 1's endorsed action down to an empty prp
    env = pu.unmarshal_envelope(block.data.data[1])
    pay = pu.get_payload(env)
    tx = txpb2.Transaction()
    tx.ParseFromString(pay.data)
    cap = txpb2.ChaincodeActionPayload()
    cap.ParseFromString(tx.actions[0].payload)
    cap.action.proposal_response_payload = b""
    tx.actions[0].payload = cap.SerializeToString()
    pay.data = tx.SerializeToString()
    env.payload = pu.marshal(pay)
    broken = pu.marshal(env)
    block.data.data[1] = broken
    # tx 2 becomes a same-txid duplicate of the broken tx
    block.data.data[2] = broken

    bp = native.block_prep(list(block.data.data), CHANNEL)
    assert bp.status[1] == native.BP_FAIL_BASE + \
        TVC.INVALID_ENDORSER_TRANSACTION
    assert bp.creator_uid[1] >= 0      # claimer interned its creator

    codes = _diff(ref_v, fast_v, block)
    assert codes[1] == TVC.INVALID_ENDORSER_TRANSACTION
    assert codes[2] == TVC.DUPLICATE_TXID
    assert codes[0] == TVC.VALID and codes[3] == TVC.VALID


def test_deletes_route_rich(net):
    """A delete write produces vp_updates (overlay traffic) — native
    marks it rich and verdicts still match."""
    from fabric_tpu import native
    ref_v, fast_v = _validators(net)
    peers, gw, _ = net
    env = gw.endorse(CHANNEL, "fastcc", [b"put", b"delkey", b"x"],
                     endorsing_peers=list(peers.values()))[0]
    block = pu.new_block(7, b"\x00" * 32)
    block.data.data.append(pu.marshal(env))
    while len(block.metadata.metadata) <= \
            cpb.BlockMetadataIndex.TRANSACTIONS_FILTER:
        block.metadata.metadata.append(b"")
    bp = native.block_prep(list(block.data.data), CHANNEL)
    assert bp.rw_mode[0] == native.RW_PLAIN
    codes = _diff(ref_v, fast_v, block)
    assert codes == [TVC.VALID]
