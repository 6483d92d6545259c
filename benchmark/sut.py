"""The system under test: one peer, assembled the way
`fabric_tpu/node/peer_node.py` assembles it minus gRPC and gossip, and
the deliverer's hand-over of one block (`peer/deliverclient.py`).
The only module of the benchmark that imports the program's node code.
"""

from __future__ import annotations

import os

CHANNEL = "benchchannel"
FALLBACK_COUNTERS = ("sw_fallbacks", "fused_fallbacks", "host_hash_fallbacks",
                     "degraded_batches", "ladder_batches")
DISPATCH_COUNTERS = ("comb_batches", "pipeline_batches")
COMPILE_COUNTERS = ("compile_total", "compile_cold_total",
                    "compile_cache_hits")


def new_provider(bccsp_cfg: dict):
    """`factory.new_bccsp` over the configuration's `bccsp` group, as
    a node's `peer.BCCSP` section would be."""
    from fabric_tpu.bccsp import factory
    return factory.new_bccsp(factory.FactoryOpts.from_config(bccsp_cfg))


class Intake:
    """A joined peer and the deliverer's view of it."""

    def __init__(self, data_dir: str, material, genesis, config: dict, csp):
        from fabric_tpu.common.policies.policydsl import from_string
        from fabric_tpu.core.chaincode import ChaincodeDefinition
        from fabric_tpu.msp import msp_config_from_dir
        from fabric_tpu.msp.mspimpl import X509MSP
        from fabric_tpu.peer import Peer
        from fabric_tpu.protos import policies as polpb

        self.csp = csp
        org = material.orgs[0]
        local_msp = X509MSP(csp)
        local_msp.setup(msp_config_from_dir(org.peer_msp_dir, org.mspid,
                                            csp=csp))
        self.peer = Peer(os.path.join(data_dir, "peer"), local_msp, csp,
                         commit_pipeline_depth=int(
                             config["peer"].get("CommitPipelineDepth", 0)))
        self.channel = self.peer.join_channel(genesis)
        cc = config["chaincode"]
        self.channel.define_chaincode(ChaincodeDefinition(
            name=cc["name"],
            endorsement_policy=polpb.ApplicationPolicy(
                signature_policy=from_string(cc["endorsement_policy"])
            ).SerializeToString()))
        self.mcs = self.peer.mcs

    def hand_over(self, block) -> None:
        """`deliverclient.py:202-208`: verify the block, then process
        it; done when `process_block` returns."""
        self.mcs.verify_block(CHANNEL, block.header.number, block)
        self.channel.process_block(block)

    def stats(self) -> dict:
        return dict(getattr(self.csp, "stats", {}))

    def read_block(self, number: int):
        """(previous_hash, data_hash, envelopes, flags) as the peer's
        block store serves block `number`, or None."""
        from fabric_tpu.protos import common
        b = self.channel.ledger.block_store.get_block_by_number(number)
        if b is None:
            return None
        return (bytes(b.header.previous_hash), bytes(b.header.data_hash),
                [bytes(e) for e in b.data.data],
                bytes(b.metadata.metadata[
                    common.BlockMetadataIndex.TRANSACTIONS_FILTER]))

    def read_state(self, namespace: str, key: str):
        return self.channel.ledger.get_state(namespace, key)

    def close(self) -> None:
        flush = getattr(self.csp, "flush_warm_tables", None)
        if flush is not None:
            flush(30.0)
        self.peer.close()
