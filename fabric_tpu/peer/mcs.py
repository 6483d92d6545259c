"""Message crypto service: block + gossip message verification.

Rebuild of `internal/peer/gossip/mcs.go` (MSPMessageCryptoService):
`VerifyBlock:123-192` = data-hash integrity + BlockValidation policy
over the metadata signatures; `Verify/VerifyByChannel:203,229` for
gossip message authentication. All signature evaluation routes through
the batched policy path.
"""

from __future__ import annotations

import logging

from fabric_tpu.protos import common
from fabric_tpu.protoutil import protoutil as pu
from fabric_tpu.common import tracing
from fabric_tpu.common.policies import policy as papi

logger = logging.getLogger("peer.mcs")


class BlockVerificationError(Exception):
    pass


class MSPMessageCryptoService:
    def __init__(self, channel_policy_getter, local_deserializer=None):
        """`channel_policy_getter(channel_id)` → the channel's policy
        manager + msp manager source (a bundle); `local_deserializer`
        authenticates channel-less gossip messages."""
        self._bundle_for = channel_policy_getter
        self._local = local_deserializer

    def verify_block(self, channel_id: str, seq_num: int,
                     block: common.Block) -> None:
        """Reference mcs.go:123: structural checks, header-number match,
        data-hash integrity, then the BlockValidation policy over the
        orderer signatures."""
        # (the envelopes' bytes: `ByteSize()` of a 1.6 MB block costs
        # 2.5 ms, this sum 10 us)
        with tracing.span("peer.verify_block", block=seq_num,
                          bytes=sum(map(len, block.data.data))):
            self._verify_block(channel_id, seq_num, block)

    def _verify_block(self, channel_id: str, seq_num: int,
                      block: common.Block) -> None:
        if not block.HasField("header"):
            raise BlockVerificationError(
                f"invalid block on [{channel_id}]: no header")
        if block.header.number != seq_num:
            raise BlockVerificationError(
                f"expected block [{seq_num}] but got "
                f"[{block.header.number}]")
        data_hash = pu.block_data_hash(block.data)
        if data_hash != block.header.data_hash:
            raise BlockVerificationError(
                f"block [{seq_num}] data hash mismatch")
        sig_idx = common.BlockMetadataIndex.SIGNATURES
        if len(block.metadata.metadata) <= sig_idx or \
                not block.metadata.metadata[sig_idx]:
            raise BlockVerificationError(
                f"block [{seq_num}] carries no signatures")
        try:
            signed = pu.block_signature_set(block)
        except Exception as e:
            raise BlockVerificationError(
                f"block [{seq_num}] signature metadata unreadable: {e}")
        bundle = self._bundle_for(channel_id)
        if bundle is None:
            raise BlockVerificationError(
                f"no channel [{channel_id}]")
        try:
            policy = bundle.policy_manager.get_policy(
                "/Channel/Orderer/BlockValidation")
        except papi.PolicyError as e:
            raise BlockVerificationError(
                f"no BlockValidation policy on [{channel_id}]: {e}")
        try:
            policy.evaluate_signed_data(signed)
        except papi.PolicyError as e:
            raise BlockVerificationError(
                f"block [{seq_num}] signature set rejected: {e}")

    def verify_by_channel(self, channel_id: str, identity_bytes: bytes,
                          signature: bytes, message: bytes) -> bool:
        """Gossip message auth against the channel's MSPs
        (reference mcs.go:229)."""
        bundle = self._bundle_for(channel_id)
        if bundle is None:
            return False
        try:
            ident = bundle.msp_manager.deserialize_identity(
                identity_bytes)
            ident.validate()
            return ident.verify(message, signature)
        except Exception:
            return False

    def verify(self, identity_bytes: bytes, signature: bytes,
               message: bytes) -> bool:
        """Channel-less verification against the local MSP."""
        if self._local is None:
            return False
        try:
            ident = self._local.deserialize_identity(identity_bytes)
            return ident.verify(message, signature)
        except Exception:
            return False
