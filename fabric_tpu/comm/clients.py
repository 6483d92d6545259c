"""gRPC client adapters matching the in-process duck types.

Rebuild of `internal/pkg/comm` client side: each adapter speaks the
method tables of comm/services.py and presents the same surface the
in-process objects do, so peers/orderers/CLIs compose identically in
one process or across the network.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import grpc

from fabric_tpu.comm import services as svc
from fabric_tpu.protos import common, gateway as gwpb
from fabric_tpu.protos import orderer as opb, proposal as ppb

logger = logging.getLogger("comm.clients")

_OPTS = [
    ("grpc.max_send_message_length", 100 * 1024 * 1024),
    ("grpc.max_receive_message_length", 100 * 1024 * 1024),
]


def channel_to(address: str, tls_root_ca: Optional[bytes] = None,
               client_cert: Optional[bytes] = None,
               client_key: Optional[bytes] = None) -> grpc.Channel:
    if tls_root_ca is None:
        return grpc.insecure_channel(address, options=_OPTS)
    creds = grpc.ssl_channel_credentials(
        root_certificates=tls_root_ca,
        private_key=client_key, certificate_chain=client_cert)
    return grpc.secure_channel(address, creds, options=_OPTS)


def _uu(channel, service, method, req_cls, resp_cls):
    return channel.unary_unary(
        f"/{service}/{method}",
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=resp_cls.FromString)


def _us(channel, service, method, req_cls, resp_cls):
    return channel.unary_stream(
        f"/{service}/{method}",
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=resp_cls.FromString)


def _ss(channel, service, method, req_cls, resp_cls):
    return channel.stream_stream(
        f"/{service}/{method}",
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=resp_cls.FromString)


class EndorserClient:
    """Duck-type of `peer.endorser` (process_proposal)."""

    def __init__(self, channel: grpc.Channel, timeout_s: float = 30.0):
        self._call = _uu(channel, svc.ENDORSER_SERVICE,
                         "ProcessProposal", ppb.SignedProposal,
                         ppb.ProposalResponse)
        self._timeout = timeout_s

    def process_proposal(self, sp: ppb.SignedProposal
                         ) -> ppb.ProposalResponse:
        return self._call(sp, timeout=self._timeout)


class BroadcastClient:
    """Duck-type of BroadcastHandler (process_message /
    process_messages)."""

    def __init__(self, channel: grpc.Channel, timeout_s: float = 30.0):
        self._call = _uu(channel, svc.BROADCAST_SERVICE, "Broadcast",
                         common.Envelope, opb.BroadcastResponse)
        self._stream = _ss(channel, svc.BROADCAST_SERVICE,
                           "BroadcastStream", common.Envelope,
                           opb.BroadcastResponse)
        self._timeout = timeout_s

    def process_message(self, env: common.Envelope
                        ) -> opb.BroadcastResponse:
        # round 18: a client submitting under an ambient trace sends
        # its carrier in metadata so the orderer resumes the SAME
        # trace (no ambient trace / tracing off = no metadata)
        from fabric_tpu.common import clustertrace
        carrier = clustertrace.capture_carrier()
        if carrier is not None:
            return self._call(
                env, timeout=self._timeout,
                metadata=(("ftpu-trace-carrier",
                           carrier.to_header()),))
        return self._call(env, timeout=self._timeout)

    def process_messages(self, envs) -> list:
        """Streamed window: the server batches the filter + enqueue
        (services.register_broadcast handle_stream)."""
        return list(self._stream(iter(envs), timeout=self._timeout))


class DeliverClient:
    """Duck-type of DeliverHandler (handle → iterator) — plugs into
    peer.deliverclient.Deliverer as its orderer_source."""

    def __init__(self, channel: grpc.Channel):
        self._call = _us(channel, svc.DELIVER_SERVICE, "Deliver",
                         common.Envelope, opb.DeliverResponse)

    def handle(self, env: common.Envelope):
        yield from self._call(env)


class PeerDeliverClient(DeliverClient):
    """The peer's event-stream variants (reference peer deliver service:
    DeliverFiltered / DeliverWithPrivateData — what event-consuming
    client SDKs dial)."""

    def __init__(self, channel: grpc.Channel):
        super().__init__(channel)
        from fabric_tpu.protos import events as evpb
        self._filtered = _us(channel, svc.DELIVER_SERVICE,
                             "DeliverFiltered",
                             common.Envelope, evpb.DeliverResponse)
        self._pvt = _us(channel, svc.DELIVER_SERVICE,
                        "DeliverWithPrivateData",
                        common.Envelope, evpb.DeliverResponse)

    def handle_filtered(self, env: common.Envelope):
        yield from self._filtered(env)

    def handle_with_pvtdata(self, env: common.Envelope):
        yield from self._pvt(env)


class GatewayClient:
    """Client-side SDK over the Gateway service: builds and SIGNS
    proposals/envelopes locally (the reference's client SDK role)."""

    def __init__(self, channel: grpc.Channel, signer,
                 timeout_s: float = 30.0):
        self._signer = signer
        self._timeout = timeout_s
        self._evaluate = _uu(channel, svc.GATEWAY_SERVICE, "Evaluate",
                             gwpb.EvaluateRequest, gwpb.EvaluateResponse)
        self._endorse = _uu(channel, svc.GATEWAY_SERVICE, "Endorse",
                            gwpb.EndorseRequest, gwpb.EndorseResponse)
        self._submit = _uu(channel, svc.GATEWAY_SERVICE, "Submit",
                           gwpb.SubmitRequest, gwpb.SubmitResponse)
        self._status = _uu(channel, svc.GATEWAY_SERVICE, "CommitStatus",
                           gwpb.SignedCommitStatusRequest,
                           gwpb.CommitStatusResponse)
        self._events = _us(channel, svc.GATEWAY_SERVICE,
                           "ChaincodeEvents",
                           gwpb.SignedChaincodeEventsRequest,
                           gwpb.ChaincodeEventsResponse)

    def _proposal(self, channel_id: str, cc_name: str,
                  args: Sequence[bytes], transient=None):
        from fabric_tpu.protoutil import txutils
        prop, tx_id = txutils.create_proposal(
            channel_id, cc_name, list(args),
            self._signer.serialize(), transient_map=transient)
        return txutils.sign_proposal(prop, self._signer), tx_id

    def evaluate(self, channel_id: str, cc_name: str,
                 args: Sequence[bytes], transient=None) -> ppb.Response:
        sp, tx_id = self._proposal(channel_id, cc_name, args, transient)
        req = gwpb.EvaluateRequest(transaction_id=tx_id,
                                   channel_id=channel_id)
        req.proposed_transaction.CopyFrom(sp)
        return self._evaluate(req, timeout=self._timeout).result

    def endorse(self, channel_id: str, cc_name: str,
                args: Sequence[bytes], transient=None,
                endorsing_organizations: Sequence[str] = ()
                ) -> tuple[str, common.Envelope]:
        """Gather endorsements and sign the prepared transaction;
        returns (tx_id, signed envelope) ready for `submit`."""
        from fabric_tpu.protoutil import protoutil as pu
        sp, tx_id = self._proposal(channel_id, cc_name, args, transient)
        req = gwpb.EndorseRequest(transaction_id=tx_id,
                                  channel_id=channel_id)
        req.proposed_transaction.CopyFrom(sp)
        req.endorsing_organizations.extend(endorsing_organizations)
        prepared = self._endorse(req, timeout=self._timeout) \
            .prepared_transaction
        # client-side signature over the prepared payload
        payload = common.Payload()
        payload.ParseFromString(prepared.payload)
        return tx_id, pu.sign_or_panic(self._signer, payload)

    def submit(self, channel_id: str, tx_id: str,
               env: common.Envelope) -> None:
        """Hand an endorsed, signed envelope to the ordering service."""
        sreq = gwpb.SubmitRequest(transaction_id=tx_id,
                                  channel_id=channel_id)
        sreq.prepared_transaction.CopyFrom(env)
        self._submit(sreq, timeout=self._timeout)

    def commit_status(self, channel_id: str, tx_id: str,
                      timeout_s: float = 30.0) -> int:
        """Block until `tx_id` commits; returns its validation code."""
        inner = gwpb.CommitStatusRequest(
            transaction_id=tx_id, channel_id=channel_id,
            identity=self._signer.serialize())
        creq = gwpb.SignedCommitStatusRequest(
            request=inner.SerializeToString())
        return self._status(creq, timeout=timeout_s).result

    def submit_transaction(self, channel_id: str, cc_name: str,
                           args: Sequence[bytes], transient=None,
                           endorsing_organizations: Sequence[str] = (),
                           timeout_s: float = 30.0) -> tuple[str, int]:
        """endorse → sign → submit → wait for commit; returns
        (tx_id, validation_code)."""
        tx_id, env = self.endorse(channel_id, cc_name, args, transient,
                                  endorsing_organizations)
        self.submit(channel_id, tx_id, env)
        return tx_id, self.commit_status(channel_id, tx_id, timeout_s)

    def chaincode_events(self, channel_id: str, cc_name: str,
                         from_genesis: bool = False,
                         start_block: int = 0, timeout_s: float = 30.0):
        """Stream committed chaincode events (reference: the client
        SDK's ChaincodeEvents). Yields ChaincodeEventsResponse."""
        inner = gwpb.ChaincodeEventsRequest(
            channel_id=channel_id, chaincode_id=cc_name,
            identity=self._signer.serialize(),
            start_block=start_block, from_genesis=from_genesis)
        req = gwpb.SignedChaincodeEventsRequest(
            request=inner.SerializeToString(),
            signature=self._signer.sign(inner.SerializeToString()))
        yield from self._events(req, timeout=timeout_s)


class DiscoveryClient:
    """Client SDK for the discovery service (reference:
    `discovery/client/`)."""

    def __init__(self, channel: grpc.Channel, signer,
                 timeout_s: float = 15.0):
        from fabric_tpu.protos import discovery as dpb
        self._dpb = dpb
        self._signer = signer
        self._timeout = timeout_s
        self._call = _uu(channel, svc.DISCOVERY_SERVICE, "Discover",
                         dpb.SignedRequest, dpb.Response)

    def _send(self, query) -> object:
        dpb = self._dpb
        req = dpb.Request(authentication=self._signer.serialize())
        req.queries.add().CopyFrom(query)
        payload = req.SerializeToString()
        signed = dpb.SignedRequest(payload=payload,
                                   signature=self._signer.sign(payload))
        resp = self._call(signed, timeout=self._timeout)
        result = resp.results[0]
        if result.WhichOneof("result") == "error":
            raise RuntimeError(result.error.content)
        return result

    def peers(self, channel_id: str):
        q = self._dpb.Query(channel=channel_id)
        q.peer_query.SetInParent()
        return list(self._send(q).members.peers)

    def config(self, channel_id: str):
        q = self._dpb.Query(channel=channel_id)
        q.config_query.SetInParent()
        return self._send(q).config_result

    def endorsers(self, channel_id: str, cc_name: str):
        q = self._dpb.Query(channel=channel_id)
        interest = q.cc_query.interests.add()
        interest.chaincodes.add(name=cc_name)
        res = self._send(q).cc_query_res
        return list(res.descriptors)


class ClusterClient:
    """Duck-type of ClusterTransport's outbound half for one target."""

    def __init__(self, channel: grpc.Channel, self_endpoint: str,
                 timeout_s: float = 10.0):
        self._step = _uu(channel, svc.CLUSTER_SERVICE, "Step",
                         opb.StepRequest, opb.StepResponse)
        self._pull = _us(channel, svc.CLUSTER_SERVICE, "PullBlocks",
                         common.Envelope, opb.DeliverResponse)
        self._meta = (("sender-endpoint", self_endpoint),)
        self._timeout = timeout_s

    def send_consensus(self, channel_id: str, payload: bytes) -> None:
        req = opb.StepRequest()
        req.consensus_request.channel = channel_id
        req.consensus_request.payload = payload
        self._step(req, metadata=self._meta, timeout=self._timeout)

    def submit(self, channel_id: str, env_bytes: bytes,
               config_seq: int = 0) -> opb.SubmitResponse:
        req = opb.StepRequest()
        req.submit_request.channel = channel_id
        req.submit_request.payload = env_bytes
        req.submit_request.last_validation_seq = config_seq
        resp = self._step(req, metadata=self._meta,
                          timeout=self._timeout)
        return resp.submit_response

    def pull_blocks(self, channel_id: str, start: int,
                    end: int) -> list[common.Block]:
        from fabric_tpu.protoutil import protoutil as pu
        seek = opb.SeekInfo()
        seek.start.specified.number = start
        seek.stop.specified.number = end
        ch = pu.make_channel_header(common.HeaderType.DELIVER_SEEK_INFO,
                                    channel_id)
        sh = common.SignatureHeader()
        payload = pu.make_payload(ch, sh, seek.SerializeToString())
        env = common.Envelope(payload=payload.SerializeToString())
        out = []
        for resp in self._pull(env, metadata=self._meta,
                               timeout=self._timeout):
            if resp.WhichOneof("type") == "block":
                block = common.Block()
                block.CopyFrom(resp.block)
                out.append(block)
        return out
