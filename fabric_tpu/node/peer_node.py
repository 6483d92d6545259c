"""Peer node assembly: core.yaml → a serving peer process.

Rebuild of `internal/peer/node/start.go:189-911` serve(): wire BCCSP →
local MSP → Peer (ledgers, endorser, chaincode support) → gossip
service (gRPC transport) → gRPC server (Endorser, Deliver, Gateway,
Gossip) → operations endpoint (metrics/healthz/logspec/version).
Config keys mirror core.yaml (`sampleconfig/core.yaml`), env overrides
CORE_* (e.g. CORE_PEER_ADDRESS) via viperutil.
"""

from __future__ import annotations

import importlib
import logging
import os
from typing import Optional

from fabric_tpu.bccsp import factory as bccsp_factory
from fabric_tpu.comm import clients as comm_clients
from fabric_tpu.comm import services as comm_services
from fabric_tpu.comm.gossip_grpc import GRPCGossipTransport
from fabric_tpu.comm.server import GRPCServer, ServerConfig
from fabric_tpu.common import metrics as metrics_mod
from fabric_tpu.common.viperutil import Config
from fabric_tpu.gossip import GossipService
from fabric_tpu.gossip.discovery import DiscoveryConfig
from fabric_tpu.msp import msp_config_from_dir
from fabric_tpu.msp.mspimpl import X509MSP
from fabric_tpu.node.operations import OperationsServer
from fabric_tpu.peer import Peer
from fabric_tpu.peer.deliverclient import Deliverer
from fabric_tpu.peer.gateway import Gateway
from fabric_tpu.protos import common
from fabric_tpu.protoutil import protoutil as pu

logger = logging.getLogger("peer.node")


class _FailoverBroadcast:
    """Broadcast across orderer endpoints with rotation on failure
    (reference: the SDK/gateway orderer failover behavior; a raft
    follower also rejects while leaderless, which counts as failure
    here)."""

    def __init__(self, endpoints):
        self._endpoints = list(endpoints)
        self._clients = {}

    def process_message(self, env):
        last = None
        for _ in range(len(self._endpoints)):
            ep = self._endpoints[0]
            client = self._clients.get(ep)
            if client is None:
                client = comm_clients.BroadcastClient(
                    comm_clients.channel_to(ep), timeout_s=10.0)
                self._clients[ep] = client
            try:
                resp = client.process_message(env)
                if resp.status == common.Status.SUCCESS:
                    return resp
                last = resp
            except Exception as e:
                logger.warning("broadcast to %s failed: %s", ep, e)
                last = None
            self._endpoints.append(self._endpoints.pop(0))
        if last is not None:
            return last
        from fabric_tpu.protos import orderer as opb
        return opb.BroadcastResponse(
            status=common.Status.SERVICE_UNAVAILABLE,
            info="no orderer reachable")


class PeerNode:
    def __init__(self, config: Config):
        self.cfg = config
        self.peer: Optional[Peer] = None
        self.server: Optional[GRPCServer] = None
        self.ops: Optional[OperationsServer] = None
        self.gossip: Optional[GossipService] = None
        self._orderer_channels = []

    # -- assembly (start.go serve()) --

    def start(self) -> None:
        cfg = self.cfg
        # persistent XLA cache: a restarting peer must not recompile the
        # verify programs (minutes) before its first big block
        from fabric_tpu.common import jaxenv
        jaxenv.enable_compilation_cache()
        provider = metrics_mod.provider_from_config(
            cfg.get("metrics.provider", "prometheus"),
            statsd_address=cfg.get("metrics.statsd.address",
                                   "127.0.0.1:8125"),
            statsd_prefix=cfg.get("metrics.statsd.prefix", ""),
            statsd_interval_s=cfg.get_duration(
                "metrics.statsd.writeInterval", 10.0))
        self.metrics = provider
        from fabric_tpu.common import flogging as _flog
        _flog.wire_logging_metrics(provider)
        # round-14 lifecycle tracing: operations.tracing.* knobs (the
        # viperutil lookup is case-insensitive) + span durations into
        # the trace_stage_seconds histogram; /debug/trace reads the
        # always-on flight recorder
        from fabric_tpu.common import tracing as _tracing
        _tracing.configure_from_config(cfg, metrics_provider=provider)
        # round-18 cross-node layer: the commit-latency SLO target
        # (operations.slo.commitP99S -> /healthz components.slo)
        from fabric_tpu.common import clustertrace as _ctrace
        _ctrace.configure_from_config(cfg)
        # round-19 serving knobs: Operations.Overload.* config keys
        # (env remains the override) + the adaptive controller toggle
        from fabric_tpu.common import adaptive as _adaptive
        from fabric_tpu.common import overload as _overload
        _overload.configure_from_config(cfg)
        _adaptive.configure_from_config(cfg)

        fs_path = cfg.get_path("peer.fileSystemPath")
        os.makedirs(fs_path, exist_ok=True)

        bccsp_cfg = dict(cfg.get("peer.BCCSP") or {})
        # default the warm-key persistence under the peer's data dir so
        # a restarted peer's prewarm rebuilds its Q tables before the
        # first block needs them (BCCSP.TPU.WarmKeysDir overrides)
        tpu_cfg = dict(bccsp_cfg.get("TPU") or {})
        tpu_cfg.setdefault("WarmKeysDir",
                           os.path.join(fs_path, "bccsp-warm"))
        bccsp_cfg["TPU"] = tpu_cfg
        csp = bccsp_factory.new_bccsp(
            bccsp_factory.FactoryOpts.from_config(bccsp_cfg))
        # the TPU provider's perf-cliff counters become scrapeable
        # gauges (bccsp_*) on /metrics
        from fabric_tpu.common import profiling
        profiling.publish_provider_stats(provider, csp)
        # round-16 device-cost gauges: per-chip memory occupancy +
        # busy ratios beside the compile/cache counters above
        profiling.publish_devicecost_stats(provider, csp)
        # round-12 overload stages (commit pipeline, gossip inboxes)
        # as overload_* gauges
        profiling.publish_overload_stats(provider)
        # pre-compile the standard validation shapes in the background
        # so the first blocks after (re)start don't stall on device
        # compilation (BCCSP.TPU.Prewarm: false to disable)
        if hasattr(csp, "prewarm") and \
                (bccsp_cfg.get("TPU") or {}).get("Prewarm", True):
            import threading as _threading
            _threading.Thread(target=csp.prewarm, name="bccsp-prewarm",
                              daemon=True).start()

        msp_dir = cfg.get_path("peer.mspConfigPath")
        msp_id = cfg.get("peer.localMspId", "SampleOrg")
        local_msp = X509MSP(csp)
        local_msp.setup(msp_config_from_dir(msp_dir, msp_id, csp=csp))

        # pluggable state database (reference core.yaml
        # ledger.state.stateDatabase goleveldb|CouchDB): "http" points
        # the VersionedDB seam at an external state-server process
        # (fabric_tpu/ledger/stateserver.py, statecouchdb's role)
        state_db_factory = None
        state_kind = cfg.get("ledger.state.stateDatabase", "embedded")
        if str(state_kind).lower() in ("http", "couchdb"):
            state_addr = cfg.get("ledger.state.stateDatabaseAddress",
                                 "127.0.0.1:5984")
            state_token = cfg.get("ledger.state.stateDatabaseAuthToken",
                                  os.environ.get("FTPU_STATE_TOKEN")
                                  or None)
            from fabric_tpu.ledger.stateserver import HTTPVersionedDB

            def state_db_factory(ledger_id, _handle,
                                 _addr=state_addr,
                                 _tok=state_token):
                return HTTPVersionedDB(_addr, ledger_id,
                                       auth_token=_tok)

            logger.info("state database: external http engine at %s",
                        state_addr)

        # pipelined block intake (core.yaml `peer.CommitPipeline`):
        # Depth N > 0 lets each channel validate up to N blocks ahead
        # of the block being committed; 0 (the default) keeps the
        # sequential verify→validate→commit loop
        cp_cfg = dict(cfg.get("peer.CommitPipeline") or {})
        commit_pipeline_depth = int(cp_cfg.get("Depth", 0) or 0)

        self.peer = Peer(fs_path, local_msp, csp,
                         metrics_provider=provider,
                         state_db_factory=state_db_factory,
                         commit_pipeline_depth=commit_pipeline_depth)
        self.msp_id = msp_id

        # gossip over gRPC; external endpoint = peer.address
        address = cfg.get("peer.address", "127.0.0.1:7051")
        self.gossip = GossipService(
            self.peer, GRPCGossipTransport(address), self.peer.mcs,
            org_id=msp_id,
            config=DiscoveryConfig(
                alive_interval_s=cfg.get_duration(
                    "peer.gossip.aliveTimeInterval", 0.3),
                alive_expiration_s=cfg.get_duration(
                    "peer.gossip.aliveExpirationTimeout", 1.5)))
        self.peer.gossip_service = self.gossip

        # cert-expiration tracking + thread-dump diagnostics
        # (reference start.go:319 TrackExpiration, :913 handleSignals)
        from fabric_tpu.common import cryptoutil, diag
        signcert_dir = os.path.join(msp_dir, "signcerts")
        if os.path.isdir(signcert_dir):
            for name in os.listdir(signcert_dir):
                with open(os.path.join(signcert_dir, name), "rb") as f:
                    cryptoutil.track_expiration("peer enrollment",
                                                f.read())
        diag.capture_thread_dumps_on_signal()

        # gRPC server (+ per-service concurrency caps —
        # reference internal/peer/node/grpc_limiters.go, keys
        # peer.limits.concurrency.* in core.yaml:473-485)
        limits = {}
        for key, svc in (
                ("endorserService", comm_services.ENDORSER_SERVICE),
                ("deliverService", comm_services.DELIVER_SERVICE),
                ("gatewayService", comm_services.GATEWAY_SERVICE)):
            n = int(cfg.get(f"peer.limits.concurrency.{key}", 0) or 0)
            if n > 0:
                limits[svc] = n
        sc = ServerConfig(address=address, metrics_provider=provider,
                          concurrency_limits=limits or None)
        tls_cert = cfg.get_path("peer.tls.cert.file")
        if cfg.get_bool("peer.tls.enabled") and tls_cert:
            sc.tls_cert = open(tls_cert, "rb").read()
            sc.tls_key = open(
                cfg.get_path("peer.tls.key.file"), "rb").read()
            root = cfg.get_path("peer.tls.rootcert.file")
            if cfg.get_bool("peer.tls.clientAuthRequired") and root:
                sc.client_root_cas = open(root, "rb").read()
        self.server = GRPCServer(sc)
        self.address = self.server.address

        gateway = Gateway(self.peer, self._broadcast_client())
        gateway.endorsers[msp_id] = self.peer.endorser
        gateway.endorser_source = self._gossip_endorsers
        self._endorser_clients: dict[str, object] = {}
        from fabric_tpu.discovery import DiscoveryService
        self.discovery = DiscoveryService(self.peer, self.gossip)
        gateway.layout_source = (
            lambda cid, cc: self.discovery.chaincode_layouts(
                self.peer.channel(cid), cc)
            if self.peer.channel(cid) else [])
        comm_services.register_endorser(self.server,
                                        self.peer.endorser)
        comm_services.register_gateway(self.server, gateway)
        comm_services.register_discovery(self.server, self.discovery)
        from fabric_tpu.peer.deliverevents import EventsDeliverHandler
        comm_services.register_peer_deliver(
            self.server, EventsDeliverHandler(
                lambda cid: self.peer.channel(cid),
                metrics_provider=provider))
        comm_services.register_gossip(
            self.server, self.gossip.node._on_message)
        self.server.start()

        bootstrap = cfg.get("peer.gossip.bootstrap") or []
        if isinstance(bootstrap, str):
            bootstrap = bootstrap.split()
        self.gossip.start(bootstrap=bootstrap)

        # operations endpoint (+ the local admin surface the peer CLI
        # uses — the reference routes `peer channel join` through the
        # in-process cscc; here it is an operator-local HTTP call)
        ops_addr = cfg.get("operations.listenAddress", "127.0.0.1:0")
        self.ops = OperationsServer(
            ops_addr, metrics_provider=provider,
            profile_enabled=bool(cfg.get("operations.profile.enabled",
                                         False)))
        self.ops.register_checker("peer", lambda: None)
        # the TPU provider's breaker state on /healthz: degraded means
        # verdicts are served (bit-identically) by the sw path while
        # the device cools down — report, don't fail the node. The
        # elastic-mesh sub-state rides the same string
        # (`device;degraded_mesh:<k>/<n>`): serving on k of n chips
        # after a quarantine — or 1/<requested> when startup device
        # enumeration failed — is degraded-but-serving, never a
        # failed check.
        health = getattr(csp, "health", None)
        if callable(health):
            self.ops.register_checker("bccsp", health)
        # ...and WHICH backend that `device` is: platform:kind:count
        # as JAX reports it (a TPU provider on a CPU-only JAX also
        # answers `device`)
        device_info = getattr(csp, "device_info", None)
        if callable(device_info):
            self.ops.register_checker(
                "bccsp_device", lambda: "{platform}:{device_kind}:"
                "{count}".format(**device_info()))
        # overload state (ok | shedding:<stages>): shedding is
        # degraded-but-serving — load past capacity refused cleanly,
        # never a failed health check
        self.ops.register_checker("overload", _overload.health)
        # commit-latency SLO burn state (ok | burning:<rate>) — this
        # IS the node that commits, so the e2e histogram/error budget
        # fills here; a sustained burn auto-dumps the flight recorder
        self.ops.register_checker("slo", _ctrace.slo_health)
        # round-19 adaptive admission controller: closes the loop
        # from the slo/overload/devicecost signals above onto the
        # registered serving knobs (disabled -> no thread, no moves)
        self.adaptive = _adaptive.start_controller(
            csp=csp, metrics_provider=provider)
        self.ops.register_checker("adaptive", _adaptive.health)
        self.ops.set_trace_peers(
            cfg.get("operations.tracing.clusterPeers")
            or os.environ.get("FTPU_TRACE_PEERS", ""))
        self.ops.register_handler("/admin", self._admin_http)
        self.ops.start()

        # register python chaincodes listed in config (in-process
        # runtime; external CCaaS chaincodes register over gRPC)
        for spec in cfg.get("chaincode.registered") or []:
            name, _, target = spec.partition("=")
            mod_name, _, cls_name = target.partition(":")
            cls = getattr(importlib.import_module(mod_name), cls_name)
            self.peer.chaincode_support.register(name, cls())
            logger.info("registered in-process chaincode %s (%s)",
                        name, target)
        # chaincode-as-a-service processes (reference ccaas_builder):
        # "name=host:port" — the peer dials the chaincode server
        from fabric_tpu.core.chaincode.external import (
            ExternalChaincodeClient,
        )
        for spec in cfg.get("chaincode.external") or []:
            name, _, address = spec.partition("=")
            self.peer.chaincode_support.register(
                name, ExternalChaincodeClient(
                    name, address, metrics_provider=provider))
            logger.info("registered external chaincode %s at %s",
                        name, address)

        # join channels whose genesis blocks are on disk
        for path in cfg.get("peer.channels") or []:
            with open(path, "rb") as f:
                block = common.Block()
                block.ParseFromString(f.read())
            self.join_channel(block)
        logger.info("peer node up: grpc=%s ops=%s", self.address,
                    self.ops.address)

    def _broadcast_client(self):
        endpoints = self.cfg.get("peer.ordererEndpoints") or []
        if not endpoints:
            return None
        return _FailoverBroadcast(endpoints)

    def _deliver_client_factory(self):
        endpoints = list(self.cfg.get("peer.ordererEndpoints") or [])

        def source():
            if not endpoints:
                return None
            # failover rotation (reference blocksprovider endpoint
            # shuffling)
            endpoints.append(endpoints.pop(0))
            return comm_clients.DeliverClient(
                comm_clients.channel_to(endpoints[-1]))
        return source

    def join_channel(self, genesis_block) -> None:
        from fabric_tpu.core.chaincode import ChaincodeDefinition
        channel = self.peer.join_channel(genesis_block)
        # lifecycle-lite: registered USER chaincodes are defined with
        # the channel-default endorsement policy (the state-backed
        # _lifecycle flow supersedes this per-definition)
        from fabric_tpu.core.scc import SYSTEM_CHAINCODES
        for name in self.peer.chaincode_support.registered():
            if name not in SYSTEM_CHAINCODES:
                channel.define_chaincode(ChaincodeDefinition(name=name))
        source = self._deliver_client_factory()
        self.gossip.initialize_channel(
            channel,
            lambda adapter: Deliverer(
                adapter, self.peer.signer, source, self.peer.mcs,
                metrics_provider=getattr(self, "metrics", None)))
        logger.info("joined channel %s", channel.channel_id)

    def _gossip_endorsers(self, channel_id: str) -> dict:
        """One endorser per org, resolved from gossip channel
        membership (the discovery-service feed of the reference's
        gateway registry)."""
        out = {}
        gchannel = self.gossip.node.channel(channel_id)
        if gchannel is None:
            return out
        for m in gchannel.members():
            if not m.identity:
                continue
            org = self.gossip._org_of_identity(m.identity)
            if org is None or org in out or org == self.msp_id:
                continue
            client = self._endorser_clients.get(m.member.endpoint)
            if client is None:
                client = comm_clients.EndorserClient(
                    comm_clients.channel_to(m.member.endpoint))
                self._endorser_clients[m.member.endpoint] = client
            out[org] = client
        return out

    def _admin_http(self, method: str, path: str,
                    body: bytes) -> tuple[int, bytes]:
        import json
        parts = [p for p in path.split("/") if p]
        try:
            if method == "POST" and parts[:2] == ["admin", "channels"]:
                block = common.Block()
                block.ParseFromString(body)
                self.join_channel(block)
                return 201, json.dumps({"status": "joined"}).encode()
            if method == "GET" and parts[:2] == ["admin", "channels"]:
                return 200, json.dumps(
                    {"channels": sorted(self.peer.channels)}).encode()
            if method == "GET" and parts[:2] == ["admin", "chaincodes"]:
                return 200, json.dumps(
                    {"chaincodes":
                     self.peer.chaincode_support.registered()}).encode()
            # snapshots (reference: `peer snapshot` CLI → snapshotgrpc)
            if parts[:2] == ["admin", "snapshots"]:
                return self._snapshot_http(method, parts, body)
        except Exception as e:
            return 400, json.dumps({"error": str(e)}).encode()
        return 404, json.dumps({"error": "not found"}).encode()

    def _snapshot_http(self, method: str, parts: list[str],
                       body: bytes) -> tuple[int, bytes]:
        import json
        # /admin/snapshots/<channel>  POST body={"height": N} submit
        #                             GET → pending + completed
        # /admin/snapshots/<channel>/join  POST body={"dir": path}
        channel = parts[2] if len(parts) > 2 else ""
        if len(parts) == 4 and parts[3] == "join" and method == "POST":
            req = json.loads(body or b"{}")
            ch = self.peer.join_channel_by_snapshot(req["dir"], channel)
            from fabric_tpu.core.chaincode import ChaincodeDefinition
            for name in self.peer.chaincode_support.registered():
                ch.define_chaincode(ChaincodeDefinition(name=name))
            source = self._deliver_client_factory()
            self.gossip.initialize_channel(
                ch, lambda adapter: Deliverer(
                    adapter, self.peer.signer, source, self.peer.mcs,
                    metrics_provider=getattr(self, "metrics", None)))
            return 201, json.dumps(
                {"status": "joined", "height": ch.ledger.height}
            ).encode()
        ch = self.peer.channel(channel)
        if ch is None:
            return 404, json.dumps({"error": "unknown channel"}).encode()
        if method == "POST":
            req = json.loads(body or b"{}")
            height = int(req.get("height") or ch.ledger.height)
            ch.ledger.snapshot_requests.submit(height)
            return 201, json.dumps({"status": "submitted",
                                    "height": height}).encode()
        completed_dir = ch.ledger.snapshots_dir()
        completed = sorted(os.listdir(completed_dir)) \
            if os.path.isdir(completed_dir) else []
        return 200, json.dumps(
            {"pending": ch.ledger.snapshot_requests.pending(),
             "completed": completed,
             "dir": completed_dir}).encode()

    def stop(self) -> None:
        from fabric_tpu.common import adaptive as _adaptive
        _adaptive.stop_controller()
        if self.gossip:
            self.gossip.stop()
        if self.server:
            self.server.stop()
        if self.ops:
            self.ops.stop()
        if self.peer:
            self.peer.close()
        # final metrics flush + flusher-thread shutdown (statsd)
        stop_metrics = getattr(getattr(self, "metrics", None), "stop",
                               None)
        if stop_metrics is not None:
            stop_metrics()
