"""Orderer node assembly: orderer.yaml → a serving orderer process.

Rebuild of `orderer/common/server/main.go:73-300` Main(): local config
→ BCCSP → local MSP → multichannel registrar (solo + raft consenters,
gRPC cluster transport) → gRPC server (AtomicBroadcast, Deliver,
Cluster) → operations endpoint with the channel-participation admin
API mounted (reference: admin server + osnadmin). Env overrides
ORDERER_* (e.g. ORDERER_GENERAL_LISTENADDRESS).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Optional

from fabric_tpu.bccsp import factory as bccsp_factory
from fabric_tpu.comm import services as comm_services
from fabric_tpu.comm.cluster_grpc import GRPCClusterTransport
from fabric_tpu.comm.server import GRPCServer, ServerConfig
from fabric_tpu.common import metrics as metrics_mod
from fabric_tpu.common.deliver import DeliverHandler
from fabric_tpu.common.viperutil import Config
from fabric_tpu.msp import msp_config_from_dir
from fabric_tpu.msp.mspimpl import X509MSP
from fabric_tpu.node.operations import OperationsServer
from fabric_tpu.orderer import raft as raft_mod, solo
from fabric_tpu.orderer.broadcast import BroadcastHandler
from fabric_tpu.orderer.channelparticipation import (
    ChannelParticipation, ParticipationError,
)
from fabric_tpu.orderer.multichannel import Registrar
from fabric_tpu.protos import common

logger = logging.getLogger("orderer.node")


class OrdererNode:
    def __init__(self, config: Config):
        self.cfg = config
        self.server: Optional[GRPCServer] = None
        self.ops: Optional[OperationsServer] = None
        self.registrar: Optional[Registrar] = None
        self.cluster: Optional[GRPCClusterTransport] = None

    def start(self) -> None:
        cfg = self.cfg
        from fabric_tpu.common import jaxenv
        jaxenv.enable_compilation_cache()
        provider = metrics_mod.provider_from_config(
            cfg.get("Metrics.Provider", "prometheus"),
            statsd_address=cfg.get("Metrics.Statsd.Address",
                                   "127.0.0.1:8125"),
            statsd_prefix=cfg.get("Metrics.Statsd.Prefix", ""),
            statsd_interval_s=cfg.get_duration(
                "Metrics.Statsd.WriteInterval", 10.0))
        self.metrics = provider
        from fabric_tpu.common import flogging as _flog
        _flog.wire_logging_metrics(provider)
        # round-14 lifecycle tracing: Operations.Tracing.* knobs +
        # span durations into the trace_stage_seconds histogram (the
        # recorder itself is always on; /debug/trace reads it)
        from fabric_tpu.common import tracing as _tracing
        _tracing.configure_from_config(cfg, metrics_provider=provider)
        # round-18 cross-node layer: the commit-latency SLO target
        # (Operations.SLO.CommitP99S -> /healthz components.slo)
        from fabric_tpu.common import clustertrace as _ctrace
        _ctrace.configure_from_config(cfg)
        # round-19 serving knobs: Operations.Overload.* config keys
        # (env remains the override) + the adaptive controller toggle
        from fabric_tpu.common import adaptive as _adaptive
        from fabric_tpu.common import overload as _overload
        _overload.configure_from_config(cfg)
        _adaptive.configure_from_config(cfg)

        bccsp_cfg = cfg.get("General.BCCSP") or {}
        csp = bccsp_factory.new_bccsp(
            bccsp_factory.FactoryOpts.from_config(bccsp_cfg))
        # breaker/degradation counters (bccsp_*) scrapeable on the
        # orderer's /metrics too, not just the peer's
        from fabric_tpu.common import profiling
        profiling.publish_provider_stats(provider, csp)
        # round-16 device-cost gauges: per-chip memory occupancy +
        # busy ratios beside the compile/cache counters above
        profiling.publish_devicecost_stats(provider, csp)
        # round-12 overload stages (broadcast ingress, raft event
        # queues, write stages, admission window) as overload_* gauges
        profiling.publish_overload_stats(provider)
        msp_dir = cfg.get_path("General.LocalMSPDir")
        msp_id = cfg.get("General.LocalMSPID", "OrdererMSP")
        local_msp = X509MSP(csp)
        local_msp.setup(msp_config_from_dir(msp_dir, msp_id, csp=csp))
        signer = local_msp.get_default_signing_identity()

        address = cfg.get("General.ListenAddress", "127.0.0.1") + ":" \
            + str(cfg.get("General.ListenPort", 7050))

        # Cluster transport. With TLS material configured (reference
        # `General.Cluster` in orderer.yaml), cluster RPCs get a
        # DEDICATED mutual-TLS listener and callers are authenticated
        # against each channel's consenter set; without it, the Cluster
        # service shares the general listener unauthenticated (dev
        # only — a warning is logged on first use).
        cluster_server_cert = cfg.get_path("Cluster.ServerCertificate")
        cluster_tls = bool(cluster_server_cert)
        cluster_listen = (cfg.get("Cluster.ListenAddress", "127.0.0.1")
                          + ":" + str(cfg.get("Cluster.ListenPort", 0)))
        root_ca_paths = cfg.get("Cluster.RootCAs") or []
        if isinstance(root_ca_paths, str):
            root_ca_paths = [root_ca_paths]
        root_cas = b"".join(
            open(cfg.resolve_path(p), "rb").read()
            for p in root_ca_paths) or None

        def _read(key):
            p = cfg.get_path(key)
            return open(p, "rb").read() if p else None

        client_cert = _read("Cluster.ClientCertificate") or \
            (_read("Cluster.ServerCertificate") if cluster_tls else None)
        client_key = _read("Cluster.ClientPrivateKey") or \
            (_read("Cluster.ServerPrivateKey") if cluster_tls else None)

        # the advertised consenter endpoint
        cluster_ep = cfg.get("Cluster.Endpoint",
                             cluster_listen if cluster_tls else address)
        self.cluster = GRPCClusterTransport(
            cluster_ep,
            tls_root_ca=root_cas if cluster_tls else None,
            client_cert=client_cert, client_key=client_key,
            require_client_auth=cluster_tls,
            metrics_provider=provider)

        ledger_dir = cfg.get_path("FileLedger.Location")
        os.makedirs(ledger_dir, exist_ok=True)
        tick = cfg.get_duration("Consensus.TickInterval", 0.1)
        def _kafka_deprecated(support):
            raise ValueError(
                f"[{support.channel_id}] the kafka consenter is "
                "deprecated (as in the reference's 2.x line) and not "
                "provided; migrate the channel to etcdraft")

        self.registrar = Registrar(
            ledger_dir, signer, csp,
            {"solo": solo.consenter,
             "raft": raft_mod.consenter(self.cluster,
                                        tick_interval_s=tick,
                                        metrics_provider=provider),
             "etcdraft": raft_mod.consenter(self.cluster,
                                            tick_interval_s=tick,
                                            metrics_provider=provider),
             "kafka": _kafka_deprecated},
            metrics_provider=provider,
            cluster_transport=self.cluster)
        # batched-ordering pipeline gauges (orderer_batch_*) beside
        # the provider's bccsp_* ones
        profiling.publish_order_stats(provider, self.registrar)
        from fabric_tpu.orderer.broadcast import BroadcastMetrics
        broadcast = BroadcastHandler(
            self.registrar, metrics=BroadcastMetrics(provider))
        from fabric_tpu.common.deliver import DeliverMetrics
        deliver = DeliverHandler(self.registrar.get_chain,
                                 metrics=DeliverMetrics(provider))
        participation = ChannelParticipation(self.registrar)

        from fabric_tpu.common import cryptoutil, diag
        signcert_dir = os.path.join(msp_dir, "signcerts")
        if os.path.isdir(signcert_dir):
            for name in os.listdir(signcert_dir):
                with open(os.path.join(signcert_dir, name), "rb") as f:
                    cryptoutil.track_expiration("orderer enrollment",
                                                f.read())
        diag.capture_thread_dumps_on_signal()

        sc = ServerConfig(address=address, metrics_provider=provider)
        tls_cert = cfg.get_path("General.TLS.Certificate")
        if cfg.get_bool("General.TLS.Enabled") and tls_cert:
            sc.tls_cert = open(tls_cert, "rb").read()
            sc.tls_key = open(
                cfg.get_path("General.TLS.PrivateKey"), "rb").read()
        self.server = GRPCServer(sc)
        self.address = self.server.address
        comm_services.register_broadcast(self.server, broadcast)
        comm_services.register_deliver(self.server, deliver)
        if cluster_tls:
            cluster_sc = ServerConfig(
                address=cluster_listen,
                tls_cert=open(cluster_server_cert, "rb").read(),
                tls_key=open(
                    cfg.get_path("Cluster.ServerPrivateKey"),
                    "rb").read(),
                client_root_cas=root_cas,  # mTLS required
                metrics_provider=provider)
            self.cluster_server = GRPCServer(cluster_sc)
            comm_services.register_cluster(self.cluster_server,
                                           self.cluster)
            self.cluster_server.start()
            logger.info("cluster mTLS listener on %s",
                        self.cluster_server.address)
        else:
            self.cluster_server = None
            comm_services.register_cluster(self.server, self.cluster)
        self.server.start()

        ops_addr = cfg.get("Admin.ListenAddress",
                           cfg.get("Operations.ListenAddress",
                                   "127.0.0.1:0"))
        self.ops = OperationsServer(
            ops_addr, metrics_provider=provider,
            profile_enabled=bool(cfg.get("Operations.Profile.Enabled",
                                         False)))
        self.ops.register_checker("orderer", lambda: None)
        # breaker state of the sig-filter's TPU provider on /healthz
        # (device | degraded | probing); degraded still serves. The
        # elastic-mesh sub-state (`;degraded_mesh:<k>/<n>` — serving
        # on k of n chips after a quarantine, or 1/<requested> when
        # startup enumeration failed) rides the same string.
        health = getattr(csp, "health", None)
        if callable(health):
            self.ops.register_checker("bccsp", health)
        # onboarding/replication state (discover|pull|verify|commit|
        # failed per channel) — degraded-but-serving, like the bccsp
        # breaker: catch-up in progress never fails the health check
        self.ops.register_checker("onboarding",
                                  self.registrar.onboarding_health)
        # overload state (ok | shedding:<stages>): shedding is
        # degraded-but-serving — the orderer refusing load past
        # capacity with SERVICE_UNAVAILABLE is working as designed
        self.ops.register_checker("overload", _overload.health)
        # commit-latency SLO burn state (ok | burning:<rate>):
        # degraded-but-serving, the breaker-trip trigger discipline —
        # a sustained burn also auto-dumps the flight recorder
        self.ops.register_checker("slo", _ctrace.slo_health)
        # round-19 adaptive admission controller: closes the loop
        # from the slo/overload/devicecost signals above onto the
        # registered serving knobs (disabled -> no thread, no moves)
        self.adaptive = _adaptive.start_controller(
            csp=csp, metrics_provider=provider)
        self.ops.register_checker("adaptive", _adaptive.health)
        self.ops.set_trace_peers(
            cfg.get("Operations.Tracing.ClusterPeers")
            or os.environ.get("FTPU_TRACE_PEERS", ""))
        self.ops.register_handler("/participation",
                                  self._participation_http(
                                      participation))
        self.ops.start()

        # bootstrap: join channels from configured genesis blocks
        for path in cfg.get("General.BootstrapFiles") or []:
            with open(path, "rb") as f:
                block = common.Block()
                block.ParseFromString(f.read())
            try:
                self.registrar.join(block)
            except ValueError as e:
                if "already exists" not in str(e):
                    raise
        logger.info("orderer node up: grpc=%s admin=%s", self.address,
                    self.ops.address)

    @staticmethod
    def _participation_http(participation: ChannelParticipation):
        """REST-ish mapping (reference
        `orderer/common/channelparticipation/rest.go`):
        GET  /participation/v1/channels
        GET  /participation/v1/channels/<name>
        POST /participation/v1/channels        (body: config block)
        DELETE /participation/v1/channels/<name>"""
        from google.protobuf.json_format import MessageToDict

        def handler(method: str, path: str,
                    body: bytes) -> tuple[int, bytes]:
            parts = [p for p in path.split("/") if p]
            # ["participation", "v1", "channels", <name>?]
            try:
                if method == "GET" and len(parts) == 3:
                    out = MessageToDict(participation.list())
                    return 200, json.dumps(out).encode()
                if method == "GET" and len(parts) == 4:
                    out = MessageToDict(participation.info(parts[3]))
                    return 200, json.dumps(out).encode()
                if method == "POST" and len(parts) == 3:
                    info = participation.join(body)
                    return 201, json.dumps(
                        MessageToDict(info)).encode()
                if method == "DELETE" and len(parts) == 4:
                    participation.remove(parts[3])
                    return 204, b""
            except ParticipationError as e:
                return e.status, json.dumps(
                    {"error": str(e)}).encode()
            return 405, json.dumps({"error": "bad request"}).encode()
        return handler

    def stop(self) -> None:
        from fabric_tpu.common import adaptive as _adaptive
        _adaptive.stop_controller()
        if self.registrar:
            self.registrar.halt()
        if self.cluster:
            self.cluster.close()
        if getattr(self, "cluster_server", None):
            self.cluster_server.stop()
        if self.server:
            self.server.stop()
        if self.ops:
            self.ops.stop()
        stop_metrics = getattr(getattr(self, "metrics", None), "stop",
                               None)
        if stop_metrics is not None:
            stop_metrics()
