"""nwo — "network world order" multi-process test harness.

Rebuild of `integration/nwo/network.go` (SURVEY §4): renders
core.yaml / orderer.yaml / configtx.yaml / crypto-config.yaml,
runs the cryptogen + configtxgen CLIs, launches REAL peer/orderer
processes (`python -m fabric_tpu.cmd.{peer,orderer}`) on random ports,
joins channels through the admin APIs, and tears everything down.
Node processes run the sw BCCSP with JAX pinned to the CPU, except a
peer started with a `BCCSP: {Default: TPU}` mapping: that one inherits
the caller's environment untouched (on a chip host it is the chip's
only owner — one process per chip).
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_http(url: str, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as resp:
                if resp.status == 200:
                    return
        except Exception as e:
            last = e
        time.sleep(0.2)
    raise TimeoutError(f"{url} not healthy: {last}")


class Node:
    def __init__(self, name: str, argv: list[str], log_path: str,
                 extra_env: dict | None = None, pin_cpu: bool = True):
        self.name = name
        self.log_path = log_path
        self.log = open(log_path, "ab")
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO,
            "FABRIC_LOGGING_SPEC": env.get("FABRIC_LOGGING_SPEC",
                                           "info"),
        })
        if pin_cpu:
            env["JAX_PLATFORMS"] = "cpu"
        if extra_env:
            env.update(extra_env)
        self.proc = subprocess.Popen(argv, stdout=self.log,
                                     stderr=subprocess.STDOUT, env=env)

    def kill(self, sig=signal.SIGKILL) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=5)
        self.log.close()

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None


class Network:
    """2-org (1 peer each by default) × N-orderer raft network."""

    def __init__(self, root: str, n_orderers: int = 3,
                 peers_per_org: int = 1, channel: str = "testchannel",
                 state_backend: dict | None = None,
                 spare_orderers: int = 0,
                 batch_timeout: str = "250ms",
                 max_message_count: int = 10):
        self.root = root
        # the small test default cuts a block per handful of invokes;
        # the orderer's own default (orderer/blockcutter.py) is 2s/500
        self.batch_timeout = batch_timeout
        self.max_message_count = max_message_count
        self.channel = channel
        self.n_orderers = n_orderers
        # spare orderers get crypto material and ports but are NOT in
        # the genesis consenter set — they join later (onboarding /
        # consenter-addition tests)
        self.spare_orderers = spare_orderers
        self.peers_per_org = peers_per_org
        # org -> "http" runs that org's peers against an external
        # state-server process (the statecouchdb deployment shape)
        self.state_backend = state_backend or {}
        self.state_server_port = free_port() if self.state_backend \
            else None
        self.nodes: dict[str, Node] = {}
        # (general grpc, ops, mTLS cluster listener) per orderer
        self.orderer_ports = [(free_port(), free_port(), free_port())
                              for _ in range(n_orderers +
                                             spare_orderers)]
        self.peer_ports = {}   # (org, i) -> (grpc, ops)
        for org in ("org1", "org2"):
            for i in range(peers_per_org):
                self.peer_ports[(org, i)] = (free_port(), free_port())
        self._generate_material()

    # -- config generation --

    def orderer_tls_cert_path(self, i: int) -> str:
        return os.path.join(
            self.root, "crypto", "ordererOrganizations", "example.com",
            "orderers", f"orderer{i}.example.com", "tls", "server.crt")

    def orderer_admin_msp_dir(self) -> str:
        return os.path.join(
            self.root, "crypto", "ordererOrganizations", "example.com",
            "users", "Admin@example.com", "msp")

    def _generate_material(self) -> None:
        os.makedirs(self.root, exist_ok=True)
        crypto = os.path.join(self.root, "crypto")
        with open(os.path.join(self.root, "crypto-config.yaml"),
                  "w") as f:
            yaml.safe_dump({
                "OrdererOrgs": [{
                    "Name": "Orderer", "Domain": "example.com",
                    "Template": {"Count": self.n_orderers +
                                 self.spare_orderers}}],
                "PeerOrgs": [
                    {"Name": "Org1", "Domain": "org1.example.com",
                     "Template": {"Count": self.peers_per_org},
                     "Users": {"Count": 1}},
                    {"Name": "Org2", "Domain": "org2.example.com",
                     "Template": {"Count": self.peers_per_org},
                     "Users": {"Count": 1}},
                ],
            }, f)
        self._run_cli("fabric_tpu.cmd.cryptogen", "generate",
                      "--config",
                      os.path.join(self.root, "crypto-config.yaml"),
                      "--output", crypto)

        orderer_eps = [f"127.0.0.1:{g}" for g, _o, _c in
                       self.orderer_ports[:self.n_orderers]]

        _otls = self.orderer_tls_cert_path

        profile = {
            "Consortium": "SampleConsortium",
            "Capabilities": {"V2_0": True},
            "Application": {
                "Organizations": [
                    {"Name": "Org1", "ID": "Org1MSP",
                     "MSPDir": os.path.join(
                         crypto, "peerOrganizations",
                         "org1.example.com", "msp")},
                    {"Name": "Org2", "ID": "Org2MSP",
                     "MSPDir": os.path.join(
                         crypto, "peerOrganizations",
                         "org2.example.com", "msp")},
                ],
                "Capabilities": {"V2_0": True},
            },
            "Orderer": {
                "OrdererType": "etcdraft",
                "Addresses": orderer_eps,
                "BatchTimeout": self.batch_timeout,
                "BatchSize": {"MaxMessageCount": self.max_message_count},
                "Raft": {"Consenters": [
                    {"Host": "127.0.0.1", "Port": c,
                     "ClientTLSCert": _otls(i),
                     "ServerTLSCert": _otls(i)}
                    for i, (_g, _o, c) in
                    enumerate(self.orderer_ports[:self.n_orderers])]},
                "Organizations": [{
                    "Name": "OrdererOrg", "ID": "OrdererMSP",
                    "MSPDir": os.path.join(
                        crypto, "ordererOrganizations",
                        "example.com", "msp"),
                    "OrdererEndpoints": orderer_eps}],
                "Capabilities": {"V2_0": True},
            },
        }
        with open(os.path.join(self.root, "configtx.yaml"), "w") as f:
            yaml.safe_dump({"Profiles": {"Genesis": profile}}, f)
        self.genesis_path = os.path.join(self.root, "genesis.block")
        self._run_cli("fabric_tpu.cmd.configtxgen",
                      "-profile", "Genesis",
                      "-channelID", self.channel,
                      "-configPath",
                      os.path.join(self.root, "configtx.yaml"),
                      "-outputBlock", self.genesis_path)

    def _run_cli(self, module: str, *argv) -> str:
        env = dict(os.environ)
        env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
        out = subprocess.run(
            [sys.executable, "-m", module, *argv], env=env,
            capture_output=True, text=True, timeout=120)
        if out.returncode != 0:
            raise RuntimeError(
                f"{module} {argv} failed:\n{out.stdout}\n{out.stderr}")
        return out.stdout

    # -- node lifecycle --

    def start_orderer(self, i: int,
                      extra_env: dict | None = None) -> Node:
        grpc_port, ops_port, cluster_port = self.orderer_ports[i]
        crypto = os.path.join(self.root, "crypto")
        tls_dir = os.path.join(
            crypto, "ordererOrganizations", "example.com", "orderers",
            f"orderer{i}.example.com", "tls")
        cfg = {
            "General": {
                "ListenAddress": "127.0.0.1",
                "ListenPort": grpc_port,
                "LocalMSPDir": os.path.join(
                    crypto, "ordererOrganizations", "example.com",
                    "orderers", f"orderer{i}.example.com", "msp"),
                "LocalMSPID": "OrdererMSP",
                # a spare orderer starts with no channel: the test
                # joins it through channel participation (osnadmin),
                # which refuses a channel the node already bootstrapped
                "BootstrapFiles": ([self.genesis_path]
                                   if i < self.n_orderers else []),
            },
            "FileLedger": {"Location": os.path.join(
                self.root, f"orderer{i}", "ledger")},
            "Cluster": {
                "Endpoint": f"127.0.0.1:{cluster_port}",
                "ListenAddress": "127.0.0.1",
                "ListenPort": cluster_port,
                "ServerCertificate": os.path.join(tls_dir,
                                                  "server.crt"),
                "ServerPrivateKey": os.path.join(tls_dir, "server.key"),
                "ClientCertificate": os.path.join(tls_dir,
                                                  "server.crt"),
                "ClientPrivateKey": os.path.join(tls_dir, "server.key"),
                "RootCAs": [os.path.join(tls_dir, "ca.crt")],
            },
            "Consensus": {"TickInterval": "100ms"},
            "Admin": {"ListenAddress": f"127.0.0.1:{ops_port}"},
        }
        path = os.path.join(self.root, f"orderer{i}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(cfg, f)
        node = Node(f"orderer{i}",
                    [sys.executable, "-m", "fabric_tpu.cmd.orderer",
                     "start", "--config", path],
                    os.path.join(self.root, f"orderer{i}.log"),
                    extra_env=extra_env)
        self.nodes[f"orderer{i}"] = node
        return node

    def start_peer(self, org: str, i: int = 0, bootstrap: str = "",
                   bccsp: dict | None = None) -> Node:
        """`bccsp` is this peer's `peer.BCCSP` mapping (core.yaml);
        None = the sw default."""
        grpc_port, ops_port = self.peer_ports[(org, i)]
        crypto = os.path.join(self.root, "crypto")
        orderer_eps = [f"127.0.0.1:{g}" for g, _o, _c in
                       self.orderer_ports]
        cfg = {
            "peer": {
                "id": f"peer{i}.{org}.example.com",
                "address": f"127.0.0.1:{grpc_port}",
                "localMspId": f"{org.capitalize()}MSP",
                "mspConfigPath": os.path.join(
                    crypto, "peerOrganizations", f"{org}.example.com",
                    "peers", f"peer{i}.{org}.example.com", "msp"),
                "fileSystemPath": os.path.join(
                    self.root, f"peer_{org}_{i}"),
                "ordererEndpoints": orderer_eps,
                "gossip": {"bootstrap": bootstrap or
                           f"127.0.0.1:{self.peer_ports[('org1', 0)][0]}"},
            },
            "chaincode": {"registered": [
                "assetcc=fabric_tpu.examples.assetcc:AssetChaincode"]},
            "operations": {
                "listenAddress": f"127.0.0.1:{ops_port}"},
        }
        if self.state_backend.get(org) == "http":
            cfg["ledger"] = {"state": {
                "stateDatabase": "http",
                "stateDatabaseAddress":
                    f"127.0.0.1:{self.state_server_port}"}}
        if bccsp:
            cfg["peer"]["BCCSP"] = bccsp
        path = os.path.join(self.root, f"core_{org}_{i}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(cfg, f)
        on_device = (bccsp or {}).get("Default", "SW") != "SW"
        node = Node(f"peer_{org}_{i}",
                    [sys.executable, "-m", "fabric_tpu.cmd.peer",
                     "node", "start", "--config", path],
                    os.path.join(self.root, f"peer_{org}_{i}.log"),
                    pin_cpu=not on_device)
        self.nodes[f"peer_{org}_{i}"] = node
        return node

    def start_state_server(self) -> Node:
        node = Node("stateserver",
                    [sys.executable, "-m",
                     "fabric_tpu.ledger.stateserver",
                     "--data-dir", os.path.join(self.root,
                                                "stateserver"),
                     "--listen",
                     f"127.0.0.1:{self.state_server_port}"],
                    os.path.join(self.root, "stateserver.log"))
        self.nodes["stateserver"] = node
        return node

    def start_all(self) -> None:
        if self.state_server_port is not None:
            self.start_state_server()
            wait_http(f"http://127.0.0.1:{self.state_server_port}"
                      "/healthz")
        for i in range(self.n_orderers):
            self.start_orderer(i)
        for i in range(self.n_orderers):
            wait_http(f"http://127.0.0.1:{self.orderer_ports[i][1]}"
                      "/healthz")
        for org in ("org1", "org2"):
            for i in range(self.peers_per_org):
                self.start_peer(org, i)
        for (org, i), (_g, ops) in self.peer_ports.items():
            wait_http(f"http://127.0.0.1:{ops}/healthz")

    def join_all(self) -> None:
        for (org, i), (_g, ops) in sorted(self.peer_ports.items()):
            self._run_cli("fabric_tpu.cmd.peer", "channel", "join",
                          "--ops", f"127.0.0.1:{ops}",
                          "--block", self.genesis_path)

    # -- client helpers --

    def peer_cli_identity(self, org: str) -> list[str]:
        crypto = os.path.join(self.root, "crypto")
        return ["--msp-dir",
                os.path.join(crypto, "peerOrganizations",
                             f"{org}.example.com", "users",
                             f"User1@{org}.example.com", "msp"),
                "--msp-id", f"{org.capitalize()}MSP"]

    def invoke(self, org: str, peer_i: int, *cc_args,
               transient: str = "") -> str:
        gport = self.peer_ports[(org, peer_i)][0]
        argv = ["chaincode", "invoke", "--gateway",
                f"127.0.0.1:{gport}",
                *self.peer_cli_identity(org),
                "-C", self.channel, "-n", "assetcc", "-a", *cc_args]
        if transient:
            argv += ["--transient", transient]
        return self._run_cli("fabric_tpu.cmd.peer", *argv)

    def query(self, org: str, peer_i: int, *cc_args) -> str:
        gport = self.peer_ports[(org, peer_i)][0]
        return self._run_cli(
            "fabric_tpu.cmd.peer", "chaincode", "query", "--gateway",
            f"127.0.0.1:{gport}", *self.peer_cli_identity(org),
            "-C", self.channel, "-n", "assetcc", "-a", *cc_args)

    def osnadmin(self, orderer_i: int, *argv) -> str:
        ops = self.orderer_ports[orderer_i][1]
        return self._run_cli("fabric_tpu.cmd.osnadmin", "channel",
                             *argv, "--orderer-address",
                             f"127.0.0.1:{ops}")

    def teardown(self) -> None:
        for node in self.nodes.values():
            node.kill()
