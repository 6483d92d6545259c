"""Cross-cutting utilities: configtxlator, cert expiry, diag, grpc
observability (SURVEY §2.12)."""

import datetime
import json
import os
import subprocess
import sys

from fabric_tpu.common import cryptoutil, diag

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(module, *argv):
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    return subprocess.run([sys.executable, "-m", module, *argv],
                          env=env, capture_output=True, text=True,
                          timeout=120)


class TestConfigtxlator:
    def test_decode_encode_round_trip(self, tmp_path):
        from fabric_tpu.internal import cryptogen
        from fabric_tpu.internal.configtxgen import (
            genesis_block, new_channel_group,
        )
        org = cryptogen.generate_org(str(tmp_path), "o.example.com",
                                     n_peers=1)
        block = genesis_block("ch", new_channel_group({
            "Consortium": "C",
            "Application": {"Organizations": [
                {"Name": "O", "ID": "OMSP",
                 "MSPDir": os.path.join(org, "msp")}]},
            "Orderer": {"OrdererType": "solo", "Organizations": [
                {"Name": "Ord", "ID": "OrdMSP",
                 "MSPDir": os.path.join(org, "msp")}]},
        }))
        pb = tmp_path / "b.block"
        pb.write_bytes(block.SerializeToString())
        out = _cli("fabric_tpu.cmd.configtxlator", "proto_decode",
                   "--type", "common.Block", "--input", str(pb),
                   "--output", str(tmp_path / "b.json"))
        assert out.returncode == 0, out.stderr
        decoded = json.loads((tmp_path / "b.json").read_text())
        assert "dataHash" in decoded["header"]  # genesis number=0 omitted (proto3 default)
        out = _cli("fabric_tpu.cmd.configtxlator", "proto_encode",
                   "--type", "common.Block",
                   "--input", str(tmp_path / "b.json"),
                   "--output", str(tmp_path / "b2.block"))
        assert out.returncode == 0, out.stderr
        assert (tmp_path / "b2.block").read_bytes() == \
            block.SerializeToString()

    def test_compute_update(self, tmp_path):
        from fabric_tpu.protos import configtx as ctxpb
        orig = ctxpb.Config(sequence=1)
        orig.channel_group.version = 0
        orig.channel_group.values["BatchSize"].value = b"a"
        new = ctxpb.Config(sequence=1)
        new.channel_group.version = 0
        new.channel_group.values["BatchSize"].value = b"b"
        (tmp_path / "o.pb").write_bytes(orig.SerializeToString())
        (tmp_path / "n.pb").write_bytes(new.SerializeToString())
        out = _cli("fabric_tpu.cmd.configtxlator", "compute_update",
                   "--channel_id", "ch",
                   "--original", str(tmp_path / "o.pb"),
                   "--updated", str(tmp_path / "n.pb"),
                   "--output", str(tmp_path / "u.pb"))
        assert out.returncode == 0, out.stderr
        upd = ctxpb.ConfigUpdate()
        upd.ParseFromString((tmp_path / "u.pb").read_bytes())
        assert upd.channel_id == "ch"
        assert "BatchSize" in upd.write_set.values


class TestExpirationTracking:
    def _cert(self, days: int) -> bytes:
        from cryptography import x509
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec
        from cryptography.x509.oid import NameOID
        key = ec.generate_private_key(ec.SECP256R1())
        now = datetime.datetime.now(datetime.timezone.utc)
        name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME,
                                             "t")])
        return (x509.CertificateBuilder()
                .subject_name(name).issuer_name(name)
                .public_key(key.public_key())
                .serial_number(1)
                .not_valid_before(now - datetime.timedelta(days=1))
                .not_valid_after(now + datetime.timedelta(days=days))
                .sign(key, hashes.SHA256())
                .public_bytes(
                    __import__("cryptography.hazmat.primitives."
                               "serialization",
                               fromlist=["Encoding"]).Encoding.PEM))

    def test_warns_inside_window(self):
        warnings = []
        t = cryptoutil.track_expiration("test", self._cert(days=3),
                                        warn=warnings.append)
        assert t is None and len(warnings) == 1
        assert "expires within" in warnings[0]

    def test_expired_warns_immediately(self):
        warnings = []
        cryptoutil.track_expiration("test", self._expired(),
                                    warn=warnings.append)
        assert warnings and "expired" in warnings[0]

    def _expired(self) -> bytes:
        from cryptography import x509
        from cryptography.hazmat.primitives import hashes, serialization
        from cryptography.hazmat.primitives.asymmetric import ec
        from cryptography.x509.oid import NameOID
        key = ec.generate_private_key(ec.SECP256R1())
        now = datetime.datetime.now(datetime.timezone.utc)
        name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "t")])
        return (x509.CertificateBuilder()
                .subject_name(name).issuer_name(name)
                .public_key(key.public_key()).serial_number(1)
                .not_valid_before(now - datetime.timedelta(days=9))
                .not_valid_after(now - datetime.timedelta(days=2))
                .sign(key, hashes.SHA256())
                .public_bytes(serialization.Encoding.PEM))

    def test_distant_expiry_arms_timer(self):
        warnings = []
        t = cryptoutil.track_expiration("test", self._cert(days=365),
                                        warn=warnings.append)
        assert t is not None and not warnings
        t.cancel()


class TestDiag:
    def test_thread_dump_contains_all_threads(self):
        import threading

        stop = threading.Event()

        def parked():
            stop.wait(10)

        t = threading.Thread(target=parked, name="parked-thread",
                             daemon=True)
        t.start()
        logs = []
        text = diag.dump_threads(log=lambda fmt, *a: logs.append(
            fmt % a))
        stop.set()
        assert "parked-thread" in text
        assert logs and "thread dump" in logs[0]


class TestGrpcObservability:
    def test_rpc_metrics_counted(self):
        from fabric_tpu.comm.server import (
            GRPCServer, ServerConfig, UNARY_UNARY,
        )
        from fabric_tpu.comm.clients import channel_to, _uu
        from fabric_tpu.common import metrics as m
        from fabric_tpu.protos import gossip as gpb
        provider = m.PrometheusProvider()
        server = GRPCServer(ServerConfig(metrics_provider=provider))
        server.add_service("ftpu.Test", {
            "Ping": (UNARY_UNARY, lambda req, ctx: gpb.Empty(),
                     gpb.Empty, gpb.Empty)})
        server.start()
        try:
            call = _uu(channel_to(server.address), "ftpu.Test",
                       "Ping", gpb.Empty, gpb.Empty)
            for _ in range(3):
                call(gpb.Empty(), timeout=5)
            body = provider.render()
            assert "grpc_server_unary_requests_completed" in body
            assert 'method="Ping"' in body
        finally:
            server.stop()
