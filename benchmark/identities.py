"""Deterministic MSP material for the benchmark's channel.

Same layout as `fabric_tpu/internal/cryptogen.py` writes (the program's
`msp_config_from_dir` and configtxgen profile read it), but every key,
serial number and CA signature is a function of the seed, so the same
seed gives the same certificates, transaction ids and block hashes.
JAX-free: worker processes import this.
"""

from __future__ import annotations

import datetime
import hashlib
import os
from dataclasses import dataclass

from cryptography import x509
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.x509.oid import NameOID

P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
_NOT_BEFORE = datetime.datetime(2020, 1, 1)
_NOT_AFTER = datetime.datetime(2099, 1, 1)

NODE_OU_CONFIG = b"""NodeOUs:
  Enable: true
  ClientOUIdentifier:
    OrganizationalUnitIdentifier: client
  PeerOUIdentifier:
    OrganizationalUnitIdentifier: peer
  AdminOUIdentifier:
    OrganizationalUnitIdentifier: admin
  OrdererOUIdentifier:
    OrganizationalUnitIdentifier: orderer
"""


def _scalar(seed: int, label: str) -> int:
    h = hashlib.sha512(f"ftpu-bench/{seed}/{label}".encode()).digest()
    return int.from_bytes(h, "big") % (P256_N - 1) + 1


def derive_key(seed: int, label: str) -> ec.EllipticCurvePrivateKey:
    return ec.derive_private_key(_scalar(seed, label), ec.SECP256R1())


def _serial(seed: int, label: str) -> int:
    return _scalar(seed, "serial/" + label) >> 100 | 1


def pem_cert(cert) -> bytes:
    return cert.public_bytes(serialization.Encoding.PEM)


def pem_key(key) -> bytes:
    return key.private_bytes(serialization.Encoding.PEM,
                             serialization.PrivateFormat.PKCS8,
                             serialization.NoEncryption())


def _make_ca(seed: int, cn: str, org: str):
    key = derive_key(seed, "ca/" + cn)
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, cn),
                      x509.NameAttribute(NameOID.ORGANIZATION_NAME, org)])
    cert = (
        x509.CertificateBuilder()
        .subject_name(name).issuer_name(name)
        .public_key(key.public_key())
        .serial_number(_serial(seed, cn))
        .not_valid_before(_NOT_BEFORE).not_valid_after(_NOT_AFTER)
        .add_extension(x509.BasicConstraints(ca=True, path_length=None),
                       critical=True)
        .add_extension(
            x509.KeyUsage(digital_signature=True, content_commitment=False,
                          key_encipherment=False, data_encipherment=False,
                          key_agreement=False, key_cert_sign=True,
                          crl_sign=True, encipher_only=False,
                          decipher_only=False), critical=True)
        .sign(key, hashes.SHA256(), ecdsa_deterministic=True))
    return cert, key


def _issue(seed: int, cn: str, org: str, ou: str, ca_cert, ca_key):
    key = derive_key(seed, "node/" + cn)
    cert = (
        x509.CertificateBuilder()
        .subject_name(x509.Name([
            x509.NameAttribute(NameOID.COMMON_NAME, cn),
            x509.NameAttribute(NameOID.ORGANIZATION_NAME, org),
            x509.NameAttribute(NameOID.ORGANIZATIONAL_UNIT_NAME, ou)]))
        .issuer_name(ca_cert.subject)
        .public_key(key.public_key())
        .serial_number(_serial(seed, cn))
        .not_valid_before(_NOT_BEFORE).not_valid_after(_NOT_AFTER)
        .add_extension(x509.BasicConstraints(ca=False, path_length=None),
                       critical=True)
        .sign(ca_key, hashes.SHA256(), ecdsa_deterministic=True))
    return cert, key


@dataclass(frozen=True)
class Signer:
    """One identity the synthesiser signs as. `serialized` is the
    marshaled SerializedIdentity {1: mspid, 2: PEM cert}."""
    mspid: str
    cert_pem: bytes
    key_pem: bytes
    serialized: bytes


@dataclass(frozen=True)
class Org:
    name: str            # "Org1"
    mspid: str           # "Org1MSP"
    msp_dir: str         # org-level verification MSP (channel config)
    peer: Signer         # the org's endorsing peer
    peer_msp_dir: str


@dataclass(frozen=True)
class Material:
    orgs: tuple          # application orgs, each with one endorsing peer
    client: Signer       # the one submitting client (a user of orgs[0])
    orderer: Signer
    orderer_org_msp_dir: str
    orderer_mspid: str
    trust_roots: dict    # mspid -> CA cert PEM (what the reference trusts)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def serialized_identity(mspid: str, cert_pem: bytes) -> bytes:
    """ftpu.msp.SerializedIdentity, deterministic proto3 encoding."""
    m = mspid.encode()
    return (b"\x0a" + _varint(len(m)) + m +
            b"\x12" + _varint(len(cert_pem)) + cert_pem)


def _write(path: str, data: bytes) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(data)


def _write_local_msp(msp_dir: str, ca_cert, cert, key) -> None:
    _write(os.path.join(msp_dir, "cacerts", "ca-cert.pem"), pem_cert(ca_cert))
    _write(os.path.join(msp_dir, "signcerts", "cert.pem"), pem_cert(cert))
    _write(os.path.join(msp_dir, "keystore", "key_sk"), pem_key(key))
    _write(os.path.join(msp_dir, "config.yaml"), NODE_OU_CONFIG)


def _signer(mspid: str, cert, key) -> Signer:
    pem = pem_cert(cert)
    return Signer(mspid=mspid, cert_pem=pem, key_pem=pem_key(key),
                  serialized=serialized_identity(mspid, pem))


def generate(out_dir: str, seed: int, n_orgs: int) -> Material:
    """Write the MSP directories for `n_orgs` application orgs (one
    endorsing peer each, one client under the first) and one orderer
    org; return what the synthesiser signs with."""
    orgs = []
    roots = {}
    client = None
    for i in range(1, n_orgs + 1):
        domain = f"org{i}.example.com"
        mspid = f"Org{i}MSP"
        org_dir = os.path.join(out_dir, "peerOrganizations", domain)
        ca_cert, ca_key = _make_ca(seed, f"ca.{domain}", domain)
        _write(os.path.join(org_dir, "msp", "cacerts", "ca-cert.pem"),
               pem_cert(ca_cert))
        _write(os.path.join(org_dir, "msp", "config.yaml"), NODE_OU_CONFIG)
        admin_cert, _ = _issue(seed, f"Admin@{domain}", domain, "admin",
                               ca_cert, ca_key)
        _write(os.path.join(org_dir, "msp", "admincerts", "admin-cert.pem"),
               pem_cert(admin_cert))
        cn = f"peer0.{domain}"
        cert, key = _issue(seed, cn, domain, "peer", ca_cert, ca_key)
        peer_msp = os.path.join(org_dir, "peers", cn, "msp")
        _write_local_msp(peer_msp, ca_cert, cert, key)
        if i == 1:
            ucert, ukey = _issue(seed, f"User1@{domain}", domain, "client",
                                 ca_cert, ca_key)
            client = _signer(mspid, ucert, ukey)
        roots[mspid] = pem_cert(ca_cert)
        orgs.append(Org(name=f"Org{i}", mspid=mspid,
                        msp_dir=os.path.join(org_dir, "msp"),
                        peer=_signer(mspid, cert, key),
                        peer_msp_dir=peer_msp))
    domain = "example.com"
    ord_dir = os.path.join(out_dir, "ordererOrganizations", domain)
    ca_cert, ca_key = _make_ca(seed, f"ca.{domain}", domain)
    _write(os.path.join(ord_dir, "msp", "cacerts", "ca-cert.pem"),
           pem_cert(ca_cert))
    _write(os.path.join(ord_dir, "msp", "config.yaml"), NODE_OU_CONFIG)
    admin_cert, _ = _issue(seed, f"Admin@{domain}", domain, "admin",
                           ca_cert, ca_key)
    _write(os.path.join(ord_dir, "msp", "admincerts", "admin-cert.pem"),
           pem_cert(admin_cert))
    cert, key = _issue(seed, f"orderer0.{domain}", domain, "orderer",
                       ca_cert, ca_key)
    roots["OrdererMSP"] = pem_cert(ca_cert)
    return Material(orgs=tuple(orgs), client=client,
                    orderer=_signer("OrdererMSP", cert, key),
                    orderer_org_msp_dir=os.path.join(ord_dir, "msp"),
                    orderer_mspid="OrdererMSP", trust_roots=roots)
