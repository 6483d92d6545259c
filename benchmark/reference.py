"""The plain reference: what a correct peer must report for a chain.

Imports nothing of the program. It reads committed transactions with a
minimal protobuf wire reader, checks identities against the channel's
CA certificates and signatures with OpenSSL (strict DER, low-S only, as
Fabric requires), evaluates the N-of-M endorsement policy, replays MVCC
over a plain dict, and hashes block headers by the wire format's rule.
`accept_high_s` / `skip_mvcc` break one stated guarantee each: they are
the controls, never used by a measuring run.
"""

from __future__ import annotations

import hashlib
from typing import Iterable, Optional, Sequence

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature, encode_dss_signature)

P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
HALF_N = P256_N >> 1

VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11


# ---- wire format -----------------------------------------------------------

def header_bytes(number: int, previous_hash: bytes, data_hash: bytes) -> bytes:
    return (number.to_bytes(8, "big")
            + len(previous_hash).to_bytes(4, "big") + previous_hash
            + len(data_hash).to_bytes(4, "big") + data_hash)


def header_hash(number: int, previous_hash: bytes, data_hash: bytes) -> bytes:
    return hashlib.sha256(
        header_bytes(number, previous_hash, data_hash)).digest()


def data_hash(envelopes: Iterable[bytes]) -> bytes:
    h = hashlib.sha256()
    for e in envelopes:
        h.update(e)
    return h.digest()


def _varint(buf: bytes, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def fields(buf: bytes) -> dict:
    """proto3 message -> {field number: [values]}; varints as int,
    length-delimited as bytes. Fixed-width fields do not occur in the
    messages read here and are an error."""
    out: dict = {}
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = bytes(buf[i:i + ln])
            if len(val) != ln:
                raise ValueError("truncated field")
            i += ln
        else:
            raise ValueError(f"unexpected wire type {wt}")
        out.setdefault(num, []).append(val)
    return out


def _one(f: dict, num: int, default=b""):
    v = f.get(num)
    return v[-1] if v else default


class ParsedTx:
    """What the reference needs of one committed envelope."""
    __slots__ = ("payload", "signature", "creator", "nonce", "tx_id",
                 "channel", "prp", "endorsements", "namespace", "reads",
                 "writes")


def parse_tx(env: bytes) -> ParsedTx:
    t = ParsedTx()
    e = fields(env)
    t.payload = _one(e, 1)
    t.signature = _one(e, 2)
    p = fields(t.payload)
    hdr = fields(_one(p, 1))
    ch = fields(_one(hdr, 1))
    sh = fields(_one(hdr, 2))
    t.channel = _one(ch, 4).decode()
    t.tx_id = _one(ch, 5).decode()
    t.creator = _one(sh, 1)
    t.nonce = _one(sh, 2)
    tx = fields(_one(p, 2))
    action = fields(tx[1][0])
    cap = fields(_one(action, 2))
    cea = fields(_one(cap, 2))
    t.prp = _one(cea, 1)
    t.endorsements = []
    for raw in cea.get(2, []):
        en = fields(raw)
        t.endorsements.append((_one(en, 1), _one(en, 2)))
    cc_action = fields(_one(fields(t.prp), 2))
    txrw = fields(_one(cc_action, 1))
    t.reads, t.writes = [], []
    t.namespace = ""
    for raw in txrw.get(2, []):
        ns = fields(raw)
        t.namespace = _one(ns, 1).decode()
        kv = fields(_one(ns, 2))
        for r in kv.get(1, []):
            rf = fields(r)
            ver = None
            if 2 in rf:
                vf = fields(rf[2][-1])
                ver = (_one(vf, 1, 0), _one(vf, 2, 0))
            t.reads.append((_one(rf, 1).decode(), ver))
        for w in kv.get(3, []):
            wf = fields(w)
            t.writes.append((_one(wf, 1).decode(), _one(wf, 3)))
    return t


# ---- identities and signatures ---------------------------------------------

class Verifier:
    """Identity and signature checks against the channel's CAs."""

    def __init__(self, trust_roots: dict, accept_high_s: bool = False):
        self._roots = {m: x509.load_pem_x509_certificate(p)
                       for m, p in trust_roots.items()}
        self._idents: dict = {}
        self._accept_high_s = accept_high_s

    def identity(self, serialized: bytes):
        """(mspid, public key) for a SerializedIdentity whose
        certificate chains to its MSP's CA, else None. Cached."""
        hit = self._idents.get(serialized, 0)
        if hit != 0:
            return hit
        out = None
        try:
            f = fields(serialized)
            mspid = _one(f, 1).decode()
            cert = x509.load_pem_x509_certificate(_one(f, 2))
            ca = self._roots.get(mspid)
            if ca is not None:
                ca.public_key().verify(
                    cert.signature, cert.tbs_certificate_bytes,
                    ec.ECDSA(cert.signature_hash_algorithm))
                out = (mspid, cert.public_key())
        except (ValueError, InvalidSignature, KeyError):
            out = None
        self._idents[serialized] = out
        return out

    def signature_ok(self, pub, signature: bytes, message: bytes) -> bool:
        try:
            r, s = decode_dss_signature(signature)
        except ValueError:
            return False
        if encode_dss_signature(r, s) != signature:
            return False            # non-canonical DER
        if s > HALF_N and not self._accept_high_s:
            return False            # the malleable twin is refused
        try:
            pub.verify(signature, message, ec.ECDSA(hashes.SHA256()))
            return True
        except InvalidSignature:
            return False


def signatures_verdict(t: ParsedTx, v: Verifier, channel: str,
                       orgs: Sequence[str], need: int) -> int:
    """The validation code before MVCC: creator signature, then N of
    the policy's orgs with a valid endorsement, one per identity."""
    ident = v.identity(t.creator)
    if (ident is None or t.channel != channel
            or t.tx_id != hashlib.sha256(t.nonce + t.creator).hexdigest()
            or not v.signature_ok(ident[1], t.signature, t.payload)):
        return BAD_CREATOR_SIGNATURE
    seen, ok_orgs = set(), set()
    for endorser, sig in t.endorsements:
        if endorser in seen:
            continue
        seen.add(endorser)
        eid = v.identity(endorser)
        if eid is None or eid[0] not in orgs:
            continue
        if v.signature_ok(eid[1], sig, t.prp + endorser):
            ok_orgs.add(eid[0])
    return VALID if len(ok_orgs) >= need else ENDORSEMENT_POLICY_FAILURE


# ---- MVCC and state --------------------------------------------------------

class LedgerModel:
    """Versioned key-value state with Fabric's block-level MVCC: a read
    conflicts if an earlier valid transaction of the same block wrote
    the key, or the committed version is not the one read."""

    def __init__(self, skip_mvcc: bool = False):
        self.state: dict = {}        # key -> ((block, tx), value)
        self.height = 1              # genesis is block 0
        self._skip_mvcc = skip_mvcc

    def version(self, key: str) -> Optional[tuple]:
        hit = self.state.get(key)
        return hit[0] if hit else None

    def value(self, key: str) -> Optional[bytes]:
        hit = self.state.get(key)
        return hit[1] if hit else None

    def commit_block(self, number: int, txs) -> bytes:
        """`txs`: (code before MVCC, reads, writes) per transaction, in
        block order. Returns the final validation codes."""
        if number != self.height:
            raise ValueError(f"block {number} at height {self.height}")
        flags = bytearray(len(txs))
        pending: dict = {}
        for i, (code, reads, writes) in enumerate(txs):
            if code == VALID and not self._skip_mvcc:
                for key, ver in reads:
                    if key in pending or self.version(key) != ver:
                        code = MVCC_READ_CONFLICT
                        break
            flags[i] = code
            if code == VALID:
                for key, value in writes:
                    pending[key] = ((number, i), value)
        self.state.update(pending)
        self.height += 1
        return bytes(flags)
