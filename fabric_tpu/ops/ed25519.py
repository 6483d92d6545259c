"""Batched Ed25519 verification core on TPU.

The device half of the multi-scheme dispatch: where `ops/p256.py`
rebuilds `crypto/ecdsa.Verify` as one fixed-shape XLA program, this
module does the same for Ed25519 — the signature scheme Fabric's
modern-MSP and smart-BFT-style identity scenarios use (the
committee-consensus measurement in PAPERS.md, arXiv:2302.00418, shows
exactly this cost dominating at scale).

Per lane the kernel decides the cofactorless equation

    [S]B + [k](-A) == R

with every policy gate (canonical encodings, S < L, small-order
rejection, challenge k = SHA-512(R‖A‖M) mod L) already applied on the
host by `bccsp/ed25519_host.prep_verify` — mirroring where the P-256
path applies DER/low-S/range gates, so device and host accept/reject
sets are structurally identical.

TPU-first design:
  * Field arithmetic is `ops/mont.MontMod(2^255 - 19)` on the shared
    13-bit/20-limb int32 layout (`ops/limb.py`): the sparse-prime fold
    in `limb.Mod` needs m > 2^255, which 2^255 - 19 misses by a hair —
    Montgomery REDC (the BN254 discipline) covers it with the same
    vmap/shard_map batching. The compact fori_loop REDC form keeps the
    ladder's ~3k multiplies compilable.
  * Extended twisted Edwards coordinates with the COMPLETE a = -1
    addition law (add-2008-hwcd-3): one branchless formula for P+Q,
    P+P and P+∞ — ed25519's d is a non-square and a = -1 a square, so
    completeness holds unconditionally and padded/identity lanes need
    no special casing.
  * [S]B rides a fixed-base 8-bit comb over B (ZERO doublings — 32
    gathered points, 5 tree levels), through the SAME table
    build/persist/sidecar seam as `ops/comb.py` (B is a universal
    constant like G; the table persists beside gtab8.npy).
  * [k](-A) is a per-lane 2-bit Shamir-style ladder (the proven
    `p256.double_scalar_mul` shape): a 4-entry multiples table, then
    128 steps of two doublings plus one branchless table add.
"""

from __future__ import annotations

import functools
import logging

import numpy as np

import jax.numpy as jnp
from jax import lax

from fabric_tpu.bccsp import ed25519_host as edh
from fabric_tpu.ops import comb, limb, mont
from fabric_tpu.ops.limb import L
from fabric_tpu.ops.p256 import _bar

logger = logging.getLogger("ops.ed25519")

P_ED = edh.P
L_ED = edh.L

# compact-REDC Montgomery context: the ladder's multiply count (~3k
# per lane) with unrolled REDC would blow the HLO past what this
# container compiles in minutes (the BN254 tower lesson)
FED = mont.MontMod(P_ED, unroll=False)

WBITS = comb.WBITS              # 8-bit comb windows, as the G/Q tables
NWIN = comb.NWIN
NENT = comb.NENT

_R2 = FED.r2_mod_m              # to-Montgomery factor (int)
_R2_LIMBS = limb.int_to_limbs(_R2)
_ONE_M = limb.int_to_limbs(FED.r_mod_m)          # mont(1)
_D2_M = limb.int_to_limbs(edh.D2 * FED.R % P_ED)  # mont(2d)


def _to_mont(v):
    """Plain canonical limbs -> Montgomery domain (one REDC mul)."""
    return FED.mul(v, jnp.asarray(_R2_LIMBS))


# ---------------------------------------------------------------------------
# Extended twisted Edwards arithmetic over limb tensors (a = -1)
# ---------------------------------------------------------------------------

def ed_add(p, q):
    """Complete addition (add-2008-hwcd-3): tuples of (…, L) int32
    Montgomery-domain coordinates (X, Y, Z, T). Mirrors
    `ed25519_host.pt_add` exactly."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    d2 = jnp.broadcast_to(jnp.asarray(_D2_M), X1.shape)
    a = FED.mul(FED.sub(Y1, X1), FED.sub(Y2, X2))
    b = FED.mul(FED.add(Y1, X1), FED.add(Y2, X2))
    c = FED.mul(FED.mul(T1, d2), T2)
    dd = FED.mul(Z1, Z2)
    dd = FED.add(dd, dd)
    a, b, c, dd = _bar(a, b, c, dd)
    e, f, g, h = FED.sub(b, a), FED.sub(dd, c), FED.add(dd, c), \
        FED.add(b, a)
    e, f, g, h = _bar(e, f, g, h)
    return _bar(FED.mul(e, f), FED.mul(g, h), FED.mul(f, g),
                FED.mul(e, h))


def ed_double(p):
    """a = -1 doubling (dbl-2008-hwcd); complete, ~2 muls cheaper than
    ed_add(p, p). Mirrors `ed25519_host.pt_double` exactly."""
    X1, Y1, Z1, _ = p
    a = FED.mul(X1, X1)
    b = FED.mul(Y1, Y1)
    c = FED.mul(Z1, Z1)
    c = FED.add(c, c)
    xy = FED.add(X1, Y1)
    a, b, c, xy = _bar(a, b, c, xy)
    h = FED.add(a, b)
    e = FED.sub(h, FED.mul(xy, xy))
    g = FED.sub(a, b)
    f = FED.add(c, g)
    e, f, g, h = _bar(e, f, g, h)
    return _bar(FED.mul(e, f), FED.mul(g, h), FED.mul(f, g),
                FED.mul(e, h))


def _identity(shape):
    one = jnp.broadcast_to(jnp.asarray(_ONE_M), shape)
    zero = jnp.zeros(shape, dtype=jnp.int32)
    return (zero, one, one, zero)


# ---------------------------------------------------------------------------
# Fixed-base comb table for B (host-precomputed constants, persisted
# through the comb.py sidecar seam — B is a universal constant like G)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def b_tables() -> np.ndarray:
    """(NWIN * NENT, 4, L) int32 — T_B[i*NENT + j] = j * 2^(8i) * B in
    Montgomery-domain extended coordinates (Z = mont(1), T = X*Y).
    Entry j=0 is the identity. Built once over Python ints (exact),
    persisted beside the G tables ($FABRIC_TPU_EDTAB_CACHE, default
    <checkout>/.cache/edtab8.npy, empty string disables) with the
    same sha256 sidecar/verify-on-load/rebuild contract as
    `comb.g_tables` — a corrupt table must rebuild, never feed the
    kernel wrong points."""
    import os

    from fabric_tpu.common import jaxenv
    cache = os.environ.get(
        "FABRIC_TPU_EDTAB_CACHE", jaxenv.local_cache("edtab8.npy"))
    if cache:
        try:
            if comb.verify_digest_sidecar(cache) is not False:
                arr = np.load(cache)
                if (arr.dtype == np.int32
                        and arr.shape == (NWIN * NENT, 4, L)):
                    return arr
        except FileNotFoundError:
            pass
        except Exception as e:
            logger.warning("Ed25519 B-table cache %s unreadable (%s); "
                           "rebuilding", cache, e)
    out = np.zeros((NWIN * NENT, 4, L), dtype=np.int32)
    base = edh.from_affine(edh.BX, edh.BY)
    for i in range(NWIN):
        acc = edh._IDENT
        for j in range(NENT):
            if j == 0:
                x, y = 0, 1
            else:
                x, y = edh.to_affine(acc)
            coords = (x, y, 1, x * y % P_ED)
            for c in range(4):
                out[i * NENT + j, c] = limb.int_to_limbs(
                    coords[c] * FED.R % P_ED)
            acc = edh.pt_add(acc, base)
        for _ in range(WBITS):
            base = edh.pt_double(base)
    if cache:
        try:
            os.makedirs(os.path.dirname(cache), exist_ok=True)
            tmp = cache + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                np.save(f, out)
            digest = comb.file_sha256(tmp)
            os.replace(tmp, cache)
            comb.write_digest_sidecar(cache, digest)
        except Exception as e:
            logger.warning("Ed25519 B-table cache persist to %s failed "
                           "(%s); next start rebuilds", cache, e)
    return out


def _tree_reduce4(X, Y, Z, T):
    """(B, M, L) extended-point arrays -> (B, L) sum via log2(M)
    complete-add levels (the comb._tree_reduce shape, 4 coords)."""
    while X.shape[1] > 1:
        if X.shape[1] % 2:          # pad with the identity
            pad = [(0, 0), (0, 1), (0, 0)]
            X = jnp.pad(X, pad)
            T = jnp.pad(T, pad)
            Y = jnp.pad(Y, pad)
            Y = Y.at[:, -1, :].set(jnp.asarray(_ONE_M))
            Z = jnp.pad(Z, pad)
            Z = Z.at[:, -1, :].set(jnp.asarray(_ONE_M))
        X, Y, Z, T = ed_add(
            (X[:, 0::2], Y[:, 0::2], Z[:, 0::2], T[:, 0::2]),
            (X[:, 1::2], Y[:, 1::2], Z[:, 1::2], T[:, 1::2]))
    return X[:, 0], Y[:, 0], Z[:, 0], T[:, 0]


def comb_mul_base(s, tab):
    """[S]B via the fixed-base comb: s (B, L) canonical scalar limbs,
    tab the b_tables() device array. 32 gathered points, zero
    doublings."""
    w = comb._windows(s)                        # (B, NWIN)
    win = jnp.arange(NWIN, dtype=jnp.int32)[None, :]
    pts = jnp.take(tab, win * NENT + w, axis=0)  # (B, NWIN, 4, L)
    return _tree_reduce4(pts[:, :, 0], pts[:, :, 1], pts[:, :, 2],
                         pts[:, :, 3])


def _select4(idx, table):
    """Branchless 4-way point select: idx (B,), table a list of four
    extended points as tuples of (B, L) coords."""
    w = idx[:, None]
    out = []
    for c in range(4):
        lo = jnp.where(w < 1, table[0][c], table[1][c])
        hi = jnp.where(w < 3, table[2][c], table[3][c])
        out.append(jnp.where(w < 2, lo, hi))
    return tuple(out)


def ladder_mul(k, pt):
    """[k]pt for a batch: k (B, L) canonical scalar limbs, pt an
    extended point of (B, L) coords. 2-bit windows, 128 fori_loop
    steps of two doublings + one complete table add (the
    p256.double_scalar_mul shape)."""
    Bsz = k.shape[0]
    ident = _identity((Bsz, L))
    p2 = ed_double(pt)
    p3 = ed_add(p2, pt)
    table = [ident, pt, p2, p3]

    def body(i, acc):
        acc = ed_double(ed_double(acc))
        pos = 254 - 2 * i

        def bit(b):
            j = b // limb.W
            off = b % limb.W
            return (lax.dynamic_slice_in_dim(k, j, 1,
                                             axis=1)[:, 0] >> off) & 1

        sel = _select4(bit(pos) + 2 * bit(pos + 1), table)
        return ed_add(acc, sel)

    return lax.fori_loop(0, 128, body, ident)


# ---------------------------------------------------------------------------
# The batched verify kernel
# ---------------------------------------------------------------------------

def verify_core(tab, s8, k8, anx8, ay8, rx8, ry8, premask):
    """Batched Ed25519 accept/reject.

    tab: b_tables() as a device array (passed in, like q_flat, so the
        provider controls placement/replication under a mesh).
    s8, k8: (B, 32) uint8 big-endian rows — S and the SHA-512
        challenge k (host-reduced mod L; window extraction only, no
        scalar arithmetic on device).
    anx8, ay8: (B, 32) uint8 big-endian affine coordinates of -A.
    rx8, ry8: (B, 32) uint8 big-endian affine coordinates of R.
    premask: (B,) bool — host gate verdicts (encoding canonicality,
        S range, small-order policy); dead lanes carry the identity
        for A/R so the complete formulas stay on curve points.
    Returns (B,) bool accept mask: premask & ([S]B + [k](-A) == R).
    """
    s = limb.be_bytes_to_limbs_jnp(s8)
    k = limb.be_bytes_to_limbs_jnp(k8)
    anx = _to_mont(limb.be_bytes_to_limbs_jnp(anx8))
    ay = _to_mont(limb.be_bytes_to_limbs_jnp(ay8))
    rx = _to_mont(limb.be_bytes_to_limbs_jnp(rx8))
    ry = _to_mont(limb.be_bytes_to_limbs_jnp(ry8))

    sb = comb_mul_base(s, tab)
    neg_a = (anx, ay, jnp.broadcast_to(jnp.asarray(_ONE_M), anx.shape),
             FED.mul(anx, ay))
    ka = ladder_mul(k, neg_a)
    X3, Y3, Z3, _ = ed_add(sb, ka)

    def eq(a, b):
        return jnp.all(FED.canonical(a) == FED.canonical(b), axis=-1)

    okx = eq(X3, FED.mul(rx, Z3))
    oky = eq(Y3, FED.mul(ry, Z3))
    return premask & okx & oky


# -- host staging helper (numpy; the provider's prep path) --

def stage_rows(prep, bucket: int):
    """Pack `prep` — a list of per-lane `ed25519_host.prep_verify`
    results (None = host-rejected) — into the kernel's operand rows.
    Dead/padded lanes carry zero scalars and identity points, so every
    lane's math stays on the curve. Returns (s8, k8, anx8, ay8, rx8,
    ry8, premask)."""
    s8 = np.zeros((bucket, 32), dtype=np.uint8)
    k8 = np.zeros((bucket, 32), dtype=np.uint8)
    anx8 = np.zeros((bucket, 32), dtype=np.uint8)
    ay8 = np.zeros((bucket, 32), dtype=np.uint8)
    rx8 = np.zeros((bucket, 32), dtype=np.uint8)
    ry8 = np.zeros((bucket, 32), dtype=np.uint8)
    premask = np.zeros(bucket, dtype=bool)
    # identity (0, 1) for every dead lane
    one = (1).to_bytes(32, "big")
    ay8[:] = np.frombuffer(one, np.uint8)
    ry8[:] = np.frombuffer(one, np.uint8)
    for i, p in enumerate(prep):
        if p is None:
            continue
        s, k, neg_ax, ay, rx, ry = p
        premask[i] = True
        for row, v in ((s8, s), (k8, k), (anx8, neg_ax), (ay8, ay),
                       (rx8, rx), (ry8, ry)):
            row[i] = np.frombuffer(v.to_bytes(32, "big"), np.uint8)
    return s8, k8, anx8, ay8, rx8, ry8, premask
