"""Pallas TPU kernel: the per-signature complete-add tree in VMEM.

Round-2 profiling (ARCHITECTURE.md "Round-2 kernel") showed the comb
pipeline is HBM-bound under plain XLA: one complete add at
(30720, 64, 20) costs ~670 ms because every temporary of the RCB15
formula (~30 of them, ~150 MB each at that shape) materializes to HBM
between fusion islands, while the raw multiply+carry compute is ~100 ms.
This kernel runs the WHOLE 31-add tree (plus the projective verify
check) for a tile of signatures inside one Pallas program, so the
20-limb working set never leaves VMEM.

Layout (the whole point of the kernel):
  * limb index = LEADING axis — a pure compile-time dimension, so limb
    shifts/carries/folds are register renames, never data movement;
  * batch = the (sublane, lane) tile: every arithmetic op is a clean
    elementwise VPU op over (M, BLOCK_B) int32 tiles;
  * the tree pairs points by contiguous halves of the sublane axis
    (point sums are commutative, so halving is as good as
    odd/even interleave and needs no shuffles), re-packing to 8
    sublanes as M shrinks so deep tree levels keep full vregs.

The arithmetic mirrors fabric_tpu/ops/limb.py (13-bit limbs, carry3,
fold-at-2^256, offset subtraction) and fabric_tpu/ops/p256.py cadd
(RCB15 Alg. 1) exactly — same bounds, same semantics, differentially
tested against the Python-int reference. Reference semantics being
accelerated: `bccsp/sw/ecdsa.go:41-57` under the validator pool
(`core/committer/txvalidator/v20/validator.go:180-237`).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from fabric_tpu.ops import limb, p256
from fabric_tpu.ops.limb import L, MASK, W

BLOCK_B = 512               # batch lanes per kernel program
VMEM_LIMIT = 100 * 1024 * 1024      # Mosaic scoped-VMEM cap per program

# Lane-count granule for callers that slice a batch into dispatch
# chunks (the provider's overlapped verify pipeline): chunks aligned
# to this never force `tree_verify_points` to pad a partial Mosaic
# tile per chunk, so every pipeline span reuses one compiled shape.
LANE_ALIGN = 128


def aligned_span(lanes: int, mesh_size: int = 1) -> int:
    """Round a requested pipeline-chunk lane count to the kernel/mesh
    granule: a multiple of LANE_ALIGN * mesh_size (floor, min one
    granule) — the chunk shim between the provider's PipelineChunk
    config and the Pallas tree's tile constraints."""
    granule = LANE_ALIGN * max(1, mesh_size)
    return max(granule, (lanes // granule) * granule)


# ---------------------------------------------------------------------------
# Limb-leading modular arithmetic (mirrors limb.Mod, axis 0 = limbs)
# ---------------------------------------------------------------------------

class KMod:
    """limb.Mod twin with the limb axis LEADING instead of trailing.

    Shapes are (nlimbs, *tile); all tile ops are elementwise. Constants
    are reused from the proven limb.Mod instance so the two
    implementations cannot drift.

    Pallas kernels may not close over array constants, so the constant
    vectors are packed into one (NCONST, L) int32 array passed as a
    kernel input and re-bound inside the kernel via `bind()`; outside
    a kernel the numpy closures work directly (plain XLA).
    """

    # packed-constant row layout (see pack_consts)
    _ROWS = ("c256", "sub_off", "m_limbs", "curve_a", "curve_b3")
    NCONST = len(_ROWS) + L                 # + fold_hi rows

    def __init__(self, mod: limb.Mod):
        self.mod = mod
        self.fold_hi = mod.fold_hi          # (L, L) numpy int32
        self.c256 = mod.c256                # (L,)
        self.sub_off = mod.sub_off          # (L,)
        self.m_limbs = mod.m_limbs          # (L,)
        self._bound = None                  # jnp (NCONST, L) when bound

    def pack_consts(self) -> np.ndarray:
        """(NCONST, L) int32: rows [c256, sub_off, m_limbs, A, B3,
        fold_hi[0..L-1]] — the kernel-input twin of the closures."""
        rows = [self.c256, self.sub_off, self.m_limbs, _A_K, _B3_K]
        return np.concatenate(
            [np.stack(rows), self.fold_hi]).astype(np.int32)

    def bind(self, carr) -> "KMod":
        """Shallow copy whose constants come from the packed array
        `carr` (a value read from a kernel input ref)."""
        import copy
        b = copy.copy(self)
        b._bound = carr
        return b

    def _row(self, name: str, like):
        """Constant row -> (L, 1, ...) broadcastable against like."""
        if self._bound is not None:
            # bound array is pre-shaped (NCONST, L, 1, 1): slicing gives
            # a broadcast-ready (L, 1, 1) with no shape cast (Mosaic
            # does not support 1D->3D vector reshapes)
            if name.startswith("fold_hi"):
                idx = len(self._ROWS) + int(name.split(":")[1])
            else:
                idx = self._ROWS.index(name)
            return self._bound[idx]
        else:
            src = {"c256": self.c256, "sub_off": self.sub_off,
                   "m_limbs": self.m_limbs, "curve_a": _A_K,
                   "curve_b3": _B3_K}
            if name.startswith("fold_hi"):
                arr = self.fold_hi[int(name.split(":")[1])]
            else:
                arr = src[name]
            v = jnp.asarray(np.asarray(arr, dtype=np.int32))
        return v.reshape(v.shape + (1,) * (like.ndim - 1))

    # -- carries --

    @staticmethod
    def carry3(x):
        for _ in range(3):
            lo = x & MASK
            c = x >> W
            x = lo + jnp.concatenate(
                [jnp.zeros_like(c[:1]), c[:-1]], axis=0)
        return x

    @staticmethod
    def full_carry(x):
        n = x.shape[0]
        outs = []
        c = jnp.zeros_like(x[0])
        for i in range(n):
            t = x[i] + c
            outs.append(t & MASK)
            c = t >> W
        return jnp.stack(outs, axis=0)

    # -- schoolbook product, limb-leading --

    @staticmethod
    def mul_columns(a, b):
        """(L, *t) x (L, *t) -> (2L, *t) product columns (no carry)."""
        pad_tail = [(0, 0)] * (b.ndim - 1)
        acc = None
        for i in range(L):
            p = a[i][None] * b                          # (L, *t)
            p = jnp.pad(p, [(i, L - i)] + pad_tail)     # place at column i
            acc = p if acc is None else acc + p
        return acc

    def _fold256(self, x):
        """Same contract as limb.Mod._fold256, limb-leading."""
        k = x.shape[0]
        pad_tail = [(0, 0)] * (x.ndim - 1)
        lo = jnp.concatenate([x[:L - 1], (x[L - 1] & 0x1FF)[None]], axis=0)
        h0 = x[L - 1] >> 9
        h1 = None
        if k > L:
            h0 = h0 + ((x[L] & 0x1FF) << 4)
            h1 = x[L] >> 9
            if k > L + 1:
                h1 = h1 + ((x[L + 1] & 0x1FF) << 4)
        c256 = self._row("c256", x)
        acc = lo + h0[None] * c256
        if h1 is not None:
            shifted = h1[None] * c256[:L - 1]
            acc = acc + jnp.pad(shifted, [(1, 0)] + pad_tail)
        return self.carry3(acc)

    def mulmod(self, a, b):
        pad_tail = [(0, 0)] * (a.ndim - 1)
        x = self.carry3(self.mul_columns(a, b))         # (2L, *t)
        lo, hi = x[:L], x[L:]
        folded = None
        for k in range(L):
            t = hi[k][None] * self._row(f"fold_hi:{k}", x)
            folded = t if folded is None else folded + t
        acc = jnp.pad(lo + folded, [(0, 2)] + pad_tail)
        x = self.carry3(acc)
        x = self._fold256(x)
        return self._fold256(x)

    def addmod(self, a, b):
        pad_tail = [(0, 0)] * (a.ndim - 1)
        s = self.carry3(jnp.pad(a + b, [(0, 1)] + pad_tail))
        return self._fold256(s)

    def submod(self, a, b):
        pad_tail = [(0, 0)] * (a.ndim - 1)
        off = self._row("sub_off", a)
        s = self.carry3(jnp.pad(a + off - b, [(0, 1)] + pad_tail))
        return self._fold256(s)

    def _cond_sub_m(self, x):
        d = x - self._row("m_limbs", x)
        outs = []
        c = jnp.zeros_like(x[0])
        for i in range(L):
            t = d[i] + c
            outs.append(t & MASK)
            c = t >> W                      # arithmetic shift: borrow=-1
        sub = jnp.stack(outs, axis=0)
        ge = (c >= 0)[None]
        return jnp.where(ge, sub, x)

    def canonical(self, a):
        x = self.full_carry(a)
        for _ in range(2):
            x = self._cond_sub_m(x)
        return x


@functools.lru_cache(maxsize=None)
def _fpk() -> KMod:
    return KMod(p256.FP)


_A_K = limb.int_to_limbs(p256.A)
_B3_K = limb.int_to_limbs(p256.B3)


def cadd_k(p1, p2, F: KMod | None = None):
    """Complete projective addition, limb-leading (RCB15 Alg. 1).

    p1, p2: tuples of (L, *tile) int32 semi-reduced coordinates.
    Mirrors p256.cadd / p256.cadd_int exactly, minus the XLA
    optimization barriers (Mosaic schedules the kernel itself).
    """
    if F is None:
        F = _fpk()
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    a = F._row("curve_a", X1)
    b3 = F._row("curve_b3", X1)
    t0 = F.mulmod(X1, X2)
    t1 = F.mulmod(Y1, Y2)
    t2 = F.mulmod(Z1, Z2)
    t3 = F.mulmod(F.addmod(X1, Y1), F.addmod(X2, Y2))
    t3 = F.submod(F.submod(t3, t0), t1)
    t4 = F.mulmod(F.addmod(X1, Z1), F.addmod(X2, Z2))
    t4 = F.submod(F.submod(t4, t0), t2)
    t5 = F.mulmod(F.addmod(Y1, Z1), F.addmod(Y2, Z2))
    t5 = F.submod(F.submod(t5, t1), t2)
    Z3 = F.addmod(F.mulmod(a, t4), F.mulmod(b3, t2))
    X3 = F.submod(t1, Z3)
    Z3 = F.addmod(t1, Z3)
    Y3 = F.mulmod(X3, Z3)
    at2 = F.mulmod(a, t2)
    n_t1 = F.addmod(F.addmod(t0, t0), F.addmod(t0, at2))
    n_t2 = F.mulmod(F.submod(t0, at2), a)
    n_t4 = F.addmod(F.mulmod(b3, t4), n_t2)
    Y3 = F.addmod(Y3, F.mulmod(n_t1, n_t4))
    X3 = F.submod(F.mulmod(t3, X3), F.mulmod(t5, n_t4))
    Z3 = F.addmod(F.mulmod(t5, Z3), F.mulmod(t3, n_t1))
    return X3, Y3, Z3


# ---------------------------------------------------------------------------
# The tree body (plain jnp — runs inside the kernel, testable outside)
# ---------------------------------------------------------------------------

def _pack_operand(x, pts: int):
    """(L, S, R) -> (L, 8, S*R//8) when it tightens sublane use.

    Deep tree levels shrink the sublane axis below the vreg height of
    8; merging lanes back into sublanes keeps the VPU full. Only legal
    when the operand's point count is a power of two (so point
    boundaries stay row-aligned — rows are sliced into point halves at
    the NEXT level) and the element count fills whole vregs. Both
    cadd operands are reshaped identically, so elementwise pairing is
    preserved.
    """
    _, S, R = x.shape
    if S >= 8 or pts & (pts - 1) or (S * R) % (8 * 128):
        return x
    return x.reshape(x.shape[0], 8, S * R // 8)


def _inf_rows(x, rows: int):
    """(L, rows, R) point-at-infinity (0 : 1 : 0) coordinate triple."""
    zeros = jnp.zeros_like(x[:, :rows])
    y = zeros.at[0].set(jnp.ones_like(zeros[0]))
    return zeros, y, zeros


def tree_body(X, Y, Z, r, rpn, premask, F: KMod | None = None):
    """(L, M, B) gathered points -> verify mask, all in one trace.

    M is the per-signature point count (32 for 16/16-bit windows).
    Invariant through the loop: the sublane axis holds `pts`
    point-major point slots of equal row span, so slicing the top/bottom
    half of rows pairs every point exactly once (point addition is
    commutative — pairing order is free). The output tile shape equals
    r's tail shape; `_collapse_tile` computes it for callers.
    """
    if F is None:
        F = _fpk()
    pts = X.shape[1]
    while pts > 1:
        if pts % 2:
            rpp = X.shape[1] // pts          # rows per point slot
            ix, iy, iz = _inf_rows(X, rpp)
            X = jnp.concatenate([X, ix], axis=1)
            Y = jnp.concatenate([Y, iy], axis=1)
            Z = jnp.concatenate([Z, iz], axis=1)
            pts += 1
        h = X.shape[1] // 2
        hp = pts // 2
        A = tuple(_pack_operand(v[:, :h], hp) for v in (X, Y, Z))
        Bo = tuple(_pack_operand(v[:, h:], hp) for v in (X, Y, Z))
        X, Y, Z = cadd_k(A, Bo, F)
        pts = hp
    zc = F.canonical(Z)
    nonzero = jnp.any(zc != 0, axis=0)
    xc = F.canonical(X)
    ok1 = jnp.all(xc == F.canonical(F.mulmod(r, Z)), axis=0)
    ok2 = jnp.all(xc == F.canonical(F.mulmod(rpn, Z)), axis=0)
    return (premask != 0) & nonzero & (ok1 | ok2)


# ---------------------------------------------------------------------------
# pallas_call wrapper
# ---------------------------------------------------------------------------

def _kernel(consts, px, py, pz, r, rpn, pm, out):
    F = _fpk().bind(consts[:])
    ts, tr = out.shape[1], out.shape[2]
    r_t = r[0].reshape(L, ts, tr)
    rpn_t = rpn[0].reshape(L, ts, tr)
    pm_t = pm[0].reshape(ts, tr)
    res = tree_body(px[:], py[:], pz[:], r_t, rpn_t, pm_t, F)
    out[0] = res.astype(jnp.int32)


def tree_verify_points(pts, r_l, rpn_l, premask, *, interpret=None,
                       block_b: int = BLOCK_B):
    """Batched R = sum(points); accept iff x(R) ≡ r (mod n).

    pts: (B, M, 3, L) int32 gathered comb points (semi-reduced).
    r_l, rpn_l: (B, L) canonical limbs; premask: (B,) bool.
    Returns (B,) bool. The tree + projective check run as ONE Pallas
    program per `block_b` signatures, entirely in VMEM.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        from fabric_tpu.common import jaxenv

        interpret = jaxenv.pallas_interpret()

    B, M = pts.shape[0], pts.shape[1]
    bb = min(block_b, _round_up(B, 128))
    Bp = _round_up(B, bb)
    if Bp != B:
        pad = [(0, Bp - B)]
        pts = jnp.pad(pts, pad + [(0, 0)] * (pts.ndim - 1))
        r_l = jnp.pad(r_l, pad + [(0, 0)])
        rpn_l = jnp.pad(rpn_l, pad + [(0, 0)])
        premask = jnp.pad(premask, pad)

    # (B, M, 3, L) -> per-coordinate (L, M, B)
    pt = jnp.transpose(pts, (2, 3, 1, 0))
    px, py, pz = pt[0], pt[1], pt[2]

    # scalars get a leading grid axis: Mosaic requires block tails to
    # be (8, 128)-divisible OR equal to the array dims — (1, L, bb)
    # blocks of a (g, L, bb) array satisfy the "equal" clause; the
    # kernel reshapes to the collapsed tile internally
    ts, tr = _collapse_tile(M, bb)
    g = Bp // bb

    def scal(v):
        # (B, L) -> (g, L, bb): batch-major flat order per block
        return jnp.transpose(v, (1, 0)).reshape(L, g, bb) \
                  .transpose(1, 0, 2)

    r_t = scal(r_l)
    rpn_t = scal(rpn_l)
    pm_t = premask.astype(jnp.int32).reshape(g, 1, bb)

    consts = jnp.asarray(_fpk().pack_consts()).reshape(
        KMod.NCONST, L, 1, 1)
    grid = (g,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((KMod.NCONST, L, 1, 1), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((L, M, bb), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((L, M, bb), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((L, M, bb), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, L, bb), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, L, bb), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bb), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, ts, tr), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((g, ts, tr), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(consts, px, py, pz, r_t, rpn_t, pm_t)
    return out.reshape(Bp)[:B] != 0


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _collapse_tile(M: int, B: int):
    """The (S, R) tile shape tree_body collapses an (M, B) block to.

    Mirrors tree_body's row/pack bookkeeping exactly (shapes only).
    """
    S, R, pts = M, B, M
    while pts > 1:
        if pts % 2:
            S += S // pts
            pts += 1
        h, hp = S // 2, pts // 2
        if h < 8 and not (hp & (hp - 1)) and (h * R) % (8 * 128) == 0:
            h, R = 8, h * R // 8
        S, pts = h, hp
    return S, R
