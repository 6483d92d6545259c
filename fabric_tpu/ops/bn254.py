"""Batched BN254 optimal-ate Miller loop on TPU (idemix stretch).

The reference's identity mixer verifies BBS+ credentials with pairings
over BN254 (vendored `IBM/idemix`, wired at `msp/idemix.go`). Its hot
verify path computes a pairing product PER credential on CPU; here the
Miller loop — the data-dependent bulk of the pairing — runs for a whole
batch of (P, Q) pairs as one fixed-shape XLA program over the
Montgomery limb engine (fabric_tpu/ops/mont.py).

TPU-first shape:
  * G2 state stays on the twist E'(Fp2): y^2 = x^3 + 3/(9+u), in
    HOMOGENEOUS projective coordinates with the complete a=0
    add/double formulas (Renes-Costello-Batina Algs 7/9) — branchless,
    fixed-shape, safe at every edge case.
  * Line functions are evaluated sparsely: l = A + B*w + C*w^3 with
    A,B,C in Fp2 (coefficients scaled by Fp2 denominators, which the
    final exponentiation kills).
  * The loop is one lax.scan over the STATIC bit array of 6t+2; the
    addition step is always computed and folded in with a lane-wide
    select (bits are compile-time constants but a scan keeps the HLO
    one-body-sized).
  * The optimal-ate Frobenius correction points pi_p(Q), -pi_{p^2}(Q)
    live on the twist, so the host precomputes them with exact int
    arithmetic (fabric_tpu/ops/bn254_ref.g2_frobenius) and the device
    runs two more add+line steps.

The Fp2/Fp6/Fp12 tower arithmetic, the complete twist steps and the
register-machine final-exponentiation runner are the generic
`fabric_tpu.ops.tower.Tower` parameterized with BN254's constants
(D-type twist over xi = 9+u on the default 20-limb layout); this
module keeps the BN-specific pieces — the 6t+2 Miller loop with its
optimal-ate Frobenius correction adds, the parameter-t final-exp
PROGRAM, the G2 MSM scan and the host staging helpers. The final
exponentiation runs fully on device, amortized: pairing products
multiply their Miller values and pay `final_exp_batch` once.

Differential oracle: fabric_tpu/ops/bn254_ref.miller_loop at matching
loop counts (tests run truncated loops on CPU; the full 6t+2 loop is
exercised on real hardware via bench paths).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from fabric_tpu.ops import bn254_ref as ref
from fabric_tpu.ops import limb
from fabric_tpu.ops import tower
from fabric_tpu.ops.limb import L
from fabric_tpu.ops.mont import MontMod

# compact-HLO Montgomery: the Miller scan body holds hundreds of muls
F = MontMod(ref.P, unroll=False)

# b3 = 3 * b' = 9/(9+u) on the twist, as exact Fp2 ints
_XI_INV = ref.f2_inv(ref.XI)
_B_TW = ref.f2_mul((3, 0), _XI_INV)
_B3_TW = ref.f2_mul((3, 0), ref.f2_mul((3, 0), _XI_INV))


def _f2_pow_int(a, e: int):
    """Host: exact Fp2 pow (for Frobenius constants)."""
    out = (1, 0)
    base = a
    while e:
        if e & 1:
            out = ref.f2_mul(out, base)
        base = ref.f2_mul(base, base)
        e >>= 1
    return out


# gamma = xi^((p-1)/6); (v^j w^i)^p = conj-coeffs * gamma^(2j+i)
_GAMMA = [_f2_pow_int(ref.XI, k * (ref.P - 1) // 6) for k in range(6)]


# ---------------------------------------------------------------------------
# Tower instance — BN254's D-type twist over xi = 9 + u. Every bound
# method below is bit-identical to the arithmetic that used to live
# inline here (proven by the kernel-parity suites).
# ---------------------------------------------------------------------------

_T = tower.Tower(F, xi=ref.XI, b3_tw=_B3_TW, gammas=_GAMMA,
                 mtwist=False)

_const_fp2 = _T.const_fp2
f2_add = _T.f2_add
f2_sub = _T.f2_sub
f2_mul = _T.f2_mul
f2_sqr = _T.f2_sqr
f2_scale = _T.f2_scale
f2_neg = _T.f2_neg
f2_conj = _T.f2_conj
f2_mul_xi = _T.f2_mul_xi
f2_small = _T.f2_small
f6_add = _T.f6_add
f6_sub = _T.f6_sub
f6_mul = _T.f6_mul
f6_mul_v = _T.f6_mul_v
f12_mul = _T.f12_mul
f12_sqr = _T.f12_sqr
f12_conj = _T.f12_conj
f12_frob = _T.f12_frob
f12_one_like = _T.f12_one_like
line_to_f12 = _T.line_to_f12
g2_dbl_line = _T.g2_dbl_line
g2_add_line = _T.g2_add_line
g2_dbl = _T.g2_dbl
g2_add_mixed = _T.g2_add_mixed
fp_inv = _T.fp_inv
f2_inv = _T.f2_inv
f6_inv = _T.f6_inv
f12_inv = _T.f12_inv
gt_is_one = _T.gt_is_one
_f12_select = _T.f12_select
_select_pt = tower.select_pt
_select_f12 = tower.select_f12
_flat_from_f12 = tower.flat_from_f12
_f12_from_flat = tower.f12_from_flat
_pow_scan = tower.pow_scan
_OP_MUL, _OP_CONJ, _OP_FROB = tower.OP_MUL, tower.OP_CONJ, tower.OP_FROB
_NREG = tower.NREG


def _f2_zero_like(x):
    z = jnp.zeros_like(x[0])
    return (z, z)


# ---------------------------------------------------------------------------
# Batched Miller loop
# ---------------------------------------------------------------------------

def miller_loop_batch(xP, yP, Q, Q1, nQ2, loop: int = ref.ATE_LOOP):
    """f_{loop,Q}(P) for a batch, with optimal-ate corrections.

    xP, yP: (B, L) Montgomery limbs of the G1 points.
    Q, Q1, nQ2: affine twist points as ((x0,x1),(y0,y1)) of (B, L)
    Montgomery limbs — Q1 = pi_p(Q) and nQ2 = -pi_{p^2}(Q) are
    host-precomputed (exact ints, ref.g2_frobenius).
    Returns the Fp12 Miller value as nested tuples of (B, L) tensors.
    """
    bits = [int(b) for b in bin(loop)[3:]]
    bit_arr = jnp.asarray(np.array(bits, dtype=bool))
    one = jnp.broadcast_to(jnp.asarray(F.to_mont(1)), xP.shape)
    zero = jnp.zeros_like(one)
    T0 = (Q[0], Q[1], ((one, zero)))
    f0 = f12_one_like(xP)

    def body(carry, bit):
        T, f = carry
        f = f12_sqr(f)
        T, l = g2_dbl_line(T, xP, yP)
        f = f12_mul(f, l)
        Ta, la = g2_add_line(T, Q, xP, yP)
        fa = f12_mul(f, la)
        mask = jnp.broadcast_to(bit, xP.shape[:1])
        T = _select_pt(mask, Ta, T)
        f = _select_f12(mask, fa, f)
        return (T, f), None

    (T, f), _ = lax.scan(body, (T0, f0), bit_arr)
    # optimal-ate corrections
    T, l1 = g2_add_line(T, Q1, xP, yP)
    f = f12_mul(f, l1)
    _, l2 = g2_add_line(T, nQ2, xP, yP)
    f = f12_mul(f, l2)
    return f


# ---------------------------------------------------------------------------
# Final exponentiation (device)
# ---------------------------------------------------------------------------

def f12_pow_t(m):
    """m^t for the BN parameter t (63-bit static scan)."""
    return _pow_scan(m, ref.T_BN, f12_mul, f12_sqr, _f12_select)


class _Asm(tower.Asm):
    """BN-flavored assembler: pow_t is pow by the static parameter t."""

    def pow_t(self, dst, src, tmp):
        self.pow_static(dst, src, tmp, ref.T_BN)


def _final_exp_program() -> np.ndarray:
    """Registers: 0=f (input), 1=inv_f (input), 2=m, 3=mx, 4=mx2,
    5=mx3, 6=t0/scratch, 7=t1/scratch. Mirrors
    ref.final_exponentiation_chain exactly (oracle-pinned)."""
    A = _Asm()
    # easy part: m = frob^2(f^(p^6-1)) * f^(p^6-1)
    A.conj(2, 0)                 # m <- conj(f)
    A.mul(2, 2, 1)               # m <- conj(f)*inv(f) = f^(p^6-1)
    A.frob(6, 2)
    A.frob(6, 6)                 # t0 <- m^(p^2)
    A.mul(2, 6, 2)               # m <- m^(p^2+1)
    # hard part powers of t
    A.pow_t(3, 2, 6)             # mx  = m^t
    A.pow_t(4, 3, 6)             # mx2 = mx^t
    A.pow_t(5, 4, 6)             # mx3 = mx2^t
    # y0 = mp*mp2*mp3 -> reg 6
    A.frob(6, 2)                 # mp
    A.frob(7, 6)                 # mp2
    A.mul(6, 6, 7)               # mp*mp2
    A.frob(7, 7)                 # mp3
    A.mul(6, 6, 7)               # y0
    # y4 = conj(mx * frob(mx2)) -> reg 7 ... build T0 incrementally:
    # T0 = y6^2 * y4 * y5;  y6 = conj(mx3 * frob(mx3))
    # use reg 0 (f no longer needed) and reg 1 (inv_f done) as scratch
    A.frob(0, 5)                 # frob(mx3)
    A.mul(0, 5, 0)               # mx3*mx3p
    A.conj(0, 0)                 # y6
    A.sqr(0, 0)                  # y6^2
    A.frob(1, 4)                 # mx2p
    A.mul(1, 3, 1)               # mx*mx2p
    A.conj(1, 1)                 # y4
    A.mul(0, 0, 1)               # y6^2*y4
    A.conj(1, 4)                 # y5
    A.mul(0, 0, 1)               # T0 = y6^2*y4*y5
    # T1 = y3*y5*T0; y3 = conj(frob(mx))
    A.frob(7, 3)
    A.conj(7, 7)                 # y3
    A.mul(7, 7, 1)               # y3*y5
    A.mul(7, 7, 0)               # T1
    # T0 = T0 * y2; y2 = frob^2(mx2)
    A.frob(1, 4)
    A.frob(1, 1)                 # y2
    A.mul(0, 0, 1)               # T0*y2
    # T1 = T1^2 * T0; T1 = T1^2
    A.sqr(7, 7)
    A.mul(7, 7, 0)
    A.sqr(7, 7)
    # T0 = T1 * y1; y1 = conj(m)
    A.conj(1, 2)                 # y1
    A.mul(0, 7, 1)               # T0 = T1*y1
    # T1 = T1 * y0 (y0 in reg 6)
    A.mul(7, 7, 6)
    # result = T0^2 * T1 -> reg 0
    A.sqr(0, 0)
    A.mul(0, 0, 7)
    return np.asarray(A.rows, dtype=np.int32)


_FINAL_EXP_PROGRAM = _final_exp_program()


def final_exp_batch(f):
    """The full final exponentiation on device: easy part
    (p^6-1)(p^2+1) then the BN hard part via the parameter-t addition
    chain (mirrors ref.final_exponentiation_chain, which is pinned
    against the single-pow oracle). Runs as the tower's
    register-machine scan — see fabric_tpu.ops.tower."""
    return _T.run_final_exp(f, _FINAL_EXP_PROGRAM)


def pairing_product_is_one(xPs, yPs, Qs, Q1s, nQ2s,
                           loop: int = ref.ATE_LOOP):
    """prod_i e(P_i, Q_i) == 1 for a batch of pairing PRODUCTS.

    Each argument is a list over the product terms; list element i
    carries the (B, L) staged tensors of that term. One shared final
    exponentiation over the multiplied Miller values — the standard
    product-of-pairings trick (and why the BBS+ verify equation
    e(A, X) = e(B, Y) is checked as e(A, X)·e(B, -Y) == 1).
    """
    import jax

    # ONE shared Miller scan with the product terms STACKED into the
    # batch axis: T terms of B lanes run as one (T*B)-lane loop, so
    # the (large) scan body appears once in the HLO instead of T
    # times — program size is what the compilers choke on (T copies
    # of this body; not re-measured on the v5e).
    nterms = len(xPs)
    B = xPs[0].shape[0]
    cat = lambda ts: jax.tree_util.tree_map(  # noqa: E731
        lambda *xs: jnp.concatenate(xs, axis=0), *ts)
    f_all = miller_loop_batch(cat(xPs), cat(yPs), cat(Qs), cat(Q1s),
                              cat(nQ2s), loop=loop)
    acc = None
    for t in range(nterms):
        fi = jax.tree_util.tree_map(
            lambda x: x[t * B:(t + 1) * B], f_all)
        acc = fi if acc is None else f12_mul(acc, fi)
    return gt_is_one(final_exp_batch(acc))


def stage_pairing_products(products):
    """[[(P_int, Q_tw_int), ...] per lane] (uniform term count) ->
    the staged tensor lists pairing_product_is_one consumes."""
    nterms = len(products[0])
    assert all(len(p) == nterms for p in products)
    xPs, yPs, Qs, Q1s, nQ2s = [], [], [], [], []
    for t in range(nterms):
        g1s = [p[t][0] for p in products]
        g2s = [p[t][1] for p in products]
        xP, yP = stage_g1(g1s)
        Q, Q1, nQ2 = stage_g2(g2s)
        xPs.append(jnp.asarray(xP))
        yPs.append(jnp.asarray(yP))
        Qs.append(jax_tree(Q))
        Q1s.append(jax_tree(Q1))
        nQ2s.append(jax_tree(nQ2))
    return xPs, yPs, Qs, Q1s, nQ2s


def jax_tree(t):
    import jax
    return jax.tree_util.tree_map(jnp.asarray, t)


# ---------------------------------------------------------------------------
# Batched G2 multi-scalar multiplication (idemix PS Schnorr on device)
# ---------------------------------------------------------------------------
#
# The PS presentation verifier recomputes K~ = s_sk*Y~ + s_r*G~ - c*T~
# per credential (msp/idemix_ps.verify_schnorr) — three G2 scalar muls
# of host bigint work per lane. Here the whole batch runs as ONE
# lax.scan of complete RCB15 double/add steps over the scalar bit
# columns: per bit, one doubling + T masked mixed additions, all lanes
# in parallel on the Montgomery limb engine. The subgroup membership
# test ([6x^2]T~ == psi(T~), ops/bn254_ref.g2_in_subgroup) batches
# through the same kernel as 1-term lanes. The reference verifies each
# credential's proof serially on CPU (vendored IBM/idemix).

NBITS_R = 254                       # ref.R.bit_length()


def g2_msm_scan(bit_cols, *Q_flat):
    """sum_t k_t * Q_t per lane. bit_cols: (NBITS, B, T) bool, msb
    first; Q_flat: 4*T tensors (x0, x1, y0, y1 per term), (B, L)
    Montgomery limbs. Returns the projective result (X, Y, Z) Fp2."""
    nterms = len(Q_flat) // 4
    Qs = [((Q_flat[4 * t], Q_flat[4 * t + 1]),
           (Q_flat[4 * t + 2], Q_flat[4 * t + 3]))
          for t in range(nterms)]
    shape = Q_flat[0].shape
    one = jnp.broadcast_to(jnp.asarray(F.to_mont(1)), shape)
    zero = jnp.zeros_like(one)
    acc0 = ((zero, zero), (one, zero), (zero, zero))   # infinity

    def body(acc, bits):
        acc = g2_dbl(acc)
        for t, Q in enumerate(Qs):
            added = g2_add_mixed(acc, Q)
            acc = _select_pt(bits[:, t], added, acc)
        return acc, None

    acc, _ = lax.scan(body, acc0, bit_cols)
    return acc


def stage_g2_msm(lanes, nbits: int = NBITS_R):
    """[[(k, Q_affine_int | None), ...] x T per lane] -> (bit_cols,
    q_flat list). None/zero terms get an all-zero bit column (the
    point is never added; any valid placeholder works)."""
    nterms = len(lanes[0])
    assert all(len(lane) == nterms for lane in lanes)
    B = len(lanes)
    bit_cols = np.zeros((nbits, B, nterms), dtype=bool)
    g2 = (ref.G2_X, ref.G2_Y)
    q_flat = []
    for t in range(nterms):
        xs0, xs1, ys0, ys1 = [], [], [], []
        for i, lane in enumerate(lanes):
            k, q = lane[t]
            k %= ref.R
            if q is None:
                k = 0
            if k:
                kb = bin(k)[2:].zfill(nbits)
                bit_cols[:, i, t] = np.frombuffer(
                    kb.encode(), dtype=np.uint8) == 0x31
            p = q if (q is not None and k) else g2
            xs0.append(F.to_mont(p[0][0]))
            xs1.append(F.to_mont(p[0][1]))
            ys0.append(F.to_mont(p[1][0]))
            ys1.append(F.to_mont(p[1][1]))
        q_flat.extend([np.stack(xs0), np.stack(xs1),
                       np.stack(ys0), np.stack(ys1)])
    return bit_cols, q_flat


def read_g2_msm(out) -> list:
    """Projective mont limb result -> affine int points (None for
    infinity), via host Fp2 inversion per lane."""
    (X0, X1), (Y0, Y1), (Z0, Z1) = out
    X0, X1, Y0, Y1, Z0, Z1 = (np.asarray(a)
                              for a in (X0, X1, Y0, Y1, Z0, Z1))
    res = []
    for i in range(X0.shape[0]):
        z = (F.from_limbs(Z0[i]), F.from_limbs(Z1[i]))
        if z == (0, 0):
            res.append(None)
            continue
        zi = ref.f2_inv(z)
        x = ref.f2_mul((F.from_limbs(X0[i]), F.from_limbs(X1[i])), zi)
        y = ref.f2_mul((F.from_limbs(Y0[i]), F.from_limbs(Y1[i])), zi)
        res.append((x, y))
    return res


def bls_products(pk_tw, msgs, sig_points):
    """Per-lane BLS verify as a 2-term pairing product:
    e(sig, G2) * e(H(m), -pk) == 1."""
    g2 = (ref.G2_X, ref.G2_Y)
    npk = ref.g2_neg_tw(pk_tw)
    return [[(sig, g2), (ref.hash_to_g1(m), npk)]
            for m, sig in zip(msgs, sig_points)]


# ---------------------------------------------------------------------------
# Host staging + verification helpers
# ---------------------------------------------------------------------------

def stage_g1(points) -> tuple[np.ndarray, np.ndarray]:
    """[(x, y) ints] -> (B, L) Montgomery limb arrays."""
    xs = np.stack([F.to_mont(p[0]) for p in points])
    ys = np.stack([F.to_mont(p[1]) for p in points])
    return xs, ys


def stage_g2(points):
    """[((x0,x1),(y0,y1)) ints] -> twist-point limb tuples + the
    host-precomputed Frobenius correction points."""
    def pack(pts):
        return ((np.stack([F.to_mont(p[0][0]) for p in pts]),
                 np.stack([F.to_mont(p[0][1]) for p in pts])),
                (np.stack([F.to_mont(p[1][0]) for p in pts]),
                 np.stack([F.to_mont(p[1][1]) for p in pts])))

    q1s = [ref.g2_frobenius(q) for q in points]
    nq2s = [ref.g2_neg_tw(ref.g2_frobenius(q1)) for q1 in q1s]
    return pack(points), pack(q1s), pack(nq2s)


def f12_from_device(f) -> list:
    """Device Fp12 (nested tuples of (B, L) mont limbs) -> list of
    int-reference Fp12 elements, for differential comparison."""
    d0, d1 = f
    B = d0[0][0].shape[0]
    out = []
    for i in range(B):
        def cvt_f2(c):
            return (F.from_limbs(np.asarray(c[0][i])),
                    F.from_limbs(np.asarray(c[1][i])))
        out.append((tuple(cvt_f2(c) for c in d0),
                    tuple(cvt_f2(c) for c in d1)))
    return out
