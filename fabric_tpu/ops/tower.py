"""Generic Fp2/Fp6/Fp12 pairing tower over Montgomery limb tensors.

One tower, two curves: the arithmetic that used to live inline in
`ops/bn254.py` (Karatsuba Fp2, xi-folded Fp6, quadratic Fp12, the
complete RCB15 a=0 projective point/line steps, Frobenius maps, the
Fermat inversion scans and the register-machine final-exponentiation
runner) is parameterized here by

  * ``F``       — a `mont.MontMod` context (ANY limb layout: BN254's
                  20-limb/254-bit field and BLS12-381's 30-limb/381-bit
                  field ride the identical code);
  * ``xi``      — the sextic-twist non-residue as an exact small-int
                  Fp2 pair (BN254: 9+u; BLS12-381: 1+u), expanded into
                  branch-free add chains;
  * ``b3_tw``   — 3*b' on the twist, exact Fp2 ints;
  * ``gammas``  — xi^(k*(p-1)/6) for k = 0..5, the p-power Frobenius
                  constants (host-exact ints);
  * ``mtwist``  — the sparse-line placement: a D-type twist's line
                  A + B*w + C*w^3 lands on Fp12 slots (w^0, w, w^3);
                  an M-type twist's scaled line lands on
                  (w^0, w^2, w^3). The Fp2 COEFFICIENT formulas are
                  identical either way (both scalings are killed by
                  the final exponentiation) — only the placement moves.

The tower layout is fixed: Fp2 = Fp[u]/(u^2+1) as (a0, a1);
Fp6 = Fp2[v]/(v^3 - xi) as (c0, c1, c2); Fp12 = Fp6[w]/(w^2 - v) as
(d0, d1). Everything is branchless, fixed-shape, vmap/shard_map-safe —
the ops are plain jnp over the MontMod limb primitives.

`ops/bn254.py` instantiates this with its historical constants and
rebinds its public names onto the instance, so every existing consumer
(and the kernel-parity suites) sees bit-identical arithmetic;
`ops/bls12_381_kernel.py` is the second instantiation.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Shape-only helpers (no field context)
# ---------------------------------------------------------------------------

def select_pt(mask, a, b):
    """Lane select between two Fp2 point triples; mask: (B,) bool."""
    m = mask[:, None]
    return tuple(
        (jnp.where(m, x[0], y[0]), jnp.where(m, x[1], y[1]))
        for x, y in zip(a, b))


def select_f12(mask, a, b):
    m = mask[:, None]

    def sel(x, y):
        return jnp.where(m, x, y)

    return tuple(
        tuple((sel(x[0], y[0]), sel(x[1], y[1]))
              for x, y in zip(c6a, c6b))
        for c6a, c6b in zip(a, b))


def flat_from_f12(f):
    """Nested-tuple f12 -> (12, ...) stacked coeff tensor."""
    coeffs = [c for half in f for fp2 in half for c in fp2]
    return jnp.stack(coeffs, axis=0)


def f12_from_flat(x):
    return tuple(
        tuple((x[h * 6 + j * 2], x[h * 6 + j * 2 + 1])
              for j in range(3))
        for h in range(2))


def pow_scan(x, e: int, mul, sqr, select):
    """Square-and-multiply by a STATIC positive exponent as a lax.scan
    (keeps the HLO one-body-sized for multi-thousand-bit chains)."""
    bits = [int(b) for b in bin(e)[3:]]          # skip the leading 1
    if not bits:
        return x
    bit_arr = jnp.asarray(np.array(bits, dtype=bool))

    def body(acc, bit):
        acc = sqr(acc)
        acc = select(bit, mul(acc, x), acc)
        return acc, None

    out, _ = lax.scan(body, x, bit_arr)
    return out


# -- the final-exp REGISTER MACHINE --
#
# A monolithic unrolled exponentiation chain (several pow-by-parameter
# scans + dozens of Fp12 muls, each >= 54 Montgomery muls) produces an
# HLO too large to compile: the CPU jit OOMs on it (the TPU compiler
# was not re-tried on the v5e — program size is the reason either
# way). Instead the whole post-inversion chain runs as
# ONE lax.scan whose body is a tiny f12-op interpreter (MUL/CONJ/FROB
# over a register file), driven by a static instruction program. HLO
# cost: one multiply body, regardless of chain length. The PROGRAM is
# per-curve (BN254's t-chain, BLS12-381's x-chain); the interpreter is
# this tower's.

OP_MUL, OP_CONJ, OP_FROB = 0, 1, 2
NREG = 8


class Asm:
    """Assembles a final-exp chain into (op, dst, a, b) rows."""

    def __init__(self):
        self.rows = []

    def emit(self, op, dst, a, b=0):
        self.rows.append((op, dst, a, b))

    def mul(self, dst, a, b):
        self.emit(OP_MUL, dst, a, b)

    def sqr(self, dst, a):
        self.emit(OP_MUL, dst, a, a)

    def conj(self, dst, a):
        self.emit(OP_CONJ, dst, a)

    def frob(self, dst, a):
        self.emit(OP_FROB, dst, a)

    def copy(self, dst, a):
        self.conj(dst, a)            # conj . conj = identity
        self.conj(dst, dst)

    def pow_static(self, dst, src, tmp, e: int):
        """dst = src^e for a STATIC positive e: square-and-multiply
        over e's bits (src, tmp, dst must be distinct registers)."""
        assert len({dst, src, tmp}) == 3 and e > 0
        self.copy(tmp, src)          # acc <- src (leading bit)
        for b in bin(e)[3:]:
            self.sqr(tmp, tmp)
            if b == "1":
                self.mul(tmp, tmp, src)
        self.copy(dst, tmp)

    def program(self) -> np.ndarray:
        return np.asarray(self.rows, dtype=np.int32)


class Tower:
    """One pairing curve's Fp12 tower over a MontMod limb context."""

    def __init__(self, F, xi, b3_tw, gammas, mtwist: bool = False):
        assert len(xi) == 2 and min(xi) >= 0
        self.F = F
        self.xi = tuple(int(c) for c in xi)
        self.b3_tw = tuple(int(c) % F.m for c in b3_tw)
        self.gammas = [tuple(int(c) % F.m for c in g) for g in gammas]
        assert len(self.gammas) == 6
        self.mtwist = bool(mtwist)

    # -- host constant staging --

    def const_fp2(self, c):
        """Exact Fp2 int pair -> broadcastable Montgomery limb
        constants."""
        F = self.F
        return (jnp.asarray(F.to_mont(c[0])), jnp.asarray(F.to_mont(c[1])))

    def _b3(self, shape):
        return tuple(jnp.broadcast_to(c, shape)
                     for c in self.const_fp2(self.b3_tw))

    def _one(self, shape):
        return jnp.broadcast_to(jnp.asarray(self.F.to_mont(1)), shape)

    # -- Fp small-scalar add chains --

    def fp_small(self, x, k: int):
        """x * k for a small positive static int, via a binary add
        chain (no Montgomery multiply)."""
        F = self.F
        acc = None
        base = x
        while k:
            if k & 1:
                acc = base if acc is None else F.add(acc, base)
            k >>= 1
            if k:
                base = F.add(base, base)
        if acc is None:
            return jnp.zeros_like(x)
        return acc

    # -- Fp2 --

    def f2_add(self, a, b):
        F = self.F
        return (F.add(a[0], b[0]), F.add(a[1], b[1]))

    def f2_sub(self, a, b):
        F = self.F
        return (F.sub(a[0], b[0]), F.sub(a[1], b[1]))

    def f2_mul(self, a, b):
        """Karatsuba: 3 base multiplications."""
        F = self.F
        m0 = F.mul(a[0], b[0])
        m1 = F.mul(a[1], b[1])
        m2 = F.mul(F.add(a[0], a[1]), F.add(b[0], b[1]))
        return (F.sub(m0, m1), F.sub(F.sub(m2, m0), m1))

    def f2_sqr(self, a):
        return self.f2_mul(a, a)

    def f2_scale(self, a, s):
        """Fp2 times an Fp element."""
        F = self.F
        return (F.mul(a[0], s), F.mul(a[1], s))

    def f2_neg(self, a):
        F = self.F
        return (F.neg(a[0]), F.neg(a[1]))

    def f2_conj(self, a):
        return (a[0], self.F.neg(a[1]))

    def f2_mul_xi(self, a):
        """Multiply by xi = x0 + x1*u:
        ((x0*a0 - x1*a1), (x1*a0 + x0*a1)), small-int add chains."""
        x0, x1 = self.xi
        F = self.F
        t0 = F.sub(self.fp_small(a[0], x0), self.fp_small(a[1], x1))
        t1 = F.add(self.fp_small(a[0], x1), self.fp_small(a[1], x0))
        return (t0, t1)

    def f2_small(self, a, k: int):
        """Multiply by a small positive int via a binary add chain."""
        acc = None
        base = a
        while k:
            if k & 1:
                acc = base if acc is None else self.f2_add(acc, base)
            k >>= 1
            if k:
                base = self.f2_add(base, base)
        return acc

    # -- Fp6 --

    def f6_add(self, a, b):
        return tuple(self.f2_add(x, y) for x, y in zip(a, b))

    def f6_sub(self, a, b):
        return tuple(self.f2_sub(x, y) for x, y in zip(a, b))

    def f6_mul(self, a, b):
        f2_mul, f2_add = self.f2_mul, self.f2_add
        c0, c1, c2 = a
        d0, d1, d2 = b
        t0, t1, t2 = f2_mul(c0, d0), f2_mul(c1, d1), f2_mul(c2, d2)
        r0 = f2_add(t0, self.f2_mul_xi(
            f2_add(f2_mul(c1, d2), f2_mul(c2, d1))))
        r1 = f2_add(f2_add(f2_mul(c0, d1), f2_mul(c1, d0)),
                    self.f2_mul_xi(t2))
        r2 = f2_add(f2_add(f2_mul(c0, d2), f2_mul(c2, d0)), t1)
        return (r0, r1, r2)

    def f6_mul_v(self, a):
        """Multiply an Fp6 element by v (v^3 = xi)."""
        return (self.f2_mul_xi(a[2]), a[0], a[1])

    # -- Fp12 --

    def f12_mul(self, a, b):
        a0, a1 = a
        b0, b1 = b
        t0 = self.f6_mul(a0, b0)
        t1 = self.f6_mul(a1, b1)
        r0 = self.f6_add(t0, self.f6_mul_v(t1))
        r1 = self.f6_sub(
            self.f6_mul(self.f6_add(a0, a1), self.f6_add(b0, b1)),
            self.f6_add(t0, t1))
        return (r0, r1)

    def f12_sqr(self, a):
        return self.f12_mul(a, a)

    def f12_conj(self, f):
        """x -> x^(p^6): negate the w half. Inverse inside the
        cyclotomic subgroup (post easy part)."""
        d0, d1 = f
        return (d0, tuple(self.f2_neg(c) for c in d1))

    def f12_one_like(self, x):
        """Fp12 one, broadcast to the batch shape of Fp element x."""
        one = self._one(x.shape)
        z = jnp.zeros_like(x)
        return (((one, z), (z, z), (z, z)), ((z, z), (z, z), (z, z)))

    def f12_frob(self, f):
        """x -> x^p: coefficient-wise Fp2 conjugation times the gamma
        constants (host-exact, differentially pinned vs the curve's
        int reference)."""
        d0, d1 = f

        def g(k, c):
            const = tuple(jnp.broadcast_to(v, c[0].shape)
                          for v in self.const_fp2(self.gammas[k]))
            return self.f2_mul(self.f2_conj(c), const)

        return ((self.f2_conj(d0[0]), g(2, d0[1]), g(4, d0[2])),
                (g(1, d1[0]), g(3, d1[1]), g(5, d1[2])))

    # -- inversion (Fermat scans) --

    def fp_inv(self, x):
        """Montgomery Fermat inverse: x^(p-2) via a static bit scan."""
        F = self.F

        def select(bit, a, b):
            return jnp.where(bit, a, b)

        return pow_scan(x, F.m - 2, F.mul, lambda a: F.mul(a, a),
                        select)

    def f2_inv(self, a):
        F = self.F
        d = self.fp_inv(F.add(F.mul(a[0], a[0]), F.mul(a[1], a[1])))
        return (F.mul(a[0], d), F.mul(F.neg(a[1]), d))

    def f6_inv(self, a):
        """Adjoint/norm method (mirrors the int references)."""
        f2_mul, f2_sub, f2_add = self.f2_mul, self.f2_sub, self.f2_add
        f2_sqr, f2_mul_xi = self.f2_sqr, self.f2_mul_xi
        c0, c1, c2 = a
        t0 = f2_sub(f2_sqr(c0), f2_mul_xi(f2_mul(c1, c2)))
        t1 = f2_sub(f2_mul_xi(f2_sqr(c2)), f2_mul(c0, c1))
        t2 = f2_sub(f2_sqr(c1), f2_mul(c0, c2))
        norm = f2_add(f2_mul(c0, t0),
                      f2_mul_xi(f2_add(f2_mul(c2, t1),
                                       f2_mul(c1, t2))))
        ninv = self.f2_inv(norm)
        return (f2_mul(t0, ninv), f2_mul(t1, ninv), f2_mul(t2, ninv))

    def f12_inv(self, a):
        a0, a1 = a
        t1 = self.f6_mul(a1, a1)
        norm = self.f6_sub(self.f6_mul(a0, a0), self.f6_mul_v(t1))
        ninv = self.f6_inv(norm)
        return (self.f6_mul(a0, ninv),
                tuple(self.f2_neg(c) for c in self.f6_mul(a1, ninv)))

    def f12_select(self, bit, a, b):
        mask = jnp.broadcast_to(bit, a[0][0][0].shape[:1])
        return select_f12(mask, a, b)

    # -- sparse line placement --

    def line_to_f12(self, A, B, C):
        """Sparse line as a full Fp12 element.

        D-type (BN254): the line is A + B*w + C*w^3 with A the
        yP-scaled, B the xP-scaled and C the constant coefficient —
        slots ((A, 0, 0), (B, C, 0)) since w^3 = v*w.

        M-type (BLS12-381): scaling the untwisted line by w^3 and the
        Fp2 denominators (both annihilated by the final exponentiation
        — w^3 lies in Fp4, and (p^12-1)/r contains the factor p^4-1)
        lands the SAME three coefficients on C + B*w^2 + A*w^3, i.e.
        slots ((C, B, 0), (0, A, 0)) with w^2 = v.
        """
        z = (jnp.zeros_like(A[0]), jnp.zeros_like(A[0]))
        if self.mtwist:
            return ((C, B, z), (z, A, z))
        return ((A, z, z), (B, C, z))

    # -- complete twist-curve steps (RCB15 a=0) --

    def g2_dbl_line(self, T, xP, yP):
        """Complete a=0 doubling (RCB15 Alg 9 with b3 on the twist)
        plus the tangent line at T evaluated at P = (xP, yP) in G1.

        T: ((X0,X1),(Y0,Y1),(Z0,Z1)) Fp2 limb tensors. Coefficients
        (scaled by Z^3 — killed by the final exponentiation):
          A = 2*Y*Z^2 * yP,  B = -3*X^2*Z * xP,  C = 3*X^3 - 2*Y^2*Z.
        """
        f2_mul, f2_sqr = self.f2_mul, self.f2_sqr
        f2_add, f2_sub = self.f2_add, self.f2_sub
        f2_small, f2_scale = self.f2_small, self.f2_scale
        X, Y, Z = T
        b3 = self._b3(X[0].shape)
        # line first (uses the pre-doubling T)
        Z2 = f2_sqr(Z)
        X2 = f2_sqr(X)
        YZ = f2_mul(Y, Z)
        A = f2_scale(f2_small(f2_mul(Y, Z2), 2), yP)
        B = f2_scale(self.f2_neg(f2_small(f2_mul(X2, Z), 3)), xP)
        C = f2_sub(f2_small(f2_mul(X2, X), 3),
                   f2_small(f2_mul(Y, YZ), 2))
        # RCB15 Alg 9 doubling
        t0 = f2_sqr(Y)
        Z3 = f2_small(t0, 8)
        t1 = YZ
        t2 = f2_sqr(Z)
        t2 = f2_mul(b3, t2)
        X3 = f2_mul(t2, Z3)
        Y3 = f2_add(t0, t2)
        Z3 = f2_mul(t1, Z3)
        t1 = f2_small(t2, 2)
        t2 = f2_add(t1, t2)
        t0 = f2_sub(t0, t2)
        Y3 = f2_mul(t0, Y3)
        Y3 = f2_add(X3, Y3)
        t1 = f2_mul(X, Y)
        X3 = f2_mul(t0, t1)
        X3 = f2_small(X3, 2)
        return (X3, Y3, Z3), self.line_to_f12(A, B, C)

    def g2_add_line(self, T, Q, xP, yP):
        """Complete a=0 mixed addition T + Q (RCB15 Alg 7 with Z2=1)
        plus the chord line through T, Q evaluated at P.

        Chord coefficients scaled by Z (and the twist scaling):
          A = (X - xQ*Z) * yP,  B = -(Y - yQ*Z) * xP,
          C = (Y - yQ*Z)*xQ - (X - xQ*Z)*yQ.
        """
        f2_mul, f2_add, f2_sub = self.f2_mul, self.f2_add, self.f2_sub
        f2_small, f2_scale = self.f2_small, self.f2_scale
        X1, Y1, Z1 = T
        xQ, yQ = Q
        b3 = self._b3(X1[0].shape)
        # line
        dX = f2_sub(X1, f2_mul(xQ, Z1))
        dY = f2_sub(Y1, f2_mul(yQ, Z1))
        A = f2_scale(dX, yP)
        B = f2_scale(self.f2_neg(dY), xP)
        C = f2_sub(f2_mul(dY, xQ), f2_mul(dX, yQ))
        # RCB15 Alg 7, complete addition for a=0 (general Z2; the
        # twist point Q is affine so Z2 = mont(1))
        one = self._one(X1[0].shape)
        zero = jnp.zeros_like(one)
        X2, Y2, Z2 = xQ, yQ, (one, zero)
        t0 = f2_mul(X1, X2)
        t1 = f2_mul(Y1, Y2)
        t2 = f2_mul(Z1, Z2)
        t3 = f2_mul(f2_add(X1, Y1), f2_add(X2, Y2))
        t3 = f2_sub(t3, f2_add(t0, t1))
        t4 = f2_mul(f2_add(Y1, Z1), f2_add(Y2, Z2))
        t4 = f2_sub(t4, f2_add(t1, t2))
        X3 = f2_mul(f2_add(X1, Z1), f2_add(X2, Z2))
        Y3 = f2_sub(X3, f2_add(t0, t2))      # Y3 = X1*Z2 + X2*Z1
        t0 = f2_small(t0, 3)                 # 3*X1*X2
        t2 = f2_mul(b3, t2)
        Z3 = f2_add(t1, t2)
        t1 = f2_sub(t1, t2)
        Y3 = f2_mul(b3, Y3)
        X3 = f2_mul(t4, Y3)
        X3 = f2_sub(f2_mul(t3, t1), X3)
        Y3 = f2_mul(Y3, t0)
        Y3 = f2_add(f2_mul(t1, Z3), Y3)
        Z3 = f2_mul(Z3, t4)
        Z3 = f2_add(Z3, f2_mul(t0, t3))
        return (X3, Y3, Z3), self.line_to_f12(A, B, C)

    def g2_dbl(self, T):
        """RCB15 Alg 9 complete doubling on the twist (no line)."""
        f2_mul, f2_sqr = self.f2_mul, self.f2_sqr
        f2_add, f2_sub, f2_small = self.f2_add, self.f2_sub, self.f2_small
        X, Y, Z = T
        b3 = self._b3(X[0].shape)
        t0 = f2_sqr(Y)
        Z3 = f2_small(t0, 8)
        t1 = f2_mul(Y, Z)
        t2 = f2_mul(b3, f2_sqr(Z))
        X3 = f2_mul(t2, Z3)
        Y3 = f2_add(t0, t2)
        Z3 = f2_mul(t1, Z3)
        t1 = f2_small(t2, 2)
        t2 = f2_add(t1, t2)
        t0 = f2_sub(t0, t2)
        Y3 = f2_mul(t0, Y3)
        Y3 = f2_add(X3, Y3)
        t1 = f2_mul(X, Y)
        X3 = f2_mul(t0, t1)
        X3 = f2_small(X3, 2)
        return X3, Y3, Z3

    def g2_add_mixed(self, T, Q):
        """RCB15 Alg 7 complete mixed addition T + (affine Q), no
        line."""
        f2_mul, f2_add, f2_sub = self.f2_mul, self.f2_add, self.f2_sub
        f2_small = self.f2_small
        X1, Y1, Z1 = T
        xQ, yQ = Q
        b3 = self._b3(X1[0].shape)
        one = self._one(X1[0].shape)
        zero = jnp.zeros_like(one)
        X2, Y2, Z2 = xQ, yQ, (one, zero)
        t0 = f2_mul(X1, X2)
        t1 = f2_mul(Y1, Y2)
        t2 = f2_mul(Z1, Z2)
        t3 = f2_mul(f2_add(X1, Y1), f2_add(X2, Y2))
        t3 = f2_sub(t3, f2_add(t0, t1))
        t4 = f2_mul(f2_add(Y1, Z1), f2_add(Y2, Z2))
        t4 = f2_sub(t4, f2_add(t1, t2))
        X3 = f2_mul(f2_add(X1, Z1), f2_add(X2, Z2))
        Y3 = f2_sub(X3, f2_add(t0, t2))
        t0 = f2_small(t0, 3)
        t2 = f2_mul(b3, t2)
        Z3 = f2_add(t1, t2)
        t1 = f2_sub(t1, t2)
        Y3 = f2_mul(b3, Y3)
        X3 = f2_mul(t4, Y3)
        X3 = f2_sub(f2_mul(t3, t1), X3)
        Y3 = f2_mul(Y3, t0)
        Y3 = f2_add(f2_mul(t1, Z3), Y3)
        Z3 = f2_mul(Z3, t4)
        Z3 = f2_add(Z3, f2_mul(t0, t3))
        return X3, Y3, Z3

    # -- verdict + final exponentiation --

    def gt_is_one(self, f):
        """(B,) bool: is the GT element the identity? Canonical-compare
        every coefficient (mont(1) for c000, zero elsewhere)."""
        F = self.F
        one = jnp.asarray(F.to_mont(1))
        coeffs = [c for d in f for fp2 in d for c in fp2]
        first = coeffs[0]
        ok = jnp.all(F.canonical(first) ==
                     F.canonical(jnp.broadcast_to(one, first.shape)),
                     axis=-1)
        for c in coeffs[1:]:
            ok = ok & jnp.all(F.canonical(c) == 0, axis=-1)
        return ok

    def run_final_exp(self, f, program):
        """The full final exponentiation on device: seeds the register
        file with (f, 1/f), then executes the curve's static final-exp
        program (registers 0/1 are inputs; the result lands in
        register 0) as the register-machine scan described above."""
        inv = self.f12_inv(f)
        regs0 = jnp.stack(
            [flat_from_f12(f), flat_from_f12(inv)] +
            [jnp.zeros_like(flat_from_f12(f))] * (NREG - 2),
            axis=0)                    # (NREG, 12, ...)
        program = jnp.asarray(program)

        def body(regs, instr):
            op, dst, a, b = instr[0], instr[1], instr[2], instr[3]
            A = f12_from_flat(jnp.take(regs, a, axis=0))
            Bv = f12_from_flat(jnp.take(regs, b, axis=0))
            res = lax.switch(op, [
                lambda: flat_from_f12(self.f12_mul(A, Bv)),
                lambda: flat_from_f12(self.f12_conj(A)),
                lambda: flat_from_f12(self.f12_frob(A)),
            ])
            regs = lax.dynamic_update_index_in_dim(regs, res, dst,
                                                   axis=0)
            return regs, None

        regs, _ = lax.scan(body, regs0, program)
        return f12_from_flat(regs[0])
