"""Fixed-limb big-integer modular arithmetic on TPU (int32 tensors).

This is the arithmetic core of the TPU BCCSP provider — the rebuild of the
reference's hot verify path (`bccsp/sw/ecdsa.go:41-57` does one
`crypto/ecdsa.Verify` per signature on CPU; here thousands of verifications
run as one fixed-shape XLA program).

Design (TPU-first, no int64):
  * A 256-bit integer is a little-endian vector of ``L = 20`` limbs of
    ``W = 13`` bits each, dtype int32, shape ``(..., 20)``.
  * 13-bit limbs make schoolbook products safe in int32: a product column
    accumulates at most 20 terms of (2^13)^2, and 20 * 2^26 < 2^31.
  * Values are kept **semi-reduced** (< 2^256 + eps, limbs in [0, 2^13])
    rather than canonical; a cheap "fold at 2^256" (v = hi*2^256 + lo ≡
    hi*C + lo mod m, with C = 2^256 mod m precomputed) follows every op.
    Canonical form ([0, m), strict 13-bit limbs) is computed once at the
    end for equality checks.
  * Subtraction adds a precomputed multiple of m redistributed so every
    limb offset is ≥ 2*2^13, keeping all intermediate limbs non-negative —
    carries never have to propagate borrows, so three vectorized
    carry-rounds always settle.
  * Everything is branchless and fixed-shape: `vmap`-able over the batch
    axis and shardable with `shard_map` over a device mesh.

All bounds asserted below were derived for 256-bit moduli (P-256 field
prime and group order); `Mod.__init__` checks its preconditions.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

W = 13                      # bits per limb
L = 20                      # limbs per 256-bit value (13*20 = 260 bits)
MASK = (1 << W) - 1
PROD = 2 * L                # limbs in a schoolbook product


# ---------------------------------------------------------------------------
# Limb layout (parameterized limb count; 13-bit limbs stay)
# ---------------------------------------------------------------------------

class LimbLayout:
    """Limb geometry for one modulus width.

    The module constants above describe the historical 20-limb/256-bit
    layout every existing kernel (P-256, Ed25519, BN254) was built on;
    this object is the same geometry with the limb COUNT a parameter so
    wider primes (BLS12-381's 381-bit field needs 30 limbs) ride the
    identical carry/multiply machinery. 13-bit limbs are load-bearing
    and stay fixed: every int32 bound below is a function of W and L.

    int32 safety, re-derived per layout (ValueError, not assert — a
    silently overflowing column would corrupt field arithmetic):
      * a schoolbook product column accumulates at most L terms of
        (2^W)^2 (redundant limbs reach 2^W inclusive), so the column
        bound is L * 2^(2W);
      * Montgomery REDC adds up to L more terms of u_i * m_limb
        (< 2^(2W) each) into a column that already holds a carried
        (<= 2^W) limb, plus a propagated carry < 2^(31-W);
    both are covered by requiring
        L * 2^(2W) + 2^(31-W) + 2^W  <  2^31
    which admits L <= 31 at W = 13 (L = 32 overflows exactly).
    """

    def __init__(self, nlimbs: int, w: int = W):
        if nlimbs < 1:
            raise ValueError("LimbLayout needs at least one limb")
        worst = nlimbs * (1 << (2 * w)) + (1 << (31 - w)) + (1 << w)
        if worst >= 1 << 31:
            raise ValueError(
                f"limb layout L={nlimbs} W={w} overflows int32 column "
                f"accumulation ({worst} >= 2^31); the schoolbook/REDC "
                f"bound admits at most L={(((1 << 31) - (1 << (31 - w)) - (1 << w)) >> (2 * w))} limbs at W={w}")
        self.W = w
        self.MASK = (1 << w) - 1
        self.L = nlimbs
        self.PROD = 2 * nlimbs

    @property
    def bits(self) -> int:
        """Total representable bits (W * L)."""
        return self.W * self.L

    def max_modulus_bits(self) -> int:
        """Largest modulus width this layout's Montgomery R covers:
        REDC needs 4m < R = 2^(W*L), i.e. bit_length(m) <= W*L - 2."""
        return self.W * self.L - 2

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"LimbLayout(L={self.L}, W={self.W})"

    def __eq__(self, other) -> bool:
        return (isinstance(other, LimbLayout)
                and (self.L, self.W) == (other.L, other.W))

    def __hash__(self) -> int:
        return hash((self.L, self.W))


# the historical layout, as THE default instance: every <=256-bit
# kernel stages through this exact geometry, so existing paths are
# bit-identical by construction
DEFAULT_LAYOUT = LimbLayout(L)


def layout_for_bits(bits: int) -> LimbLayout:
    """Smallest layout whose Montgomery R covers a `bits`-wide odd
    modulus (4m < 2^(W*L) => W*L >= bits + 2). Yields exactly the
    historical 20-limb layout for every 251..258-bit modulus and 30
    limbs for BLS12-381's 381-bit field; widths past ~401 bits fail
    loudly in LimbLayout's int32 column bound."""
    if bits < 1:
        raise ValueError("modulus width must be positive")
    n = -(-(bits + 2) // W)          # ceil((bits + 2) / W)
    if n <= DEFAULT_LAYOUT.L:
        return DEFAULT_LAYOUT
    return LimbLayout(n)


# ---------------------------------------------------------------------------
# Host-side converters (numpy; used to stage inputs/constants)
# ---------------------------------------------------------------------------

def int_to_limbs(x: int, n: int = L) -> np.ndarray:
    """Python int -> little-endian canonical limb vector (numpy int32)."""
    if x < 0:
        raise ValueError("negative")
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[i] = x & MASK
        x >>= W
    if x:
        raise ValueError("value does not fit in limbs")
    return out


def limbs_to_int(a) -> int:
    """Limb vector (any redundant form) -> Python int."""
    a = np.asarray(a)
    return sum(int(v) << (W * i) for i, v in enumerate(a.tolist()))


def ints_to_limbs(xs, n: int = L) -> np.ndarray:
    """Batch of Python ints -> (B, n) int32 limb array."""
    return np.stack([int_to_limbs(x, n) for x in xs])


def be_bytes_to_limbs(raw: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 big-endian 256-bit values -> (B, L) canonical limbs.

    Fully vectorized (no per-element Python) — this is the host-side
    packing path for whole-block signature batches, where a Python loop
    over 30k values x 20 limbs would dominate the pipeline.
    """
    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    B = raw.shape[0]
    # bit k of the value at bits[:, k] (value little-endian bit order)
    bits = np.unpackbits(raw[:, ::-1], axis=1, bitorder="little")
    bits = np.pad(bits, ((0, 0), (0, L * W - 256)))
    weights = (1 << np.arange(W, dtype=np.int32))
    return (bits.reshape(B, L, W) * weights).sum(axis=2, dtype=np.int32)


def be_bytes_to_limbs_jnp(raw):
    """Device-side (B, 32) uint8 big-endian -> (B, L) limbs.

    Same output as `be_bytes_to_limbs`, expressed in jnp so the
    conversion runs ON DEVICE: the host then ships 32 B/scalar instead
    of 80 B of int32 limbs — 2.5x fewer H2D bytes per scalar (what
    that buys on the v5e's host link is not measured).
    """
    raw = raw.astype(jnp.int32)             # (B, 32), big-endian bytes
    B = raw.shape[0]
    # value bit k (little-endian) = byte (31 - k//8), bit (k % 8)
    k = jnp.arange(L * W)                   # 260 bits; top 4 are zero
    byte_idx = 31 - (k // 8)
    bit_idx = k % 8
    valid = k < 256
    bytes_k = jnp.where(valid, raw[:, jnp.clip(byte_idx, 0, 31)], 0)
    bits = (bytes_k >> bit_idx) & 1         # (B, L*W)
    weights = (1 << jnp.arange(W, dtype=jnp.int32))
    return (bits.reshape(B, L, W) * weights).sum(
        axis=2, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Carry propagation
# ---------------------------------------------------------------------------

def carry3(x: jnp.ndarray) -> jnp.ndarray:
    """Three vectorized carry rounds: limbs < 2^31 -> limbs in [0, 2^13].

    Requires all input limbs non-negative. Round 1 leaves limbs
    ≤ mask + 2^18, round 2 ≤ mask + 2^5, round 3 ≤ 2^13. The output is a
    valid *redundant* representation (limb value 2^13 = mask+1 allowed),
    safe as multiplication input.
    """
    for _ in range(3):
        lo = x & MASK
        c = x >> W
        x = lo + jnp.pad(c[..., :-1], [(0, 0)] * (c.ndim - 1) + [(1, 0)])
    return x


def full_carry(x: jnp.ndarray) -> jnp.ndarray:
    """Exact sequential carry: non-negative limbs -> strict 13-bit limbs.

    Unrolled over the (static) limb count; each step is a vectorized op
    over the batch, so under `vmap` this costs O(limbs) cheap ops.
    Any carry out of the top limb is dropped (callers guarantee the value
    fits, which holds for all semi-reduced values here).
    """
    n = x.shape[-1]
    outs = []
    c = jnp.zeros(x.shape[:-1], dtype=jnp.int32)
    for i in range(n):
        t = x[..., i] + c
        outs.append(t & MASK)
        c = t >> W
    return jnp.stack(outs, axis=-1)


# ---------------------------------------------------------------------------
# Schoolbook multiply
# ---------------------------------------------------------------------------

def mul_columns(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(…, L) x (…, L) -> (…, 2L) product columns (no carry).

    Inputs must have limbs ≤ 2^13 (redundant ok): max column is
    L * (2^13)^2 = 20 * 2^26 < 2^31.
    """
    na, nb = a.shape[-1], b.shape[-1]
    cols = jnp.zeros(a.shape[:-1] + (na + nb,), dtype=jnp.int32)
    for i in range(na):
        cols = cols.at[..., i : i + nb].add(a[..., i : i + 1] * b)
    return cols


# ---------------------------------------------------------------------------
# Modulus context
# ---------------------------------------------------------------------------

class Mod:
    """Precomputed tables for arithmetic mod a 256-bit modulus ``m``.

    Holds (as numpy constants, closed over by jitted code):
      * ``fold_hi``  — (L, L) rows: canonical limbs of 2^(13*(L+k)) mod m,
        for folding product limbs L..2L-1 in one pass;
      * ``c256``     — canonical limbs of 2^256 mod m (fold-at-256);
      * ``sub_off``  — limbs of 4m redistributed so every limb ≥ 2*2^13
        (non-negative subtraction, see module docstring);
      * ``m_limbs``  — canonical limbs of m.
    """

    def __init__(self, m: int):
        if not (1 << 255) < m < (1 << 256):
            raise ValueError("Mod supports 256-bit moduli")
        self.m = m
        self.m_limbs = int_to_limbs(m)
        self.c256 = int_to_limbs((1 << 256) % m)
        self.fold_hi = np.stack(
            [int_to_limbs(pow(2, W * (L + k), m)) for k in range(L)]
        )
        # 4m redistributed: add 2 units of limb i+1 into limb i (2*2^13 at
        # weight 13i == 2 at weight 13(i+1)), so limbs 0..L-2 gain 16384
        # and limbs 1..L-1 lose 2. Top limb of 4m is ~2^11, safely ≥ 2.
        off = int_to_limbs(4 * m).astype(np.int64)
        off[: L - 1] += 2 << W
        off[1:] -= 2
        # Non-negativity of (a + off - b) per limb: a semi-reduced b has
        # limbs ≤ 2^13 except the top limb ≤ 2^10 (since its value
        # < 2^256 + 2^243 < 2^257 and limb 19 has weight 2^247).
        # ValueError, not assert: wrong-shaped moduli must fail loudly
        # even under python -O — silent wrong residues would corrupt
        # signature verification.
        if not ((off[: L - 1] >= 1 << W).all() and off[L - 1] >= 1 << 10):
            raise ValueError("modulus shape unsupported (sub offsets)")
        if limbs_to_int(off) != 4 * m:
            raise ValueError("internal: sub_off redistribution broken")
        self.sub_off = off.astype(np.int32)
        # _fold256 places a limb-shifted copy of c256 and requires its top
        # two limbs to be zero (c256 < 2^234). True for the P-256 field
        # prime and group order (both have 2^256 mod m < 2^225).
        if (1 << 256) % m >= (1 << 225):
            raise ValueError("modulus shape unsupported (2^256 mod m too big)")

    # -- semi-reduction helpers (all jnp, fixed shape) --

    def _fold256(self, x: jnp.ndarray) -> jnp.ndarray:
        """Fold bits ≥ 256 back in: x ≡ hi*2^256 + lo, and 2^256 ≡ c256
        (mod m), so x ≡ hi*c256 + lo. Input: carried limbs (≤ 2^13) of
        width L, L+1 or L+2 (2^256 sits 9 bits into limb 19); total value
        < 2^(13*width). Output: width L, value < 2^256 + 2^243.
        """
        k = x.shape[-1]
        lo = x[..., :L]
        lo = lo.at[..., L - 1].set(x[..., L - 1] & 0x1FF)
        # hi = x >> 256, reassembled into 13-bit limbs h0, h1.
        h0 = x[..., L - 1] >> 9
        h1 = None
        if k > L:
            h0 = h0 + ((x[..., L] & 0x1FF) << 4)
            h1 = x[..., L] >> 9
            if k > L + 1:
                h1 = h1 + ((x[..., L + 1] & 0x1FF) << 4)
                # any higher bits of limb L+1 would be lost; callers keep
                # total value < 2^274 so h1 < 2^18 and this is exact
        c256 = jnp.asarray(self.c256, dtype=jnp.int32)
        acc = lo + h0[..., None] * c256       # limbs ≤ 2^13 + 2^26
        if h1 is not None:
            # h1 has weight 2^13 relative to h0: add c256 shifted one limb
            # (its top two limbs are zero — asserted in __init__).
            acc = acc.at[..., 1:].add(h1[..., None] * c256[: L - 1])
        return carry3(acc)

    def mulmod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Semi-reduced modular multiply: inputs/output limbs ≤ 2^13,
        output value < 2^256 + 2^243."""
        cols = mul_columns(a, b)              # width 2L
        x = carry3(cols)                      # limbs ≤ 2^13
        lo, hi = x[..., :L], x[..., L:]
        fold = jnp.asarray(self.fold_hi, dtype=jnp.int32)
        acc = jnp.pad(lo, [(0, 0)] * (lo.ndim - 1) + [(0, 2)])
        acc = acc.at[..., :L].add(
            sum(hi[..., k : k + 1] * fold[k] for k in range(L))
        )
        x = carry3(acc)                       # width L+2, value < 2^274
        x = self._fold256(x)                  # width L, value < 2^256+2^243
        x = self._fold256(x)                  # settle to < 2^256 + 2^226
        return x

    def addmod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Semi-reduced add: output < 2^256 + small."""
        s = a + b                             # limbs ≤ 2^14
        s = carry3(jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, 1)]))
        return self._fold256(s)

    def submod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Semi-reduced subtract: a - b + 4m, all limbs stay ≥ 0."""
        off = jnp.asarray(self.sub_off, dtype=jnp.int32)
        s = a + off - b                       # limbs in [0, 2^13+2^14+2^13]
        s = carry3(jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, 1)]))
        return self._fold256(s)

    def canonical(self, a: jnp.ndarray) -> jnp.ndarray:
        """Semi-reduced -> canonical [0, m), strict 13-bit limbs."""
        x = full_carry(a)
        # value < 2^256 + 2^243 < 2m (m > 2^255), so at most two
        # conditional subtractions reach [0, m).
        for _ in range(2):
            x = self._cond_sub_m(x)
        return x

    def _cond_sub_m(self, x: jnp.ndarray) -> jnp.ndarray:
        m_l = jnp.asarray(self.m_limbs, dtype=jnp.int32)
        d = x - m_l
        # sequential signed borrow propagation
        outs = []
        c = jnp.zeros(x.shape[:-1], dtype=jnp.int32)
        for i in range(L):
            t = d[..., i] + c
            outs.append(t & MASK)
            c = t >> W                        # arithmetic shift: borrow=-1
        sub = jnp.stack(outs, axis=-1)
        ge = (c >= 0)[..., None]              # no final borrow -> x >= m
        return jnp.where(ge, sub, x)

    def eq(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Exact equality of two semi-reduced values mod m -> (...,) bool."""
        return jnp.all(self.canonical(a) == self.canonical(b), axis=-1)

    def to_semi(self, x: int) -> np.ndarray:
        """Host: Python int (already < m) -> canonical limbs (valid
        semi-reduced input)."""
        return int_to_limbs(x % self.m)


# ---------------------------------------------------------------------------
# Bit repacking (SHA-256 words -> limbs)
# ---------------------------------------------------------------------------

def words_be_to_limbs(words: jnp.ndarray) -> jnp.ndarray:
    """(…, 8) big-endian uint32 words (a SHA-256 digest) -> (…, L) limbs.

    The digest is interpreted as a 256-bit big-endian integer, exactly as
    the reference's ECDSA verify treats the hash (hashValue -> big.Int).
    """
    w = words.astype(jnp.uint32)
    # value = sum_{j} word[7-j] * 2^(32j)  (big-endian)
    le = w[..., ::-1]
    limbs = []
    for i in range(L):
        bit0 = W * i
        j0, s0 = bit0 // 32, bit0 % 32
        limb = (le[..., j0] >> jnp.uint32(s0)).astype(jnp.uint32)
        if s0 + W > 32 and j0 + 1 < 8:
            limb = limb | (le[..., j0 + 1] << jnp.uint32(32 - s0))
        limbs.append((limb & jnp.uint32(MASK)).astype(jnp.int32))
    return jnp.stack(limbs, axis=-1)
