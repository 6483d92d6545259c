"""Fused Pallas verify kernel family: on-device SHA-256 feeding the comb.

Round-20. The staged verify path (bccsp/tpu.py `_dispatch_comb_digest`)
still pays a HOST hash per message lane — BENCH_r03 measured 276k
`host_hashed_lanes` per run, and every one of them is host SHA-256 plus
a 32-byte digest transfer before the device sees work. The
FPGA-ECDSA-engine paper (arXiv:2112.02229, PAPERS.md) shows the winning
shape: a fully pipelined engine where the hash, scalar-mul and compare
stages overlap on the accelerator. This module is that shape for the
TPU:

  stage A (this file's Pallas program): raw SHA-padded message blocks
    stream HBM->VMEM double-buffered (`pltpu.make_async_copy`, two
    slots, one DMA in flight ahead of compute), the scan-structured
    SHA-256 compression from ops/sha256.py runs per lane, the digest
    feeds the mod-n scalar derivation (u1 = e*w, u2 = r*w via the
    limb-leading KMod arithmetic of ops/ptree.py) and the comb WINDOW
    extraction — so what leaves the kernel is not a digest round-trip
    but the (B, nwin) table indices the comb needs;
  stage B: the existing gather + ops/ptree.py VMEM complete-add tree
    (or the XLA tree for q8 dispatches), unchanged and bit-identical;
  resident variant: for key sets whose 8-bit comb tables fit the VMEM
    budget, ONE program runs SHA + scalars + windows + an in-kernel
    table gather + the complete-add tree with the tables pinned in
    VMEM across grid steps (constant index_map) — nothing but the
    verdict bitmap comes back.

Layout matches ops/ptree.py: batch = trailing (sublane, lane) tile,
limb/word index = leading compile-time axis, so every op is an
elementwise VPU op over (rows, BLOCK_B) tiles. The SHA compression
keeps ops/sha256.py's lax.scan structure on purpose: unrolling the 64
rounds makes XLA's fusion search blow up exponentially (measured: 24
unrolled rounds trace in ~0.4 s, 32 rounds take minutes), while the
scan traces one round body.

Differentially tested against sha256.sha256_host / the sw oracle and
pinned bit-identical to the comb_digest path in
tests/test_fused_verify.py.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from fabric_tpu.ops import comb, limb, p256, ptree, sha256
from fabric_tpu.ops.limb import L, MASK, W

BLOCK_B = 512               # batch lanes per kernel program
LANE_ALIGN = ptree.LANE_ALIGN

# VMEM byte budget for the resident variant's pinned tables: the g8 +
# q8 comb tables cost ~1.97 MB per key slot, so 64 MB holds ~31 keys
# with working-set headroom inside ptree.VMEM_LIMIT.
RESIDENT_TABLE_BUDGET = 64 * 1024 * 1024


@functools.lru_cache(maxsize=None)
def _fnk() -> ptree.KMod:
    """Limb-leading mod-n arithmetic (scalar field) for in-kernel
    u1/u2 derivation — the KMod twin of comb's `FN` usage."""
    return ptree.KMod(p256.FN)


def _sha_consts() -> np.ndarray:
    """(64,) uint32 SHA-256 round constants. Pallas kernels may not
    close over array constants, so these ride an SMEM input (scalar
    loads; the round index is the one dynamic index in the kernel)."""
    return np.asarray(sha256._K).reshape(64)


def _sha_h0(S: int) -> np.ndarray:
    """(8, S, 128) uint32: the initial state words, one lane tile
    each, as a pinned VMEM input. NOT splatted in the kernel: a
    splat's replicated layout as the round loop's initial carry is a
    relayout Mosaic refuses ("replicated in destination but not in
    source")."""
    return np.broadcast_to(
        np.asarray(sha256._H0, dtype=np.uint32).reshape(8, 1, 1),
        (8, S, LANE_ALIGN)).copy()


# ---------------------------------------------------------------------------
# Kernel body pieces (plain jnp over leading-axis tiles — testable
# outside a kernel, traced inside one)
# ---------------------------------------------------------------------------

def _rotr(x, n: int):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _compress_rows(state, block, k_at):
    """One SHA-256 compression over a lane tile, word-leading layout.

    state: 8 uint32 tiles; block: 16 uint32 message-word tiles;
    k_at(t): round constant t (traced index). Same arithmetic as
    sha256._compress, restructured for Mosaic, which lowers only
    `fori_loop`-shaped scans (a carry, no scanned inputs/outputs): ONE
    loop of 64 rounds whose carry is the 8 working registers plus a
    rolling 16-word schedule window — round t consumes win[0] and
    appends W[t+16]. Every index is static except k_at's. The rounds
    must NOT unroll: XLA's fusion search (interpret mode on CPU) blows
    up past ~24 unrolled rounds.
    """

    def round_step(t, carry):
        (a, b, c, d, e, f, g, h), win = carry
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k_at(t) + win[0]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        wm15, wm2 = win[1], win[14]
        g0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> jnp.uint32(3))
        g1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> jnp.uint32(10))
        nxt = win[0] + g0 + win[9] + g1
        return ((t1 + s0 + maj, a, b, c, d + t1, e, f, g),
                win[1:] + (nxt,))

    regs, _ = lax.fori_loop(0, 64, round_step,
                            (tuple(state), tuple(block)))
    return tuple(s + r for s, r in zip(state, regs))


def _words_to_limbs_rows(words):
    """8 big-endian uint32 digest word tiles -> (L, *t) int32 limbs.

    The leading-axis twin of limb.words_be_to_limbs — same static
    bit-position bookkeeping, word index on axis 0.
    """
    le = [words[7 - j] for j in range(8)]
    rows = []
    for i in range(L):
        bit0 = W * i
        j0, s0 = bit0 // 32, bit0 % 32
        v = le[j0] >> jnp.uint32(s0)
        if s0 + W > 32 and j0 + 1 < 8:
            v = v | (le[j0 + 1] << jnp.uint32(32 - s0))
        rows.append((v & jnp.uint32(MASK)).astype(jnp.int32))
    return jnp.stack(rows, axis=0)


def _windows_rows(u, wbits: int):
    """(L, *t) canonical scalar rows -> (256//wbits, *t) int32 windows.

    The leading-axis twin of comb._windows: window bit positions are
    static, limb indices/shifts resolve at trace time."""
    rows = []
    for i in range(256 // wbits):
        bit0 = i * wbits
        j0, off = bit0 // W, bit0 % W
        v = u[j0] >> off
        got = W - off
        j = j0 + 1
        while got < wbits and j < L:
            v = v | (u[j] << got)
            got += W
            j += 1
        rows.append(v & ((1 << wbits) - 1))
    return jnp.stack(rows, axis=0)


def _sha_scalar_rows(F, k_at, h0, blk, nb_live, digests, has_digest,
                     r, w, nb: int):
    """SHA + mod-n scalar derivation for one lane tile.

    Tiles are (S, 128) = BLOCK_B lanes, leading axes are word / limb
    indices (untiled, compile-time — the ops/ptree.py layout). k_at:
    SHA round constant t; h0: (8, S, 128) initial state tiles; blk: (nb*16, S, 128) uint32 padded message blocks;
    nb_live: (S, 128) int32 per-lane block count (0 for digest-only
    lanes); digests: (8, S, 128) uint32 precomputed digest words;
    has_digest: (S, 128) int32; r, w: (L, S, 128) int32 canonical
    limbs. Returns (words (8, S, 128), u1, u2).

    The block loop is a STATIC Python loop with a masked state update
    (exactly sha256.sha256_blocks' fori_loop semantics) — Mosaic has
    no dynamic leading-axis slicing of values, and nb is tiny
    (messages bucket to a handful of 64-byte blocks).
    """
    state = tuple(h0[i] for i in range(8))
    for j in range(nb):
        nxt = _compress_rows(
            state, [blk[16 * j + i] for i in range(16)], k_at)
        live = jnp.int32(j) < nb_live
        state = tuple(jnp.where(live, n, s)
                      for n, s in zip(nxt, state))
    words = jnp.stack([jnp.where(has_digest != 0, digests[i], state[i])
                       for i in range(8)], axis=0)
    e = _words_to_limbs_rows(words)
    u1 = F.canonical(F.mulmod(e, w))
    u2 = F.canonical(F.mulmod(r, w))
    return words, u1, u2


# ---------------------------------------------------------------------------
# Stage-A kernels: SHA-256 + scalar derivation + window extraction
# ---------------------------------------------------------------------------

def _sha_tile(nb, wbits_g, wbits_q, consts, shc, h0, blk, nblocks,
              digests, has_digest, r, w, w1_out, w2_out, d_out):
    """Shared body of the two stage-A kernels; `blk` is the
    (nb*16, S, 128) message tile, wherever it came from. shc is the
    (64,) SMEM ref of _sha_consts: scalar loads, the round index the
    only dynamic one."""
    F = _fnk().bind(consts[:])
    words, u1, u2 = _sha_scalar_rows(
        F, lambda t: shc[t], h0[:], blk,
        nblocks[0, 0], digests[0], has_digest[0, 0], r[0], w[0], nb)
    d_out[0] = words
    w1_out[0] = _windows_rows(u1, wbits_g)
    w2_out[0] = _windows_rows(u2, wbits_q)


def _sha_kernel(nb, wbits_g, wbits_q, consts, shc, h0, blocks, *rest):
    _sha_tile(nb, wbits_g, wbits_q, consts, shc, h0, blocks[0], *rest)


def _sha_kernel_dma(nb, wbits_g, wbits_q, consts, shc, h0, blocks_hbm,
                    nblocks, digests, has_digest, r, w, w1_out, w2_out,
                    d_out, blk_vmem, dma_sem):
    """The streaming variant: `blocks` stays in HBM (memory_space=ANY)
    and each grid step's message tile is DMA'd into one of two VMEM
    slots, with the NEXT step's copy started before this step's
    compute — transfer rides under the SHA rounds instead of
    serializing with them. Only the verdict-feeding windows/digest
    rows come back through blocked outputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    ng = pl.num_programs(0)
    slot = lax.rem(i, 2)
    nxt_slot = lax.rem(i + 1, 2)

    @pl.when(i == 0)
    def _start_first():
        pltpu.make_async_copy(blocks_hbm.at[0], blk_vmem.at[0],
                              dma_sem.at[0]).start()

    @pl.when(i + 1 < ng)
    def _prefetch_next():
        pltpu.make_async_copy(blocks_hbm.at[i + 1],
                              blk_vmem.at[nxt_slot],
                              dma_sem.at[nxt_slot]).start()

    pltpu.make_async_copy(blocks_hbm.at[i], blk_vmem.at[slot],
                          dma_sem.at[slot]).wait()
    _sha_tile(nb, wbits_g, wbits_q, consts, shc, h0, blk_vmem[slot],
              nblocks, digests, has_digest, r, w, w1_out, w2_out,
              d_out)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _lead(v, g: int, bb: int):
    """(B, rows) -> (g, rows, bb//128, 128): batch-major flat order
    per block (lane b of grid block i is batch index i*bb + b), the
    block's lanes folded into a (sublane, lane) tile so the row axis
    is an untiled leading axis in the kernel — limb shifts, carries
    and pads along it are register renames (the ops/ptree.py
    layout), never sublane data movement."""
    rows = v.shape[1]
    return jnp.transpose(v, (1, 0)).reshape(
        rows, g, bb // LANE_ALIGN, LANE_ALIGN).transpose(1, 0, 2, 3)


def _unlead(v, Bp: int, B: int):
    """(g, rows, bb//128, 128) -> (B, rows): inverse of _lead."""
    rows = v.shape[1]
    return jnp.transpose(v, (1, 0, 2, 3)).reshape(rows, Bp) \
              .transpose(1, 0)[:B]


def sha_windows(blocks, nblocks, digests, has_digest, r_l, w_l, *,
                wbits_g: int = comb.WBITS, wbits_q: int = comb.WBITS,
                interpret=None, block_b: int = BLOCK_B, dma=None):
    """Batched on-device SHA-256 + scalar derivation + comb windows.

    blocks: (B, NB, 16) uint32 SHA-padded message words
    (sha256.pack_messages); nblocks: (B,) int32 live block counts (0
    for digest-only lanes); digests: (B, 8) uint32 precomputed digest
    words; has_digest: (B,) bool; r_l, w_l: (B, L) canonical limbs.

    Returns (w1 (B, 256//wbits_g), w2 (B, 256//wbits_q), words (B, 8))
    — the G-side and Q-side comb table windows of u1 = e*w and
    u2 = r*w (mod n), plus the digest words (for parity checks).

    dma=True (default) streams the message blocks HBM->VMEM through a
    two-slot double buffer; dma=False uses plain blocked VMEM inputs
    (the shape-confirmation path). interpret=None autodetects via
    jaxenv.pallas_interpret().
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        from fabric_tpu.common import jaxenv

        interpret = jaxenv.pallas_interpret()
    if dma is None:
        dma = True

    B, NB = blocks.shape[0], blocks.shape[1]
    NB16 = NB * 16
    bb = min(block_b, _round_up(B, LANE_ALIGN))
    Bp = _round_up(B, bb)
    g = Bp // bb
    if Bp != B:
        pad = [(0, Bp - B)]
        blocks = jnp.pad(blocks, pad + [(0, 0), (0, 0)])
        nblocks = jnp.pad(nblocks, pad)
        digests = jnp.pad(digests, pad + [(0, 0)])
        has_digest = jnp.pad(has_digest, pad)
        r_l = jnp.pad(r_l, pad + [(0, 0)])
        w_l = jnp.pad(w_l, pad + [(0, 0)])

    blk_t = _lead(blocks.astype(jnp.uint32).reshape(Bp, NB16), g, bb)
    nb_t = _lead(nblocks.astype(jnp.int32).reshape(Bp, 1), g, bb)
    dig_t = _lead(digests.astype(jnp.uint32), g, bb)
    hd_t = _lead(has_digest.astype(jnp.int32).reshape(Bp, 1), g, bb)
    r_t = _lead(r_l, g, bb)
    w_t = _lead(w_l, g, bb)

    consts = jnp.asarray(_fnk().pack_consts()).reshape(
        ptree.KMod.NCONST, L, 1, 1)
    shc = jnp.asarray(_sha_consts())
    n1, n2 = 256 // wbits_g, 256 // wbits_q
    S = bb // LANE_ALIGN

    def spec(rows):
        return pl.BlockSpec((1, rows, S, LANE_ALIGN),
                            lambda i: (i, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    cspec = pl.BlockSpec((ptree.KMod.NCONST, L, 1, 1),
                         lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM)
    shspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    h0spec = pl.BlockSpec((8, S, LANE_ALIGN), lambda i: (0, 0, 0),
                          memory_space=pltpu.VMEM)
    if dma:
        kernel = functools.partial(_sha_kernel_dma, NB, wbits_g,
                                   wbits_q)
        blk_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((2, NB16, S, LANE_ALIGN), jnp.uint32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_sha_kernel, NB, wbits_g, wbits_q)
        blk_spec = spec(NB16)
        scratch = []

    w1, w2, dwords = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[cspec, shspec, h0spec, blk_spec, spec(1), spec(8),
                  spec(1), spec(L), spec(L)],
        out_specs=[spec(n1), spec(n2), spec(8)],
        out_shape=[
            jax.ShapeDtypeStruct((g, n1, S, LANE_ALIGN), jnp.int32),
            jax.ShapeDtypeStruct((g, n2, S, LANE_ALIGN), jnp.int32),
            jax.ShapeDtypeStruct((g, 8, S, LANE_ALIGN), jnp.uint32)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=ptree.VMEM_LIMIT),
        interpret=interpret,
    )(consts, shc, jnp.asarray(_sha_h0(S)), blk_t, nb_t, dig_t, hd_t,
      r_t, w_t)
    return (_unlead(w1, Bp, B), _unlead(w2, Bp, B),
            _unlead(dwords, Bp, B))


# ---------------------------------------------------------------------------
# Stage B: gather from precomputed windows + the existing tree
# ---------------------------------------------------------------------------

def gather_from_windows(w1, w2, key_idx, g_flat, q_flat, K: int,
                        g16=None, q16: bool = False):
    """comb.comb_gather_points with the window extraction already done
    on device (stage A): (B, M, 3, L) gathered comb points."""
    if g16 is not None:
        win = jnp.arange(comb.NWIN_G16, dtype=jnp.int32)[None, :]
        pts_g = jnp.take(g16, win * comb.NENT_G16 + w1, axis=0)
    else:
        win = jnp.arange(comb.NWIN, dtype=jnp.int32)[None, :]
        pts_g = jnp.take(g_flat, win * comb.NENT + w1, axis=0)
    if q16:
        win = jnp.arange(comb.NWIN_G16, dtype=jnp.int32)[None, :]
        q_idx = (win * K + key_idx[:, None]) * comb.NENT_G16 + w2
    else:
        win = jnp.arange(comb.NWIN, dtype=jnp.int32)[None, :]
        q_idx = (win * K + key_idx[:, None]) * comb.NENT + w2
    pts_q = jnp.take(q_flat, q_idx, axis=0)
    return jnp.concatenate([pts_g, pts_q], axis=1)


def fused_verify_with_tables(blocks, nblocks, key_idx, q_flat, r8, rpn8,
                             w8, premask, digests, has_digest, g16=None,
                             q16: bool = False, tree: str = "pallas",
                             interpret=None, dma=None,
                             block_b: int = BLOCK_B):
    """The fused verify pipeline: device SHA + windows (stage A
    kernel), table gather, complete-add tree — bit-identical verdicts
    to comb.comb_verify_with_tables over host-hashed digests.

    blocks/nblocks: SHA-padded message words + live counts
    (sha256.pack_messages; nblocks 0 on digest-only lanes);
    r8/rpn8/w8: (B, 32) big-endian u8 scalar rows (limb conversion on
    device, same transfer-minimal shape as the comb_digest path);
    digests/has_digest: precomputed digest words for digest-only
    lanes. Table args exactly as comb_verify_with_tables.
    """
    ent = (comb.NWIN_G16 * comb.NENT_G16 if q16
           else comb.NWIN * comb.NENT)
    K = q_flat.shape[0] // ent
    g_flat = jnp.asarray(comb.g_tables()) if g16 is None else None
    r_l = limb.be_bytes_to_limbs_jnp(r8)
    rpn_l = limb.be_bytes_to_limbs_jnp(rpn8)
    w_l = limb.be_bytes_to_limbs_jnp(w8)
    wbits_g = 16 if g16 is not None else comb.WBITS
    wbits_q = 16 if q16 else comb.WBITS
    w1, w2, _ = sha_windows(blocks, nblocks, digests, has_digest, r_l,
                            w_l, wbits_g=wbits_g, wbits_q=wbits_q,
                            interpret=interpret, dma=dma,
                            block_b=block_b)
    pts = gather_from_windows(w1, w2, key_idx, g_flat, q_flat, K,
                              g16=g16, q16=q16)
    if tree == "pallas":
        return ptree.tree_verify_points(pts, r_l, rpn_l, premask,
                                        interpret=interpret)
    X, _, Z = comb._tree_reduce(pts[:, :, 0], pts[:, :, 1],
                                pts[:, :, 2])
    FP = p256.FP
    nonzero = jnp.any(FP.canonical(Z) != 0, axis=-1)
    x_canon = FP.canonical(X)
    ok1 = jnp.all(x_canon == FP.canonical(FP.mulmod(r_l, Z)), axis=-1)
    ok2 = jnp.all(x_canon == FP.canonical(FP.mulmod(rpn_l, Z)),
                  axis=-1)
    return premask & nonzero & (ok1 | ok2)


# ---------------------------------------------------------------------------
# The resident variant: ONE program, tables pinned in VMEM
# ---------------------------------------------------------------------------

def resident_table_bytes(K: int) -> int:
    """VMEM bytes the resident variant pins: the 8-bit G table plus K
    key slots of 8-bit Q table, (NWIN*NENT, 3, L) int32 each."""
    return comb.NWIN * comb.NENT * (1 + K) * 3 * L * 4


def _resident_kernel(nb, K, consts_n, consts_p, shc, h0, g_tab, q_tab,
                     blocks, nblocks, digests, has_digest, key_idx,
                     r, rpn, w, pm, out):
    Fn = _fnk().bind(consts_n[:])
    Fp = ptree._fpk().bind(consts_p[:])
    _, u1, u2 = _sha_scalar_rows(
        Fn, lambda t: shc[t], h0[:],
        blocks[0], nblocks[0, 0], digests[0], has_digest[0, 0],
        r[0], w[0], nb)
    bb = r.shape[-2] * r.shape[-1]
    # (rows, S, 128) lane tiles -> flat (rows, bb) for the gather
    w1 = _windows_rows(u1, comb.WBITS).reshape(comb.NWIN, bb)
    w2 = _windows_rows(u2, comb.WBITS).reshape(comb.NWIN, bb)
    win = lax.broadcasted_iota(jnp.int32, (comb.NWIN, bb), 0)
    g_pts = jnp.take(g_tab[:], win * comb.NENT + w1, axis=0)
    q_idx = (win * K + key_idx[0].reshape(1, bb)) * comb.NENT + w2
    q_pts = jnp.take(q_tab[:], q_idx, axis=0)
    pts = jnp.concatenate([g_pts, q_pts], axis=0)  # (M, bb, 3L)
    M = 2 * comb.NWIN
    pts = pts.reshape(M, bb, 3, L).transpose(2, 3, 0, 1)
    ts, tr = out.shape[1], out.shape[2]
    r_t = r[0].reshape(L, ts, tr)
    rpn_t = rpn[0].reshape(L, ts, tr)
    pm_t = pm[0].reshape(ts, tr)
    res = ptree.tree_body(pts[0], pts[1], pts[2], r_t, rpn_t, pm_t, Fp)
    out[0] = res.astype(jnp.int32)


def fused_verify_resident(blocks, nblocks, key_idx, q_flat, r8, rpn8,
                          w8, premask, digests, has_digest, g_flat=None,
                          *, interpret=None, block_b: int = BLOCK_B):
    """The single-program variant: SHA + scalars + windows + table
    gather + complete-add tree in ONE Pallas program, with the 8-bit
    g/q comb tables pinned in VMEM across grid steps via a constant
    index_map — only the verdict bitmap leaves the device.

    q_flat must be an 8-bit table (comb.build_q_tables) whose
    resident_table_bytes(K) fits the budget; callers gate on that.
    Verdicts are bit-identical to fused_verify_with_tables(tree=
    either). NOTE the in-kernel gather + 64-point tree lower cleanly
    under interpret; on real Mosaic this variant is gated behind the
    same `_tree_impl` guard as the q8 tree (unimplemented lowerings).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        from fabric_tpu.common import jaxenv

        interpret = jaxenv.pallas_interpret()

    K = q_flat.shape[0] // (comb.NWIN * comb.NENT)
    if g_flat is None:
        g_flat = jnp.asarray(comb.g_tables())
    g_tab = jnp.asarray(g_flat).reshape(-1, 3 * L)
    q_tab = jnp.asarray(q_flat).reshape(-1, 3 * L)

    r_l = limb.be_bytes_to_limbs_jnp(r8)
    rpn_l = limb.be_bytes_to_limbs_jnp(rpn8)
    w_l = limb.be_bytes_to_limbs_jnp(w8)

    B, NB = blocks.shape[0], blocks.shape[1]
    NB16 = NB * 16
    bb = min(block_b, _round_up(B, LANE_ALIGN))
    Bp = _round_up(B, bb)
    g = Bp // bb
    if Bp != B:
        pad = [(0, Bp - B)]
        blocks = jnp.pad(blocks, pad + [(0, 0), (0, 0)])
        nblocks = jnp.pad(nblocks, pad)
        key_idx = jnp.pad(key_idx, pad)
        digests = jnp.pad(digests, pad + [(0, 0)])
        has_digest = jnp.pad(has_digest, pad)
        r_l = jnp.pad(r_l, pad + [(0, 0)])
        rpn_l = jnp.pad(rpn_l, pad + [(0, 0)])
        w_l = jnp.pad(w_l, pad + [(0, 0)])
        premask = jnp.pad(premask, pad)

    blk_t = _lead(blocks.astype(jnp.uint32).reshape(Bp, NB16), g, bb)
    nb_t = _lead(nblocks.astype(jnp.int32).reshape(Bp, 1), g, bb)
    dig_t = _lead(digests.astype(jnp.uint32), g, bb)
    hd_t = _lead(has_digest.astype(jnp.int32).reshape(Bp, 1), g, bb)
    ki_t = _lead(key_idx.astype(jnp.int32).reshape(Bp, 1), g, bb)
    r_t = _lead(r_l, g, bb)
    rpn_t = _lead(rpn_l, g, bb)
    w_t = _lead(w_l, g, bb)
    pm_t = _lead(premask.astype(jnp.int32).reshape(Bp, 1), g, bb)

    consts_n = jnp.asarray(_fnk().pack_consts()).reshape(
        ptree.KMod.NCONST, L, 1, 1)
    consts_p = jnp.asarray(ptree._fpk().pack_consts()).reshape(
        ptree.KMod.NCONST, L, 1, 1)
    M = 2 * comb.NWIN
    ts, tr = ptree._collapse_tile(M, bb)

    def spec(rows):
        return pl.BlockSpec((1, rows, bb // LANE_ALIGN, LANE_ALIGN),
                            lambda i: (i, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    def pinned(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i: (0,) * nd,
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_resident_kernel, NB, K),
        grid=(g,),
        in_specs=[pinned((ptree.KMod.NCONST, L, 1, 1)),
                  pinned((ptree.KMod.NCONST, L, 1, 1)),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pinned((8, bb // LANE_ALIGN, LANE_ALIGN)),
                  pinned(tuple(g_tab.shape)),
                  pinned(tuple(q_tab.shape)),
                  spec(NB16), spec(1), spec(8), spec(1), spec(1),
                  spec(L), spec(L), spec(L), spec(1)],
        out_specs=pl.BlockSpec((1, ts, tr), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((g, ts, tr), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=ptree.VMEM_LIMIT),
        interpret=interpret,
    )(consts_n, consts_p, jnp.asarray(_sha_consts()),
      jnp.asarray(_sha_h0(bb // LANE_ALIGN)), g_tab, q_tab,
      blk_t, nb_t, dig_t, hd_t, ki_t, r_t, rpn_t, w_t, pm_t)
    return out.reshape(Bp)[:B] != 0
