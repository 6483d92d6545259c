"""Batched SHA-256 on TPU (uint32 tensor ops, fixed-shape buckets).

Rebuild of the reference's hashing hot path: every signature verification
hashes its message first (`msp/identities.go:179` → `bccsp.Hash` →
`bccsp/sw/hash.go`, SHA-256). Here a whole batch of messages is hashed as
one XLA program: messages are SHA-padded host-side, packed into a fixed
number of 64-byte blocks per bucket, and the compression function runs as a
`lax.fori_loop` over blocks with all lanes advancing in lockstep; lanes
whose message has fewer blocks mask out the extra state updates.

All arithmetic is uint32 (native TPU int32 units; wrap-around add is the
SHA-256 semantics).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

_K = np.array([
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
], dtype=np.uint32)

_H0 = np.array([
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
], dtype=np.uint32)


def _rotr(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _compress(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """One SHA-256 compression: state (B, 8), block (B, 16) -> (B, 8).

    Both the message schedule and the 64 rounds run as `lax.scan`s. This
    is not just graph-size hygiene: fully unrolled, XLA's elementwise
    fusion duplicates multi-consumer round values, and the rotating
    8-register dependency makes the recomputation exponential in the
    round count (measured: 24 unrolled rounds ≈ 0.4 s on CPU, 32 rounds
    > 100 s). scan bodies materialize per step, bounding the fusion.
    """
    # message schedule: carry a rolling window of the last 16 words
    def sched_step(win, _):
        # win: (B, 16) = W[t-16..t-1]; emit W[t-16], produce W[t]
        wm15, wm2 = win[..., 1], win[..., 14]
        s0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> jnp.uint32(3))
        s1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> jnp.uint32(10))
        wt = win[..., 0] + s0 + win[..., 9] + s1
        new_win = jnp.concatenate([win[..., 1:], wt[..., None]], axis=-1)
        return new_win, win[..., 0]

    win, w_early = lax.scan(sched_step, block, None, length=48)
    # w_early: (48, B) = W[0..47]; win holds W[48..63]
    w_all = jnp.concatenate([w_early, jnp.moveaxis(win, -1, 0)], axis=0)

    def round_step(regs, inp):
        a, b, c, d, e, f, g, h = regs
        wt, kt = inp
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + kt + wt
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = S0 + maj
        return (t1 + t2, a, b, c, d + t1, e, f, g), None

    regs0 = tuple(state[..., i] for i in range(8))
    regs, _ = lax.scan(round_step, regs0, (w_all, jnp.asarray(_K)))
    return state + jnp.stack(regs, axis=-1)


def sha256_blocks(blocks: jnp.ndarray, nblocks: jnp.ndarray) -> jnp.ndarray:
    """Hash pre-padded messages.

    blocks: (B, NB, 16) uint32 big-endian words (SHA padding already
        applied host-side; trailing blocks beyond a message's own padded
        length are ignored).
    nblocks: (B,) int32 — number of real (padded) blocks per message.
    Returns (B, 8) uint32 digest words.
    """
    B, NB, _ = blocks.shape
    init = jnp.broadcast_to(jnp.asarray(_H0), (B, 8))

    def body(j, state):
        new = _compress(state, blocks[:, j, :])
        live = (j < nblocks)[:, None]
        return jnp.where(live, new, state)

    return lax.fori_loop(0, NB, body, init)


# ---------------------------------------------------------------------------
# Host-side packing
# ---------------------------------------------------------------------------

def max_message_len(nb: int) -> int:
    """Largest message (bytes) that fits nb SHA-256 blocks after padding."""
    return nb * 64 - 9


def pack_messages(msgs: list[bytes], nb: int) -> tuple[np.ndarray, np.ndarray]:
    """SHA-pad each message and pack into (B, nb, 16) uint32 words + block
    counts. Every message must satisfy len(msg) <= max_message_len(nb).

    Vectorized: one flat-byte scatter plus numpy word assembly instead
    of a per-message Python loop. Byte-identical to the per-message
    reference; pinned by tests/test_sha256.py::TestPackMessages.
    """
    B = len(msgs)
    out = np.zeros((B, nb, 16), dtype=np.uint32)
    counts = np.zeros((B,), dtype=np.int32)
    if B == 0:
        return out, counts
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int64, count=B)
    if lens.max() > max_message_len(nb):
        i = int(np.argmax(lens > max_message_len(nb)))
        raise ValueError(f"message {i} too long for {nb} blocks")
    counts[:] = (lens + 9 + 63) // 64

    # one (B, nb*64) byte plane: message bytes scattered flat (a single
    # flat-index store — the destination of byte j of the join is its
    # row offset plus its position within the message), then the 0x80
    # terminator and the 8-byte big-endian bit length per row
    rowlen = nb * 64
    buf = np.zeros((B, rowlen), dtype=np.uint8)
    total = int(lens.sum())
    if total:
        flat = np.frombuffer(b"".join(msgs), dtype=np.uint8)
        starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
        shift = np.repeat(np.arange(B, dtype=np.int64) * rowlen - starts,
                          lens)
        buf.reshape(-1)[np.arange(total, dtype=np.int64) + shift] = flat
    rows_all = np.arange(B)
    buf[rows_all, lens] = 0x80
    bitlen = 8 * lens.astype(np.uint64)
    tail0 = counts.astype(np.int64) * 64 - 8
    for j in range(8):
        buf[rows_all, tail0 + j] = \
            ((bitlen >> np.uint64(8 * (7 - j))) & np.uint64(0xFF))

    # big-endian 32-bit words in one byteswap pass; blocks past each
    # row's count stay zero because their bytes in buf were never
    # written, matching the per-message reference
    out = buf.view(">u4").astype(np.uint32).reshape(B, nb, 16)
    return np.ascontiguousarray(out), counts


def sha256_host(msgs: list[bytes], nb: int | None = None) -> np.ndarray:
    """Convenience: hash a batch, returning (B, 8) uint32 digest words."""
    if nb is None:
        nb = max((len(m) + 9 + 63) // 64 for m in msgs) if msgs else 1
    blocks, counts = pack_messages(msgs, nb)
    return np.asarray(
        jax.jit(sha256_blocks)(jnp.asarray(blocks), jnp.asarray(counts))
    )
