"""Device-mesh sharding for the batched verify pipeline.

The reference scales validation with a goroutine pool bounded by
`validatorPoolSize` (`core/peer/peer.go:501`, default NumCPU); the TPU
rebuild scales by sharding the signature-batch axis of one XLA program over
a `jax.sharding.Mesh`. Verification is embarrassingly batch-parallel —
XLA's SPMD partitioner splits every op along the batch dim and the only
collective is the implicit all-gather of the (B,) result bits back to the
host. Multi-host sidecars would extend the same mesh over DCN; nothing in
the kernel changes.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fabric_tpu.ops import verify as verify_ops

BATCH_AXIS = "batch"


def batch_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the first `n_devices` local devices (default: all)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


def _shardings(mesh: Mesh):
    """Input shardings for verify_pipeline's 8 args (all batch-leading)."""
    s = NamedSharding(mesh, P(BATCH_AXIS))
    return (s,) * 8


def shard_batch(mesh: Mesh, *host_arrays):
    """Place batch-leading host arrays onto the mesh, split on dim 0.

    Batch size must be a multiple of the mesh size — callers pad to a
    fixed bucket first (fabric_tpu/bccsp handles bucketing).
    """
    s = NamedSharding(mesh, P(BATCH_AXIS))
    return tuple(jax.device_put(a, s) for a in host_arrays)


def sharded_verify_fn(mesh: Mesh):
    """jit-compiled verify_pipeline with batch-dim sharding over `mesh`."""
    return jax.jit(
        verify_ops.verify_pipeline,
        in_shardings=_shardings(mesh),
        out_shardings=NamedSharding(mesh, P(BATCH_AXIS)),
    )


def shardmap_comb_verify(mesh: Mesh, q16: bool):
    """The flagship comb pipeline as a per-shard program (shard_map).

    This is the SAME layout the TPU provider compiles under a mesh
    (bccsp/tpu.py _comb_pipeline): batch-sharded operand lanes,
    replicated tables, no collectives — shard_map rather than GSPMD so
    each chip runs the whole per-shard program on its own lanes and
    the partitioner has nothing to decide. With q16=True the 16-bit
    window configuration (the one the chip serves) is exercised.
    """
    from fabric_tpu.common import jaxenv
    from fabric_tpu.ops import comb

    def local(words, key_idx, q_flat, g16, r, rpn, w, premask):
        return comb.comb_verify_with_tables(
            words, key_idx, q_flat, r, rpn, w, premask,
            g16=g16 if q16 else None, q16=q16)

    s = P(BATCH_AXIS)
    rep = P()
    return jax.jit(jaxenv.shard_map(
        local, mesh=mesh,
        in_specs=(s, s, rep, rep, s, s, s, s), out_specs=s))


def sharded_comb_fns(mesh: Mesh):
    """(table_builder, verify_fn) for the comb kernel over `mesh`.

    The per-key tables are small and read by every lane, so they are
    REPLICATED to all devices; every per-signature operand is sharded on
    the batch axis. This is the flagship kernel's multi-chip layout: no
    collectives in the main path at all — each chip combs its own batch
    shard against its local table copy.
    """
    from fabric_tpu.ops import comb

    rep = NamedSharding(mesh, P())
    s = NamedSharding(mesh, P(BATCH_AXIS))
    build = jax.jit(comb.build_q_tables,
                    in_shardings=(rep, rep), out_shardings=rep)
    verify = jax.jit(
        comb.comb_verify_with_tables,
        in_shardings=(s, s, rep, s, s, s, s),
        out_shardings=s)
    return build, verify
