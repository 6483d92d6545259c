"""One lane shape whatever the batch size (ISSUE 28).

A TPU provider pads every device batch up to its pipeline span — by
default 2,048 lanes on each device — and sends a larger batch span by
span, padded to the next whole span, never to the next power of two.
So a (K, q16) pair costs ONE pipeline compile whatever the block size,
on the prepared-block path (`verify_prepared`) and on the item path
(`verify_batch`) alike.

`bucket_floor` stands in for the floor a TPU backend resolves
(`TPUProvider._floor`). Staging, padding, span splitting, the compile
seam (`_jit`, whose recorder counts a compile per new argument shape)
and the counters are the provider's own; the device program is a
stand-in that accepts a lane only where the provider's premask does
AND the digest in that lane is one the sw provider accepted — so a
lane whose operands landed in the wrong place reads wrong.
"""

import functools
import hashlib

import numpy as np
import pytest

from fabric_tpu import native
from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, utils
from fabric_tpu.bccsp import factory
from fabric_tpu.bccsp import tpu as tpu_mod
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider, host_prep_scalars
from fabric_tpu.common import faults

SPAN = tpu_mod.SPAN_LANES_PER_DEVICE
MIN_BATCH = 16
PERIOD = 61     # prime: a span's lanes never line up with the corpus
SIZES = (1 * MIN_BATCH, 1500, 2048, 2049, 5000, 30720)

_SW = SWProvider()
_KEYS = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(4)]


def _digest_key(digest: bytes) -> bytes:
    """A digest as the provider's uint32 lane words hold it."""
    return np.frombuffer(digest, dtype=">u4").astype(np.uint32).tobytes()


@functools.lru_cache(maxsize=None)
def _unique():
    """PERIOD distinct signed messages over 4 keys, one in four
    tampered in each of chip_smoke.py's ways; the sw verdict of each."""
    entries = []
    for i in range(PERIOD):
        k = _KEYS[i % 4]
        msg = f"span padding {i}".encode()
        sig = _SW.sign(k, hashlib.sha256(msg).digest())
        pub = k.public_key()
        how = i % 16
        if how == 3:            # bad signature: message changed
            msg += b"!"
        elif how == 7:          # wrong key
            pub = _KEYS[(i + 1) % 4].public_key()
        elif how == 11:         # high-S twin
            r, s = utils.unmarshal_signature(sig)
            sig = utils.marshal_signature(r, utils.P256_N - s)
        elif how == 15:         # malformed DER
            sig = sig[:-2]
        entries.append(VerifyItem(key=pub, signature=sig, message=msg))
    verdicts = _SW.verify_batch(entries)
    assert any(verdicts) and not all(verdicts)
    accepted = {_digest_key(hashlib.sha256(e.message).digest())
                for e, v in zip(entries, verdicts) if v}
    return entries, verdicts, accepted


def _batch(n):
    entries, verdicts, _ = _unique()
    return ([entries[j % PERIOD] for j in range(n)],
            [verdicts[j % PERIOD] for j in range(n)])


def _prepared_args(items):
    """`items` as native block prep hands them to `verify_prepared`."""
    n = len(items)
    sigs = [it.signature for it in items]
    prep = native.batch_prep(sigs) if native.available() else None
    if prep is not None:
        der_ok, r, rpn, w = (np.asarray(a) for a in prep)
    else:
        der_ok = np.zeros(n, dtype=bool)
        r, rpn, w = (np.zeros((n, 32), dtype=np.uint8) for _ in range(3))
        for i, it in enumerate(items):
            p = host_prep_scalars(it.key, it.signature)
            if p is not None:
                der_ok[i] = True
                r[i], rpn[i], w[i] = (np.frombuffer(b, np.uint8)
                                      for b in p)
    keys = [k.public_key() for k in _KEYS]
    slot = {k.x_bytes().tobytes() + k.y_bytes().tobytes(): j
            for j, k in enumerate(keys)}
    key_idx = np.array([slot[it.key.x_bytes().tobytes()
                             + it.key.y_bytes().tobytes()]
                        for it in items], dtype=np.int32)
    digests = np.frombuffer(
        b"".join(hashlib.sha256(it.message).digest() for it in items),
        dtype=np.uint8).reshape(n, 32)
    return (digests, r, rpn, w, der_ok.astype(bool), key_idx, keys,
            lambda i: sigs[i])


def _stand_in(prov):
    """Swap `prov`'s digest program for a cheap jitted stand-in built
    through the provider's own compile seam; the lane counts it ran."""
    shapes = []
    accepted = _unique()[2]
    programs = {}

    def fake_pipeline_digest():
        if not programs:
            programs["digest"] = prov._jit(
                "comb_digest",
                lambda key_idx, q_flat, g16, r8, rpn8, w8, premask,
                digests: premask)

        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            out = np.asarray(programs["digest"](
                key_idx, q_flat, g16, r8, rpn8, w8, premask, digests))
            shapes.append(out.shape[0])
            dg = np.asarray(digests)
            return out & np.array([dg[j].tobytes() in accepted
                                   for j in range(len(out))], dtype=bool)
        return run

    # a pool of a few hundred bytes: real slots, real pool writes
    prov._slab_rows = lambda: 8
    prov._qtab_fn = lambda: lambda qx, qy: np.zeros((8, 3, 20), np.int32)
    prov._comb_pipeline_digest = fake_pipeline_digest
    return shapes


def _provider(**kw):
    """A provider as a TPU backend resolves it — the floor at the
    span — with the stand-in digest program."""
    faults.clear()
    kw.setdefault("min_batch", MIN_BATCH)
    kw.setdefault("use_g16", False)
    kw.setdefault("bucket_floor", SPAN)
    prov = TPUProvider(**kw)
    return prov, _stand_in(prov)


def _run(prov, path, items):
    if path == "prepared":
        return prov.verify_prepared(*_prepared_args(items))
    return prov.verify_batch(items)


@pytest.mark.parametrize("path", ["prepared", "item"])
@pytest.mark.parametrize("n", SIZES)
def test_every_batch_size_runs_whole_spans_of_one_shape(n, path):
    prov, shapes = _provider()
    assert prov._pipeline_span() == prov._floor() == SPAN
    # the first batch pays the one compile
    first, want_first = _batch(MIN_BATCH)
    assert _run(prov, path, first) == want_first
    assert shapes == [SPAN]
    compiled = prov.stats["compile_total"]
    assert compiled >= 1
    del shapes[:]
    before = dict(prov.stats)

    items, want = _batch(n)
    got = _run(prov, path, items)
    assert got == want == _SW.verify_batch(items)
    assert not all(want) and any(want)

    spans = -(-n // SPAN)
    assert shapes == [SPAN] * spans
    assert prov._bucket(n) == spans * SPAN
    assert prov.stats["compile_total"] == compiled
    assert prov.stats["sw_fallbacks"] == 0
    assert prov.stats["ladder_batches"] == 0
    booked = {k: prov.stats[k] - before[k] for k in (
        "comb_batches", "pipeline_batches", "pipeline_chunks",
        "lanes_real", "lanes_padded")}
    assert booked["comb_batches"] == 1
    if path == "prepared":
        assert booked["lanes_real"] == n
        assert booked["lanes_padded"] == spans * SPAN
        # one batch, however many spans: the span counters are the
        # overlapped item path's alone
        assert booked["pipeline_batches"] == booked["pipeline_chunks"] == 0
    else:
        # executions as the benchmark reads them off the counters
        assert (booked["comb_batches"] - booked["pipeline_batches"]
                + booked["pipeline_chunks"]) == spans


def test_buckets_above_the_span_are_whole_spans_not_powers_of_two():
    prov, _ = _provider()
    assert [prov._bucket(n) for n in (1, 2048, 2049, 4097, 5000, 30720,
                                      32769)] == \
        [2048, 2048, 4096, 6144, 6144, 30720, 34816]
    assert all(prov._mesh_chunk(prov._bucket(n)) == SPAN
               for n in (1, 2049, 5000, 30720, 32769, 100000))
    # off the chip the floor is 0: tight powers of two up to the span,
    # whole spans above it
    cpu = TPUProvider(min_batch=MIN_BATCH)
    assert cpu._floor() == 0
    assert [cpu._bucket(n) for n in (1, 17, 1500, 2049, 5000)] == \
        [16, 32, 2048, 4096, 6144]
    # a floor above the span still pins small batches to itself
    pinned = TPUProvider(min_batch=MIN_BATCH, bucket_floor=8192)
    assert [pinned._bucket(n) for n in (10, 8192, 8193)] == \
        [8192, 8192, 10240]
    assert pinned._mesh_chunk(8192) == SPAN
    # with the span pipeline off, nothing but powers of two
    off = TPUProvider(min_batch=MIN_BATCH, pipeline_chunk=0)
    assert [off._bucket(n) for n in (17, 5000)] == [32, 8192]


class _Mesh:
    """Only what `_pipeline_span` / `_bucket` read of a mesh."""

    def __init__(self, size):
        self.size = size
        self.devices = np.empty((size,), dtype=object)


@pytest.mark.parametrize("ndev, want", [(None, 2048), (2, 4096),
                                        (4, 8192), (8, 16384)])
def test_default_span_is_2048_lanes_a_device(ndev, want):
    mesh = _Mesh(ndev) if ndev else None
    prov = TPUProvider(mesh=mesh)
    assert prov._pipeline_span() == want
    assert want // (ndev or 1) == SPAN


@pytest.mark.parametrize("given, ndev, want", [
    (8192, None, 8192),     # four times the default on one chip
    (8192, 4, 8192),        # total lanes of a span, not lanes a chip
    (1000, 4, 512),         # floored to the tile / mesh granule
    (512, None, 512),
    (0, None, None),        # the span pipeline switched off
])
def test_explicit_pipeline_chunk_is_taken_as_given(given, ndev, want):
    mesh = _Mesh(ndev) if ndev else None
    assert TPUProvider(mesh=mesh,
                       pipeline_chunk=given)._pipeline_span() == want


def test_factory_leaves_the_span_unset_unless_configured():
    assert factory.TpuOpts().pipeline_chunk is None
    opts = factory.FactoryOpts.from_config({"Default": "TPU"})
    assert opts.tpu.pipeline_chunk is None
    opts = factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"PipelineChunk": 8192}})
    assert opts.tpu.pipeline_chunk == 8192
    opts = factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"PipelineChunk": 0}})
    assert opts.tpu.pipeline_chunk == 0
    # Devices: 1 builds no mesh: the default is one device's span
    prov = factory.new_bccsp(factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"Devices": 1}}))
    assert prov._pipeline_span() == SPAN
    # every local device: 2,048 lanes each
    import jax
    prov = factory.new_bccsp(factory.FactoryOpts.from_config(
        {"Default": "TPU"}))
    assert prov._pipeline_span() == SPAN * len(jax.devices())
    prov = factory.new_bccsp(factory.FactoryOpts.from_config(
        {"Default": "TPU", "TPU": {"Devices": 1, "PipelineChunk": 8192}}))
    assert prov._pipeline_span() == 8192


@pytest.mark.parametrize("switch", [
    "FTPU_FUSED", "FTPU_FUSED_RESIDENT", "FTPU_PALLAS",
    "FTPU_PALLAS_INTERPRET", "FusedVerify"])
def test_removed_switch_is_inert(switch, monkeypatch):
    """Nothing a user sets selects a kernel (ISSUE 32): with a retired
    switch set, a message-lane batch is still hashed on the host and
    served by `comb_digest`, and by nothing else."""
    faults.clear()
    cfg = {"MinBatch": MIN_BATCH, "UseG16": False, "BucketFloor": SPAN,
           "Devices": 1}
    if switch.startswith("FTPU_"):
        monkeypatch.setenv(switch, "1")
    else:
        cfg[switch] = True
    opts = factory.FactoryOpts.from_config({"Default": "TPU", "TPU": cfg})
    assert not hasattr(opts.tpu, "fused_verify")
    prov = factory.new_bccsp(opts)
    shapes = _stand_in(prov)
    items, want = _batch(1500)
    assert all(it.digest is None for it in items)
    assert prov.verify_batch(items) == want
    assert shapes == [SPAN]
    assert prov.stats["comb_batches"] == 1
    assert prov.stats["host_hashed_lanes"] == sum(
        host_prep_scalars(it.key, it.signature) is not None
        for it in items)
    assert prov.stats["sw_fallbacks"] == prov.stats["ladder_batches"] == 0
    assert not [k for k in prov.stats if "fused" in k]
    assert {e["kind"] for e in prov.device_cost.events} == {
        "pool_write", "comb_digest"}
