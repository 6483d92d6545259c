"""Overlapped dispatch pipeline (ISSUE 2 tentpole): parity + timers.

`BCCSP.TPU.PipelineChunk` splits a device batch into fixed spans so
span N's device execution overlaps span N+1's host prep and transfer.
The contract under test: verdicts are BIT-IDENTICAL to the whole-batch
staging path and the sw oracle — including span counts that do not
divide the lane count (the padded tail must stay premasked-dead) —
and the overlap is observable through the `pipeline_*` stats that back
the `bccsp_pipeline_*` gauges.

Device math uses the recorder-stub idiom (tests/test_bucket_floor.py):
real staging, key canonicalization, span splitting and premask
assembly, with the jitted kernel replaced by a premask recorder.
"""

import hashlib

import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider, aligned_span
from fabric_tpu.common import faults

_SW = SWProvider()
_KEYS = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(2)]

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



def _stubbed_provider(**kw):
    kw.setdefault("min_batch", 1)
    kw.setdefault("use_g16", False)
    tpu = TPUProvider(**kw)
    calls = {"premask": [], "key_idx": [], "ladder": 0}

    def fake_qtab_fn():
        return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                           dtype=np.int32)

    def fake_pipeline_digest():
        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            calls["premask"].append(np.asarray(premask).copy())
            calls["key_idx"].append(np.asarray(key_idx).copy())
            return np.asarray(premask)
        return run

    def fake_ladder():
        def run(blocks, nblocks, qx, qy, r, rpn, w, premask, digests,
                has_digest):
            calls["ladder"] += 1
            return np.asarray(premask)
        return run

    tpu._slab_rows = lambda: SLAB_ROWS

    tpu._qtab_fn = fake_qtab_fn
    tpu._comb_pipeline_digest = fake_pipeline_digest
    tpu._pipeline = fake_ladder
    return tpu, calls


def _corpus(n, all_invalid=False):
    items, expected = [], []
    for i in range(n):
        k = _KEYS[i % 2]
        m = f"pipeline {i}".encode()
        sig = _SW.sign(k, hashlib.sha256(m).digest())
        if all_invalid or i % 3 == 2:
            r, s = utils.unmarshal_signature(sig)
            sig = (sig[:-2] if i % 2 else
                   utils.marshal_signature(r, utils.P256_N - s))
            expected.append(False)
        else:
            expected.append(True)
        items.append(VerifyItem(key=k.public_key(), signature=sig,
                                message=m))
    return items, expected


class TestSpanMath:
    def test_aligned_span_granule(self):
        assert aligned_span(8192) == 8192
        assert aligned_span(100) == 128      # min one granule
        assert aligned_span(300) == 256      # floored
        assert aligned_span(1000, mesh_size=4) == 512

    def test_provider_span_caps_at_chunk(self):
        tpu = TPUProvider(pipeline_chunk=8192, chunk=512)
        assert tpu._pipeline_span() == 512
        assert TPUProvider(pipeline_chunk=0)._pipeline_span() is None


class TestPipelineParity:
    def test_nondividing_span_parity(self):
        """300 lanes over 128-lane spans: 3 spans, 84 padded tail
        lanes — verdicts match the sw oracle lane for lane and the
        padding never leaks a verdict."""
        faults.clear()
        tpu, calls = _stubbed_provider(pipeline_chunk=128)
        items, expected = _corpus(300)
        out = tpu.verify_batch(items)
        assert out == expected == _SW.verify_batch(items)
        assert tpu.stats["pipeline_batches"] == 1
        assert tpu.stats["pipeline_chunks"] == 3
        # every span the kernel saw is exactly one compiled shape
        assert [len(p) for p in calls["premask"]] == [128, 128, 128]
        # the padded tail is premasked dead
        assert not calls["premask"][-1][300 - 256:].any()

    def test_matches_whole_batch_path(self):
        faults.clear()
        piped, _ = _stubbed_provider(pipeline_chunk=128)
        whole, _ = _stubbed_provider(pipeline_chunk=0)
        items, expected = _corpus(200)
        assert piped.verify_batch(items) == \
            whole.verify_batch(items) == expected
        assert piped.stats["pipeline_batches"] == 1
        assert whole.stats["pipeline_batches"] == 0

    def test_digest_lanes_and_sw_lanes_merge(self):
        """Digest-carrying lanes ride the pipeline; non-32-byte-digest
        lanes fall to the sw path per lane without degrading the
        batch."""
        faults.clear()
        tpu, _ = _stubbed_provider(pipeline_chunk=128)
        items, expected = _corpus(150)
        for i in range(0, 150, 10):
            it = items[i]
            items[i] = VerifyItem(
                key=it.key, signature=it.signature,
                digest=hashlib.sha256(it.message).digest())
        # lane 5: truncated digest -> sw path -> False
        items[5] = VerifyItem(key=items[5].key,
                              signature=items[5].signature,
                              digest=b"\x00" * 20)
        expected[5] = False
        out = tpu.verify_batch(items)
        assert out == expected
        assert tpu.stats["nonp256_sw_lanes"] == 1

    def test_all_invalid_batch_routes_like_whole_batch_path(self):
        """Every lane failing the host gates leaves key_map empty —
        exactly as on the whole-batch path — so the batch routes to
        the generic ladder staging, not the comb pipeline."""
        faults.clear()
        tpu, calls = _stubbed_provider(pipeline_chunk=128)
        items, expected = _corpus(140, all_invalid=True)
        assert tpu.verify_batch(items) == expected
        assert not any(expected)
        assert tpu.stats["pipeline_batches"] == 0
        # 140 lanes over 128-lane spans: the ladder goes span by
        # span too, one batch in two dispatches of one shape
        assert calls["ladder"] == 2
        assert tpu.stats["ladder_batches"] == 1

    def test_single_span_takes_whole_batch_path(self):
        faults.clear()
        tpu, _ = _stubbed_provider(pipeline_chunk=128)
        items, expected = _corpus(100)      # n <= span
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["pipeline_batches"] == 0

    def test_gate_failed_lanes_do_not_register_keys(self):
        """Key-set MEMBERSHIP must match the whole-batch path: a key
        appearing only on lanes whose signatures fail the host gates
        must not enter key_map (it would change K and the canonical
        q16 cache key, churning multi-minute table builds)."""
        faults.clear()
        tpu, calls = _stubbed_provider(pipeline_chunk=128)
        items, expected = [], []
        for i in range(200):
            m = f"member {i}".encode()
            if i % 4 == 3:
                # key 1 appears ONLY with malformed signatures
                sig = _SW.sign(_KEYS[1],
                               hashlib.sha256(m).digest())[:-2]
                items.append(VerifyItem(key=_KEYS[1].public_key(),
                                        signature=sig, message=m))
                expected.append(False)
            else:
                sig = _SW.sign(_KEYS[0], hashlib.sha256(m).digest())
                items.append(VerifyItem(key=_KEYS[0].public_key(),
                                        signature=sig, message=m))
                expected.append(True)
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["pipeline_batches"] == 1
        # every lane names the ONE key's slot, as the whole-batch path
        # would resolve for this batch
        assert tpu.stats["key_slots_resident"] == 1
        for kidx in calls["key_idx"]:
            assert (kidx == 0).all()

    def test_many_keys_fall_back_to_ladder(self):
        faults.clear()
        tpu, calls = _stubbed_provider(pipeline_chunk=128, max_keys=1)
        items, expected = _corpus(200)      # 2 distinct keys > max
        assert tpu.verify_batch(items) == expected
        assert tpu.stats["pipeline_batches"] == 0
        assert calls["ladder"] == 2         # 200 lanes, 128-lane spans
        assert tpu.stats["ladder_batches"] == 1


class TestPipelineObservability:
    def test_stage_timers_and_overlap_exported(self):
        faults.clear()
        tpu, _ = _stubbed_provider(pipeline_chunk=128)
        items, expected = _corpus(300)
        assert tpu.verify_batch(items) == expected
        s = tpu.stats
        assert s["pipeline_host_s"] > 0
        assert s["pipeline_device_s"] >= 0
        assert s["pipeline_transfer_s"] >= 0
        assert 0.0 <= s["pipeline_overlap_ratio"] <= 1.0

    def test_pipeline_gauges_published(self):
        """The four canonical bccsp_pipeline_* series render on
        /metrics with their declared help text (not the generic
        stats-gauge fallback)."""
        from fabric_tpu.common import metrics as m
        from fabric_tpu.common import profiling

        faults.clear()
        tpu, _ = _stubbed_provider(pipeline_chunk=128)
        items, _ = _corpus(300)
        tpu.verify_batch(items)
        provider = m.PrometheusProvider()
        t = profiling.publish_provider_stats(provider, tpu,
                                             poll_s=0.01)
        assert t is not None
        import time
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            text = provider.render()
            if "bccsp_pipeline_overlap_ratio" in text:
                break
            time.sleep(0.02)
        text = provider.render()
        for name in ("bccsp_pipeline_host_s",
                     "bccsp_pipeline_transfer_s",
                     "bccsp_pipeline_device_s",
                     "bccsp_pipeline_overlap_ratio"):
            assert name in text
        assert "hidden behind device execution" in text

    def test_fault_at_dispatch_falls_back_bit_identical(self):
        """The tpu.dispatch fault point fires once per pipelined batch
        and degrades to sw with identical verdicts."""
        faults.clear()
        faults.arm("tpu.dispatch", mode="error", count=1)
        try:
            tpu, _ = _stubbed_provider(pipeline_chunk=128)
            items, expected = _corpus(200)
            assert tpu.verify_batch(items) == expected
            assert tpu.stats["sw_fallbacks"] == 1
            assert tpu.stats["pipeline_batches"] == 0
            # next batch (fault exhausted) rides the pipeline again
            assert tpu.verify_batch(items) == expected
            assert tpu.stats["pipeline_batches"] == 1
        finally:
            faults.clear()
