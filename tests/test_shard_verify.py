"""Multi-chip sharded batch verify (ISSUE 6 tentpole): parity, knob,
faults, observability.

The provider shards the batch axis of its comb/tree pipeline over a
1-D device mesh (`BCCSP.TPU.Devices`, default = all local devices;
1 = the pre-mesh single-device path bit-for-bit). The contract under
test: sharded verdicts are BIT-IDENTICAL to the single-chip path and
the sw oracle — on dividing and non-dividing batch sizes, mixed and
all-invalid accept/reject bitmaps — the round-robin span feeder deals
lanes across the mesh with per-device transfer streams, a faulted
sharded dispatch degrades through the breaker exactly like the
single-chip path, and the per-device `bccsp_shard_*` gauges publish.

Device math uses the recorder-stub idiom (tests/test_pipeline_overlap
.py): real staging, mesh placement, span splitting, premask assembly;
the jitted kernel is replaced by a premask recorder. The real sharded
XLA arithmetic is covered by the multi-process case below (sharded
SHA-256, bit-exact vs hashlib — compiles in under a second) and by
the slow-marked full-kernel parity at the bottom; the multi-minute
comb compiles stay out of tier-1.
"""

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, factory, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider
from fabric_tpu.common import faults
from fabric_tpu.parallel import batch_mesh

_SW = SWProvider()
_KEYS = [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(2)]

# aligned_span granule for an 8-way mesh (bccsp/tpu.py LANE_ALIGN=128)
SPAN8 = 1024

# rows of a stub key table: a pool of a few hundred bytes, so the
# real slot bookkeeping and pool write run under the stubbed math
SLAB_ROWS = 8



def _stubbed_provider(mesh=None, **kw):
    kw.setdefault("min_batch", 1)
    kw.setdefault("use_g16", False)
    tpu = TPUProvider(mesh=mesh, **kw)
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


def _corpus(n, all_invalid=False, keys=_KEYS):
    items, expected = [], []
    for i in range(n):
        k = keys[i % len(keys)]
        m = f"shard {i}".encode()
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


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh from conftest")
    return batch_mesh(8)


class TestShardedParity:
    def test_dividing_batch_parity(self, mesh8):
        """2048 lanes over 1024-lane spans: sharded verdicts match the
        mesh-less provider and the sw oracle lane for lane, and the
        per-device shard stats populate."""
        faults.clear()
        sharded, calls = _stubbed_provider(mesh=mesh8,
                                           pipeline_chunk=SPAN8)
        single, _ = _stubbed_provider(pipeline_chunk=SPAN8)
        items, expected = _corpus(2048)
        out8 = sharded.verify_batch(items)
        out1 = single.verify_batch(items)
        assert out8 == out1 == expected == _SW.verify_batch(items)
        assert sharded.stats["pipeline_batches"] == 1
        assert sharded.stats["pipeline_chunks"] == 2
        assert [len(p) for p in calls["premask"]] == [SPAN8, SPAN8]
        assert sharded.stats["shard_devices"] == 8
        assert sharded.stats["shard_dispatches"] == 2
        assert sharded.shard_stats["lanes"] == [SPAN8 // 8] * 8
        assert len(sharded.shard_stats["transfer_s"]) == 8

    def test_nondividing_batch_parity(self, mesh8):
        """2500 lanes -> 3 spans with 572 padded tail lanes: one
        compiled shape per device count, padding never leaks a
        verdict, bitmaps bit-identical to single-chip and oracle."""
        faults.clear()
        sharded, calls = _stubbed_provider(mesh=mesh8,
                                           pipeline_chunk=SPAN8)
        single, _ = _stubbed_provider(pipeline_chunk=SPAN8)
        items, expected = _corpus(2500)
        out8 = sharded.verify_batch(items)
        assert out8 == single.verify_batch(items) == expected
        assert sharded.stats["pipeline_chunks"] == 3
        assert [len(p) for p in calls["premask"]] == [SPAN8] * 3
        # the padded tail is premasked dead
        assert not calls["premask"][-1][2500 - 2048:].any()

    def test_all_invalid_batch_parity(self, mesh8):
        """Every lane failing the host gates leaves key_map empty:
        the batch routes to the generic ladder staging — sharded and
        single-chip alike — and the all-False bitmap matches."""
        faults.clear()
        sharded, calls = _stubbed_provider(mesh=mesh8,
                                           pipeline_chunk=SPAN8)
        items, expected = _corpus(1100, all_invalid=True)
        assert sharded.verify_batch(items) == expected
        assert not any(expected)
        assert sharded.stats["pipeline_batches"] == 0
        # 1,100 lanes over 1,024-lane spans: the ladder goes span by
        # span like the comb tiers, two dispatches of the one shape
        assert calls["ladder"] == 2
        assert sharded.stats["ladder_batches"] == 1

    def test_whole_batch_digest_path_sharded(self, mesh8):
        """pipeline_chunk=0 (overlap off): the whole-batch digest comb
        staging also rides the sharded feeder, with mesh-aligned
        buckets."""
        faults.clear()
        sharded, calls = _stubbed_provider(mesh=mesh8,
                                           pipeline_chunk=0)
        single, _ = _stubbed_provider(pipeline_chunk=0)
        items, expected = _corpus(300)
        out8 = sharded.verify_batch(items)
        assert out8 == single.verify_batch(items) == expected
        assert sharded.stats["shard_dispatches"] >= 1
        # mesh-aligned bucket: every staged span divides the mesh
        assert all(len(p) % 8 == 0 for p in calls["premask"])

    def test_mixed_digest_and_sw_lanes(self, mesh8):
        """Digest-carrying lanes ride the sharded pipeline; non-P256 /
        bad-digest lanes fall to the per-lane sw path without
        degrading the batch — same contract as single-chip."""
        faults.clear()
        sharded, _ = _stubbed_provider(mesh=mesh8,
                                       pipeline_chunk=SPAN8)
        items, expected = _corpus(1200)
        for i in range(0, 1200, 10):
            it = items[i]
            items[i] = VerifyItem(
                key=it.key, signature=it.signature,
                digest=hashlib.sha256(it.message).digest())
        items[5] = VerifyItem(key=items[5].key,
                              signature=items[5].signature,
                              digest=b"\x00" * 20)
        expected[5] = False
        assert sharded.verify_batch(items) == expected
        assert sharded.stats["nonp256_sw_lanes"] == 1


class TestProgramInventory:
    def test_program_inventory_on_the_mesh(self, mesh8, monkeypatch):
        """The sharded twin of tests/test_bccsp.py's inventory: the
        provider's OWN sharded builders (shard_map specs, the ladder's
        NamedShardings, the compile seam's names) around stand-in
        math that accepts a lane where the premask does AND the digest
        in that lane is one the sw provider accepted — so lanes staged
        to the wrong device read wrong. After prewarm() and one batch
        on each side of the pool's capacity the seam has named nothing
        but the programs a P-256 batch can need — the pool replicated
        on all eight devices, as the shardings stated to `aot` say."""
        import jax.numpy as jnp

        from fabric_tpu.ops import comb, p256, sha256
        faults.clear()
        keys17 = _KEYS + [_SW.key_gen(ECDSAKeyGenOpts(ephemeral=True))
                          for _ in range(15)]
        few, want_few = _corpus(64)
        many, want_many = _corpus(40, keys=keys17)
        accepted = jnp.asarray(sorted(
            int.from_bytes(hashlib.sha256(it.message).digest()[:4], "big")
            for it, ok in zip(few + many, want_few + want_many) if ok),
            dtype=jnp.uint32)

        def verdict(words, premask):
            return premask & jnp.isin(words[:, 0], accepted)
        monkeypatch.setattr(
            comb, "comb_verify_with_tables",
            lambda words, key_idx, q_flat, r, rpn, w, premask, g16=None,
            q16=False: verdict(words, premask))
        monkeypatch.setattr(
            p256, "verify_core",
            lambda words, qx, qy, r, rpn, w, premask:
            verdict(words, premask))
        monkeypatch.setattr(
            sha256, "sha256_blocks",
            lambda blocks, nblocks: jnp.zeros((blocks.shape[0], 8),
                                              jnp.uint32))

        prov = TPUProvider(min_batch=16, use_g16=False, mesh=mesh8,
                           max_keys=16)
        prov.prewarm(buckets=(len(few),), bounded=True)
        assert prov.verify_batch(few) == want_few == \
            _SW.verify_batch(few)
        assert prov.verify_batch(many) == want_many == \
            _SW.verify_batch(many)
        assert any(want_many) and not all(want_many)
        st = prov.stats
        assert (st["comb_batches"], st["ladder_batches"]) == (1, 1)
        assert st["shard_dispatches"] >= 1      # the comb side books them
        assert st["sw_fallbacks"] == st["degraded_batches"] == 0
        assert st["host_hashed_lanes"] == sum(want_few) + sum(want_many)
        assert st["key_slots_resident"] == len(_KEYS)
        assert len(prov._pool.addressable_shards) == 8
        assert prov._pool.sharding.is_fully_replicated
        # prewarm's executables served the dispatches: nothing was
        # compiled for a shape or a sharding it had not stated
        assert [e["kind"] for e in prov.device_cost.events
                if not e["aot"]] == ["ladder"]
        kinds = {e["kind"] for e in prov.device_cost.events}
        assert {"qtab", "pool_write", "comb_digest", "ladder"} <= kinds
        assert kinds <= {"qtab", "qtab16", "pool_write", "comb_digest",
                         "comb", "ladder"}


class TestDevicesKnob:
    def test_default_is_all_local_devices(self):
        prov = factory.new_bccsp(factory.FactoryOpts.from_config(
            {"Default": "TPU"}))
        assert prov._mesh is not None
        assert prov._mesh.size == len(jax.devices())
        assert prov.stats["shard_devices"] == len(jax.devices())

    def test_devices_one_pins_the_single_device_path(self):
        """Devices: 1 must be the pre-mesh path bit for bit: no mesh
        object at all, so every dispatch takes exactly the code the
        single-chip provider always took."""
        prov = factory.new_bccsp(factory.FactoryOpts.from_config(
            {"Default": "TPU", "TPU": {"Devices": 1}}))
        assert prov._mesh is None
        assert prov.stats["shard_devices"] == 1

    def test_devices_n_uses_first_n(self):
        prov = factory.new_bccsp(factory.FactoryOpts.from_config(
            {"Default": "TPU", "TPU": {"Devices": 4}}))
        assert prov._mesh is not None and prov._mesh.size == 4

    def test_devices_over_ask_clamps_to_available(self, caplog):
        """A stale `Devices: N` on a smaller rig serves on every
        device there IS (with a warning) — degrading to ONE device
        would silently cost ~N x the configured throughput."""
        import logging
        with caplog.at_level(logging.WARNING, logger="bccsp.factory"):
            prov = factory.new_bccsp(factory.FactoryOpts.from_config(
                {"Default": "TPU", "TPU": {"Devices": 999}}))
        assert prov._mesh is not None
        assert prov._mesh.size == len(jax.devices())
        assert any("clamping" in r.message for r in caplog.records)

    def test_devices_one_verdicts_match_premesh_provider(self):
        """A factory-built Devices:1 provider takes the identical code
        path (and produces identical bitmaps) as a directly-built
        pre-mesh provider."""
        faults.clear()
        premesh, _ = _stubbed_provider(pipeline_chunk=SPAN8)
        one = factory.new_bccsp(factory.FactoryOpts.from_config(
            {"Default": "TPU",
             "TPU": {"Devices": 1, "MinBatch": 1, "UseG16": False,
                     "PipelineChunk": SPAN8}}))
        assert one._mesh is None
        # same recorder stubs on the factory-built provider
        stub_src, _ = _stubbed_provider(pipeline_chunk=SPAN8)
        one._qtab_fn = stub_src._qtab_fn
        one._comb_pipeline_digest = stub_src._comb_pipeline_digest
        one._pipeline = stub_src._pipeline
        items, expected = _corpus(1500)
        assert premesh.verify_batch(items) == \
            one.verify_batch(items) == expected
        assert one.stats["pipeline_chunks"] == \
            premesh.stats["pipeline_chunks"]


class TestShardedFaults:
    def test_dispatch_fault_falls_back_bit_identical(self, mesh8):
        """tpu.dispatch armed: the sharded dispatch fires the SAME
        per-dispatch fault point, the breaker path serves sw with
        identical verdicts, and the next batch rides the sharded
        pipeline again."""
        faults.clear()
        faults.arm("tpu.dispatch", mode="error", count=1)
        try:
            sharded, _ = _stubbed_provider(mesh=mesh8,
                                           pipeline_chunk=SPAN8)
            items, expected = _corpus(1100)
            assert sharded.verify_batch(items) == expected
            assert sharded.stats["sw_fallbacks"] == 1
            assert sharded.stats["pipeline_batches"] == 0
            assert sharded.verify_batch(items) == expected
            assert sharded.stats["pipeline_batches"] == 1
        finally:
            faults.clear()


class TestShardObservability:
    def test_shard_gauges_published(self, mesh8):
        """bccsp_shard_devices/skew and the per-device
        transfer_s/lanes series render on /metrics with their
        canonical help text and a device label."""
        from fabric_tpu.common import metrics as m
        from fabric_tpu.common import profiling

        faults.clear()
        sharded, _ = _stubbed_provider(mesh=mesh8,
                                       pipeline_chunk=SPAN8)
        items, _ = _corpus(2048)
        sharded.verify_batch(items)
        provider = m.PrometheusProvider()
        t = profiling.publish_provider_stats(provider, sharded,
                                             poll_s=0.01)
        assert t is not None
        import time
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            text = provider.render()
            if 'bccsp_shard_lanes{device="7"}' in text:
                break
            time.sleep(0.02)
        text = provider.render()
        assert "bccsp_shard_devices 8" in text
        assert 'bccsp_shard_transfer_s{device="0"}' in text
        assert 'bccsp_shard_lanes{device="7"} 128' in text
        assert "bccsp_shard_skew_s" in text
        assert "round-robin span feeder" in text


class TestMultiProcessCPUMesh:
    def test_sharded_provider_in_fresh_forced_mesh_process(self,
                                                           tmp_path):
        """The satellite's multi-process case: a CHILD process forces
        its own 8-device CPU platform (XLA_FLAGS, not the conftest
        in-process mesh), builds factory providers at Devices=all and
        Devices=1, and reports (a) provider-seam verdict parity on a
        mixed corpus through the sharded staging (recorder-stub
        kernels — the real comb compile is minutes on CPU) and (b) a
        REAL sharded XLA computation: the device SHA-256 stage under
        batch sharding, bit-exact vs hashlib."""
        child = tmp_path / "shard_child.py"
        child.write_text(_CHILD_SRC)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=8")
        env["XLA_FLAGS"] = " ".join(flags)
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH",
                                                        "")
        p = subprocess.run([sys.executable, str(child)], env=env,
                           cwd=repo, capture_output=True, text=True,
                           timeout=420)
        assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
        res = json.loads(p.stdout.strip().splitlines()[-1])
        assert res["devices"] == 8
        assert res["mesh_all"] == 8
        assert res["mesh_one"] is None
        assert res["parity"] is True
        assert res["expected_mixed"] is True
        assert res["sha_ok"] is True
        if not os.environ.get("FTPU_FAULTS"):
            # chaos runs arm tpu.dispatch in the child's env too: the
            # faulted dispatch serves sw (parity above still binds),
            # so only fault-free runs can pin the dispatch count
            assert res["shard_dispatches"] >= 1


_CHILD_SRC = '''
import json
import hashlib
import os

import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, factory, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.ops import sha256
from fabric_tpu.parallel import BATCH_AXIS, batch_mesh

res = {"devices": len(jax.devices())}
SLAB_ROWS = 8

def stub(tpu):
    def fake_qtab_fn():
        return lambda qx, qy: np.zeros((SLAB_ROWS, 3, 20),
                                           dtype=np.int32)
    def fake_pipeline_digest():
        def run(key_idx, q_flat, g16, r8, rpn8, w8, premask, digests):
            return np.asarray(premask)
        return run
    def fake_ladder():
        def run(blocks, nblocks, qx, qy, r, rpn, w, premask, digests,
                has_digest):
            return np.asarray(premask)
        return run
    tpu._slab_rows = lambda: SLAB_ROWS
    tpu._qtab_fn = fake_qtab_fn
    tpu._comb_pipeline_digest = fake_pipeline_digest
    tpu._pipeline = fake_ladder
    return tpu

sw = SWProvider()
keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(2)]
items, expected = [], []
for i in range(96):
    k = keys[i % 2]
    m = f"mp shard {i}".encode()
    sig = sw.sign(k, hashlib.sha256(m).digest())
    if i % 3 == 2:
        r, s = utils.unmarshal_signature(sig)
        sig = utils.marshal_signature(r, utils.P256_N - s)
        expected.append(False)
    else:
        expected.append(True)
    items.append(VerifyItem(key=k.public_key(), signature=sig,
                            message=m))

alldev = stub(factory.new_bccsp(factory.FactoryOpts.from_config(
    {"Default": "TPU",
     "TPU": {"MinBatch": 1, "UseG16": False, "PipelineChunk": 0}})))
onedev = stub(factory.new_bccsp(factory.FactoryOpts.from_config(
    {"Default": "TPU",
     "TPU": {"Devices": 1, "MinBatch": 1, "UseG16": False,
             "PipelineChunk": 0}})))
res["mesh_all"] = alldev.stats["shard_devices"]
res["mesh_one"] = (onedev._mesh.size if onedev._mesh is not None
                   else None)
out_all = alldev.verify_batch(items)
out_one = onedev.verify_batch(items)
res["parity"] = out_all == out_one == expected
res["expected_mixed"] = (any(expected) and not all(expected))
res["shard_dispatches"] = alldev.stats["shard_dispatches"]

# real sharded XLA compute: device SHA-256 under batch sharding
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = batch_mesh(8)
msgs = [f"mp sha {i}".encode() * (1 + i % 3) for i in range(16)]
blocks, nblocks = sha256.pack_messages(msgs, 2)
s = NamedSharding(mesh, P(BATCH_AXIS))
fn = jax.jit(sha256.sha256_blocks, in_shardings=(s, s),
             out_shardings=s)
words = np.asarray(fn(jax.device_put(blocks, s),
                      jax.device_put(nblocks, s)))
res["sha_ok"] = bool(all(
    (np.frombuffer(hashlib.sha256(m).digest(),
                   dtype=">u4") == words[i]).all()
    for i, m in enumerate(msgs)))
print(json.dumps(res))
'''


class TestCompatDerivePrivateKey:
    """The multichip dry run (`__graft_entry__._dryrun_in_process`)
    signs its q16 oracle lanes with `ec.derive_private_key` through
    the compat seam — on wheel-free images the pure-python fallback
    must provide it (MULTICHIP regression: a direct `cryptography`
    import made the dry run rc=1 on this container)."""

    def test_scalar_one_is_generator_and_signs(self):
        from fabric_tpu.bccsp._crypto_compat import ec, hashes
        from fabric_tpu.ops import p256
        priv = ec.derive_private_key(1, ec.SECP256R1())
        nums = priv.public_key().public_numbers()
        assert (nums.x, nums.y) == (p256.GX, p256.GY)
        msg = b"compat derive"
        der = priv.sign(msg, ec.ECDSA(hashes.SHA256()))
        priv.public_key().verify(der, msg, ec.ECDSA(hashes.SHA256()))

    def test_out_of_range_scalar_rejected(self):
        from fabric_tpu.bccsp._crypto_compat import ec
        from fabric_tpu.bccsp import utils
        with pytest.raises(ValueError):
            ec.derive_private_key(0, ec.SECP256R1())
        with pytest.raises(ValueError):
            ec.derive_private_key(utils.P256_N, ec.SECP256R1())


@pytest.mark.slow
class TestShardedRealKernel:
    def test_real_comb_parity_sharded_vs_oracle(self, mesh8):
        """Full provider, REAL q8 comb kernel under shard_map on the
        8-device CPU mesh: verdicts bit-identical to the sw oracle on
        a mixed 64-lane batch. Minutes of XLA compile — slow suite
        only; tier-1 covers the same plumbing with recorder stubs."""
        faults.clear()
        prov = TPUProvider(min_batch=16, use_g16=False, mesh=mesh8,
                           pipeline_chunk=0, hash_on_host=True)
        items, expected = _corpus(64)
        assert prov.verify_batch(items) == expected == \
            _SW.verify_batch(items)
        assert prov.stats["comb_batches"] == 1
        assert prov.stats["shard_dispatches"] >= 1
