"""A restarted provider loads its verify programs, it does not trace
them: `TPUProvider.prewarm` through the store of compiled executables
(common/execstore.py), with the provider's REAL programs — `qtab`,
the donating `pool_write` and the 74,000-operation `comb_digest` at the
CPU bucket of 16 lanes.

One writer provider fills a store under a temporary directory (a cold
compile, about a minute on the CPU backend: XLA:CPU cannot serialize
an executable it loaded from JAX's persistent cache, so that cache is
off here); every test then starts a provider over that store, as a
restarted peer would. In a file of its own so that it is a load of its
own under `--dist loadfile`.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

import jax

from fabric_tpu.bccsp import ECDSAKeyGenOpts, VerifyItem, utils
from fabric_tpu.bccsp.sw import SWProvider
from fabric_tpu.bccsp.tpu import TPUProvider, host_prep_scalars
from fabric_tpu.common.execstore import ExecutableStore

LANES = 16          # TPUProvider(min_batch=4)'s bucket for the corpus
FALLBACK_COUNTERS = ("sw_fallbacks", "host_hash_fallbacks",
                     "degraded_batches", "ladder_batches",
                     "breaker_trips", "compile_failures")


@pytest.fixture(scope="module")
def fresh_compiles():
    from jax.experimental.compilation_cache import (
        compilation_cache as cc,
    )
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _counting(prov, traces):
    """Count every trace of a program `prov` builds: `_jit` wraps the
    function it is given, and that wrapper runs only while JAX traces
    it."""
    real = prov._jit

    def jit(kind, fn, *args, **kw):
        def counted(*a, **k):
            traces.append(kind)
            return fn(*a, **k)
        return real(kind, counted, *args, **kw)
    prov._jit = jit


def _corpus():
    """16 lanes over 3 keys: accepts (message and digest lanes) and one
    lane of each way to be rejected; the sw provider's verdicts."""
    sw = SWProvider()
    keys = [sw.key_gen(ECDSAKeyGenOpts(ephemeral=True)) for _ in range(3)]
    items = []
    for i in range(LANES):
        k = keys[i % 3]
        pub = k.public_key()
        m = f"lane {i}".encode()
        digest = hashlib.sha256(m).digest()
        sig = sw.sign(k, digest)
        kind = {1: "tampered", 4: "wrong_key", 6: "high_s",
                9: "malformed_der", 11: "r_out_of_range",
                12: "digest", 14: "tampered_digest"}.get(i, "message")
        if kind == "tampered":
            m += b"!"
        elif kind == "wrong_key":
            pub = keys[(i + 1) % 3].public_key()
        elif kind == "high_s":
            r, s = utils.unmarshal_signature(sig)
            sig = utils.marshal_signature(r, utils.P256_N - s)
        elif kind == "malformed_der":
            sig = sig[:-2]
        elif kind == "r_out_of_range":
            sig = utils.marshal_signature(utils.P256_N, 5)
        if kind == "digest":
            items.append(VerifyItem(key=pub, signature=sig, digest=digest))
        elif kind == "tampered_digest":
            items.append(VerifyItem(key=pub, signature=sig,
                                    digest=digest[::-1]))
        else:
            items.append(VerifyItem(key=pub, signature=sig, message=m))
    want = sw.verify_batch(items)
    assert want.count(False) == 6 and want.count(True) == LANES - 6
    return items, want


def _prepared_args(items):
    """`items` as native block prep hands them to `verify_prepared`."""
    n = len(items)
    der_ok = np.zeros(n, dtype=bool)
    r, rpn, w = (np.zeros((n, 32), dtype=np.uint8) for _ in range(3))
    keys, slot, key_idx = [], {}, np.zeros(n, dtype=np.int32)
    digests = np.zeros((n, 32), dtype=np.uint8)
    for i, it in enumerate(items):
        prep = host_prep_scalars(it.key, it.signature)
        if prep is not None:
            der_ok[i] = True
            r[i], rpn[i], w[i] = (np.frombuffer(b, np.uint8)
                                  for b in prep)
        if (it.key.x, it.key.y) not in slot:
            slot[it.key.x, it.key.y] = len(keys)
            keys.append(it.key)
        key_idx[i] = slot[it.key.x, it.key.y]
        digests[i] = np.frombuffer(
            it.digest or hashlib.sha256(it.message).digest(), np.uint8)
    return (digests, r, rpn, w, der_ok, key_idx, keys,
            lambda i: items[i].signature)


@pytest.fixture(scope="module")
def filled(tmp_path_factory, fresh_compiles):
    """(store directory, corpus, the writer's verdicts and traces)."""
    directory = str(tmp_path_factory.mktemp("exe"))
    items, want = _corpus()
    prov = TPUProvider(min_batch=4)
    prov.device_cost.store = ExecutableStore(directory)
    traces: list = []
    _counting(prov, traces)
    prov.prewarm(buckets=(LANES,), bounded=True)
    got = prov.verify_batch(items)
    return directory, items, want, got, traces, dict(prov.stats)


def test_the_writer_traces_once_a_program_and_writes_each(filled):
    _, _, want, got, traces, stats = filled
    assert got == want
    # prewarm traced each program once; the first batch traced nothing
    assert sorted(traces) == ["comb_digest", "pool_write", "qtab"]
    assert (stats["executable_store_misses"],
            stats["executable_store_hits"],
            stats["executable_store_errors"]) == (3, 0, 0)
    assert stats["compile_total"] == 3


@pytest.mark.parametrize("entry", ["verify_batch", "verify_prepared"])
def test_restarted_provider_traces_nothing(filled, entry):
    """The second provider's prewarm and first batch: nothing traced,
    every program from the store, the sw provider's verdicts through
    the loaded executables, and no cold compile."""
    directory, items, want, _, _, _ = filled
    prov = TPUProvider(min_batch=4)
    prov.device_cost.store = ExecutableStore(directory)
    traces: list = []
    _counting(prov, traces)
    prov.prewarm(buckets=(LANES,), bounded=True)
    if entry == "verify_prepared":
        got = prov.verify_prepared(*_prepared_args(items))
    else:
        got = prov.verify_batch(items)
    assert got == want == SWProvider().verify_batch(items)
    assert traces == []
    st = prov.stats
    assert (st["executable_store_hits"], st["executable_store_misses"],
            st["executable_store_errors"]) == (3, 0, 0)
    assert st["compile_total"] == 3 and st["compile_cold_total"] == 0
    assert st["comb_batches"] == 1
    # the loaded `pool_write` still donates: three keys admitted, one
    # pool, and the sw provider's verdicts read through it
    assert st["key_slot_builds"] == st["key_slots_resident"] == 3
    assert [st[c] for c in FALLBACK_COUNTERS] == [0] * 6
    assert {(e["kind"], e["source"], e["aot"])
            for e in prov.device_cost.events} == {
        ("qtab", "store", True), ("pool_write", "store", True),
        ("comb_digest", "store", True)}


def test_a_pool_of_another_capacity_misses(filled):
    """The number of keys in a batch is no part of a program any more,
    but the pool's capacity is its table argument's shape: the store
    must not serve the 32-slot pool's executable to a provider whose
    MaxKeys gives it 16 (requested only, not compiled: the request's
    entry is simply not there)."""
    directory = filled[0]
    store = ExecutableStore(directory)
    sd = jax.ShapeDtypeStruct
    for slots, hit in ((32, True), (16, False)):
        prov = TPUProvider(min_batch=4, max_keys=slots)
        assert prov._key_capacity() == slots
        rows = prov._slab_rows()
        fn = prov._pool_write_fn()
        shapes = (sd((slots * rows, 3, 20), np.int32),
                  sd((rows, 3, 20), np.int32), sd((), np.int32))
        req = store.request("pool_write", fn._params, shapes, (),
                            jax.devices()[:1])
        assert (store.load(req) is not None) == hit
