"""The store of compiled executables behind the compile seam's AOT
path (common/execstore.py, `InstrumentedJit.aot`).

Real `jax.jit` on programs of a line or two (milliseconds on the CPU
backend), each test against a store of its own under `tmp_path`: on
the CPU backend a provider resolves no store by itself
(`ExecutableStore.beside_compile_cache`). The provider's own programs
go through the same seam in tests/test_execstore_provider.py.
"""

from __future__ import annotations

import glob
import os
import stat
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fabric_tpu.common import execstore, tracing
from fabric_tpu.common.devicecost import CompileRecorder
from fabric_tpu.common.execstore import ExecutableStore


@pytest.fixture(autouse=True, scope="module")
def fresh_compiles():
    """conftest turns JAX's persistent compile cache on, and XLA:CPU
    cannot serialize an executable it LOADED from that cache (the
    entry then loads and fails at its first execution: "Function ...
    not found"): these tests compile their few lines fresh."""
    from jax.experimental.compilation_cache import (
        compilation_cache as cc,
    )
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def sd(shape, dtype=np.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _program(calls, scale=3):
    """`x * scale + y`; `calls` grows by one each time it is traced."""
    def probe(x, y):
        calls.append("trace")
        return x * scale + y
    return probe


def _seam(tmp_path, calls=None, params=None, scale=3):
    """(recorder with a store of its own, instrumented `probe`)."""
    rec = CompileRecorder(cache_dir=str(tmp_path), analysis=False,
                          store=ExecutableStore(str(tmp_path / "exe")))
    calls = [] if calls is None else calls
    fn = rec.wrap("probe", jax.jit(_program(calls, scale)),
                  params={"scale": scale} if params is None else params)
    return rec, fn


def _entries(tmp_path):
    return sorted(glob.glob(str(tmp_path / "exe" / "*.exe")))


X = np.arange(8, dtype=np.int32)
SHAPES = (sd((8,)), sd((8,)))


class TestAotSeam:
    def test_miss_writes_then_a_second_process_loads_untraced(
            self, tmp_path):
        calls = []
        rec, fn = _seam(tmp_path, calls)
        fn.aot(*SHAPES)
        assert calls == ["trace"]
        assert (rec.stats["executable_store_misses"],
                rec.stats["executable_store_hits"],
                rec.stats["executable_store_errors"]) == (1, 0, 0)
        (path,) = _entries(tmp_path)
        assert stat.S_IMODE(os.stat(path).st_mode) == 0o600
        assert os.path.basename(path).startswith("probe-")
        # the first dispatch calls the registered executable: no trace
        assert np.asarray(fn(X, X)).tolist() == (X * 4).tolist()
        assert calls == ["trace"] and rec.stats["compile_total"] == 1

        # "restart": a new recorder and a new jit over the same store
        calls2 = []
        rec2, fn2 = _seam(tmp_path, calls2)
        fn2.aot(*SHAPES)
        assert np.asarray(fn2(X, X)).tolist() == (X * 4).tolist()
        assert calls2 == []
        assert (rec2.stats["executable_store_hits"],
                rec2.stats["executable_store_misses"],
                rec2.stats["executable_store_errors"]) == (1, 0, 0)
        # a load from the store is no cold compile
        assert rec2.stats["compile_total"] == 1
        assert rec2.stats["compile_cache_hits"] == 1
        assert rec2.stats["compile_cold_total"] == 0
        (ev,) = rec2.events
        assert ev["source"] == "store" and ev["aot"] and ev["cache_hit"]

    def test_span_says_where_the_executable_came_from(self, tmp_path):
        tracing.configure(enabled=True, sample_every=1)
        tracing.reset()
        try:
            for want in ("miss", "store"):
                _, fn = _seam(tmp_path)
                fn.aot(*SHAPES)
            spans = [e[8] for e in tracing.snapshot()
                     if e[1] == "tpu.compile"]
        finally:
            tracing.reset()
        assert [s["aot"] for s in spans] == [True, True]
        first, second = spans
        assert first["source"] in ("cache", "cold")
        assert first["lower_s"] > 0 and first["store_bytes"] > 0
        assert second["source"] == "store" and second["lower_s"] == 0.0
        assert second["load_s"] > 0

    def test_other_shapes_still_go_through_jit(self, tmp_path):
        calls = []
        rec, fn = _seam(tmp_path, calls)
        fn.aot(*SHAPES)
        y = np.arange(4, dtype=np.int32)
        assert np.asarray(fn(y, y)).tolist() == (y * 4).tolist()
        assert calls == ["trace", "trace"]
        assert rec.stats["compile_total"] == 2
        assert rec.events[-1]["aot"] is False

    def test_registered_executable_is_strict_about_shardings(
            self, tmp_path):
        """What `aot` was told is what the dispatch must bring: the
        same shape on other devices raises, it does not retrace."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("batch",))
        lane = NamedSharding(mesh, P("batch"))
        _, fn = _seam(tmp_path)
        fn.aot(jax.ShapeDtypeStruct((8,), np.int32, sharding=lane),
               jax.ShapeDtypeStruct((8,), np.int32, sharding=lane))
        xs = jax.device_put(X, lane)
        assert np.asarray(fn(xs, xs)).tolist() == (X * 4).tolist()
        with pytest.raises(ValueError):
            fn(jax.device_put(X, jax.devices()[3]), xs)

    def test_static_argument_is_keyed_by_value_and_not_passed(
            self, tmp_path):
        def scaled(x, k):
            return x * k
        for round_ in range(2):
            rec = CompileRecorder(
                cache_dir=str(tmp_path), analysis=False,
                store=ExecutableStore(str(tmp_path / "exe")))
            fn = rec.wrap("scaled", jax.jit(scaled, static_argnums=1),
                          static_argnums=1)
            fn.aot(sd((8,)), 5)
            fn.aot(sd((8,)), 7)
            assert np.asarray(fn(X, 5)).tolist() == (X * 5).tolist()
            assert np.asarray(fn(X, 7)).tolist() == (X * 7).tolist()
            assert rec.stats["compile_total"] == 2
            key = "hits" if round_ else "misses"
            assert rec.stats["executable_store_" + key] == 2
        assert len(_entries(tmp_path)) == 2

    def test_second_request_of_a_ready_shape_is_free(self, tmp_path):
        rec, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        fn.aot(*SHAPES)
        assert rec.stats["compile_total"] == 1

    def test_without_a_store_the_executable_is_still_registered(
            self, tmp_path):
        calls = []
        rec = CompileRecorder(cache_dir=None, analysis=False)
        assert rec.store is None       # no cache directory: no store
        fn = rec.wrap("probe", jax.jit(_program(calls)))
        fn.aot(*SHAPES)
        fn(X, X)
        assert calls == ["trace"]
        assert rec.stats["executable_store_misses"] == 0
        assert rec.events[0]["aot"] and rec.stats["compile_total"] == 1

    def test_cpu_backend_resolves_no_store_by_itself(self, tmp_path):
        assert jax.default_backend() == "cpu"
        assert ExecutableStore.beside_compile_cache(str(tmp_path)) is None
        assert ExecutableStore.beside_compile_cache(None) is None
        rec = CompileRecorder(cache_dir=str(tmp_path))
        assert rec.store is None

    def test_failed_write_only_logs_and_counts(self, tmp_path,
                                               monkeypatch):
        rec, fn = _seam(tmp_path)

        def refuse(*_a, **_kw):
            raise OSError("disk full")
        monkeypatch.setattr(execstore.os, "replace", refuse)
        fn.aot(*SHAPES)
        assert np.asarray(fn(X, X)).tolist() == (X * 4).tolist()
        assert rec.stats["executable_store_errors"] == 1
        assert rec.stats["executable_store_misses"] == 1
        assert _entries(tmp_path) == []
        assert glob.glob(str(tmp_path / "exe" / "*.tmp")) == []


def _request(store, **over):
    args = dict(kind="comb_digest",
                params={"K": 4, "q16": True, "g16": True, "mesh": None},
                shapes=(sd((2048,)), sd((2048, 32), np.uint8), 4),
                static=(2,), devices=jax.devices()[:1])
    args.update(over)
    return store.request(args["kind"], args["params"], args["shapes"],
                         args["static"], args["devices"])


class TestKey:
    """The key changes with each of its inputs, and with nothing else."""

    def test_same_request_same_entry_whatever_the_directory(
            self, tmp_path):
        a = ExecutableStore(str(tmp_path / "a"))
        b = ExecutableStore(str(tmp_path / "b"))
        assert os.path.basename(a.entry_path(_request(a))) == \
            os.path.basename(b.entry_path(_request(b)))

    @pytest.mark.parametrize("over", [
        {"kind": "comb"},
        {"params": {"K": 8, "q16": True, "g16": True, "mesh": None}},
        {"params": {"K": 4, "q16": False, "g16": True, "mesh": None}},
        {"params": {"K": 4, "q16": True, "g16": False, "mesh": None}},
        {"params": {"K": 4, "q16": True, "g16": True,
                    "mesh": (("batch",), [0, 1])}},
        {"shapes": (sd((4096,)), sd((4096, 32), np.uint8), 4)},
        {"shapes": (sd((2048,)), sd((2048, 32), np.int8), 4)},
        {"shapes": (sd((2048,)), sd((2048, 32), np.uint8), 8)},
        {"devices": jax.devices()[1:2]},
        {"devices": jax.devices()[:2]},
    ], ids=["kind", "K", "q16", "g16", "mesh", "lanes", "dtype",
            "static_value", "device", "device_count"])
    def test_slot_changes_with(self, tmp_path, over):
        store = ExecutableStore(str(tmp_path))
        base, other = _request(store), _request(store, **over)
        assert other["slot_digest"] != base["slot_digest"]
        assert other["env_digest"] == base["env_digest"]
        assert store.entry_path(other) != store.entry_path(base)

    def test_slot_changes_with_the_sharding(self, tmp_path):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("batch",))
        store = ExecutableStore(str(tmp_path))

        def req(spec):
            s = NamedSharding(mesh, spec)
            return _request(store, static=(), devices=jax.devices()[:2],
                            shapes=(jax.ShapeDtypeStruct(
                                (2048,), np.int32, sharding=s),))
        assert req(P("batch"))["slot_digest"] != req(P())["slot_digest"]

    @pytest.mark.parametrize("what", [
        "sources", "jax", "jaxlib", "x64", "XLA_FLAGS",
        "LIBTPU_INIT_ARGS"])
    def test_env_changes_with(self, tmp_path, monkeypatch, request,
                              what):
        base = _request(ExecutableStore(str(tmp_path)))
        if what == "sources":
            monkeypatch.setattr(execstore, "source_digest",
                                lambda: "0" * 64)
        elif what == "jax":
            monkeypatch.setattr(jax, "__version__", "0.0.1")
        elif what == "jaxlib":
            import jaxlib
            monkeypatch.setattr(jaxlib, "__version__", "0.0.1")
        elif what == "x64":
            was = jax.config.jax_enable_x64
            request.addfinalizer(
                lambda: jax.config.update("jax_enable_x64", was))
            jax.config.update("jax_enable_x64", not was)
        else:
            monkeypatch.setenv(what, "--some_flag=1")
        other = _request(ExecutableStore(str(tmp_path)))
        assert other["env_digest"] != base["env_digest"]
        assert other["slot_digest"] == base["slot_digest"]

    def test_source_digest_reads_every_listed_module(self, tmp_path):
        for rel in ("ops/a.py", "ops/b.py", "bccsp/tpu.py", "x/c.py"):
            p = tmp_path / rel
            p.parent.mkdir(exist_ok=True)
            p.write_text("one")
        pats = ("ops/*.py", "bccsp/tpu.py")
        d0 = execstore.source_digest(str(tmp_path), pats)
        assert d0 == execstore.source_digest(str(tmp_path), pats)
        (tmp_path / "x/c.py").write_text("two")       # not listed
        assert execstore.source_digest(str(tmp_path), pats) == d0
        (tmp_path / "ops/b.py").write_text("two")
        d1 = execstore.source_digest(str(tmp_path), pats)
        assert d1 != d0
        (tmp_path / "ops/c.py").write_text("")         # a new module
        assert execstore.source_digest(str(tmp_path), pats) != d1
        # the real list names files that are there
        for pattern in execstore.SOURCES:
            assert glob.glob(os.path.join(
                os.path.dirname(os.path.dirname(execstore.__file__)),
                pattern)), pattern

    def test_changed_key_misses_and_replaces_the_older_entry(
            self, tmp_path, monkeypatch):
        calls = []
        rec, fn = _seam(tmp_path, calls)
        fn.aot(*SHAPES)
        (old,) = _entries(tmp_path)
        # the sources changed: same request, another env
        monkeypatch.setattr(execstore, "source_digest", lambda: "1" * 64)
        rec2, fn2 = _seam(tmp_path, calls)
        fn2.aot(*SHAPES)
        assert calls == ["trace", "trace"]
        assert rec2.stats["executable_store_misses"] == 1
        assert rec2.stats["executable_store_hits"] == 0
        (new,) = _entries(tmp_path)
        assert new != old
        # another builder parameter: another slot, both entries stay
        rec3, fn3 = _seam(tmp_path, calls, scale=5)
        fn3.aot(*SHAPES)
        assert rec3.stats["executable_store_misses"] == 1
        assert len(_entries(tmp_path)) == 2
        assert np.asarray(fn3(X, X)).tolist() == (X * 6).tolist()


def _truncate(raw):
    return raw[:len(raw) // 2]


def _flip_a_bit(raw):
    i = len(raw) - 100
    return raw[:i] + bytes([raw[i] ^ 0x10]) + raw[i + 1:]


def _no_magic(raw):
    return b"garbage\n" + raw


class TestDamagedEntries:
    @pytest.mark.parametrize("damage", [_truncate, _flip_a_bit, _no_magic,
                                        lambda raw: b""],
                             ids=["truncated", "bit_flipped", "no_magic",
                                  "empty"])
    def test_counted_served_by_compiling_and_replaced(self, tmp_path,
                                                      damage):
        _, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        (path,) = _entries(tmp_path)
        with open(path, "rb") as f:
            whole = f.read()
        with open(path, "wb") as f:
            f.write(damage(whole))

        calls = []
        rec, fn = _seam(tmp_path, calls)
        fn.aot(*SHAPES)
        assert np.asarray(fn(X, X)).tolist() == (X * 4).tolist()
        assert calls == ["trace"]           # lower().compile()
        assert (rec.stats["executable_store_errors"],
                rec.stats["executable_store_hits"],
                rec.stats["executable_store_misses"]) == (1, 0, 0)
        assert rec.stats["compile_total"] == 1
        assert rec.stats["compile_failures"] == 0
        # replaced: the next process hits
        assert _entries(tmp_path) == [path]
        rec, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        assert rec.stats["executable_store_hits"] == 1
        assert rec.stats["executable_store_errors"] == 0

    @pytest.mark.parametrize("writer_has_zstd", [True, False])
    def test_either_codec_round_trips_and_a_missing_one_is_an_error(
            self, tmp_path, monkeypatch, writer_has_zstd):
        if execstore.zstandard is None:
            pytest.skip("zstandard is not installed here")
        if not writer_has_zstd:
            monkeypatch.setattr(execstore, "zstandard", None)
        _, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        rec, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        assert rec.stats["executable_store_hits"] == 1
        # a reader without zstandard: zlib entries load, zstd ones are
        # counted, compiled and replaced by entries it can read
        monkeypatch.setattr(execstore, "zstandard", None)
        rec, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        assert (rec.stats["executable_store_hits"],
                rec.stats["executable_store_errors"]) == \
            ((0, 1) if writer_has_zstd else (1, 0))
        assert np.asarray(fn(X, X)).tolist() == (X * 4).tolist()
        rec, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        assert rec.stats["executable_store_hits"] == 1

    def test_entry_of_another_request_under_this_name(self, tmp_path):
        """A whole, well-formed entry in the wrong place: a wider
        program's executable copied over this request's file."""
        store = ExecutableStore(str(tmp_path / "exe"))
        _, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        fn.aot(sd((16,)), sd((16,)))
        narrow, wide = (store.entry_path(store.request(
            "probe", {"scale": 3}, s, (), jax.devices()[:1]))
            for s in (SHAPES, (sd((16,)), sd((16,)))))
        assert sorted([narrow, wide]) == _entries(tmp_path)
        with open(wide, "rb") as f, open(narrow, "wb") as g:
            g.write(f.read())
        rec, fn = _seam(tmp_path)
        fn.aot(*SHAPES)
        assert rec.stats["executable_store_errors"] == 1
        assert np.asarray(fn(X, X)).tolist() == (X * 4).tolist()

    def test_wrong_shape_executable_is_refused(self, tmp_path):
        """The last check: an executable whose input avals are not the
        shapes asked for is never registered."""
        compiled = jax.jit(lambda x, y: x + y).lower(
            sd((16,)), sd((16,))).compile()
        with pytest.raises(execstore.StoreError, match="asked"):
            execstore.check_avals(compiled, SHAPES, ())
        execstore.check_avals(compiled, (sd((16,)), sd((16,))), ())
        with pytest.raises(execstore.StoreError):
            execstore.check_avals(
                compiled, (sd((16,), np.uint32), sd((16,))), ())


class TestConcurrentWriters:
    def test_two_writers_of_one_entry_leave_one_whole_file(
            self, tmp_path):
        """Several xdist workers (or a peer and its compiling child)
        can write one entry at once: every reader, then and after,
        sees a whole file."""
        store = ExecutableStore(str(tmp_path / "exe"))
        compiled = jax.jit(_program([])).lower(*SHAPES).compile()
        req = store.request("probe", {"scale": 3}, SHAPES, (),
                            jax.devices()[:1])
        start = threading.Barrier(8)
        failures: list = []
        # the writers race, the readers race the writers; two LOADS of
        # one program at once are not part of the claim (XLA:CPU's
        # loader registers a program's functions by name, and a
        # provider loads from one thread, prewarm's)
        one_load = threading.Lock()

        def work(k):
            try:
                start.wait(timeout=30)
                for _ in range(5):
                    if k % 2:
                        store.save(req, compiled)
                    else:
                        with one_load:
                            got = store.load(req)
                            if got is not None:
                                out = got(jnp.asarray(X), jnp.asarray(X))
                                assert np.asarray(out).tolist() == \
                                    (X * 4).tolist()
            except BaseException as e:      # noqa: BLE001 (reported)
                failures.append(repr(e))

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not [t for t in threads if t.is_alive()]
        assert failures == []
        assert _entries(tmp_path) == [store.entry_path(req)]
        assert glob.glob(str(tmp_path / "exe" / "*.tmp")) == []
        assert store.load(req) is not None
