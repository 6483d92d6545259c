"""A store of compiled executables behind the compile seam's AOT path.

JAX's persistent compile cache saves the COMPILE of a program a
restarted process has run before, but not the work of finding it: its
key is a hash of the lowered program, so every process traces the
program in Python and lowers it to StableHLO again only to learn which
executable to load unchanged — ~15 s of a validating peer's restart
for the 74,000-operation `comb_digest` pipeline (PERF.md, Findings
PR 33). This store keys a serialized executable by what DETERMINES the
program and can be read without tracing it, so `InstrumentedJit.aot`
(common/devicecost.py) loads it and the process never traces at all.

The key (`entry_path`) has two halves, both in the file's name:

  slot  what the caller asked for: the program's `kind`, the static
        parameters of the builder that made it (given by the call
        site, never guessed from shapes), the argument shapes, dtypes,
        shardings and static values, and the ids of the devices it
        runs on.
  env   what the same request would compile to HERE: a digest of the
        source bytes of every module the programs are built from
        (`SOURCES`), the jax and jaxlib versions, the backend's
        platform, `platform_version`, device kind and count, the JAX
        config flags that change lowering, `XLA_FLAGS` and
        `LIBTPU_INIT_ARGS`.

No path and no time is in either. A wrong hit means wrong verdicts, so
the key errs on the side of missing: any edit to a `SOURCES` file ages
every entry, and `save` drops a slot's entries under an older env.
What the key cannot see is a program changed with its sources
untouched — a test that monkeypatches a kernel — which is one reason
`beside_compile_cache` serves accelerator backends only (the other:
an XLA:CPU executable is built for the compiling machine's CPU
features, which the loader can only warn about). Tests drive a store
of their own against a temporary directory.

An entry is `MAGIC`, the sha256 of the body, and the body: one byte
naming the codec and the compressed pickle of the full key and the
executable as `jax.experimental.serialize_executable` wrote it (a TPU
executable of the `comb_digest` pipeline is hundreds of MB as
serialized and a fourteenth of that compressed: zstandard where it is
installed, as for JAX's own cache entries, else zlib). The digest is
checked BEFORE the body is decompressed or unpickled, the file is
written 0600 through a temporary name and `os.replace`, and the
directory is this process's own cache directory: unpickling runs code,
so the store reads only what this program wrote.
"""

from __future__ import annotations

import glob
import hashlib
import json
import logging
import os
import pickle
import tempfile
import zlib
from typing import Optional, Sequence

try:
    import zstandard
except ImportError:                 # zlib serves, more slowly
    zstandard = None

logger = logging.getLogger("common.execstore")

MAGIC = b"FTPU-EXE-1\n"
SUBDIR = "executables"
_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# every module a prewarmed program is built from, relative to the
# package: the kernels, the builders, the mesh and shard_map helpers,
# and the seam and store themselves (the entry's format)
SOURCES = ("ops/*.py", "bccsp/tpu.py", "parallel/mesh.py",
           "common/jaxenv.py", "common/devicecost.py",
           "common/execstore.py")
# JAX config flags that change what a program lowers to
_LOWERING_FLAGS = ("jax_enable_x64", "jax_default_matmul_precision",
                   "jax_numpy_dtype_promotion", "jax_default_prng_impl",
                   "jax_threefry_partitionable")


class StoreError(Exception):
    """An entry that is there but cannot be served: truncated,
    altered, written for another request, or refused by the runtime."""


def source_digest(root: str = _PKG, patterns=SOURCES) -> str:
    """sha256 over the names and bytes of the `SOURCES` files."""
    h = hashlib.sha256()
    for pattern in patterns:
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def environment(devices: Sequence) -> dict:
    """The env half of the key, spelled out (the entry records it)."""
    import jax
    import jaxlib

    client = devices[0].client
    return {
        "sources": source_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": client.platform,
        "platform_version": client.platform_version,
        "device_kind": devices[0].device_kind,
        "device_count": len(client.devices()),
        "process_count": jax.process_count(),
        "flags": {f: str(getattr(jax.config, f, None))
                  for f in _LOWERING_FLAGS},
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
    }


def describe(shapes: Sequence, static: Sequence[int] = ()) -> list:
    """The request's arguments as the key spells them: a static
    argument by its value, any other by shape, dtype and (where the
    caller gave one) sharding."""
    out = []
    for i, a in enumerate(shapes):
        if i in static:
            out.append(["static", repr(a)])
        else:
            out.append([list(a.shape), str(a.dtype),
                        repr(getattr(a, "sharding", None))])
    return out


def _compress(raw: bytes) -> bytes:
    if zstandard is not None:
        return b"z" + zstandard.ZstdCompressor(level=3).compress(raw)
    return b"d" + zlib.compress(raw, 1)


def _decompress(body) -> bytes:
    codec, data = bytes(body[:1]), body[1:]
    if codec == b"d":
        return zlib.decompress(data)
    if codec == b"z" and zstandard is not None:
        return zstandard.ZstdDecompressor().decompress(data)
    raise StoreError(f"codec {codec!r} cannot be read here")


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()


class ExecutableStore:
    """Serialized executables under one directory, one file an entry."""

    def __init__(self, directory: str):
        self.directory = directory
        self._env: dict = {}        # device ids -> (env, digest): once

    @classmethod
    def beside_compile_cache(cls, cache_dir: Optional[str]
                             ) -> Optional["ExecutableStore"]:
        """The store a provider uses: a sub-directory of the persistent
        compile cache's directory, on exactly when that cache is — and
        only on an accelerator backend (module docstring)."""
        if not cache_dir:
            return None
        import jax
        if jax.default_backend() == "cpu":
            return None
        return cls(os.path.join(cache_dir, SUBDIR))

    # -- the key --

    def _environment(self, devices) -> tuple:
        ids = tuple(d.id for d in devices)
        if ids not in self._env:
            env = environment(devices)
            self._env[ids] = (env, _digest(env))
        return self._env[ids]

    def request(self, kind: str, params: dict, shapes: Sequence,
                static: Sequence[int], devices: Sequence) -> dict:
        """One request: its whole key, spelled out (`slot`, `env` and
        their digests), and what `load` needs beside it."""
        env, env_digest = self._environment(devices)
        slot = {"kind": kind,
                "params": {k: repr(v) for k, v in params.items()},
                "args": describe(shapes, static),
                "devices": [d.id for d in devices]}
        return {"slot": slot, "env": env, "slot_digest": _digest(slot),
                "env_digest": env_digest, "shapes": shapes,
                "static": static, "devices": devices}

    def entry_path(self, req: dict) -> str:
        return os.path.join(
            self.directory, "%s-%s-%s.exe" % (
                req["slot"]["kind"], req["slot_digest"][:24],
                req["env_digest"][:24]))

    # -- read --

    def load(self, req: dict):
        """The request's executable, loaded onto its devices; None
        where the store holds none. Raises `StoreError` for an entry
        that is there and cannot be served."""
        from jax.experimental import serialize_executable as se

        path, devices = self.entry_path(req), req["devices"]
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            raise StoreError(f"{path}: {e}") from e
        head = len(MAGIC) + 65
        if not raw.startswith(MAGIC) or len(raw) < head:
            raise StoreError(f"{path}: not an entry of this store")
        want = raw[len(MAGIC):head - 1].decode("ascii", "replace")
        body = memoryview(raw)[head:]
        if hashlib.sha256(body).hexdigest() != want:
            raise StoreError(f"{path}: body does not match its sha256")
        try:
            entry = pickle.loads(_decompress(body))
            if entry["slot"] != req["slot"] or entry["env"] != req["env"]:
                raise StoreError(f"{path}: written for another request")
            compiled = se.deserialize_and_load(
                entry["executable"], entry["in_tree"], entry["out_tree"],
                backend=devices[0].client, execution_devices=devices)
        except StoreError:
            raise
        except Exception as e:      # noqa: BLE001 (any refusal = error)
            raise StoreError(f"{path}: {type(e).__name__}: {e}") from e
        check_avals(compiled, req["shapes"], req["static"], path)
        return compiled

    # -- write --

    def save(self, req: dict, compiled) -> int:
        """Write the request's entry (temporary file + `os.replace`,
        mode 0600) and drop the slot's entries under any other env.
        Returns the entry's size. Raises on any failure: the caller
        logs and counts."""
        from jax.experimental import serialize_executable as se

        executable, in_tree, out_tree = se.serialize(compiled)
        body = _compress(pickle.dumps({
            "slot": req["slot"], "env": req["env"],
            "executable": executable, "in_tree": in_tree,
            "out_tree": out_tree}, protocol=pickle.HIGHEST_PROTOCOL))
        path = self.entry_path(req)
        os.makedirs(self.directory, mode=0o700, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:      # mkstemp: mode 0600
                f.write(MAGIC)
                f.write(hashlib.sha256(body).hexdigest().encode()
                        + b"\n")
                f.write(body)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        stale = "%s-%s-*.exe" % (req["slot"]["kind"],
                                 req["slot_digest"][:24])
        for old in glob.glob(os.path.join(self.directory, stale)):
            if old != path:
                try:
                    os.remove(old)
                except OSError:
                    pass
        return len(MAGIC) + 65 + len(body)


def check_avals(compiled, shapes: Sequence, static: Sequence[int],
                what: str = "executable") -> None:
    """The executable's input avals must be the shapes asked for: a
    `Compiled` refuses anything else at every call, so a mismatch here
    would turn every dispatch of the shape into a failed one."""
    want = [(tuple(a.shape), str(a.dtype))
            for i, a in enumerate(shapes) if i not in static]
    have = [(tuple(a.shape), str(a.dtype))
            for a in compiled.in_avals[0]]
    if have != want:
        raise StoreError(f"{what}: compiled for {have}, asked {want}")
