"""JAX environment knobs shared by node startup, benchmarks, tools
and tests.

The reference pays its crypto setup cost per-signature at runtime; this
framework pays it once at XLA compile time, and the verify programs
take minutes to compile (tools/chip_compile.py prints the seconds per
program). A persistent compilation cache makes that a once-per-checkout
cost instead of once-per-process: a peer restart (crash recovery,
upgrade) must not stall block validation re-compiling a kernel that has
not changed.

ONE cache rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own
handling of it is the whole story and this module sets no directory;
otherwise the cache is ``<checkout>/.cache/xla`` (git-ignored) — the
same directory for nodes, bench, tools, tests and chip_smoke.py. The
path is part of a cache entry's key, so a directory that moves never
hits. The other derived state the framework keeps across processes
lives with it: the store of compiled executables that lets a restarted
process skip tracing its verify programs (common/execstore.py) is the
sub-directory ``executables/`` of the cache directory itself — it
follows ``JAX_COMPILATION_CACHE_DIR`` as the cache does and is on
exactly when the cache is — and host-built constant tables and bench
warm keys sit beside the cache under ``<checkout>/.cache``
(`local_cache`). Nothing is read from or written to the user's home
directory.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("common.jaxenv")

_ENV = "JAX_COMPILATION_CACHE_DIR"
_LOCAL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache")
_DEFAULT = os.path.join(_LOCAL, "xla")
_done = False


def local_cache(*parts: str) -> str:
    """A path under the checkout's git-ignored ``.cache`` directory."""
    return os.path.join(_LOCAL, *parts)


def cache_dir() -> str | None:
    """The persistent-compile-cache directory once enabled, or None.
    The compile seam (common/devicecost.py) probes this dir's entry
    count around each compile: a cold compile WRITES an entry, a warm
    load only reads — the cache-hit-vs-miss signal. Its AOT path keeps
    the executable store in this dir's ``executables/``."""
    if not _done:
        return None
    return os.environ.get(_ENV, _DEFAULT) or None


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent on-disk compilation cache (see the
    module docstring for where it lives). Safe to call repeatedly;
    must run before the first jit compilation to help."""
    global _done
    if _done:
        return cache_dir()
    try:
        import jax

        if os.environ.get(_ENV) is None:
            os.makedirs(_DEFAULT, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", _DEFAULT)
        # cache every program regardless of compile time or size
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _done = True
        logger.info("XLA compilation cache at %s", cache_dir())
        return cache_dir()
    except Exception:
        logger.exception("could not enable the XLA compilation cache")
        return None


def shard_map(fn, mesh, in_specs, out_specs):
    """`jax.shard_map` for the sharded verify pipeline, with
    replication checking off: every output is batch-sharded, so there
    is no replicated result for it to vouch for, and the tables really
    are replicated by construction (`TPUProvider._replicated`
    places them with an empty PartitionSpec)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
