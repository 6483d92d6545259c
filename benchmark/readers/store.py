"""Readers of the ledger's store commits in the program's own span tree:
the `kvdb.write` spans `fabric_tpu/ledger/kvdb.py` opens around every
commit of a `KVStore` (attrs `ops`, and `frames` on the store that
checkpoints behind, a channel's `index.db`), read over the
blocks handed over under the profiler as `readers/program.py` reads
them. A program without those spans (any before they were added) gives
None, never an error.
"""

from __future__ import annotations

from benchmark.readers.program import _ATTRS, _NAME, _blocks


def attr_per_ktx(ctx, span: str, attr: str):
    """The sum of one attr over the traced blocks' spans of that name
    that book it, per 1,000 transactions. None where no such span books
    it (a program without the spans, or a kernel that keeps no
    per-thread I/O account)."""
    blocks = _blocks(ctx)
    if blocks is None:
        return None
    values = [(e[_ATTRS] or {}).get(attr)
              for _, evs in blocks for e in evs if e[_NAME] == span]
    values = [v for v in values if v is not None]
    txs = sum(r.n_tx for r, _ in blocks)
    if not values or not txs:
        return None
    return sum(values) / (txs / 1000.0)
