"""A provider that stands in for the device where there is none: the
software provider behind the TPU provider's two entries, counting what
it serves the way `TPUProvider.stats` does. Used by `--rehearse` and by
the tests under tests/benchmark; a measuring run never builds it."""

from __future__ import annotations

from fabric_tpu.bccsp.sw import SWProvider


class StandInProvider(SWProvider):
    def __init__(self):
        super().__init__()
        self.stats = {k: 0 for k in (
            "comb_batches", "pipeline_batches", "sw_fallbacks",
            "fused_fallbacks", "host_hash_fallbacks", "degraded_batches",
            "ladder_batches", "compile_total", "compile_cold_total",
            "compile_cache_hits")}

    def verify_batch(self, items):
        if len(items) >= 16:
            self.stats["pipeline_batches"] += 1
        return super().verify_batch(items)

    def verify_prepared_start(self, digests, r, rpn, w, der_ok, key_idx,
                              keys, get_sig):
        n = len(der_ok)
        if n >= 16:
            self.stats["comb_batches"] += 1
        out = []
        for i in range(n):
            k = keys[key_idx[i]]
            try:
                out.append(k is not None and self.verify(
                    k, get_sig(i), digests[i].tobytes()))
            except Exception:       # noqa: BLE001 (as the sw rung does)
                out.append(False)
        return lambda: out
