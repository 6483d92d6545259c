"""The benchmark's own spans, recorded only in a traced run: timing
wrappers around the calls into each layer, each also a
`jax.profiler.TraceAnnotation` so the host spans sit on the device
trace's clock, and the profiler session over the last seconds of the
window. The program's own flight recorder is left as a node runs it.
"""

from __future__ import annotations

import collections
import contextlib
import statistics
import time

from benchmark import tracered

RECENT_BLOCKS = 5     # the recent time of a block is their median


class NothingTraced(RuntimeError):
    """A traced run has no device window to report: no block began
    under the profiler, the profiler left no file, or its trace holds
    no `bench.block` or no device operation. No result line: one
    without `busy_s` / `window_s` would be malformed and say nothing."""


class Tracer:
    def __init__(self, intake, trace_dir: str, trace_seconds: float,
                 trace_blocks: int):
        import jax
        self._jax = jax
        self._dir = trace_dir
        self._seconds = trace_seconds
        self._blocks = trace_blocks
        self._took = collections.deque(maxlen=RECENT_BLOCKS)
        self._window_end = None
        self.traced_blocks = 0
        self.current = [None]
        self.trace_lead_s = None    # the profiler's start before the end
        self.trace_stop_s = 0.0
        self.trace_load_s = None
        self.busy_and_window = None  # (busy_s, window_s) of the trace

        validator = intake.channel.validator
        validator.validate = self._wrap(validator.validate, "validate")
        intake.channel.commit_validated = self._wrap(
            intake.channel.commit_validated, "ledger.commit")
        intake.mcs.verify_block = self._wrap(intake.mcs.verify_block,
                                             "verify_block")
        csp = intake.csp
        csp.verify_batch = self._wrap(
            csp.verify_batch, "provider.call", lanes=lambda a: len(a[0]))
        start = getattr(csp, "verify_prepared_start", None)
        if start is not None:
            def prepared(*a, **kw):
                with self.span("provider.call", lanes=len(a[4])):
                    resolve = start(*a, **kw)
                return self._wrap(resolve, "provider.call")
            csp.verify_prepared_start = prepared

    @contextlib.contextmanager
    def span(self, name: str, lanes=None):
        rec = self.current[0]
        t0 = time.perf_counter()
        try:
            with self._jax.profiler.TraceAnnotation(name):
                yield
        finally:
            if rec is not None:
                rec.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))
                if lanes is not None:
                    rec.lanes.append(lanes)

    def _wrap(self, fn, name: str, lanes=None):
        def wrapped(*a, **kw):
            with self.span(name, lanes(a) if lanes is not None else None):
                return fn(*a, **kw)
        return wrapped

    def lead_s(self) -> float:
        """How long before the window's end the profiler is due:
        `trace_seconds`, or `trace_blocks` x the recent time of a
        block where that is longer, so that a boundary falls inside
        it whatever the length of a block."""
        if not self._took:
            return self._seconds
        return max(self._seconds,
                   self._blocks * statistics.median(self._took))

    def before_block(self, rec, window_end: float) -> None:
        """Make `rec` the block the spans belong to, and start the
        profiler at the first block boundary inside the window's last
        `lead_s()`: it is stopped after the window, where it slows
        nobody."""
        last = self.current[0]
        if last is not None and last.done is not None:
            self._took.append(last.done - last.start)
        self.current[0] = rec
        self._window_end = window_end
        if self.trace_lead_s is None and \
                time.perf_counter() >= window_end - self.lead_s():
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self._jax.profiler.start_trace(self._dir, profiler_options=opts)
            self.trace_lead_s = window_end - time.perf_counter()
        if self.trace_lead_s is not None:
            rec.traced = True
            self.traced_blocks += 1

    def _nothing_traced(self, what: str) -> NothingTraced:
        """The error for a traced run that ends without a device
        window: what was missing, and what the profiler's start went
        by."""
        last = self.current[0]
        if last is not None and last.done is not None:
            began = (f"the window's last block began "
                     f"{self._window_end - last.start:.2f} s before its end "
                     f"and took {last.done - last.start:.2f} s, the "
                     f"{len(self._took)} before it "
                     f"{[round(s, 2) for s in self._took]} s")
        else:
            began = "no block was handed over"
        return NothingTraced(
            f"nothing traced: {what}. The profiler starts at the first "
            f"block boundary in the window's last max(TRACE_SECONDS = "
            f"{self._seconds:g} s, TRACE_BLOCKS = {self._blocks} x the "
            f"recent time of a block) = {self.lead_s():.2f} s; {began}. "
            f"No result")

    def finish(self, need_device: bool = True) -> dict:
        """Stop the profiler and reduce what it wrote. A traced run
        always has its device window: `NothingTraced` where no block
        began under the profiler, it left no file, or the trace holds
        no `bench.block` or (a CPU has no device plane: a rehearsal
        does not `need_device`) no device operation inside them."""
        if self.trace_lead_s is None:
            raise self._nothing_traced("no block began under the profiler")
        t = time.perf_counter()
        self._jax.profiler.stop_trace()
        self.trace_stop_s = time.perf_counter() - t
        t = time.perf_counter()
        try:
            trace = tracered.load_xplane(tracered.find_xplane(self._dir))
        except FileNotFoundError:
            raise self._nothing_traced(
                f"the profiler left no .xplane.pb under {self._dir}") \
                from None
        finally:
            self.trace_load_s = time.perf_counter() - t
        if tracered.traced_window(trace) is None:
            raise self._nothing_traced(
                "the trace holds no bench.block annotation")
        self.busy_and_window = tracered.busy_and_window(trace)
        if need_device and self.busy_and_window is None:
            raise self._nothing_traced(
                "no device operation ran in the traced window")
        return trace
