"""The benchmark's own spans, recorded only in a traced run: timing
wrappers around the calls into each layer, each also a
`jax.profiler.TraceAnnotation` so the host spans sit on the device
trace's clock, and the profiler session over the first seconds of the
window. The program's own flight recorder is left as a node runs it.
"""

from __future__ import annotations

import contextlib
import time

from benchmark import tracered


class Tracer:
    def __init__(self, intake, trace_dir: str, trace_seconds: float):
        import jax
        self._jax = jax
        self._dir = trace_dir
        self._seconds = trace_seconds
        self._t0 = None
        self._tracing = False
        self.traced_blocks = 0
        self.current = [None]
        self.trace_stop_s = 0.0
        self.trace_load_s = None

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

    def before_block(self, rec, window_end: float) -> None:
        """Make `rec` the block the spans belong to, and start the
        profiler once the window has only its last seconds left: it is
        stopped after the window, where it slows nobody."""
        self.current[0] = rec
        if not self._tracing and self._t0 is None and \
                time.perf_counter() >= window_end - self._seconds:
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self._jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._t0 = time.perf_counter()
            self._tracing = True
        if self._tracing:
            rec.traced = True
            self.traced_blocks += 1

    def finish(self):
        if self._tracing:
            self._tracing = False
            t = time.perf_counter()
            self._jax.profiler.stop_trace()
            self.trace_stop_s = time.perf_counter() - t
        t = time.perf_counter()
        try:
            return tracered.load_xplane(tracered.find_xplane(self._dir))
        except FileNotFoundError:
            return None
        finally:
            self.trace_load_s = time.perf_counter() - t
