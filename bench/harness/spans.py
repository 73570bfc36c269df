"""Host spans the benchmark records around its calls into the program, and
the count of programs compiled or loaded from the cache.

A span wraps one bound method of a program instance.  Its durations are
kept in memory while the measured window is open; in a traced run each
span is also a ``jax.profiler.TraceAnnotation``, so the trace reduction
can say what the host was doing in each of the device's idle gaps."""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict

WINDOW_SPAN = "bench.window"


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.recording = False
        self.seconds = defaultdict(float)     # span name -> total in window
        self.calls = defaultdict(int)         # span name -> calls in window

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            if self.recording:
                self.seconds[name] += dt
                self.calls[name] += 1

    def wrapped(self, fn, name: str):
        """``fn`` called inside span ``name``."""

        def call(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)

        return call

    def wrap(self, obj, attr: str, name: str):
        """Replace ``obj.attr`` (a bound method or callable attribute) by
        the same call inside span ``name``; returns the original."""
        orig = getattr(obj, attr)
        setattr(obj, attr, self.wrapped(orig, name))
        return orig


class CompileWatch:
    """Backend compiles and persistent-cache loads in this process; the
    window's are the difference of two snapshots."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.cache_hits
