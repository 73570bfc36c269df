"""Host spans and counters of DAG-AFL's own work, for whoever profiles it.

    rec = obs.Recorder()
    with obs.recording(rec):
        coordinator.run()
    rec.seconds["dagafl.flush"], rec.counters["dagafl.device_syncs"]

``span(name, **ids)`` and ``count(name, n)`` cost one module-level check
when no recorder is active.  While one is, a span is also a
``jax.profiler.TraceAnnotation`` carrying its ids, so a profiler trace
taken at the same time holds it on the clock of the device planes, and the
recorder adds up per name its calls and seconds.  Only the thread that
activated the recorder is recorded: spans and counts on other threads (the
window assembler's worker) are no-ops.  Names carry the prefix ``dagafl.``.
"""
import contextlib
import threading
import time
from collections import defaultdict

import numpy as np
from jax.profiler import TraceAnnotation

_NULL = contextlib.nullcontext()
_active = None          # the active Recorder, or None


class Recorder:
    """Per span name its calls and seconds, and counters, of one thread,
    kept in memory."""

    def __init__(self):
        self.calls = defaultdict(int)
        self.seconds = defaultdict(float)
        self.counters = defaultdict(int)
        self.thread = None

    def names(self) -> set:
        """The span names recorded so far."""
        return set(self.calls)


class _Span:
    __slots__ = ("rec", "name", "ann", "start_ns")

    def __init__(self, rec: Recorder, name: str, ids: dict):
        self.rec, self.name = rec, name
        self.ann = TraceAnnotation(name, **ids)

    def __enter__(self):
        self.ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.start_ns
        self.ann.__exit__(*exc)
        self.rec.calls[self.name] += 1
        self.rec.seconds[self.name] += dur * 1e-9
        return False


def _recorder():
    """The active recorder when called on its thread, else None."""
    rec = _active
    if rec is None or rec.thread != threading.get_ident():
        return None
    return rec


def span(name: str, **ids):
    """Context manager: span ``name`` with ``ids`` (client, epoch, window,
    ...) while a recorder is active on this thread; else a shared no-op."""
    if _active is None:
        return _NULL
    rec = _recorder()
    return _NULL if rec is None else _Span(rec, name, ids)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the active recorder, if any."""
    if _active is None:
        return
    rec = _recorder()
    if rec is not None:
        rec.counters[name] += n


def fetch(x) -> np.ndarray:
    """``np.asarray(x)``: a blocking device-to-host read, spanned as
    ``dagafl.sync`` and counted in ``dagafl.device_syncs``."""
    if _active is None:
        return np.asarray(x)
    count("dagafl.device_syncs")
    with span("dagafl.sync"):
        return np.asarray(x)


@contextlib.contextmanager
def recording(rec: Recorder):
    """Activate ``rec`` on the calling thread for the block."""
    global _active
    prev = _active
    rec.thread = threading.get_ident()
    _active = rec
    try:
        yield rec
    finally:
        _active = prev
