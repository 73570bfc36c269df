"""Profiler capture of the measured window, and its reduction to device
busy time, per-program and per-op device time, Pallas kernel time per
program, collective time, and the longest idle gaps named by the host span
that was open in each.

The reduction works on plain tuples so that it can be checked on a small
recorded trace without a chip (see bench/tests/test_bench_trace.py)."""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field

from bench.harness.spans import WINDOW_SPAN

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"psum|allreduce|send|recv", re.IGNORECASE)
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_TPU_KERNEL = 'custom_call_target="tpu_custom_call"'


@dataclass
class TraceEvents:
    """What the reduction reads: per device, its op and program (module)
    events as (name, start_ns, dur_ns); the host spans as (name, start_ns,
    dur_ns)."""
    ops: dict = field(default_factory=dict)        # device -> [(n, s, d)]
    modules: dict = field(default_factory=dict)    # device -> [(n, s, d)]
    host: list = field(default_factory=list)
    op_stats: dict = field(default_factory=dict)   # op name -> its stats
    kernels: dict = field(default_factory=dict)    # device -> [(n, s, d)]


def module_name(name: str) -> str:
    """``jit_train_impl(1234)`` -> ``jit_train_impl``."""
    return _ID_SUFFIX.sub("", name.strip())


def read_xplane(path: str, host_span_names) -> TraceEvents:
    """Events of one ``.xplane.pb``: device planes' "XLA Ops" and
    "XLA Modules" lines, and the host events named like the benchmark's
    spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = TraceEvents()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = plane.name
            for line in plane.lines:
                events = list(line.events)
                rows = [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in events]
                if line.name == "XLA Ops":
                    ev.ops.setdefault(dev, []).extend(rows)
                    for e, row in zip(events, rows):
                        st = _stats(e)
                        ev.op_stats.setdefault(e.name, st)
                        if is_kernel(e.name, st):
                            ev.kernels.setdefault(dev, []).append(row)
                elif line.name == "XLA Modules":
                    ev.modules.setdefault(dev, []).extend(rows)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                ev.host.extend((e.name, float(e.start_ns),
                                float(e.duration_ns))
                               for e in line.events
                               if e.name in host_span_names)
    for dev, mods in ev.modules.items():
        if dev not in ev.ops:            # no op line: programs mark busy time
            ev.ops[dev] = list(mods)
    return ev


def _stats(event) -> dict:
    """An event's stats as short plain values (what names its HLO op)."""
    try:
        return {str(k): v if isinstance(v, (int, float)) else str(v)[:200]
                for k, v in event.stats}
    except Exception:                    # noqa: BLE001 - stats are optional
        return {}


def is_kernel(name: str, stats: dict) -> bool:
    """A Pallas (Mosaic) kernel.  XLA names its custom call after the
    enclosing function (``%closed_call.10 = ... custom-call(...)``); on a
    TPU v5e trace the op's event name is that whole HLO instruction, which
    carries ``custom_call_target="tpu_custom_call"``.  Other profilers put
    the instruction or the ``pallas_call`` it was lowered from in the
    stats."""
    if "pallas" in name or _TPU_KERNEL in name:
        return True
    return any(isinstance(v, str) and ("pallas_call" in v
                                       or _TPU_KERNEL in v)
               for v in stats.values())


def by_program(events, programs) -> dict:
    """Seconds of ``events`` per program (module) whose event on the same
    device holds each one's start."""
    spans = sorted((s, s + d, module_name(n)) for n, s, d in programs)
    out = defaultdict(float)
    for _, s, d in events:
        home = "outside any program"
        for a, b, name in spans:
            if a <= s < b:
                home = name
                break
            if a > s:
                break
        out[home] += d
    return out


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def union(intervals):
    """Merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def idle_gaps(busy, lo, hi):
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(gap, host):
    """Innermost host span covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, s, d in host:
        if name != WINDOW_SPAN and s <= mid <= s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "host outside any benchmark span"


def window_of(ev: TraceEvents):
    for name, s, d in ev.host:
        if name == WINDOW_SPAN:
            return s, s + d
    alls = [(s, s + d) for evs in ev.ops.values() for _, s, d in evs]
    if not alls:
        return 0.0, 0.0
    return min(a for a, _ in alls), max(b for _, b in alls)


def reduce_events(ev: TraceEvents, top: int = 10) -> dict:
    """Busy and idle time, per-program and per-op device seconds (each
    averaged over the devices), collective seconds, and the top device ops
    and idle gaps for the result's ``breakdown``."""
    lo, hi = window_of(ev)
    devices = sorted(ev.ops) or ["none"]
    n = len(devices)
    busy_ns, coll_ns = 0.0, 0.0
    per_op, per_mod = defaultdict(float), defaultdict(float)
    per_kernel = defaultdict(float)
    gap_by_label = defaultdict(float)
    longest = []
    for dev in devices:
        ops = list(_clip(ev.ops.get(dev, []), lo, hi))
        merged = union((a, b) for _, a, b in ops)
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in ops:
            per_op[name] += (b - a) / n
            if COLLECTIVE_RE.search(name):
                coll_ns += (b - a) / n
        mods = [(name, a, b - a) for name, a, b in
                _clip(ev.modules.get(dev, []), lo, hi)]
        for name, _, d in mods:
            per_mod[module_name(name)] += d / n
        kern = [(name, a, b - a) for name, a, b in
                _clip(ev.kernels.get(dev, []), lo, hi)]
        for name, d in by_program(kern, mods).items():
            per_kernel[name] += d / n
        if dev == devices[0]:
            for g in idle_gaps(merged, lo, hi):
                lab = label(g, ev.host)
                gap_by_label[lab] += g[1] - g[0]
                longest.append((g[1] - g[0], lab))
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns * 1e-9 / n
    longest.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": n,
        "collective_s": coll_ns * 1e-9,
        "modules": {k: v * 1e-9 for k, v in per_mod.items()},
        "ops": {k: v * 1e-9 for k, v in per_op.items()},
        "kernels": {k: v * 1e-9 for k, v in per_kernel.items()},
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v * 1e-9] for k, v in sorted(
                gap_by_label.items(), key=lambda kv: -kv[1])[:top]],
        },
        "longest_gaps": [[lab, d * 1e-9] for d, lab in longest[:top]],
        "op_stats": ev.op_stats,
    }


def seconds_matching(table: dict, pattern: str) -> float:
    """Total seconds of the entries whose names match ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


class Capture:
    """Profiler trace of the measured window, written under ``$TMPDIR``
    and removed once reduced."""

    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self, span_names) -> dict:
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            ev = read_xplane(paths[0], set(span_names) | {WINDOW_SPAN})
            return reduce_events(ev)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
