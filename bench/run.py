#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, entry driver, correctness limits and
per-layer metric readers are files found by the names in BENCHMARK.json.
Set-up (chip start, data and weights from the seed, compile-cache loads,
warm-up of every shape the window uses) is timed as ``setup_s``; then the
driver measures for ``--seconds``.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window and reports its
per-layer metrics.  After the window, what the timed path produced is
compared with a plain reference; ``correct`` is false when any compared
number passes its limit.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import device, loader, manifest  # noqa: E402
from bench.harness.spans import WINDOW_SPAN, CompileWatch, Spans  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Run:
    """What a driver gets: the cell's files, the seed and window length,
    spans, and the window's open/close hooks."""

    def __init__(self, args, m, devices):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.workload = manifest.workload(m, args.workload)
        entry = manifest.config_entry(m, self.workload["config"])
        self.config = loader.config(entry)
        self.traffic = loader.traffic(self.workload["traffic"])
        self.devices = devices
        self.spans = Spans(annotate=self.trace)
        self.compiles = CompileWatch()
        self.capture = None
        self.setup_s = None
        self.window_s = None
        self.window_compiles = None
        self._ann = None
        self._t0 = None
        self._c0 = None
        self.log = log

    def open_window(self) -> None:
        self.setup_s = time.perf_counter() - T_START
        self._c0 = self.compiles.snapshot()
        if self.trace:
            import jax

            from bench.harness.trace import Capture
            self.capture = Capture()
            self.capture.start()
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
        self.spans.recording = True
        self._t0 = time.perf_counter()

    def close_window(self) -> None:
        self.window_s = time.perf_counter() - self._t0
        self.spans.recording = False
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self.capture.stop()
        c1 = self.compiles.snapshot()
        self.window_compiles = (c1[0] - self._c0[0], c1[1] - self._c0[1])


def judge(checks: dict, limits: dict):
    """(correct, {name: {value, limit}}): every compared number at or
    under its limit, and every limit read."""
    out, ok = {}, True
    for name, spec in limits["numbers"].items():
        v = checks.get(name)
        lim = spec["limit"]
        good = v is not None and v == v and v <= lim
        ok = ok and good
        out[name] = {"value": v, "limit": lim}
    return ok, out


def per_layer(m, run: Run, raw: dict, reduced, peaks) -> dict:
    """The cell's per-layer metrics, each from its own reader; a reader
    that finds nothing to read leaves its metric out."""
    r = SimpleNamespace(workload=run.workload["name"], raw=raw,
                        trace=reduced, window_s=run.window_s,
                        chips=len(run.devices), peaks=peaks,
                        config=run.config, traffic=run.traffic,
                        spans=run.spans)
    out = {}
    for met in manifest.per_layer_for(m, run.workload["name"]):
        v = loader.metric_reader(met["name"]).read(r)
        if v is not None:
            out[met["name"]] = {"value": v, "unit": met["unit"]}
    return out


def execute(args, m: dict, devices) -> dict:
    """One run on ``devices``: the entry driver's set-up, window and check, then
    the result object (the caller prints it)."""
    w = manifest.workload(m, args.workload)
    run = Run(args, m, devices)
    drv = loader.driver(run.config["driver"])
    out = drv.run(run)
    correct, checks = judge(out["checks"], loader.limits(w["name"]))
    log(f"window {run.window_s:.3f} s; programs compiled in the window "
        f"{run.window_compiles[0]}, loaded from the cache "
        f"{run.window_compiles[1]}; set-up {run.setup_s:.3f} s")
    res = {"correct": correct, "attempted": out["attempted"],
           "failed": out["failed"]}
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    if run.trace:
        peaks = device.peaks(devices[0].device_kind)
        reduced = run.capture.reduce(run.spans.seconds.keys()
                                     | set(out.get("span_names", ())))
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        res["metrics"] = per_layer(m, run, out["raw"], reduced, peaks)
        res["breakdown"] = reduced["breakdown"]
        log("longest idle gaps: " + json.dumps(reduced["longest_gaps"]))
        for what in ("modules", "ops"):
            log(f"device seconds by {what}: " + json.dumps(dict(sorted(
                reduced[what].items(), key=lambda kv: -kv[1])[:30])))
        for name, _ in sorted(reduced["ops"].items(),
                              key=lambda kv: -kv[1])[:30]:
            log(f"op {name}: {json.dumps(reduced['op_stats'].get(name))}")
        silent = [met["name"] for met in manifest.per_layer_for(m, w["name"])
                  if met["name"] not in res["metrics"]]
        if silent:
            log(f"per-layer metrics that found nothing to read: {silent}")
    else:
        e2e = dict(out["e2e"], setup_s=run.setup_s)
        res["metrics"] = {
            met["name"]: {"value": e2e[met["name"]], "unit": met["unit"]}
            for met in manifest.end_to_end_for(m, w["name"])}
    res["device"] = dev
    res["checks"] = checks
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    m = manifest.load()
    w = manifest.workload(m, args.workload)
    devices = device.require_tpu(int(w["chips"]))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    res = execute(args, m, devices)
    for name, c in res["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
