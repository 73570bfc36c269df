"""BENCHMARK.json: loading, the lookups a run needs, and the contract's
checks on names, units and cells (the tests run :func:`validate`)."""
from __future__ import annotations

import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in manifest["workloads"])
    raise SystemExit(f"unknown workload {name!r}; known: {known}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"workload names config {name!r}, which "
                     f"BENCHMARK.json does not list")


def reports(metric: dict, workload_name: str) -> bool:
    """Whether ``metric`` is reported in the cell ``workload_name``."""
    return workload_name in metric.get("workloads", [workload_name])


def end_to_end_for(manifest: dict, workload_name: str) -> list:
    return [m for m in manifest["end_to_end"] if reports(m, workload_name)]


def per_layer_for(manifest: dict, workload_name: str) -> list:
    """Per-layer metrics of a cell: those listing it, and those without a
    ``workloads`` key whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(manifest, workload_name)}
    out = []
    for m in manifest["per_layer"]:
        if "workloads" in m:
            if workload_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def validate(m: dict) -> list:
    """Every breach of the contract's static rules, as messages."""
    errs = []
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
    cmd = m.get("command", [])
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        errs.append("command must be 1..32 one-line strings")
    paths = m.get("paths", [])
    if not 1 <= len(paths) <= 16:
        errs.append("paths must hold 1..16 directories")
    for p in paths:
        if not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) or \
                p.startswith("/") or ".." in p.split("/"):
            errs.append(f"bad path {p!r}")
    for w in cmd[1:]:
        if w.startswith("/") or ".." in w.split("/"):
            errs.append(f"command word {w!r} leaves the repo")
        elif "/" in w and not any(w == p or w.startswith(p.rstrip("/") + "/")
                                  for p in paths):
            errs.append(f"command names {w!r} outside paths")
    rs = m.get("run_seconds")
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        errs.append("run_seconds must be a whole number 1..51")

    def named(items, kind, keys, optional=()):
        names = set()
        for it in items:
            extra = set(it) - set(keys) - set(optional)
            missing = set(keys) - set(it)
            if extra or missing:
                errs.append(f"{kind} {it.get('name')}: keys extra {sorted(extra)}"
                            f" missing {sorted(missing)}")
            n = it.get("name", "")
            if not NAME_RE.match(n):
                errs.append(f"{kind} name {n!r} breaks the name rule")
            if n in names:
                errs.append(f"duplicate {kind} name {n!r}")
            names.add(n)
        return names

    configs = m.get("configs", [])
    workloads = m.get("workloads", [])
    e2e = m.get("end_to_end", [])
    pl = m.get("per_layer", [])
    if not 1 <= len(configs) <= 24:
        errs.append("configs must hold 1..24 entries")
    if not 1 <= len(workloads) <= 24:
        errs.append("workloads must hold 1..24 entries")
    if not 1 <= len(e2e) <= 16:
        errs.append("end_to_end must hold 1..16 metrics")
    if not 1 <= len(pl) <= 128:
        errs.append("per_layer must hold 1..128 metrics")
    cnames = named(configs, "config",
                   ("name", "source", "file", "reduced", "why"))
    wnames = named(workloads, "workload",
                   ("name", "config", "traffic", "chips", "why"))
    named(e2e + pl, "metric", ("name", "unit", "better", "source"),
          ("bound", "layer", "moves", "workloads"))
    files = set()
    for c in configs:
        if not _line(c.get("source")) or not _line(c.get("why")):
            errs.append(f"config {c.get('name')}: source/why not one line")
        f = c.get("file", "")
        if f in files:
            errs.append(f"config file {f} shared")
        files.add(f)
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            errs.append(f"config file {f} not under paths")
        red = c.get("reduced", [])
        if len(red) > 16 or not all(NAME_RE.match(k) for k in red):
            errs.append(f"config {c.get('name')}: bad reduced keys")
    pairs = set()
    for w in workloads:
        if w.get("config") not in cnames:
            errs.append(f"workload {w['name']} names unknown config")
        if not NAME_RE.match(w.get("traffic", "")):
            errs.append(f"workload {w['name']}: bad traffic name")
        if w.get("chips") not in (1, 4):
            errs.append(f"workload {w['name']}: chips must be 1 or 4")
        if not _line(w.get("why")):
            errs.append(f"workload {w['name']}: why not one line")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errs.append(f"config/traffic pair {pair} repeats")
        pairs.add(pair)
    used = {w.get("config") for w in workloads}
    if cnames - used:
        errs.append(f"configs used by no cell: {sorted(cnames - used)}")
    four = sum(1 for w in workloads if w.get("chips") == 4)
    if four > max(len(workloads) // 2, 1):
        errs.append(f"{four} of {len(workloads)} cells ask for 4 chips")
    for met in e2e + pl:
        if not UNIT_RE.match(met.get("unit", "")):
            errs.append(f"metric {met['name']}: bad unit {met.get('unit')!r}")
        if met.get("better") not in ("lower", "higher"):
            errs.append(f"metric {met['name']}: better must be lower/higher")
        if met.get("source") not in SOURCES:
            errs.append(f"metric {met['name']}: bad source")
        for wn in met.get("workloads", []):
            if wn not in wnames:
                errs.append(f"metric {met['name']} lists unknown cell {wn}")
    e2e_names = set()
    for met in e2e:
        e2e_names.add(met["name"])
        if set(met) - {"name", "unit", "better", "bound", "source",
                       "workloads"} or "bound" not in met:
            errs.append(f"end-to-end {met['name']}: keys {sorted(met)}")
        if met.get("source") not in ("host_clock", "device_trace"):
            errs.append(f"end-to-end {met['name']}: source must be "
                        "host_clock or device_trace")
        b = met.get("bound", 0)
        if not (0.01 <= b <= 0.25):
            errs.append(f"end-to-end {met['name']}: bound {b} out of range")
    if "setup_s" not in e2e_names:
        errs.append("setup_s missing from end_to_end")
    for met in pl:
        if set(met) - {"name", "unit", "better", "source", "layer", "moves",
                       "workloads"} or "layer" not in met or "moves" not in met:
            errs.append(f"per-layer {met['name']}: keys {sorted(met)}")
        if not _line(met.get("layer")):
            errs.append(f"per-layer {met['name']}: layer not one line")
        if met.get("moves") not in e2e_names:
            errs.append(f"per-layer {met['name']} moves unknown "
                        f"{met.get('moves')!r}")
        mv = next((e for e in e2e if e["name"] == met.get("moves")), None)
        for wn in met.get("workloads", wnames):
            if mv is not None and not reports(mv, wn):
                errs.append(f"per-layer {met['name']} in {wn}, which does "
                            f"not report {met['moves']}")
        if "roofline" in met["name"] or "mfu" in met["name"]:
            if met.get("unit") != "%":
                errs.append(f"{met['name']}: a share of a peak is in %")
    for w in workloads:
        e = end_to_end_for(m, w["name"])
        if not any(x["name"] == "setup_s" for x in e) or len(e) < 2:
            errs.append(f"cell {w['name']} lacks setup_s or another metric")
        if not per_layer_for(m, w["name"]):
            errs.append(f"cell {w['name']} reports no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        errs.append("BENCHMARK.json over 64 KiB")
    return errs
