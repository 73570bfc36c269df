#!/usr/bin/env python3
"""Readings that set an MLA + expert-layer serving cell's limits: for each
seed, ``served_logit_gap`` and ``misrouted_margin`` of the program's
served tokens and routing, and of two controls (the reference with fp8
operands, and the reference routing by plain top-k without the group
limit), on the chip.  Each seed makes the cell's weights and serves one
query through the program, as the entry driver does; the rows the driver
would replay are judged as it judges them
(``replica_serving_mla_moe.readings``).  One JSON line per seed.

``--faults`` also serves the first seed's query through two programs with
a routing fault planted, each judged by the same checks on the tokens it
served and the routing it reports: ``swap_fault`` routes each token to
its 7th expert in place of its 6th (the expert just outside the top-k
boundary), ``group_fault`` holds the experts of another routing group than
the one the reference holds.  One JSON line per fault.

    python bench/calibrate_mla_moe.py <cell> [--faults] <seed> [<seed> ...]
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def swapped_route(route):
    """``route`` with each token's (k+1)-th expert in place of its k-th."""
    def swapped(logits, mo):
        idx, gates = route(logits, dataclasses.replace(mo,
                                                       top_k=mo.top_k + 1))
        keep = list(range(mo.top_k - 1)) + [mo.top_k]
        return idx[:, keep], gates[:, keep]
    return swapped


def main(argv=None) -> int:
    import gc

    import numpy as np

    from bench.drivers.replica_serving_mla_moe import (
        margin_limit, program_config, readings, replay_rows, served_checks,
        world)
    from bench.harness import device, loader, manifest
    from repro.launch.serve import greedy_decode, make_serving_fns
    from repro.models import moe
    from repro.runtime import serve_runtime

    argv = sys.argv[1:] if argv is None else argv
    faults = "--faults" in argv
    cell, *seeds = [a for a in argv if a != "--faults"]
    m = manifest.load()
    w = manifest.workload(m, cell)
    devices = device.require_tpu(int(w["chips"]))
    cfg = loader.config(manifest.config_entry(m, w["config"]))
    t = loader.traffic(w["traffic"])
    limit = margin_limit(cell)

    def serve(arch, params, prompt):
        prefill, decode = make_serving_fns(
            arch, serve_runtime(cfg["kernel_policy"]))
        r = greedy_decode(prefill, decode, arch, params, {"tokens": prompt},
                          t["new_tokens"])
        out = np.asarray(r["tokens"]), np.asarray(r["moe"]["held"])
        del prefill, decode, r
        gc.collect()
        return out

    for n, seed in enumerate(map(int, seeds)):
        arch, params, make_prompts, pick_seed = world(cfg, t, seed)
        prompt = make_prompts(0)
        tokens, held = serve(arch, params, prompt)
        rows = replay_rows(devices, t["batch"],
                           np.random.default_rng(pick_seed))
        prompt = np.asarray(prompt)
        print(json.dumps({"seed": seed, "rows": rows.tolist(),
                          **readings(params, cfg, prompt[rows],
                                     tokens[rows], held[:, rows],
                                     t["new_tokens"], limit)}),
              flush=True)
        if faults and n == 0:
            route = moe.route
            planted = (
                ("swap_fault", arch, lambda: setattr(
                    moe, "route", swapped_route(route))),
                ("group_fault", program_config(dict(
                    cfg, held_group=(cfg["held_group"] + 1)
                    % cfg["n_group"])), lambda: None))
            for name, fault_arch, plant in planted:
                plant()
                try:
                    f_tokens, f_held = serve(fault_arch, params, prompt)
                finally:
                    moe.route = route
                seq = np.concatenate([prompt, f_tokens[:, :-1]], 1)[rows]
                print(json.dumps({"seed": seed, "fault": name, name:
                                  served_checks(params, cfg, seq,
                                                f_tokens[rows],
                                                f_held[:, rows],
                                                t["new_tokens"], limit)}),
                      flush=True)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
