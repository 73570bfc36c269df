"""Live-traffic serving benchmark: frontier -> replica publication under
a concurrent query stream (``kind=serve``), gated in CI by
``benchmarks/check_perf_gate.py``.

For each backend (CNN batched eval, LM prefill + KV-cache greedy decode)
this runs one DAG-AFL training simulation with the consensus publisher and
a seeded Poisson query stream riding the same event loop
(``repro/fl/serving.py``), then checks three things the gate pins:

* **deterministic counters** — replica versions published, queries served,
  staleness lag (in ledger append seqs — ``head_seq`` advances exactly once
  per publish, so lags are event counts, not clock readings) and the
  replica-version histogram are pure functions of the seed; a same-seed
  rerun must reproduce every counter exactly (``determinism`` leg).
* **exact output parity** — a replica IS the Eq. 6 aggregate over its
  pinned frontier refs: recomputing the aggregate from the replica's own
  refs must match bit for bit, batched eval on both must agree exactly,
  and (LM) greedy-decoding the same prompts through the replica and the
  recomputed aggregate must produce identical token streams.
* **eviction protection** — the CNN leg runs on the bounded ledger with an
  aggressive checkpoint cadence, so replica frontiers DO get pruned out
  from under the publisher; every ref pinned by a live replica must still
  be resident in the ModelStore when the run ends.

Wall-clock throughput is reported for eyeballing but NEVER gated.

Usage::

  python benchmarks/serve_perf.py --quick                # CI geometry
  python benchmarks/serve_perf.py --quick --backend cnn  # one backend
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chain_perf import _WORLDS  # noqa: E402

BACKEND_ORDER = ["cnn", "lm"]

#: serving-report keys excluded from the determinism comparison: the mean
#: query accuracy (a float average of eval outputs — the gate pins event
#: counts, never accuracies)
NONDETERMINISTIC_KEYS = ("query_accuracy_mean",)


def _geometry(quick: bool, backend: str) -> Dict:
    if backend == "cnn":
        geo = dict(n_clients=4, n_samples=1200, max_rounds=3, local_epochs=1,
                   serve_every=4.0, query_rate=1.0, query_batch=16,
                   prompt_len=0, new_tokens=0,
                   # bounded ledger with an aggressive cadence: replica
                   # frontiers MUST get pruned so eviction protection is
                   # actually exercised
                   ledger_checkpoint_every=4.0)
        if not quick:
            geo.update(n_clients=8, n_samples=2400, max_rounds=4)
        return geo
    geo = dict(n_clients=3, n_samples=512, max_rounds=2, local_epochs=1,
               serve_every=4.0, query_rate=0.5, query_batch=2,
               prompt_len=8, new_tokens=4,
               ledger_checkpoint_every=0.0)   # unbounded reference ledger
    if not quick:
        geo.update(n_clients=4, max_rounds=3, query_rate=1.0)
    return geo


def _run_serve(backend_kind: str, geo: Dict, seed: int):
    """One coordinator run with serving on; convergence tracking disabled
    (patience >> max_rounds) so every serving counter is a pure function
    of the seed."""
    from repro.core.coordinator import DagAflConfig, DagAflCoordinator
    from repro.core.simulator import CostModel, make_profiles
    from repro.fl.serving import ServingConfig

    backend, client_data, test = _WORLDS[backend_kind](
        geo["n_clients"], geo["n_samples"], geo["local_epochs"], seed)
    scfg = ServingConfig(every=geo["serve_every"],
                         query_rate=geo["query_rate"],
                         query_batch=geo["query_batch"],
                         prompt_len=max(geo["prompt_len"], 1),
                         new_tokens=max(geo["new_tokens"], 2),
                         seed=seed + 777, backend=backend_kind)
    cfg = DagAflConfig(
        n_clients=geo["n_clients"], max_rounds=geo["max_rounds"],
        local_epochs=geo["local_epochs"], seed=seed,
        target_accuracy=None, patience=10 ** 6,
        ledger_checkpoint_every=geo["ledger_checkpoint_every"],
        serving=scfg)
    t0 = time.time()
    coord = DagAflCoordinator(
        backend, client_data, test, cfg, CostModel(),
        make_profiles(geo["n_clients"], 1.0, seed))
    res = coord.run()
    return coord, res, time.time() - t0


def _parity_leg(backend_kind: str, coord, geo: Dict, seed: int) -> Dict:
    """Exact replica-vs-direct-aggregation parity on the FINAL replica."""
    from repro.fl.serving import (LMQueryDriver, consensus_over_refs,
                                  replica_parity, trees_bitwise_equal)
    replica = coord.publisher.replica()
    pinned = coord.publisher.pinned_refs()
    out = {
        "final_version": replica.version,
        "params_bitwise": bool(replica_parity(replica, coord.store)),
        "pinned_refs": len(pinned),
        "pinned_resident": all(r in coord.store for r in pinned),
    }
    direct = consensus_over_refs(coord.store, replica.model_refs)
    if backend_kind == "lm":
        drv = LMQueryDriver(coord.backend.cfg,
                            query_batch=geo["query_batch"],
                            prompt_len=geo["prompt_len"],
                            new_tokens=geo["new_tokens"], seed=seed)
        rng = np.random.default_rng(seed + 1)
        prompts = rng.integers(0, coord.backend.cfg.vocab_size,
                               (geo["query_batch"], geo["prompt_len"]))
        a = drv.decode_prompts(replica.params, prompts)
        b = drv.decode_prompts(direct, prompts)
        out["output_parity"] = bool(np.array_equal(a, b))
        out["parity_probe"] = "greedy_decode"
    else:
        acc_rep = coord.backend.evaluate(replica.params, coord.global_test,
                                         limit=256)
        acc_dir = coord.backend.evaluate(direct, coord.global_test, limit=256)
        out["output_parity"] = bool(acc_rep == acc_dir)
        out["parity_probe"] = "batched_eval"
    out["direct_bitwise"] = bool(trees_bitwise_equal(replica.params, direct))
    return out


def _counters(report: Dict) -> Dict:
    return {k: v for k, v in report.items() if k not in NONDETERMINISTIC_KEYS}


def run_serve_perf(backends: Optional[List[str]] = None, quick: bool = True,
                   seed: int = 0, out_dir: str = "experiments/fl",
                   determinism: bool = True) -> Dict:
    names = backends or BACKEND_ORDER
    report = {"kind": "serve", "quick": quick, "seed": seed, "backends": {}}
    for kind in names:
        geo = _geometry(quick, kind)
        print(f"# serve: backend '{kind}' (n={geo['n_clients']}, "
              f"rounds={geo['max_rounds']}, every={geo['serve_every']}s, "
              f"rate={geo['query_rate']}/s)", file=sys.stderr)
        coord, res, wall = _run_serve(kind, geo, seed)
        serving = res.extra["serving"]
        entry = {
            **geo,
            "serving": serving,
            "parity": _parity_leg(kind, coord, geo, seed),
            "rounds": res.rounds,
            "sim_time": res.sim_time,
            "n_pruned": getattr(coord.ledger, "n_pruned", 0),
            "wall_s": wall,
        }
        if determinism:
            coord2, res2, _ = _run_serve(kind, geo, seed)
            a, b = _counters(serving), _counters(res2.extra["serving"])
            entry["determinism"] = {
                "counters_match": a == b,
                "mismatched_keys": sorted(k for k in a
                                          if a.get(k) != b.get(k)),
            }
        report["backends"][kind] = entry
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, "serve_perf.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# serve report -> {out_path}", file=sys.stderr)
    return report


def rows(report: Dict) -> List[str]:
    """``name,us_per_call,derived`` CSV rows (benchmarks/run.py convention):
    derived = queries served; us_per_call = mean seq-staleness."""
    out = []
    for kind, b in report["backends"].items():
        s = b["serving"]
        out.append(f"serve_queries[{kind}],"
                   f"{s['mean_seq_lag']:.4f},{s['queries']}")
        out.append(f"serve_replicas[{kind}],"
                   f"{s['max_seq_lag']:.1f},{s['replica_versions']}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized geometry")
    ap.add_argument("--backend", action="append", default=None,
                    choices=BACKEND_ORDER,
                    help="run only this backend (repeatable; default: both)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default="experiments/fl")
    ap.add_argument("--no-determinism", action="store_true",
                    help="skip the same-seed rerun (faster local iteration; "
                         "the CI gate requires the determinism leg)")
    args = ap.parse_args()
    report = run_serve_perf(backends=args.backend, quick=args.quick,
                            seed=args.seed, out_dir=args.out_dir,
                            determinism=not args.no_determinism)
    print("name,us_per_call,derived")
    for r in rows(report):
        print(r)


if __name__ == "__main__":
    main()
