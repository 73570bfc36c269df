"""CI perf-regression gate for the cohort engine and the bounded ledger.

Dispatches on the results file's ``kind`` field: ``ledger_day`` results
(written by ``benchmarks/ledger_perf.py``) are gated on the bounded-frontier
invariants under the ``ledger_day`` thresholds sub-dict; ``robustness``
results (``benchmarks/robustness.py``) on fault-event counts and accuracy
deltas; ``serve`` results (``benchmarks/serve_perf.py``) on deterministic
serving counters (replica versions, queries, seq-staleness) plus exact
replica-vs-direct Eq. 6 parity flags; ``kernel_perf`` results on analytic
memory-footprint ratios; everything else is a cohort smoke (written by
``benchmarks/chain_perf.py --cohort-size K``).
Both compare against the checked-in floors in
``benchmarks/baseline_thresholds.json`` and exit non-zero on regression.

Cohort smoke:

  * ``speedup``            — vectorized cohort engine vs the sequential
                             path; must stay above ``cohort_speedup_min``
                             (times ``quick_speedup_factor`` under
                             ``--quick``, matching the smaller CI geometry).
  * ``accuracy_gap``       — cohort vs sequential final accuracy; the
                             engines must agree on learning outcome.
  * ``mesh_accuracy_gap``  — (only present when the smoke ran with
                             ``--mesh``) sharded SPMD vs single-device
                             cohort accuracy; mesh partitioning must not
                             change numerics.  This covers the 2-D
                             (clients, data) mesh too: a ``--mesh CxD``
                             smoke's gap compares data-sharded gradients
                             against the single-device path, and
                             ``--require-data-axis`` pins CI to actually
                             exercising it.

The sharded wall-clock is reported but NOT gated: on CI's 2-core runners a
forced 8-device host mesh oversubscribes cores, so its speedup measures the
runner, not the code.  Correctness of the sharded path is gated through
``mesh_accuracy_gap`` and the test suite instead.

A cohort smoke run with ``--kernels on`` additionally carries a kernel-path
A/B leg (Pallas dispatch vs the incumbent jnp math, same engine, same
seed), gated under the same sub-dict:

  * ``kernels_accuracy_gap`` — must stay within
    ``kernels_accuracy_gap_max`` (0.0: Eq. 3 signatures are bit-stable by
    contract, so the kernel path must reproduce the jnp run's learning
    outcome EXACTLY, not approximately).
  * ``kernels_tip_decisions_identical`` — the two runs' full publish
    traces (per-transaction ``(client, epoch)`` plus the sorted parent
    set each tip selection chose) must match transaction for transaction;
    signature drift changes DAG topology, and this is the field that
    catches it.  ``--require-kernels`` pins a CI leg to having run the
    A/B at all.

Kernel micro-benchmarks (``kind: kernel_perf``, written by
``benchmarks/kernel_perf.py``) are gated under the ``kernel_perf``
thresholds sub-dict:

  * ``<name>_intermediate_ratio_max`` — ANALYTIC kernel-vs-jnp
    intermediate-footprint ratio per op (derived from shapes, so it is
    deterministic on any runner).  The signature ceilings assert the
    core claim of the swap: the kernel must NOT materialize the (T, d)
    flag tensor the jnp path does.
  * ``signature_rel_time_max``  — generous wall-clock parity ceiling for
    the Eq. 3 bucket kernel vs jnp.  CI runs the INTERPRETER (an
    emulation), so this only catches order-of-magnitude pathologies;
    the ratio ceilings above carry the real gate.  Other ops' wall-clock
    is reported, never gated (the per-channel interpreter emulation is
    legitimately slower than fused XLA on tiny CPU shapes).
  * the records must cover all three swapped hot-path ops.

Ledger day-in-the-life (``kind: ledger_day``):

  * ``peak_live_frac``     — peak live-transaction count as a fraction of
                             all published transactions; must stay under
                             ``peak_live_frac_max`` — memory is bounded by
                             the consensus frontier, not by history.
  * ``peak_store_frac``    — same bound for ModelStore entries: pruning
                             must evict model bodies, not just metadata.
  * ``pruned_frac``        — at least ``pruned_frac_min`` of history must
                             actually have been folded into checkpoints.
  * ``select_work_vs_history`` — deterministic per-selection ledger work
                             (reachability log entries + BFS visits +
                             tip-heap pops) over the last quarter of
                             rounds, as a fraction of total transactions;
                             must stay under
                             ``select_work_vs_history_max``.  A
                             linear-in-history implementation (whole-DAG
                             BFS, all-tips scan) scores ~1; index-backed
                             selection sits orders of magnitude below.
  * ``audit_tx_ratio``     — the incremental verifier must have re-derived
                             every transaction's Eq. 7 hash at least once
                             (``audit_tx_ratio_min``).
  * ``verify_ok``          — every incremental audit plus the final full
                             verification passed.

The ledger gate is wall-clock-free by construction — every gated quantity
is an event count, so a loaded CI runner cannot flake it.

Robustness suite (``kind: robustness``, written by
``benchmarks/robustness.py``), per scenario under the ``robustness``
thresholds sub-dict:

  * deterministic event counts — the scenario's primary fault counter must
    be nonzero AND the same-seed rerun must report identical counts and
    tamper-detection sets (``determinism`` leg).
  * ``accuracy_delta_max``    — DAG-AFL's honest-vs-attacked accuracy drop
                                (honest clients' models) must stay under the
                                per-scenario floor.
  * ``poison_advantage_min``  — (poison only) fedavg's AND fedasync's
                                accuracy delta must exceed DAG-AFL's by at
                                least this margin: the DAG defense must
                                demonstrably beat the defenseless baselines.
  * ``poisoned_tip_approval_rate_max`` — (poison only) how often honest tip
                                selection approved a malicious tx.
  * tamper detection          — (poison only) nonzero tampered txs, every
                                one detected by the Eq. 7 sweep, and the
                                incremental verifier flagged the ledger.

Accuracy-DELTA floors are gated (a run-to-run borderline flip moves both
legs of the subtraction together at fixed seeds); wall-clock never is.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_THRESHOLDS = os.path.join(os.path.dirname(__file__),
                                  "baseline_thresholds.json")


def active_thresholds(thresholds: dict, results: dict) -> dict:
    """Per-backend floors: the top-level keys gate the default (cnn) smoke;
    a sub-dict keyed by the results' ``backend`` field (e.g. ``"lm"``)
    overrides them for that suite's smoke."""
    sub = thresholds.get(results.get("backend", "cnn"))
    if isinstance(sub, dict):
        merged = {k: v for k, v in thresholds.items()
                  if not isinstance(v, dict)}
        merged.update(sub)
        return merged
    return thresholds


def check_ledger(results: dict, thresholds: dict) -> list:
    """Gate a ``ledger_day`` results file (see module docstring)."""
    failures = []
    t = thresholds.get("ledger_day", {})

    def over(key, limit_key):
        limit = t[limit_key]
        val = results.get(key)
        if val is None:
            failures.append(f"results carry no '{key}' field")
        elif val > limit:
            failures.append(f"{key} {val:.4f} above {limit:.4f}")

    over("peak_live_frac", "peak_live_frac_max")
    over("peak_store_frac", "peak_store_frac_max")
    over("select_work_vs_history", "select_work_vs_history_max")
    pruned = results.get("pruned_frac", 0.0)
    if pruned < t["pruned_frac_min"]:
        failures.append(f"pruned_frac {pruned:.4f} below "
                        f"{t['pruned_frac_min']:.4f}")
    audited = results.get("audit_tx_ratio", 0.0)
    if audited < t["audit_tx_ratio_min"]:
        failures.append(f"audit_tx_ratio {audited:.4f} below "
                        f"{t['audit_tx_ratio_min']:.4f} — the incremental "
                        "verifier did not cover every append")
    if not results.get("verify_ok", False):
        failures.append("verify_ok is false — an incremental audit or the "
                        "final full verification failed")
    return failures


# each scenario's primary fault counter (mirrors
# benchmarks/robustness.py EVENT_KEYS; duplicated so the gate stays
# importable without the repro package)
ROBUSTNESS_EVENT_KEYS = {
    "poison": "updates_scaled", "lazy": "updates_lazy",
    "dp": "updates_noised", "straggler": "straggler_draws",
    "dropout": "publishes_dropped",
}


def check_robustness(results: dict, thresholds: dict) -> list:
    """Gate a ``kind=robustness`` results file (see module docstring)."""
    failures = []
    t = thresholds.get("robustness", {})
    for name, s in results.get("scenarios", {}).items():
        st = t.get(name, {})
        counts = s.get("counts", {})
        event_key = ROBUSTNESS_EVENT_KEYS.get(name)
        if event_key and counts.get(event_key, 0) < 1:
            failures.append(f"{name}: no fault events injected "
                            f"({event_key}=0) — the scenario did nothing")
        det = s.get("determinism")
        if t.get("determinism_required", True):
            if det is None:
                failures.append(f"{name}: no determinism leg (run without "
                                "--no-determinism)")
            elif not (det.get("counts_match")
                      and det.get("detections_match")):
                failures.append(f"{name}: same-seed rerun diverged "
                                f"(counts_match={det.get('counts_match')}, "
                                f"detections_match="
                                f"{det.get('detections_match')})")
        dag_delta = s["methods"]["dagafl"]["accuracy_delta"]
        delta_max = st.get("accuracy_delta_max")
        if delta_max is not None and dag_delta > delta_max:
            failures.append(f"{name}: dagafl honest-vs-attacked delta "
                            f"{dag_delta:.4f} above {delta_max:.4f}")
        adv_min = st.get("poison_advantage_min")
        if adv_min is not None:
            for algo in ("fedavg", "fedasync"):
                m = s["methods"].get(algo)
                if m is None:
                    failures.append(f"{name}: no {algo} comparison leg")
                    continue
                adv = m["accuracy_delta"] - dag_delta
                if adv < adv_min:
                    failures.append(
                        f"{name}: dagafl advantage over {algo} "
                        f"{adv:.4f} below {adv_min:.4f} (the DAG defense "
                        f"must beat the defenseless baseline)")
        dag = s.get("dag", {})
        rate_max = st.get("poisoned_tip_approval_rate_max")
        if rate_max is not None:
            rate = dag.get("poisoned_tip_approval_rate", 1.0)
            if rate > rate_max:
                failures.append(f"{name}: poisoned-tip approval rate "
                                f"{rate:.4f} above {rate_max:.4f}")
        if st.get("require_tamper_detection"):
            if dag.get("txs_tampered", 0) < 1:
                failures.append(f"{name}: no txs were tampered — the Eq. 7 "
                                "audit was never exercised")
            if not dag.get("detections_exact"):
                failures.append(f"{name}: Eq. 7 sweep did not return "
                                f"exactly the tampered set "
                                f"(tampered={dag.get('txs_tampered')}, "
                                f"detected={dag.get('tamper_detections')})")
            if not dag.get("incremental_audit_flagged"):
                failures.append(f"{name}: IncrementalVerifier did not flag "
                                "the tampered ledger")
    if not results.get("scenarios"):
        failures.append("results carry no scenarios")
    return failures


def check_serve(results: dict, thresholds: dict) -> list:
    """Gate a ``kind=serve`` results file (benchmarks/serve_perf.py).

    Everything gated is a deterministic event count (replica versions,
    queries served, staleness in ledger append seqs) or an exact-parity
    flag; wall-clock throughput is reported, never gated.  Per-backend
    floors live under the ``serve`` thresholds sub-dict, keyed by backend.
    """
    failures = []
    t = thresholds.get("serve", {})
    backends = results.get("backends", {})
    if not backends:
        failures.append("results carry no backends")
    for name, b in backends.items():
        bt = {k: v for k, v in t.items() if not isinstance(v, dict)}
        bt.update(t.get(name, {}))
        s = b.get("serving", {})

        def floor(key, floor_key):
            limit = bt.get(floor_key)
            if limit is not None and s.get(key, 0) < limit:
                failures.append(f"{name}: {key} {s.get(key, 0)} below "
                                f"{limit} — serving never got going")

        def ceiling(key, ceil_key):
            limit = bt.get(ceil_key)
            if limit is not None and s.get(key, 0) > limit:
                failures.append(f"{name}: {key} {s.get(key, 0)} above "
                                f"{limit} — replicas went stale past the "
                                "publish-cadence budget")

        floor("replica_versions", "replica_versions_min")
        floor("queries", "queries_min")
        floor("distinct_versions_served", "distinct_versions_min")
        ceiling("max_seq_lag", "max_seq_lag_max")
        ceiling("mean_seq_lag", "mean_seq_lag_max")
        if s.get("skipped", 0) != 0:
            failures.append(f"{name}: {s['skipped']} queries arrived before "
                            "any replica existed — the publisher must "
                            "publish v0 at start")
        par = b.get("parity", {})
        for flag in ("params_bitwise", "direct_bitwise", "output_parity",
                     "pinned_resident"):
            if not par.get(flag, False):
                failures.append(
                    f"{name}: parity flag '{flag}' is false — the replica "
                    "is not bit-identical to direct Eq. 6 aggregation over "
                    "its frontier (probe: "
                    f"{par.get('parity_probe', '?')})")
        if bt.get("require_pruning") and b.get("n_pruned", 0) < 1:
            failures.append(f"{name}: bounded-ledger leg pruned nothing — "
                            "eviction protection was never exercised")
        det = b.get("determinism")
        if t.get("determinism_required", True):
            if det is None:
                failures.append(f"{name}: no determinism leg (run without "
                                "--no-determinism)")
            elif not det.get("counters_match"):
                failures.append(
                    f"{name}: same-seed rerun diverged on counters "
                    f"{det.get('mismatched_keys')}")
    return failures


# the three hot-path swaps kernel_perf.py must cover (ISSUE 9 tentpole)
KERNEL_PERF_OPS = ("signature", "signature_per_channel", "flash_attention")


def check_kernel_perf(results: dict, thresholds: dict) -> list:
    """Gate a ``kind=kernel_perf`` results file (see module docstring)."""
    failures = []
    t = thresholds.get("kernel_perf", {})
    kernels = results.get("kernels") or []
    if not kernels:
        failures.append("results carry no kernel records")
    seen = {r.get("name") for r in kernels}
    for op in KERNEL_PERF_OPS:
        if op not in seen:
            failures.append(f"no '{op}' records — the micro-bench must "
                            "cover every swapped hot-path op")
    for r in kernels:
        name = r.get("name", "?")
        tag = f"{name}{r.get('shape')}"
        ratio_max = t.get(f"{name}_intermediate_ratio_max")
        if ratio_max is not None:
            ratio = r.get("intermediate_ratio")
            if ratio is None:
                failures.append(f"{tag}: no intermediate_ratio field")
            elif ratio > ratio_max:
                failures.append(
                    f"{tag}: kernel-vs-jnp intermediate footprint ratio "
                    f"{ratio:.4f} above {ratio_max:.4f} — the kernel path "
                    "materializes an intermediate it promised to stream")
        rel_max = t.get(f"{name}_rel_time_max")
        if rel_max is not None:
            rel = r.get("rel_time")
            if rel is None:
                failures.append(f"{tag}: no rel_time field")
            elif rel > rel_max:
                failures.append(f"{tag}: kernel wall-clock {rel:.2f}x jnp, "
                                f"above the {rel_max:.2f}x parity ceiling")
    return failures


def check_kernels_ab(results: dict, thresholds: dict) -> list:
    """Gate the cohort smoke's ``--kernels on`` A/B fields when present."""
    failures = []
    kgap = results.get("kernels_accuracy_gap")
    if kgap is None:
        return failures
    kmax = thresholds.get("kernels_accuracy_gap_max", 0.0)
    if kgap > kmax:
        failures.append(f"kernel-vs-jnp accuracy gap {kgap:.6f} above "
                        f"{kmax:.6f} — Eq. 3 signatures must be bit-stable "
                        "across dispatch policies")
    if not results.get("kernels_tip_decisions_identical", False):
        failures.append("kernel-path run made different tip-selection "
                        "decisions than the jnp run — signature drift "
                        "changed the DAG topology")
    return failures


def check(results: dict, thresholds: dict, quick: bool = False) -> list:
    """Returns a list of failure strings (empty = gate passes)."""
    if results.get("kind") == "ledger_day":
        return check_ledger(results, thresholds)
    if results.get("kind") == "robustness":
        return check_robustness(results, thresholds)
    if results.get("kind") == "kernel_perf":
        return check_kernel_perf(results, thresholds)
    if results.get("kind") == "serve":
        return check_serve(results, thresholds)
    failures = []
    thresholds = active_thresholds(thresholds, results)
    floor = thresholds["cohort_speedup_min"]
    if quick:
        floor *= thresholds.get("quick_speedup_factor", 1.0)
    speedup = results.get("speedup")
    if speedup is None:
        failures.append("results carry no 'speedup' field — did the smoke "
                        "run with --cohort-size?")
    elif speedup < floor:
        failures.append(f"cohort speedup {speedup:.2f}x below floor "
                        f"{floor:.2f}x")

    gap = results.get("accuracy_gap")
    gap_max = thresholds["accuracy_gap_max"]
    if gap is not None and gap > gap_max:
        failures.append(f"cohort-vs-sequential accuracy gap {gap:.4f} above "
                        f"{gap_max:.4f}")

    mesh_gap = results.get("mesh_accuracy_gap")
    if mesh_gap is not None:
        mesh_max = thresholds["mesh_accuracy_gap_max"]
        if mesh_gap > mesh_max:
            failures.append(f"sharded-vs-single-device accuracy gap "
                            f"{mesh_gap:.4f} above {mesh_max:.4f}")
    failures += check_kernels_ab(results, thresholds)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("results", nargs="?",
                    default="experiments/fl/cohort_speedup.json",
                    help="cohort smoke results json")
    ap.add_argument("--thresholds", default=DEFAULT_THRESHOLDS)
    ap.add_argument("--quick", action="store_true",
                    help="apply the quick-mode speedup tolerance")
    ap.add_argument("--require-mesh", action="store_true",
                    help="fail unless the results carry the sharded-engine "
                         "fields (the smoke must have run with --mesh on a "
                         "multi-device host)")
    ap.add_argument("--require-data-axis", action="store_true",
                    help="fail unless the sharded run used a 2-D (clients, "
                         "data) mesh with data > 1 (the smoke must have run "
                         "with --mesh CxD, D >= 2, on a host with enough "
                         "devices)")
    ap.add_argument("--require-kernels", action="store_true",
                    help="fail unless the cohort smoke carries the kernel "
                         "A/B fields (it must have run with --kernels on)")
    args = ap.parse_args()

    with open(args.results) as f:
        results = json.load(f)
    with open(args.thresholds) as f:
        thresholds = json.load(f)

    failures = check(results, thresholds, quick=args.quick)
    if results.get("kind") == "ledger_day":
        print(f"perf gate[ledger_day, n={results.get('n_clients')}]: "
              f"peak_live_frac="
              f"{results.get('peak_live_frac', float('nan')):.3f} "
              f"peak_store_frac="
              f"{results.get('peak_store_frac', float('nan')):.3f} "
              f"pruned_frac={results.get('pruned_frac', float('nan')):.3f} "
              f"work_vs_history="
              f"{results.get('select_work_vs_history', float('nan')):.4f} "
              f"audit_tx_ratio="
              f"{results.get('audit_tx_ratio', float('nan')):.2f} "
              f"verify_ok={results.get('verify_ok')}")
        if failures:
            for msg in failures:
                print(f"PERF GATE FAIL: {msg}", file=sys.stderr)
            sys.exit(1)
        print("perf gate: PASS")
        return
    if results.get("kind") == "robustness":
        for name, s in results.get("scenarios", {}).items():
            dagafl = s["methods"]["dagafl"]
            det = s.get("determinism", {})
            dag = s.get("dag", {})
            print(f"perf gate[robustness/{name}]: "
                  f"delta={dagafl['accuracy_delta']:+.3f} "
                  f"approval={dag.get('poisoned_tip_approval_rate', 0):.3f} "
                  f"tampered/detected={dag.get('txs_tampered', 0)}/"
                  f"{dag.get('tamper_detections', 0)} "
                  f"deterministic={bool(det.get('counts_match')) and bool(det.get('detections_match'))}")
        if failures:
            for msg in failures:
                print(f"PERF GATE FAIL: {msg}", file=sys.stderr)
            sys.exit(1)
        print("perf gate: PASS")
        return
    if results.get("kind") == "serve":
        for name, b in results.get("backends", {}).items():
            s = b.get("serving", {})
            det = b.get("determinism", {})
            par = b.get("parity", {})
            print(f"perf gate[serve/{name}]: "
                  f"replicas={s.get('replica_versions')} "
                  f"queries={s.get('queries')} "
                  f"seq_lag={s.get('mean_seq_lag', float('nan')):.2f}/"
                  f"{s.get('max_seq_lag')} (mean/max) "
                  f"versions_served={s.get('distinct_versions_served')} "
                  f"parity={par.get('params_bitwise')}/"
                  f"{par.get('output_parity')} "
                  f"deterministic={det.get('counters_match')}")
        if failures:
            for msg in failures:
                print(f"PERF GATE FAIL: {msg}", file=sys.stderr)
            sys.exit(1)
        print("perf gate: PASS")
        return
    if results.get("kind") == "kernel_perf":
        print(f"perf gate[kernel_perf, {results.get('policy')} on "
              f"{results.get('platform')}]:")
        for r in results.get("kernels", []):
            print(f"  {r.get('name', '?'):>22} {str(r.get('shape')):>18}: "
                  f"rel_time x{r.get('rel_time', float('nan')):.2f} "
                  f"intermediate_ratio "
                  f"x{r.get('intermediate_ratio', float('nan')):.4f}")
        if failures:
            for msg in failures:
                print(f"PERF GATE FAIL: {msg}", file=sys.stderr)
            sys.exit(1)
        print("perf gate: PASS")
        return
    if args.require_mesh and "mesh_accuracy_gap" not in results:
        failures.append("--require-mesh: no sharded-engine results; the "
                        "multi-device smoke did not exercise shard_map")
    if args.require_data_axis and results.get("mesh_data_devices", 1) < 2:
        failures.append("--require-data-axis: the smoke did not exercise "
                        "the 2-D (clients, data) mesh (mesh_data_devices="
                        f"{results.get('mesh_data_devices', 1)})")
    if args.require_kernels and "kernels_accuracy_gap" not in results:
        failures.append("--require-kernels: no kernel A/B fields; the "
                        "smoke did not run with --kernels on")

    kern = ""
    if "kernels_accuracy_gap" in results:
        kern = (f" kernels[{results.get('kernels_policy')}]: "
                f"acc_gap={results['kernels_accuracy_gap']:.6f} "
                f"tips_identical="
                f"{results.get('kernels_tip_decisions_identical')} "
                f"rel_wall=x{results.get('kernels_rel_wall', float('nan')):.2f}")
    print(f"perf gate[{results.get('backend', 'cnn')}"
          f"{',' + results['mesh_shape'] if 'mesh_shape' in results else ''}"
          f"]: speedup={results.get('speedup', float('nan')):.2f}x "
          f"acc_gap={results.get('accuracy_gap', float('nan')):.4f} "
          f"mesh_acc_gap={results.get('mesh_accuracy_gap', float('nan')):.4f}"
          f" sharded_speedup="
          f"{results.get('sharded_speedup', float('nan')):.2f}x"
          f" (quick={args.quick}){kern}")
    if failures:
        for msg in failures:
            print(f"PERF GATE FAIL: {msg}", file=sys.stderr)
        sys.exit(1)
    print("perf gate: PASS")


if __name__ == "__main__":
    main()
