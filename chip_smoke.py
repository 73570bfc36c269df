#!/usr/bin/env python3
"""Chip smoke of the DAG-AFL main path: proves the system runs on a TPU.

One chip (no arguments) runs two phases at published widths:

  A  VGG16 federated rounds: ``DagAflCoordinator.run()`` with the cohort
     engine (cohort of 8) and a bounded ledger on seeded 32x32x3, 10-class
     synthetic images — once with the Pallas signature kernels compiled
     for the chip and once on the jnp reference path.  The Eq. 3
     signatures must be bit-equal, every transaction's tip selection
     identical, the full DAG must verify and accuracy must be finite.
  B  internlm2-1.8b replica serving (24 layers, d_model 2048, 16/8 heads,
     vocab 92544; f32 params, bf16 compute): prefill 4 x 128 tokens and
     greedy-decode 16 through ``launch/serve`` with compiled kernels and
     with the stock XLA path.  Per-sample Eq. 3 signature rows of the
     final-norm hidden state must be bit-equal, prefill logits close at
     LOGIT_RTOL, and a greedy token may differ only at a near tie.

``--chips 4`` runs only the mesh phase: one Phase A cohort window (train,
global-test eval, signatures) on a 4 and a 2x2 ``clients x data`` mesh
against the same window with ``mesh=None``: signatures identical, trained
parameters allclose, accuracy within one test-set prediction quantum, and
client groups on all four devices.

Every phase runs in this one process and starts no other (a chip belongs
to one process at a time).  Without a TPU the script exits non-zero before
any phase.  Weights and data come from ``--seed``.  The last line of
output is one JSON object, ``{"ok": true, "device": {...}}``; a failed
check makes the exit code non-zero.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip mesh phase
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# prefill logits of the kernel path vs the stock XLA path: max |diff| over
# the reference logits' max |value| (bf16 compute through 24 layers)
LOGIT_RTOL = 0.05
# trained-parameter budget, mesh vs single device (tests/test_cohort_mesh)
PARAM_ATOL = 5e-3
COHORT = 8
# VGG16 images per client step: at 64 the K=8 cohort train program needs
# 9.2 GB of temporaries on a v5e, too close to 16 GB beside the model store
BATCH = 32


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


class CompileClock:
    """Seconds spent in XLA backend compiles since construction."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def require_tpu(n_chips: int):
    """The accelerator devices, or SystemExit naming what JAX found."""
    import jax
    devices = jax.devices()
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU; JAX found platform {platform!r} "
            f"({len(devices)} x {devices[0].device_kind}).  This script "
            f"runs only on the chip.")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, found {len(devices)}")
    return devices[:n_chips]


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Phase A: VGG16 federated rounds
# ---------------------------------------------------------------------------


def cnn_world(cfg, n_clients: int, n_samples: int, seed: int):
    """Dirichlet-partitioned client shards of seeded synthetic images in
    ``cfg``'s input shape; returns (client_data, global_test)."""
    from repro.data import partition_dirichlet, split_811
    from repro.data.synthetic import make_image_dataset
    ds = make_image_dataset("cifar10", n_samples, n_classes=cfg.n_classes,
                            size=cfg.image_size, channels=cfg.in_channels,
                            noise=0.55, seed=seed)
    splits = split_811(ds, seed=seed)
    parts = partition_dirichlet(splits["train"], n_clients, beta=1.0,
                                seed=seed)
    return [split_811(p, seed=seed + 1) for p in parts], splits["test"]


def _federate(cfg, world, policy, *, n_clients, max_rounds, local_epochs,
              seed):
    """One DAG-AFL run; returns (coordinator, result, published sigs, s)."""
    import numpy as np

    from repro.core.coordinator import DagAflConfig, DagAflCoordinator
    from repro.core.simulator import CostModel, make_profiles
    from repro.core.tip_selection import TipSelectionConfig
    from repro.fl.backend import CNNBackend

    client_data, test = world
    backend = CNNBackend(cfg, local_epochs=local_epochs, batch_size=BATCH,
                         kernel_policy=policy)
    dcfg = DagAflConfig(n_clients=n_clients, max_rounds=max_rounds,
                        local_epochs=local_epochs,
                        tip=TipSelectionConfig(n_select=2), seed=seed,
                        cohort_size=COHORT, cohort_window=2.0, mesh=None,
                        kernel_policy=policy, ledger_checkpoint_every=4.0)
    coord = DagAflCoordinator(backend, client_data, test, dcfg,
                              CostModel(local_epoch=2.0),
                              make_profiles(n_clients, 0.5, seed))
    sigs = []
    post = coord.contract.post_signature

    def record(client, sig):          # every published Eq. 3 signature
        sigs.append((client, np.asarray(sig, np.float32)))
        post(client, sig)

    coord.contract.post_signature = record
    t0 = time.perf_counter()
    res = coord.run()                 # returns host floats: device synced
    return coord, res, sigs, time.perf_counter() - t0


def _sig_program_text(coord, cfg, n: int = 128) -> str:
    """StableHLO of the cohort engine's signature program at a window of
    ``COHORT`` clients."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregate import tree_stack
    stacked = tree_stack([coord.backend.init(jax.random.PRNGKey(0))] * COHORT)
    x = jnp.zeros((COHORT, n, cfg.image_size, cfg.image_size,
                   cfg.in_channels), jnp.float32)
    mask = jnp.ones((COHORT, n), jnp.float32)
    return coord.cohort._sig_jit.lower(stacked, x, mask).as_text()


def phase_a(cfg, *, policy: str, expect_custom_call: bool,
            n_clients: int = 8, n_samples: int = 4000, max_rounds: int = 2,
            local_epochs: int = 1, seed: int = 0) -> dict:
    """Federated rounds with ``policy`` kernels vs the reference path."""
    import numpy as np

    from benchmarks.chain_perf import _tip_decisions
    from repro.core.verify import verify_full_dag

    world = cnn_world(cfg, n_clients, n_samples, seed)
    runs = {}
    for name, pol in (("kernel", policy), ("reference", "reference")):
        coord, res, sigs, wall = _federate(
            cfg, world, pol, n_clients=n_clients, max_rounds=max_rounds,
            local_epochs=local_epochs, seed=seed)
        ok, why = verify_full_dag(coord.ledger)
        runs[name] = {
            "wall_s": wall, "rounds": res.rounds,
            "cohorts": res.extra["cohorts_dispatched"],
            "final_accuracy": res.final_accuracy, "verify": (ok, why),
            "sigs": sigs, "decisions": _tip_decisions(coord),
            "custom_call": "tpu_custom_call" in _sig_program_text(coord, cfg),
        }
        del coord, res
        gc.collect()
    k, r = runs["kernel"], runs["reference"]
    sig_equal = (len(k["sigs"]) == len(r["sigs"]) and all(
        ca == cb and np.array_equal(sa, sb)
        for (ca, sa), (cb, sb) in zip(k["sigs"], r["sigs"])))
    checks = {
        "signatures_bit_equal": sig_equal,
        "tip_decisions_identical": k["decisions"] == r["decisions"],
        "dag_verifies": k["verify"][0] and r["verify"][0],
        "accuracy_finite": all(math.isfinite(x["final_accuracy"])
                               for x in (k, r)),
        "signature_program_kernel": (
            k["custom_call"] == expect_custom_call and not r["custom_call"]),
    }
    return {
        "checks": checks,
        "policy": policy,
        "published_signatures": len(k["sigs"]),
        "transactions_compared": len(k["decisions"]),
        **{f"{n}_{f}": runs[n][f] for n in runs
           for f in ("wall_s", "rounds", "cohorts", "final_accuracy")},
        "verify": [k["verify"][1], r["verify"][1]],
    }


# ---------------------------------------------------------------------------
# Phase B: internlm2-1.8b replica serving
# ---------------------------------------------------------------------------


def phase_b(cfg, *, policy: str, expect_custom_call: bool, batch: int = 4,
            prompt_len: int = 128, new_tokens: int = 16,
            seed: int = 0) -> dict:
    """Prefill + greedy decode with ``policy`` kernels vs stock XLA."""
    import jax
    import numpy as np

    from repro.launch.serve import greedy_decode, make_serving_fns
    from repro.models import transformer as tfm
    from repro.runtime import Runtime, serve_runtime

    k_params, k_prompt = jax.random.split(jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    params = jax.jit(tfm.init_params, static_argnums=1)(k_params, cfg)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    prompts = {"tokens": jax.random.randint(
        k_prompt, (batch, prompt_len), 0, cfg.vocab_size)}

    out = {}
    for name, pol in (("kernel", policy), ("reference", None)):
        prefill, decode = make_serving_fns(cfg, serve_runtime(pol))
        t0 = time.perf_counter()
        greedy_decode(prefill, decode, cfg, params, prompts, new_tokens)
        first_s = time.perf_counter() - t0          # includes compiles
        r = greedy_decode(prefill, decode, cfg, params, prompts, new_tokens)
        logits = prefill(params, prompts)[0]
        out[name] = {
            "first_call_s": first_s, "prefill_s": r["prefill_s"],
            "decode_s": r["decode_s"],
            "tokens": np.asarray(r["tokens"]),
            "margins": np.asarray(r["margins"]),
            "logits": np.asarray(logits),
            "custom_call": "tpu_custom_call" in prefill.lower(
                params, prompts).as_text(),
        }

    # Eq. 3 rows on ONE final-norm hidden state (the kernel path's prefill)
    hidden = jax.jit(lambda p, b: tfm.forward_hidden(
        p, b, cfg, serve_runtime(policy), mode="prefill")[0])(params, prompts)
    sig_rt = {"kernel": Runtime(use_pallas=True, kernel_policy=policy),
              "reference": Runtime()}
    sig_fns = {n: jax.jit(lambda h, rt=rt: tfm.per_sample_signature(h, rt))
               for n, rt in sig_rt.items()}
    sig = {n: np.asarray(f(hidden)) for n, f in sig_fns.items()}
    sig_custom_call = "tpu_custom_call" in sig_fns["kernel"].lower(
        hidden).as_text()
    del params, hidden
    gc.collect()

    k, r = out["kernel"], out["reference"]
    scale = float(np.max(np.abs(r["logits"])))
    max_diff = float(np.max(np.abs(k["logits"] - r["logits"])))
    tol = LOGIT_RTOL * scale
    # per row: first greedy divergence and the reference's top-2 margin
    # there (later steps decode different contexts and say nothing more)
    mismatches = []
    for b in range(batch):
        diff = np.nonzero(k["tokens"][b] != r["tokens"][b])[0]
        if diff.size:
            j = int(diff[0])
            mismatches.append({"row": b, "step": j,
                               "ref_margin": float(r["margins"][b, j])})
    checks = {
        "signature_rows_bit_equal": (
            sig["kernel"].shape == (batch, 64)
            and np.array_equal(sig["kernel"], sig["reference"])),
        "prefill_logits_close": max_diff <= tol,
        "greedy_mismatch_only_at_near_tie": all(
            m["ref_margin"] <= 2 * tol for m in mismatches),
        "logits_finite": bool(np.all(np.isfinite(k["logits"]))),
        "kernels_in_programs": (
            k["custom_call"] == expect_custom_call
            and sig_custom_call == expect_custom_call
            and not r["custom_call"]),
    }
    return {
        "checks": checks, "policy": policy,
        "params": cfg.param_count(), "init_s": init_s,
        "logits_max_abs_diff": max_diff, "logits_max_abs_ref": scale,
        "logits_tol": tol,
        "logits_rms_diff": float(np.sqrt(np.mean(
            (k["logits"] - r["logits"]) ** 2))),
        "greedy_tokens_equal": bool(np.array_equal(k["tokens"],
                                                   r["tokens"])),
        "greedy_first_mismatches": mismatches,
        "signature_rows": list(sig["kernel"].shape),
        **{f"{n}_{f}": out[n][f] for n in out
           for f in ("first_call_s", "prefill_s", "decode_s")},
    }


# ---------------------------------------------------------------------------
# mesh phase (--chips 4)
# ---------------------------------------------------------------------------


def _window(cfg, world, policy, mesh_spec, *, stacked, local_epochs, seed):
    """One cohort window (train, global-test eval, signatures) on an
    engine built for ``mesh_spec`` exactly as ``DagAflConfig.mesh`` is."""
    import jax
    import numpy as np

    from repro.core.aggregate import tree_unstack
    from repro.fl.backend import CNNBackend
    from repro.fl.cohort import build_cohort_engine

    client_data, test = world
    train = [c["train"] for c in client_data[:COHORT]]
    backend = CNNBackend(cfg, local_epochs=local_epochs, batch_size=BATCH,
                         kernel_policy=policy)
    engine = build_cohort_engine(backend, train, cohort_size=COHORT,
                                 mesh=mesh_spec, epochs=local_epochs,
                                 kernel_policy=policy)
    t0 = time.perf_counter()
    sig_in = engine.signature_cohort_stacked(stacked, train)
    trained, _ = engine.train_cohort_stacked(
        stacked, train, [seed + c for c in range(COHORT)], local_epochs)
    jax.block_until_ready(trained)
    acc = engine.evaluate_many(tree_unstack(trained), test)
    sig_out = engine.signature_cohort_stacked(trained, train)
    wall = time.perf_counter() - t0
    held = sorted({d.id for leaf in jax.tree_util.tree_leaves(trained)
                   for d in leaf.sharding.device_set})
    mesh = engine.mesh
    return {
        "mesh": None if mesh is None else dict(mesh.shape),
        "mesh_devices": [] if mesh is None else sorted(
            d.id for d in mesh.devices.flat),
        "devices_holding_clients": held, "wall_s": wall,
        "sig_in": sig_in, "sig_out": sig_out, "acc": np.asarray(acc),
        "params": jax.tree_util.tree_map(np.asarray, trained),
    }


def phase_mesh(cfg, *, policy: str, n_devices: int,
               mesh_specs=("4", "2x2"), n_clients: int = COHORT,
               n_samples: int = 4000, local_epochs: int = 1,
               seed: int = 0) -> dict:
    """Phase A's cohort window on each mesh vs ``mesh=None``."""
    import jax
    import numpy as np

    from repro.core.aggregate import tree_stack
    from repro.models.cnn import init_cnn

    world = cnn_world(cfg, n_clients, n_samples, seed)
    stacked = tree_stack([init_cnn(jax.random.PRNGKey(seed + c), cfg)
                          for c in range(COHORT)])
    quantum = 1.0 / min(len(world[1]), 512)   # evaluate_many's test limit
    base = _window(cfg, world, policy, None, stacked=stacked,
                   local_epochs=local_epochs, seed=seed)
    checks, report = {}, {"quantum": quantum, "single_wall_s": base["wall_s"]}
    for spec in mesh_specs:
        w = _window(cfg, world, policy, spec, stacked=stacked,
                    local_epochs=local_epochs, seed=seed)
        diffs = [float(np.max(np.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(w["params"]),
            jax.tree_util.tree_leaves(base["params"]))]
        gap = float(np.max(np.abs(w["acc"] - base["acc"])))
        checks[f"{spec}_signatures_identical"] = bool(
            np.array_equal(w["sig_in"], base["sig_in"]))
        checks[f"{spec}_params_allclose"] = max(diffs) <= PARAM_ATOL
        checks[f"{spec}_accuracy_within_quantum"] = gap <= quantum + 1e-9
        checks[f"{spec}_clients_on_all_devices"] = (
            len(w["mesh_devices"]) == n_devices
            and len(w["devices_holding_clients"]) == n_devices)
        report[spec] = {
            "mesh": w["mesh"], "devices": w["devices_holding_clients"],
            "wall_s": w["wall_s"], "param_max_abs_diff": max(diffs),
            "accuracy_max_gap": gap,
            "trained_signature_max_abs_diff": float(np.max(np.abs(
                w["sig_out"] - base["sig_out"]))),
        }
    return {"checks": checks, "policy": policy, **report}


# ---------------------------------------------------------------------------


def _run(name: str, fn, failures: list, clock: CompileClock) -> None:
    """Run one phase, print its record, and collect failed checks (a
    raised phase fails too) so the exit code reports every phase."""
    c0, t0 = clock.seconds, time.perf_counter()
    try:
        res = fn()
    except Exception:                                  # noqa: BLE001
        failures.append(f"{name}: raised")
        _log(name, error=traceback.format_exc())
        return
    finally:
        gc.collect()
    failures += [f"{name}: {k}" for k, ok in res["checks"].items() if not ok]
    _log(name, wall_s=time.perf_counter() - t0,
         compile_s=clock.seconds - c0, peak_bytes=peak_bytes(), **res)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, data and prompts")
    args = ap.parse_args(argv)

    import jax
    devices = require_tpu(args.chips)
    from repro.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    _log("device", platform=devices[0].platform,
         kind=devices[0].device_kind, count=len(devices),
         compile_cache=cache_dir, jax=jax.__version__)

    from repro.configs import get_config
    from repro.configs.cnn import VGG16
    clock = CompileClock()
    failures: list = []
    if args.chips == 4:
        _run("mesh", lambda: phase_mesh(
            VGG16, policy="compiled", n_devices=4, seed=args.seed),
            failures, clock)
    else:
        _run("A_vgg16_rounds", lambda: phase_a(
            VGG16, policy="compiled", expect_custom_call=True,
            seed=args.seed), failures, clock)
        _run("B_internlm2_serving", lambda: phase_b(
            get_config("internlm2-1.8b"), policy="compiled",
            expect_custom_call=True, seed=args.seed), failures, clock)
    if failures:
        print("chip_smoke FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
