#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own
sizes on the chip.  The benchmark's runs never call this.

    python bench/calibrate.py --workload <name> --seeds 101 102 103

Federated cells: per seed, a cohort of clients' local training is
recomputed by the float32 reference at the configuration's matmul
precision and by the lower-precision controls (the reference in bfloat16,
or with fp8 operands, put in the program's place), by the reference at
full float32 precision (HIGHEST), and by the reference with each step's
mean taken over half its batch (a planted fault).  Each prints the run's
compared numbers as the control or fault reads them.

Serving cell: per seed, one query is served by the program (the run's own
reading of ``served_logit_gap``) and the same prompts and tokens are read
by the fp8 control: the reference's gap for the token the fp8 forward puts
first at each position.

One JSON line per seed and reading."""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def federated(w, cfg, t, seed):
    """Per seed: ``cohort_size`` clients trained from the genesis, then two
    more from the Eq. 6 mean of the first two, each by the float32
    reference and by every control and fault; one line per round and
    reading with the raw numbers the run compares (first losses, change
    norm of every leaf), and the answers' gaps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.drivers.federated_rounds import _seeds
    from bench.gen import images
    from bench.harness import checks
    from bench.reference import vgg as ref

    sd = _seeds(seed)
    clients, _ = images.client_world(t, cfg, sd["content"])
    g = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(sd["weights"]))
    rng = np.random.default_rng(sd["sample"])
    k = min(t["cohort_size"], len(clients))
    picks = [int(c) for c in rng.choice(len(clients), k, replace=False)]
    opt, bs, ep = cfg["optimizer"], cfg["batch_size"], cfg["local_epochs"]
    fz = ref.freeze(cfg)
    prec = ref.precision(cfg)
    kinds = {"control_bf16": {"dtype": jnp.bfloat16},
             "control_fp8": {"quant": "fp8"},
             "half_batch": {"drop_half": True, "quant": prec},
             "highest": {"quant": None}}

    def emit(**kw):
        print(json.dumps({"workload": w["name"], "seed": seed, **kw}),
              flush=True)

    def round_(start, c, stage):
        ds = clients[c]["train"]
        bseed = int(rng.integers(2 ** 31))
        r, lr_, g0 = ref.train(start, ds.x, ds.y, bseed, cfg, opt, bs, ep,
                               keep_first_grads=True, quant=prec)
        keep = checks.moving_leaves(checks.norms(g0))
        dr = np.asarray(checks.delta_norms(r, start))
        emit(stage=stage, client=c, reading="reference", steps=len(lr_),
             losses=lr_[:5], delta=dr.tolist(), keep=keep.tolist())
        for kind, kw in kinds.items():
            p, lp, _ = ref.train(start, ds.x, ds.y, bseed, cfg, opt, bs, ep,
                                 **kw)
            cast = jax.tree_util.tree_map(lambda a: a.astype(
                kw.get("dtype", jnp.float32)), start)
            dp = np.asarray(checks.delta_norms(p, cast))
            emit(stage=stage, client=c, reading=kind, losses=lp[:5],
                 delta=dp.tolist(), loss_gap=checks.loss_gap(lp, lr_),
                 change_gap=checks.norm_gap(dp, dr, keep),
                 median_change_gap=checks.median_norm_gap(dp, dr, keep))
        val = clients[c]["val"]
        n, ns = min(len(val), 512), min(len(ds), 128)
        a32 = float(ref.accuracy(r, val.x[:n], val.y[:n], cfg_key=fz))
        s32 = np.asarray(ref.signature(r, ds.x[:ns], cfg_key=fz))
        for kind, kw in (("control_bf16", {"dtype": "bfloat16"}),
                         ("control_fp8", {"quant": "fp8"})):
            a = float(ref.accuracy(r, val.x[:n], val.y[:n], cfg_key=fz, **kw))
            s = np.asarray(ref.signature(r, ds.x[:ns], cfg_key=fz, **kw))
            emit(stage=stage, client=c, reading=kind + "_answers",
                 accuracy_gap=abs(a32 - a),
                 signature_gap=float(np.max(np.abs(s32 - s))))
        # answers altered where they are produced, as the fault tests plant
        # them (bench/tests/test_bench_faults.py)
        a_off = a32 + 0.25 if a32 < 0.5 else a32 - 0.25
        s_off = np.where(s32 > 0.5, s32 - 0.1, s32 + 0.1)
        emit(stage=stage, client=c, reading="answers_altered",
             accuracy_gap=abs(a32 - a_off),
             signature_gap=float(np.max(np.abs(s32 - s_off))))
        return r

    models = [round_(g, c, "genesis") for c in picks]
    exact = ref.mean(models[:2])
    low = {"control_bf16": jax.tree_util.tree_map(
        lambda a, b: ((a.astype(jnp.bfloat16) + b.astype(jnp.bfloat16))
                      / 2).astype(jnp.float32), models[0], models[1]),
        "control_fp8": jax.tree_util.tree_map(
            lambda a, b: (ref.q8(a) + ref.q8(b)) / 2, models[0], models[1])}
    # and Eq. 6 over the first selected model alone
    low["one_parent"] = models[0]
    for kind, m in low.items():
        emit(stage="aggregate", reading=kind,
             aggregate_gap=checks.relative_diff(m, exact))
    for c in picks[2:]:
        round_(exact, c, "aggregate")


def serving(w, cfg, t, seed):
    import jax
    import numpy as np

    from bench.drivers.replica_serving import control_gap, world
    from bench.reference import decoder as ref
    from repro.launch.serve import greedy_decode, make_serving_fns
    from repro.runtime import serve_runtime

    arch, params, make_prompts, _ = world(cfg, t, seed)
    new = t["new_tokens"]
    prompt = make_prompts(0)
    prefill, decode = make_serving_fns(arch, serve_runtime(cfg["kernel_policy"]))
    tokens = np.asarray(greedy_decode(prefill, decode, arch, params,
                                      {"tokens": prompt}, new)["tokens"])
    del prefill, decode
    prompt = np.asarray(prompt)
    seq = jax.numpy.asarray(np.concatenate([prompt, tokens[:, :-1]], 1))
    exact = np.asarray(ref.logits(params, seq, cfg, last=new), np.float64)
    got = np.take_along_axis(exact, tokens[..., None], -1)[..., 0]
    program = float(np.max(exact.max(-1) - got))
    ctrl = control_gap(params, cfg, prompt, tokens, new)
    # a served token altered where it is produced, as the fault test does
    off = (tokens + cfg["vocab_size"] // 2) % cfg["vocab_size"]
    altered = float(np.max(exact.max(-1) - np.take_along_axis(
        exact, off[..., None], -1)[..., 0]))
    for kind, v in (("program", program), ("control", ctrl),
                    ("token_altered", altered)):
        print(json.dumps({"workload": w["name"], "seed": seed,
                          "reading": kind, "served_logit_gap": v}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.harness import device, loader, manifest
    m = manifest.load()
    w = manifest.workload(m, args.workload)
    device.require_tpu(1)
    cfg = loader.config(manifest.config_entry(m, w["config"]))
    t = loader.traffic(w["traffic"])
    fn = {"federated_rounds": federated,
          "replica_serving": serving}[cfg["driver"]]
    for seed in args.seeds:
        fn(w, cfg, t, seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
