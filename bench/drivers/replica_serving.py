"""Entry driver: a consensus replica serving greedy decode through the
program's serving path (``launch/serve.make_serving_fns`` under
``serve_runtime(kernel_policy)`` and ``greedy_decode``) at a
configuration's published widths.

Weights are made on the device from the seed in one jitted call, in the
layout the serving programs take.  Queries run in a closed loop: each holds
``batch`` prompts of ``prompt_len`` token ids drawn uniformly from the seed
and decodes ``new_tokens`` greedily; the next starts when it returns.
Set-up serves one query, which compiles (or loads) both programs.  The
window runs queries for ``--seconds`` and closes when the query running at
that time returns, so it holds only whole queries.

After the window, one query drawn from the seed is replayed through the
plain float32 reference (bench/reference/decoder.py): every served token's
reference logit is compared with the reference's best at its position.
"""
from __future__ import annotations

import time

import numpy as np


def _check_layout(params, cfg_obj):
    """The bench-made weights must have the program's own layout."""
    import jax

    from repro.models import transformer as tfm
    want = jax.eval_shape(lambda k: tfm.init_params(k, cfg_obj),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise SystemExit("bench: the program's parameter layout no longer "
                         "matches bench/reference/decoder.py")


def _program_config(cfg: dict):
    """The program's ArchConfig of the configuration's family, with every
    size and dtype taken from the configuration file."""
    import dataclasses

    from repro.configs import get_config
    c = get_config(cfg["name"])
    n = cfg["num_hidden_layers"]
    stage = c.stages[0]
    return dataclasses.replace(
        c, n_layers=n, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], act=cfg["hidden_act"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"],
        cache_dtype=cfg["cache_dtype"],
        stages=(dataclasses.replace(stage,
                                    repeats=n // len(stage.pattern)),))


def world(cfg: dict, t: dict, seed: int):
    """What a seed makes: the program's config, the weights (on the
    device, one jitted call), the prompts of query ``q`` and the seed that
    draws the query the reference replays."""
    import jax

    from bench.reference import decoder as ref
    arch = _program_config(cfg)
    ss = np.random.SeedSequence(seed).generate_state(3)
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(int(ss[0])))
    _check_layout(params, arch)
    jax.block_until_ready(params)
    prompt_key = jax.random.PRNGKey(int(ss[1]))
    make_prompts = jax.jit(lambda q: jax.random.randint(
        jax.random.fold_in(prompt_key, q), (t["batch"], t["prompt_len"]), 0,
        cfg["vocab_size"], dtype=jax.numpy.int32))
    return arch, params, make_prompts, int(ss[2])


def run(ctx) -> dict:
    import gc

    import jax

    from bench.harness import device
    from bench.reference import decoder as ref
    from repro.launch.serve import greedy_decode, make_serving_fns
    from repro.runtime import serve_runtime

    cfg, t = ctx.config, ctx.traffic
    arch, params, make_prompts, pick_seed = world(cfg, t, ctx.seed)
    b, s, new = t["batch"], t["prompt_len"], t["new_tokens"]
    prefill, decode = make_serving_fns(arch, serve_runtime(cfg["kernel_policy"]))
    spans = ctx.spans

    def query(q):
        with spans.span("query"):
            batch = {"tokens": make_prompts(q)}
            return greedy_decode(prefill, decode, arch, params, batch, new)

    with spans.span("warm_up"):
        jax.block_until_ready(query(-1)["tokens"])
    ctx.open_window()
    t0 = time.perf_counter()
    served, ttft, dec = [], [], []
    q = 0
    while time.perf_counter() - t0 < ctx.seconds:
        r = query(q)
        served.append(r["tokens"])
        ttft.append(r["prefill_s"])
        dec.append(r["decode_s"])
        q += 1
    ctx.close_window()
    peak = device.memory_peak_bytes(ctx.devices)
    n_tok = q * b * new
    ctx.log(f"{q} queries, {n_tok} tokens; prefill mean "
            f"{1e3 * np.mean(ttft):.3f} ms; decode step "
            f"{1e3 * np.sum(dec) / (q * (new - 1)):.3f} ms")

    # one finished query, drawn from the seed, replayed by the reference
    pick = int(np.random.default_rng(pick_seed).integers(q))
    tokens = np.asarray(served[pick])
    prompt = np.asarray(make_prompts(pick))
    del prefill, decode, served
    gc.collect()
    seq = jax.numpy.asarray(np.concatenate([prompt, tokens[:, :-1]], axis=1))
    ref_logits = ref.logits(params, seq, cfg, last=new)
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    gap = float(np.max(best - got))
    return {"attempted": q * b, "failed": 0,
            "e2e": {"serve_tokens_per_s": n_tok / ctx.window_s,
                    "ttft_ms": 1e3 * float(np.mean(ttft))},
            "raw": {"queries": q, "batch": b, "prompt_len": s,
                    "new_tokens": new, "prefill_s": ttft, "decode_s": dec},
            "checks": {"served_logit_gap": gap},
            "memory_peak_bytes": peak,
            "span_names": ["query", "warm_up"]}


def control_gap(params, cfg: dict, prompt, tokens, new: int) -> float:
    """The control's reading on one query: at each position, the
    reference's gap for the token the fp8 forward puts first."""
    import jax.numpy as jnp

    from bench.reference import decoder as ref
    seq = jnp.asarray(np.concatenate([prompt, tokens[:, :-1]], axis=1))
    exact = np.asarray(ref.logits(params, seq, cfg, last=new), np.float64)
    low = np.asarray(ref.logits(params, seq, cfg, last=new, quant="fp8"))
    pick = low.argmax(-1)
    got = np.take_along_axis(exact, pick[..., None], -1)[..., 0]
    return float(np.max(exact.max(-1) - got))
