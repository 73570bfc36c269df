"""Entry driver: a replica serving greedy decode of an MLA + expert-layer
model (the DeepSeek-V2 family) through the program's serving path
(``launch/serve.make_serving_fns`` under ``serve_runtime(kernel_policy)``
and ``greedy_decode``), at one chip's share of a published deployment: the
layer holds the experts of one routing group, the router scores all of
them (see the configuration's ``deployment``).

Weights are made on the device from the seed in one jitted call, in the
layout the serving programs take and in ``param_dtype``.  Queries run in a
closed loop: each holds ``batch`` prompts of ``prompt_len`` token ids drawn
uniformly from the vocabulary slice and decodes ``new_tokens`` greedily;
the next starts when it returns.  Set-up serves one query, which compiles
(or loads) both programs.  The window runs queries for ``--seconds`` under
an ``obs.Recorder`` and closes when the query running at that time
returns; the recorder's counters (``dagafl.moe_routed_rows``,
``dagafl.moe_experts_hit``) go to ``raw``.

After the window, one query drawn from the seed is replayed through the
plain float32 reference (bench/reference/deepseek_v2.py): as many of its
rows as the chip's free memory holds, at least four, drawn from the seed.
A bfloat16 program and the float32 reference route a token differently
where its router logits lie near a tie (an expert, or a group, on one
side of the boundary in one and on the other in the other), and a token
routed differently moves its logits by more than any rounding does.  So
the reference routes each token's expert layers as the program did (the
held experts ``greedy_decode`` returns under ``moe["held"]``) and judges the
routing apart:

- ``served_logit_gap``: the widest gap by which a served token's
  reference logit lies below the reference's best at its position, over
  the vocabulary slice;
- ``misrouted_margin``: the widest router-logit distance from the
  reference's own routing boundary at which the program's held experts
  differ from the reference's own choice, over every replayed position
  and expert layer (0 where they agree).
"""
from __future__ import annotations

import time

import numpy as np

MIN_ROWS = 4
# device bytes a reference layer needs beside the weights: a base, then
# more for each replayed row (compiled for a described v5e at 2,304
# tokens a row: 1.21 GB of temporaries at one row, 0.27 GB more a row)
BASE_BYTES = 1.5e9
ROW_BYTES = 0.3e9


def program_config(cfg: dict):
    """The program's ArchConfig, every size and dtype from the file."""
    from repro.configs.base import (ArchConfig, LayerSpec, MLAConfig,
                                    MoEConfig, Stage, YarnConfig)
    if (cfg["topk_method"], cfg["scoring_func"], cfg["moe_layer_freq"],
            cfg["rope_scaling"]["type"]) != ("group_limited_greedy",
                                             "softmax", 1, "yarn"):
        raise SystemExit("bench: the program serves group-limited greedy "
                         "softmax routing on every layer after the dense "
                         "ones, under YaRN")
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    rs = cfg["rope_scaling"]
    stages = (Stage((LayerSpec(kind="attn", ffn="dense",
                               d_ff=cfg["intermediate_size"]),), dense),)
    if n > dense:
        stages += (Stage((LayerSpec(kind="attn", ffn="moe"),), n - dense),)
    return ArchConfig(
        name=cfg["name"], family="moe", citation=cfg["source"], n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["v_head_dim"], d_ff=cfg["moe_intermediate_size"],
        vocab_size=cfg["vocab_size"], stages=stages,
        rope_theta=float(cfg["rope_theta"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      q_lora_rank=cfg["q_lora_rank"],
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        yarn=YarnConfig(factor=float(rs["factor"]),
                        original_max_position=rs[
                            "original_max_position_embeddings"],
                        beta_fast=float(rs["beta_fast"]),
                        beta_slow=float(rs["beta_slow"]),
                        mscale=float(rs["mscale"]),
                        mscale_all_dim=float(rs["mscale_all_dim"])),
        moe=MoEConfig(n_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      n_routed=cfg["router_experts"],
                      expert_offset=cfg["held_group"]
                      * cfg["n_routed_experts"],
                      n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                      norm_topk=cfg["norm_topk_prob"],
                      routed_scale=float(cfg["routed_scaling_factor"])),
        norm="rmsnorm", act=cfg["hidden_act"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"], cache_dtype=cfg["cache_dtype"])


def _check_layout(params, arch):
    """The bench-made weights must have the program's own layout."""
    import jax

    from repro.models import transformer as tfm
    want = jax.eval_shape(lambda k: tfm.init_params(k, arch),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise SystemExit("bench: the program's parameter layout no longer "
                         "matches bench/reference/deepseek_v2.py")


def world(cfg: dict, t: dict, seed: int):
    """What a seed makes: the program's config, the weights (on the
    device, one jitted call), the prompts of query ``q`` and the seed that
    draws the query and rows the reference replays."""
    import jax

    from bench.reference import deepseek_v2 as ref
    arch = program_config(cfg)
    ss = np.random.SeedSequence(seed).generate_state(3)
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(int(ss[0])))
    _check_layout(params, arch)
    jax.block_until_ready(params)
    prompt_key = jax.random.PRNGKey(int(ss[1]))
    make_prompts = jax.jit(lambda q: jax.random.randint(
        jax.random.fold_in(prompt_key, q), (t["batch"], t["prompt_len"]), 0,
        cfg["vocab_size"], dtype=jax.numpy.int32))
    return arch, params, make_prompts, int(ss[2])


def replay_rows(devices, batch: int, rng) -> np.ndarray:
    """The rows the reference replays: as many as the fullest chip's free
    memory holds, ``BASE_BYTES`` and ``ROW_BYTES`` a row (all of them
    where the backend reports no limit), at least ``MIN_ROWS``, drawn with
    ``rng``."""
    free = []
    for d in devices:
        st = d.memory_stats() or {}
        if "bytes_limit" in st:
            free.append(st["bytes_limit"] - st.get("bytes_in_use", 0))
    n = batch if not free else int((min(free) - BASE_BYTES) // ROW_BYTES)
    n = max(MIN_ROWS, min(batch, n))
    return np.sort(rng.choice(batch, size=n, replace=False))


def served_checks(params, cfg: dict, seq, tokens, held, new: int,
                  margin_limit: float):
    """(checks, notes) of served rows: ``seq`` (R, S) the prompts and the
    served tokens before the last, ``tokens`` (R, new) the served tokens,
    ``held`` (L, R, S, E) the program's routing (see the module
    docstring).  ``notes``: the share of (layer, position) the program
    routes differently from the reference's own choice; the share whose
    routing lies within ``margin_limit`` of a boundary, where
    ``misrouted_margin`` passes either choice; and the mean gap."""
    from bench.reference import deepseek_v2 as ref
    lg, routing = ref.forward(params, seq, cfg, last=new, held=held)
    lg = np.asarray(lg, np.float64)
    gap = lg.max(-1) - np.take_along_axis(lg, tokens[..., None], -1)[..., 0]
    checks = {"served_logit_gap": float(np.max(gap)),
              "misrouted_margin": float(np.max(routing["misrouted"]))}
    notes = {"rerouted_share": float(np.mean(np.any(
        np.asarray(routing["held"]) != np.asarray(held), axis=-1))),
        "near_boundary_share": float(np.mean(
            np.asarray(routing["boundary"]) < margin_limit)),
        "served_logit_gap_mean": float(np.mean(gap))}
    return checks, notes


def run(ctx) -> dict:
    import gc

    import jax

    from bench.harness import device
    from repro import obs
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
    rec = obs.Recorder()
    ctx.open_window()
    t0 = time.perf_counter()
    served, ttft, dec, dec_hits, dec_rows = [], [], [], 0, 0
    q = 0
    with obs.recording(rec):
        while time.perf_counter() - t0 < ctx.seconds:
            r = query(q)
            served.append((r["tokens"], r["moe"]["held"]))
            ttft.append(r["prefill_s"])
            dec.append(r["decode_s"])
            dec_hits += int(r["moe"]["decode"]["moe_experts_hit"].sum())
            dec_rows += int(r["moe"]["decode"]["moe_routed_rows"].sum())
            q += 1
    ctx.close_window()
    peak = device.memory_peak_bytes(ctx.devices)
    n_tok = q * b * new
    counters = dict(rec.counters)
    ctx.log(f"{q} queries, {n_tok} tokens; prefill mean "
            f"{1e3 * np.mean(ttft):.3f} ms; decode step "
            f"{1e3 * np.sum(dec) / (q * (new - 1)):.3f} ms; counters "
            f"{counters}; peak {peak} bytes")

    # one finished query and some of its rows, drawn from the seed,
    # replayed by the reference
    rng = np.random.default_rng(pick_seed)
    pick = int(rng.integers(q))
    tokens, held = (np.asarray(a) for a in served[pick])
    prompt = np.asarray(make_prompts(pick))
    del prefill, decode, served
    gc.collect()
    rows = replay_rows(ctx.devices, b, rng)
    ctx.log(f"reference replays query {pick}, rows {rows.tolist()}; in use "
            f"before it {_in_use(ctx.devices)} bytes")
    seq = np.concatenate([prompt, tokens[:, :-1]], axis=1)[rows]
    checks, notes = served_checks(params, cfg, jax.numpy.asarray(seq),
                                  tokens[rows], held[:, rows], new,
                                  margin_limit(ctx.workload["name"]))
    ctx.log(f"reference: {notes}; peak "
            f"{device.memory_peak_bytes(ctx.devices)} bytes")
    return {"attempted": q * b, "failed": 0,
            "e2e": {"serve_tokens_per_s": n_tok / ctx.window_s,
                    "ttft_ms": 1e3 * float(np.mean(ttft))},
            "raw": {"queries": q, "batch": b, "prompt_len": s,
                    "new_tokens": new, "prefill_s": ttft, "decode_s": dec,
                    "counters": counters, "decode_experts_hit": dec_hits,
                    "decode_routed_rows": dec_rows, **notes},
            "checks": checks,
            "memory_peak_bytes": peak,
            "span_names": ["query", "warm_up", "dagafl.serve_prefill",
                           "dagafl.serve_decode"]}


def margin_limit(workload: str) -> float:
    """The cell's ``misrouted_margin`` limit."""
    from bench.harness import loader
    return loader.limits(workload)["numbers"]["misrouted_margin"]["limit"]


def _in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def readings(params, cfg: dict, prompt, tokens, held, new: int,
             limit: float) -> dict:
    """A limit's readings on served rows (:func:`served_checks`):
    ``sound``, of the served ``tokens`` and the program's routing
    ``held``; and two controls, each judged by the same checks on the
    tokens it puts first and the routing it took: ``fp8_control``, the
    reference with fp8 operands, and ``greedy_control``, the reference
    routing by plain top-k without the group limit.  ``limit``: the
    ``misrouted_margin`` limit the notes count boundaries within."""
    import jax.numpy as jnp

    from bench.reference import deepseek_v2 as ref
    seq = jnp.asarray(np.concatenate([prompt, tokens[:, :-1]], axis=1))
    out = {"sound": served_checks(params, cfg, seq, tokens, held, new,
                                  limit)}
    for name, c, quant in (("fp8_control", cfg, "fp8"),
                           ("greedy_control",
                            dict(cfg, topk_method="greedy"), None)):
        lg, routing = ref.forward(params, seq, c, last=new, quant=quant)
        out[name] = served_checks(params, cfg, seq,
                                  np.asarray(lg).argmax(-1),
                                  np.asarray(routing["held"]), new, limit)
    return out
