"""Serving launcher: batched prefill + greedy decode with a KV cache.

The prefill/decode program construction and the greedy KV-cache decode loop
live here as reusable functions (``make_serving_fns`` / ``greedy_decode`` /
``extend_caches``) — the live-traffic consensus-serving path
(:mod:`repro.fl.serving`) drives the same programs against DAG frontier
replicas that this CLI drives against freshly initialized params.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import ARCH_IDS, get_config, reduced
from repro.models import transformer as tfm
from repro.models.attention import cache_seq_axis
from repro.runtime import Runtime, enable_compile_cache
from repro.train.step import make_serve_decode, make_serve_prefill


def extend_caches(caches, cfg, extra: int):
    """Grow every attention cache by ``extra`` slots along its SEQUENCE
    axis.  The axis is derived from the cache spec
    (:data:`repro.models.attention.KV_CACHE_TRAILING_DIMS`, counted from the
    trailing end), not hardcoded: prefill-collected caches carry a leading
    stacked-layer axis, per-layer caches do not, and both layouts must
    extend correctly."""
    out = []
    for si, stage in enumerate(cfg.stages):
        d = {}
        for j, spec in enumerate(stage.pattern):
            cc = dict(caches[si][f"l{j}"])
            if spec.kind == "attn":
                for kk in ("k", "v", "ckv", "krope"):
                    if kk in cc:
                        pad = [(0, 0)] * cc[kk].ndim
                        pad[cache_seq_axis(kk, cc[kk].ndim)] = (0, extra)
                        cc[kk] = jnp.pad(cc[kk], pad)
            d[f"l{j}"] = cc
        out.append(d)
    return out


def make_serving_fns(cfg, runtime: Optional[Runtime] = None):
    """The jitted (prefill, decode) pair for one arch config.  ``runtime``
    carries the kernel-dispatch policy (see :func:`repro.runtime.
    serve_runtime`); the decode step has no static arguments — every input
    (params, token, caches, pos) is traced."""
    runtime = Runtime() if runtime is None else runtime
    prefill = jax.jit(make_serve_prefill(cfg, runtime))
    decode = jax.jit(make_serve_decode(cfg, runtime))
    return prefill, decode


def greedy_decode(prefill_fn, decode_fn, cfg, params, batch,
                  new_tokens: int):
    """Prefill ``batch`` then greedy-decode ``new_tokens`` against the KV
    cache.  Returns {tokens (B, new_tokens) int32, margins (B, new_tokens)
    f32 — the top-2 logit gap behind each greedy pick, prefill_s,
    decode_s}; both clock reads are synced on the device results."""
    prompt_len = batch["tokens"].shape[1]
    with obs.span("dagafl.serve_prefill"):
        t0 = time.time()
        last_logits, caches = prefill_fn(params, batch)
        caches = extend_caches(caches, cfg, new_tokens)
        jax.block_until_ready(last_logits)
        t_prefill = time.time() - t0

    tok = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]
    generated, step_logits = [tok], [last_logits]
    with obs.span("dagafl.serve_decode"):
        t0 = time.time()
        for step in range(new_tokens - 1):
            pos = jnp.int32(prompt_len + step)
            tok, logits, caches = decode_fn(params, tok, caches, pos)
            tok = tok[:, None] if tok.ndim == 1 else tok
            generated.append(tok)
            step_logits.append(logits)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0
    top2 = jax.lax.top_k(jnp.stack(step_logits, axis=1), 2)[0]
    return {"tokens": jnp.concatenate(generated, axis=1),
            "margins": top2[..., 0] - top2[..., 1],
            "prefill_s": t_prefill, "decode_s": t_decode}


def serve(cfg, batch: int, prompt_len: int, new_tokens: int, seed: int = 0):
    prefill, decode = make_serving_fns(cfg)
    key = jax.random.PRNGKey(seed)
    params = tfm.init_params(key, cfg)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    b = {"tokens": prompts}
    if cfg.encoder is not None:
        b["enc_embed"] = jax.random.normal(
            key, (batch, cfg.encoder.n_ctx, cfg.d_model)) * 0.1

    r = greedy_decode(prefill, decode, cfg, params, b, new_tokens)
    return {
        "prefill_s": r["prefill_s"],
        "decode_s": r["decode_s"],
        "decode_tok_per_s": batch * (new_tokens - 1) / max(r["decode_s"],
                                                           1e-9),
        "tokens": r["tokens"],
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="internlm2-1.8b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced(cfg), compute_dtype="float32")
    r = serve(cfg, args.batch, args.prompt, args.new_tokens)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt} "
          f"new={args.new_tokens}")
    print(f"prefill={r['prefill_s']*1e3:.1f}ms decode={r['decode_s']*1e3:.1f}ms "
          f"({r['decode_tok_per_s']:.1f} tok/s)")
    print("sample:", r["tokens"][0, :12].tolist())


if __name__ == "__main__":
    main()
