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
import weakref
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


class ServingWeights:
    """The compute-dtype copy of a served model's weights, made once per
    parameter tree and shared by a serving pair.

    Called with a tree, it returns the tree the serving programs take: the
    leaves that the model casts to ``cfg.compute_dtype`` before use
    (:func:`repro.models.transformer.compute_weight_mask`) already cast,
    and every other leaf the same object.  The first call with a tree casts
    it with one program (``serve_weights``); later calls with the same
    tree (every leaf the identical, undeleted array) reuse that copy.  One
    copy is held at a time, keyed on weak references, so a replaced model is
    never kept alive; a new tree drops the old copy before casting.  Where
    no leaf's dtype differs from the compute dtype, the tree is returned
    as it is."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.compute = compute = jnp.dtype(cfg.compute_dtype)

        def serve_weights(leaves):
            return [a.astype(compute) for a in leaves]

        self._cast = jax.jit(serve_weights)
        self._source = ()     # weak references to the copied tree's leaves
        self._copy = ()       # (positions of the cast leaves, their casts)

    def _to_cast(self, leaves, params):
        """Positions of the leaves to cast: the model casts them and their
        dtype is not the compute dtype."""
        mask = jax.tree_util.tree_leaves(tfm.compute_weight_mask(params,
                                                                 self.cfg))
        return [i for i, (a, m) in enumerate(zip(leaves, mask))
                if m and a.dtype != self.compute]

    def _holds(self, leaves) -> bool:
        return len(leaves) == len(self._source) and all(
            r() is a and not (isinstance(a, jax.Array) and a.is_deleted())
            for r, a in zip(self._source, leaves))

    def __call__(self, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if self._copy and self._holds(leaves):
            obs.count("dagafl.serve_weight_reuses")
            idx, cast = self._copy
        else:
            idx = self._to_cast(leaves, params)
            if not idx:
                return params
            if any(isinstance(a, jax.core.Tracer) for a in leaves):
                # inside another trace: cast inline, hold nothing
                cast = self._cast([leaves[i] for i in idx])
            else:
                self._source = self._copy = ()
                obs.count("dagafl.serve_weight_casts")
                with obs.span("dagafl.serve_cast"):
                    cast = self._cast([leaves[i] for i in idx])
                self._source = tuple(weakref.ref(a) for a in leaves)
                self._copy = (idx, cast)
        return treedef.unflatten(_replace(leaves, idx, cast))

    def shapes(self, params):
        """The tree the serving programs take, as shapes: the leaves to
        cast become ``ShapeDtypeStruct``s of the compute dtype (keeping
        their sharding).  Casts nothing and holds no copy."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        idx = self._to_cast(leaves, params)
        cast = [jax.ShapeDtypeStruct(
            leaves[i].shape, self.compute,
            sharding=getattr(leaves[i], "sharding", None)) for i in idx]
        return treedef.unflatten(_replace(leaves, idx, cast))


def _replace(leaves, idx, new) -> list:
    """``leaves`` with ``new[k]`` at position ``idx[k]``."""
    out = list(leaves)
    for i, a in zip(idx, new):
        out[i] = a
    return out


class _ServingProgram:
    """A jitted serving program that takes the weights through the pair's
    :class:`ServingWeights`.  ``lower`` lowers the program the replica runs,
    on the compute-dtype shapes, and casts nothing."""

    def __init__(self, jitted, weights: ServingWeights):
        self.jitted, self.weights = jitted, weights

    def __call__(self, params, *args):
        return self.jitted(self.weights(params), *args)

    def lower(self, params, *args):
        return self.jitted.lower(self.weights.shapes(params), *args)


def make_serving_fns(cfg, runtime: Optional[Runtime] = None):
    """The jitted (prefill, decode) pair for one arch config, sharing one
    :class:`ServingWeights`: the weights are cast to the compute dtype
    once per parameter tree, not in every call.  ``runtime`` carries the
    kernel-dispatch policy (see :func:`repro.runtime.serve_runtime`); the
    decode step has no static arguments — every input (params, token,
    caches, pos) is traced."""
    runtime = Runtime() if runtime is None else runtime
    weights = ServingWeights(cfg)
    prefill = _ServingProgram(jax.jit(make_serve_prefill(cfg, runtime)),
                              weights)
    decode = _ServingProgram(jax.jit(make_serve_decode(cfg, runtime)),
                             weights)
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
