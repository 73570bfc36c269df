"""Staged decoder (and optional encoder) assembled from ArchConfig.

The layer stack is organised as *stages*: each stage is a repeating pattern of
heterogeneous blocks scanned with ``lax.scan`` over parameters stacked along a
leading ``repeats`` axis.  One traced period covers every distinct block in
the architecture, so the HLO stays small for 62-80-layer models.

Public API
----------
init_params / init_cache / forward / loss_fn / prefill / decode_step
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import moe as moe_mod
from repro.models import xlstm as xl
from repro.models.layers import (EMBED_COMPUTE_CAST, MLP_COMPUTE_CAST,
                                 apply_mlp, apply_norm, compute_cast_mask,
                                 cross_entropy, embed_tokens, init_embedding,
                                 init_mlp, init_norm, unembed)
from repro.runtime import DEFAULT, Runtime


def _shard_batch(x, runtime: Runtime):
    """Constrain dim 0 (batch) of an activation to the launcher's batch axes.

    Without this, XLA's sharding propagation is free to replicate the batch
    and shard d_model off the embedding table's layout instead — which
    explodes per-device activation memory (observed: 70 GiB/chip on
    internlm2 train_4k before this constraint)."""
    if runtime.batch_axes is None or x.ndim < 2:
        return x
    if x.shape[0] % max(runtime.batch_axis_size, 1):
        return x
    from jax.sharding import PartitionSpec as P
    axes = (runtime.batch_axes if len(runtime.batch_axes) > 1
            else runtime.batch_axes[0])
    try:
        return jax.lax.with_sharding_constraint(
            x, P(axes, *([None] * (x.ndim - 1))))
    except Exception:          # no mesh context (plain CPU tests)
        return x


# ---------------------------------------------------------------------------
# window resolution (long-context adaptation, see DESIGN.md)
# ---------------------------------------------------------------------------


def _arch_is_subquadratic(cfg: ArchConfig) -> bool:
    return any(s.window > 0 or s.kind in ("mamba", "mlstm", "slstm")
               for s in cfg.layer_specs())


def resolve_window(cfg: ArchConfig, spec: LayerSpec, seq_len: int) -> int:
    if spec.kind != "attn":
        return -1
    w = spec.window
    if (w <= 0 and seq_len >= cfg.long_context_threshold
            and not _arch_is_subquadratic(cfg)):
        w = cfg.long_context_window
    return w


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, spec: LayerSpec, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if spec.kind == "attn":
        p["core"] = attn.init_attn(k1, cfg, spec, dtype)
    elif spec.kind == "mamba":
        p["core"] = mam.init_mamba(k1, cfg, dtype)
    elif spec.kind == "mlstm":
        p["core"] = xl.init_mlstm(k1, cfg, dtype)
    elif spec.kind == "slstm":
        p["core"] = xl.init_slstm(k1, cfg, dtype)
    else:
        raise ValueError(spec.kind)
    if spec.cross_attn:
        p["xnorm"] = init_norm(cfg.norm, cfg.d_model, dtype)
    if spec.ffn == "dense" and cfg.d_ff > 0:
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["ffn"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    elif spec.ffn == "moe":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["ffn"] = moe_mod.init_moe(k3, cfg, dtype)
    return p


def init_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    n_stages = len(cfg.stages)
    keys = jax.random.split(key, n_stages + 3)
    params = {"embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model,
                                      dtype, cfg.tie_embeddings),
              "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
              "stages": []}
    for si, stage in enumerate(cfg.stages):
        skeys = jax.random.split(keys[si + 1], stage.repeats)

        def one_period(k):
            pk = jax.random.split(k, len(stage.pattern))
            return {f"l{j}": _init_layer(pk[j], cfg, spec, dtype)
                    for j, spec in enumerate(stage.pattern)}

        params["stages"].append(jax.vmap(one_period)(skeys))
    if cfg.encoder is not None:
        params["encoder"] = _init_encoder(keys[-1], cfg, dtype)
    return params


def _init_encoder(key, cfg: ArchConfig, dtype):
    e = cfg.encoder
    keys = jax.random.split(key, e.n_layers + 1)
    spec = LayerSpec(kind="attn", ffn="dense")

    def one(k):
        return _init_layer(k, cfg, spec, dtype)

    return {"layers": jax.vmap(one)(keys[:e.n_layers]),
            "final_norm": init_norm(cfg.norm, cfg.d_model, dtype)}


_CORE_COMPUTE_CAST = {"attn": attn.COMPUTE_CAST, "mamba": mam.COMPUTE_CAST,
                      "mlstm": xl.MLSTM_COMPUTE_CAST,
                      "slstm": xl.SLSTM_COMPUTE_CAST}


def _layer_compute_cast(spec: LayerSpec):
    return {"core": _CORE_COMPUTE_CAST[spec.kind],
            "ffn": moe_mod.COMPUTE_CAST if spec.ffn == "moe"
            else MLP_COMPUTE_CAST}


def compute_weight_mask(params, cfg: ArchConfig):
    """``params``' structure with True at each leaf that the forward,
    prefill and decode paths cast to ``cfg.compute_dtype`` before use, as
    each block module declares it; False at the leaves read as stored."""
    cast = {"embed": EMBED_COMPUTE_CAST,
            "stages": [{f"l{j}": _layer_compute_cast(spec)
                        for j, spec in enumerate(stage.pattern)}
                       for stage in cfg.stages],
            "encoder": {"layers": _layer_compute_cast(
                LayerSpec(kind="attn", ffn="dense"))}}
    return compute_cast_mask(params, cast)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_seq: int):
    """Zero decode cache mirroring the stage structure."""
    caches = []
    for stage in cfg.stages:
        sc = {}
        for j, spec in enumerate(stage.pattern):
            lead = (stage.repeats,)
            if spec.kind == "attn":
                c = attn.init_kv_cache(cfg, spec, batch, max_seq, leading=lead)
                if spec.cross_attn:
                    e = cfg.encoder
                    c["xk"] = jnp.zeros(lead + (batch, e.n_ctx, cfg.n_kv_heads,
                                                cfg.head_dim),
                                        jnp.dtype(cfg.cache_dtype))
                    c["xv"] = jnp.zeros_like(c["xk"])
            elif spec.kind == "mamba":
                c = mam.init_mamba_state(cfg, batch, leading=lead)
            elif spec.kind == "mlstm":
                c = xl.init_mlstm_state(cfg, batch, leading=lead)
            elif spec.kind == "slstm":
                c = xl.init_slstm_state(cfg, batch, leading=lead)
            sc[f"l{j}"] = c
        caches.append(sc)
    return caches


# ---------------------------------------------------------------------------
# layer / stage forward
# ---------------------------------------------------------------------------


def _layer_forward(lp, x, *, cfg, spec, positions, window, runtime,
                   enc_out=None, causal=True, mode="train"):
    """Full-sequence block. Returns (x, cache_out, aux_scalar)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    if spec.kind == "attn":
        if causal:
            core, cache = attn.attn_forward(lp["core"], h, cfg=cfg, spec=spec,
                                            positions=positions, window=window,
                                            runtime=runtime)
        else:
            core, cache = _encoder_attn(lp["core"], h, cfg, positions, runtime)
    elif spec.kind == "mamba":
        core, cache = mam.mamba_forward(lp["core"], h, cfg=cfg, runtime=runtime)
    elif spec.kind == "mlstm":
        core, cache = xl.mlstm_forward(lp["core"], h, cfg=cfg, runtime=runtime)
    elif spec.kind == "slstm":
        core, cache = xl.slstm_forward(lp["core"], h, cfg=cfg, runtime=runtime)
    x = x + core
    if spec.cross_attn and enc_out is not None:
        h2 = apply_norm(lp["xnorm"], x, cfg.norm, cfg.norm_eps)
        xk, xv = attn.cross_kv(lp["core"], enc_out, cfg=cfg)
        x = x + attn.cross_attn_forward(lp["core"], h2, xk, xv, cfg=cfg)
        cache = dict(cache)
        cache["xk"], cache["xv"] = xk, xv
    if spec.ffn == "dense" and cfg.d_ff > 0:
        h3 = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
        y, _ = apply_mlp(lp["ffn"], h3, cfg.act, jnp.dtype(cfg.compute_dtype))
        x = x + y
    elif spec.ffn == "moe":
        h3 = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
        y, maux = moe_mod.moe_forward(lp["ffn"], h3, cfg=cfg,
                                      generous_capacity=(mode != "train"))
        aux = aux + maux["moe_aux"]
        x = x + y
    return x, cache, aux


def _encoder_attn(params, h, cfg, positions, runtime):
    from repro.models.attention import _project_qkv, scaled_attention
    compute = jnp.dtype(cfg.compute_dtype)
    q, k, v = _project_qkv(params, h, cfg, compute)
    from repro.models.layers import apply_rope
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    pos1d = jnp.arange(h.shape[1], dtype=jnp.int32)
    out = scaled_attention(q, k, v, pos1d, pos1d, causal=False,
                           window=-1, cap=cfg.attn_softcap, runtime=runtime)
    out = out.reshape(h.shape[0], h.shape[1], cfg.q_dim)
    out = (out.astype(compute) @ params["wo"].astype(compute)).astype(h.dtype)
    return out, {}


def _layer_decode(lp, x, cache, pos, *, cfg, spec, window, runtime):
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    if spec.kind == "attn":
        core, new_cache = attn.attn_decode(lp["core"], h, cache, pos, cfg=cfg,
                                           spec=spec, window=window,
                                           runtime=runtime)
        if spec.cross_attn:
            new_cache = dict(new_cache)
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
    elif spec.kind == "mamba":
        core, new_cache = mam.mamba_decode(lp["core"], h, cache, cfg=cfg)
    elif spec.kind == "mlstm":
        core, new_cache = xl.mlstm_decode(lp["core"], h, cache, cfg=cfg)
    elif spec.kind == "slstm":
        core, new_cache = xl.slstm_decode(lp["core"], h, cache, cfg=cfg)
    x = x + core
    if spec.cross_attn:
        h2 = apply_norm(lp["xnorm"], x, cfg.norm, cfg.norm_eps)
        x = x + attn.cross_attn_forward(lp["core"], h2, cache["xk"], cache["xv"],
                                        cfg=cfg)
    if spec.ffn == "dense" and cfg.d_ff > 0:
        h3 = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
        y, _ = apply_mlp(lp["ffn"], h3, cfg.act, jnp.dtype(cfg.compute_dtype))
        x = x + y
    elif spec.ffn == "moe":
        h3 = apply_norm(lp["norm2"], x, cfg.norm, cfg.norm_eps)
        y, maux = moe_mod.moe_forward(lp["ffn"], h3, cfg=cfg)
        aux = aux + maux["moe_aux"]
        x = x + y
    return x, new_cache, aux


def _stage_forward(stage_params, x, *, cfg, pattern, positions, seq_len,
                   runtime, enc_out, collect_cache, mode):
    windows = [resolve_window(cfg, spec, seq_len) for spec in pattern]

    def body(carry, pp):
        x, aux = carry
        caches = {}
        for j, spec in enumerate(pattern):
            x, c, a = _layer_forward(pp[f"l{j}"], x, cfg=cfg, spec=spec,
                                     positions=positions, window=windows[j],
                                     runtime=runtime, enc_out=enc_out,
                                     mode=mode)
            x = _shard_batch(x, runtime)
            caches[f"l{j}"] = c if collect_cache else {}
            aux = aux + a
        return (x, aux), caches

    if runtime.remat and mode == "train":
        body = jax.checkpoint(body)
    (x, aux), caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), stage_params)
    return x, aux, caches


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _positions_for(cfg: ArchConfig, batch_dict, B, S):
    if "positions" in batch_dict and batch_dict["positions"] is not None:
        return batch_dict["positions"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _encoder_forward(params, enc_embed, cfg: ArchConfig, runtime):
    e = cfg.encoder
    B, S = enc_embed.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    spec = LayerSpec(kind="attn", ffn="dense")
    x = enc_embed.astype(jnp.dtype(cfg.compute_dtype))

    def body(x, lp):
        x, _, _ = _layer_forward(lp, x, cfg=cfg, spec=spec, positions=pos,
                                 window=-1, runtime=runtime, causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm, cfg.norm_eps)


def forward_hidden(params, batch, cfg: ArchConfig, runtime: Runtime = DEFAULT,
                   collect_cache: bool = False, mode: str = "train"):
    """Full-sequence forward up to the final norm (no unembedding).

    Returns (h (B,S,d), aux dict, caches).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, compute) * (cfg.d_model ** 0.5
        if cfg.norm == "rmsnorm" and cfg.tie_embeddings else 1.0)
    x = _shard_batch(x, runtime)
    positions = _positions_for(cfg, batch, B, S)
    enc_out = None
    if cfg.encoder is not None:
        enc_out = _encoder_forward(params, batch["enc_embed"], cfg, runtime)
        enc_out = _shard_batch(enc_out, runtime)

    aux_total = jnp.zeros((), jnp.float32)
    caches = []
    for si, stage in enumerate(cfg.stages):
        x, aux, cache = _stage_forward(
            params["stages"][si], x, cfg=cfg, pattern=stage.pattern,
            positions=positions, seq_len=S, runtime=runtime, enc_out=enc_out,
            collect_cache=collect_cache, mode=mode)
        x = _shard_batch(x, runtime)
        aux_total = aux_total + aux
        caches.append(cache)

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    aux = {"moe_aux": aux_total}
    if runtime.want_signature:
        from repro.kernels import ops as kops
        aux["signature"] = kops.signature(
            x, tau=runtime.signature_tau, n_sig=runtime.signature_dims,
            policy=kops.policy_from_runtime(runtime))
    return x, aux, caches


def per_sample_signature(h, runtime: Runtime = DEFAULT):
    """Per-sample Eq. 3 signature rows from the designated signature layer.

    ``forward_hidden`` emits ONE signature averaged over the whole batch
    (``aux["signature"]``); the cohort engine needs a (B, n_sig) row per
    sample so padded rows can be masked out of the mean.  Rows of equal
    length average back to the fused signature exactly, so the two paths
    agree whenever no padding is present.
    h: (B, S, d) activations of the designated layer (the final-norm
    output, matching ``Runtime.want_signature``).  Routed through the
    kernel dispatch layer: one batched kernel over the B rows.
    """
    from repro.kernels import ops as kops
    return kops.signature_rows(
        h, tau=runtime.signature_tau, n_sig=runtime.signature_dims,
        policy=kops.policy_from_runtime(runtime))


def forward(params, batch, cfg: ArchConfig, runtime: Runtime = DEFAULT,
            collect_cache: bool = False, mode: str = "train"):
    """Full logits (B,S,V) f32 — eval/tests; serving and training use the
    memory-sane paths (``prefill`` / ``loss_fn``)."""
    h, aux, caches = forward_hidden(params, batch, cfg, runtime,
                                    collect_cache, mode)
    logits = unembed(params["embed"], h,
                     jnp.dtype(cfg.compute_dtype), cfg.final_softcap)
    return logits, aux, caches


def _ce_chunk(cfg: ArchConfig, B: int, S: int) -> int:
    """Sequence-chunk size keeping per-chunk f32 logits ~<= 32 GB global
    (~2 GB per device on the 16-way data axis)."""
    budget = 32e9
    c = int(budget / (4.0 * B * cfg.vocab_size))
    c = max(64, min(1024, 1 << (c.bit_length() - 1) if c > 0 else 64))
    while S % c:
        c //= 2
        if c < 1:
            return S
    return c


def loss_fn(params, batch, cfg: ArchConfig, runtime: Runtime = DEFAULT):
    """Chunked-CE training loss: unembedding + softmax-CE run per sequence
    chunk under remat, so the full (B,S,V) f32 logits never materialise."""
    h, aux, _ = forward_hidden(params, batch, cfg, runtime, mode="train")
    labels = batch["labels"]
    mask = batch.get("mask")
    B, S, d = h.shape
    compute = jnp.dtype(cfg.compute_dtype)
    C = _ce_chunk(cfg, B, S)
    n_chunks = S // C

    hc = h.reshape(B, n_chunks, C, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(B, n_chunks, C).transpose(1, 0, 2)
    mc = (mask.reshape(B, n_chunks, C).transpose(1, 0, 2)
          if mask is not None else jnp.ones_like(yc, jnp.float32))

    def chunk_body(carry, xs):
        tot, cnt = carry
        h_c, y_c, m_c = xs
        logits = unembed(params["embed"], h_c, compute, cfg.final_softcap)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        m = m_c.astype(jnp.float32)
        return (tot + jnp.sum((logz - ll) * m), cnt + jnp.sum(m)), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(chunk_body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, yc, mc))
    loss = tot / jnp.maximum(cnt, 1.0)
    total = loss + aux["moe_aux"]
    aux = dict(aux)
    aux["ce_loss"] = loss
    return total, aux


def prefill(params, batch, cfg: ArchConfig, runtime: Runtime = DEFAULT):
    """Serve-prefill: last-position logits + full KV cache (the full
    (B,S,V) logits are never formed)."""
    h, aux, caches = forward_hidden(params, batch, cfg, runtime,
                                    collect_cache=True, mode="prefill")
    logits = unembed(params["embed"], h[:, -1:],
                     jnp.dtype(cfg.compute_dtype), cfg.final_softcap)
    return logits[:, 0], caches, aux


def decode_step(params, token, caches, pos, cfg: ArchConfig,
                runtime: Runtime = DEFAULT):
    """One decode step. token (B,1) int32, pos scalar int32.

    Returns (logits (B,V) f32, new caches).
    """
    B = token.shape[0]
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], token, compute) * (cfg.d_model ** 0.5
        if cfg.norm == "rmsnorm" and cfg.tie_embeddings else 1.0)
    x = _shard_batch(x, runtime)
    # decode window must match the shape the cache was built for
    new_caches = []
    for si, stage in enumerate(cfg.stages):
        pattern = stage.pattern
        cache_seq = _cache_seq_len(caches[si], pattern, cfg)
        windows = [resolve_window(cfg, spec, cache_seq) for spec in pattern]

        def body(x, xs):
            pp, cache = xs
            new_cache = {}
            for j, spec in enumerate(pattern):
                xx, c, _ = _layer_decode(pp[f"l{j}"], x, cache[f"l{j}"], pos,
                                         cfg=cfg, spec=spec, window=windows[j],
                                         runtime=runtime)
                new_cache[f"l{j}"] = c
                x = xx
            return x, new_cache

        x, nc = jax.lax.scan(body, x, (params["stages"][si], caches[si]))
        new_caches.append(nc)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, compute, cfg.final_softcap)
    return logits[:, 0], new_caches


def _cache_seq_len(stage_cache, pattern, cfg) -> int:
    for j, spec in enumerate(pattern):
        if spec.kind == "attn":
            c = stage_cache[f"l{j}"]
            key = "ckv" if cfg.mla is not None else "k"
            return c[key].shape[2]
    return 0
