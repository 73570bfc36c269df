"""Attention blocks: GQA (windowed / soft-capped / biased / M-RoPE) and MLA.

Three score paths keep the traced memory realistic for the dry-run:
  - ``_dense_attn``   : materialised scores, small sequences & cross-attn;
  - ``_chunked_attn`` : online-softmax scan over kv chunks (flash-style HLO
                        memory), full-causal long sequences;
  - ``_banded_attn``  : scan over q blocks with a static kv band, sliding
                        window layers (flops ~ S*(W+bq) instead of S^2).

Decode (q_len = 1) uses dense scores over the cache; MLA decode uses the
absorbed form (scores in latent space, no per-head key expansion) and caches
only ``c_kv`` + the shared RoPE key — the MLA memory saving the paper's
DeepSeek-V2 source motivates.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models.layers import (apply_norm, apply_rope, dense_init, init_norm,
                                 softcap, yarn_freqs, yarn_scales)

_NEG = -2.0e9
_DENSE_MAX = 2048          # above this, use chunked/banded paths
_KV_CHUNK = 1024
_Q_BLOCK = 512
_MLA_TOKENS = 8192         # tokens an MLA prefill pass expands at once


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ArchConfig, spec: LayerSpec, dtype):
    d = cfg.d_model
    if cfg.mla is not None:
        m = cfg.mla
        keys = jax.random.split(key, 6)
        q_head = m.qk_nope_dim + m.qk_rope_dim
        p = {}
        if m.q_lora_rank:
            p["wq_a"] = dense_init(keys[0], d, m.q_lora_rank, dtype)
            p["q_norm"] = init_norm(cfg.norm, m.q_lora_rank, dtype)
            p["wq_b"] = dense_init(keys[1], m.q_lora_rank,
                                   cfg.n_heads * q_head, dtype)
        else:
            p["wq"] = dense_init(keys[0], d, cfg.n_heads * q_head, dtype)
        p["wkv_a"] = dense_init(keys[2], d, m.kv_lora_rank + m.qk_rope_dim, dtype)
        p["kv_norm"] = init_norm(cfg.norm, m.kv_lora_rank, dtype)
        p["wkv_b"] = dense_init(keys[3], m.kv_lora_rank,
                                cfg.n_heads * (m.qk_nope_dim + m.v_head_dim), dtype)
        p["wo"] = dense_init(keys[4], cfg.n_heads * m.v_head_dim, d, dtype)
        return p
    keys = jax.random.split(key, 5)
    p = {
        "wq": dense_init(keys[0], d, cfg.q_dim, dtype),
        "wk": dense_init(keys[1], d, cfg.kv_dim, dtype),
        "wv": dense_init(keys[2], d, cfg.kv_dim, dtype),
        "wo": dense_init(keys[3], cfg.q_dim, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    if spec.cross_attn:
        p["xwq"] = dense_init(keys[4], d, cfg.q_dim, dtype)
        kx = jax.random.split(keys[4], 3)
        p["xwk"] = dense_init(kx[0], d, cfg.kv_dim, dtype)
        p["xwv"] = dense_init(kx[1], d, cfg.kv_dim, dtype)
        p["xwo"] = dense_init(kx[2], cfg.q_dim, d, dtype)
    return p


# The KV-cache layout spec: number of trailing dims AFTER the sequence axis
# for each cache entry ("k"/"v": (n_kv_heads, head_dim); MLA "ckv"/"krope":
# (rank,)).  Any number of leading axes may be stacked in front (the layer
# axis the stage scan adds, or none at all), so code that grows a cache
# along its sequence axis must derive the axis from this spec — counting
# from the END — never hardcode an index from the front.
KV_CACHE_TRAILING_DIMS = {"k": 2, "v": 2, "ckv": 1, "krope": 1}


def cache_seq_axis(key: str, ndim: int) -> int:
    """Sequence axis of a KV-cache entry, for any number of leading axes."""
    return ndim - 1 - KV_CACHE_TRAILING_DIMS[key]


def init_kv_cache(cfg: ArchConfig, spec: LayerSpec, batch: int, max_seq: int,
                  dtype=None, leading: tuple = ()):
    """Zero cache for one attention layer (stacked over ``leading``)."""
    dtype = jnp.dtype(cfg.cache_dtype) if dtype is None else dtype
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros(leading + (batch, max_seq, m.kv_lora_rank), dtype),
            "krope": jnp.zeros(leading + (batch, max_seq, m.qk_rope_dim), dtype),
        }
    shape = leading + (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# masks and score paths
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """(Sq, Sk) additive bias from 1-D position vectors (sequence positions
    are uniform across the batch in every path, so the mask never carries a
    batch dim — this keeps the traced mask O(S^2), not O(B*S^2))."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok &= diff >= 0
    if window > 0:
        ok &= diff < window
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)


def _sdpa(q, k, v, bias, cap: float, scale=None):
    """q (B,Sq,H,hd) k,v (B,Sk,K,hd) bias (Sq,Sk) -> (B,Sq,H,hd); the scores
    are scaled by ``scale`` (None: 1/sqrt(hd)).

    k/v stay in their storage dtype (bf16 caches) — the MXU accumulates in
    f32 via ``preferred_element_type``, so no cache-wide f32 convert is ever
    materialised (that convert dominated decode HBM traffic before).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qs = (q.astype(jnp.float32) * scale).astype(k.dtype)
    qs = qs.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qs, k,
                        preferred_element_type=jnp.float32)
    scores = softcap(scores, cap)
    scores = scores + bias
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _dense_attn(q, k, v, q_pos, k_pos, causal, window, cap, scale=None):
    bias = _mask_bias(q_pos, k_pos, causal, window)          # (B,Sq,Sk)
    return _sdpa(q, k, v, bias, cap, scale)


def _chunked_attn(q, k, v, q_pos, k_pos, causal, cap, chunk=_KV_CHUNK,
                  scale=None):
    """Online-softmax scan over kv chunks. Full causal, O(S*chunk) memory."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    G = H // K
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad keys at +inf-like positions so the causal mask kills them
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=10 ** 9)
    kc = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)

    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qf = ((q.astype(jnp.float32) * scale)
          .astype(k.dtype).reshape(B, Sq, K, G, hd))

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bqkgd,bckd->bkgqc", qf, kb,
                       preferred_element_type=jnp.float32)
        s = softcap(s, cap)
        bias = _mask_bias(q_pos, pb, causal, -1)             # (Sq,C)
        s = s + bias
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def _banded_attn(q, k, v, q_pos, k_pos, window, cap, q_block=_Q_BLOCK,
                 scale=None):
    """Sliding-window causal attention: scan over q blocks, static kv band."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    band = window + q_block
    nq = -(-Sq // q_block)
    pad_q = nq * q_block - Sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-(10 ** 9))
    if Sk < band:
        k = jnp.pad(k, ((0, 0), (0, band - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, band - Sk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, band - Sk), constant_values=-(10 ** 9))
        Sk = band
    qb = q.reshape(B, nq, q_block, H, hd).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(nq, q_block)
    idx = jnp.arange(nq)

    def per_block(i, qblk, qpos_blk):
        start = jnp.maximum(i * q_block + q_block - band, 0)
        start = jnp.minimum(start, Sk - band)
        kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        pb = jax.lax.dynamic_slice_in_dim(k_pos, start, band, axis=0)
        bias = _mask_bias(qpos_blk, pb, True, window)        # (bq,band)
        return _sdpa(qblk, kb, vb, bias, cap, scale)

    out = jax.lax.map(lambda xs: per_block(*xs), (idx, qb, qp))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_block, H, hd)
    return out[:, :Sq]


def scaled_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                     cap: float, runtime=None, scale=None):
    """Dispatch over score paths (and the Pallas kernel when enabled).

    q_pos (Sq,), k_pos (Sk,): 1-D global sequence positions; ``scale``
    multiplies the scores (None: 1/sqrt(head_dim)).
    """
    Sq, Sk = q.shape[1], k.shape[1]
    if runtime is not None and getattr(runtime, "use_pallas", False) \
            and causal and Sq == Sk:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True, window=window,
                                    softcap=cap, scale=scale,
                                    policy=kops.policy_from_runtime(runtime))
    if window > 0 and causal and Sq == Sk and Sq > _DENSE_MAX:
        return _banded_attn(q, k, v, q_pos, k_pos, window, cap, scale=scale)
    if max(Sq, Sk) <= _DENSE_MAX or Sq != Sk:
        return _dense_attn(q, k, v, q_pos, k_pos, causal, window, cap, scale)
    return _chunked_attn(q, k, v, q_pos, k_pos, causal, cap, scale=scale)


# ---------------------------------------------------------------------------
# GQA attention layer (full-sequence and decode)
# ---------------------------------------------------------------------------


# The leaves the GQA, cross-attention and MLA paths cast to the compute
# dtype; MLA's q_norm / kv_norm are read as stored.
COMPUTE_CAST = dict.fromkeys(
    ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "xwq", "xwk", "xwv", "xwo",
     "wq_a", "wq_b", "wkv_a", "wkv_b"), True)


def _project_qkv(params, x, cfg: ArchConfig, compute_dtype):
    xc = x.astype(compute_dtype)
    q = xc @ params["wq"].astype(compute_dtype)
    k = xc @ params["wk"].astype(compute_dtype)
    v = xc @ params["wv"].astype(compute_dtype)
    if "bq" in params:
        q = q + params["bq"].astype(compute_dtype)
        k = k + params["bk"].astype(compute_dtype)
        v = v + params["bv"].astype(compute_dtype)
    B, S = x.shape[:2]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def attn_forward(params, x, *, cfg: ArchConfig, spec: LayerSpec, positions,
                 window: int, runtime=None):
    """Full-sequence self-attention (train / prefill). Returns (out, kv)."""
    compute = jnp.dtype(cfg.compute_dtype)
    if cfg.mla is not None:
        return _mla_forward(params, x, cfg=cfg, positions=positions,
                            window=window, runtime=runtime)
    q, k, v = _project_qkv(params, x, cfg, compute)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    pos1d = jnp.arange(x.shape[1], dtype=jnp.int32)
    out = scaled_attention(q, k, v, pos1d, pos1d, causal=True, window=window,
                           cap=cfg.attn_softcap, runtime=runtime)
    out = out.reshape(x.shape[0], x.shape[1], cfg.q_dim)
    out = (out.astype(compute) @ params["wo"].astype(compute)).astype(x.dtype)
    cache_dt = jnp.dtype(cfg.cache_dtype)
    return out, {"k": k.astype(cache_dt), "v": v.astype(cache_dt)}


def attn_decode(params, x, cache, pos, *, cfg: ArchConfig, spec: LayerSpec,
                window: int, runtime=None):
    """One-token decode against a cache. x (B,1,d); pos scalar int32."""
    compute = jnp.dtype(cfg.compute_dtype)
    if cfg.mla is not None:
        return _mla_decode(params, x, cache, pos, cfg=cfg, window=window)
    B = x.shape[0]
    S = cache["k"].shape[1]
    q, k_new, v_new = _project_qkv(params, x, cfg, compute)
    positions = jnp.full((B, 1), pos, jnp.int32)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.mrope_sections)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    k_pos = jnp.arange(S, dtype=jnp.int32)
    # mask out not-yet-written slots and out-of-window slots
    valid = k_pos <= pos
    if window > 0:
        valid &= k_pos > pos - window
    bias = jnp.where(valid, 0.0, _NEG).astype(jnp.float32)
    out = _sdpa(q, k, v, bias[None], cfg.attn_softcap)
    out = out.reshape(B, 1, cfg.q_dim)
    out = (out.astype(compute) @ params["wo"].astype(compute)).astype(x.dtype)
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_attn_forward(params, x, enc_k, enc_v, *, cfg: ArchConfig):
    compute = jnp.dtype(cfg.compute_dtype)
    B, S = x.shape[:2]
    q = (x.astype(compute) @ params["xwq"].astype(compute)).reshape(
        B, S, cfg.n_heads, cfg.head_dim)
    bias = jnp.zeros((S, enc_k.shape[1]), jnp.float32)
    out = _sdpa(q, enc_k, enc_v, bias, 0.0)
    out = out.reshape(B, S, cfg.q_dim)
    return (out.astype(compute) @ params["xwo"].astype(compute)).astype(x.dtype)


def cross_kv(params, enc_out, *, cfg: ArchConfig):
    compute = jnp.dtype(cfg.compute_dtype)
    B, S = enc_out.shape[:2]
    k = (enc_out.astype(compute) @ params["xwk"].astype(compute)).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out.astype(compute) @ params["xwv"].astype(compute)).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim)
    cache_dt = jnp.dtype(cfg.cache_dtype)
    return k.astype(cache_dt), v.astype(cache_dt)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def _mla_rope(x, positions, cfg: ArchConfig):
    """Rotary embedding of MLA's decoupled rope part (B,S,H,rd), under
    YaRN where the config has it."""
    if cfg.yarn is None:
        return apply_rope(x, positions, cfg.rope_theta)
    rot, _ = yarn_scales(cfg.yarn)
    return apply_rope(x, positions, cfg.rope_theta,
                      inv_freq=yarn_freqs(x.shape[-1], cfg.rope_theta,
                                          cfg.yarn), mscale=rot)


def _mla_softmax_scale(cfg: ArchConfig) -> float:
    """1/sqrt(nope + rope dims), times YaRN's mscale(mscale_all_dim)^2."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    return scale if cfg.yarn is None else scale * yarn_scales(cfg.yarn)[1]


def _mla_q(params, x, cfg: ArchConfig, compute):
    m = cfg.mla
    B, S = x.shape[:2]
    xc = x.astype(compute)
    if "wq_a" in params:
        qa = xc @ params["wq_a"].astype(compute)
        qa = apply_norm(params["q_norm"], qa, cfg.norm, cfg.norm_eps)
        q = qa.astype(compute) @ params["wq_b"].astype(compute)
    else:
        q = xc @ params["wq"].astype(compute)
    q = q.reshape(B, S, cfg.n_heads, m.qk_nope_dim + m.qk_rope_dim)
    return q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]


def _mla_latents(params, x, cfg: ArchConfig, positions, compute):
    m = cfg.mla
    xc = x.astype(compute)
    kv_a = xc @ params["wkv_a"].astype(compute)
    ckv = apply_norm(params["kv_norm"], kv_a[..., :m.kv_lora_rank],
                     cfg.norm, cfg.norm_eps)
    krope = kv_a[..., m.kv_lora_rank:]                        # (B,S,rd)
    krope = _mla_rope(krope[:, :, None, :], positions, cfg)[:, :, 0]
    return ckv, krope


def _mla_wkvb_split(params, cfg: ArchConfig, compute):
    m = cfg.mla
    w = params["wkv_b"].astype(compute).reshape(
        m.kv_lora_rank, cfg.n_heads, m.qk_nope_dim + m.v_head_dim)
    return w[..., :m.qk_nope_dim], w[..., m.qk_nope_dim:]    # (r,H,nd),(r,H,vd)


def _mla_forward(params, x, *, cfg: ArchConfig, positions, window, runtime=None):
    """Full-sequence MLA with expanded keys and values.  Batches of more
    than ``_MLA_TOKENS`` tokens run their expansion and attention a few
    rows at a time, so the expanded q/k/v of the whole batch are never
    held at once."""
    compute = jnp.dtype(cfg.compute_dtype)
    B, S = x.shape[:2]
    ckv, krope = _mla_latents(params, x, cfg, positions, compute)

    def attend(a):
        xb, ckvb, kropeb, posb = a
        return _mla_attend(params, xb, ckvb, kropeb, posb, cfg=cfg,
                           window=window, runtime=runtime)

    rows = max(1, min(B, _MLA_TOKENS // S))
    while B % rows:
        rows -= 1
    if rows == B:
        out = attend((x, ckv, krope, positions))
    else:
        def split(a):
            return a.reshape((B // rows, rows) + a.shape[1:])
        out = jax.lax.map(attend, tuple(split(a) for a in
                                        (x, ckv, krope, positions)))
        out = out.reshape((B,) + out.shape[2:])
    cache_dt = jnp.dtype(cfg.cache_dtype)
    return out, {"ckv": ckv.astype(cache_dt), "krope": krope.astype(cache_dt)}


def _mla_attend(params, x, ckv, krope, positions, *, cfg: ArchConfig,
                window, runtime):
    """Causal MLA of rows x (b,S,d) over their latents: q, keys and values
    expanded per head (the rope part of the keys shared), then the output
    projection."""
    m = cfg.mla
    compute = jnp.dtype(cfg.compute_dtype)
    B, S = x.shape[:2]
    q_nope, q_rope = _mla_q(params, x, cfg, compute)
    q_rope = _mla_rope(q_rope, positions, cfg)
    wk, wv = _mla_wkvb_split(params, cfg, compute)
    # expand keys/values (chunk-recomputed inside scaled_attention paths by
    # concatenating rope and nope sections into a single head_dim)
    k_nope = jnp.einsum("bsr,rhd->bshd", ckv, wk)
    v = jnp.einsum("bsr,rhd->bshd", ckv, wv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :],
                                  (B, S, cfg.n_heads, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    pos1d = jnp.arange(S, dtype=jnp.int32)
    # pad v to q/k head_dim for the shared kernel, then strip
    vd = m.v_head_dim
    hd = q.shape[-1]
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd - vd))) if hd > vd else v
    out = scaled_attention(q, k, v_pad, pos1d, pos1d, causal=True,
                           window=window, cap=0.0, runtime=runtime,
                           scale=_mla_softmax_scale(cfg))
    out = out[..., :vd].reshape(B, S, cfg.n_heads * vd)
    return (out.astype(compute) @ params["wo"].astype(compute)).astype(x.dtype)


def _mla_decode(params, x, cache, pos, *, cfg: ArchConfig, window: int):
    """Absorbed MLA decode: scores and values stay in the latent space."""
    m = cfg.mla
    compute = jnp.dtype(cfg.compute_dtype)
    B = x.shape[0]
    S = cache["ckv"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(params, x, cfg, compute)          # (B,1,H,*)
    q_rope = _mla_rope(q_rope, positions, cfg)
    ckv_new, krope_new = _mla_latents(params, x, cfg, positions, compute)
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["krope"], krope_new.astype(cache["krope"].dtype), pos, axis=1)
    wk, wv = _mla_wkvb_split(params, cfg, compute)
    # absorb: q_eff[h,r] = sum_d q_nope[h,d] * wk[r,h,d]
    q_eff = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk,
                       preferred_element_type=jnp.float32)
    scale = _mla_softmax_scale(cfg)
    s_lat = jnp.einsum("bqhr,bsr->bhqs", q_eff.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(krope.dtype), krope,
                        preferred_element_type=jnp.float32)
    scores = (s_lat + s_rope) * scale
    k_pos = jnp.arange(S, dtype=jnp.int32)
    valid = k_pos <= pos
    if window > 0:
        valid &= k_pos > pos - window
    scores = scores + jnp.where(valid, 0.0, _NEG)[None, None, None, :]
    w8 = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhqs,bsr->bqhr", w8.astype(ckv.dtype), ckv,
                         preferred_element_type=jnp.float32)
    out = jnp.einsum("bqhr,rhd->bqhd", out_lat.astype(wv.dtype), wv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, cfg.n_heads * m.v_head_dim).astype(compute)
    out = (out @ params["wo"].astype(compute)).astype(x.dtype)
    return out, {"ckv": ckv, "krope": krope}
