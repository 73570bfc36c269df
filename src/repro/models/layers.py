"""Shared neural-net building blocks (pure functions over param pytrees).

Conventions
-----------
- ``init_*`` functions return nested dicts of jnp arrays; leaf *names* are the
  contract with ``repro.sharding.rules`` (path-based PartitionSpec mapping).
- ``apply`` functions take ``params`` first and are shape-polymorphic over a
  leading batch/seq prefix.
- Matmuls run in ``compute_dtype`` (bf16 on TPU); accumulations that need it
  (softmax, norms, losses) run in f32.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------


def init_norm(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def compute_cast_mask(tree, cast):
    """``tree``'s structure with True at each leaf that ``cast`` names.

    ``cast`` mirrors the part of ``tree`` that a module casts to the compute
    dtype before use: True at such a leaf, a dict or list at a group.  A
    leaf it does not name (norms, leaves read in float32) is False."""
    if isinstance(tree, dict):
        cast = cast if isinstance(cast, dict) else {}
        return {k: compute_cast_mask(v, cast.get(k)) for k, v in tree.items()}
    if isinstance(tree, list):
        cast = cast if isinstance(cast, list) else [None] * len(tree)
        return [compute_cast_mask(v, c) for v, c in zip(tree, cast)]
    return cast is True


def apply_norm(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def softcap(x, cap: float):
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, d, d_ff, dtype),
        "wi": dense_init(k2, d, d_ff, dtype),
        "wdown": dense_init(k3, d_ff, d, dtype),
    }


# The leaves apply_mlp casts to the compute dtype.
MLP_COMPUTE_CAST = dict.fromkeys(("wg", "wi", "wdown"), True)


def apply_mlp(params, x, act: str, compute_dtype, sc=None):
    xc = x.astype(compute_dtype)
    g = xc @ params["wg"].astype(compute_dtype)
    h = xc @ params["wi"].astype(compute_dtype)
    a = activation(act)(g) * h
    if sc is not None:
        a = sc.shard_act_ff(a)
    out = a @ params["wdown"].astype(compute_dtype)
    return out.astype(x.dtype), a


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x, positions, theta: float,
               mrope_sections: Optional[Tuple[int, int, int]] = None):
    """Rotary embedding.

    x: (..., S, n_heads, head_dim); positions: (B, S) int32 or (3, B, S) for
    M-RoPE (temporal/height/width ids — equal for pure-text streams).
    """
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta)                       # (half,)
    if mrope_sections is None:
        pos = positions if positions.ndim == 2 else positions[0]
        ang = pos[..., None].astype(jnp.float32) * inv      # (B, S, half)
    else:
        if positions.ndim == 2:                             # text-only stream
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        parts = []
        start = 0
        for sec, p in zip(mrope_sections, positions):
            parts.append(p[..., None].astype(jnp.float32) * inv[start:start + sec])
            start += sec
        ang = jnp.concatenate(parts, axis=-1)               # (B, S, half)
    ang = jnp.concatenate([ang, ang], axis=-1)              # (B, S, head_dim)
    cos = jnp.cos(ang)[..., None, :]                        # (B, S, 1, hd)
    sin = jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    out = xf * cos + _rotate_half(xf) * sin
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype, tied: bool):
    k1, k2 = jax.random.split(key)
    p = {"embedding": embed_init(k1, vocab, d, dtype)}
    if not tied:
        p["unembed"] = dense_init(k2, d, vocab, dtype, scale=0.02)
    return p


# The leaves embed_tokens and unembed cast to the compute dtype.
EMBED_COMPUTE_CAST = dict.fromkeys(("embedding", "unembed"), True)


def embed_tokens(params, tokens, compute_dtype):
    return params["embedding"].astype(compute_dtype)[tokens]


def unembed(params, x, compute_dtype, final_cap: float = 0.0):
    xc = x.astype(compute_dtype)
    if "unembed" in params:
        logits = xc @ params["unembed"].astype(compute_dtype)
    else:
        logits = xc @ params["embedding"].astype(compute_dtype).T
    logits = logits.astype(jnp.float32)
    return softcap(logits, final_cap)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE in f32. logits (B,S,V) f32, labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# feature signatures (paper Eq. 3-4, transformer adaptation)
# ---------------------------------------------------------------------------


def activation_signature(h, n_sig: int = 64, tau: float = 0.05):
    """Threshold-zero fraction of hidden activations, bucketed to n_sig dims.

    The paper's Eq. 3 counts exact zeros of post-ReLU conv maps; GeLU/SiLU
    emit no exact zeros, so the transformer adaptation uses |a| < tau.
    h: (..., d) -> (n_sig,) f32, averaged over all leading axes.
    """
    d = h.shape[-1]
    pad = (-d) % n_sig
    flags = (jnp.abs(h.astype(jnp.float32)) < tau).astype(jnp.float32)
    flags = flags.reshape(-1, d)
    if pad:
        flags = jnp.pad(flags, ((0, 0), (0, pad)))
    flags = flags.reshape(flags.shape[0], n_sig, -1)
    return jnp.mean(flags, axis=(0, 2))
