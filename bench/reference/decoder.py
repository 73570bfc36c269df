"""Plain reference of a dense GQA decoder (the internlm2 family): full
forward in float32 at HIGHEST matmul precision, one layer at a time, with
RMSNorm, rotary embeddings (rotate-half, NeoX order), causal softmax
attention with grouped key/value heads, a SiLU-gated MLP and an untied
head.  It imports nothing of the program.

``quant="fp8"`` is the lower-precision control: every matmul's operands
rounded to float8 e4m3 with a per-tensor absmax scale, accumulated in
float32.

Weights live in the layout the serving program takes:
{"embed": {"embedding", "unembed"}, "final_norm": {"scale"},
 "stages": [{"l0": {"norm1", "core": {"wq","wk","wv","wo"}, "norm2",
                    "ffn": {"wg","wi","wdown"}}}]}, layers stacked on the
leading axis."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the serving layout, from the configuration."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]
    layer = {"norm1": {"scale": (L, d)},
             "core": {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
                      "wo": (L, q, d)},
             "norm2": {"scale": (L, d)},
             "ffn": {"wg": (L, d, ff), "wi": (L, d, ff), "wdown": (L, ff, d)}}
    return {"embed": {"embedding": (v, d), "unembed": (d, v)},
            "final_norm": {"scale": (d,)}, "stages": [{"l0": layer}]}


def init(key, cfg: dict):
    """Seeded weights: norms at one, the embedding N(0, 0.02), the head
    N(0, 0.02), every other matrix N(0, 1/fan_in)."""
    leaves, tree = jax.tree_util.tree_flatten(
        shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shp, path in zip(keys, leaves, paths):
        if "scale" in path:
            out.append(jnp.ones(shp, F32))
        elif "embedding" in path or "unembed" in path:
            out.append(0.02 * jax.random.normal(k, shp, F32))
        else:
            out.append(jax.random.normal(k, shp, F32) / math.sqrt(shp[-2]))
    return jax.tree_util.tree_unflatten(tree, out)


def _q8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def layer(p, x, *, cfg_key, quant=None):
    cfg = dict(cfg_key)
    b, s, d = x.shape
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    a = _rms(x, p["norm1"]["scale"], eps)
    q = _mm(a, p["core"]["wq"], quant).reshape(b, s, h, hd)
    k = _mm(a, p["core"]["wk"], quant).reshape(b, s, kvh, hd)
    v = _mm(a, p["core"]["wv"], quant).reshape(b, s, kvh, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    g = h // kvh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    if quant == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    if quant == "fp8":
        w = _q8(w)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI).reshape(b, s, h * hd)
    x = x + _mm(o, p["core"]["wo"], quant)
    a = _rms(x, p["norm2"]["scale"], eps)
    up = jax.nn.silu(_mm(a, p["ffn"]["wg"], quant)) * _mm(a, p["ffn"]["wi"],
                                                         quant)
    return x + _mm(up, p["ffn"]["wdown"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def head(params, x, *, cfg_key, quant=None):
    cfg = dict(cfg_key)
    a = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(a, params["embed"]["unembed"], quant)


def freeze(cfg: dict):
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rope_theta", "rms_norm_eps")
    return tuple((k, cfg[k]) for k in keys)


def logits(params, tokens, cfg: dict, last: int, quant=None):
    """Float32 logits at the last ``last`` positions of ``tokens`` (B, S)."""
    fz = freeze(cfg)
    x = params["embed"]["embedding"][tokens].astype(F32)
    stage = params["stages"][0]["l0"]
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], stage)
        x = layer(p, x, cfg_key=fz, quant=quant)
    return head(params, x[:, -last:], cfg_key=fz, quant=quant)
