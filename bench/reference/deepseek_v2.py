"""Plain reference of DeepSeek-V2 (arXiv:2405.04434; the released
config.json and modeling code): full forward in float32 at HIGHEST matmul
precision, one layer at a time.  It imports nothing of the program.

Per layer: RMSNorm; multi-head latent attention in its plain form (q
through the rank-1536 down-projection, its RMSNorm and the up-projection;
keys and values expanded from the normed 512-dim latent; a 64-dim rotary
part shared by the heads' keys); YaRN written from the published formulas
(frequencies interpolated between ``beta_fast`` and ``beta_slow`` turns,
cos and sin times mscale(mscale)/mscale(mscale_all_dim), the softmax scale
1/sqrt(192) times mscale(mscale_all_dim)^2); causal softmax attention; then
the dense SiLU-gated MLP of the first ``first_k_dense_replace`` layers, or
the expert layer: softmax scores over the router's experts, group-limited
greedy routing (the best ``topk_group`` of ``n_group`` groups by their top
score, scores elsewhere set to zero, then the top ``num_experts_per_tok``),
gates not renormalised and times ``routed_scaling_factor``, each held
expert's SiLU-gated MLP over every token routed to it, and the shared
experts' MLP over every token.  Final RMSNorm and an untied head.

Departures from the published model, as the configuration states them:
- the layer holds only the experts of the held group
  (``held_group`` x ``n_routed_experts``, of the router's
  ``router_experts``): the other groups' outputs are left out, and that
  partial sum goes on to the next layer, as on one chip of the deployment;
- the vocabulary is the slice of ``vocab_size`` ids: logits are over it;
- depth is ``num_hidden_layers`` of the published 60;
- the rotary dims are taken in rotate-half order; the released code
  de-interleaves them first, which is a fixed permutation of the rotary
  columns of ``wq_b`` and ``wkv_a`` (weights here are seeded).

``quant="fp8"`` is the lower-precision control: every matmul's operands
rounded to float8 e4m3 with a per-tensor absmax scale, accumulated in
float32.

Weights live in the layout the serving program takes:
{"embed": {"embedding", "unembed"}, "final_norm": {"scale"},
 "stages": [dense layers, expert layers], each {"l0": {"norm1",
 "core": {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"},
 "norm2", "ffn": {"wg", "wi", "wdown"} or {"router", "we_gate", "we_up",
 "we_down", "shared": {"wg", "wi", "wdown"}}}}, layers stacked on the
leading axis."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
_HEAD_BLOCK = 16          # attention heads whose scores are formed at once


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the serving layout, from the configuration."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, qr = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense

    def core(n):
        return {"wq_a": (n, d, qr), "q_norm": {"scale": (n, qr)},
                "wq_b": (n, qr, h * (nope + rope)),
                "wkv_a": (n, d, r + rope), "kv_norm": {"scale": (n, r)},
                "wkv_b": (n, r, h * (nope + vd)), "wo": (n, h * vd, d)}

    def mlp(n, w):
        return {"wg": (n, d, w), "wi": (n, d, w), "wdown": (n, w, d)}

    def layer(n, ffn):
        return {"l0": {"norm1": {"scale": (n, d)}, "core": core(n),
                       "norm2": {"scale": (n, d)}, "ffn": ffn}}

    experts = {"router": (moe, d, cfg["router_experts"]),
               "we_gate": (moe, e, d, f), "we_up": (moe, e, d, f),
               "we_down": (moe, e, f, d),
               "shared": mlp(moe, cfg["n_shared_experts"] * f)}
    stages = [layer(dense, mlp(dense, cfg["intermediate_size"]))]
    if moe:
        stages.append(layer(moe, experts))
    v = cfg["vocab_size"]
    return {"embed": {"embedding": (v, d), "unembed": (d, v)},
            "final_norm": {"scale": (d,)}, "stages": stages}


def init(key, cfg: dict):
    """Seeded weights in ``param_dtype``: norms at one, the embedding and
    the head N(0, 0.02), every other matrix N(0, 1/fan_in)."""
    dtype = jnp.dtype(cfg["param_dtype"])
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shp) in zip(keys, flat):
        path = jax.tree_util.keystr(path)
        if "scale" in path:
            out.append(jnp.ones(shp, dtype))
        elif "embedding" in path or "unembed" in path:
            out.append(0.02 * jax.random.normal(k, shp, dtype))
        else:
            out.append(jax.random.normal(k, shp, dtype)
                       * (1.0 / math.sqrt(shp[-2])))
    return jax.tree_util.tree_unflatten(tree, out)


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2 modeling code: yarn_get_mscale, yarn_find_correction_*)
# ---------------------------------------------------------------------------


def yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]

    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64)
                                 / dim))
    freq_inter = 1.0 / (rs["factor"] * base ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def rotary_mscale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    return (yarn_mscale(rs["factor"], rs["mscale"])
            / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


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


def _rope(x, cfg):
    """x (S, H, rd) at positions 0..S-1, rotate-half order."""
    s = x.shape[0]
    inv = jnp.asarray(yarn_inv_freq(cfg), F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    m = rotary_mscale(cfg)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * (jnp.cos(ang) * m) + rot * (jnp.sin(ang) * m)


def _attention_row(p, x, cfg, quant):
    """Plain MLA over one sequence x (S, d)."""
    s = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c = p["core"]
    q = _mm(_rms(_mm(x, c["wq_a"], quant), c["q_norm"]["scale"], eps),
            c["wq_b"], quant).reshape(s, h, nope + rope)
    kv_a = _mm(x, c["wkv_a"], quant)
    ckv = _rms(kv_a[:, :r], c["kv_norm"]["scale"], eps)
    k_rope = _rope(kv_a[:, None, r:], cfg)                     # (S, 1, rd)
    kv = _mm(ckv, c["wkv_b"], quant).reshape(s, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, h, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    if quant == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)
    mask = jnp.tril(jnp.ones((s, s), bool))
    blk = min(_HEAD_BLOCK, h)

    def heads(args):
        qb, kb, vb = args                                      # (S, hb, *)
        sc = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HI) \
            * softmax_scale(cfg)
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        if quant == "fp8":
            w = _q8(w)
        return jnp.einsum("hqk,khd->qhd", w, vb, precision=HI)

    def split(a):
        return a.reshape(s, h // blk, blk, -1).transpose(1, 0, 2, 3)

    o = jax.lax.map(heads, (split(q), split(k), split(v)))    # (h/b,S,b,vd)
    o = o.transpose(1, 0, 2, 3).reshape(s, h * vd)
    return _mm(o, c["wo"], quant)


def _mlp(w, x, quant):
    return _mm(jax.nn.silu(_mm(x, w["wg"], quant))
               * _mm(x, w["wi"], quant), w["wdown"], quant)


def route_gates(logits, cfg: dict):
    """Dense gates (T, R): each token's chosen experts' weights, zero
    elsewhere (DeepSeekV2MoEGate, softmax scores): ``topk_method``
    ``group_limited_greedy`` picks the top experts inside the best
    ``topk_group`` groups, ``greedy`` the top experts of all."""
    scores = jax.nn.softmax(logits, axis=-1)
    t, n = scores.shape
    k = cfg["num_experts_per_tok"]
    scores_for_choice = scores
    if cfg["topk_method"] == "group_limited_greedy":
        g, tg = cfg["n_group"], cfg["topk_group"]
        group_scores = scores.reshape(t, g, n // g).max(-1)
        group_idx = jax.lax.top_k(group_scores, tg)[1]
        group_mask = jnp.zeros((t, g), F32).at[
            jnp.arange(t)[:, None], group_idx].set(1.0)
        score_mask = jnp.repeat(group_mask, n // g, axis=1)
        scores_for_choice = jnp.where(score_mask > 0, scores, 0.0)
    weight, idx = jax.lax.top_k(scores_for_choice, k)
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    else:
        weight = weight * cfg["routed_scaling_factor"]
    return jnp.zeros((t, n), F32).at[jnp.arange(t)[:, None], idx].set(
        weight)


def held_distances(logits, cfg: dict):
    """(T, E): for each token and held expert, how far in router-logit
    units group-limited routing lies from changing whether the token
    reaches that expert.  With the held group left out, the group's
    distance to the ``topk_group`` boundary; with it chosen, the smaller
    of the gap at that boundary (another group swapped in changes the
    experts that compete) and the expert's distance to the
    ``num_experts_per_tok`` boundary.  Softmax keeps the order of a
    token's logits, so each boundary is a gap between two logits."""
    t, n = logits.shape
    k = cfg["num_experts_per_tok"]
    g, tg = cfg["n_group"], cfg["topk_group"]
    per, h = n // g, cfg["held_group"]
    group = logits.reshape(t, g, per).max(-1)
    gt = jax.lax.top_k(group, tg + 1)[0]
    g_in, g_out = gt[:, tg - 1:tg], gt[:, tg:tg + 1]
    chosen = group >= g_in
    masked = jnp.where(jnp.repeat(chosen, per, axis=1), logits, -jnp.inf)
    et = jax.lax.top_k(masked, k + 1)[0]
    e_in, e_out = et[:, k - 1:k], et[:, k:k + 1]
    held = logits[:, h * per:(h + 1) * per]
    expert = jnp.where(held >= e_in, held - e_out, e_in - held)
    return jnp.where(chosen[:, h:h + 1], jnp.minimum(g_in - g_out, expert),
                     g_in - group[:, h:h + 1])


def experts(f, x, cfg: dict, quant=None, held=None):
    """An expert layer over tokens x (T, d): the held experts' gated part
    plus the shared experts, and the routing: ``held`` (T, E) bool, true
    where the layer's own routing reaches held expert e; ``boundary``
    (T,), the token's smallest :func:`held_distances`; and ``misrouted``
    (T,), the widest :func:`held_distances` at which the given ``held``
    (T, E) differs from that routing (0 where they agree or none is
    given).  Given ``held``, the layer computes the experts it
    names, each gated by this layer's own score times
    ``routed_scaling_factor``."""
    router = _mm(x, f["router"], quant)
    gates = route_gates(router, cfg)
    e_held = cfg["n_routed_experts"]
    first = cfg["held_group"] * e_held
    own = gates[:, first:first + e_held] > 0
    distances = held_distances(router, cfg)
    misrouted = jnp.zeros(x.shape[:1], F32)
    if held is None:
        g_held = gates[:, first:first + e_held]
    else:
        if cfg["norm_topk_prob"]:
            raise ValueError("given routing needs unnormalised gates")
        chosen = held
        g_held = jnp.where(chosen, jax.nn.softmax(router, -1)[
            :, first:first + e_held] * cfg["routed_scaling_factor"], 0.0)
        misrouted = jnp.max(jnp.where(chosen != own, distances, 0.0),
                            axis=-1)
    out = _mlp(f["shared"], x, quant)
    for e in range(e_held):
        g = g_held[:, e]
        w = {"wg": f["we_gate"][e], "wi": f["we_up"][e],
             "wdown": f["we_down"][e]}
        y = _mlp(w, x, quant)
        out = out + jnp.where((g > 0)[:, None], y * g[:, None], 0.0)
    return out, {"held": own, "boundary": jnp.min(distances, axis=-1),
                 "misrouted": misrouted}


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def layer(p, x, held=None, *, cfg_key, quant=None):
    """One decoder layer over rows x (R, S, d), float32 throughout: the
    layer's weights are upcast here, one layer at a time.  Returns the
    new x and, in an expert layer, its routing (:func:`experts`, per row
    and position); ``held`` (R, S, E) routes an expert layer as it says."""
    cfg = _thaw(cfg_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    eps = cfg["rms_norm_eps"]
    rows, s, d = x.shape
    a = _rms(x, p["norm1"]["scale"], eps)
    x = x + jax.lax.map(lambda xr: _attention_row(p, xr, cfg, quant), a)
    a = _rms(x, p["norm2"]["scale"], eps).reshape(rows * s, d)
    if "router" not in p["ffn"]:
        return x + _mlp(p["ffn"], a, quant).reshape(rows, s, d), None
    y, routing = experts(p["ffn"], a, cfg, quant, None if held is None
                         else held.reshape(rows * s, -1))
    return x + y.reshape(rows, s, d), {
        k: v.reshape((rows, s) + v.shape[1:]) for k, v in routing.items()}


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def head(params, x, *, cfg_key, quant=None):
    cfg = _thaw(cfg_key)
    a = _rms(x, params["final_norm"]["scale"].astype(F32),
             cfg["rms_norm_eps"])
    return _mm(a, params["embed"]["unembed"].astype(F32), quant)


_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "rope_theta", "rope_scaling", "n_group",
         "topk_group", "topk_method", "num_experts_per_tok",
         "norm_topk_prob",
         "routed_scaling_factor", "n_routed_experts", "held_group")


def freeze(cfg: dict):
    return tuple((k, tuple(sorted(cfg[k].items())) if isinstance(cfg[k], dict)
                  else cfg[k]) for k in _KEYS)


def _thaw(cfg_key) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_key}


def forward(params, tokens, cfg: dict, last: int, quant=None, held=None):
    """Float32 logits at the last ``last`` positions of ``tokens`` (R, S),
    over the configuration's vocabulary, and the routing: ``held``
    (L, R, S, E) bool, true where a token reaches held expert e in each
    of the L expert layers, ``boundary`` (L, R, S), the distances of
    :func:`experts` per layer, and ``misrouted`` (R, S),
    each token's widest :func:`experts` ``misrouted`` over the layers.
    ``held`` given in that form routes the expert layers as it says."""
    fz = freeze(cfg)
    x = params["embed"]["embedding"][tokens].astype(F32)
    own, boundary, misrouted, moe = [], [], jnp.zeros(tokens.shape, F32), 0
    for stage in params["stages"]:
        lp = stage["l0"]
        for i in range(jax.tree_util.tree_leaves(lp)[0].shape[0]):
            p = jax.tree_util.tree_map(lambda a: a[i], lp)
            given = None
            if held is not None and "router" in p["ffn"]:
                given = jnp.asarray(held[moe], bool)
            x, routing = layer(p, x, given, cfg_key=fz, quant=quant)
            if routing is not None:
                own.append(routing["held"])
                boundary.append(routing["boundary"])
                misrouted = jnp.maximum(misrouted, routing["misrouted"])
                moe += 1
    out = head(params, x[:, -last:], cfg_key=fz, quant=quant)
    return out, {"held": jnp.stack(own) if own else None,
                 "boundary": jnp.stack(boundary) if boundary else None,
                 "misrouted": misrouted}


def logits(params, tokens, cfg: dict, last: int, quant=None):
    """The logits of :func:`forward` under the reference's own routing."""
    return forward(params, tokens, cfg, last, quant)[0]
