"""Mixture-of-Experts FFN: capacity dispatch for training, every routed
row for serving.

Training (``moe_forward``): tokens are grouped (``group_size`` tokens per
dispatch group) so the dispatch tensor is (G, S_g, E, C) with per-group
capacity C = ceil(S_g * top_k / E * capacity_factor); experts shard over the
``model`` mesh axis (expert parallelism) and groups over ``data``, so XLA
materialises the all-to-all in the lowered HLO — which is exactly what the
roofline's collective term wants to see.  Overflow tokens are dropped
(standard Switch behaviour); the router carries a load-balance aux loss and
a z-loss.

Serving (``moe_serve``): no capacity and no drops.  The layer may hold a
share of the experts its router scores (``MoEConfig.expert_offset`` /
``n_experts`` of ``router_width``), as one chip of an expert-parallel
deployment does: it routes every token over all of them, then computes
only its own experts' gated outputs, for every row routed to them, by a
grouped matmul over the rows sorted by expert (``kernels/moe_gmm.py``).
The absent experts' part is left out; shared experts are computed whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.layers import (MLP_COMPUTE_CAST, activation, apply_mlp,
                                 dense_init, init_mlp)

_GROUP = 512
_SERVE_CHUNK = 4096      # tokens a serving expert pass routes at a time


def init_moe(key, cfg: ArchConfig, dtype):
    mo = cfg.moe
    d = cfg.d_model
    keys = jax.random.split(key, 5)
    p = {
        "router": dense_init(keys[0], d, mo.router_width, dtype, scale=0.02),
        "we_gate": _expert_init(keys[1], mo.n_experts, d, mo.d_expert, dtype),
        "we_up": _expert_init(keys[2], mo.n_experts, d, mo.d_expert, dtype),
        "we_down": _expert_init(keys[3], mo.n_experts, mo.d_expert, d, dtype),
    }
    if mo.n_shared:
        p["shared"] = init_mlp(keys[4], d, mo.n_shared * mo.d_expert, dtype)
    return p


def _expert_init(key, e, din, dout, dtype):
    import math
    return (jax.random.normal(key, (e, din, dout), jnp.float32)
            / math.sqrt(din)).astype(dtype)


# The leaves moe_forward casts to the compute dtype: router, experts and
# the shared MLP.
COMPUTE_CAST = {**dict.fromkeys(("router", "we_gate", "we_up", "we_down"),
                                True),
                "shared": MLP_COMPUTE_CAST}


def moe_forward(params, x, *, cfg: ArchConfig, sc=None):
    """Training: x (B, S, d) -> (out, aux) where aux has load-balance and z
    losses; Switch-style ``capacity_factor`` dropping."""
    mo = cfg.moe
    if mo.router_width != mo.n_experts:
        raise NotImplementedError("training holds every routed expert")
    compute = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    E, k = mo.n_experts, mo.top_k

    tokens = x.reshape(B * S, d)
    g_size = min(_GROUP, B * S)
    n_groups = (B * S) // g_size
    rem = B * S - n_groups * g_size
    if rem:                                   # pad to whole groups
        tokens = jnp.pad(tokens, ((0, g_size - rem), (0, 0)))
        n_groups += 1
    xg = tokens.reshape(n_groups, g_size, d).astype(compute)

    logits = (xg @ params["router"].astype(compute)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (G,Sg,E)

    cap = max(int(g_size * k / E * mo.capacity_factor), 1)

    # top-k routing with per-slot cumulative capacity positions
    routed = probs * _group_mask(probs, mo) if mo.n_group > 1 else probs
    gates, dispatch = _topk_dispatch(routed, k, cap,
                                     normalize=mo.norm_topk)    # (G,Sg,E,C)
    if not mo.norm_topk:
        gates = gates * mo.routed_scale

    # dispatch tokens to expert slots
    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(compute), xg)
    # expert FFN (E sharded over "model")
    act = activation(cfg.act)
    h = act(jnp.einsum("gecd,edf->gecf", xe, params["we_gate"].astype(compute)))
    h = h * jnp.einsum("gecd,edf->gecf", xe, params["we_up"].astype(compute))
    ye = jnp.einsum("gecf,efd->gecd", h, params["we_down"].astype(compute))
    # combine
    combine = (dispatch.astype(jnp.float32) * gates[..., None]).astype(compute)
    out = jnp.einsum("gsec,gecd->gsd", combine, ye)

    out = out.reshape(-1, d)[: B * S].reshape(B, S, d)

    if mo.n_shared:
        shared, _ = apply_mlp(params["shared"], x, cfg.act, compute, sc=sc)
        out = out + shared.reshape(B, S, d)

    # aux losses (Switch-style load balance + router z-loss)
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    ce = jnp.mean(jnp.max(dispatch, axis=-1).reshape(-1, E).astype(jnp.float32),
                  axis=0)
    aux_lb = E * jnp.sum(me * ce) * mo.router_aux_weight
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    aux_z = jnp.mean(jnp.square(z)) * mo.router_z_weight
    return out.astype(x.dtype), {"moe_aux": aux_lb + aux_z,
                                 "expert_load": ce}


def _topk_dispatch(probs, k: int, cap: int, normalize: bool = True):
    """Greedy top-k dispatch with capacity. Returns (gates (G,Sg,E),
    dispatch one-hot (G,Sg,E,C)); ``normalize`` rescales each token's
    kept gates to sum to one."""
    G, Sg, E = probs.shape
    remaining = probs
    fill = jnp.zeros((G, E), jnp.int32)                 # slots used per expert
    gates = jnp.zeros((G, Sg, E), jnp.float32)
    dispatch = jnp.zeros((G, Sg, E, cap), bool)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)            # (G,Sg)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        # position of each token within its expert queue (priority = seq order)
        pos = jnp.cumsum(onehot, axis=1) - 1.0 + fill[:, None, :].astype(jnp.float32)
        pos_tok = jnp.sum(pos * onehot, axis=-1)        # (G,Sg)
        keep = pos_tok < cap
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), cap, dtype=bool)
        dispatch = dispatch | (
            (onehot[..., None] > 0) & slot[:, :, None, :] & keep[:, :, None, None])
        gates = gates + onehot * probs * keep[..., None].astype(jnp.float32)
        fill = fill + jnp.sum(onehot * keep[..., None], axis=1).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    if normalize:
        denom = jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        gates = gates / denom
    return gates, dispatch


# ---------------------------------------------------------------------------
# routing shared by both paths, and the serving path
# ---------------------------------------------------------------------------


def _group_mask(scores, mo: MoEConfig):
    """1.0 on the experts of each token's best ``topk_group`` groups (by
    their top score), 0.0 elsewhere; all ones without groups."""
    if mo.n_group <= 1:
        return jnp.ones_like(scores)
    *lead, n = scores.shape
    g = scores.reshape(*lead, mo.n_group, n // mo.n_group)
    best = jax.lax.top_k(jnp.max(g, axis=-1), mo.topk_group)[1]
    keep = jnp.sum(jax.nn.one_hot(best, mo.n_group, dtype=scores.dtype),
                   axis=-2)                                  # (..., n_group)
    return jnp.repeat(keep, n // mo.n_group, axis=-1)


def route(logits, mo: MoEConfig):
    """Router logits (T, R) -> (expert ids (T, k) int32, gates (T, k) f32):
    softmax scores, group-limited greedy top-k (scores outside the chosen
    groups set to zero, as DeepSeek-V2 does), then the gates renormalised
    or scaled by ``routed_scale``."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(scores * _group_mask(scores, mo), mo.top_k)
    if mo.norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    else:
        gates = gates * mo.routed_scale
    return idx.astype(jnp.int32), gates


def _serve_tile(rows: int, n_experts: int) -> int:
    """Row tile of the grouped matmul: 16 (bfloat16's sublane tile) when
    experts see a few rows each, as in decode, else 256."""
    return 16 if rows <= 16 * n_experts else 256


def _held_experts(params, x, idx, gates, *, cfg: ArchConfig, runtime):
    """The held experts' gated outputs for tokens x (T, d) and each
    expert's routed row count (E,)."""
    from repro.kernels import ops as kops
    mo = cfg.moe
    compute = jnp.dtype(cfg.compute_dtype)
    T, d = x.shape
    E, k = mo.n_experts, mo.top_k
    local = idx - mo.expert_offset
    held = (local >= 0) & (local < E)
    e = jnp.where(held, local, E).reshape(-1)                  # (T*k,)
    onehot = jax.nn.one_hot(e, E + 1, dtype=jnp.int32)         # (T*k, E+1)
    counts = jnp.sum(onehot[:, :E], axis=0)                    # (E,)
    tm = _serve_tile(T * min(k, E), E)
    padded = -(-counts // tm) * tm
    start = jnp.cumsum(padded) - padded
    rank = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    m = -(-(T * min(k, E) + E * (tm - 1)) // tm) * tm
    dest = jnp.where(e < E, start[jnp.minimum(e, E - 1)] + rank, m)
    token = jnp.arange(T * k, dtype=jnp.int32) // k
    row_token = jnp.zeros((m,), jnp.int32).at[dest].set(token, mode="drop")
    rows = x.astype(compute)[row_token]                        # (m, d)
    policy = kops.policy_from_runtime(runtime)

    def gmm(a, w):
        return kops.moe_gmm(a, w.astype(compute), padded, tm=tm,
                            layer=params.get("layer"), policy=policy)

    h = activation(cfg.act)(gmm(rows, params["we_gate"])) \
        * gmm(rows, params["we_up"])
    y = gmm(h.astype(compute), params["we_down"])              # (m, d)
    pos = jnp.minimum(dest, m - 1).reshape(T, k)
    ok = held.reshape(T, k)
    out = jnp.zeros((T, d), jnp.float32)
    for j in range(k):
        part = jnp.where(ok[:, j:j + 1], y[pos[:, j]].astype(jnp.float32),
                         0.0)
        out = out + part * gates[:, j:j + 1]
    return out, counts


def moe_serve(params, x, *, cfg: ArchConfig, runtime=None):
    """Serving expert layer: x (B, S, d) -> (out, stats).  Every token is
    routed over the router's experts; the held experts' outputs are
    computed for every row routed to them, the shared experts for every
    token.  ``stats``: ``moe_routed_rows`` (rows routed to held experts)
    and ``moe_experts_hit`` (distinct held experts they reach), int32
    scalars, and ``moe_held`` (B, S, E) bool, true where a token is routed
    to held expert e.
    ``params`` may hold the experts of every layer of a stage with the
    index of this one under ``"layer"`` (see ``transformer``'s scans)."""
    mo = cfg.moe
    compute = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    tokens = x.reshape(B * S, d)
    logits = jnp.matmul(tokens.astype(compute),
                        params["router"].astype(compute),
                        preferred_element_type=jnp.float32)
    idx, gates = route(logits, mo)
    T = B * S
    chunk = min(T, _SERVE_CHUNK)
    if T % chunk:
        chunk = T
    if chunk == T:
        out, counts = _held_experts(params, tokens, idx, gates, cfg=cfg,
                                    runtime=runtime)
    else:
        n = T // chunk
        out, counts = jax.lax.map(
            lambda a: _held_experts(params, *a, cfg=cfg, runtime=runtime),
            (tokens.reshape(n, chunk, d), idx.reshape(n, chunk, -1),
             gates.reshape(n, chunk, -1)))
        out, counts = out.reshape(T, d), jnp.sum(counts, axis=0)
    out = out.reshape(B, S, d)
    if mo.n_shared:
        shared, _ = apply_mlp(params["shared"], x, cfg.act, compute)
        out = out + shared.astype(jnp.float32)
    held = jnp.any(idx[..., None] - mo.expert_offset
                   == jnp.arange(mo.n_experts), axis=-2)      # (T, E)
    stats = {"moe_routed_rows": jnp.sum(counts).astype(jnp.int32),
             "moe_experts_hit": jnp.sum(counts > 0).astype(jnp.int32),
             "moe_held": held.reshape(B, S, mo.n_experts)}
    return out.astype(x.dtype), stats
