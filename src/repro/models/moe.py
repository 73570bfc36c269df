"""Mixture-of-Experts FFN with capacity-bounded one-hot dispatch (GSPMD style).

Tokens are grouped (``group_size`` tokens per dispatch group) so the dispatch
tensor is (G, S_g, E, C) with per-group capacity C = ceil(S_g * top_k / E *
capacity_factor); experts shard over the ``model`` mesh axis (expert
parallelism) and groups over ``data``, so XLA materialises the all-to-all in
the lowered HLO — which is exactly what the roofline's collective term wants
to see.  Overflow tokens are dropped (standard Switch behaviour); the router
carries a load-balance aux loss and a z-loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.layers import (MLP_COMPUTE_CAST, activation, apply_mlp,
                                 dense_init, init_mlp)

_GROUP = 512


def init_moe(key, cfg: ArchConfig, dtype):
    mo = cfg.moe
    d = cfg.d_model
    keys = jax.random.split(key, 5)
    p = {
        "router": dense_init(keys[0], d, mo.n_experts, dtype, scale=0.02),
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


def moe_forward(params, x, *, cfg: ArchConfig, sc=None,
                generous_capacity: bool = False):
    """x (B, S, d) -> (out, aux) where aux has load-balance and z losses.

    ``generous_capacity`` (serving: prefill/decode) widens expert capacity to
    4x the balanced load (floor 8) so tokens are effectively never dropped;
    training keeps Switch-style ``capacity_factor`` dropping.
    """
    mo = cfg.moe
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

    if S == 1 or generous_capacity:
        cap = min(g_size, max(8, -(-g_size * k * 4 // E)))
    else:
        cap = max(int(g_size * k / E * mo.capacity_factor), 1)

    # top-k routing with per-slot cumulative capacity positions
    gates, dispatch = _topk_dispatch(probs, k, cap)            # (G,Sg,E,C)

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


def _topk_dispatch(probs, k: int, cap: int):
    """Greedy top-k dispatch with capacity. Returns (gates (G,Sg,E),
    dispatch one-hot (G,Sg,E,C))."""
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
    denom = jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    gates = gates / denom
    return gates, dispatch
