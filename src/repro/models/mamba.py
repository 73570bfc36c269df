"""Mamba-1 selective SSM block (used by jamba-v0.1).

Train/prefill: chunked sequential scan (outer ``lax.scan`` over chunks with
``jax.checkpoint`` on the chunk body, inner scan over time) — the remat
pattern mirrors the CUDA kernel's recompute-in-backward trick adapted to the
TPU memory hierarchy: only chunk-boundary states (B, d_in, N) are saved.
Decode: single recurrent step against carried {ssm state, conv tail}.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MambaConfig
from repro.models.layers import dense_init


def _dims(cfg: ArchConfig):
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    dt_rank = mc.dt_rank or -(-cfg.d_model // 16)
    return mc, d_in, dt_rank


def init_mamba(key, cfg: ArchConfig, dtype):
    mc, d_in, dt_rank = _dims(cfg)
    keys = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, mc.d_state + 1, dtype=jnp.float32)[None],
                 (d_in, 1))
    return {
        "in_proj": dense_init(keys[0], cfg.d_model, 2 * d_in, dtype),
        "conv_w": (jax.random.normal(keys[1], (mc.d_conv, d_in), jnp.float32)
                   / math.sqrt(mc.d_conv)).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": dense_init(keys[2], d_in, dt_rank + 2 * mc.d_state, dtype),
        "dt_proj": dense_init(keys[3], dt_rank, d_in, dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((d_in,), 0.01))).astype(jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(keys[4], d_in, cfg.d_model, dtype),
    }


def init_mamba_state(cfg: ArchConfig, batch: int, leading: tuple = ()):
    mc, d_in, _ = _dims(cfg)
    return {
        "h": jnp.zeros(leading + (batch, d_in, mc.d_state), jnp.float32),
        "conv": jnp.zeros(leading + (batch, mc.d_conv - 1, d_in), jnp.float32),
    }


# The leaves the block casts to the compute dtype; dt_proj, dt_bias, A_log
# and D are read in float32.
COMPUTE_CAST = dict.fromkeys(
    ("in_proj", "conv_w", "conv_b", "x_proj", "out_proj"), True)


def _ssm_params(params, xb, cfg, compute):
    """xb (..., d_in) conv-activated input -> dt (softplus), B, C."""
    mc, d_in, dt_rank = _dims(cfg)
    proj = xb.astype(compute) @ params["x_proj"].astype(compute)
    dt, Bc, Cc = jnp.split(proj.astype(jnp.float32),
                           [dt_rank, dt_rank + mc.d_state], axis=-1)
    dt = dt @ params["dt_proj"].astype(jnp.float32) + params["dt_bias"]
    dt = jax.nn.softplus(dt)
    return dt, Bc, Cc


def mamba_forward(params, x, *, cfg: ArchConfig, state=None, runtime=None):
    """Full-sequence scan. x (B,S,d) -> (out (B,S,d), final state)."""
    mc, d_in, _ = _dims(cfg)
    compute = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    xz = x.astype(compute) @ params["in_proj"].astype(compute)
    xs, z = jnp.split(xz, 2, axis=-1)                        # (B,S,d_in)

    if state is None:
        state = init_mamba_state(cfg, B)
    # causal depthwise conv over time (prepend carried tail)
    tail = state["conv"].astype(compute)
    xp = jnp.concatenate([tail, xs], axis=1)                 # (B, S+dc-1, d_in)
    conv_w = params["conv_w"].astype(compute)
    xconv = sum(xp[:, i:i + S] * conv_w[i] for i in range(mc.d_conv))
    xb = jax.nn.silu(xconv + params["conv_b"].astype(compute))

    dt, Bc, Cc = _ssm_params(params, xb, cfg, compute)       # (B,S,*)
    A = -jnp.exp(params["A_log"])                            # (d_in, N)
    xbf = xb.astype(jnp.float32)

    if runtime is not None and getattr(runtime, "use_pallas", False):
        from repro.kernels import ops as kops
        y, h_last = kops.selective_scan(
            xbf, dt, A, Bc, Cc, state["h"], chunk=mc.chunk,
            policy=kops.policy_from_runtime(runtime))
    else:
        y, h_last = selective_scan_ref(xbf, dt, A, Bc, Cc, state["h"],
                                       chunk=mc.chunk)
    y = y + xbf * params["D"]
    out = (y.astype(compute) * jax.nn.silu(z)) @ params["out_proj"].astype(compute)
    new_state = {"h": h_last,
                 "conv": xp[:, -(mc.d_conv - 1):].astype(jnp.float32)}
    return out.astype(x.dtype), new_state


def selective_scan_ref(x, dt, A, Bc, Cc, h0, chunk: int = 256):
    """Chunked sequential selective scan (pure jnp oracle).

    x,dt (B,S,d_in) f32; A (d_in,N); Bc,Cc (B,S,N); h0 (B,d_in,N).
    Returns (y (B,S,d_in), h_last).
    """
    B, S, d_in = x.shape
    N = A.shape[1]
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))

    def chunk_body(h, xs):
        xc, dtc, bc, cc = xs                                  # (C,B,...)

        def step(h, s):
            xt, dtt, bt, ct = s                               # (B,d_in),(B,d_in),(B,N),(B,N)
            da = jnp.exp(dtt[..., None] * A)                  # (B,d_in,N)
            h = da * h + (dtt * xt)[..., None] * bt[:, None, :]
            y = jnp.sum(h * ct[:, None, :], axis=-1)          # (B,d_in)
            return h, y

        h, ys = jax.lax.scan(step, h, (xc, dtc, bc, cc))
        return h, ys

    xs = tuple(a.reshape(B, n_chunks, chunk, -1).transpose(1, 2, 0, 3)
               for a in (x, dt, Bc, Cc))
    h, ys = jax.lax.scan(jax.checkpoint(chunk_body), h0, xs)
    y = ys.reshape(n_chunks * chunk, B, d_in).transpose(1, 0, 2)
    return y[:, :S], h


def mamba_decode(params, x, state, *, cfg: ArchConfig):
    """Single-token recurrent step. x (B,1,d)."""
    mc, d_in, _ = _dims(cfg)
    compute = jnp.dtype(cfg.compute_dtype)
    B = x.shape[0]
    xz = x[:, 0].astype(compute) @ params["in_proj"].astype(compute)
    xs, z = jnp.split(xz, 2, axis=-1)                        # (B,d_in)
    conv_w = params["conv_w"].astype(compute)
    window = jnp.concatenate([state["conv"].astype(compute), xs[:, None]], axis=1)
    xconv = jnp.sum(window * conv_w[None], axis=1)
    xb = jax.nn.silu(xconv + params["conv_b"].astype(compute))
    dt, Bc, Cc = _ssm_params(params, xb, cfg, compute)
    A = -jnp.exp(params["A_log"])
    da = jnp.exp(dt[..., None] * A)
    h = da * state["h"] + (dt * xb.astype(jnp.float32))[..., None] * Bc[:, None, :]
    y = jnp.sum(h * Cc[:, None, :], axis=-1) + xb.astype(jnp.float32) * params["D"]
    out = (y.astype(compute) * jax.nn.silu(z)) @ params["out_proj"].astype(compute)
    return out[:, None].astype(x.dtype), {"h": h,
                                          "conv": window[:, 1:].astype(jnp.float32)}
