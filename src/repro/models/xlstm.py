"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, strictly recurrent) — arXiv:2405.04517.

mLSTM uses the stabilized chunkwise formulation (intra-chunk quadratic D
matrix over ``chunk`` steps + carried inter-chunk state (C, n, m)), which is
the TPU-friendly adaptation of the paper's recurrence: within-chunk work maps
onto the MXU as (L x L) matmuls, across chunks a short ``lax.scan``.
``mlstm_recurrent_ref`` is the step-by-step oracle used by tests.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import apply_norm, dense_init, init_norm


def _mdims(cfg: ArchConfig):
    xc = cfg.xlstm
    d_in = xc.m_expand * cfg.d_model
    d_qk = int(xc.m_qk_dim_factor * d_in)
    H = cfg.n_heads
    return xc, d_in, d_qk, H


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def init_mlstm(key, cfg: ArchConfig, dtype):
    xc, d_in, d_qk, H = _mdims(cfg)
    keys = jax.random.split(key, 8)
    return {
        "up_proj": dense_init(keys[0], cfg.d_model, 2 * d_in, dtype),
        "conv_w": (jax.random.normal(keys[1], (xc.s_conv, d_in), jnp.float32)
                   / math.sqrt(xc.s_conv)).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "wq": dense_init(keys[2], d_in, d_qk, dtype),
        "wk": dense_init(keys[3], d_in, d_qk, dtype),
        "wv": dense_init(keys[4], d_in, d_in, dtype),
        "w_if": dense_init(keys[5], d_in, 2 * H, dtype, scale=0.01),
        "b_if": jnp.concatenate([jnp.zeros((H,)), 3.0 * jnp.ones((H,))]
                                ).astype(jnp.float32),
        "head_norm": init_norm("rmsnorm", d_in, dtype),
        "down_proj": dense_init(keys[6], d_in, cfg.d_model, dtype),
    }


def init_mlstm_state(cfg: ArchConfig, batch: int, leading: tuple = ()):
    xc, d_in, d_qk, H = _mdims(cfg)
    return {
        "C": jnp.zeros(leading + (batch, H, d_qk // H, d_in // H), jnp.float32),
        "n": jnp.zeros(leading + (batch, H, d_qk // H), jnp.float32),
        "m": jnp.full(leading + (batch, H), -1e30, jnp.float32),
        "conv": jnp.zeros(leading + (batch, xc.s_conv - 1, d_in), jnp.float32),
    }


# The leaves the mLSTM block casts to the compute dtype; b_if and head_norm
# are read as stored.
MLSTM_COMPUTE_CAST = dict.fromkeys(
    ("up_proj", "conv_w", "conv_b", "wq", "wk", "wv", "w_if", "down_proj"),
    True)


def _mlstm_qkvif(params, x, cfg, compute):
    """x (B,S,d) -> q,k (B,S,H,dqk/H), v (B,S,H,dv/H), i,f (B,S,H), z (B,S,d_in)."""
    xc, d_in, d_qk, H = _mdims(cfg)
    B, S, _ = x.shape
    up = x.astype(compute) @ params["up_proj"].astype(compute)
    xm, z = jnp.split(up, 2, axis=-1)
    # causal conv + silu feeds q/k (paper's block layout)
    conv_w = params["conv_w"].astype(compute)
    xp = jnp.pad(xm, ((0, 0), (xc.s_conv - 1, 0), (0, 0)))
    xconv = sum(xp[:, i:i + S] * conv_w[i] for i in range(xc.s_conv))
    xcn = jax.nn.silu(xconv + params["conv_b"].astype(compute))
    q = (xcn @ params["wq"].astype(compute)).reshape(B, S, H, d_qk // H)
    k = (xcn @ params["wk"].astype(compute)).reshape(B, S, H, d_qk // H)
    v = (xm @ params["wv"].astype(compute)).reshape(B, S, H, d_in // H)
    gif = (xm @ params["w_if"].astype(compute)).astype(jnp.float32) + params["b_if"]
    i_gate, f_gate = jnp.split(gif, 2, axis=-1)              # (B,S,H)
    return q, k, v, i_gate, f_gate, z


def mlstm_chunkwise(q, k, v, i_gate, f_gate, state, chunk: int = 256):
    """Stabilized chunkwise mLSTM.

    q,k (B,S,H,dk) v (B,S,H,dv); gates (B,S,H) raw (i pre-exp, f pre-logsig).
    state: {C (B,H,dk,dv), n (B,H,dk), m (B,H)}.  Returns (h (B,S,H,dv), state).
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(dk)
    L = min(chunk, S)
    n_chunks = -(-S // L)
    pad = n_chunks * L - S
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)))
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)),
                         constant_values=30.0)  # ~sigmoid->1, keeps state

    def chunk_body(carry, xs):
        C, n, m = carry
        qc, kc, vc, ic, fc = xs                  # (L,B,H,*) time-major
        qc = qc.transpose(1, 2, 0, 3).astype(jnp.float32) * scale   # (B,H,L,dk)
        kc = kc.transpose(1, 2, 0, 3).astype(jnp.float32)
        vc = vc.transpose(1, 2, 0, 3).astype(jnp.float32)
        ic = ic.transpose(1, 2, 0)                                   # (B,H,L)
        fc = fc.transpose(1, 2, 0)
        logf = jax.nn.log_sigmoid(fc)
        b = jnp.cumsum(logf, axis=-1)                                # (B,H,L)
        g = b[..., -1]
        # intra-chunk decay matrix D[t,s] = b_t - b_s + i_s  (s <= t)
        D = b[..., :, None] - b[..., None, :] + ic[..., None, :]
        tri = jnp.tril(jnp.ones((L, L), bool))
        D = jnp.where(tri, D, -jnp.inf)
        m_intra = jnp.max(D, axis=-1)                                # (B,H,L)
        m_t = jnp.maximum(b + m[..., None], m_intra)
        # inter contribution
        w_inter = jnp.exp(b + m[..., None] - m_t)                    # (B,H,L)
        num_inter = jnp.einsum("bhld,bhdv->bhlv", qc, C) * w_inter[..., None]
        den_inter = jnp.einsum("bhld,bhd->bhl", qc, n) * w_inter
        # intra contribution
        logits = jnp.einsum("bhld,bhsd->bhls", qc, kc)
        decay = jnp.where(tri, jnp.exp(D - m_t[..., None]), 0.0)
        Wn = decay * logits
        num_intra = jnp.einsum("bhls,bhsv->bhlv", Wn, vc)
        den_intra = jnp.sum(Wn, axis=-1)
        num = num_inter + num_intra
        den = den_inter + den_intra
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # state update
        m_next = jnp.maximum(g + m, jnp.max(g[..., None] - b + ic, axis=-1))
        w_c = jnp.exp(g + m - m_next)
        w_s = jnp.exp(g[..., None] - b + ic - m_next[..., None])     # (B,H,L)
        C_next = C * w_c[..., None, None] + jnp.einsum(
            "bhs,bhsd,bhsv->bhdv", w_s, kc, vc)
        n_next = n * w_c[..., None] + jnp.einsum("bhs,bhsd->bhd", w_s, kc)
        h_out = h.transpose(2, 0, 1, 3)                              # (L,B,H,dv)
        return (C_next, n_next, m_next), h_out

    xs = tuple(a.reshape(B, n_chunks, L, H, -1).transpose(1, 2, 0, 3, 4)
               if a.ndim == 4 else
               a.reshape(B, n_chunks, L, H).transpose(1, 2, 0, 3)
               for a in (q, k, v, i_gate, f_gate))
    (C, n, m), hs = jax.lax.scan(jax.checkpoint(chunk_body),
                                 (state["C"], state["n"], state["m"]), xs)
    h = hs.transpose(2, 0, 1, 3, 4).reshape(B, n_chunks * L, H, dv)
    if pad:
        h = h[:, :S]
    return h, {"C": C, "n": n, "m": m}


def mlstm_recurrent_ref(q, k, v, i_gate, f_gate, state):
    """Step-by-step oracle (same signature, scan over every timestep)."""
    B, S, H, dk = q.shape
    scale = 1.0 / math.sqrt(dk)

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, it, ft = xs                  # (B,H,dk),(B,H,dk),(B,H,dv),(B,H)
        qt = qt.astype(jnp.float32) * scale
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        fprime = jnp.exp(logf + m - m_new)
        iprime = jnp.exp(it - m_new)
        C = C * fprime[..., None, None] + iprime[..., None, None] * (
            kt.astype(jnp.float32)[..., :, None] * vt.astype(jnp.float32)[..., None, :])
        n = n * fprime[..., None] + iprime[..., None] * kt.astype(jnp.float32)
        num = jnp.einsum("bhd,bhdv->bhv", qt, C)
        den = jnp.einsum("bhd,bhd->bh", qt, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
        return (C, n, m_new), h

    xs = tuple(a.transpose(1, 0, 2, 3) if a.ndim == 4 else a.transpose(1, 0, 2)
               for a in (q, k, v, i_gate, f_gate))
    (C, n, m), hs = jax.lax.scan(step, (state["C"], state["n"], state["m"]), xs)
    return hs.transpose(1, 0, 2, 3), {"C": C, "n": n, "m": m}


def mlstm_forward(params, x, *, cfg: ArchConfig, state=None, runtime=None):
    xc, d_in, d_qk, H = _mdims(cfg)
    compute = jnp.dtype(cfg.compute_dtype)
    B, S, _ = x.shape
    if state is None:
        state = init_mlstm_state(cfg, B)
    q, k, v, i_gate, f_gate, z = _mlstm_qkvif(params, x, cfg, compute)
    h, core = mlstm_chunkwise(q, k, v, i_gate, f_gate, state, chunk=xc.chunk)
    h = h.reshape(B, S, d_in)
    h = apply_norm(params["head_norm"], h, "rmsnorm")
    out = (h.astype(compute) * jax.nn.silu(z)) @ params["down_proj"].astype(compute)
    new_state = dict(core)
    # conv tail kept for decode continuity
    xm = (x.astype(compute) @ params["up_proj"].astype(compute))[..., :d_in]
    new_state["conv"] = xm[:, -(xc.s_conv - 1):].astype(jnp.float32) if S >= xc.s_conv - 1 \
        else jnp.concatenate([state["conv"][:, S:], xm.astype(jnp.float32)], axis=1)
    return out.astype(x.dtype), new_state


def mlstm_decode(params, x, state, *, cfg: ArchConfig):
    """Single-step recurrent decode. x (B,1,d)."""
    xc, d_in, d_qk, H = _mdims(cfg)
    compute = jnp.dtype(cfg.compute_dtype)
    B = x.shape[0]
    up = x[:, 0].astype(compute) @ params["up_proj"].astype(compute)
    xm, z = jnp.split(up, 2, axis=-1)
    window = jnp.concatenate([state["conv"].astype(compute), xm[:, None]], axis=1)
    conv_w = params["conv_w"].astype(compute)
    xcn = jax.nn.silu(jnp.sum(window * conv_w[None], axis=1)
                      + params["conv_b"].astype(compute))
    q = (xcn @ params["wq"].astype(compute)).reshape(B, 1, H, d_qk // H)
    k = (xcn @ params["wk"].astype(compute)).reshape(B, 1, H, d_qk // H)
    v = (xm @ params["wv"].astype(compute)).reshape(B, 1, H, d_in // H)
    gif = (xm @ params["w_if"].astype(compute)).astype(jnp.float32) + params["b_if"]
    i_gate, f_gate = jnp.split(gif[:, None], 2, axis=-1)
    h, core = mlstm_recurrent_ref(q, k, v, i_gate, f_gate, state)
    h = apply_norm(params["head_norm"], h.reshape(B, 1, d_in), "rmsnorm")
    out = (h[:, 0].astype(compute) * jax.nn.silu(z)) @ params["down_proj"].astype(compute)
    new_state = dict(core)
    new_state["conv"] = window[:, 1:].astype(jnp.float32)
    return out[:, None].astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def init_slstm(key, cfg: ArchConfig, dtype):
    d = cfg.d_model
    xc = cfg.xlstm
    keys = jax.random.split(key, 6)
    d_up = int(4 * d / 3) // 2 * 2
    return {
        "conv_w": (jax.random.normal(keys[0], (xc.s_conv, d), jnp.float32)
                   / math.sqrt(xc.s_conv)).astype(dtype),
        "conv_b": jnp.zeros((d,), dtype),
        "w_gates": dense_init(keys[1], d, 4 * d, dtype),
        "r_gates": dense_init(keys[2], d, 4 * d, dtype, scale=0.01),
        "b_gates": jnp.concatenate(
            [jnp.zeros((d,)), 3.0 * jnp.ones((d,)),
             jnp.zeros((2 * d,))]).astype(jnp.float32),
        "up_proj": dense_init(keys[3], d, 2 * d_up, dtype),
        "down_proj": dense_init(keys[4], d_up, d, dtype),
        "out_norm": init_norm("rmsnorm", d, dtype),
    }


def init_slstm_state(cfg: ArchConfig, batch: int, leading: tuple = ()):
    d = cfg.d_model
    xc = cfg.xlstm
    z = lambda: jnp.zeros(leading + (batch, d), jnp.float32)
    return {"c": z(), "n": z(), "h": z(),
            "m": jnp.full(leading + (batch, d), -1e30, jnp.float32),
            "conv": jnp.zeros(leading + (batch, xc.s_conv - 1, d), jnp.float32)}


# The leaves the sLSTM block casts to the compute dtype; the gate weights
# (w_gates, r_gates, b_gates) and out_norm are read in float32.
SLSTM_COMPUTE_CAST = dict.fromkeys(
    ("conv_w", "conv_b", "up_proj", "down_proj"), True)


def _slstm_scan_maybe_sharded(params, xconv, x_raw, state, compute, runtime):
    """Run the recurrence under ``shard_map`` over the batch axes when a mesh
    is available.

    Why: with batch-sharded activations and replicated gate weights, GSPMD
    places the weight-gradient all-reduce INSIDE the per-timestep backward
    loop (observed: 232 GB/chip of (3072,768) all-reduces on xlstm-125m
    train_4k).  Inside a shard_map region everything is shard-local; the
    psum of the replicated weights' cotangent is inserted ONCE at region
    exit — the mathematically identical reduction, hoisted out of the loop.
    """
    mesh = getattr(runtime, "mesh", None) if runtime is not None else None
    baxes = getattr(runtime, "batch_axes", None) if runtime is not None else None
    B = x_raw.shape[0]
    if mesh is None or not baxes or B % max(runtime.batch_axis_size, 1):
        return _slstm_scan(params, xconv, x_raw, state, compute)
    from jax.sharding import PartitionSpec as P
    bx = tuple(baxes) if len(baxes) > 1 else baxes[0]
    b3 = P(bx, None, None)
    b2 = P(bx, None)
    used = {k: params[k] for k in ("w_gates", "r_gates", "b_gates")}
    fn = jax.shard_map(
        lambda pr, xc, xr, st: _slstm_scan(pr, xc, xr, st, compute),
        mesh=mesh,
        in_specs=(P(), b3, b3, {"c": b2, "n": b2, "h": b2, "m": b2,
                                "conv": b3}),
        out_specs=(b3, {"c": b2, "n": b2, "h": b2, "m": b2}),
        check_vma=False)
    state_in = {k: state[k] for k in ("c", "n", "h", "m")}
    state_in["conv"] = state["conv"]
    return fn(used, xconv, x_raw, state_in)


def _slstm_scan(params, xconv, x_raw, state, compute):
    """xconv/x_raw (B,S,d). Sequential exponential-gated recurrence.

    The input-side gate projection (xconv @ W + b) is hoisted out of the
    timestep loop as ONE batched matmul — W then streams from HBM once per
    layer instead of once per timestep (the recurrent R @ h matvec stays in
    the loop; holding R VMEM-resident across steps is the Pallas-kernel
    follow-up, see EXPERIMENTS.md §Perf).
    """
    r = params["r_gates"].astype(jnp.float32)
    d = x_raw.shape[-1]
    gates_x = (xconv.astype(jnp.float32)
               @ params["w_gates"].astype(jnp.float32) + params["b_gates"])

    def step(carry, xs):
        c, n, h, m = carry
        gx_t, xr_t = xs                                       # (B,4d),(B,d)
        gates = gx_t + h @ r
        i_t, f_t, z_t, o_t = jnp.split(gates, 4, axis=-1)
        m_new = jnp.maximum(f_t + m, i_t)                     # exp forget gate
        iprime = jnp.exp(i_t - m_new)
        fprime = jnp.exp(f_t + m - m_new)
        c = fprime * c + iprime * jnp.tanh(z_t)
        n = fprime * n + iprime
        h = jax.nn.sigmoid(o_t) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    xs = (gates_x.transpose(1, 0, 2),
          x_raw.astype(jnp.float32).transpose(1, 0, 2))
    (c, n, h, m), hs = jax.lax.scan(
        step, (state["c"], state["n"], state["h"], state["m"]), xs)
    return hs.transpose(1, 0, 2), {"c": c, "n": n, "h": h, "m": m}


def slstm_forward(params, x, *, cfg: ArchConfig, state=None, runtime=None):
    xc = cfg.xlstm
    compute = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    if state is None:
        state = init_slstm_state(cfg, B)
    xp = jnp.concatenate([state["conv"].astype(compute), x.astype(compute)], axis=1)
    conv_w = params["conv_w"].astype(compute)
    xconv = sum(xp[:, i:i + S] * conv_w[i] for i in range(xc.s_conv))
    xconv = jax.nn.silu(xconv + params["conv_b"].astype(compute))
    hs, core = _slstm_scan_maybe_sharded(params, xconv, x, state, compute,
                                         runtime)
    hs = apply_norm(params["out_norm"], hs.astype(x.dtype), "rmsnorm")
    up = hs.astype(compute) @ params["up_proj"].astype(compute)
    a, g = jnp.split(up, 2, axis=-1)
    out = (jax.nn.gelu(a, approximate=True) * g) @ params["down_proj"].astype(compute)
    new_state = dict(core)
    new_state["conv"] = xp[:, -(xc.s_conv - 1):].astype(jnp.float32)
    return out.astype(x.dtype), new_state


def slstm_decode(params, x, state, *, cfg: ArchConfig):
    return slstm_forward(params, x, cfg=cfg, state=state)
