"""Pure-jnp oracles for every Pallas kernel (the allclose contract)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG = -2.0e9


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = -1,
                        softcap: float = 0.0, scale=None):
    """q (B,H,S,hd); k,v (B,K,S,hd); GQA by head repetition. f32 math.
    ``scale`` multiplies the scores (None: divide by sqrt(hd))."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / math.sqrt(hd) if scale is None else s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(S)[None, :]
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= rows >= cols
    if window > 0:
        ok &= (rows - cols) < window
    s = jnp.where(ok, s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def moe_gmm_ref(lhs, rhs, group_sizes):
    """Oracle of ``moe_gmm``: rows in consecutive groups of
    ``group_sizes``, each times its expert's matrix, accumulated in f32;
    rows past the groups are zero."""
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def selective_scan_seq_ref(x, dt, A, Bc, Cc, h0):
    """Plain sequential scan oracle. Shapes as in selective_scan_bsd."""
    def step(h, xs):
        xt, dtt, bt, ct = xs
        da = jnp.exp(dtt[..., None] * A)
        h = da * h + (dtt * xt)[..., None] * bt[:, None, :]
        y = jnp.sum(h * ct[:, None, :], axis=-1)
        return h, y

    xs = tuple(a.transpose(1, 0, 2) for a in (x, dt, Bc, Cc))
    h, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2), h


def signature_ref(x, tau: float = 0.05):
    """x (T, d) -> per-channel zero-fraction (d,)."""
    if tau <= 0.0:
        flags = (x == 0.0)
    else:
        flags = jnp.abs(x) < tau
    return jnp.mean(flags.astype(jnp.float32), axis=0)


def mlstm_chunkwise_ref(q, k, v, i_gate, f_gate):
    """Fresh-state oracle for the chunkwise mLSTM kernel: the sequential
    recurrent formulation from ``models.xlstm`` (the same ground truth the
    kernel parity tests compare against), started from C=0, n=0, m=-inf."""
    from repro.models.xlstm import mlstm_recurrent_ref
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    st0 = {"C": jnp.zeros((B, H, dk, dv)), "n": jnp.zeros((B, H, dk)),
           "m": jnp.full((B, H), -1e30)}
    return mlstm_recurrent_ref(q, k, v, i_gate, f_gate, st0)


def slstm_scan_ref(gates_x, R, c0, n0, h0, m0):
    """Sequential oracle for the sLSTM kernel (same math as models.xlstm)."""
    d = R.shape[0]

    def step(carry, gx_t):
        c, n, h, m = carry
        gates = gx_t + h @ R
        i_t, f_t, z_t, o_t = jnp.split(gates, 4, axis=-1)
        m_new = jnp.maximum(f_t + m, i_t)
        iprime = jnp.exp(i_t - m_new)
        fprime = jnp.exp(f_t + m - m_new)
        c = fprime * c + iprime * jnp.tanh(z_t)
        n = fprime * n + iprime
        h = jax.nn.sigmoid(o_t) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    (c, n, h, m), hs = jax.lax.scan(step, (c0, n0, h0, m0),
                                    gates_x.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2), (c, n, h, m)
