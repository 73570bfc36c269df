"""Chunkwise mLSTM Pallas TPU kernel (stabilized matrix-memory recurrence).

TPU-native mapping of the xLSTM paper's mLSTM kernel: the grid is
(batch, head, chunk) with chunks innermost; the matrix memory C (dk, dv),
normalizer n (dk,) and stabilizer m live in VMEM scratch across chunk steps.
Within a chunk the intra-term is the (L, L) decay-masked attention the MXU
likes; HBM sees q/k/v/gates once and h once — no inter-chunk state traffic.

Matches ``repro.models.xlstm.mlstm_chunkwise`` (the lax.scan formulation)
and the step-by-step recurrent oracle to float tolerance.  Forward/inference
path (training keeps the XLA scan; a custom VJP would be needed here).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, i_ref, f_ref,
            h_ref, c_out_ref, n_out_ref, m_out_ref,
            C, nvec, mval, *, L: int, dk: int, dv: int, n_chunks: int,
            seq_len: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        C[...] = jnp.zeros_like(C)
        nvec[...] = jnp.zeros_like(nvec)
        mval[...] = jnp.full_like(mval, _NEG)

    scale = 1.0 / math.sqrt(dk)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    ig = i_ref[0, 0].astype(jnp.float32)              # (L,)
    fg = f_ref[0, 0].astype(jnp.float32)

    # padded steps (beyond seq_len): forget->1 (logf=0), input->-inf
    pos = j * L + jax.lax.broadcasted_iota(jnp.int32, (L,), 0)
    valid = pos < seq_len
    logf = jnp.where(valid, jax.nn.log_sigmoid(fg), 0.0)
    ig = jnp.where(valid, ig, _NEG)

    b = jnp.cumsum(logf)                              # (L,)
    g = b[L - 1]
    m_prev = mval[0]

    # intra-chunk decay D[t,s] = b_t - b_s + i_s (s <= t)
    D = b[:, None] - b[None, :] + ig[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    D = jnp.where(tri, D, -jnp.inf)
    m_intra = jnp.max(D, axis=1)
    m_t = jnp.maximum(b + m_prev, m_intra)            # (L,)

    w_inter = jnp.exp(b + m_prev - m_t)
    num_inter = jax.lax.dot_general(
        q, C[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * w_inter[:, None]
    den_inter = (q @ nvec[...]) * w_inter             # (L,)

    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.where(tri, jnp.exp(D - m_t[:, None]), 0.0)
    Wn = decay * logits
    num = num_inter + jax.lax.dot_general(
        Wn, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    den = den_inter + jnp.sum(Wn, axis=1)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[:, None]
    h_ref[0, 0] = h.astype(h_ref.dtype)

    # state update
    m_next = jnp.maximum(g + m_prev, jnp.max(g - b + ig))
    w_c = jnp.exp(g + m_prev - m_next)
    w_s = jnp.exp(g - b + ig - m_next)                # (L,)
    C[...] = C[...] * w_c + jax.lax.dot_general(
        k * w_s[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    nvec[...] = nvec[...] * w_c + jnp.sum(k * w_s[:, None], axis=0)
    mval[0] = m_next

    @pl.when(j == n_chunks - 1)
    def _emit():
        c_out_ref[0, 0] = C[...]
        n_out_ref[0, 0] = nvec[...]
        m_out_ref[0, 0] = mval[...]


def mlstm_chunkwise_bshd(q, k, v, i_gate, f_gate, *, chunk: int = 128,
                         interpret=None):
    """q,k (B,S,H,dk); v (B,S,H,dv); gates (B,S,H) raw.

    Fresh state (C=0, n=0, m=-inf). Returns (h (B,S,H,dv),
    state {C (B,H,dk,dv), n (B,H,dk), m (B,H)}).
    ``interpret=None`` resolves from the platform dispatch policy.
    """
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, S)
    n_chunks = -(-S // L)
    pad = n_chunks * L - S
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)))
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)))
    Sp = n_chunks * L
    # layout (B, H, S, *) for head-major blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    it = i_gate.transpose(0, 2, 1)
    ft = f_gate.transpose(0, 2, 1)

    kernel = functools.partial(_kernel, L=L, dk=dk, dv=dv, n_chunks=n_chunks,
                               seq_len=S)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, L, dk), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, L, dk), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, L, dv), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, L), lambda b, h, j: (b, h, j)),
            pl.BlockSpec((1, 1, L), lambda b, h, j: (b, h, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, dv), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, j: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dk), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((dk,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        interpret=interpret,
        name="dagafl_mlstm",
    )(qt, kt, vt, it, ft)
    h = h.transpose(0, 2, 1, 3)
    if pad:
        h = h[:, :S]
    return h, {"C": C, "n": n, "m": m[..., 0]}
