"""Flash attention Pallas TPU kernel: causal / sliding-window / soft-cap / GQA.

TPU-native design (not a CUDA port): the grid is (batch, q_head, q_block,
kv_block) with the kv_block dim innermost — TPU executes grid steps
sequentially per core, so the online-softmax state (m, l, acc) lives in VMEM
scratch and persists across kv steps.  Block shapes are MXU-aligned
(multiples of 128 on the contracting dims); the probability matrix never
leaves VMEM, which is exactly the HBM-traffic term the roofline analysis
shows dominating the pure-JAX chunked path.

Fully-masked kv blocks (beyond the causal frontier or outside the sliding
window) are skipped with ``pl.when`` — the causal speedup the XLA scan path
cannot express.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -2.0e9


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, nk: int, seq_len: int):
    i = pl.program_id(2)              # q block
    j = pl.program_id(3)              # kv block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = i * bq
    k_start = j * bk

    # block-level skip: block fully above the causal diagonal or fully
    # outside the sliding window
    live = jnp.bool_(True)
    if causal:
        live = live & (k_start <= q_start + bq - 1)
    if window > 0:
        live = live & (k_start + bk - 1 >= q_start - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = cols < seq_len                                # kv padding
        ok &= rows < seq_len
        if causal:
            ok &= rows >= cols
        if window > 0:
            ok &= (rows - cols) < window
        s = jnp.where(ok, s, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = -1,
                         softcap: float = 0.0, block_q: int = 128,
                         block_k: int = 128, interpret=None, scale=None):
    """q (B,H,Sq,hd); k,v (B,K,Sk,hd) with H % K == 0 (GQA).

    ``scale`` multiplies the scores (None: 1/sqrt(hd)).

    Returns (B,H,Sq,hd) in q.dtype.  Sq must equal Sk (self-attention over
    the same positions); callers pad to block multiples.
    ``interpret=None`` resolves from the platform dispatch policy.
    """
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, S)
    nq = -(-S // bq)
    nk = -(-S // bk)
    pad_q = nq * bq - S
    pad_k = nk * bk - S
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd) if scale is None else scale,
        causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk, seq_len=S)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
            pltpu.VMEM((bq,), jnp.float32),      # m
            pltpu.VMEM((bq,), jnp.float32),      # l
        ],
        interpret=interpret,
        name="dagafl_flash_attention",
    )(q, k, v)
    return out[:, :, :S] if pad_q else out
