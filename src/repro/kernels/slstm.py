"""sLSTM recurrence Pallas TPU kernel (inference path).

The xLSTM paper's CUDA kernel keeps the recurrent gate matrix R in shared
memory across timesteps; the TPU analogue holds R (d, 4d) in VMEM scratch
for the whole grid row, so HBM traffic is O(S*d) for the gate inputs and
outputs instead of O(S*d^2) for per-step R re-reads — on xlstm-125m
train_4k the per-step R stream was ~60% of the memory roofline term
(EXPERIMENTS.md §Perf H1 iteration 3).

The input-side projection (x @ W + b) is already hoisted out of the loop
(one batched matmul) by the caller, so the kernel consumes precomputed
``gates_x`` and only applies the recurrent part.  Forward-only: training
keeps the XLA scan (a custom VJP would be needed to differentiate through
``pallas_call``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(gx_ref, r_ref, c0_ref, n0_ref, h0_ref, m0_ref,
            hs_ref, c_ref, n_ref, h_ref, m_ref,
            r_vmem, state, *, chunk: int, n_chunks: int, d: int,
            seq_len: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        r_vmem[...] = r_ref[...]                 # R resident for all chunks
        state[0, :] = c0_ref[0]
        state[1, :] = n0_ref[0]
        state[2, :] = h0_ref[0]
        state[3, :] = m0_ref[0]

    R = r_vmem[...]

    def step(t, _):
        c = state[0, :]
        n = state[1, :]
        h = state[2, :]
        m = state[3, :]
        gates = gx_ref[0, t] + h @ R             # (4d,)
        i_t = gates[:d]
        f_t = gates[d:2 * d]
        z_t = gates[2 * d:3 * d]
        o_t = gates[3 * d:]
        m_new = jnp.maximum(f_t + m, i_t)
        iprime = jnp.exp(i_t - m_new)
        fprime = jnp.exp(f_t + m - m_new)
        c_new = fprime * c + iprime * jnp.tanh(z_t)
        n_new = fprime * n + iprime
        h_new = jax.nn.sigmoid(o_t) * c_new / jnp.maximum(n_new, 1e-6)
        # padded timesteps beyond seq_len must not mutate the carried state
        valid = (j * chunk + t) < seq_len
        state[0, :] = jnp.where(valid, c_new, c)
        state[1, :] = jnp.where(valid, n_new, n)
        state[2, :] = jnp.where(valid, h_new, h)
        state[3, :] = jnp.where(valid, m_new, m)
        hs_ref[0, t] = h_new.astype(hs_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(j == n_chunks - 1)
    def _emit():
        c_ref[0] = state[0, :]
        n_ref[0] = state[1, :]
        h_ref[0] = state[2, :]
        m_ref[0] = state[3, :]


def slstm_scan_bsd(gates_x, R, c0, n0, h0, m0, *, chunk: int = 256,
                   interpret=None):
    """gates_x (B,S,4d) f32; R (d,4d); states (B,d).

    Returns (hs (B,S,d), (c,n,h,m) final states).
    ``interpret=None`` resolves from the platform dispatch policy.
    """
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S, d4 = gates_x.shape
    d = d4 // 4
    c = min(chunk, S)
    n_chunks = -(-S // c)
    pad = n_chunks * c - S
    if pad:
        gates_x = jnp.pad(gates_x, ((0, 0), (0, pad), (0, 0)))
    Sp = n_chunks * c

    kernel = functools.partial(_kernel, chunk=c, n_chunks=n_chunks, d=d,
                               seq_len=S)
    hs, cf, nf, hf, mf = pl.pallas_call(
        kernel,
        grid=(B, n_chunks),
        in_specs=[
            pl.BlockSpec((1, c, d4), lambda b, j: (b, j, 0)),
            pl.BlockSpec((d, d4), lambda b, j: (0, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, d), gates_x.dtype),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d4), jnp.float32),
                        pltpu.VMEM((4, d), jnp.float32)],
        interpret=interpret,
        name="dagafl_slstm",
    )(gates_x, R, c0, n0, h0, m0)
    return (hs[:, :S] if pad else hs), (cf, nf, hf, mf)
