"""Selective-scan (Mamba SSM) Pallas TPU kernel.

TPU adaptation of the CUDA selective-scan: the grid is (batch, n_chunks)
with chunks innermost, so the recurrent state h (d_in, N) persists in VMEM
scratch across chunk steps — HBM sees each input element once and each
output element once, with zero intermediate state traffic (the CUDA kernel's
shared-memory trick mapped onto the TPU memory hierarchy).  Within a chunk
the recurrence is a ``fori_loop`` over timesteps on (d_in, N) vector
registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
            h_ref, *, chunk: int, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = h0_ref[0]                       # (d_in, N)

    A = a_ref[...]                                   # (d_in, N)

    def step(t, _):
        xt = x_ref[0, t]                             # (d_in,)
        dtt = dt_ref[0, t]                           # (d_in,)
        bt = b_ref[0, t]                             # (N,)
        ct = c_ref[0, t]                             # (N,)
        h = h_ref[...]
        da = jnp.exp(dtt[:, None] * A)               # (d_in, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        h_ref[...] = h
        y_ref[0, t] = jnp.sum(h * ct[None, :], axis=1).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(j == n_chunks - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


def selective_scan_bsd(x, dt, A, Bc, Cc, h0, *, chunk: int = 256,
                       interpret=None):
    """x, dt (B,S,d_in) f32; A (d_in,N); Bc,Cc (B,S,N); h0 (B,d_in,N).

    Returns (y (B,S,d_in), h_last (B,d_in,N)).
    ``interpret=None`` resolves from the platform dispatch policy.
    """
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S, d_in = x.shape
    N = A.shape[1]
    c = min(chunk, S)
    n_chunks = -(-S // c)
    pad = n_chunks * c - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    Sp = n_chunks * c

    kernel = functools.partial(_kernel, chunk=c, n_chunks=n_chunks)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, n_chunks),
        in_specs=[
            pl.BlockSpec((1, c, d_in), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, c, d_in), lambda b, j: (b, j, 0)),
            pl.BlockSpec((d_in, N), lambda b, j: (0, 0)),
            pl.BlockSpec((1, c, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, c, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, d_in, N), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, d_in), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, d_in, N), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, d_in), x.dtype),
            jax.ShapeDtypeStruct((B, d_in, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_in, N), jnp.float32)],
        interpret=interpret,
        name="dagafl_selective_scan",
    )(x, dt, A, Bc, Cc, h0)
    return (y[:, :S] if pad else y), h_last
