"""DAG-AFL feature-signature Pallas TPU kernel (paper Eq. 3-4 adaptation).

Computes the per-channel threshold-zero fraction of a batch of activation
matrices (N, T, d) as a block-tiled VMEM reduction: the grid walks the N
rows (parallel) and, per row, the T blocks sequentially while a (1, d) VMEM
scratch accumulates counts — the activation tensor is read from HBM exactly
once and no intermediate (T, d) flag tensor is ever materialised (the
pure-jnp path writes one).  The CNN path's exact-zero count is the tau=0
special case.

The batch is a grid axis, not a ``jax.vmap`` over a single-row kernel: a
vmapped ``(d,)`` output block gains a unit batch dim that breaks Mosaic's
(8, 128)-or-full-dim block rule, whereas each row's ``(1, d)`` block of the
``(N, 1, d)`` output spans its array's last two dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, out_ref, acc_ref, *, tau: float, block_t: int,
            n_blocks: int, total_t: int, mean: bool):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # compare in f32: v5e's VPU has no bf16 compare, and the upcast is exact
    # for every float input, so the flags match the reference bit for bit
    x = x_ref[...].astype(jnp.float32)                # (bt, d)
    rows = i * block_t + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = rows < total_t
    if tau <= 0.0:
        flags = (x == 0.0) & valid
    else:
        flags = (jnp.abs(x) < tau) & valid
    acc_ref[...] = acc_ref[...] + jnp.sum(flags.astype(jnp.float32), axis=0,
                                          keepdims=True)

    @pl.when(i == n_blocks - 1)
    def _emit():
        if mean:
            out_ref[...] = acc_ref[...] / total_t
        else:
            out_ref[...] = acc_ref[...]


def signature_ntd(x, *, tau: float = 0.05, block_t: int = 256,
                  mean: bool = True, interpret=None):
    """x (N, T, d) -> per-row per-channel zero-fraction (N, d) f32.

    ``mean=False`` emits the raw per-channel counts instead of fractions:
    0/1 flag sums are exact integers in f32 (up to 2**24), so callers can
    bucket and normalise them with the exact float ops of the jnp path
    they must stay bit-consistent with (see ``ops.signature``) — whereas
    a fraction cannot be multiplied back into an exact count.

    ``interpret=None`` resolves from the platform dispatch policy
    (``kernels.dispatch``): compiled on TPU, interpreted elsewhere.
    """
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    N, T, d = x.shape
    bt = min(block_t, T)
    n_blocks = -(-T // bt)
    pad = n_blocks * bt - T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)

    kernel = functools.partial(_kernel, tau=tau, block_t=bt,
                               n_blocks=n_blocks, total_t=T, mean=mean)
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[pl.BlockSpec((None, bt, d), lambda n, i: (n, i, 0))],
        out_specs=pl.BlockSpec((None, 1, d), lambda n, i: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dagafl_signature",
    )(x)
    return out[:, 0]


def signature_td(x, *, tau: float = 0.05, block_t: int = 256,
                 mean: bool = True, interpret=None):
    """x (T, d) -> per-channel zero-fraction (d,) f32: one row of
    :func:`signature_ntd`."""
    return signature_ntd(x[None], tau=tau, block_t=block_t, mean=mean,
                         interpret=interpret)[0]
