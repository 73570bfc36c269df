"""Grouped matmul of the experts a layer holds: a Pallas TPU kernel.

``lhs`` holds the token rows routed to the held experts, sorted by expert,
with each expert's rows starting on a ``tm``-row tile boundary (the rows
that pad a group to whole tiles are never read back).  ``group_sizes``
gives each expert's padded row count, so every row tile belongs to one
expert.  The grid is (N tiles, row tiles, K tiles) with K innermost and
accumulated in VMEM; the expert of each row tile and the number of live
tiles are scalar-prefetched, and a dead tile (past the routed rows) maps
its blocks onto the last live tile's, so it costs a grid step and no copy.
The rows of dead tiles are left unwritten.  The experts may be one layer of
a layer-stacked array, picked by a scalar-prefetched index, so that a
layer scan does not copy its layer's experts out of the stack.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tile(n: int, choices) -> int:
    """The first of ``choices`` that divides ``n``, else ``n`` itself."""
    for c in choices:
        if n % c == 0:
            return c
    return n


def _kernel(tile_group, n_live, layer, lhs_ref, rhs_ref, out_ref, acc_ref, *,
            nk: int):
    i = pl.program_id(1)
    kk = pl.program_id(2)
    live = i < n_live[0]

    @pl.when(live & (kk == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (kk == nk - 1))
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tile_groups(group_sizes, tm: int, n_tiles: int):
    """(expert of each row tile, number of live tiles) of a tile-aligned
    layout with padded ``group_sizes``."""
    ends = jnp.cumsum(group_sizes) // tm
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    group = jnp.sum(tiles[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    group = jnp.minimum(group, group_sizes.shape[0] - 1)
    return group, ends[-1:].astype(jnp.int32)


def moe_gmm_rows(lhs, rhs, group_sizes, *, tm: int, layer=None,
                 interpret=None):
    """lhs (M, K) rows sorted and tile-aligned by expert; rhs (E, K, N), or
    (L, E, K, N) with ``layer`` the int32 index of the layer to use;
    group_sizes (E,) int32 padded row counts, each a multiple of ``tm``.
    Returns (M, N) in lhs.dtype: row r is ``lhs[r] @ rhs[e(r)]`` for every
    row of a live tile, accumulated in float32."""
    from repro.kernels.dispatch import resolve_interpret
    interpret = resolve_interpret(interpret)
    m, k = lhs.shape
    n = rhs.shape[-1]
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    if m % tm:
        raise ValueError(f"rows {m} not a multiple of the row tile {tm}")
    tk = _tile(k, (1024, 768, 512, 256, 128))
    tn = _tile(n, (1024, 768, 512, 256, 128))
    nk, n_tiles = k // tk, m // tm
    tile_group, n_live = tile_groups(group_sizes.astype(jnp.int32), tm,
                                     n_tiles)

    def _at(i, kk, n_live):
        last = jnp.maximum(n_live[0] - 1, 0)
        dead = i > last
        return jnp.where(dead, last, i), jnp.where(dead, nk - 1, kk)

    def lhs_map(j, i, kk, tile_group, n_live, layer):
        return _at(i, kk, n_live)

    def rhs_map(j, i, kk, tile_group, n_live, layer):
        ii, kc = _at(i, kk, n_live)
        return layer[0], tile_group[ii], kc, j

    def out_map(j, i, kk, tile_group, n_live, layer):
        return _at(i, kk, n_live)[0], j

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_tiles, nk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="dagafl_moe_gmm",
    )(tile_group, n_live, layer, lhs, rhs)
