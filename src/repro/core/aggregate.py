"""Model aggregation (paper Eq. 6) as jitted pytree programs.

Eq. 6 is a plain average over the N selected tip models; ``tree_weighted``
is the beyond-paper generalisation (staleness- or accuracy-weighted) used by
the optimized DAG-AFL variant and by several baselines (FedAsync mixing,
FedAT tier weighting).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def tree_mean(models: Sequence):
    """Eq. 6: w = (1/N) * sum_i w_i  over a list of congruent pytrees."""
    n = len(models)
    return jax.tree_util.tree_map(
        lambda *leaves: sum(l.astype(jnp.float32) for l in leaves) / n
        if jnp.issubdtype(leaves[0].dtype, jnp.floating) else leaves[0],
        *models)


def tree_weighted(models: Sequence, weights: Sequence[float]):
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-12)

    def combine(*leaves):
        if not jnp.issubdtype(leaves[0].dtype, jnp.floating):
            return leaves[0]
        return sum(wi * l.astype(jnp.float32) for wi, l in zip(w, leaves))

    return jax.tree_util.tree_map(combine, *models)


# -- stacked-tree variants (leading client axis) ----------------------------
#
# The cohort execution engine keeps K client models stacked as ONE pytree
# whose leaves carry a leading client axis.  Aggregating over that axis is a
# single XLA reduction instead of K Python-level ``tree_mean`` calls.
#
# With a device mesh carrying a ``clients`` axis (see
# ``repro.launch.mesh.make_cohort_mesh``), the stacked axis lives sharded
# across devices; ``stacked_mean`` / ``stacked_weighted`` then reduce it with
# ``shard_map`` + ``lax.psum`` cross-device collectives — each device sums
# its local client shard, one psum produces the Eq. 6 aggregate replicated
# everywhere.  ``mesh=None`` (the default) keeps the single-device programs
# bit-for-bit as before.


def round_up_multiple(x: int, n: int) -> int:
    """Smallest multiple of ``n`` that is >= ``x`` (the mesh-divisibility
    pad target for stacked client/model axes)."""
    return -(-x // n) * n


def pad_leading(arr, target: int):
    """Zero-pad the leading axis of ``arr`` out to ``target`` rows."""
    if arr.shape[0] == target:
        return arr
    pad = [(0, target - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def next_pow2(x: int) -> int:
    """Smallest power of two >= ``x`` (>= 1): the shared shape-quantization
    policy that keeps jitted program families bounded at ~log2."""
    p = 1
    while p < x:
        p *= 2
    return p


def _quantized_target(x: int, n: int) -> int:
    """Pad target for a sharded stacked axis: next power of two >= ``x``,
    rounded up to a multiple of the mesh size ``n``.  The power-of-two
    quantization bounds the psum reducers' compiled-program family at
    ~log2 of the largest window (K and M vary every cohort window; padding
    to the bare multiple would recompile per geometry)."""
    return round_up_multiple(next_pow2(x), n)


_COLLECTIVE_CACHE = {}


def _psum_reducer(mesh, axis_names: tuple, kind: str):
    """Cached jitted shard_map programs reducing a LIST of float leaves whose
    leading axis is sharded over ``axis_names`` (one mesh axis, or — on the
    2-D (clients, data) cohort mesh — BOTH axes, so every device in the mesh
    holds a slice of the stacked models and one psum over the axis pair
    assembles the aggregate).

    ``sum``:  leaves (M, ...) -> total over M, replicated.
    ``wsum``: leaves (M, ...) + weights (K, M) -> (K, ...) einsum, replicated.
    Padding rows must carry zeros (zero weight) — they fall out of the sum.
    """
    key = (mesh, axis_names, kind)
    fn = _COLLECTIVE_CACHE.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    lead = axis_names if len(axis_names) > 1 else axis_names[0]
    if kind == "sum":
        def local(leaves):
            return [jax.lax.psum(jnp.sum(l, axis=0), axis_names)
                    for l in leaves]
        fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(lead),),
                                   out_specs=P()))
    elif kind == "wsum":
        def local(leaves, w):
            return [jax.lax.psum(jnp.einsum("km,m...->k...", w, l),
                                 axis_names)
                    for l in leaves]
        fn = jax.jit(jax.shard_map(local, mesh=mesh,
                                   in_specs=(P(lead), P(None, lead)),
                                   out_specs=P()))
    else:
        raise ValueError(kind)
    _COLLECTIVE_CACHE[key] = fn
    return fn


def _mesh_axis_size(mesh, axis_name: str) -> int:
    if mesh is None or axis_name is None:
        return 1
    return int(dict(mesh.shape).get(axis_name, 1))


def _reduce_axes(mesh, axis_name: str, data_axis) -> tuple:
    """Mesh axes a stacked reduction shards its leading dim over: the
    clients axis, joined by the data axis when the mesh carries one larger
    than 1 (2-D cohort mesh — aggregation has no per-sample structure, so
    the model axis simply spreads over every device)."""
    axes = (axis_name,)
    if _mesh_axis_size(mesh, data_axis) > 1:
        axes = axes + (data_axis,)
    return axes


def tree_stack(models: Sequence):
    """Stack K congruent pytrees into one with a leading K axis per leaf."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *models)


def tree_unstack(stacked) -> list:
    """Inverse of :func:`tree_stack`: split the leading axis back out."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    n = leaves[0].shape[0]
    return [jax.tree_util.tree_unflatten(treedef, [leaf[i] for leaf in leaves])
            for i in range(n)]


@jax.jit
def _stacked_mean_single(stacked):
    return jax.tree_util.tree_map(
        lambda leaf: jnp.mean(leaf.astype(jnp.float32), axis=0)
        if jnp.issubdtype(leaf.dtype, jnp.floating) else leaf[0], stacked)


def stacked_mean(stacked, mesh=None, axis_name: str = "clients",
                 data_axis=None):
    """Eq. 6 over a stacked tree: mean over the leading client axis.

    With a ``mesh`` whose ``axis_name`` axis is larger than one, the leading
    axis is treated as sharded over it: each device part-sums its local
    clients and one ``psum`` yields the mean (leading axis zero-padded to a
    mesh-size multiple; zeros drop out of the sum, the divisor stays K).
    On a 2-D (clients, data) cohort mesh, pass ``data_axis`` to spread the
    stacked axis over BOTH mesh axes — the psum then runs over the axis
    pair and every device carries 1/(C*D) of the models."""
    axes = _reduce_axes(mesh, axis_name, data_axis)
    n = int(np.prod([_mesh_axis_size(mesh, a) for a in axes]))
    if n <= 1:
        return _stacked_mean_single(stacked)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    k = int(leaves[0].shape[0])
    target = _quantized_target(k, n)
    is_f = [jnp.issubdtype(l.dtype, jnp.floating) for l in leaves]
    floats = [pad_leading(l.astype(jnp.float32), target)
              for l, f in zip(leaves, is_f) if f]
    summed = iter(_psum_reducer(mesh, axes, "sum")(floats)
                  if floats else [])
    out = [next(summed) / k if f else l[0] for l, f in zip(leaves, is_f)]
    return jax.tree_util.tree_unflatten(treedef, out)


def stacked_weighted(stacked, weights, mesh=None, axis_name: str = "clients",
                     data_axis=None):
    """Weighted aggregation over a stacked tree's leading axis M.

    ``weights`` of shape (M,) produces one aggregate tree;  shape (K, M)
    produces a stacked tree of K aggregates in one einsum per leaf — the
    cohort path's "aggregate every client's tip selection at once", where
    row k holds client k's (normalised) weights over the M stacked models.

    With a ``mesh``, the M axis is sharded over ``axis_name`` (joined by
    ``data_axis`` on a 2-D cohort mesh): each device einsums its local
    models against its weight columns and one ``psum`` assembles the
    (K, ...) aggregates (M zero-padded to a mesh-size multiple with zero
    weights — identical math).
    """
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-12)
    batched = w.ndim == 2

    axes = _reduce_axes(mesh, axis_name, data_axis) if mesh is not None \
        else (axis_name,)
    n = int(np.prod([_mesh_axis_size(mesh, a) for a in axes]))
    if n > 1:
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        m = int(leaves[0].shape[0])
        target = _quantized_target(m, n)
        # quantize BOTH stacked axes: K (weight rows) and M (models) vary
        # every cohort window, and each shape pair is a compiled program
        w2 = w if batched else w[None]
        k = int(w2.shape[0])
        k_pad = _quantized_target(k, 1)
        w2 = jnp.pad(w2, ((0, k_pad - k), (0, target - m)))
        is_f = [jnp.issubdtype(l.dtype, jnp.floating) for l in leaves]
        floats = [pad_leading(l.astype(jnp.float32), target)
                  for l, f in zip(leaves, is_f) if f]
        red = iter(_psum_reducer(mesh, axes, "wsum")(floats, w2)
                   if floats else [])

        def pick(l, f):
            if f:
                r = next(red)
                return r[:k] if batched else r[0]
            if batched:
                return jnp.broadcast_to(l[0], (k,) + l.shape[1:])
            return l[0]

        out = [pick(l, f) for l, f in zip(leaves, is_f)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def combine(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            if batched:
                return jnp.broadcast_to(leaf[0], w.shape[:1] + leaf.shape[1:])
            return leaf[0]
        f = leaf.astype(jnp.float32)
        if batched:
            return jnp.einsum("km,m...->k...", w, f)
        return jnp.einsum("m,m...->...", w, f)

    return jax.tree_util.tree_map(combine, stacked)


@jax.jit
def tree_interpolate(a, b, alpha: float):
    """FedAsync-style mixing: (1-alpha)*a + alpha*b."""
    return jax.tree_util.tree_map(
        lambda x, y: ((1 - alpha) * x.astype(jnp.float32)
                      + alpha * y.astype(jnp.float32))
        if jnp.issubdtype(x.dtype, jnp.floating) else x, a, b)


def tree_size_bytes(model) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(model) if hasattr(a, "size"))
