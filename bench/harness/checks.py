"""The comparisons that decide ``correct``: gaps between what the timed
path produced and what the plain reference computes, each reduced to one
number that ``limits/<workload>.json`` bounds."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def delta_norms(after, before):
    """Per-leaf Frobenius norm of ``after - before``, in float32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(after),
                        jax.tree_util.tree_leaves(before))])


@jax.jit
def norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(tree)])


def moving_leaves(ref_grad_norms) -> np.ndarray:
    """Leaves the reference moves: first-step gradient at least a
    thousandth of the median leaf's (the rest move by round-off alone)."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= 1e-3 * np.median(g)


def leaf_gaps(prog_norms, ref_norms, keep=None) -> np.ndarray:
    """Each leaf's gap between the program's and the reference's norm,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    p = np.asarray(prog_norms, np.float64)
    r = np.asarray(ref_norms, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    scale = np.maximum(r, np.median(r))
    return np.abs(p - r) / np.maximum(scale, 1e-30)


def norm_gap(prog_norms, ref_norms, keep=None) -> float:
    """The worst leaf's gap (``leaf_gaps``)."""
    return float(np.max(leaf_gaps(prog_norms, ref_norms, keep)))


def median_norm_gap(prog_norms, ref_norms, keep=None) -> float:
    """The median leaf's gap (``leaf_gaps``): steadier from seed to seed
    than the worst leaf's, which carries the noise of a round's later
    steps."""
    return float(np.median(leaf_gaps(prog_norms, ref_norms, keep)))


def relative_diff(prog_tree, ref_tree) -> float:
    """Worst leaf's ||prog - ref|| / ||ref|| (an answer, such as an
    aggregate, judged by what it says)."""
    d = np.asarray(delta_norms(prog_tree, ref_tree), np.float64)
    r = np.asarray(norms(ref_tree), np.float64)
    return float(np.max(d / np.maximum(r, 1e-30)))


def loss_gap(prog_losses, ref_losses, steps: int = 3) -> float:
    """Largest gap over a client's first ``steps`` losses, relative to the
    reference's loss or to one nat, whichever is larger: a loss that a
    one-class shard drives to nought has no relative precision left."""
    p = np.asarray(prog_losses[:steps], np.float64)
    r = np.asarray(ref_losses[:steps], np.float64)
    n = min(len(p), len(r))
    return float(np.max(np.abs(p[:n] - r[:n]) / np.maximum(np.abs(r[:n]),
                                                          1.0)))
