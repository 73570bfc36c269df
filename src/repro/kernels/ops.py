"""Platform-aware kernel dispatch layer over the Pallas kernels.

Model code calls these through ``Runtime(use_pallas=True)``; every wrapper
takes a ``policy`` (see :mod:`repro.kernels.dispatch`) deciding how the op
executes:

  ``"compiled"``   the Pallas kernel lowered to Mosaic (TPU),
  ``"interpret"``  the same kernel through the Pallas interpreter (the
                   CPU-container CI path),
  ``"reference"``  the pure-jnp oracle (``kernels/ref.py`` / inline jnp)
                   — bit-for-bit the stock-XLA incumbent math,
  ``"auto"``/None  resolved from ``$REPRO_KERNEL_POLICY`` and then
                   ``jax.default_backend()`` (TPU -> compiled, else
                   interpret).

``interpret=`` remains as an explicit last-resort override of the
policy's compile/interpret choice; call sites outside ``kernels/`` should
pass ``policy`` instead (lint rule KER001 enforces this).

Bit-stability contract for ``signature``/``signature_per_channel``: the
Eq. 3 signatures feed tip selection through the similarity contract, so a
1-ulp drift changes which parents a client approves and therefore the DAG
topology.  The kernel path accumulates raw 0/1 flag COUNTS (exact
integers in f32) and normalises them with ``counts * (1/n)`` — the same
multiply-by-reciprocal XLA lowers ``jnp.mean`` to — so kernel and
reference signatures agree bit-for-bit, padding tail included, for every
``d % n_sig`` (pinned by tests/test_kernel_dispatch.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import (KERNEL_POLICIES, POLICY_ENV,  # noqa: F401
                                    policy_from_runtime, resolve_interpret,
                                    resolve_policy)
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.mlstm import mlstm_chunkwise_bshd
from repro.kernels.moe_gmm import moe_gmm_rows
from repro.kernels.selective_scan import selective_scan_bsd
from repro.kernels.signature import signature_ntd
from repro.kernels.slstm import slstm_scan_bsd


def flash_attention(q, k, v, *, causal: bool = True, window: int = -1,
                    softcap: float = 0.0, scale=None, policy=None,
                    interpret=None):
    """(B,S,H,hd) layout wrapper used by repro.models.attention; ``scale``
    multiplies the scores (None: 1/sqrt(hd))."""
    p = resolve_policy(policy)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if p == "reference" and interpret is None:
        from repro.kernels.ref import flash_attention_ref
        out = flash_attention_ref(qt, kt, vt, causal=causal, window=window,
                                  softcap=softcap, scale=scale)
    else:
        out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   softcap=softcap, scale=scale,
                                   interpret=resolve_interpret(interpret, p))
    return out.transpose(0, 2, 1, 3)


def moe_gmm(lhs, rhs, group_sizes, *, tm: int, layer=None, policy=None,
            interpret=None):
    """Grouped matmul of held experts (``kernels/moe_gmm.py``): lhs (M, K)
    rows sorted by expert, each expert's rows starting on a ``tm`` tile;
    rhs (E, K, N), or (L, E, K, N) and the ``layer`` to use; group_sizes
    (E,) padded to multiples of ``tm``.  Only the rows inside the groups
    are defined."""
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        from repro.kernels.ref import moe_gmm_ref
        return moe_gmm_ref(lhs, rhs if layer is None else rhs[layer],
                           group_sizes)
    return moe_gmm_rows(lhs, rhs, group_sizes, tm=tm, layer=layer,
                        interpret=resolve_interpret(interpret, p))


def selective_scan(x, dt, A, Bc, Cc, h0, *, chunk: int = 256,
                   policy=None, interpret=None):
    """Drop-in for repro.models.mamba.selective_scan_ref."""
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        from repro.kernels.ref import selective_scan_seq_ref
        return selective_scan_seq_ref(x, dt, A, Bc, Cc, h0)
    return selective_scan_bsd(x, dt, A, Bc, Cc, h0, chunk=chunk,
                              interpret=resolve_interpret(interpret, p))


def _threshold_flags(x, tau: float):
    """0/1 flag tensor with the kernels' tau semantics: ``tau <= 0`` is the
    EXACT-zero count (the CNN path), ``tau > 0`` the |x| < tau band (the
    LM path, matching ``models.layers.activation_signature``)."""
    if tau <= 0.0:
        flags = (x == 0.0)
    else:
        flags = jnp.abs(x.astype(jnp.float32)) < tau
    return flags.astype(jnp.float32)


def signature(x, *, tau: float = 0.05, n_sig: int = 64,
              policy=None, interpret=None):
    """Activation (..., d) -> bucketed Eq. 3 signature vector (n_sig,).

    Bit-identical to ``models.layers.activation_signature`` (for
    ``tau > 0``; ``tau <= 0`` swaps in the exact-zero flags) on every
    policy: the reference path runs its literal math, the kernel path
    reduces exact flag counts in VMEM and applies the identical
    ``* (1 / (T * w))`` normalisation — zero-padded tail channels simply
    contribute zero counts, exactly as zero-padded flag columns do.
    """
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        t = flat.shape[0]
        pad = (-d) % n_sig
        w = (d + pad) // n_sig
        flags = _threshold_flags(flat, tau)              # (T, d)
        if pad:
            flags = jnp.pad(flags, ((0, 0), (0, pad)))
        return jnp.mean(flags.reshape(t, n_sig, w), axis=(0, 2))
    return signature_rows(flat[None], tau=tau, n_sig=n_sig, policy=p,
                          interpret=interpret)[0]


def signature_rows(x, *, tau: float = 0.05, n_sig: int = 64,
                   policy=None, interpret=None):
    """Per-row Eq. 3 signatures: (N, ..., d) -> (N, n_sig).

    Bit-identical to ``jax.vmap`` of :func:`signature` over the leading
    axis, but the kernel path is ONE ``pallas_call`` with the rows as a
    grid axis (see ``kernels/signature.py``) instead of a vmapped one.
    """
    n, d = x.shape[0], x.shape[-1]
    flat = x.reshape(n, -1, d)
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        return jax.vmap(lambda row: signature(
            row, tau=tau, n_sig=n_sig, policy=p))(flat)
    t = flat.shape[1]
    pad = (-d) % n_sig
    w = (d + pad) // n_sig
    counts = signature_ntd(flat, tau=tau, mean=False,
                           interpret=resolve_interpret(interpret, p))
    if pad:
        counts = jnp.pad(counts, ((0, 0), (0, pad)))
    bucket_sums = jnp.sum(counts.reshape(n, n_sig, w), axis=2)
    # multiply-by-reciprocal, NOT division: jnp.mean lowers to
    # sum * (1/n), and the two roundings differ by 1 ulp on ~3% of
    # fraction values — enough to flip tip selections
    return bucket_sums * (1.0 / np.float32(t * w))


def signature_per_channel(x, *, tau: float = 0.0, policy=None,
                          interpret=None):
    """Per-sample per-channel threshold fractions: (N, ..., C) -> (N, C).

    The CNN suites' Eq. 3 rows: for each sample the fraction of exact
    zeros (ReLU kill rate) over the spatial axes, per channel.
    Bit-identical to ``jnp.mean((x == 0.0).astype(f32), axis=spatial)``
    on every policy (same exact-count + multiply-by-reciprocal argument
    as :func:`signature`).
    """
    n, c = x.shape[0], x.shape[-1]
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        flags = _threshold_flags(x, tau)
        return jnp.mean(flags, axis=tuple(range(1, x.ndim - 1)))
    flat = x.reshape(n, -1, c)
    hw = flat.shape[1]
    counts = signature_ntd(flat, tau=tau, mean=False,
                           interpret=resolve_interpret(interpret, p))
    return counts * (1.0 / np.float32(hw))


def slstm_scan(gates_x, R, c0, n0, h0, m0, *, chunk: int = 256,
               policy=None, interpret=None):
    """R-resident sLSTM recurrence (inference path)."""
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        from repro.kernels.ref import slstm_scan_ref
        return slstm_scan_ref(gates_x, R, c0, n0, h0, m0)
    return slstm_scan_bsd(gates_x, R, c0, n0, h0, m0, chunk=chunk,
                          interpret=resolve_interpret(interpret, p))


def mlstm_chunkwise(q, k, v, i_gate, f_gate, *, chunk: int = 128,
                    policy=None, interpret=None):
    """Chunkwise mLSTM with VMEM-resident matrix memory (inference path)."""
    p = resolve_policy(policy)
    if p == "reference" and interpret is None:
        from repro.kernels.ref import mlstm_chunkwise_ref
        return mlstm_chunkwise_ref(q, k, v, i_gate, f_gate)
    return mlstm_chunkwise_bshd(q, k, v, i_gate, f_gate, chunk=chunk,
                                interpret=resolve_interpret(interpret, p))
