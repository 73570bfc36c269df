"""Jittable train / serve step builders used by the launcher and dry-run.

``make_train_step`` folds loss, grad, clip, optimizer update and the
DAG-AFL signature extraction into one pjit-able program;
``make_serve_prefill`` / ``make_serve_decode`` are the serving pair
(decode = ONE new token against a KV cache, per the assigned decode shapes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.optim.optimizers import (Optimizer, adamw, apply_updates,
                                    clip_by_global_norm)
from repro.runtime import Runtime


def default_optimizer(cfg: ArchConfig, lr: float = 3e-4) -> Optimizer:
    return adamw(lr, weight_decay=0.1,
                 moment_dtype=jnp.dtype(cfg.moment_dtype))


def make_train_step(cfg: ArchConfig, optimizer: Optional[Optimizer] = None,
                    runtime: Runtime = Runtime(want_signature=True),
                    clip_norm: float = 1.0, microbatches: int = 1):
    """``microbatches > 1`` = gradient accumulation: the global batch is
    split into N sequential microbatches scanned with f32 grad accumulation.
    Activation (and layer-scan carry) memory scales by 1/N — the lever that
    brings 200B+ MoE training under the per-chip HBM budget (see
    EXPERIMENTS.md §Perf H3)."""
    opt = optimizer or default_optimizer(cfg)
    compute = jnp.dtype(cfg.compute_dtype)

    def cast_params(p):
        """Mixed precision: compute against a bf16 copy so FSDP all-gathers
        move half the bytes; the f32 master stays sharded."""
        return jax.tree_util.tree_map(
            lambda a: a.astype(compute)
            if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != compute
            else a, p)

    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: tfm.loss_fn(cast_params(p), batch, cfg, runtime),
            has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, aux), grads = grad_fn(params, batch)
        else:
            def split(leaf):
                # M-RoPE positions are (3, B, S): batch dim is 1 there
                bdim = 1 if (leaf.ndim == 3 and leaf.shape[0] == 3) else 0
                B = leaf.shape[bdim]
                assert B % microbatches == 0, (B, microbatches)
                if bdim == 0:
                    return leaf.reshape(microbatches, B // microbatches,
                                        *leaf.shape[1:])
                out = leaf.reshape(leaf.shape[0], microbatches,
                                   B // microbatches, *leaf.shape[2:])
                return jnp.moveaxis(out, 1, 0)

            mb = jax.tree_util.tree_map(split, batch)

            def body(carry, batch_mb):
                gsum, lsum, auxsum = carry
                (loss, aux), g = grad_fn(params, batch_mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                auxsum = {k: auxsum[k] + v for k, v in aux.items()
                          if k in auxsum}
                return (gsum, lsum + loss, auxsum), aux.get("signature")

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            aux0 = {"ce_loss": jnp.zeros(()), "moe_aux": jnp.zeros(())}
            (grads, loss, aux), sigs = jax.lax.scan(
                body, (g0, jnp.zeros(()), aux0), mb)
            n = float(microbatches)
            grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            loss = loss / n
            aux = {k: v / n for k, v in aux.items()}
            if sigs is not None and runtime.want_signature:
                aux["signature"] = jnp.mean(sigs, axis=0)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, new_opt_state = opt.update(grads, opt_state, params)
        new_params = apply_updates(params, updates)
        metrics = {"loss": loss, "ce_loss": aux["ce_loss"],
                   "moe_aux": aux["moe_aux"], "grad_norm": gnorm}
        if "signature" in aux:
            metrics["signature"] = aux["signature"]
        return new_params, new_opt_state, metrics

    return train_step, opt


def make_serve_prefill(cfg: ArchConfig, runtime: Runtime = Runtime()):
    """``serve_prefill(params, batch) -> (last_logits, caches, stats)``;
    ``stats`` holds the expert layers' per-layer counts
    (``tfm.MOE_STATS``), and nothing for a model without them."""
    def serve_prefill(params, batch):
        last_logits, caches, aux = tfm.prefill(params, batch, cfg, runtime)
        return last_logits, caches, {k: aux[k] for k in tfm.MOE_STATS
                                     if k in aux}

    return serve_prefill


def make_serve_decode(cfg: ArchConfig, runtime: Runtime = Runtime()):
    """``serve_decode(params, token, caches, pos) -> (next_token, logits,
    caches, stats)``, ``stats`` as :func:`make_serve_prefill`'s."""
    def serve_decode(params, token, caches, pos):
        logits, new_caches, stats = tfm.decode_step(params, token, caches,
                                                    pos, cfg, runtime)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_caches, stats

    return serve_decode


def make_eval_step(cfg: ArchConfig, runtime: Runtime = Runtime()):
    def eval_step(params, batch):
        logits, aux, _ = tfm.forward(params, batch, cfg, runtime,
                                     mode="prefill")
        pred = jnp.argmax(logits[:, :-1], axis=-1)
        acc = jnp.mean((pred == batch["tokens"][:, 1:]).astype(jnp.float32))
        return {"accuracy": acc}

    return eval_step
