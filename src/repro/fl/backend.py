"""Client training backends shared by DAG-AFL and all baselines.

A backend owns the jitted local-training/eval/signature programs for one
model family.  ``CNNBackend`` is the paper-faithful path (VGG family, exact
Eq. 3 zero-count signatures); ``LMBackend`` federates any ArchConfig
transformer (threshold-zero signatures; see DESIGN.md hardware adaptation).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.configs.cnn import CNNConfig
from repro.data.synthetic import Dataset
from repro.models import cnn as cnn_mod
from repro.models import transformer as tfm
from repro.optim.optimizers import apply_updates, sgd
from repro.runtime import Runtime


class CNNBackend:
    """VGG-family clients on image data (the paper's experimental setup)."""

    def __init__(self, cfg: CNNConfig, lr: float = 0.01,
                 local_epochs: int = 5, batch_size: int = 64,
                 kernel_policy: Optional[str] = None):
        self.cfg = cfg
        self.lr = lr
        self.local_epochs = local_epochs
        self.batch_size = batch_size
        # None -> incumbent pure-jnp signature math; anything else resolves
        # through the dispatch layer (e.g. "auto" -> interpret on CPU CI).
        if kernel_policy is None:
            self.kernel_policy = "reference"
        else:
            from repro.kernels.dispatch import resolve_policy
            self.kernel_policy = resolve_policy(kernel_policy)
        self.opt = sgd(lr, momentum=0.9)
        self._train_epoch = jax.jit(self._train_epoch_impl)
        self._eval = jax.jit(self._eval_impl)
        self._signature = jax.jit(self._signature_impl)

    # -- jitted programs ----------------------------------------------------

    def _train_epoch_impl(self, params, opt_state, xb, yb):
        """xb (n_batches, B, H, W, C); yb (n_batches, B)."""

        def step(carry, batch):
            params, opt_state = carry
            x, y = batch
            loss, grads = jax.value_and_grad(
                lambda p: cnn_mod.cnn_loss(p, {"images": x, "labels": y},
                                           self.cfg)[0])(params)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), (xb, yb))
        return params, opt_state, jnp.mean(losses)

    def _eval_impl(self, params, x, y):
        logits, _ = cnn_mod.cnn_forward(params, x, self.cfg)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    def _signature_impl(self, params, x):
        _, sig = cnn_mod.cnn_forward(params, x, self.cfg, want_signature=True,
                                     kernel_policy=self.kernel_policy)
        return sig

    # -- public API ----------------------------------------------------------

    def init(self, key):
        return cnn_mod.init_cnn(key, self.cfg)

    def init_opt(self, params):
        return self.opt.init(params)

    def _batches(self, ds: Dataset, rng) -> tuple:
        n = (len(ds) // self.batch_size) * self.batch_size
        if n == 0:  # tiny shard: single batch with repetition
            idx = rng.integers(0, len(ds), self.batch_size)
            return (jnp.asarray(ds.x[idx])[None], jnp.asarray(ds.y[idx])[None])
        idx = rng.permutation(len(ds))[:n]
        xb = jnp.asarray(ds.x[idx]).reshape(-1, self.batch_size, *ds.x.shape[1:])
        yb = jnp.asarray(ds.y[idx]).reshape(-1, self.batch_size)
        return xb, yb

    def train_local(self, params, ds: Dataset, seed: int = 0,
                    epochs: Optional[int] = None):
        with obs.span("dagafl.train", rounds=1):
            rng = np.random.default_rng(seed)
            opt_state = self.init_opt(params)
            loss = jnp.zeros(())
            for _ in range(epochs or self.local_epochs):
                xb, yb = self._batches(ds, rng)
                params, opt_state, loss = self._train_epoch(params, opt_state,
                                                            xb, yb)
            return params, float(obs.fetch(loss))

    def evaluate(self, params, ds: Dataset, limit: int = 512) -> float:
        with obs.span("dagafl.eval", rounds=1):
            n = min(len(ds), limit)
            return float(obs.fetch(self._eval(params, jnp.asarray(ds.x[:n]),
                                              jnp.asarray(ds.y[:n]))))

    def signature(self, params, ds: Dataset, limit: int = 128) -> np.ndarray:
        with obs.span("dagafl.signature", rounds=1):
            n = min(len(ds), limit)
            return obs.fetch(self._signature(params, jnp.asarray(ds.x[:n])))


class LMBackend:
    """Transformer clients on token streams (framework-scale DAG-AFL)."""

    def __init__(self, cfg: ArchConfig, lr: float = 3e-3,
                 local_steps: int = 8, batch_size: int = 8, seq_len: int = 64,
                 kernel_policy: Optional[str] = None):
        self.cfg = cfg
        self.local_steps = local_steps
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.opt = sgd(lr, momentum=0.9)
        # kernel_policy=None keeps the incumbent stock-XLA forward; a policy
        # turns on the Pallas hot paths (attention + Eq. 3 signature) for
        # eval/signature programs — training stays on the XLA path because
        # pallas_call is not differentiable (see cohort.LMCohortPrograms).
        if kernel_policy is None:
            self.kernel_policy = "reference"
            self.runtime = Runtime(want_signature=True)
        else:
            from repro.kernels.dispatch import resolve_policy
            self.kernel_policy = resolve_policy(kernel_policy)
            self.runtime = Runtime(want_signature=True, use_pallas=True,
                                   kernel_policy=self.kernel_policy)
        self._train_steps = jax.jit(self._train_steps_impl)
        self._eval = jax.jit(self._eval_impl)

    def _train_steps_impl(self, params, opt_state, tokens):
        """tokens (n_steps, B, S+1)."""

        def step(carry, tb):
            params, opt_state = carry
            batch = {"tokens": tb[:, :-1], "labels": tb[:, 1:]}
            (loss, aux), grads = jax.value_and_grad(
                lambda p: tfm.loss_fn(p, batch, self.cfg), has_aux=True)(params)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(step, (params, opt_state),
                                                   tokens)
        return params, opt_state, jnp.mean(losses)

    def _eval_impl(self, params, tokens):
        batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
        logits, aux, _ = tfm.forward(params, batch, self.cfg, self.runtime,
                                     mode="prefill")
        pred = jnp.argmax(logits, -1)
        acc = jnp.mean((pred == tokens[:, 1:]).astype(jnp.float32))
        return acc, aux.get("signature", jnp.zeros((64,)))

    def init(self, key):
        return tfm.init_params(key, self.cfg)

    def _sample(self, stream: np.ndarray, rng, n: int):
        starts = rng.integers(0, len(stream) - self.seq_len - 1,
                              (n, self.batch_size))
        return jnp.asarray(np.stack([
            np.stack([stream[s:s + self.seq_len + 1] for s in row])
            for row in starts]))

    def train_local(self, params, stream: np.ndarray, seed: int = 0,
                    epochs: Optional[int] = None):
        with obs.span("dagafl.train", rounds=1):
            rng = np.random.default_rng(seed)
            toks = self._sample(stream, rng, epochs or self.local_steps)
            opt_state = self.opt.init(params)
            params, _, loss = self._train_steps(params, opt_state, toks)
            return params, float(obs.fetch(loss))

    def evaluate(self, params, stream: np.ndarray, seed: int = 1) -> float:
        with obs.span("dagafl.eval", rounds=1):
            rng = np.random.default_rng(seed)
            toks = self._sample(stream, rng, 1)[0]
            acc, _ = self._eval(params, toks)
            return float(obs.fetch(acc))

    def signature(self, params, stream: np.ndarray, seed: int = 2) -> np.ndarray:
        with obs.span("dagafl.signature", rounds=1):
            rng = np.random.default_rng(seed)
            toks = self._sample(stream, rng, 1)[0]
            _, sig = self._eval(params, toks)
            return obs.fetch(sig)
