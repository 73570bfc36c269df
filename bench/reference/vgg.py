"""Plain VGG reference: the forward pass in float32 at HIGHEST matmul
precision with ``lax.conv``, softmax cross-entropy, SGD with momentum, the
Eq. 6 mean, Eq. 3 zero fractions and accuracy.  It imports nothing of the
program.  Two lower-precision controls: ``dtype=bfloat16`` holds params,
activations and optimizer state in bfloat16; ``quant="fp8"`` rounds every
conv and matmul operand to float8 e4m3 under a per-tensor absmax scale
(straight through in the backward pass) and keeps the rest float32.
``quant="default"`` runs every conv and matmul at XLA's default precision,
the program's own (on a TPU: float32 operands in one bfloat16 pass)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def init(key, cfg: dict):
    """He-normal weights and zero biases in the program's pytree layout
    ({"convs": [[{"w", "b"}, ...], ...], "fcs": [{"w", "b"}, ...]})."""
    k, cin, size = cfg["kernel_size"], cfg["in_channels"], cfg["image_size"]
    n_layers = sum(len(s) for s in cfg["conv_stacks"]) + len(cfg["fc_dims"]) + 1
    keys = iter(jax.random.split(key, n_layers))
    params = {"convs": [], "fcs": []}
    for stack in cfg["conv_stacks"]:
        layer = []
        for cout in stack:
            w = jax.random.normal(next(keys), (k, k, cin, cout), F32)
            layer.append({"w": w * math.sqrt(2.0 / (k * k * cin)),
                          "b": jnp.zeros((cout,), F32)})
            cin = cout
        params["convs"].append(layer)
        size //= 2
    d = cin * size * size
    for out in list(cfg["fc_dims"]) + [cfg["n_classes"]]:
        w = jax.random.normal(next(keys), (d, out), F32) * math.sqrt(2.0 / d)
        params["fcs"].append({"w": w, "b": jnp.zeros((out,), F32)})
        d = out
    return params


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == F32
            else jax.lax.Precision.DEFAULT)


def q8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale; the gradient
    passes straight through."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    r = (x * s).astype(jnp.float8_e4m3fn).astype(x.dtype) / s
    return x + jax.lax.stop_gradient(r - x)


def _ops(a, b, quant):
    return (q8(a), q8(b)) if quant == "fp8" else (a, b)


def _precision(dtype, quant):
    return jax.lax.Precision.DEFAULT if quant == "default" else _prec(dtype)


def _conv(x, w, dtype, quant=None):
    x, w = _ops(x, w, quant)
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(dtype, quant))


def _dot(x, w, dtype, quant=None):
    x, w = _ops(x, w, quant)
    return jnp.dot(x, w, precision=_precision(dtype, quant))


def features(params, x, cfg: dict, dtype=F32, upto=None, quant=None):
    """Post-ReLU output of conv ``upto`` (an index over all convs), or the
    logits when ``upto`` is None."""
    x = x.astype(dtype)
    idx = 0
    for stack in params["convs"]:
        for p in stack:
            x = jax.nn.relu(_conv(x, p["w"].astype(dtype), dtype, quant)
                            + p["b"].astype(dtype))
            if idx == upto:
                return x
            idx += 1
        b, h, w, c = x.shape
        x = jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))
    x = x.reshape(x.shape[0], -1)
    for p in params["fcs"][:-1]:
        x = jax.nn.relu(_dot(x, p["w"].astype(dtype), dtype, quant)
                        + p["b"].astype(dtype))
    p = params["fcs"][-1]
    return _dot(x, p["w"].astype(dtype), dtype, quant) + p["b"].astype(dtype)


def loss(params, x, y, cfg: dict, dtype=F32, quant=None):
    logits = features(params, x, cfg, dtype, quant=quant).astype(F32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "lr",
                                             "momentum", "quant"))
def _step(params, mu, x, y, *, cfg_key, dtype, lr, momentum, quant=None):
    cfg = dict(cfg_key)
    value, grads = jax.value_and_grad(loss)(params, x, y, cfg, dtype, quant)
    mu = jax.tree_util.tree_map(lambda m, g: (momentum * m + g).astype(m.dtype),
                                mu, grads)
    params = jax.tree_util.tree_map(lambda p, m: (p - lr * m).astype(p.dtype),
                                    params, mu)
    return params, mu, value, grads


def precision(cfg: dict):
    """``quant`` for the matmul precision the configuration states:
    ``"default"`` (XLA's default: on a TPU, float32 operands in one
    bfloat16 pass) or ``"highest"`` (None, full float32)."""
    return {"default": "default", "highest": None}[cfg["precision"]]


def freeze(cfg: dict):
    """Hashable view of a configuration (a static jit argument)."""
    return tuple(sorted((k, tuple(map(tuple, v)) if k == "conv_stacks" else
                         (tuple(v) if isinstance(v, list) else v))
                        for k, v in cfg.items()
                        if k in ("conv_stacks", "fc_dims", "n_classes",
                                 "image_size", "in_channels", "kernel_size",
                                 "signature_layer")))


def batch_order(n: int, batch: int, seed: int, epochs: int = 1):
    """Row indices of each local step: per epoch, a permutation from one
    seeded stream cut into whole batches; a shard smaller than one batch
    draws one batch with repetition."""
    rng = np.random.default_rng(seed)
    whole = (n // batch) * batch
    out = []
    for _ in range(epochs):
        if whole == 0:
            out.append(rng.integers(0, n, batch)[None])
        else:
            out.append(rng.permutation(n)[:whole].reshape(-1, batch))
    return np.concatenate(out)


def train(params, ds_x, ds_y, seed: int, cfg: dict, opt: dict, batch: int,
          epochs: int = 1, dtype=F32, keep_first_grads: bool = False,
          drop_half: bool = False, quant=None):
    """Local SGD from ``params``; returns (params, per-step losses, first
    step's gradients or None).  ``drop_half`` takes each step's mean over
    the first half of its batch only (a planted fault)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    mu = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, dtype), params)
    losses, first = [], None
    key = freeze(cfg)
    for rows in batch_order(len(ds_y), batch, seed, epochs):
        if drop_half:
            rows = rows[: len(rows) // 2]
        p, mu, v, g = _step(p, mu, jnp.asarray(ds_x[rows]),
                            jnp.asarray(ds_y[rows]), cfg_key=key,
                            dtype=jnp.dtype(dtype).name, lr=opt["lr"],
                            momentum=opt["momentum"], quant=quant)
        losses.append(v)
        if keep_first_grads and first is None:
            first = g
    return p, [float(v) for v in losses], first


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "quant"))
def accuracy(params, x, y, *, cfg_key, dtype="float32", quant=None):
    logits = features(params, x, dict(cfg_key), jnp.dtype(dtype), quant=quant)
    return jnp.mean((jnp.argmax(logits, -1) == y).astype(F32))


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "quant"))
def signature(params, x, *, cfg_key, dtype="float32", quant=None):
    """Eq. 3-4: per-channel fraction of exact zeros of the post-ReLU map
    of conv ``signature_layer``, averaged over the samples."""
    cfg = dict(cfg_key)
    fmap = features(params, x, cfg, jnp.dtype(dtype),
                    upto=cfg["signature_layer"], quant=quant)
    return jnp.mean((fmap == 0).astype(F32), axis=(0, 1, 2))


@jax.jit
def mean(models):
    """Eq. 6: the plain average of the selected models, in float32."""
    n = len(models)
    return jax.tree_util.tree_map(lambda *ls: sum(ls) / n, *models)
