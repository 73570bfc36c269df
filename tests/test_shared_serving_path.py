"""The serving path DeepSeek-V2 shares with the dense GQA decoders stays as
it was for them: internlm2's tiny prefill and decode give bitwise the
outputs they gave before YaRN, per-layer FFN widths and the expert layers'
counts were added, and their lowered programs hold the same operations.

The expected values were read from the tree before those changes, on
this CPU backend (float32, stock-XLA kernels)."""
import collections
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.serve import extend_caches
from repro.models import transformer as T
from repro.runtime import serve_runtime
from repro.train.step import make_serve_decode, make_serve_prefill

DIGEST = "c492b7585bb870e0af6c695c77aba1d54fc5557121535b74cd70deac5a0e28b8"
COMMON = {"call @_where": 1, "call @silu": 1, "func.call": 1,
          "stablehlo.and": 1, "stablehlo.concatenate": 4,
          "stablehlo.convert": 3, "stablehlo.cosine": 2,
          "stablehlo.divide": 9, "stablehlo.dot_general": 10,
          "stablehlo.exponential": 2, "stablehlo.gather": 2,
          "stablehlo.iota": 4, "stablehlo.maximum": 2,
          "stablehlo.multiply": 16, "stablehlo.negate": 3,
          "stablehlo.power": 2, "stablehlo.rsqrt": 3, "stablehlo.sine": 2,
          "stablehlo.transpose": 2, "stablehlo.while": 1}
PREFILL_OPS = {**COMMON, "stablehlo.add": 15,
               "stablehlo.broadcast_in_dim": 68, "stablehlo.compare": 3,
               "stablehlo.constant": 57, "stablehlo.dynamic_slice": 9,
               "stablehlo.dynamic_update_slice": 2, "stablehlo.reduce": 5,
               "stablehlo.reshape": 16, "stablehlo.return": 2,
               "stablehlo.select": 2, "stablehlo.slice": 6,
               "stablehlo.subtract": 2}
DECODE_OPS = {**COMMON, "call @argmax": 1, "stablehlo.add": 17,
              "stablehlo.broadcast_in_dim": 63, "stablehlo.compare": 9,
              "stablehlo.constant": 75, "stablehlo.dynamic_slice": 11,
              "stablehlo.dynamic_update_slice": 4, "stablehlo.or": 2,
              "stablehlo.reduce": 6, "stablehlo.reshape": 18,
              "stablehlo.return": 3, "stablehlo.select": 6,
              "stablehlo.slice": 5, "stablehlo.subtract": 1}


def _ops(lowered) -> dict:
    return dict(collections.Counter(re.findall(
        r"(stablehlo\.[a-z_]+|func\.call|call @\w+)", lowered.as_text())))


def test_internlm2_serving_outputs_and_programs_are_unchanged():
    cfg = dataclasses.replace(reduced(get_config("internlm2-1.8b")),
                              compute_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    pre = jax.jit(make_serve_prefill(cfg, serve_runtime("reference")))
    dec = jax.jit(make_serve_decode(cfg, serve_runtime("reference")))
    logits, caches, stats = pre(params, {"tokens": toks})
    assert stats == {}
    caches = extend_caches(caches, cfg, 2)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    nxt, step_logits, new_caches, stats = dec(params, tok, caches,
                                              jnp.int32(16))
    assert stats == {}
    h = hashlib.sha256()
    for a in [logits, nxt, step_logits] + jax.tree_util.tree_leaves(
            new_caches):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == DIGEST
    assert _ops(pre.lower(params, {"tokens": toks})) == PREFILL_OPS
    assert _ops(dec.lower(params, tok, caches, jnp.int32(16))) == DECODE_OPS
