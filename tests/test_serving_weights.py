"""The serving pair's compute-dtype copy of the weights
(``launch/serve.ServingWeights``).

* Serving through the pair is bitwise what the same jitted programs give
  when called directly on the float32 tree, for every architecture family;
* the copy casts exactly the leaves the model casts before use, and leaves
  read in float32 are the source's own objects;
* one cast per tree, reused while every leaf is the same array; a new tree
  casts again and releases the old copy; dropping the pair frees it;
* ``lower`` lowers the program the replica runs, with no cast of a float32
  weight left in it, and casts nothing.
"""
import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCH_IDS, get_config, reduced
from repro.configs.base import Stage
from repro.launch.serve import extend_caches, greedy_decode, make_serving_fns
from repro.models import transformer as T


def _cfg(arch: str, compute_dtype: str = "bfloat16"):
    cfg = reduced(get_config(arch), d_model=64)
    if arch == "xlstm-125m":          # reduced keeps mLSTM only; add sLSTM
        m, s = get_config(arch).stages[0].pattern[2:]
        cfg = dataclasses.replace(cfg, stages=(Stage((m, s), 1),))
    return dataclasses.replace(cfg, vocab_size=128, param_dtype="float32",
                               compute_dtype=compute_dtype)


def _batch(cfg, seed: int = 1):
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (2, 8), 0,
                                      cfg.vocab_size)}
    if cfg.encoder is not None:
        b["enc_embed"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1),
            (2, cfg.encoder.n_ctx, cfg.d_model)) * 0.1
    return b


def _leaf(tree, *path):
    for k in path:
        tree = tree[k]
    return tree


_FFN_WG = ("stages", 0, "l0", "ffn", "wg")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serving_pair_is_bitwise_the_direct_programs(arch):
    cfg = _cfg(arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    prefill, decode = make_serving_fns(cfg)
    got = greedy_decode(prefill, decode, cfg, params, batch, 4)
    # the parent's path: the same jitted programs on the float32 tree
    want = greedy_decode(prefill.jitted, decode.jitted, cfg, params, batch, 4)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))
    np.testing.assert_allclose(np.asarray(got["margins"]),
                               np.asarray(want["margins"]), rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(prefill(params, batch)[0]),
                               np.asarray(prefill.jitted(params, batch)[0]),
                               rtol=1e-6, atol=0)

    copy = prefill.weights(params)
    mask = jax.tree_util.tree_leaves(T.compute_weight_mask(params, cfg))
    src = jax.tree_util.tree_leaves(params)
    out = jax.tree_util.tree_leaves(copy)
    assert any(mask) and not all(mask)
    for a, b, m in zip(src, out, mask):
        if m:
            assert b.dtype == jnp.bfloat16 and b.shape == a.shape
        else:                         # read as stored: the same object
            assert b is a and b.dtype == jnp.float32


@pytest.mark.parametrize("arch,path", [
    ("internlm2-1.8b", ("final_norm", "scale")),
    ("internlm2-1.8b", ("stages", 0, "l0", "norm1", "scale")),
    ("jamba-v0.1-52b", ("stages", 0, "l0", "core", "dt_proj")),
    ("jamba-v0.1-52b", ("stages", 0, "l0", "core", "dt_bias")),
    ("xlstm-125m", ("stages", 0, "l1", "core", "r_gates")),
])
def test_float32_read_leaves_keep_their_dtype_in_the_copy(arch, path):
    cfg = _cfg(arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefill, _ = make_serving_fns(cfg)
    copy = prefill.weights(params)
    assert _leaf(copy, *path) is _leaf(params, *path)
    assert _leaf(copy, *path).dtype == jnp.float32


def test_one_cast_per_tree_and_reuse_after():
    cfg = _cfg("internlm2-1.8b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefill, decode = make_serving_fns(cfg)
    rec = obs.Recorder()
    with obs.recording(rec):
        a = greedy_decode(prefill, decode, cfg, params, _batch(cfg), 4)
        b = greedy_decode(prefill, decode, cfg, params, _batch(cfg), 4)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    assert rec.counters["dagafl.serve_weight_casts"] == 1
    # one prefill and three decode steps a query, less the one cast
    assert rec.counters["dagafl.serve_weight_reuses"] == 7
    assert rec.calls["dagafl.serve_cast"] == 1


def test_a_new_tree_casts_again_and_releases_the_old_copy():
    cfg = _cfg("internlm2-1.8b")
    prefill, decode = make_serving_fns(cfg)
    first = T.init_params(jax.random.PRNGKey(0), cfg)
    old_copy = weakref.ref(_leaf(prefill.weights(first), *_FFN_WG))
    old_source = weakref.ref(_leaf(first, *_FFN_WG))
    second = dict(first, embed=T.init_params(jax.random.PRNGKey(1),
                                             cfg)["embed"])
    rec = obs.Recorder()
    with obs.recording(rec):
        greedy_decode(prefill, decode, cfg, second, _batch(cfg), 3)
    assert rec.counters["dagafl.serve_weight_casts"] == 1
    assert rec.counters["dagafl.serve_weight_reuses"] == 2
    gc.collect()
    assert old_copy() is None
    # the pair keeps no source tree alive
    del first, second
    gc.collect()
    assert old_source() is None


def test_dropping_the_pair_frees_the_copy():
    cfg = _cfg("internlm2-1.8b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefill, decode = make_serving_fns(cfg)
    greedy_decode(prefill, decode, cfg, params, _batch(cfg), 3)
    held = weakref.ref(_leaf(prefill.weights(params), *_FFN_WG))
    assert held() is not None
    del prefill, decode
    gc.collect()
    assert held() is None


def test_float32_compute_never_casts():
    cfg = _cfg("internlm2-1.8b", compute_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefill, decode = make_serving_fns(cfg)
    rec = obs.Recorder()
    with obs.recording(rec):
        greedy_decode(prefill, decode, cfg, params, _batch(cfg), 3)
    assert prefill.weights(params) is params
    assert not rec.counters and "dagafl.serve_cast" not in rec.calls


def _f32_converts(text: str, shapes) -> list:
    """Lines of StableHLO ``text`` that convert a float32 tensor of one of
    ``shapes``."""
    types = {"tensor<" + "x".join(map(str, s)) + "xf32>" for s in shapes}
    return [ln for ln in text.splitlines() if "stablehlo.convert" in ln
            and any(f"({t})" in ln or f": {t} ->" in ln for t in types)]


def test_decode_lowering_casts_no_float32_weight():
    cfg = _cfg("internlm2-1.8b")
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    prefill, decode = make_serving_fns(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    caches = jax.eval_shape(lambda p, b: extend_caches(
        prefill(p, b)[1], cfg, 4), params, batch)
    args = (jax.ShapeDtypeStruct((2, 1), jnp.int32), caches,
            jax.ShapeDtypeStruct((), jnp.int32))
    mask = jax.tree_util.tree_leaves(T.compute_weight_mask(params, cfg))
    stack = [a.shape for a, m in zip(jax.tree_util.tree_leaves(params), mask)
             if m]
    shapes = stack + [s[1:] for s in stack if len(s) == 3]
    rec = obs.Recorder()
    with obs.recording(rec):
        text = decode.lower(params, *args).as_text()
    assert _f32_converts(decode.jitted.lower(params, *args).as_text(), shapes)
    assert not _f32_converts(text, shapes)
    assert not rec.counters           # lowering casts and holds nothing
    decode.lower(params, *args).compile()
