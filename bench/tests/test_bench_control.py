"""The lower-precision controls come out not correct at a size a test run
holds: the VGG reference in bfloat16 put in the program's place, and the
decoder in fp8, read against the float32 reference by the runs' own
comparisons and held to the tiny configuration's limits (tiny/limits/)."""
import json
import os

import numpy as np
import pytest

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def _json(*parts):
    with open(os.path.join(TINY, *parts)) as f:
        return json.load(f)


def test_bfloat16_vgg_control_fails_a_limit():
    import jax
    import jax.numpy as jnp

    from bench.gen import images
    from bench.harness import checks
    from bench.reference import vgg as ref
    cfg = _json("vgg-tiny.json")
    limits = _json("limits", "vgg16.noniid.json")["numbers"]
    t = _json("noniid-tiny.json")
    clients, _ = images.client_world(t, cfg, content_seed=3)
    g = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(3))
    ds = clients[0]["train"]
    opt, bs = cfg["optimizer"], cfg["batch_size"]
    r, lr_, g0 = ref.train(g, ds.x, ds.y, 5, cfg, opt, bs,
                           keep_first_grads=True)
    g16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), g)
    c, lc, _ = ref.train(g, ds.x, ds.y, 5, cfg, opt, bs, dtype=jnp.bfloat16)
    keep = checks.moving_leaves(checks.norms(g0))
    change = checks.median_norm_gap(checks.delta_norms(c, g16),
                                    checks.delta_norms(r, g), keep)
    loss = checks.loss_gap(lc, lr_)
    assert change > limits["median_change_gap"]["limit"] or \
        loss > limits["loss_gap"]["limit"], (change, loss)


def test_fp8_decoder_control_fails_the_limit():
    import jax

    from bench.drivers.replica_serving import control_gap
    from bench.reference import decoder as ref
    cfg = _json("internlm2-tiny.json")
    limit = _json("limits", "internlm2-1.8b.replica-decode.json")[
        "numbers"]["served_logit_gap"]["limit"]
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg["vocab_size"], (4, 32)).astype(np.int32)
    tokens = np.zeros((4, 8), np.int32)
    seq = prompt
    for j in range(8):                 # greedy tokens of the f32 reference
        nxt = np.asarray(ref.logits(params, jax.numpy.asarray(seq), cfg,
                                    last=1))[:, 0].argmax(-1)
        tokens[:, j] = nxt
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], 1)
    assert control_gap(params, cfg, prompt, tokens, 8) > limit


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_decoder_reference_is_causal(quant):
    """Logits at a position do not depend on later tokens."""
    import jax

    from bench.reference import decoder as ref
    cfg = _json("internlm2-tiny.json")
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(5))
    a = np.arange(12, dtype=np.int32)[None] % cfg["vocab_size"]
    b = a.copy()
    b[0, -1] = 7
    la = np.asarray(ref.logits(params, a, cfg, last=12, quant=quant))
    lb = np.asarray(ref.logits(params, b, cfg, last=12, quant=quant))
    if quant is None:
        np.testing.assert_array_equal(la[:, :-1], lb[:, :-1])
    assert not np.array_equal(la[:, -1], lb[:, -1])
