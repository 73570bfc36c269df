"""DeepSeek-V2 through the serving path at a small DeepSeek-shaped size, on
seeded random weights, against the plain float32 reference
(bench/reference/deepseek_v2.py): MLA with YaRN, group-limited routing
over 16 experts in 8 groups of which the layer holds one, and the expert
layer's grouped matmul.

Tolerances: program and reference both compute in float32 here; they
differ only in the order of sums (absorbed against expanded attention,
grouped against per-expert matmuls, blocked softmax), which moves a logit
by 6e-7 of the largest logit.  ``LOGIT_TOL`` leaves two decades above
that; a bfloat16 forward misses it by two (1.2e-2, the last test)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.replica_serving_mla_moe import program_config
from bench.reference import deepseek_v2 as ref
from repro.configs import get_config
from repro.kernels import ops as kops
from repro.launch.serve import extend_caches, greedy_decode, make_serving_fns
from repro.models import attention
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.layers import yarn_freqs, yarn_scales
from repro.runtime import serve_runtime

LOGIT_TOL = 1e-4

CFG = {
    "name": "deepseek-v2-small", "source": "test", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 8, "q_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_group": 8, "topk_group": 3, "num_experts_per_tok": 2,
    "n_routed_experts": 2, "router_experts": 16, "held_group": 0,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "routed_scaling_factor": 16, "scoring_func": "softmax",
    "topk_method": "group_limited_greedy", "num_hidden_layers": 3,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False, "vocab_size": 128,
    "param_dtype": "float32", "compute_dtype": "float32",
    "cache_dtype": "float32",
}


def _cfg(**kw):
    return dict(CFG, **kw)


@pytest.fixture(scope="module")
def world():
    params = jax.jit(lambda k: ref.init(k, CFG))(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0,
                              CFG["vocab_size"], dtype=jnp.int32)
    want = np.asarray(ref.logits(params, toks, CFG, last=12))
    return params, toks, want


def _scale(want):
    return float(np.max(np.abs(want)))


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("policy", ["reference", "interpret"])
def test_prefill_logits_match_the_reference(world, policy, chunked,
                                            monkeypatch):
    """Chunked: the MLA expansion a row at a time and the expert layer 8
    tokens at a time, as a 16 x 2048 prefill runs them."""
    params, toks, want = world
    if chunked:
        monkeypatch.setattr(attention, "_MLA_TOKENS", 12)
        monkeypatch.setattr(moe_mod, "_SERVE_CHUNK", 8)
    arch = program_config(CFG)
    logits, _, _ = T.prefill(params, {"tokens": toks}, arch,
                             serve_runtime(policy))
    np.testing.assert_allclose(np.asarray(logits), want[:, -1],
                               atol=LOGIT_TOL * _scale(want), rtol=0)


@pytest.mark.parametrize("policy", ["reference", "interpret"])
def test_prefill_then_latent_cache_decode_match_the_reference(world, policy):
    """Prefill 8 tokens, then decode the other 4 through the ckv/krope
    cache (the absorbed form): every step's logits are the reference's
    full forward at that position."""
    params, toks, want = world
    arch = program_config(CFG)
    rt = serve_runtime(policy)
    logits, caches, _ = T.prefill(params, {"tokens": toks[:, :8]}, arch, rt)
    caches = extend_caches(caches, arch, 4)
    assert set(caches[1]["l0"]) == {"ckv", "krope"}
    assert caches[1]["l0"]["ckv"].shape[-1] == CFG["kv_lora_rank"]
    got = [np.asarray(logits)]
    for i in range(8, 11):
        logits, caches, stats = T.decode_step(
            params, toks[:, i:i + 1], caches, jnp.int32(i), arch, rt)
        got.append(np.asarray(logits))
        assert stats["moe_routed_rows"].shape == (2,)
    np.testing.assert_allclose(np.stack(got, 1), want[:, 7:11],
                               atol=LOGIT_TOL * _scale(want), rtol=0)


def test_served_routing_is_the_references(world):
    """``greedy_decode`` returns each position's held experts per expert
    layer, prefill and decode steps in order; in float32 they are the
    reference's own routing of the served sequence, bit for bit."""
    params, toks, _ = world
    arch = program_config(CFG)
    prefill, decode = make_serving_fns(arch, serve_runtime("interpret"))
    r = greedy_decode(prefill, decode, arch, params, {"tokens": toks[:, :8]},
                      4)
    held = np.asarray(r["moe"]["held"])
    seq = jnp.concatenate([toks[:, :8], r["tokens"][:, :-1]], axis=1)
    want = np.asarray(ref.forward(params, seq, CFG, last=1)[1]["held"])
    assert held.shape == want.shape == (2, 2, 11, 2)
    np.testing.assert_array_equal(held, want)
    assert held.any()
    _, routing = ref.forward(params, seq, CFG, last=1, held=held)
    assert float(jnp.max(routing["misrouted"])) == 0.0


def _moe_params(params):
    return jax.tree_util.tree_map(lambda a: a[0],
                                  params["stages"][1]["l0"]["ffn"])


def _group_layer(full, g, per):
    """The share of expert layer ``full`` that group ``g`` holds."""
    p = dict(full)
    for k in ("we_gate", "we_up", "we_down"):
        p[k] = full[k][g * per:(g + 1) * per]
    return p


def test_group_shares_add_up_to_the_uncut_layer():
    """Each of the 8 groups' layers, holding only its 2 experts, gives its
    part; with the shared experts (which every chip computes alike)
    counted once, the parts add up to the reference's whole layer."""
    uncut = _cfg(n_routed_experts=16)
    params = jax.jit(lambda k: ref.init(k, uncut))(jax.random.PRNGKey(5))
    full = _moe_params(params)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 7, 64))
    total = 0.0
    sh, xs = full["shared"], x.reshape(-1, 64)
    shared = np.asarray((jax.nn.silu(xs @ sh["wg"]) * (xs @ sh["wi"]))
                        @ sh["wdown"])
    for g in range(8):
        arch = program_config(_cfg(held_group=g))
        out, stats = moe_mod.moe_serve(_group_layer(full, g, 2), x, cfg=arch,
                                       runtime=serve_runtime("interpret"))
        total = total + np.asarray(out).reshape(-1, 64) - shared
    total = total + shared
    want = np.asarray(ref.experts(full, x.reshape(-1, 64), uncut)[0])
    np.testing.assert_allclose(total, want, atol=1e-4 * np.abs(want).max(),
                               rtol=0)
    arch = program_config(uncut)
    whole, _ = moe_mod.moe_serve(full, x, cfg=arch)
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, 64), want,
                               atol=1e-4 * np.abs(want).max(), rtol=0)


def test_a_router_forced_onto_one_expert_drops_no_row():
    """Every token's first choice is held expert 0: all 64 rows of it are
    computed (a capacity of 1.25x the mean load would drop most)."""
    params = jax.jit(lambda k: ref.init(k, CFG))(jax.random.PRNGKey(7))
    p = _moe_params(params)
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(1e3)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 16, 64))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    arch = program_config(CFG)
    out, stats = moe_mod.moe_serve(p, x, cfg=arch,
                                   runtime=serve_runtime("interpret"))
    idx, _ = moe_mod.route(x.reshape(-1, 64) @ p["router"], arch.moe)
    assert bool(jnp.all(idx[:, 0] == 0))
    assert int(stats["moe_routed_rows"]) == int(jnp.sum(idx < 2)) >= 64
    want = np.asarray(ref.experts(p, x.reshape(-1, 64), CFG)[0])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 64), want,
                               atol=1e-4 * np.abs(want).max(), rtol=0)


def test_held_routing_for_any_number_of_held_experts():
    """A layer holding 32 experts (a group of 32 of 256) reports each
    token's held experts as the reference routes them."""
    cfg = _cfg(router_experts=256, n_routed_experts=32)
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 4, 64))
    p = _moe_params(params)
    _, stats = moe_mod.moe_serve(p, x, cfg=program_config(cfg))
    assert set(stats) == {"moe_routed_rows", "moe_experts_hit", "moe_held"}
    held = np.asarray(stats["moe_held"])
    want = np.asarray(ref.experts(p, x.reshape(-1, 64), cfg)[1]["held"])
    assert held.shape == (2, 4, 32)
    np.testing.assert_array_equal(held.reshape(-1, 32), want)
    assert int(stats["moe_routed_rows"]) == held.sum()


@pytest.mark.parametrize("seed", range(6))
def test_routing_stays_inside_each_tokens_best_groups(seed):
    mo = program_config(CFG).moe
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (256, 16))
    idx, gates = moe_mod.route(logits, mo)
    scores = np.asarray(jax.nn.softmax(logits, -1))
    best = np.argsort(-scores.reshape(256, 8, 2).max(-1), axis=1)[:, :3]
    groups = np.asarray(idx) // 2
    assert all(set(g) <= set(b) for g, b in zip(groups, best))
    # unnormalised gates times the scaling factor
    np.testing.assert_allclose(
        np.asarray(gates),
        16.0 * np.take_along_axis(scores, np.asarray(idx), 1), rtol=1e-6)
    dense = np.asarray(ref.route_gates(logits, CFG))
    np.testing.assert_allclose(
        np.take_along_axis(dense, np.asarray(idx), 1), np.asarray(gates),
        rtol=1e-6)


def test_yarn_frequencies_and_mscale_follow_the_published_formulas():
    """DeepSeek-V2: rotary dim 64, base 10000, factor 40 over 4096
    positions, beta 32 and 1: the correction range is dims 10..23 of the
    32 frequencies; mscale = 0.1 * 0.707 * ln 40 + 1."""
    yarn = get_config("deepseek-v2-236b").yarn
    got = yarn_freqs(64, 10000.0, yarn)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, base * (1 - ramp) + base / 40 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    rot, soft = yarn_scales(yarn)
    assert rot == pytest.approx(1.0) and soft == pytest.approx(m * m)
    big = dict(CFG, qk_rope_head_dim=64)
    np.testing.assert_allclose(ref.yarn_inv_freq(big), got, rtol=1e-6)
    assert ref.softmax_scale(dict(big, qk_nope_head_dim=128)) == \
        pytest.approx(m * m / math.sqrt(192))


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("policy", ["reference", "interpret"])
def test_moe_gmm_matches_per_expert_einsums(policy, stacked):
    """Rows sorted and tile-aligned by expert (16-row tiles), one expert
    holding none: each group's rows times its expert's matrix, the experts
    given alone or as layer 1 of a stack of layers."""
    sizes = [32, 0, 16, 48]
    e, k, n = len(sizes), 128, 256
    lhs = jax.random.normal(jax.random.PRNGKey(9), (128, k))
    rhs = jax.random.normal(jax.random.PRNGKey(10), (e, k, n))
    w, layer = rhs, None
    if stacked:
        w, layer = jnp.stack([-rhs, rhs, 2 * rhs]), jnp.int32(1)
    got = np.asarray(kops.moe_gmm(lhs, w, jnp.asarray(sizes, jnp.int32),
                                  tm=16, layer=layer, policy=policy))
    start = 0
    for g, size in enumerate(sizes):
        want = jnp.einsum("rk,kn->rn", lhs[start:start + size], rhs[g],
                          precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_allclose(got[start:start + size], np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        start += size


def test_a_bfloat16_forward_misses_the_tolerance(world):
    """The comparison is tight enough to see bfloat16: the same weights
    served in bfloat16 miss ``LOGIT_TOL``."""
    params, toks, want = world
    arch = dataclasses.replace(program_config(CFG), compute_dtype="bfloat16",
                               param_dtype="bfloat16")
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    logits, _, _ = T.prefill(p16, {"tokens": toks}, arch,
                             serve_runtime("reference"))
    gap = np.max(np.abs(np.asarray(logits, np.float32) - want[:, -1]))
    assert gap > LOGIT_TOL * _scale(want)
