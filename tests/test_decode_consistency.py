"""Serving correctness: prefill + decode_step == full forward, per arch;
plus frontier-replica decode parity (the live-traffic serving path must
produce bit-identical tokens to a direct Eq. 6 aggregation)."""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced
from repro.launch.serve import extend_caches
from repro.models import transformer as T
from repro.models.attention import cache_seq_axis


def _pad_caches(caches, cfg, extra=1):
    # the serving launcher's spec-driven helper IS the implementation under
    # test here: prefill-collected caches carry a stacked-layer leading axis
    return extend_caches(caches, cfg, extra)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch):
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              compute_dtype="float32")
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    B, S = 2, 16
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.encoder is not None:
        batch["enc_embed"] = jax.random.normal(
            key, (B, cfg.encoder.n_ctx, cfg.d_model)) * 0.1
    logits_full, _, _ = T.forward(params, batch, cfg, mode="prefill")
    bp = dict(batch)
    bp["tokens"] = toks[:, :S - 1]
    _, caches, _ = T.prefill(params, bp, cfg)
    caches = _pad_caches(caches, cfg)
    logits_dec, new_caches, _ = T.decode_step(
        params, toks[:, S - 1:S], caches, jnp.int32(S - 1), cfg)
    diff = float(jnp.max(jnp.abs(logits_dec - logits_full[:, -1])))
    assert diff < 2e-2, f"{arch}: decode diverges from full forward ({diff})"
    # cache structure is preserved
    assert jax.tree_util.tree_structure(new_caches) == \
        jax.tree_util.tree_structure(caches)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "jamba-v0.1-52b",
                                  "xlstm-125m"])
def test_multi_step_decode_tracks_full_forward(arch):
    """Decoding token-by-token stays close to teacher-forced full logits."""
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              compute_dtype="float32")
    key = jax.random.PRNGKey(7)
    params = T.init_params(key, cfg)
    B, S_prompt, n_new = 1, 8, 4
    S = S_prompt + n_new
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    # teacher-forced reference over the whole sequence
    logits_full, _, _ = T.forward(params, {"tokens": toks}, cfg,
                                  mode="prefill")
    # prefill prompt, then feed the same ground-truth tokens step by step
    _, caches, _ = T.prefill(params, {"tokens": toks[:, :S_prompt]}, cfg)
    caches = _pad_caches(caches, cfg, extra=n_new)
    for step in range(n_new):
        pos = S_prompt + step
        logits_dec, caches, _ = T.decode_step(
            params, toks[:, pos:pos + 1], caches, jnp.int32(pos), cfg)
        diff = float(jnp.max(jnp.abs(logits_dec - logits_full[:, pos])))
        assert diff < 2e-2, f"{arch} step {step}: {diff}"


# -- cache sequence-axis derivation ------------------------------------------


def test_cache_seq_axis_counts_from_trailing_end():
    """k/v caches keep (heads, head_dim) behind the sequence axis; latent
    ckv/krope caches keep one trailing dim — regardless of how many leading
    axes (batch, stacked layers) sit in front."""
    assert cache_seq_axis("k", 4) == 1          # (B, S, H, D)
    assert cache_seq_axis("v", 4) == 1
    assert cache_seq_axis("k", 5) == 2          # (L, B, S, H, D) stacked
    assert cache_seq_axis("v", 5) == 2
    assert cache_seq_axis("ckv", 3) == 1        # (B, S, d_latent)
    assert cache_seq_axis("krope", 3) == 1
    assert cache_seq_axis("ckv", 4) == 2        # (L, B, S, d_latent)
    assert cache_seq_axis("krope", 4) == 2


def test_extend_caches_pads_unstacked_layout_on_axis_1():
    """Regression for the old hardcoded ``pad[2]``: an UNSTACKED per-layer
    (B, S, H, D) cache entry must grow along axis 1 (its sequence axis) —
    padding axis 2 would silently corrupt the head axis instead."""
    fake_cfg = SimpleNamespace(stages=[
        SimpleNamespace(pattern=[SimpleNamespace(kind="attn")])])
    B, S, H, D = 2, 5, 3, 4
    caches = [{"l0": {"k": jnp.ones((B, S, H, D)),
                      "v": jnp.ones((B, S, H, D)),
                      "ckv": jnp.ones((B, S, 7))}}]
    out = extend_caches(caches, fake_cfg, extra=3)
    assert out[0]["l0"]["k"].shape == (B, S + 3, H, D)
    assert out[0]["l0"]["v"].shape == (B, S + 3, H, D)
    assert out[0]["l0"]["ckv"].shape == (B, S + 3, 7)
    # original sequence slots untouched, new slots zero
    np.testing.assert_array_equal(np.asarray(out[0]["l0"]["k"][:, :S]), 1.0)
    np.testing.assert_array_equal(np.asarray(out[0]["l0"]["k"][:, S:]), 0.0)


def test_extend_caches_pads_stacked_layout_on_axis_2():
    fake_cfg = SimpleNamespace(stages=[
        SimpleNamespace(pattern=[SimpleNamespace(kind="attn")])])
    L, B, S, H, D = 2, 1, 4, 2, 3
    caches = [{"l0": {"k": jnp.ones((L, B, S, H, D)),
                      "v": jnp.ones((L, B, S, H, D))}}]
    out = extend_caches(caches, fake_cfg, extra=2)
    assert out[0]["l0"]["k"].shape == (L, B, S + 2, H, D)
    assert out[0]["l0"]["v"].shape == (L, B, S + 2, H, D)


# -- frontier-replica decode parity (live-traffic serving) -------------------


def _tiny_lm_cfg():
    return dataclasses.replace(
        reduced(get_config("internlm2-1.8b"), d_model=64),
        vocab_size=128, compute_dtype="float32")


def _ledger_world(bounded: bool, cfg, n_models: int = 3):
    """A frontier of ``n_models`` distinct real LM param trees branching off
    genesis; the bounded variant also checkpoints (pruning genesis) so
    parity is exercised against a pruned ledger too."""
    from repro.core.dag import (BoundedDAGLedger, DAGLedger, ModelStore,
                                TxMetadata)
    store = ModelStore()
    ledger = (BoundedDAGLedger(evict_fn=lambda tx: store.evict(tx.model_ref))
              if bounded else DAGLedger())

    def meta(cid):
        return TxMetadata(client_id=cid, signature=(0.0,) * 16,
                          model_accuracy=0.5, current_epoch=0,
                          validation_node_id=cid)

    ref = store.put("genesis", T.init_params(jax.random.PRNGKey(99), cfg))
    ledger.add_genesis(meta(-1), 0.0, ref)
    g = ledger.genesis_id
    for c in range(n_models):
        ref = store.put(f"m{c}", T.init_params(jax.random.PRNGKey(c), cfg))
        ledger.add_transaction(meta(c), (g,), 1.0 + c, ref)
    if bounded:
        ledger.checkpoint(now=10.0)     # prunes genesis under the frontier
        assert ledger.n_pruned > 0 and "genesis" not in store
    return ledger, store


@pytest.mark.parametrize("policy", ["reference", "interpret", "auto"])
def test_replica_decode_parity_vs_direct_eq6(policy):
    """The tokens decoded from a published ServingReplica must be
    bit-identical to decoding from a directly-computed Eq. 6 aggregate over
    the same frontier — for bounded AND unbounded ledgers, under every
    kernel dispatch policy the serving path supports."""
    from repro.core.simulator import EventLoop
    from repro.fl.serving import (ConsensusPublisher, LMQueryDriver,
                                  consensus_over_refs, frontier_snapshot,
                                  trees_bitwise_equal)
    cfg = _tiny_lm_cfg()
    driver = LMQueryDriver(cfg, query_batch=2, prompt_len=6, new_tokens=4,
                           seed=0, kernel_policy=policy)
    prompts = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 6))
    for bounded in (False, True):
        ledger, store = _ledger_world(bounded, cfg)
        pub = ConsensusPublisher(ledger, store, EventLoop(), every=1.0)
        rep = pub.publish()
        _, refs = frontier_snapshot(ledger)
        assert rep.model_refs == refs and len(refs) == 3
        direct = consensus_over_refs(store, refs)
        assert trees_bitwise_equal(rep.params, direct)
        toks_replica = driver.decode_prompts(rep.params, prompts)
        toks_direct = driver.decode_prompts(direct, prompts)
        assert toks_replica.shape == (2, 4)
        np.testing.assert_array_equal(toks_replica, toks_direct)
