"""The DeepSeek-V2 cell: its entry driver at a tiny size on the CPU (a sound
run correct; an altered served token and the lower-precision control not),
its per-layer readers on a small recorded trace, and its configuration
file against the program's published DeepSeek-V2."""
import argparse
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import loader, manifest
from bench.harness import trace as tr
from bench.harness.spans import WINDOW_SPAN

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
CELL = "deepseek-v2.replica-decode-2k"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _run(monkeypatch):
    import jax

    import bench.run as br
    m = {"configs": [{"name": "deepseek-v2", "source": "tiny",
                      "file": os.path.join(TINY, "deepseek-v2-tiny.json"),
                      "reduced": [], "why": "tiny"}],
         "workloads": [{"name": CELL, "traffic": "mla-moe-decode-tiny",
                        "chips": 1, "config": "deepseek-v2", "why": "tiny"}],
         "end_to_end": [{"name": n, "unit": "x", "better": "higher",
                         "bound": 0.1, "source": "host_clock"}
                        for n in ("serve_tokens_per_s", "ttft_ms",
                                  "setup_s")],
         "per_layer": []}
    monkeypatch.setattr(loader, "TRAFFIC_DIR", TINY)
    monkeypatch.setattr(loader, "LIMITS_DIR", os.path.join(TINY, "limits"))
    args = argparse.Namespace(workload=CELL, seed=2 ** 33 + 11, seconds=0.5,
                              trace=0)
    return br.execute(args, m, jax.devices("cpu")[:1])


def test_sound_mla_moe_run_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def test_mla_moe_served_token_altered_is_not_correct(monkeypatch):
    import repro.launch.serve as serve
    greedy = serve.greedy_decode

    def altered(*a, **k):
        r = greedy(*a, **k)
        vocab = a[2].vocab_size
        r["tokens"] = (r["tokens"] + vocab // 2) % vocab
        return r

    monkeypatch.setattr(serve, "greedy_decode", altered)
    res = _run(monkeypatch)
    assert not res["correct"], res["checks"]


def test_mla_moe_swapped_routing_is_not_correct(monkeypatch):
    """A program that routes each token to its (k+1)-th expert in place of
    its k-th, and reports that routing, fails ``misrouted_margin``."""
    from bench.calibrate_mla_moe import swapped_route
    from repro.models import moe
    monkeypatch.setattr(moe, "route", swapped_route(moe.route))
    res = _run(monkeypatch)
    assert not res["correct"]
    margin = res["checks"]["misrouted_margin"]
    assert margin["value"] > margin["limit"]


def test_mla_moe_wrong_held_group_is_not_correct(monkeypatch):
    """A program holding the experts of another routing group than the
    reference's is not correct."""
    import dataclasses

    from repro.models import moe
    serve = moe.moe_serve

    def other_group(params, x, *, cfg, runtime=None):
        mo = dataclasses.replace(
            cfg.moe, expert_offset=cfg.moe.expert_offset + cfg.moe.n_experts)
        return serve(params, x, cfg=dataclasses.replace(cfg, moe=mo),
                     runtime=runtime)

    monkeypatch.setattr(moe, "moe_serve", other_group)
    res = _run(monkeypatch)
    assert not res["correct"], res["checks"]


def _control_readings():
    """Readings of the float32 reference's own greedy tokens and routing
    as the sound run, and of the two controls.  Four experts a token from
    3 groups of 2: with no more experts a token than groups, the group
    limit would change nothing."""
    import jax

    from bench.drivers.replica_serving_mla_moe import readings
    from bench.reference import deepseek_v2 as ref
    cfg = dict(_json(TINY, "deepseek-v2-tiny.json"), num_experts_per_tok=4)
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg["vocab_size"], (4, 32)).astype(np.int32)
    seq = prompt
    for _ in range(8):                 # greedy tokens of the f32 reference
        nxt = np.asarray(ref.logits(params, jax.numpy.asarray(seq), cfg,
                                    last=1))[:, 0].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], 1)
    held = ref.forward(params, jax.numpy.asarray(seq[:, :-1]), cfg,
                       last=1)[1]["held"]
    return readings(params, cfg, prompt, seq[:, 32:], np.asarray(held), 8,
                    _limit("misrouted_margin"))


@pytest.fixture(scope="module")
def control_readings():
    return _control_readings()


def _limit(name):
    return _json(TINY, "limits", f"{CELL}.json")["numbers"][name]["limit"]


def test_fp8_control_fails_the_limit(control_readings):
    """The reference with fp8 operands puts other tokens first than the
    float32 reference does; the float32 reference's own tokens and
    routing read zero."""
    sound, notes = control_readings["sound"]
    assert sound == {"served_logit_gap": 0.0, "misrouted_margin": 0.0}
    assert 0.0 <= notes["near_boundary_share"] < 1.0
    fp8, _ = control_readings["fp8_control"]
    assert fp8["served_logit_gap"] > _limit("served_logit_gap")


def test_plain_topk_control_fails_the_routing_limit(control_readings):
    """Routing by plain top-k, without the group limit, reaches held
    experts that group-limited routing leaves out by wide margins."""
    greedy, notes = control_readings["greedy_control"]
    assert greedy["misrouted_margin"] > 100 * _limit("misrouted_margin")
    assert notes["rerouted_share"] > 0


def test_replayed_rows_fit_the_free_memory():
    from bench.drivers.replica_serving_mla_moe import (BASE_BYTES, MIN_ROWS,
                                                       ROW_BYTES, replay_rows)

    class Dev:
        def __init__(self, free):
            self.free = free

        def memory_stats(self):
            return {"bytes_limit": 16.9e9,
                    "bytes_in_use": 16.9e9 - self.free}

    rng = np.random.default_rng(1)
    rows = replay_rows([Dev(BASE_BYTES + 6.5 * ROW_BYTES)], 16, rng)
    assert len(rows) == 6 and list(rows) == sorted(set(rows))
    assert len(replay_rows([Dev(1e9)], 16, rng)) == MIN_ROWS
    assert len(replay_rows([Dev(1e12)], 16, rng)) == 16
    assert len(replay_rows([SimpleNamespace(memory_stats=lambda: None)], 16,
                           rng)) == 16


# -- per-layer readers ---------------------------------------------------------


def _cfg():
    return loader.config(manifest.config_entry(manifest.load(),
                                               "deepseek-v2"))


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace():
    """Two grouped-matmul kernels as a v5e trace names them, an op that
    reads one's result, two decode programs, and a prefill program with
    two flash-attention kernels."""
    ms = 1e6
    ev = tr.TraceEvents()
    dev = "/device:TPU:0"
    gmm = ('%dagafl_moe_gmm.4 = bf16[400,1536]{1,0} custom-call(s32[25]{0} '
           '%p, bf16[400,5120]{1,0} %g), custom_call_target="tpu_custom_call"')
    gmm2 = ('%dagafl_moe_gmm.7 = bf16[400,5120]{1,0} custom-call(s32[25]{0} '
            '%p), custom_call_target="tpu_custom_call"')
    reader = ('%fusion.12 = bf16[16,5120]{1,0} fusion(bf16[400,5120]{1,0} '
              '%dagafl_moe_gmm.7), kind=kLoop')
    flash = ('%dagafl_flash_attention.2 = bf16[16,128,2048,192]{3,2,1,0} '
             'custom-call(bf16[16,128,2048,192]{3,2,1,0} %q), '
             'custom_call_target="tpu_custom_call"')
    ev.ops[dev] = [(gmm, 0, 1 * ms), (gmm2, 1 * ms, 0.5 * ms),
                   (reader, 1.5 * ms, 0.25 * ms), (gmm, 3 * ms, 1 * ms),
                   (flash, 5 * ms, 2 * ms), (flash, 7 * ms, 1 * ms)]
    ev.modules[dev] = [("jit_serve_decode(7)", 0, 2 * ms),
                       ("jit_serve_decode(7)", 3 * ms, 2 * ms),
                       ("jit_serve_prefill(3)", 5 * ms, 4 * ms)]
    ev.host = [(WINDOW_SPAN, 0.0, 10 * ms)]
    return tr.reduce_events(ev)


def _read(name, **r):
    base = dict(trace=None, raw={}, config=_cfg(), peaks=PEAKS)
    base.update(r)
    return loader.metric_reader(name).read(SimpleNamespace(**base))


def test_expert_roofline_on_a_recorded_trace():
    cfg = _cfg()
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    raw = {"counters": {"dagafl.moe_routed_rows": 48,
                        "dagafl.moe_experts_hit": 36}}
    # 48 rows hitting 36 (expert, layer) pairs: the weights bound it
    nbytes = 2.0 * (3 * d * f * 36 + 2 * d * 48)
    assert 6.0 * d * f * 48 / 197e12 < nbytes / 819e9
    want = 100.0 * (nbytes / 819e9) / 2.5e-3
    got = _read("moe_expert_roofline.serve", trace=_trace(), raw=raw)
    assert got == pytest.approx(want)


def test_decode_roofline_on_a_recorded_trace():
    cfg = _cfg()
    mod = loader.metric_reader("moe_decode_roofline.serve")
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    raw = {"queries": 1, "new_tokens": 3, "batch": 16, "prompt_len": 2048,
           "decode_experts_hit": 72}
    # by hand: 746 M attention weights, 188.7 M of dense MLP, 4 x (0.82 M
    # router + 47.2 M shared), the 65.5 M head, 16 embedding rows; 36
    # experts a step; the latent cache at 2049.5 tokens
    attn = (5120 * 1536 + 1536 + 1536 * 128 * 192 + 5120 * 576 + 512
            + 512 * 128 * 256 + 128 * 128 * 5120 + 2 * 5120)
    weights = (5 * attn + 3 * 5120 * 12288 + 4 * (5120 * 160 + 3 * 5120 * 3072)
               + 5120 * 12800 + 5120 + 16 * 5120)
    assert mod.non_expert_params(cfg, 16) == weights
    nbytes = 2.0 * (weights + 3 * d * f * 36 + 5 * 16 * 2049.5 * 576)
    want = 100.0 * (nbytes / 819e9) / (4e-3 / 2)
    got = _read("moe_decode_roofline.serve", trace=_trace(), raw=raw)
    assert got == pytest.approx(want)


def test_mla_flash_attention_roofline_on_a_recorded_trace():
    """MLA's queries and keys are 192 wide and its values 128: at 2,048
    tokens the kernel is bound by FLOPs."""
    raw = {"queries": 1, "batch": 16, "prompt_len": 2048}
    # by hand: 16 x 128 heads x 2,098,176 causal pairs x 2 x (192 + 128)
    # FLOPs a layer, 5 layers; 3 ms of the two flash ops
    flops = 16 * 128 * 2048 * 2049 / 2 * 2 * 320 * 5
    assert flops == pytest.approx(1.3751e13, rel=1e-4)
    want = 100.0 * (flops / 197e12) / 3e-3
    got = _read("mla_flash_attention_roofline.serve", trace=_trace(),
                raw=raw)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", ["moe_expert_roofline.serve",
                                  "moe_decode_roofline.serve",
                                  "mla_flash_attention_roofline.serve"])
def test_readers_find_nothing_without_a_trace_or_counts(name):
    """A parent without the counters, or a run without a trace, reports
    nothing and raises nothing."""
    assert _read(name) is None
    assert _read(name, trace=_trace(), raw={"queries": 2}) is None
    t = _trace()
    t["ops"], t["modules"] = {}, {}
    raw = {"queries": 1, "new_tokens": 3, "batch": 16, "prompt_len": 8,
           "decode_experts_hit": 3,
           "counters": {"dagafl.moe_routed_rows": 4,
                        "dagafl.moe_experts_hit": 3}}
    assert _read(name, trace=t, raw=raw) is None


# -- the configuration -------------------------------------------------------


def test_configuration_keeps_the_published_widths():
    """Every width of the cell's file is DeepSeek-V2's as the program's
    registry states it; only depth, experts held and vocabulary are cut."""
    from bench.drivers.replica_serving_mla_moe import program_config
    from repro.configs import get_config
    cfg = _cfg()
    m = manifest.load()
    entry = manifest.config_entry(m, "deepseek-v2")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert {k: cfg["published"][k] for k in entry["reduced"]} == {
        "num_hidden_layers": 60, "n_routed_experts": 160,
        "vocab_size": 102400}
    pub = get_config("deepseek-v2-236b")
    got = program_config(cfg)
    assert (got.d_model, got.n_heads, got.mla, got.yarn) == \
        (pub.d_model, pub.n_heads, pub.mla, pub.yarn)
    assert got.stages[0].pattern[0].d_ff == 12288 == \
        pub.stages[0].pattern[0].d_ff
    for k in ("top_k", "d_expert", "n_shared", "n_group", "topk_group",
              "norm_topk", "routed_scale"):
        assert getattr(got.moe, k) == getattr(pub.moe, k), k
    assert got.moe.router_width == pub.moe.n_experts == 160
    assert got.moe.n_experts * cfg["n_group"] == 160
    assert got.vocab_size * 8 == pub.vocab_size
    assert cfg["parameters"] == sum(
        int(np.prod(s)) for s in _leaves(cfg))


def _leaves(cfg):
    import jax

    from bench.reference import deepseek_v2 as ref
    return jax.tree_util.tree_leaves(
        ref.shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))


def test_tiny_file_matches_the_cell():
    """The tiny configuration changes only sizes and dtypes of the
    cell's file."""
    tiny = _json(TINY, "deepseek-v2-tiny.json")
    real = _cfg()
    assert tiny["driver"] == real["driver"]
    assert set(real) - {"assumed", "deployment", "parameters",
                        "published"} <= set(tiny)
    assert tiny["vocab_size"] * 8 >= 256 and tiny["router_experts"] == 16


def test_the_cell_reports_its_metrics():
    m = manifest.load()
    names = {x["name"] for x in manifest.per_layer_for(m, CELL)}
    assert names == {"decode_step_ms.serve", "device_idle.serve",
                     "moe_expert_roofline.serve",
                     "moe_decode_roofline.serve",
                     "mla_flash_attention_roofline.serve"}
    assert {x["name"] for x in manifest.end_to_end_for(m, CELL)} == {
        "serve_tokens_per_s", "ttft_ms", "setup_s"}
    assert manifest.workload(m, CELL)["chips"] == 1
