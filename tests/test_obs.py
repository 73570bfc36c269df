"""repro.obs: spans and counters of the program's own host work, and the
names the program gives its kernels on the device."""
import dataclasses
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


def test_inactive_span_records_nothing_and_costs_a_branch():
    rec = obs.Recorder()
    assert obs.span("dagafl.flush", window=1) is obs.span("dagafl.eq6")
    with obs.span("dagafl.flush", window=1):
        obs.count("dagafl.device_syncs")
    assert obs.fetch(jnp.ones(3)).tolist() == [1.0, 1.0, 1.0]
    assert not rec.calls and not rec.counters
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("dagafl.flush", window=1):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 20e-6      # a check and a shared null context


def test_nested_spans_add_up_calls_and_seconds_per_name():
    rec = obs.Recorder()
    with obs.recording(rec):
        with obs.span("dagafl.flush", window=3, rounds=2):
            time.sleep(0.02)
            for _ in range(2):
                with obs.span("dagafl.eq6", window=3):
                    time.sleep(0.01)
            with obs.span("dagafl.train", rounds=2):
                with obs.span("dagafl.sync"):
                    time.sleep(0.01)
    assert rec.calls == {"dagafl.flush": 1, "dagafl.eq6": 2,
                         "dagafl.train": 1, "dagafl.sync": 1}
    assert rec.seconds["dagafl.eq6"] >= 0.02
    assert rec.seconds["dagafl.train"] >= rec.seconds["dagafl.sync"] >= 0.01
    assert rec.seconds["dagafl.flush"] >= 0.02 + (
        rec.seconds["dagafl.eq6"] + rec.seconds["dagafl.train"])
    assert rec.names() == {"dagafl.flush", "dagafl.eq6", "dagafl.train",
                           "dagafl.sync"}


def test_counters_and_fetch():
    rec = obs.Recorder()
    with obs.recording(rec):
        obs.count("dagafl.example")
        obs.count("dagafl.example", 2)
        out = obs.fetch(jnp.arange(4))
    assert isinstance(out, np.ndarray) and out.tolist() == [0, 1, 2, 3]
    assert rec.counters == {"dagafl.example": 3, "dagafl.device_syncs": 1}
    assert rec.calls == {"dagafl.sync": 1}
    with obs.span("dagafl.sync"):
        obs.count("dagafl.device_syncs")      # after the block: ignored
    assert rec.counters["dagafl.device_syncs"] == 1


def test_other_threads_are_not_recorded():
    rec = obs.Recorder()

    def worker():
        with obs.span("dagafl.assembler_wait"):
            obs.fetch(jnp.ones(2))

    with obs.recording(rec):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with obs.span("dagafl.flush"):
            pass
    assert rec.names() == {"dagafl.flush"}
    assert not rec.counters


def test_profiler_trace_holds_the_spans_with_their_ids(tmp_path):
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    rec = obs.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    with obs.recording(rec):
        with obs.span("dagafl.flush", window=7, rounds=2):
            with obs.span("dagafl.front_half", client=3, epoch=5):
                obs.fetch(f(x))
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dagafl."):
                    found[e.name] = dict(e.stats)
    assert found["dagafl.flush"] == {"window": 7, "rounds": 2}
    assert found["dagafl.front_half"] == {"client": 3, "epoch": 5}
    assert "dagafl.sync" in found


def _serving_world(compute_dtype):
    from repro.configs import get_config, reduced
    from repro.launch.serve import make_serving_fns
    from repro.models import transformer as tfm
    from repro.runtime import serve_runtime
    cfg = dataclasses.replace(reduced(get_config("internlm2-1.8b")),
                              compute_dtype=compute_dtype, d_model=64,
                              vocab_size=128)
    prefill, decode = make_serving_fns(cfg, serve_runtime("interpret"))
    return cfg, prefill, decode, tfm


def test_greedy_decode_times_with_and_without_a_recorder():
    from repro.launch.serve import greedy_decode
    cfg, prefill, decode, tfm = _serving_world("float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab_size)}
    off = greedy_decode(prefill, decode, cfg, params, batch, 3)
    rec = obs.Recorder()
    with obs.recording(rec):
        on = greedy_decode(prefill, decode, cfg, params, batch, 3)
    assert set(on) == set(off)
    assert on["tokens"].tolist() == off["tokens"].tolist()
    for key in ("prefill_s", "decode_s"):
        assert isinstance(on[key], float) and on[key] > 0
    assert rec.calls == {"dagafl.serve_prefill": 1, "dagafl.serve_decode": 1}
    assert rec.seconds["dagafl.serve_prefill"] >= on["prefill_s"] - 1e-3
    assert rec.seconds["dagafl.serve_decode"] >= on["decode_s"] - 1e-3


def test_flash_attention_kernel_name_in_prefill_program():
    cfg, prefill, _, tfm = _serving_world("bfloat16")
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text = prefill.lower(params, batch).as_text(debug_info=True)
    assert "dagafl_flash_attention" in text


def test_signature_kernel_name_in_signature_program():
    from repro.configs.cnn import VGG_TINY
    from repro.fl.backend import CNNBackend
    be = CNNBackend(VGG_TINY, kernel_policy="interpret")
    params = jax.eval_shape(be.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((4, 16, 16, 1), jnp.float32)
    text = be._signature.lower(params, x).as_text(debug_info=True)
    assert "dagafl_signature" in text


def _cohort_run(recorder=None):
    from repro.configs.cnn import vgg_for
    from repro.core import DagAflConfig, DagAflCoordinator, TipSelectionConfig
    from repro.core.simulator import CostModel, make_profiles
    from repro.data import (make_benchmark_dataset, partition_dirichlet,
                            split_811)
    from repro.fl.backend import CNNBackend
    ds = make_benchmark_dataset("mnist", n_samples=600, seed=0)
    splits = split_811(ds)
    clients = []
    for p in partition_dirichlet(splits["train"], 3, beta=0.5, seed=0):
        s = split_811(p, seed=1)
        clients.append({"train": s["train"], "val": s["val"],
                        "test": s["test"]})
    cfg = DagAflConfig(n_clients=3, max_rounds=2, local_epochs=1,
                       tip=TipSelectionConfig(n_select=2), seed=0,
                       cohort_size=3, cohort_window=2.0, mesh=None,
                       ledger_checkpoint_every=2.0)
    coord = DagAflCoordinator(
        CNNBackend(vgg_for("mnist"), local_epochs=1, batch_size=32),
        clients, splits["test"], cfg, CostModel(local_epoch=2.0),
        make_profiles(3, 0.5, 0))
    if recorder is None:
        coord.run()
    else:
        with obs.recording(recorder):
            coord.run()
    return coord


def test_coordinator_records_its_round_path_and_keeps_its_results():
    rec = obs.Recorder()
    coord = _cohort_run(rec)
    plain = _cohort_run()
    hashes = [tx.tx_hash for tx in coord.ledger.transactions()]
    assert hashes == [tx.tx_hash for tx in plain.ledger.transactions()]
    calls = rec.calls
    assert all(name.startswith("dagafl.") for name in rec.names())
    assert calls["dagafl.flush"] == coord._flushes > 0
    assert calls["dagafl.front_half"] >= calls["dagafl.flush"]
    assert calls["dagafl.publish"] == calls["dagafl.ledger_append"] > 0
    assert rec.counters["dagafl.device_syncs"] == calls["dagafl.sync"]
    # one window assembly and one unstack per cohort window of two or more
    assert calls["dagafl.assembler_wait"] == calls["dagafl.unstack"] > 0
    # one engine sweep per batched tip validation, and run()'s final sweep
    # of the latest models
    assert calls["dagafl.eval_many"] == calls["dagafl.tip_validate"] + 1 > 1
    assert rec.seconds["dagafl.flush"] > (
        rec.seconds["dagafl.front_half"] + rec.seconds["dagafl.eq6"])
