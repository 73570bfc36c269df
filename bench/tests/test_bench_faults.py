"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU at a tiny size (bench/tests/tiny/), with one fault planted
in the program: a local step that returns its state unchanged, each step's
mean taken over half its batch, a published answer (validation accuracy,
Eq. 3 signature, a served token) altered where it is produced.  At this
size program and reference both run float32 on the CPU, so the limits are
the tiny configuration's own (tiny/limits/); a sound run held to them
comes out correct."""
import argparse
import json
import os

import numpy as np
import pytest

from bench.harness import loader

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def _manifest(cfg_file, workload, traffic, e2e):
    return {"configs": [{"name": workload.split(".")[0], "source": "tiny",
                         "file": os.path.join(TINY, cfg_file), "reduced": [],
                         "why": "tiny"}],
            "workloads": [{"name": workload, "traffic": traffic, "chips": 1,
                           "config": workload.split(".")[0], "why": "tiny"}],
            "end_to_end": [{"name": n, "unit": "x", "better": "higher",
                            "bound": 0.1, "source": "host_clock"}
                           for n in e2e + ["setup_s"]],
            "per_layer": []}


def _run(monkeypatch, m, workload, seconds):
    import jax

    import bench.run as br
    monkeypatch.setattr(loader, "TRAFFIC_DIR", TINY)
    monkeypatch.setattr(loader, "LIMITS_DIR", os.path.join(TINY, "limits"))
    args = argparse.Namespace(workload=workload, seed=2 ** 32 + 7,
                              seconds=seconds, trace=0)
    return br.execute(args, m, jax.devices("cpu")[:1])


def _vgg(monkeypatch):
    m = _manifest("vgg-tiny.json", "vgg16.noniid", "noniid-tiny",
                  ["rounds_per_s"])
    return _run(monkeypatch, m, "vgg16.noniid", 2.0)


def _unchanged(monkeypatch):
    from repro.fl.cohort import CohortBackend

    def keep(self, stacked, xb, yb, *rest):
        return stacked, np.zeros(xb.shape[:2], np.float32)

    monkeypatch.setattr(CohortBackend, "_train_impl", keep)
    monkeypatch.setattr(CohortBackend, "_train_uniform_impl", keep)


def _half_batch(monkeypatch):
    from repro.fl.cohort import CNNCohortPrograms
    loss = CNNCohortPrograms.loss

    def half(self, params, x, y):
        return loss(self, params, x[: len(x) // 2], y[: len(y) // 2])

    monkeypatch.setattr(CNNCohortPrograms, "loss", half)


def _accuracy_altered(monkeypatch):
    from repro.fl.cohort import CohortBackend
    ev = CohortBackend.evaluate_cohort_stacked

    def off(self, *a, **k):
        return [min(acc + 0.25, 1.0) if acc < 0.5 else acc - 0.25
                for acc in ev(self, *a, **k)]

    monkeypatch.setattr(CohortBackend, "evaluate_cohort_stacked", off)


def _signature_altered(monkeypatch):
    from repro.fl.cohort import CohortBackend
    sig = CohortBackend.signature_cohort_stacked

    def off(self, *a, **k):
        s = sig(self, *a, **k)
        return np.where(s > 0.5, s - 0.1, s + 0.1)

    monkeypatch.setattr(CohortBackend, "signature_cohort_stacked", off)


def test_sound_federated_run_is_correct(monkeypatch):
    res = _vgg(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


@pytest.mark.parametrize("fault", [_unchanged, _half_batch,
                                   _accuracy_altered, _signature_altered])
def test_federated_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _vgg(monkeypatch)
    assert not res["correct"], res["checks"]


def _serve(monkeypatch):
    m = _manifest("internlm2-tiny.json", "internlm2-1.8b.replica-decode",
                  "replica-decode-tiny", ["serve_tokens_per_s", "ttft_ms"])
    return _run(monkeypatch, m, "internlm2-1.8b.replica-decode", 0.5)


def test_sound_serving_run_is_correct(monkeypatch):
    res = _serve(monkeypatch)
    assert res["correct"], res["checks"]


def test_served_token_altered_is_not_correct(monkeypatch):
    import repro.launch.serve as serve
    greedy = serve.greedy_decode

    def altered(*a, **k):
        r = greedy(*a, **k)
        vocab = a[2].vocab_size
        r["tokens"] = (r["tokens"] + vocab // 2) % vocab
        return r

    monkeypatch.setattr(serve, "greedy_decode", altered)
    res = _serve(monkeypatch)
    assert not res["correct"], res["checks"]


def test_tiny_files_match_the_cells():
    """The tiny configurations change only sizes of the cells' files."""
    for tiny, real in (("vgg-tiny.json", "vgg16-cifar10.json"),
                       ("internlm2-tiny.json", "internlm2-1.8b.json")):
        with open(os.path.join(TINY, tiny)) as f:
            t = json.load(f)
        with open(os.path.join(loader.BENCH_DIR, "configs", real)) as f:
            r = json.load(f)
        assert t["driver"] == r["driver"]
        assert set(r) - {"assumed", "deployment", "parameters"} <= set(t)
