"""FLOP and byte counts against hand counts, and the table of peaks."""
import json
import os

import pytest

from bench.harness import counts, device
from bench.harness.manifest import BENCH_DIR


def _cfg(name):
    with open(os.path.join(BENCH_DIR, "configs", name)) as f:
        return json.load(f)


def test_vgg16_forward_flops_by_hand():
    c = _cfg("vgg16-cifar10.json")
    conv = 2 * 9 * (32 * 32 * (3 * 64 + 64 * 64)
                    + 16 * 16 * (64 * 128 + 128 * 128)
                    + 8 * 8 * (128 * 256 + 2 * 256 * 256)
                    + 4 * 4 * (256 * 512 + 2 * 512 * 512)
                    + 2 * 2 * (3 * 512 * 512))
    fc = 2 * (512 * 4096 + 4096 * 4096 + 4096 * 10)
    assert counts.vgg_forward_flops(c) == conv + fc
    assert counts.vgg_forward_flops(c) == pytest.approx(0.664e9, rel=1e-3)
    assert counts.vgg_train_flops_per_sample(c) == 3 * (conv + fc)


def test_vgg16_parameter_count():
    c = _cfg("vgg16-cifar10.json")
    assert counts.vgg_param_count(c) == c["parameters"] == 33638218


def test_internlm2_parameter_count_by_hand():
    c = _cfg("internlm2-1.8b.json")
    d, v = 2048, 92544
    layer = d * 2048 + 2 * d * 1024 + 2048 * d + 3 * d * 8192 + 2 * d
    assert counts.decoder_param_count(c) == 2 * v * d + 24 * layer + d
    assert counts.decoder_param_count(c) == c["parameters"]


def test_decode_bytes_and_flops():
    c = _cfg("internlm2-1.8b.json")
    w = counts.decoder_matmul_params(c)
    assert counts.decode_bytes(c, 1, 0) == 2 * (w + 2048)
    # one cached key and value per layer, bf16, 8 heads x 128
    assert counts.decode_bytes(c, 1, 1) - counts.decode_bytes(c, 1, 0) == \
        2 * 2 * 24 * 1024
    assert counts.decode_flops(c, 2, 10) == pytest.approx(
        2 * w * 2 + 4 * 2 * 16 * 128 * 10 * 24)


def test_causal_attention_counts_half_the_square():
    c = _cfg("internlm2-1.8b.json")
    full = counts.attention_flops(c, 1, 8, 8, causal=False)
    causal = counts.attention_flops(c, 1, 8, 8, causal=True)
    assert causal == pytest.approx(full * 36 / 64)
    flops, nbytes = counts.flash_attention_cost(c, 2, 16)
    assert flops == counts.attention_flops(c, 2, 16, 16, causal=True)
    assert nbytes == 2 * 2 * 16 * 128 * (2 * 16 + 2 * 8) * 24


def test_peaks_lookup():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v9 imaginary")
