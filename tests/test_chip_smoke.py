"""chip_smoke.py rehearsed on the CPU at tiny sizes.

The script refuses any platform but a TPU, so these tests call its phase
functions directly, with the Pallas interpreter standing in for the
compiled kernels (no ``tpu_custom_call`` can appear off the chip).
"""
import dataclasses

import pytest

import chip_smoke
from repro.configs import get_config, reduced
from repro.configs.cnn import VGG_TINY


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_to_run_without_a_tpu(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert "platform 'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_phase_a_tiny_federation_kernels_match_reference():
    cfg = dataclasses.replace(VGG_TINY, in_channels=3)
    res = chip_smoke.phase_a(cfg, policy="interpret",
                             expect_custom_call=False, n_clients=8,
                             n_samples=800, max_rounds=1)
    assert all(res["checks"].values()), res["checks"]
    assert res["kernel_rounds"] == res["reference_rounds"] == 8
    assert res["published_signatures"] == 8
    assert res["kernel_cohorts"] >= 1


def test_phase_b_tiny_serving_kernels_match_reference():
    cfg = dataclasses.replace(reduced(get_config("internlm2-1.8b"),
                                      d_model=64), vocab_size=128)
    res = chip_smoke.phase_b(cfg, policy="interpret",
                             expect_custom_call=False, batch=2,
                             prompt_len=16, new_tokens=4)
    assert all(res["checks"].values()), res["checks"]
    assert res["signature_rows"] == [2, 64]
    assert res["logits_max_abs_diff"] <= res["logits_tol"]
