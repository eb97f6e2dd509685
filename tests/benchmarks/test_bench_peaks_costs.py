import json
import os

import pytest

from benchmarks import costs, peaks

from conftest import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


def test_known_chip_has_its_published_peaks():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", "", "NVIDIA H100"])
def test_unknown_chip_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)


def test_ernie_flops_per_position():
    cfg = _cfg("ernie-3.0-base-pretrain.json")
    got = costs.ernie_train_flops_per_position(cfg, 512)
    weights = 12 * (4 * 768 ** 2 + 2 * 768 * 3072) + 768 ** 2 + 768 * 40000
    assert got == pytest.approx(6 * weights + 3 * 12 * 4 * 512 * 768)
    assert 7.4e8 < got < 7.7e8


def test_mistral_step_is_bound_by_weight_bytes_when_nearly_empty():
    cfg = _cfg("mistral-7b-v0.1-d12.json")
    wb = costs.llama_weight_bytes(cfg)
    assert 5.4e9 < wb < 5.6e9            # 12 layers + head, bf16
    c = costs.llama_step_cost(cfg, new_tokens=10, sampled_rows=10,
                              context_tokens=3000,
                              kv_bytes_per_token_layer=4096,
                              resident_tokens=3000)
    least = costs.least_seconds(c, peaks.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(c["bytes"] / 819e9)
    # a step full of real tokens is bound by compute
    full = costs.llama_step_cost(cfg, 1024, 16, 1024 * 500, 4096, 8000)
    assert costs.least_seconds(
        full, peaks.peaks_for("TPU v5 lite"))["bound"] == "compute"
