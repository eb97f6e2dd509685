"""The latent-attention + MoE family's files, on the CPU at a tiny size:
the new cell's system, reference, weights, costs and readers, in the
manner of ``test_bench_run_cpu.py``."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs_axk1, run, weights_axk1
from benchmarks.evidence import Evidence
from benchmarks.readers import axk1_roofline, steplog_quantile
from benchmarks.systems import latent_moe_serving

from conftest import ROOT, load_data

CELL = "axk1-ep16.ragchat"


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "a.x-k1-ep16-d7.json")) as f:
        return json.load(f)


def _ctx(seed, seconds, tmp_path):
    return run.Context(load_data("tiny-axk1.json"),
                       load_data("tiny-chat.json"), {"rate_rps": 4.0}, 1,
                       seed, seconds, 0, jax.devices()[:1],
                       time.monotonic(), say=lambda s: print(s),
                       trace_dir=str(tmp_path / "trace"))


@pytest.fixture(scope="module")
def ragchat_result(tmp_path_factory):
    return run.run_cell(_ctx(2 ** 31 + 5, 2.0,
                             tmp_path_factory.mktemp("axk1")))


def test_new_cell_runs_and_is_correct(ragchat_result):
    res = ragchat_result
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 8
    assert res["evidence"].compiles_in_window == 0


def test_new_cell_metrics_read_from_data_files(ragchat_result,
                                               benchmark_json):
    ev = ragchat_result["evidence"]
    e2e = run.read_metrics(benchmark_json["end_to_end"], "e2e_metrics", ev,
                           CELL)
    assert set(e2e) == {"itl_p95_ms", "setup_s"}
    layer = run.read_metrics(benchmark_json["per_layer"], "layer_metrics",
                             ev, CELL)
    assert set(layer) <= {m["name"] for m in benchmark_json["per_layer"]
                          if CELL in m["workloads"]}
    # 3 layers x (16 + 8) numbers x 2 bytes: no expanded key or value;
    # the pools hold each row in one 128-lane tile (read from the arrays)
    assert layer["latent_cache_bytes_per_token"]["value"] == 144
    assert layer["cache_bytes_per_token"]["value"] == 3 * 128 * 2
    # the host's phases of a step, as the chat cell reads them
    for name in ("loop_gap", "admit", "pack", "launch"):
        assert layer[name + "_ms_per_step"]["value"] >= 0
    assert layer["h2d_kb_per_step"]["value"] > 0
    assert layer["compiles_in_window"]["value"] == 0
    assert layer["moe_assignments_held_mean"]["value"] > 0
    assert 0 < layer["moe_experts_touched_mean"]["value"] <= 12
    assert layer["moe_held_expert_max_p95"]["value"] >= 1
    # not traced: the roofline readers found nothing to read
    assert not [n for n in layer if "roofline" in n]
    # every valid token makes top-k assignments in each expert layer
    for s in ev.steps:
        if s["kind"] in ("mixed", "decode", "prefill"):
            tokens = s["decode_rows"] + s["prefill_chunk_tokens"]
            assert s["moe_assignments_total"] == tokens * 4 * 2
            assert s["moe_assignments_held"] <= s["moe_assignments_total"]


class _Altered:
    def __init__(self, req, vocab):
        self._req, self._vocab = req, vocab

    def stream(self, timeout=None):
        for chunk in self._req.stream(timeout=timeout):
            yield (np.asarray(chunk) + 1) % self._vocab

    def result(self, timeout=None):
        return self._req.result(timeout)


class _Broken(latent_moe_serving.System):
    def submit(self, ids, max_new):
        return _Altered(super().submit(ids, max_new),
                        int(self.config["vocab_size"]))


def test_altered_tokens_come_out_not_correct(tmp_path):
    res = run.run_cell(_ctx(11, 1.5, tmp_path),
                       system_mod=type("M", (), {"System": _Broken}))
    assert res["failed"] == 0 and res["correct"] is False


def test_configuration_pins_the_published_widths(benchmark_json):
    """Every width as published (A.X-K1 config.json); only depth, the
    experts held and the vocabulary's slice are cut."""
    cfg = _published()
    published = dict(
        hidden_size=7168, intermediate_size=18432, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_attention_heads=64, num_key_value_heads=64,
        moe_intermediate_size=2048, num_experts_per_tok=8,
        n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000,
        max_position_embeddings=131072, n_routed_experts_published=192,
        scoring_func="sigmoid", topk_method="none", n_group=8, topk_group=4)
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_scaling"] == dict(
        beta_fast=32, beta_slow=1, factor=32, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096, type="yarn")
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                     "num_hidden_layers", "vocab_size"]
    # the guide's floors: a period + >= 4 expert layers, >= 8 experts,
    # >= an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= 163840
    entry = next(c for c in benchmark_json["configs"]
                 if c["name"] == "a.x-k1-ep16-d7")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    dep = cfg["deployment"]
    assert (dep["max_batch"], dep["max_model_len"], dep["mp"]) == (16, 4096,
                                                                   1)


def test_costs_against_hand_counts():
    cfg = _published()
    # ISSUE 27's arithmetic: attention of a layer 101.1 M, an expert
    # 44.04 M, the router 1.4 M, the dense FFN 396.4 M
    assert costs_axk1.attention_params(cfg) == (
        7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
        + 8192 * 7168) == 101_122_048
    assert costs_axk1.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert costs_axk1.router_params(cfg) == 7168 * 192
    assert costs_axk1.dense_ffn_params(cfg) == 3 * 7168 * 18432
    assert costs_axk1.latent_row_bytes(cfg) == 1152
    fixed = (7 * 101_122_048 + 3 * 7168 * 18432
             + 6 * (7168 * 192 + 44_040_192))
    assert costs_axk1.fixed_params_per_token(cfg) == fixed
    # one decode row at context 1000: 64 heads x (576 + 512) x 2 a key
    att = costs_axk1.latent_attention_cost(cfg, 1000, 1, 1000)
    assert att["flops"] == 2 * 64 * 1088 * 1000
    assert att["bytes"] == 1000 * 1152 + 64 * 1088 * 2
    gmm = costs_axk1.grouped_matmul_cost(cfg, 10, 3)
    assert gmm["flops"] == 2 * 10 * 44_040_192
    assert gmm["bytes"] == (3 * 44_040_192 + 10 * (2 * 7168 + 3 * 2048)) * 2
    step = costs_axk1.step_cost(cfg, 5, 2, 1000, 990, 10, 3)
    assert step["flops"] == (2 * 5 * fixed + 7 * 2 * 64 * 1088 * 1000
                             + gmm["flops"] + 2 * 2 * 7168 * 20480)
    assert step["bytes"] == ((fixed + 7168 * 20480) * 2
                             + 7 * (995 * 1152 + 5 * 64 * 1088 * 2)
                             + gmm["bytes"])


def test_weights_one_call_equals_layer_by_layer():
    cfg = load_data("tiny-axk1.json")
    whole = weights_axk1.all_weights(cfg, 2 ** 31 + 9, jnp.bfloat16)
    assert len(whole["layers"]) == 3 and "router" not in whole["layers"][0]
    for i, lw in enumerate(whole["layers"]):
        again = weights_axk1.layer_weights(cfg, 2 ** 31 + 9, i)
        assert sorted(lw) == sorted(again)
        for k in lw:
            assert lw[k].dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(lw[k], np.float32),
                                          np.asarray(again[k], np.float32))
    assert whole["layers"][1]["e_gate"].shape == (6, 64, 32)
    assert whole["layers"][1]["router"].shape == (64, 16)
    # another share of the same deployment draws the same experts
    other = weights_axk1.layer_weights(
        dict(cfg, experts_held_first=6, n_routed_experts=4), 2 ** 31 + 9, 1)
    np.testing.assert_array_equal(
        np.asarray(other["e_up"][0], np.float32),
        np.asarray(whole["layers"][1]["e_up"][2], np.float32))


def _traced_evidence(steps, op_seconds, busy_s):
    return Evidence(
        config=_published(), traffic={}, cell={}, device_kind="TPU v5 lite",
        chips=1, setup_s=1.0, w0=0.0, w1=10.0, steps=steps,
        trace={"busy_s": busy_s, "window_s": 6.0, "t0": 0.0, "t1": 6.0,
               "op_seconds": op_seconds})


def test_roofline_readers_from_counters_and_kernel_seconds():
    step = dict(t=1.0, kind="decode", failed=False, decode_rows=8,
                prefill_chunk_tokens=0, emitted_tokens=8,
                attended_keys=16000, resident_tokens=16000,
                decode_keys=16000, moe_assignments_held=24,
                moe_experts_touched=20)
    ops = {"custom-call latent_paged_decode bf16[16,64,512]": 0.004,
           "custom-call moe_grouped_matmul bf16[512,2048]": 0.02,
           "fusion fusion bf16[16,64,7168]": 0.1}
    ev = _traced_evidence([step, dict(step, t=2.0)], ops, 0.2)
    cfg, pk = ev.config, 819e9
    # decode attention at 16000 keys is memory-bound: the cached rows
    least = 2 * 7 * (16000 * 1152 + 8 * 64 * 1088 * 2) / pk
    got = axk1_roofline.read(ev, "latent_decode", "latent_paged_decode")
    assert got == pytest.approx(100 * least / 0.004)
    gmm = costs_axk1.grouped_matmul_cost(cfg, 24, 20)
    got = axk1_roofline.read(ev, "grouped_matmul", "moe_grouped_matmul")
    assert got == pytest.approx(100 * 2 * gmm["bytes"] / pk / 0.02)
    whole = axk1_roofline.read(ev, "step")
    assert 0 < whole < 100
    # a program without the counters, a trace without the kernel, no trace
    bare = {k: v for k, v in step.items() if not k.startswith("moe_")}
    assert axk1_roofline.read(_traced_evidence([bare], ops, 0.2),
                              "step") is None
    assert axk1_roofline.read(ev, "latent_decode", "no_such_kernel") is None
    ev.trace = None
    assert axk1_roofline.read(ev, "step") is None
    assert steplog_quantile.read(ev, "moe_held_expert_max", 0.95) is None
    ev.steps = [dict(step, moe_held_expert_max=m) for m in (1, 2, 3, 4, 5)]
    assert steplog_quantile.read(ev, "moe_held_expert_max", 0.5) == 3
