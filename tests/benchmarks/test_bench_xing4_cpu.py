"""The hyper-connected latent-attention + MoE family's files, on the CPU at
a tiny size: the cell's system, reference, weights, costs, readers, deck
and entries, in the manner of ``test_bench_axk1_cpu.py``."""
import collections
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import costs_axk1, costs_xing4, decks, run, weights_xing4
from benchmarks.evidence import Evidence
from benchmarks.generators import open_deck
from benchmarks.readers import (kernel_ms_per_step, steplog_quantile,
                                xing4_roofline)
from benchmarks.reference import xing4 as reference
from benchmarks.rng import SplitMix
from benchmarks.systems import xing4_serving

from conftest import ROOT, load_data

CELL, CONFIG = "xing4-d7.reasoning", "xing4.0-29b-a4b-d7"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _ctx(seed, seconds, tmp_path):
    return run.Context(load_data("tiny-xing4.json"),
                       load_data("tiny-chat.json"), {"rate_rps": 4.0}, 1,
                       seed, seconds, 0, jax.devices()[:1],
                       time.monotonic(), say=lambda s: print(s),
                       trace_dir=str(tmp_path / "trace"))


@pytest.fixture(scope="module")
def reasoning_result(tmp_path_factory):
    return run.run_cell(_ctx(2 ** 31 + 38, 2.0,
                             tmp_path_factory.mktemp("xing4")))


def test_new_cell_runs_and_is_correct(reasoning_result):
    res = reasoning_result
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 8
    assert res["evidence"].compiles_in_window == 0
    assert set(res["check"]) == {"widest_logit_gap", "logit_gap_p99"}


def test_new_cell_metrics_read_from_data_files(reasoning_result,
                                               benchmark_json):
    ev = reasoning_result["evidence"]
    e2e = run.read_metrics(benchmark_json["end_to_end"], "e2e_metrics", ev,
                           CELL)
    assert set(e2e) == {"itl_p95_ms", "setup_s"}
    layer = run.read_metrics(benchmark_json["per_layer"], "layer_metrics",
                             ev, CELL)
    listed = {m["name"] for m in benchmark_json["per_layer"]
              if CELL in m["workloads"]}
    assert layer and set(layer) <= listed
    # 4 layers x (16 + 8) numbers x 2 bytes, read from the arrays
    assert layer["latent_cache_bytes_per_token"]["value"] == 4 * 48
    # 4 streams x 64 x 2 bytes, read from the streams' array
    assert layer["residual_stream_bytes_per_token.xing4"]["value"] == 512
    assert 0 <= layer["mhc_col_sum_gap_max.xing4"]["value"] < 1e-3
    assert layer["compiles_in_window"]["value"] == 0
    assert layer["moe_assignments_held_mean"]["value"] > 0
    assert 0 < layer["moe_experts_touched_mean"]["value"] <= 2 * 8
    # the host's phases of a step, and their sum with the emit's
    phases = [layer[name + "_ms_per_step"]["value"]
              for name in ("loop_gap", "admit", "pack", "launch")]
    assert min(phases) >= 0
    assert sum(phases) <= layer["host_serial_ms_per_step"]["value"]
    assert layer["h2d_kb_per_step"]["value"] > 0
    # as stored: each layer's row in one 128-lane tile
    assert layer["cache_bytes_per_token"]["value"] == 4 * 128 * 2
    assert 0 < layer["token_slot_fill_share"]["value"] <= 100
    # not traced: what reads the trace found nothing to read
    assert not [n for n in layer if "roofline" in n or "mhc_maps_ms" in n
                or "device_idle" in n]
    for s in ev.steps:
        if s["kind"] in ("mixed", "decode", "prefill"):
            tokens = s["decode_rows"] + s["prefill_chunk_tokens"]
            # every expert is held: nothing routed is left out
            assert s["moe_assignments_total"] == tokens * 2 * 2
            assert s["moe_assignments_held"] == s["moe_assignments_total"]
            assert s["residual_streams"] == 4


def test_altered_tokens_come_out_not_correct(reasoning_result):
    """The same records with every served token shifted by one, through
    the same check: not correct."""
    import types

    from benchmarks import check_served

    cfg = load_data("tiny-xing4.json")
    records = [types.SimpleNamespace(
        index=r.index, prompt=r.prompt, prompt_len=r.prompt_len,
        tokens=[(t + 1) % cfg["vocab_size"] for t in r.tokens])
        for r in reasoning_result["evidence"].records]
    correct, compared = check_served.check(cfg, 2 ** 31 + 38, records,
                                           say=lambda s: None)
    assert correct is False
    value, limit = compared["widest_logit_gap"]
    assert value > 10 * limit


def test_configuration_is_the_catalog_row_but_for_its_depth(benchmark_json):
    """Every key of the source's ``config`` under its own name and value;
    ``num_hidden_layers`` alone is cut, to the guide's floors."""
    cfg = _published()
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert cfg["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert differs == ["num_hidden_layers"]
        assert row["config"]["num_hidden_layers"] == 40
    published = dict(
        hidden_size=3584, intermediate_size=9216, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_attention_heads=32, moe_intermediate_size=1024,
        n_routed_experts=64, num_experts_per_tok=4, n_shared_experts=1,
        first_k_dense_replace=2, routed_scaling_factor=2, vocab_size=131072,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, n_group=1,
        mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, topk_group=1,
        topk_method="noaux_tc", num_nextn_predict_layers=1,
        model_type="xing4_0")
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_scaling"]["factor"] == 64
    assert sorted(cfg["reduced"]) == ["num_hidden_layers"]
    # a whole period + >= 4 of the layers behind the leading dense ones
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    entry = next(c for c in benchmark_json["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert (dep["max_batch"], dep["max_model_len"], dep["mp"]) == (32, 4096,
                                                                   1)
    assert {"limit_logit_gap", "limit_logit_gap_p99"} <= set(cfg["check"])
    for word in ("residual_streams", "sinkhorn_order", "map_norm",
                 "num_nextn_predict_layers", "weights"):
        assert word in cfg["assumed"]


def test_the_program_builds_the_catalog_rows_config_abstractly():
    """Depth 40 as published, nothing on a device: 29.5 B parameters, 3.9 B
    of them active a token, as the model's name says."""
    from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                    LatentMoEForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters

    cfg = dict(_published(), num_hidden_layers=40)
    mcfg = LatentMoEConfig(**{k: v for k, v in cfg.items()
                              if k not in xing4_serving.NOT_MODEL_KEYS})
    with abstract_parameters():
        model = LatentMoEForCausalLM(mcfg)
    sizes = {n: math.prod(p._data.shape) for n, p in model.named_parameters()}
    total = sum(sizes.values())
    assert 29.4e9 < total < 29.6e9
    routed = sum(v for n, v in sizes.items() if ".mlp.experts.w_" in n)
    # a token passes 4 of 64 routed experts and looks one row of the
    # embedding up
    active = (total - routed * (1 - 4 / 64)
              - sizes["model.embed_tokens.weight"])
    assert 3.85e9 < active < 4.0e9
    assert not [n for n in sizes if "nextn" in n or "mtp" in n]
    # the benchmark's names cover every parameter of a layer of each kind
    for i in (0, 2):
        mine = {n for n in sizes if n.startswith(f"model.layers.{i}.")
                and not n.endswith("norm.weight")}
        assert mine == set(xing4_serving.program_names(cfg, i).values())


def test_costs_against_hand_counts():
    cfg = _published()
    # ISSUE 38's arithmetic: attention of a layer 28.41 M, an expert
    # 11.01 M, the dense FFN 99.09 M, the two maps of a layer 0.69 M
    assert costs_axk1.attention_params(cfg) == (
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
        + 4096 * 3584) == 28_409_856
    assert costs_axk1.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert costs_axk1.dense_ffn_params(cfg) == 3 * 3584 * 9216
    assert costs_axk1.router_params(cfg) == 3584 * 64
    assert costs_xing4.mhc_proj_params(cfg) == 14336 * 24
    assert 2 * costs_xing4.mhc_proj_params(cfg) == 688_128
    assert costs_axk1.latent_row_bytes(cfg) == 1152
    assert costs_xing4.residual_stream_bytes_per_token(cfg) == 28_672
    assert costs_xing4.sublayers(cfg) == 14
    # the maps of a token: 48 for the affine, 8 sigmoids of 4, 16 clamped
    # exps of 3, 20 rounds of two normalisations of 12 + 4 + 16
    maps = costs_xing4.mhc_maps_cost(cfg, 10)
    assert maps["flops"] == 10 * (48 + 32 + 48 + 20 * 2 * 32) == 14_080
    assert maps["bytes"] == (10 * 48 + 48) * 4
    streams = costs_xing4.residual_stream_cost(cfg, 10)
    assert streams["flops"] == 10 * (2 * 14336 * 24 + 2 * 14336
                                     + 2 * 4 * 14336 + 2 * 14336)
    assert streams["bytes"] == (14336 * 24 + 10 * 10 * 3584) * 2
    base = costs_axk1.step_cost(cfg, 5, 2, 1000, 990, 20, 18)
    step = costs_xing4.step_cost(cfg, 5, 2, 1000, 990, 20, 18)
    half = costs_xing4.residual_stream_cost(cfg, 5)
    assert step["flops"] == base["flops"] + 14 * (
        costs_xing4.mhc_maps_cost(cfg, 5)["flops"] + half["flops"])
    assert step["bytes"] == base["bytes"] + 14 * (
        costs_xing4.mhc_maps_cost(cfg, 5)["bytes"] + half["bytes"])
    # the weights a decode step reads whatever it routes: 2 dense layers,
    # attention and router and shared expert of 5, the head: 1.6 GB
    fixed = (7 * 28_409_856 + 2 * 3 * 3584 * 9216
             + 5 * (3584 * 64 + 11_010_048))
    assert costs_axk1.fixed_params_per_token(cfg) == fixed
    assert base["bytes"] > (fixed + 3584 * 131072) * 2


def test_weights_one_call_equals_layer_by_layer():
    cfg = load_data("tiny-xing4.json")
    seed = 2 ** 31 + 9
    whole = weights_xing4.all_weights(cfg, seed, jnp.bfloat16)
    assert len(whole["layers"]) == 4
    assert "router" not in whole["layers"][1]
    assert "e_bias" in whole["layers"][2]
    for i, lw in enumerate(whole["layers"]):
        again = weights_xing4.layer_weights(cfg, seed, i)
        assert sorted(lw) == sorted(again)
        for k in lw:
            small = k.endswith(("_alpha", "_bias")) or k == "e_bias"
            assert lw[k].dtype == (jnp.float32 if small else jnp.bfloat16)
            np.testing.assert_array_equal(np.asarray(lw[k], np.float32),
                                          np.asarray(again[k], np.float32))
    lw = whole["layers"][2]
    assert lw["hc_attn_phi"].shape == (4 * 64, 24)
    assert lw["hc_ffn_bias"].shape == (24,) and lw["e_bias"].shape == (8,)
    assert (np.asarray(lw["hc_attn_alpha"]) == 1).all()
    assert lw["e_gate"].shape == (8, 64, 32)
    # the two sub-layers' maps are drawn apart
    assert (np.asarray(lw["hc_attn_bias"])
            != np.asarray(lw["hc_ffn_bias"])).any()


def test_seeded_biases_move_the_maps_and_the_choice():
    """What the configuration's ``assumed`` says of the seeded biases, at
    the tiny size: ``H_res`` far from the identity and from the uniform
    matrix, and the router's bias changing the experts chosen for at least
    a fifth of the tokens."""
    cfg = load_data("tiny-xing4.json")
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         weights_xing4.layer_weights(cfg, 5, 2, jnp.float32).items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (200, 4 * 64))
    fhat = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    _, _, h_res = reference._maps(fhat @ w["hc_attn_phi"],
                                  w["hc_attn_alpha"], w["hc_attn_bias"],
                                  cfg, None)
    h_res = np.asarray(h_res)
    assert np.abs(h_res - np.eye(4)).mean() > 0.15
    assert np.abs(h_res - 0.25).mean() > 0.08
    y = jax.random.normal(jax.random.PRNGKey(1), (200, 64))
    s = np.asarray(jax.nn.sigmoid(y @ w["router"]))
    top = lambda v: np.sort(np.argsort(-v, axis=1)[:, :2], axis=1)
    changed = (top(s) != top(s + np.asarray(w["e_bias"]))).any(1)
    assert changed.mean() >= 0.2


def _traced_evidence(steps, op_seconds, busy_s, config=None):
    return Evidence(
        config=config or _published(), traffic={}, cell={},
        device_kind="TPU v5 lite", chips=1, setup_s=1.0, w0=0.0, w1=10.0,
        steps=steps,
        trace={"busy_s": busy_s, "window_s": 2.0, "t0": 0.0, "t1": 2.0,
               "op_seconds": op_seconds})


def test_readers_from_counters_and_kernel_seconds():
    step = dict(t=1.0, kind="decode", failed=False, decode_rows=20,
                prefill_chunk_tokens=0, emitted_tokens=20,
                attended_keys=16000, resident_tokens=16000,
                decode_keys=16000, moe_assignments_held=400,
                moe_experts_touched=240, mhc_col_sum_gap_max=0.02,
                residual_stream_bytes=28672)
    ops = {"custom-call mhc_maps f32[24,128]": 0.0003,
           "custom-call latent_paged_decode bf16[32,32,512]": 0.004,
           "fusion fusion bf16[64,4,3584]": 0.05}
    ev = _traced_evidence([step, dict(step, t=1.5, mhc_col_sum_gap_max=0.05)],
                          ops, 0.06)
    cfg = ev.config
    maps = costs_xing4.mhc_maps_cost(cfg, 20)
    # a few thousand operations on a few KB: whichever side bounds it, a
    # sliver of the launch's time
    least = 2 * 14 * max(maps["flops"] / 197e12, maps["bytes"] / 819e9)
    got = xing4_roofline.read(ev, "mhc_maps", "mhc_maps")
    assert got == pytest.approx(100 * least / 0.0003) and got < 1
    assert kernel_ms_per_step.read(ev, "mhc_maps") == pytest.approx(0.15)
    whole = xing4_roofline.read(ev, "step")
    assert 0 < whole < 100
    assert steplog_quantile.read(ev, "mhc_col_sum_gap_max", 1.0) == 0.05
    assert steplog_quantile.read(ev, "residual_stream_bytes", 0.5) == 28672
    # a program without the counters, a configuration without the streams,
    # a trace without the kernel, a trace in which nothing ran, no trace
    bare = {k: v for k, v in step.items()
            if not k.startswith(("moe_", "mhc_", "residual_"))}
    lacking = _traced_evidence([bare], ops, 0.06)
    assert xing4_roofline.read(lacking, "step") is None
    assert steplog_quantile.read(lacking, "mhc_col_sum_gap_max", 1.0) is None
    plain = {k: v for k, v in cfg.items() if k != "hc_mult"}
    assert xing4_roofline.read(_traced_evidence([step], ops, 0.06, plain),
                               "step") is None
    assert xing4_roofline.read(ev, "mhc_maps", "no_such_kernel") is None
    assert kernel_ms_per_step.read(ev, "no_such_kernel") is None
    empty = _traced_evidence([step], {}, 0.0)
    assert xing4_roofline.read(empty, "step") is None
    assert kernel_ms_per_step.read(empty, "mhc_maps") is None
    ev.trace = None
    assert xing4_roofline.read(ev, "mhc_maps", "mhc_maps") is None
    assert kernel_ms_per_step.read(ev, "mhc_maps") is None


def test_the_cell_s_deck_is_two_log_uniform_distributions(benchmark_json):
    here = os.path.join(ROOT, "benchmarks")
    traffic = json.load(open(os.path.join(here, "traffic", "reasoning.json")))
    cell = json.load(open(os.path.join(here, "cells", CELL + ".json")))
    assert traffic["prompt_len"] == {"kind": "loguniform", "lo": 64,
                                     "hi": 512}
    assert traffic["output_len"] == {"kind": "loguniform", "lo": 256,
                                     "hi": 1024}
    assert traffic["generator"] == "open_deck"
    assert (traffic["drain_s"], traffic["trace_s"]) == (30.0, 2.0)
    assert 30.0 <= traffic["ramp_s"] <= 40.0
    seconds = float(benchmark_json["run_seconds"])
    n = int(cell["rate_rps"] * seconds + 1e-9)
    assert n >= 50
    want_p = decks.quantile_midpoints(traffic["prompt_len"], n)
    want_o = decks.quantile_midpoints(traffic["output_len"], n)
    perm = SplitMix(traffic["pairing_seed"]).permutation(n)
    for seed in (5, 2 ** 31 + 11):
        win = [r for r in open_deck.plan(traffic, cell, seed, seconds,
                                         131072) if r.phase == "window"]
        assert len(win) == n
        assert collections.Counter((r.prompt_len, r.max_new) for r in win) \
            == collections.Counter((want_p[i], want_o[perm[i]])
                                   for i in range(n))
        assert max(int(r.prompt.max()) for r in win) > 65536   # whole vocab
    assert abs(want_p[n // 2] - 181) < 10 and abs(want_o[n // 2] - 512) < 20
    # the longest request fits the model's window
    assert want_p[-1] + want_o[-1] <= 4096


def test_the_cell_s_rate_is_a_stated_share_of_a_knee_it_shows_the_sweep_of():
    cell = json.load(open(os.path.join(ROOT, "benchmarks", "cells",
                                       CELL + ".json")))
    assert 0.7 <= cell["share_of_knee"] <= 0.85
    assert cell["rate_rps"] == pytest.approx(
        cell["share_of_knee"] * cell["knee_rps"], rel=0.02)
    rows = cell["sweep"]
    assert len(rows) >= 6 and all(r["seconds"] == 40 for r in rows)
    assert len({r["seed"] // 1000 for r in rows}) >= 2       # two seeds
    rates = sorted({r["rate_rps"] for r in rows})
    assert cell["knee_rps"] in rates and max(rates) > cell["knee_rps"]

    def sustained(rate):
        at = [r for r in rows if r["rate_rps"] == rate]
        first = sum(r["ttft_mean_first_half_ms"] for r in at)
        second = sum(r["ttft_mean_second_half_ms"] or float("inf")
                     for r in at)
        return second <= first and sum(
            r["no_first_token_at_close"] for r in at) <= len(at)

    assert sustained(cell["knee_rps"])
    assert not any(sustained(r) for r in rates if r > cell["knee_rps"])


# what the cell has read since PR 38: its family's shared metrics under
# their names, and what only this configuration has under its suffix
SHARED = ("step_ms_p50", "batch_rows_mean", "chunk_step_gap_share",
          "token_slot_fill_share", "compiles_in_window",
          "host_serial_ms_per_step", "readback_wait_ms_p50",
          "device_idle_share", "hbm_peak_share", "step_temp_share",
          "ttft_mean_ms", "itl_mean_ms", "queue_wait_mean_ms",
          "gen_lateness_p99_ms", "moe_assignments_held_mean",
          "moe_held_expert_max_p95", "moe_experts_touched_mean",
          "latent_cache_bytes_per_token", "latent_decode_roofline_share",
          "moe_grouped_matmul_roofline_share",
          # since PR 46, once the table had room
          "loop_gap_ms_per_step", "admit_ms_per_step", "pack_ms_per_step",
          "launch_ms_per_step", "h2d_kb_per_step", "cache_bytes_per_token")
OWN = ("step_roofline_share_counted.xing4", "mhc_maps_roofline_share.xing4",
       "mhc_maps_ms_per_step.xing4", "mhc_col_sum_gap_max.xing4",
       "residual_stream_bytes_per_token.xing4")


def test_the_cell_and_its_entries_are_there_by_name(benchmark_json):
    """Found by name, never by place: the next PR appends too, and a
    shared metric's list names the other cells that read it."""
    bench = benchmark_json
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "reasoning", 1)
    assert CONFIG in [c["name"] for c in bench["configs"]]
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert CELL in itl["workloads"] and itl["bound"] == 0.08
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in SHARED + OWN]
    assert all(CELL in m["workloads"] and m["moves"] == "itl_p95_ms"
               for m in mine)
    assert {m["layer"] for m in mine} == {
        "scheduler", "step program", "device", "load generator",
        "expert layer", "latent attention", "kernels", "residual path"}
    for m in mine:
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    # a suffix only where the reader or its cost file is the
    # configuration's own: no other cell reads those
    for m in bench["per_layer"]:
        if m["name"].endswith(".xing4"):
            assert m["name"] in OWN and set(m["workloads"]) == {CELL}
