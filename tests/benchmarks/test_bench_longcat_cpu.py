"""The family of two latent attentions and one shortcut expert block with
identity experts a layer: its files, on the CPU at a tiny size (2 layers =
4 attention sub-layers, 16 + 8 router outputs, top-4): the cell's system,
reference, weights, costs, readers, deck and entries, in the manner of
``test_bench_glm5_cpu.py``."""
import collections
import json
import math
import os
import re
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import (check_served, costs_axk1, costs_longcat, decks, run,
                        weights_longcat)
from benchmarks.evidence import Evidence
from benchmarks.generators import open_deck
from benchmarks.readers import longcat_roofline
from benchmarks.reference import longcat as reference
from benchmarks.rng import SplitMix
from benchmarks.systems import longcat_serving

from conftest import ROOT, load_data

CELL, CONFIG = "longcat-ep32.toolchat", "longcat-flash-ep32-d4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 47
CUTS = ["n_routed_experts", "num_layers", "vocab_size"]


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _ctx(seed, seconds, tmp_path):
    return run.Context(load_data("tiny-longcat.json"),
                       load_data("tiny-toolchat.json"), {"rate_rps": 4.0}, 1,
                       seed, seconds, 0, jax.devices()[:1],
                       time.monotonic(), say=lambda s: print(s),
                       trace_dir=str(tmp_path / "trace"))


@pytest.fixture(scope="module")
def toolchat_result(tmp_path_factory):
    return run.run_cell(_ctx(SEED, 2.0, tmp_path_factory.mktemp("longcat")))


def test_new_cell_runs_and_is_correct(toolchat_result):
    res = toolchat_result
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 8
    assert res["evidence"].compiles_in_window == 0
    assert set(res["check"]) == {"widest_logit_gap", "logit_gap_p99"}


def test_new_cell_metrics_read_from_data_files(toolchat_result,
                                               benchmark_json):
    ev = toolchat_result["evidence"]
    e2e = run.read_metrics(benchmark_json["end_to_end"], "e2e_metrics", ev,
                           CELL)
    assert set(e2e) == {"itl_p95_ms", "setup_s"}
    layer = run.read_metrics(benchmark_json["per_layer"], "layer_metrics",
                             ev, CELL)
    listed = {m["name"] for m in benchmark_json["per_layer"]
              if CELL in m["workloads"]}
    assert layer and set(layer) <= listed
    # 4 attention sub-layers x (16 + 8) numbers x 2 bytes cached; stored
    # in 128-lane rows
    assert layer["latent_cache_bytes_per_token"]["value"] == 4 * 24 * 2
    assert layer["cache_bytes_per_token"]["value"] == 4 * 128 * 2
    # 8 of 24 router outputs are identity experts
    assert 15 < layer["moe_identity_share.longcat"]["value"] < 50
    assert 1 <= layer["moe_real_experts_per_token_max.longcat"]["value"] <= 4
    assert layer["compiles_in_window"]["value"] == 0
    assert layer["moe_assignments_held_mean"]["value"] > 0
    assert layer["decode_grid_steps_mean"]["value"] > 0
    for name in ("loop_gap_ms_per_step", "admit_ms_per_step",
                 "pack_ms_per_step", "launch_ms_per_step", "h2d_kb_per_step",
                 "host_serial_ms_per_step"):
        assert layer[name]["value"] >= 0
    assert 0 < layer["token_slot_fill_share"]["value"] <= 100
    # not traced: what reads the trace found nothing to read
    assert not [n for n in layer if "roofline" in n or "identity_ms" in n
                or "device_idle" in n]
    # every listed entry that needs no trace and no eviction read a number
    silent = {n for n in listed - set(layer)}
    assert silent <= {
        "device_idle_share", "hbm_peak_share", "step_temp_share",
        "evict_scanned_nodes_per_block",
        "step_roofline_share_counted.longcat",
        "latent_decode_roofline_share.longcat",
        "moe_grouped_matmul_roofline_share.longcat",
        "moe_identity_ms_per_step.longcat"}, silent
    k, blocks = 4, 2
    for s in ev.steps:
        if s["kind"] in ("mixed", "decode", "prefill"):
            tokens = s["decode_rows"] + s["prefill_chunk_tokens"]
            assert s["moe_assignments_total"] == tokens * k * blocks
            # held 6 of 16: the rest of the computing assignments are the
            # absent chips'
            assert (s["moe_assignments_held"] + s["moe_assignments_identity"]
                    <= s["moe_assignments_total"])
            assert 0 < s["moe_real_per_token_max"] <= k


def _records(result, alter=lambda t: t):
    return [types.SimpleNamespace(
        index=r.index, prompt=r.prompt, prompt_len=r.prompt_len,
        tokens=[alter(t) for t in r.tokens])
        for r in result["evidence"].records]


def test_altered_tokens_come_out_not_correct(toolchat_result):
    cfg = load_data("tiny-longcat.json")
    correct, compared = check_served.check(
        cfg, SEED, _records(toolchat_result,
                            lambda t: (t + 1) % cfg["vocab_size"]),
        say=lambda s: None)
    assert correct is False
    value, limit = compared["widest_logit_gap"]
    assert value > 2 * limit


@pytest.mark.parametrize("fault", ["no_identity", "experts_read_z", "fp8"])
def test_a_control_put_in_the_programs_place_comes_out_not_correct(
        toolchat_result, fault):
    """The reference with the identity term left out, with the expert
    block reading the second sub-layer's norm, and in fp8, read as
    ``control.py`` reads a lower precision: outside one of the limits.
    (The renormalised control scales the branch by 1.4 at this size and
    flips no token: ``tests/test_longcat_flash.py`` holds it by the
    logits, and PERF.md section 2 has what it reads on the chip.)"""
    cfg = load_data("tiny-longcat.json")
    spec = cfg["check"]
    cases = check_served.sample(_records(toolchat_result), SEED,
                                int(spec["sample_requests"]),
                                int(spec["max_tokens_per_request"]))
    sound = check_served.gaps(cfg, SEED, cases)
    bad = check_served.gaps(cfg, SEED, cases, fault)
    p99 = lambda g: check_served.gap_quantile(g, 0.99)
    assert sound.max() <= spec["limit_logit_gap"]
    assert p99(sound) <= spec["limit_logit_gap_p99"]
    assert p99(bad) > spec["limit_logit_gap_p99"] \
        or bad.max() > spec["limit_logit_gap"]


def test_configuration_is_the_catalog_row_but_for_its_cuts(benchmark_json):
    """Every key of the source's ``config`` under its own name and value
    but the three cuts of scale; no width among them."""
    cfg = _published()
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Omni")
        assert cfg["source"] == row["source_url"]
        assert sorted(k for k, v in row["config"].items()
                      if cfg.get(k) != v) == CUTS
        assert (row["config"]["num_layers"],
                row["config"]["n_routed_experts"],
                row["config"]["vocab_size"]) == (28, 512, 131072)
    published = dict(
        hidden_size=6144, ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=64,
        moe_topk=12, zero_expert_num=256, zero_expert_type="identity",
        routed_scaling_factor=6, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, rms_norm_eps=1e-5, rope_theta=10000000,
        n_routed_experts_published=512, model_type="longcat_flash")
    for k, v in published.items():
        assert cfg[k] == v, k
    assert "num_hidden_layers" not in cfg and "moe_intermediate_size" not in cfg
    assert sorted(cfg["reduced"]) == CUTS
    # the guide's floors: four layers of the one-layer period, >= 8 experts
    # held, an eighth of the vocabulary in whole tiles
    assert cfg["num_layers"] == 4
    assert cfg["n_routed_experts"] == 512 // 32 >= 8
    assert cfg["vocab_size"] == 131072 // 8 and cfg["vocab_size"] % 128 == 0
    entry = next(c for c in benchmark_json["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUTS
    assert entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert (dep["max_batch"], dep["max_model_len"], dep["mp"],
            dep["token_budget"], dep["enable_prefix_cache"]) == (
        64, 4096, 1, 256, True)
    assert {"limit_logit_gap", "limit_logit_gap_p99"} <= set(cfg["check"])
    for word in ("hidden_act", "router_bias", "tie_word_embeddings",
                 "rotary_lanes", "latent_scales", "e_score_correction_bias",
                 "ties", "expert_block", "weights", "token_budget"):
        assert word in cfg["assumed"]
    assert "32 v5e chips" in cfg["deployment_stands_for"] \
        and "pipeline stages" in cfg["deployment_stands_for"]
    # the file's own arithmetic: 10.35 GB of weights, 2.68 GB of pools
    assert costs_longcat.total_params(cfg) * 2 == pytest.approx(10.35e9,
                                                                rel=2e-3)
    per_token = 640 * 2 * costs_longcat.cache_layers(cfg)
    assert per_token == 10240
    assert per_token * dep["max_batch"] * dep["max_model_len"] \
        == pytest.approx(2.684e9, rel=1e-3)
    # weights and pools: over 70 % of the chip
    assert (costs_longcat.total_params(cfg) * 2
            + per_token * dep["max_batch"] * dep["max_model_len"]) \
        > 0.8 * 16.0e9


def _abstract_model(cfg):
    from paddle_infer_tpu.models.longcat_flash import (
        LongcatFlashConfig, LongcatFlashForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters

    mcfg = LongcatFlashConfig(**{k: v for k, v in cfg.items()
                                 if k not in longcat_serving.NOT_MODEL_KEYS})
    with abstract_parameters():
        return LongcatFlashForCausalLM(mcfg)


def test_the_program_builds_the_catalog_rows_config_abstractly():
    """Depth 28, all 512 experts and the whole vocabulary as published,
    nothing on a device: 560 B parameters, about 27 B of them active a
    token on average, as the model's name says."""
    cfg = dict(_published(), num_layers=28, n_routed_experts=512,
               vocab_size=131072)
    model = _abstract_model(cfg)
    sizes = {n: math.prod(p._data.shape) for n, p in model.named_parameters()}
    total = sum(sizes.values())
    # 28 x 19.97 B + 1.61 B
    assert 559e9 < total < 562e9
    layer = sum(v for n, v in sizes.items()
                if n.startswith("model.layers.0."))
    assert layer == pytest.approx(19.97e9, rel=1e-3)
    routed = sum(v for n, v in sizes.items() if ".mlp.w_" in n)
    # a token passes 12 of 768 router outputs, a third of them identity
    # experts: 8 of 512 routed experts on average; one embedding row
    active = (total - routed * (1 - 8 / 512)
              - sizes["model.embed_tokens.weight"])
    assert 26e9 < active < 28e9
    # the benchmark's names cover every parameter of a layer
    mine = {n for n in sizes if n.startswith("model.layers.3.")
            and "norm" not in n}
    assert mine == set(longcat_serving.program_names(3).values())
    norms = {n for n in sizes if n.startswith("model.layers.3.")
             and "norm" in n}
    assert len(norms) == 4 + 2 * 2           # four of the layer, two a MLA
    # the cache a token: 576 numbers an attention sub-layer, in 640 lanes
    layout = model.cache_layout()
    assert len(layout) == 56 and {c.part for c in layout} == {0, 1}
    assert {(c.values_per_token(), c.stored_per_token())
            for c in layout} == {(576, 640)}
    assert layout[0].pool_shapes(5, 16) == ((5, 16, 640), None)


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """The parent of the PR that brought this family has no
    ``models/longcat_flash``: the adapter's first import fails, in
    seconds, before anything is built; a program whose router has no
    identity outputs is refused by the seeded shapes."""
    made = []
    monkeypatch.setattr(weights_longcat, "all_weights",
                        lambda *a, **k: made.append(a))
    monkeypatch.setitem(sys.modules, "paddle_infer_tpu.models.longcat_flash",
                        None)
    s = longcat_serving.System(load_data("tiny-longcat.json"),
                               jax.devices()[:1], SEED, False)
    with pytest.raises(ImportError):
        s.build()
    assert not made


def test_costs_against_hand_counts():
    cfg = _published()
    # ISSUE 47's arithmetic
    assert costs_axk1.attention_params(cfg) == (
        6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384
        + 8192 * 6144) == 90_570_752
    assert costs_longcat.dense_ffn_params(cfg) == 3 * 6144 * 12288 \
        == 226_492_416
    assert costs_longcat.router_params(cfg) == 768 * 6144 == 4_718_592
    assert costs_longcat.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert costs_longcat.latent_row_bytes(cfg) == 1152
    assert costs_longcat.cache_layers(cfg) == 8
    outside = 2 * 90_570_752 + 2 * 226_492_416 + 4_718_592
    assert outside == 638_844_928
    assert costs_longcat.fixed_params_per_token(cfg) == 4 * outside
    assert costs_longcat.total_params(cfg) == (
        4 * (outside + 16 * 37_748_736) + 2 * 16384 * 6144)
    # the held experts' matrices: identity assignments are in no count
    gmm = costs_longcat.grouped_matmul_cost(cfg, 50, 15)
    assert gmm == {"flops": 2.0 * 50 * 37_748_736,
                   "bytes": (15 * 37_748_736
                             + 50 * (2 * 6144 + 3 * 2048)) * 2.0}
    # 25 decode rows at 2000 beside a chunk of 200 at context 1000
    keys = 25 * 2000 + 200 * 1100
    step = costs_longcat.step_cost(
        cfg, new_tokens=225, sampled_rows=25, attended_keys=keys,
        resident_tokens=25 * 2000 + 1000, assignments_held=50,
        experts_touched=15)
    attn = costs_axk1.latent_attention_cost(cfg, keys, 225,
                                            25 * 2000 + 1000 + 225)
    assert attn["flops"] == 2 * 64 * 1088 * keys
    assert step["flops"] == (2 * 225 * 4 * outside + 8 * attn["flops"]
                             + gmm["flops"] + 2 * 25 * 6144 * 16384)
    assert step["bytes"] == ((4 * outside + 6144 * 16384) * 2
                             + 8 * attn["bytes"] + gmm["bytes"])
    # a step reads 5.3 GB outside the experts (ISSUE 47)
    assert (4 * outside + 6144 * 16384) * 2 == pytest.approx(5.31e9,
                                                             rel=2e-3)


def test_weights_one_call_equals_layer_by_layer():
    cfg = load_data("tiny-longcat.json")
    seed = 2 ** 31 + 9
    whole = weights_longcat.all_weights(cfg, seed, jnp.bfloat16)
    assert len(whole["layers"]) == 2
    lw = whole["layers"][1]
    assert set(lw) == set(weights_longcat.layer_shapes(cfg))
    assert lw["router"].shape == (64, 24) and lw["e_bias"].shape == (24,)
    assert lw["e_bias"].dtype == jnp.float32
    assert lw["a1_w_kvb"].shape == (16, 4 * 32)
    assert lw["m0_down"].shape == (96, 64)
    assert lw["e_gate"].shape == (6, 64, 32)
    again = weights_longcat.layer_weights(cfg, seed, 1)
    for k in lw:
        np.testing.assert_array_equal(np.asarray(lw[k], np.float32),
                                      np.asarray(again[k], np.float32))
    # the two sub-layers' matrices are different draws
    assert not np.array_equal(np.asarray(lw["a0_w_qa"], np.float32),
                              np.asarray(lw["a1_w_qa"], np.float32))
    std = lambda a: float(np.asarray(a, np.float32).std())
    assert std(lw["router"]) == pytest.approx(0.02, rel=0.1)
    assert std(lw["e_bias"]) == pytest.approx(
        weights_longcat.ROUTER_BIAS_STD, rel=0.4)
    # another share of the same deployment draws the same experts
    other = weights_longcat.layer_weights(
        dict(cfg, experts_held_first=7, n_routed_experts=2), seed, 1)
    np.testing.assert_array_equal(np.asarray(lw["e_up"][3], np.float32),
                                  np.asarray(other["e_up"][0], np.float32))


def _traced_evidence(steps, op_seconds, busy_s, config=None):
    return Evidence(
        config=config or _published(), traffic={}, cell={},
        device_kind="TPU v5 lite", chips=1, setup_s=1.0, w0=0.0, w1=10.0,
        steps=steps,
        trace={"busy_s": busy_s, "window_s": 2.0, "t0": 0.0, "t1": 2.0,
               "op_seconds": op_seconds})


IDENTITY_KEYS = ["fusion convert_multiply_fusion f32[256,6144]",
                 "fusion select_reduce_fusion f32[256]"]


def test_readers_from_counters_and_kernel_seconds(benchmark_json):
    step = dict(t=1.0, kind="mixed", failed=False, decode_rows=25,
                prefill_chunk_tokens=200, emitted_tokens=25,
                attended_keys=25 * 2000 + 200 * 1100,
                resident_tokens=25 * 2000 + 1000, decode_keys=25 * 2000,
                moe_assignments_total=225 * 48, moe_assignments_held=230,
                moe_experts_touched=60, moe_assignments_identity=225 * 16,
                moe_real_per_token_max=11)
    ops = {"custom-call latent_paged_decode bf16[64,64,512]": 0.004,
           "custom-call moe_grouped_matmul bf16[3072,2048]": 0.012,
           IDENTITY_KEYS[0]: 0.0002, IDENTITY_KEYS[1]: 0.0001,
           "fusion fusion bf16[256,12288]": 0.02}
    ev = _traced_evidence([step, dict(step, t=1.5)], ops, 0.05)
    cfg = ev.config
    # the decode rows' attention: 1152 B and 139,264 operations a key
    least = 2 * 8 * (25 * 2000 * 1152 + 25 * 64 * 1088 * 2) / 819e9
    got = longcat_roofline.read(ev, "latent_decode", "latent_paged_decode")
    assert got == pytest.approx(100 * least / 0.004) and 0 < got < 100
    gmm = costs_longcat.grouped_matmul_cost(cfg, 230, 60)
    got = longcat_roofline.read(ev, "grouped_matmul", "moe_grouped_matmul")
    assert got == pytest.approx(100 * 2 * gmm["bytes"] / 819e9 / 0.012)
    assert 0 < got < 100
    assert longcat_roofline.read(ev, "identity_ms", kernels=IDENTITY_KEYS) \
        == pytest.approx(1e3 * 0.0003 / 2)
    assert 0 < longcat_roofline.read(ev, "step") < 100
    # the metric files name what the reader takes, and read
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    for name in ("step_roofline_share_counted.longcat",
                 "latent_decode_roofline_share.longcat",
                 "moe_grouped_matmul_roofline_share.longcat",
                 "moe_identity_ms_per_step.longcat"):
        spec = run.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "longcat_roofline" and name in by_name
        assert longcat_roofline.read(ev, **spec["args"]) is not None
    own = [m for m in benchmark_json["per_layer"]
           if m["name"].endswith(".longcat")]
    layer = run.read_metrics(own, "layer_metrics", ev, CELL)
    assert len(layer) == len(own) == 6
    assert layer["moe_identity_share.longcat"]["value"] \
        == pytest.approx(100 / 3)
    assert layer["moe_real_experts_per_token_max.longcat"]["value"] == 11
    # a program without the counters, a configuration without identity
    # experts, a trace without the kernel, a trace in which nothing ran,
    # no trace: nothing to read, and nothing raised
    bare = {k: v for k, v in step.items()
            if k not in ("moe_assignments_identity",
                         "moe_real_per_token_max")}
    ev_bare = _traced_evidence([bare], ops, 0.05)
    assert longcat_roofline.read(ev_bare, "step") is None
    assert run.read_metrics(own, "layer_metrics", ev_bare, CELL) == {}
    plain = {k: v for k, v in cfg.items() if k != "zero_expert_num"}
    assert longcat_roofline.read(_traced_evidence([step], ops, 0.05, plain),
                                 "step") is None
    assert longcat_roofline.read(ev, "latent_decode", "no_such") is None
    assert longcat_roofline.read(ev, "identity_ms", kernels=["no"]) is None
    assert longcat_roofline.read(_traced_evidence([step], {}, 0.0),
                                 "step") is None
    ev.trace = None
    assert longcat_roofline.read(ev, "grouped_matmul",
                                 "moe_grouped_matmul") is None


def _identity_equations():
    """(primitive, shapes written) of every equation the tiny
    configuration's served step traces under the scope ``moe_identity``."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.serving.programs import (build_mixed_step,
                                                   step_input_layout)

    cfg, spec = load_data("tiny-longcat.json"), jax.ShapeDtypeStruct
    dep = cfg["deployment"]
    model = _abstract_model(cfg)
    page = dep["page_size"]
    engine = PagedGenerationEngine(model, page_size=page,
                                   cache_dtype=jnp.bfloat16)
    b, pages = dep["max_batch"], dep["max_model_len"] // page
    step = build_mixed_step(engine, b, dep["token_budget"], pages,
                            moe_stats=True)
    layout = model.cache_layout()
    pool = spec(layout[0].pool_shapes(b * pages + 1, page)[0], jnp.bfloat16)
    traced = step.trace(
        {name: spec(a.shape, a.dtype) for name, a in engine._params.items()},
        spec((step_input_layout(b, dep["token_budget"], pages, 1).size,),
             jnp.int32), [pool] * len(layout), [None] * len(layout))
    short = {"float32": "f32", "bfloat16": "bf16", "int32": "s32",
             "bool": "pred"}
    shapes = lambda vs: tuple(
        "%s[%s]" % (short.get(str(v.aval.dtype), str(v.aval.dtype)),
                    ",".join(map(str, v.aval.shape)))
        for v in vs if hasattr(v.aval, "shape"))
    found = []

    def walk(jaxpr, scope):
        for eqn in jaxpr.eqns:
            here = scope + "/" + str(eqn.source_info.name_stack)
            inner = [getattr(x, "jaxpr", x) for v in eqn.params.values()
                     for x in (v if isinstance(v, (tuple, list)) else (v,))
                     if hasattr(getattr(x, "jaxpr", x), "eqns")]
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in inner:
                walk(sub, here)
            if not inner and "moe_identity" in here:
                found.append((eqn.primitive.name, shapes(eqn.outvars)))

    walk(traced.jaxpr.jaxpr, "")
    return found


def test_the_identity_term_s_keys_name_what_its_scope_runs():
    """``moe_identity_ms_per_step.longcat`` sums device seconds by
    operation key, opcode and shape, because the scope's XLA operations
    keep their own names.  The keys were read from the step compiled for
    the chip (``tests/test_chip_compile.py`` holds them to the compiled
    text); here they are held to the program before any compiler, at the
    tiny configuration's counterparts of the cell's sizes: the scope holds
    one masked sum a token and one multiply into the combine's base, a
    block, and nothing as wide as tokens x router outputs."""
    real, tiny = _published(), load_data("tiny-longcat.json")
    sizes = lambda c: (c["deployment"]["token_budget"], c["hidden_size"])
    to_tiny = dict(zip(sizes(real), sizes(tiny)))
    kernels = run.load_json(
        "layer_metrics",
        "moe_identity_ms_per_step.longcat.json")["args"]["kernels"]
    assert kernels == IDENTITY_KEYS
    named = set()
    for key in kernels:
        kind, dims = re.search(r"(\w+)\[([\d,]*)\]", key).groups()
        named.add("%s[%s]" % (kind, ",".join(
            str(to_tiny[int(d)]) for d in dims.split(","))))
    eqns = _identity_equations()
    written = {s for _, out in eqns for s in out}
    assert named <= written, sorted(named - written)
    tokens, outputs = tiny["deployment"]["token_budget"], 24
    k = tiny["moe_topk"]
    # two blocks: one reduction over the k chosen and one multiply each
    assert sum(p == "reduce_sum" and out == ("f32[%d]" % tokens,)
               for p, out in eqns) == 2
    assert sum(p == "mul" and out == ("f32[%d,64]" % tokens,)
               for p, out in eqns) == 2
    # no [tokens, router outputs] one-hot, no scatter, no sort
    assert not [e for e in eqns if e[0] in ("scatter-add", "scatter", "sort",
                                            "gather", "argsort")]
    assert not [s for s in written if s.endswith("[%d,%d]" % (tokens,
                                                              outputs))]
    widest = max(math.prod(int(d) for d in re.search(
        r"\[([\d,]*)\]", s).group(1).split(",") if d) for s in written)
    assert widest == tokens * 64 and k * tokens < widest


def test_the_cell_s_deck_is_two_log_uniform_distributions(benchmark_json):
    here = os.path.join(ROOT, "benchmarks")
    traffic = json.load(open(os.path.join(here, "traffic", "toolchat.json")))
    cell = json.load(open(os.path.join(here, "cells", CELL + ".json")))
    cfg = _published()
    assert traffic["prompt_len"] == {"kind": "loguniform", "lo": 768,
                                     "hi": 3072}
    assert traffic["output_len"] == {"kind": "loguniform", "lo": 64,
                                     "hi": 512}
    assert traffic["generator"] == "open_deck"
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"]) == (
        30.0, 30.0, 2.0)
    seconds = float(benchmark_json["run_seconds"])
    n = int(cell["rate_rps"] * seconds + 1e-9)
    assert n >= 70
    want_p = decks.quantile_midpoints(traffic["prompt_len"], n)
    want_o = decks.quantile_midpoints(traffic["output_len"], n)
    perm = SplitMix(traffic["pairing_seed"]).permutation(n)
    for seed in (5, 2 ** 31 + 11):
        win = [r for r in open_deck.plan(traffic, cell, seed, seconds,
                                         cfg["vocab_size"])
               if r.phase == "window"]
        assert len(win) == n
        assert collections.Counter((r.prompt_len, r.max_new) for r in win) \
            == collections.Counter((want_p[i], want_o[perm[i]])
                                   for i in range(n))
        top = max(int(r.prompt.max()) for r in win)
        assert cfg["vocab_size"] // 2 < top < cfg["vocab_size"]
    assert abs(want_p[n // 2] - 1536) < 60 and abs(want_o[n // 2] - 181) < 10
    # the longest request fits the model's window and the reference's pad
    assert want_p[-1] + want_o[-1] <= cfg["check"]["reference_pad_to"] \
        <= cfg["deployment"]["max_model_len"]


def test_the_cell_s_rate_is_a_stated_share_of_a_knee_it_shows_the_sweep_of():
    cell = json.load(open(os.path.join(ROOT, "benchmarks", "cells",
                                       CELL + ".json")))
    assert 0.7 <= cell["share_of_knee"] <= 0.85
    assert cell["rate_rps"] == pytest.approx(
        cell["share_of_knee"] * cell["knee_rps"], rel=0.02)
    rows = cell["sweep"]
    assert len(rows) >= 5 and all(r["seconds"] == 40 for r in rows)
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


# the shared lists the cell joined (benchmarks/README.md, point 4), and what
# only this configuration reads under its suffix
SHARED = ("step_ms_p50", "batch_rows_mean", "chunk_step_gap_share",
          "token_slot_fill_share", "compiles_in_window",
          "host_serial_ms_per_step", "readback_wait_ms_p50",
          "device_idle_share", "hbm_peak_share", "step_temp_share",
          "ttft_mean_ms", "itl_mean_ms", "queue_wait_mean_ms",
          "gen_lateness_p99_ms", "moe_assignments_held_mean",
          "moe_held_expert_max_p95", "moe_experts_touched_mean",
          "latent_cache_bytes_per_token", "cache_bytes_per_token",
          "decode_grid_steps_mean", "loop_gap_ms_per_step",
          "admit_ms_per_step", "pack_ms_per_step", "launch_ms_per_step",
          "h2d_kb_per_step", "finish_stall_ms_p50", "emit_rows_ms_per_step")
OWN = ("step_roofline_share_counted.longcat",
       "latent_decode_roofline_share.longcat",
       "moe_grouped_matmul_roofline_share.longcat",
       "moe_identity_share.longcat",
       "moe_real_experts_per_token_max.longcat",
       "moe_identity_ms_per_step.longcat")


def test_the_cell_and_its_entries_are_there_by_name(benchmark_json):
    """Found by name, never by place: the next PR appends too."""
    bench = benchmark_json
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "toolchat", 1)
    assert "1/32" in entry["why"] and len(entry["why"]) <= 200
    assert CONFIG in [c["name"] for c in bench["configs"]]
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert CELL in itl["workloads"] and itl["bound"] == 0.08
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in SHARED + OWN]
    assert all(CELL in m["workloads"] and m["moves"] == "itl_p95_ms"
               for m in mine)
    assert {m["layer"] for m in mine} == {
        "scheduler", "step program", "device", "load generator",
        "expert layer", "latent attention", "kernels", "KV lifecycle"}
    for m in mine:
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    # the shared roofline readers take num_hidden_layers and
    # moe_intermediate_size from the file: this cell stays off them and
    # reads the same two kernels through its own cost module
    for name in ("latent_decode_roofline_share",
                 "moe_grouped_matmul_roofline_share"):
        assert CELL not in by_name[name]["workloads"]
    for m in bench["per_layer"]:
        if m["name"].endswith(".longcat"):
            assert m["name"] in OWN and set(m["workloads"]) == {CELL}
