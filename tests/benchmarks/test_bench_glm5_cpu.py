"""The indexer-selected latent-attention + MoE family's files, on the CPU
at a tiny size (``index_topk`` 12 against contexts of 40 to 145): the
cell's system, reference, weights, costs, readers, deck and entries, in
the manner of ``test_bench_xing4_cpu.py``."""
import collections
import json
import math
import os
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import (check_served, costs_axk1, costs_glm5, decks, run,
                        weights_glm5)
from benchmarks.evidence import Evidence
from benchmarks.generators import open_deck
from benchmarks.readers import glm5_roofline
from benchmarks.reference import glm5 as reference
from benchmarks.rng import SplitMix
from benchmarks.systems import glm5_serving

from conftest import ROOT, load_data

CELL, CONFIG = "glm5-ep16.longdoc", "glm-5-ep16-d6"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 44


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _ctx(seed, seconds, tmp_path):
    return run.Context(load_data("tiny-glm5.json"),
                       load_data("tiny-longdoc.json"), {"rate_rps": 3.0}, 1,
                       seed, seconds, 0, jax.devices()[:1],
                       time.monotonic(), say=lambda s: print(s),
                       trace_dir=str(tmp_path / "trace"))


@pytest.fixture(scope="module")
def longdoc_result(tmp_path_factory):
    return run.run_cell(_ctx(SEED, 2.0, tmp_path_factory.mktemp("glm5")))


def test_new_cell_runs_and_is_correct(longdoc_result):
    res = longdoc_result
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 6
    assert res["evidence"].compiles_in_window == 0
    assert set(res["check"]) == {"widest_logit_gap", "logit_gap_p99"}


def test_new_cell_metrics_read_from_data_files(longdoc_result,
                                               benchmark_json):
    ev = longdoc_result["evidence"]
    e2e = run.read_metrics(benchmark_json["end_to_end"], "e2e_metrics", ev,
                           CELL)
    assert set(e2e) == {"itl_p95_ms", "setup_s"}
    layer = run.read_metrics(benchmark_json["per_layer"], "layer_metrics",
                             ev, CELL)
    listed = {m["name"] for m in benchmark_json["per_layer"]
              if CELL in m["workloads"]}
    assert layer and set(layer) <= listed
    # 3 layers x ((16 + 8) + 16) numbers x 2 bytes cached; stored in
    # 128-lane rows: 3 x (128 + 128) x 2
    assert layer["latent_cache_bytes_per_token"]["value"] == 3 * 40 * 2
    assert layer["index_cache_bytes_per_token.glm5"]["value"] == 3 * 128 * 2
    # every decode row's context lies above index_topk 12
    assert 0 < layer["index_keep_share.glm5"]["value"] < 35
    assert layer["compiles_in_window"]["value"] == 0
    assert layer["moe_assignments_held_mean"]["value"] > 0
    for name in ("loop_gap_ms_per_step", "admit_ms_per_step",
                 "pack_ms_per_step", "launch_ms_per_step", "h2d_kb_per_step",
                 "host_serial_ms_per_step"):
        assert layer[name]["value"] >= 0
    # both pools as stored: 3 layers x (128 + 128) lanes x 2 bytes
    assert layer["cache_bytes_per_token"]["value"] == 3 * 256 * 2
    # the rows the selection gathered, of max_batch x index_topk
    assert 0 < layer["index_gather_share.glm5"]["value"] <= 100
    assert 0 < layer["token_slot_fill_share"]["value"] <= 100
    # not traced: what reads the trace found nothing to read
    assert not [n for n in layer if "roofline" in n or "dsa_select" in n
                or "device_idle" in n]
    k = 12
    for s in ev.steps:
        if s["kind"] in ("mixed", "decode", "prefill"):
            assert s["index_scored_keys"] == s["attended_keys"]
            assert s["index_decode_scored_keys"] == s["decode_keys"]
            # rows of one query token go through the decode kernels: the
            # decode rows, and a prompt's last chunk of one token
            assert s["index_decode_selected_keys"] % k == 0
            assert (s["decode_rows"] <= s["index_decode_selected_keys"] // k
                    <= s["active_rows"])
            assert (s["index_decode_selected_keys"]
                    <= s["index_selected_keys"] <= s["index_scored_keys"])
            # this program runs the selecting kernels, not the dense one
            assert s["decode_grid_steps"] == 0


def _records(result, alter=lambda t: t):
    return [types.SimpleNamespace(
        index=r.index, prompt=r.prompt, prompt_len=r.prompt_len,
        tokens=[alter(t) for t in r.tokens])
        for r in result["evidence"].records]


def test_altered_tokens_come_out_not_correct(longdoc_result):
    """The same records with every served token shifted by one, through
    the same check: not correct."""
    cfg = load_data("tiny-glm5.json")
    correct, compared = check_served.check(
        cfg, SEED, _records(longdoc_result,
                            lambda t: (t + 1) % cfg["vocab_size"]),
        say=lambda s: None)
    assert correct is False
    value, limit = compared["widest_logit_gap"]
    assert value > 2 * limit


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_selection_left_out_or_random_comes_out_not_correct(
        longdoc_result, fault):
    """The two controls of the mechanism, read as ``control.py`` reads a
    lower precision: the reference with attention over every cached token,
    and with a random set of the same size in the indexer's place, put in
    the program's place at the served positions: outside the limits."""
    cfg = load_data("tiny-glm5.json")
    spec = cfg["check"]
    cases = check_served.sample(_records(longdoc_result), SEED,
                                int(spec["sample_requests"]),
                                int(spec["max_tokens_per_request"]))
    sound = check_served.gaps(cfg, SEED, cases)
    bad = check_served.gaps(cfg, SEED, cases, fault)
    p99 = lambda g: check_served.gap_quantile(g, 0.99)
    assert sound.max() <= spec["limit_logit_gap"]
    assert p99(sound) <= spec["limit_logit_gap_p99"]
    assert p99(bad) > spec["limit_logit_gap_p99"] \
        or bad.max() > spec["limit_logit_gap"]
    assert p99(bad) > 2 * p99(sound)


def test_configuration_is_the_catalog_row_but_for_its_cuts(benchmark_json):
    """Every key of the source's ``config`` under its own name and value
    but the four cuts of scale; no width among them."""
    cfg = _published()
    cuts = ["first_k_dense_replace", "n_routed_experts",
            "num_hidden_layers", "vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert cfg["source"] == row["source_url"]
        assert sorted(k for k, v in row["config"].items()
                      if cfg.get(k) != v) == cuts
        assert (row["config"]["num_hidden_layers"],
                row["config"]["n_routed_experts"]) == (78, 256)
    published = dict(
        hidden_size=6144, intermediate_size=12288, q_lora_rank=2048,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, qk_head_dim=256, num_attention_heads=64,
        moe_intermediate_size=2048, num_experts_per_tok=8,
        n_shared_experts=1, routed_scaling_factor=2.5, n_group=1,
        topk_group=1, topk_method="noaux_tc", index_n_heads=32,
        index_head_dim=128, index_topk=2048, indexer_rope_interleave=True,
        num_nextn_predict_layers=1, rms_norm_eps=1e-5,
        n_routed_experts_published=256, model_type="glm_moe_dsa")
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert sorted(cfg["reduced"]) == cuts
    # a whole period + >= 4 of the layers behind the leading dense one,
    # >= 8 experts held, >= an eighth of the vocabulary in whole tiles
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] == 256 // 16 >= 8
    assert cfg["vocab_size"] >= 154880 / 8 and cfg["vocab_size"] % 128 == 0
    entry = next(c for c in benchmark_json["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == cuts
    assert entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert (dep["max_batch"], dep["max_model_len"], dep["mp"],
            dep["token_budget"]) == (16, 16384, 1, 256)
    assert {"limit_logit_gap", "limit_logit_gap_p99"} <= set(cfg["check"])
    for word in ("indexer", "index_key_norm", "index_rotation_and_fp8",
                 "ties", "num_nextn_predict_layers", "weights",
                 "token_budget"):
        assert word in cfg["assumed"]
    # the file's own arithmetic: 9.46 GB of weights, 2.42 GB of pools
    assert costs_glm5.total_params(cfg) * 2 == pytest.approx(9.455e9,
                                                             rel=2e-3)
    per_token = (640 + 128) * 2 * cfg["num_hidden_layers"]
    assert per_token == 9216
    assert per_token * dep["max_batch"] * dep["max_model_len"] \
        == pytest.approx(2.416e9, rel=1e-3)


def test_the_program_builds_the_catalog_rows_config_abstractly():
    """Depth 78 with three dense layers, all 256 experts and the whole
    vocabulary as published, nothing on a device: 744 B parameters, about
    40 B of them active a token, as the model's name says."""
    from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                    LatentMoEForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters

    cfg = dict(_published(), num_hidden_layers=78, first_k_dense_replace=3,
               n_routed_experts=256, vocab_size=154880)
    mcfg = LatentMoEConfig(**{k: v for k, v in cfg.items()
                              if k not in glm5_serving.NOT_MODEL_KEYS})
    with abstract_parameters():
        model = LatentMoEForCausalLM(mcfg)
    sizes = {n: math.prod(p._data.shape) for n, p in model.named_parameters()}
    total = sum(sizes.values())
    assert 740e9 < total < 748e9
    routed = sum(v for n, v in sizes.items() if ".mlp.experts.w_" in n)
    # a token passes 8 of 256 routed experts and looks one row of the
    # embedding up
    active = (total - routed * (1 - 8 / 256)
              - sizes["model.embed_tokens.weight"])
    assert 39e9 < active < 41e9
    assert not [n for n in sizes if "nextn" in n or "mtp" in n]
    # the benchmark's names cover every parameter of a layer of each kind
    for i in (0, 3):
        mine = {n for n in sizes if n.startswith(f"model.layers.{i}.")
                and not n.endswith(("layernorm.weight",))}
        assert mine == set(glm5_serving.program_names(cfg, i).values())
    # the cache a token: 576 + 128 numbers a layer, in 640 + 128 lanes
    (layer,) = set(model.cache_layout())
    assert (layer.values_per_token(), layer.stored_per_token()) == (704, 768)
    assert layer.pool_shapes(5, 16) == ((5, 16, 640), (5, 16, 128))


def test_a_program_without_the_indexer_is_refused_at_once(monkeypatch):
    """A program that swallows the ``index_*`` keys and builds the dense
    layer cannot be this model: the adapter says so before it makes a
    single array."""
    from paddle_infer_tpu.models import latent_moe

    class Swallows(latent_moe.LatentMoEConfig):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.index_topk = 0

    monkeypatch.setattr(latent_moe, "LatentMoEConfig", Swallows)
    made = []
    monkeypatch.setattr(weights_glm5, "all_weights",
                        lambda *a, **k: made.append(a))
    s = glm5_serving.System(load_data("tiny-glm5.json"), jax.devices()[:1],
                            SEED, False)
    with pytest.raises(KeyError, match="cannot be this model"):
        s.build()
    assert not made


def test_costs_against_hand_counts():
    cfg = _published()
    # ISSUE 44's arithmetic
    assert costs_axk1.attention_params(cfg) == (
        6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 28672
        + 16384 * 6144) == 165_019_648
    assert costs_glm5.indexer_params(cfg) == (
        2048 * 4096 + 6144 * 128 + 6144 * 32) == 9_371_648
    assert costs_axk1.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert costs_axk1.dense_ffn_params(cfg) == 3 * 6144 * 12288
    assert costs_axk1.router_params(cfg) == 6144 * 256
    assert costs_axk1.latent_row_bytes(cfg) == 1152
    assert costs_glm5.index_key_bytes(cfg) == 256
    idx = costs_glm5.index_scores_cost(cfg, 1000)
    assert idx == {"flops": 1000 * 2 * 32 * 128, "bytes": 1000 * 256}
    att = costs_glm5.sparse_attention_cost(cfg, 1000)
    assert att == {"flops": 1000 * 2 * 64 * 1088, "bytes": 1000 * 1152}
    fixed = (6 * (165_019_648 + 9_371_648) + 3 * 6144 * 12288
             + 5 * (6144 * 256 + 37_748_736))
    assert costs_glm5.fixed_params_per_token(cfg) == fixed
    # 3 decode rows at 8000 (selecting 2048 each) beside a chunk of 200
    # at context 5000
    step = costs_glm5.step_cost(
        cfg, new_tokens=203, sampled_rows=3,
        scored_keys=3 * 8000 + 200 * 5100, selected_keys=203 * 2048,
        decode_scored_keys=3 * 8000, decode_selected_keys=3 * 2048,
        resident_tokens=3 * 8000 + 5200, assignments_held=100,
        experts_touched=60)
    gmm = costs_axk1.grouped_matmul_cost(cfg, 100, 60)
    assert step["flops"] == (
        2 * 203 * fixed + 6 * (idx["flops"] * (3 * 8000 + 200 * 5100) / 1000
                               + att["flops"] * 203 * 2048 / 1000)
        + gmm["flops"] + 2 * 3 * 6144 * 19456)
    cache = ((3 * 8000 + 5200) * 256 + (3 * 2048 + 5200) * 1152
             + 203 * (256 + 1152))
    assert step["bytes"] == ((fixed + 6144 * 19456) * 2 + 6 * cache
                             + gmm["bytes"])


def test_weights_one_call_equals_layer_by_layer():
    cfg = load_data("tiny-glm5.json")
    seed = 2 ** 31 + 9
    whole = weights_glm5.all_weights(cfg, seed, jnp.bfloat16)
    assert len(whole["layers"]) == 3
    assert "router" not in whole["layers"][0]
    assert "e_bias" in whole["layers"][1]
    lw = whole["layers"][1]
    assert lw["idx_wq"].shape == (24, 4 * 16)
    assert lw["idx_wk"].shape == (64, 16) and lw["idx_ww"].shape == (64, 4)
    assert lw["w_kvb"].shape == (16, 4 * (16 + 24))          # nope != v
    assert lw["w_o"].shape == (4 * 24, 64)
    assert lw["e_bias"].shape == (16,) and lw["e_bias"].dtype == jnp.float32
    assert lw["e_gate"].shape == (6, 64, 32)
    # the query up-projection is drawn QUERY_GAIN times wider
    std = lambda a: float(np.asarray(a, np.float32).std())
    assert std(lw["w_qb"]) == pytest.approx(
        weights_glm5.QUERY_GAIN * 0.02, rel=0.1)
    assert std(lw["w_kvb"]) == pytest.approx(0.02, rel=0.15)
    assert abs(float(np.asarray(lw["idx_norm_w"], np.float32).mean()) - 1) \
        < 0.1
    # another share of the same deployment draws the same experts
    other = weights_glm5.layer_weights(dict(cfg, experts_held_first=6,
                                            n_routed_experts=4), seed, 1)
    np.testing.assert_array_equal(np.asarray(lw["e_up"][3], np.float32),
                                  np.asarray(
        weights_glm5.layer_weights(dict(cfg, experts_held_first=7,
                                        n_routed_experts=2), seed, 1)
        ["e_up"][0], np.float32))
    assert other["e_gate"].shape == (4, 64, 32)


def _traced_evidence(steps, op_seconds, busy_s, config=None):
    return Evidence(
        config=config or _published(), traffic={}, cell={},
        device_kind="TPU v5 lite", chips=1, setup_s=1.0, w0=0.0, w1=10.0,
        steps=steps,
        trace={"busy_s": busy_s, "window_s": 2.0, "t0": 0.0, "t1": 2.0,
               "op_seconds": op_seconds})


def test_readers_from_counters_and_kernel_seconds(benchmark_json):
    step = dict(t=1.0, kind="decode", failed=False, decode_rows=4,
                prefill_chunk_tokens=0, emitted_tokens=4,
                attended_keys=32000, resident_tokens=32000,
                decode_keys=32000, index_scored_keys=32000,
                index_selected_keys=8192, index_decode_scored_keys=32000,
                index_decode_selected_keys=8192, moe_assignments_held=10,
                moe_experts_touched=9)
    ops = {"custom-call dsa_index_scores f32[16,1,16384]": 0.0008,
           "custom-call dsa_sparse_decode bf16[16,64,512]": 0.0012,
           "sort sort f32[16,16384]": 0.003,
           "fusion fusion bf16[32768,640]": 0.001,
           "fusion fusion bf16[64,4,3584]": 0.05}
    ev = _traced_evidence([step, dict(step, t=1.5)], ops, 0.06)
    cfg = ev.config
    # index scores: 256 B and 8192 operations a key: bound by memory
    least = 2 * 6 * 32000 * 256 / 819e9
    got = glm5_roofline.read(ev, "index_scores", "dsa_index_scores")
    assert got == pytest.approx(100 * least / 0.0008) and 0 < got < 100
    # the sparse decode: 1152 B and 139,264 operations a key: by memory too
    least = 2 * 6 * 8192 * 1152 / 819e9
    got = glm5_roofline.read(ev, "sparse_decode", "dsa_sparse_decode")
    assert got == pytest.approx(100 * least / 0.0012) and 0 < got < 100
    assert glm5_roofline.read(
        ev, "select_ms", kernels=["sort sort f32[16,16384]",
                                  "bf16[32768,640]"]) \
        == pytest.approx(1e3 * 0.004 / 2)
    assert 0 < glm5_roofline.read(ev, "step") < 100
    # the metric files name what the reader takes
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    for name in ("dsa_index_roofline_share.glm5",
                 "dsa_sparse_decode_roofline_share.glm5",
                 "dsa_select_ms_per_step.glm5",
                 "step_roofline_share_counted.glm5"):
        spec = run.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "glm5_roofline" and name in by_name
        assert glm5_roofline.read(ev, **spec["args"]) is not None
    # a program without the counters, a configuration without an indexer,
    # a trace without the kernel, a trace in which nothing ran, no trace
    bare = {k: v for k, v in step.items() if not k.startswith("index_")}
    assert glm5_roofline.read(_traced_evidence([bare], ops, 0.06),
                              "step") is None
    plain = {k: v for k, v in cfg.items() if k != "index_topk"}
    assert glm5_roofline.read(_traced_evidence([step], ops, 0.06, plain),
                              "step") is None
    assert glm5_roofline.read(ev, "index_scores", "no_such_kernel") is None
    assert glm5_roofline.read(ev, "select_ms", kernels=["no_such"]) is None
    assert glm5_roofline.read(_traced_evidence([step], {}, 0.0),
                              "step") is None
    ev.trace = None
    assert glm5_roofline.read(ev, "sparse_decode",
                              "dsa_sparse_decode") is None


def _selection_equations():
    """(primitive, shapes read, shapes written) of every equation the tiny
    configuration's served step traces under the scope ``dsa_select``,
    whatever loop or branch it lies in; shapes as the trace's operation
    keys write them (``bf16[12,128]``).  The kernels' bodies are not the
    scope's."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                    LatentMoEForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters
    from paddle_infer_tpu.serving.programs import (build_mixed_step,
                                                   step_input_layout)

    cfg, spec = load_data("tiny-glm5.json"), jax.ShapeDtypeStruct
    dep = cfg["deployment"]
    with abstract_parameters():
        model = LatentMoEForCausalLM(LatentMoEConfig(**{
            k: v for k, v in cfg.items()
            if k not in glm5_serving.NOT_MODEL_KEYS}))
    page = dep["page_size"]
    engine = PagedGenerationEngine(model, page_size=page,
                                   cache_dtype=jnp.bfloat16)
    b, pages = dep["max_batch"], dep["max_model_len"] // page
    step = build_mixed_step(engine, b, dep["token_budget"], pages,
                            moe_stats=True)
    (layer,) = set(model.cache_layout())
    latent, index = (spec(shape, jnp.bfloat16)
                     for shape in layer.pool_shapes(b * pages + 1, page))
    n = cfg["num_hidden_layers"]
    traced = step.trace(
        {name: spec(a.shape, a.dtype) for name, a in engine._params.items()},
        spec((step_input_layout(b, dep["token_budget"], pages, 1).size,),
             jnp.int32), [latent] * n, [index] * n)
    short = {"float32": "f32", "bfloat16": "bf16", "int32": "s32",
             "uint32": "u32", "bool": "pred"}
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
            if not inner and "dsa_select" in here:
                found.append((eqn.primitive.name, shapes(eqn.invars),
                              shapes(eqn.outvars)))

    walk(traced.jaxpr.jaxpr, "")
    return found


def test_the_selection_s_keys_name_what_its_scope_runs():
    """``dsa_select_ms_per_step.glm5`` sums device seconds by operation
    key, opcode and shape, because the scope's XLA operations keep their
    own names (PR 45 rearranged the selection and the metric read half of
    it).  So the keys are held to the program here, at the tiny
    configuration's counterparts of the cell's sizes and before any
    compiler: every key names a shape an equation under ``dsa_select``
    reads or writes, and every equation there that sorts, gathers or
    updates ``index_topk`` elements or more is named by a key.  A PR that
    rearranges the selection fails this test, not the metric."""
    real, tiny = _published(), load_data("tiny-glm5.json")
    lanes = lambda c: -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) \
        * 128
    sizes = lambda c: (c["deployment"]["max_batch"],
                       c["deployment"]["max_model_len"], c["index_topk"],
                       lanes(c), c["deployment"]["token_budget"])
    to_tiny = dict(zip(sizes(real), sizes(tiny)))
    assert len(to_tiny) == 5

    def counterpart(key):
        kind, dims = re.search(r"(\w+)\[([\d,]*)\]", key).groups()
        return "%s[%s]" % (kind, ",".join(
            str(to_tiny[int(d)]) for d in dims.split(",")))

    kernels = run.load_json(
        "layer_metrics", "dsa_select_ms_per_step.glm5.json")["args"]["kernels"]
    # the chip's compiler stages the gathered buffer to the next loop in
    # four parts: no equation of the program has that shape
    staged = {"bf16[4,2048,640]"}
    assert staged < set(kernels)
    named = {counterpart(k) for k in kernels if k not in staged}
    eqns = _selection_equations()
    touched = {s for _, read, written in eqns for s in read + written}
    assert named <= touched, sorted(named - touched)
    # what moves the data: a sort reads its scores, the others write
    moved = {(read if prim in ("top_k", "sort") else written)[0]
             for prim, read, written in eqns
             if prim in ("top_k", "sort", "gather", "dynamic_update_slice",
                         "scatter", "scatter-add", "cumsum")}
    elements = lambda s: math.prod(
        int(d) for d in re.search(r"\[([\d,]*)\]", s).group(1).split(",")
        if d)
    moved = {s for s in moved if elements(s) >= tiny["index_topk"]}
    # at least the scores sorted, the ids looked up, the rows gathered, the
    # buffer they are put in and the chunk rows' running count
    assert len(moved) >= 5 and moved <= named, sorted(moved - named)


def test_the_cell_s_deck_is_two_log_uniform_distributions(benchmark_json):
    here = os.path.join(ROOT, "benchmarks")
    traffic = json.load(open(os.path.join(here, "traffic", "longdoc.json")))
    cell = json.load(open(os.path.join(here, "cells", CELL + ".json")))
    cfg = _published()
    assert traffic["prompt_len"] == {"kind": "loguniform", "lo": 4096,
                                     "hi": 12288}
    assert traffic["output_len"] == {"kind": "loguniform", "lo": 128,
                                     "hi": 384}
    assert traffic["generator"] == "open_deck"
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"]) == (
        30.0, 30.0, 2.0)
    seconds = float(benchmark_json["run_seconds"])
    n = int(cell["rate_rps"] * seconds + 1e-9)
    assert 16 <= n <= 48
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
        assert max(int(r.prompt.max()) for r in win) < cfg["vocab_size"]
    assert abs(want_p[n // 2] - 7094) < 400 and abs(want_o[n // 2] - 222) < 15
    # every context lies above index_topk; the longest request fits the
    # model's window and the reference's one shape
    assert want_p[0] > cfg["index_topk"]
    longest = want_p[-1] + want_o[-1]
    assert longest <= cfg["deployment"]["max_model_len"]
    assert longest <= cfg["check"]["reference_pad_to"]
    assert cfg["check"]["reference_pad_to"] % reference.QUERY_BLOCK == 0


def test_the_cell_s_rate_is_a_stated_share_of_a_knee_it_shows_the_sweep_of():
    cell = json.load(open(os.path.join(ROOT, "benchmarks", "cells",
                                       CELL + ".json")))
    assert 0.7 <= cell["share_of_knee"] <= 0.85
    assert cell["rate_rps"] == pytest.approx(
        cell["share_of_knee"] * cell["knee_rps"], rel=0.02)
    rows = cell["sweep"]
    assert len(rows) >= 6 and all(r["seconds"] == 40 for r in rows)
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


# the family's shared metrics under their plain names (the last four since
# PR 46: the table had no room for them when the cell came), and what only
# this configuration has under its suffix
SHARED = ("step_ms_p50", "batch_rows_mean", "chunk_step_gap_share",
          "token_slot_fill_share", "compiles_in_window",
          "host_serial_ms_per_step", "readback_wait_ms_p50",
          "device_idle_share", "hbm_peak_share", "step_temp_share",
          "ttft_mean_ms", "itl_mean_ms", "queue_wait_mean_ms",
          "gen_lateness_p99_ms", "moe_assignments_held_mean",
          "moe_held_expert_max_p95", "moe_experts_touched_mean",
          "latent_cache_bytes_per_token",
          "moe_grouped_matmul_roofline_share", "finish_stall_ms_p50",
          "finish_stall_wall_share", "finish_step_gap_share",
          "emit_rows_ms_per_step", "readback_wait_ms_max",
          "readback_ready_ms_max", "gc_pause_ms_max", "host_off_cpu_ms_max",
          "pack_ms_per_step", "h2d_kb_per_step", "loop_gap_ms_per_step",
          "admit_ms_per_step", "launch_ms_per_step", "cache_bytes_per_token")
OWN = ("dsa_index_roofline_share.glm5",
       "dsa_sparse_decode_roofline_share.glm5",
       "dsa_select_ms_per_step.glm5", "index_keep_share.glm5",
       "index_cache_bytes_per_token.glm5",
       "step_roofline_share_counted.glm5", "index_gather_share.glm5")


def test_the_cell_and_its_entries_are_there_by_name(benchmark_json):
    """Found by name, never by place: the next PR appends too, and a
    shared metric's list names the other cells that read it."""
    bench = benchmark_json
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "longdoc", 1)
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
    # its steps run the selecting kernels in the dense decode kernel's
    # place: it is on neither of that kernel's lists
    for name in ("latent_decode_roofline_share", "decode_grid_steps_mean"):
        assert CELL not in by_name[name]["workloads"]
    # a suffix only where the reader or its cost file is the
    # configuration's own: no other cell reads those
    for m in bench["per_layer"]:
        if m["name"].endswith(".glm5"):
            assert m["name"] in OWN and set(m["workloads"]) == {CELL}
