"""The decoder of two latent attentions, two dense blocks and one shortcut
expert block a layer (models/longcat_flash.py) on the serving path, at a
small size on the CPU (2 layers = 4 attention sub-layers, 16 routed + 8
identity experts, top-4): the router's two modes against plain
``jax.numpy``, identity experts, the layer and the served path against the
plain reference (benchmarks/reference/longcat.py), share against whole."""
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import weights_longcat                      # noqa: E402
from benchmarks.reference import longcat as reference       # noqa: E402
from benchmarks.reference.lowp import rounder               # noqa: E402
from benchmarks.systems import longcat_serving              # noqa: E402
from paddle_infer_tpu.core.tensor import Tensor             # noqa: E402
from paddle_infer_tpu.inference.cache_layout import (       # noqa: E402
    LayerCache, layout_of)
from paddle_infer_tpu.models import longcat_flash           # noqa: E402
from paddle_infer_tpu.serving.moe import dropless           # noqa: E402
from paddle_infer_tpu.serving.moe import stats as moe_stats  # noqa: E402

SEED = 2 ** 31 + 47
# float32 throughout: program and reference then differ by summation order
# alone (1e-6 at these sizes); 1e-4 leaves a decade for the absorbed form's
# reassociation and the kernel's online softmax, as tests/test_latent_moe.py
TOL = 1e-4
# bfloat16 weights and activations against the float32 reference, one
# layer whose outputs reach 4 to 8, where bfloat16 numbers lie 0.031 apart:
# the rounding of the output alone is 0.016, and a token reads 0.012 at the
# median and 0.024 at the 99th percentile of its widest element; 0.05 is
# twice that.  A token whose fourth choice is a near-tie chooses another
# expert in bfloat16 (the reference with its own operands rounded to
# bfloat16 flips the same token) and reads 0.8: at most one token in 50
TOL_BF16, FLIPS_BF16 = 0.05, 0.02
TOP_K, PUBLISHED, IDENTITY = 4, 16, 8


def tiny_config(**over):
    with open(os.path.join(ROOT, "tests", "benchmarks", "data",
                           "tiny-longcat.json")) as f:
        cfg = json.load(f)
    cfg.update(torch_dtype="float32", n_routed_experts=16,
               experts_held_first=0)
    cfg.update(over)
    return cfg


def _model_config(cfg):
    return longcat_flash.LongcatFlashConfig(**{
        k: v for k, v in cfg.items()
        if k not in longcat_serving.NOT_MODEL_KEYS})


@pytest.fixture(scope="module")
def system():
    s = longcat_serving.System(tiny_config(), jax.devices()[:1], SEED, False)
    s.build()
    yield s
    s.free()


# ------------------------------------------------------------------ router

def _plain_route(x, gate, k, scale, bias, scoring, renormalise):
    """Plain ``jax.numpy``: scores, a sort for the choice (ties to the
    lower index), the uncorrected scores as weights."""
    logits = np.asarray(x, np.float64) @ np.asarray(gate, np.float64)
    if scoring == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-logits))
    else:
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    pick = s if bias is None else s + np.asarray(bias, np.float64)
    ids = np.argsort(-pick, axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, ids, -1)
    if renormalise:
        w = w / w.sum(-1, keepdims=True)
    return ids, w * scale


@pytest.mark.parametrize("biased", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("scoring, renormalise",
                         [("sigmoid", True), ("softmax", False),
                          ("softmax", True), ("sigmoid", False)])
def test_route_in_both_modes_equals_plain_numpy(scoring, renormalise,
                                                biased):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(40, 64)).astype(np.float32)
    gate = (rng.normal(size=(64, 24)) * 0.2).astype(np.float32)
    bias = (rng.normal(size=(24,)) * 0.02).astype(np.float32) \
        if biased else None
    ids, w = dropless.route(jnp.asarray(x), jnp.asarray(gate), TOP_K, 6.0,
                            None if bias is None else jnp.asarray(bias),
                            scoring, renormalise)
    want_ids, want_w = _plain_route(x, gate, TOP_K, 6.0, bias, scoring,
                                    renormalise)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-5)
    total = np.asarray(w).sum(-1)
    if renormalise:
        np.testing.assert_allclose(total, 6.0, rtol=1e-5)
    elif scoring == "softmax":
        # four of 24 softmax scores: less than the scale, not it
        assert (total < 6.0).all() and total.mean() < 5.0


def test_softmax_route_ties_go_to_the_lower_index_and_the_bias_only_chooses():
    x = jnp.ones((2, 8), jnp.float32)
    gate = np.zeros((8, 12), np.float32)
    gate[:, 5] = gate[:, 9] = 0.5            # two outputs tied on top
    ids, w = dropless.route(x, jnp.asarray(gate), 3, 6.0, None, "softmax",
                            False)
    # 5 and 9 lead; the ten others are tied and the lowest index wins
    np.testing.assert_array_equal(np.asarray(ids), [[5, 9, 0]] * 2)
    p = np.exp([4.0, 4.0] + [0.0] * 10)
    p /= p.sum()
    np.testing.assert_allclose(np.asarray(w)[0], 6.0 * p[[0, 1, 2]],
                               rtol=1e-5)
    # a bias lifts output 11 into the choice; its WEIGHT is its own score
    bias = np.zeros((12,), np.float32)
    bias[11] = 1.0
    ids_b, w_b = dropless.route(x, jnp.asarray(gate), 3, 6.0,
                                jnp.asarray(bias), "softmax", False)
    np.testing.assert_array_equal(np.asarray(ids_b), [[11, 5, 9]] * 2)
    np.testing.assert_allclose(np.asarray(w_b)[0],
                               6.0 * p[[2, 0, 1]], rtol=1e-5)


def test_route_refuses_a_scoring_it_does_not_know():
    with pytest.raises(ValueError, match="sigmoid.*softmax"):
        dropless.route(jnp.ones((1, 4)), jnp.ones((4, 4)), 2, 1.0,
                       scoring="tanh")
    with pytest.raises(ValueError, match="sigmoid.*softmax"):
        dropless.DroplessMoE(4, 4, 4, 2, scoring="tanh")


# -------------------------------------------------------- identity experts

def _identity_layer():
    """4 published experts + 2 identity experts, top-2; the router sends a
    token along axis 0 to the identity experts, one along axis 1 to
    experts 0 and 1."""
    layer = dropless.DroplessMoE(8, 4, n_published=4, top_k=2,
                                 routed_scale=6.0, score_bias=True,
                                 identity_experts=2, scoring="softmax",
                                 renormalise=False)
    rng = np.random.default_rng(5)
    gate = np.zeros((8, 6), np.float32)
    gate[0, 4], gate[0, 5] = 3.0, 2.0
    gate[1, 0], gate[1, 1] = 3.0, 2.0
    layer.gate_weight._data = jnp.asarray(gate)
    for name in ("w_gate", "w_up", "w_down"):
        p = getattr(layer, name)
        p._data = jnp.asarray(rng.normal(size=p._data.shape) * 0.3,
                              jnp.float32)
    x = np.zeros((3, 8), np.float32)
    x[0, 0] = x[1, 1] = x[2, 0] = 2.0
    return layer, x


def test_identity_experts_add_their_weight_times_the_input_and_compute_nothing():
    """A token whose whole choice is identity experts, one with none, and
    a pad slot that routes nowhere."""
    layer, x = _identity_layer()
    valid = jnp.asarray([True, True, False])
    with moe_stats.collect(valid, max_valid=3) as col:
        y = np.asarray(layer(Tensor(jnp.asarray(x[None])))._data[0])
    total, held, held_max, touched, identity, real_max = map(
        int, col.totals())
    ids, w = dropless.route(jnp.asarray(x), layer.gate_weight._data, 2, 6.0,
                            layer.e_score_correction_bias._data, "softmax",
                            False)
    ids, w = np.asarray(ids), np.asarray(w)
    assert sorted(ids[0]) == [4, 5] and sorted(ids[1]) == [0, 1]
    # token 0: nothing computed, (g_4 + g_5) x its input; less than the
    # scale of 6 (the weights are not renormalised)
    np.testing.assert_allclose(y[0], w[0].sum() * x[0], rtol=1e-6)
    assert 0 < w[0].sum() < 6.0
    # token 1: experts 0 and 1, no identity term
    swiglu = lambda v, e: (np.asarray(jax.nn.silu(
        v @ layer.w_gate._data[e])) * np.asarray(v @ layer.w_up._data[e])) \
        @ np.asarray(layer.w_down._data[e])
    want = sum(w[1, j] * swiglu(x[1], int(ids[1, j])) for j in range(2))
    np.testing.assert_allclose(y[1], want, rtol=1e-5, atol=1e-6)
    # the pad slot: same input as token 0, routed nowhere
    assert not y[2].any()
    assert (total, held, identity) == (2 * 2, 2, 2)
    assert (held_max, touched, real_max) == (1, 2, 2)


def test_identity_assignments_stay_out_of_the_sort_and_the_grouped_matmul():
    """The held experts' counts, the rows buffer and the grouped matmul see
    the computing assignments alone: with every token's choice on identity
    experts nothing is counted and the experts' part is zero."""
    layer, x = _identity_layer()
    x = np.repeat(x[:1], 5, axis=0)
    ids, w = dropless.route(jnp.asarray(x), layer.gate_weight._data, 2, 6.0,
                            None, "softmax", False)
    assert (np.asarray(ids) >= 4).all()
    y, counts = dropless.dropless_experts(
        jnp.asarray(x), ids, w, jnp.ones((5,), bool), layer.w_gate._data,
        layer.w_up._data, layer.w_down._data, 0)
    assert not np.asarray(counts).any() and not np.asarray(y).any()
    # no operation of the step grows with the identity experts: the
    # traced program has no array as wide as the router's outputs times
    # the tokens but the scores themselves
    jaxpr = jax.make_jaxpr(lambda v: layer(Tensor(v))._data)(
        jnp.asarray(x[None]))
    wide = [str(e.primitive) for e in jaxpr.jaxpr.eqns
            for v in e.outvars if getattr(v.aval, "shape", ())[-1:] == (6,)
            and e.primitive.name in ("scatter-add", "scatter", "eq")]
    assert not wide, wide


# ------------------------------------------------- layer against reference

def _layer_case(dtype):
    cfg = tiny_config(torch_dtype=dtype, n_routed_experts=6,
                      experts_held_first=4)
    model = longcat_flash.LongcatFlashForCausalLM(_model_config(cfg))
    model.eval()
    w = weights_longcat.layer_weights(cfg, SEED, 1, jnp.dtype(dtype))
    names = longcat_serving.program_names(0)
    params = dict(model.named_parameters())
    for k, name in names.items():
        params[name]._data = w[k]
    for name, p in params.items():
        if name.startswith("model.layers.0.") and "norm" in name:
            p._data = jnp.ones(p._data.shape, jnp.dtype(dtype))
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 256, 64)) * 0.7,
                    jnp.dtype(dtype))
    return cfg, model.model.layers[0], w, x


def _reference_layer(cfg, w, x, precision="float32"):
    items = tuple((k, cfg[k]) for k in reference._KEYS) + (
        ("experts_held_first", cfg["experts_held_first"]),)
    return np.asarray(reference._layer(x[0].astype(jnp.float32), w, items,
                                       precision))


@pytest.mark.parametrize("dtype, tol", [("float32", TOL),
                                        ("bfloat16", TOL_BF16)])
def test_the_layer_equals_the_reference_layer(dtype, tol):
    cfg, layer, w, x = _layer_case(dtype)
    got = np.asarray(layer(Tensor(x))._data[0], np.float32)
    want = _reference_layer(cfg, w, x)
    assert 4.0 < np.abs(want).max() < 8.0
    err = np.abs(got - want).max(-1)                     # a token
    if dtype == "float32":
        assert err.max() <= tol
        return
    assert (err > tol).mean() <= FLIPS_BF16
    assert np.median(err) > tol / 10                     # and no looser
    # the tokens over it are the ones the reference, its operands rounded
    # to bfloat16, routes otherwise too
    low = np.abs(_reference_layer(cfg, w, x, "bfloat16") - want).max(-1)
    assert set(np.flatnonzero(err > tol)) <= set(np.flatnonzero(low > tol))


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_three_controls_of_the_expert_block_fall_outside_the_tolerance(
        system, fault):
    """The identity term left out, the chosen weights renormalised, and the
    expert block moved to read the second sub-layer's norm: each moves the
    logits by far more than program and reference differ."""
    cfg = system.config
    seq = np.random.default_rng(11).integers(0, 256, 60).astype(np.int32)
    rows = np.arange(60)
    ref = np.asarray(reference.served_logits(cfg, SEED, seq, rows))
    eager = np.asarray(system.engine._model(
        Tensor(jnp.asarray(seq[None])))._data[0])
    np.testing.assert_allclose(eager, ref, atol=TOL, rtol=0)
    bad = np.asarray(reference.served_logits(cfg, SEED, seq, rows, fault))
    assert np.abs(bad - ref).max() > 100 * TOL


# ------------------------------------------- the served path, paged pools

def _logits_program(engine, tokens):
    """The mixed step's model call over its flat token axis, returning
    every slot's logits and the six expert counters."""
    from paddle_infer_tpu.ops.pallas.ragged_paged_attention import \
        ragged_rows
    from paddle_infer_tpu.serving.programs import (_layer_caches,
                                                   _layer_pools)

    def run(params, ids, qlens, ctx, tables, scratch, k_pages, v_pages):
        caches = _layer_caches(engine, k_pages, v_pages, tables, ctx, qlens,
                               scratch)
        _, row, offset, valid = ragged_rows(qlens, tokens)
        pos = jnp.where(valid, ctx[row] + offset, 0)
        with moe_stats.collect(valid, max_valid=tokens) as col:
            logits, caches = engine._model_step(params, ids[None], pos[None],
                                                None, caches)
        return (logits[0], jnp.stack(col.totals()),
                *_layer_pools(engine, caches))

    return jax.jit(run, donate_argnums=(6, 7))


def test_chunked_prefill_then_decode_through_every_pool_equals_the_reference(
        system):
    """Rows of different lengths and kinds in one step, through the four
    latent pools of two layers under one block table."""
    eng, cfg = system.engine, system.config
    assert len(eng._cache_layout) == eng._num_layers == 4
    b, t = 4, 32
    max_pages = system.core._max_pages
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (45, 9, 30)]
    tables = np.full((b, max_pages), system.core._scratch, np.int32)
    for r in range(3):
        tables[r] = 1 + r * max_pages + np.arange(max_pages)
    done, got, seen_identity = [0, 0, 0], [[] for _ in seqs], 0
    plan = [(16, 9, 0), (16, 0, 7), (1, 0, 7), (1, 0, 7), (1, 0, 7),
            (1, 0, 2), (1, 0, 0)]
    for step in plan + [(1, 0, 0)] * 8:
        ids = np.zeros((t,), np.int32)
        qlens, ctx = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
        for r, n in enumerate(step):
            n = min(n, len(seqs[r]) - done[r])
            at = int(qlens.sum())
            ids[at:at + n] = seqs[r][done[r]:done[r] + n]
            qlens[r], ctx[r] = n, done[r]
        logits, counters = eng.run_paged_program(
            ("test-logits-longcat", b, t), lambda: _logits_program(eng, t),
            ids, qlens, ctx, tables, np.asarray(system.core._scratch,
                                                np.int32))
        total, held, _, _, identity, real_max = map(int, counters)
        # every routed expert is held here: an assignment either computes
        # or is an identity expert's
        assert total == int(qlens.sum()) * TOP_K * 2 == held + identity
        assert 0 <= identity < total and 0 < real_max <= TOP_K
        seen_identity += identity
        starts = np.cumsum(qlens) - qlens
        for r in range(3):
            got[r].append(np.asarray(
                logits[starts[r]:starts[r] + qlens[r]]))
            done[r] += int(qlens[r])
    # 8 of the router's 24 outputs are identity experts
    assert 0.15 < seen_identity / (sum(done) * TOP_K * 2) < 0.5
    for r, seq in enumerate(seqs):
        mine = np.concatenate(got[r])
        assert len(mine) == done[r] >= min(len(seq), 24)
        ref = np.asarray(reference.served_logits(
            cfg, SEED, seq[:done[r]], np.arange(done[r])))
        np.testing.assert_allclose(mine, ref, atol=TOL, rtol=0)


def test_served_tokens_through_engine_core_are_the_references_best(system):
    cfg = system.config
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (70, 6, 33, 18)]
    reqs = [system.submit(p, 6) for p in prompts]
    for p, r in zip(prompts, reqs):
        toks = np.asarray(r.result(timeout=600), np.int32)
        assert len(toks) == 6
        seq = np.concatenate([p, toks[:-1]])
        rows = np.arange(len(p) - 1, len(seq))
        ref = np.asarray(reference.served_logits(cfg, SEED, seq, rows))
        assert (ref.max(-1) - ref[np.arange(len(toks)), toks]).max() <= TOL
    seen = 0
    for s in system.steplog.records():
        if s["kind"] in ("mixed", "decode", "prefill"):
            tokens = s["decode_rows"] + s["prefill_chunk_tokens"]
            assert s["moe_assignments_total"] == tokens * TOP_K * 2
            assert (s["moe_assignments_held"] + s["moe_assignments_identity"]
                    == s["moe_assignments_total"])
            assert 0 < s["moe_real_per_token_max"] <= TOP_K
            # read from the allocated pools: 4 attention sub-layers of one
            # 128-lane float32 row a token, of which 24 lanes are cached
            assert s["cache_bytes_per_token"] == 4 * 128 * 4
            assert s["latent_cache_bytes_per_token"] == 4 * 24 * 4
            seen += s["moe_assignments_identity"]
    snap = system.core.metrics_snapshot()["identity_experts"]
    assert snap["identity_assignments"] >= seen > 0
    # 8 of 24 router outputs are identity experts
    assert 0.15 < snap["identity_share"] < 0.5


def test_a_prefix_cache_hit_serves_all_pools_of_a_layer(system):
    """A second request whose prompt repeats the first's pages is served
    its rows of BOTH sub-layers' pools, in both layers, from the shared
    blocks: its tokens are the reference's best, which with one pool's
    rows stale or missing they would not be."""
    cfg, core = system.config, system.core
    rng = np.random.default_rng(9)
    shared = rng.integers(0, cfg["vocab_size"], 96).astype(np.int32)
    first = np.concatenate([shared, rng.integers(0, 256, 9).astype(np.int32)])
    second = np.concatenate([shared,
                             rng.integers(0, 256, 13).astype(np.int32)])
    system.submit(first, 3).result(timeout=600)
    before = len(system.steplog.records())
    toks = np.asarray(system.submit(second, 6).result(timeout=600), np.int32)
    hits = sum(s["prefix_hit_pages"]
               for s in system.steplog.records()[before:])
    assert hits >= 96 // core._page
    seq = np.concatenate([second, toks[:-1]])
    rows = np.arange(len(second) - 1, len(seq))
    ref = np.asarray(reference.served_logits(cfg, SEED, seq, rows))
    assert (ref.max(-1) - ref[np.arange(len(toks)), toks]).max() <= TOL


# --------------------------------------------------------- share and whole

def test_the_ep_shares_of_one_block_add_up_to_the_uncut_reference():
    """16 published experts over 4 shares of 4 (all n / held of them): each
    share's held experts' part, plus the identity term counted ONCE (the
    identity experts are the token's home chip's), add up to what the
    reference gives for the whole block."""
    cfg = tiny_config()
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         weights_longcat.layer_weights(cfg, SEED, 1, jnp.float32).items()}
    y = jnp.asarray(np.random.default_rng(14).normal(size=(40, 64)),
                    jnp.float32)
    r = rounder("float32")
    whole = np.asarray(reference._experts(y, w, cfg, r, None))
    ids, wts = dropless.route(y, w["router"], TOP_K, 6.0, w["e_bias"],
                              "softmax", False)
    valid = jnp.ones((40,), bool)
    w_id, chosen = dropless.identity_weights(ids, wts, valid, PUBLISHED)
    total, counted = np.asarray(w_id)[:, None] * np.asarray(y), 0
    for first in (0, 4, 8, 12):
        held = {k: w[k][first:first + 4] for k in ("e_gate", "e_up",
                                                   "e_down")}
        part, counts = dropless.dropless_experts(
            y, ids, wts, valid, held["e_gate"], held["e_up"],
            held["e_down"], first)
        total = total + np.asarray(part)
        counted += int(counts.sum())
        # the reference given the same share, its identity term taken off
        share = dict(cfg, experts_held_first=first, n_routed_experts=4)
        ref_part = reference._experts(y, dict(w, **held), share, r,
                                      "no_identity")
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part),
                                   atol=TOL)
    # every assignment once: to a held expert of some share, or identity
    assert counted + int(np.asarray(chosen).sum()) == 40 * TOP_K
    np.testing.assert_allclose(total, whole, atol=TOL)
    # what one share's program layer gives is its part AND the identity
    # term: three of four would be counted thrice too often
    assert np.abs(np.asarray(w_id)).min() >= 0 and np.asarray(w_id).max() > 0


# ------------------------------------------------ configuration and layout

def test_auto_model_builds_it_from_the_sources_config_keys(tmp_path):
    from paddle_infer_tpu.models import AutoConfig, AutoModel

    cfg = tiny_config()
    model = longcat_flash.LongcatFlashForCausalLM(_model_config(cfg))
    model.save_pretrained(str(tmp_path))
    source_keys = {k: v for k, v in cfg.items()
                   if k not in longcat_serving.NOT_MODEL_KEYS}
    assert source_keys["model_type"] == "longcat_flash"
    assert "num_hidden_layers" not in source_keys
    with open(tmp_path / "config.json", "w") as f:
        json.dump(source_keys, f)
    loaded = AutoModel.from_pretrained(str(tmp_path))
    assert type(loaded) is longcat_flash.LongcatFlashForCausalLM
    auto = AutoConfig.from_pretrained(str(tmp_path))
    assert (auto.num_layers, auto.moe_topk, auto.zero_expert_num) == (2, 4, 8)
    ids = Tensor(jnp.arange(7, dtype=jnp.int32)[None])
    np.testing.assert_array_equal(np.asarray(loaded(ids)._data),
                                  np.asarray(model(ids)._data))
    names = {n for n, _ in model.named_parameters()}
    for name in ("model.layers.1.self_attn.1.kv_b_proj.weight",
                 "model.layers.0.mlps.1.down_proj.weight",
                 "model.layers.0.input_layernorm.1.weight",
                 "model.layers.1.post_attention_layernorm.0.weight",
                 "model.layers.0.mlp.e_score_correction_bias"):
        assert name in names, name
    assert dict(model.named_parameters())[
        "model.layers.0.mlp.gate_weight"]._data.shape == (64, 24)


@pytest.mark.parametrize("key, value, says", [
    ("zero_expert_type", "copy", "zero_expert_type='copy'"),
    ("attention_method", "GQA", "attention_method='GQA'"),
    ("router_bias", True, "router_bias=True"),
    ("hidden_act", "gelu", "hidden_act='gelu'"),
    ("tie_word_embeddings", True, "tie_word_embeddings=True"),
])
def test_what_the_decoder_is_not_built_for_is_refused_by_name(key, value,
                                                              says):
    with pytest.raises(NotImplementedError, match=says):
        longcat_flash.LongcatFlashConfig(**{key: value})


def test_the_sigmoid_family_names_the_class_that_takes_softmax():
    from paddle_infer_tpu.models.latent_moe import LatentMoEConfig

    for kw in (dict(scoring_func="softmax"), dict(norm_topk_prob=False)):
        with pytest.raises(NotImplementedError,
                           match="LongcatFlashForCausalLM"):
            LatentMoEConfig(**kw)


def test_cache_layout_states_two_latent_pools_a_layer(system):
    layout = layout_of(system.engine._model)
    assert layout == [LayerCache.latent(24, part=0),
                      LayerCache.latent(24, part=1)] * 2
    assert all(c.one_pool and c.lanes == 128 for c in layout)
    k_pages, v_pages = system.engine._ensure_pages()
    assert len(k_pages) == 4 and v_pages == [None] * 4
    assert len({id(p) for p in k_pages}) == 4
    assert k_pages[0].shape == (system.core._pool.num_blocks, 16, 128)
    # the engine counts CACHE layers off the layout, not decoder layers
    assert system.engine._num_layers == 4
    assert not hasattr(system.engine._model.config, "num_hidden_layers")


TWIN = [LayerCache.latent(576, part=0), LayerCache.latent(576, part=1)]
REFUSALS = {
    "mp": (dict(mp=2), "no head axis to split, in either of a decoder "
           "layer's two pools"),
    "int8": (dict(kv_dtype="int8"), "no heads to scale over, in either of "
             "a decoder layer's two pools"),
    "speculate": (dict(speculate=True), "for either of a decoder layer's "
                  "two attention sub-layers"),
    "host tier": (dict(kv_host_pages=8), "host KV tier.*two latent pools, "
                  "one an attention sub-layer"),
    "handoff": (dict(handoff=True), "KV handoff.*two latent pools, one an "
                "attention sub-layer"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_two_latent_pools_a_layer_cannot_do_is_refused_at_start_up(what):
    from paddle_infer_tpu.serving.sharded import (ShardedConfigError,
                                                  validate_cache_layout)

    kw, says = REFUSALS[what]
    with pytest.raises(ShardedConfigError, match=says):
        validate_cache_layout(TWIN * 4, **kw)
    validate_cache_layout(TWIN * 4)          # silent for what it can do


def test_the_adapter_refuses_a_program_that_cannot_be_this_model(monkeypatch):
    """A program whose decoder layer lacks the second dense block (or
    anything else a seeded array is made for) is refused before a single
    array is made."""
    made = []
    monkeypatch.setattr(weights_longcat, "all_weights",
                        lambda *a, **k: made.append(a))
    monkeypatch.setattr(longcat_flash, "SUB_LAYERS", 1)
    s = longcat_serving.System(tiny_config(), jax.devices()[:1], SEED, False)
    with pytest.raises(KeyError, match="cannot be this model"):
        s.build()
    assert not made


def test_tools_serve_serves_it_over_http_with_the_prefix_cache_on(tmp_path):
    """The front door: ``tools/serve.py`` over a directory holding the
    source's own config keys, prefix cache on; greedy ``/generate`` gives
    the tokens of the eager model's own greedy loop, twice (the second
    time from shared pages of every pool)."""
    cfg = tiny_config()
    model = longcat_flash.LongcatFlashForCausalLM(_model_config(cfg))
    model.eval()
    model.save_pretrained(str(tmp_path))
    with open(tmp_path / "config.json", "w") as f:
        json.dump({k: v for k, v in cfg.items()
                   if k not in longcat_serving.NOT_MODEL_KEYS}, f)
    prompt = np.random.default_rng(17).integers(0, 256, 60)
    seq = list(map(int, prompt))
    for _ in range(5):
        logits = model(Tensor(jnp.asarray([seq], jnp.int32)))._data[0, -1]
        seq.append(int(jnp.argmax(logits)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
         "--model_dir", str(tmp_path), "--port", str(port), "--max_batch",
         "2", "--max_model_len", "128", "--token_budget", "32",
         "--enable_prefix_cache"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    url = f"http://127.0.0.1:{port}"
    try:
        for _ in range(180):
            try:
                with urllib.request.urlopen(url + "/health", timeout=2) as r:
                    if json.load(r)["status"] == "ok":
                        break
            except Exception:
                if proc.poll() is not None:
                    raise RuntimeError(proc.stderr.read()[-1500:])
                time.sleep(1)
        else:
            raise RuntimeError("server never became healthy")
        for _ in range(2):
            req = urllib.request.Request(
                url + "/generate", data=json.dumps(
                    {"ids": [seq[:60]], "max_new_tokens": 5}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                got = json.load(r)["tokens"][0]
            assert got[-5:] == seq[60:]
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            snap = json.load(r)
        assert 0 < snap["identity_experts"]["identity_assignments"] \
            < snap["identity_experts"]["assignments"]
        assert snap["prefix_cache"]["hits"] >= 1
        assert snap["prefix_cache"]["cached_tokens"] >= 48
    finally:
        proc.terminate()
        proc.wait(timeout=30)
