"""Manifold-constrained hyper-connections and bias-corrected routing
(``model_type: xing4_0``) on the serving path, at a small size in float32
on the CPU: the program against the plain reference
(benchmarks/reference/xing4.py), each planted fault against the same
comparison, the kernel against a plain loop, and the plain residual's
program untouched.  (``EngineCore``'s own step serves the same model in
tests/benchmarks/test_bench_xing4_cpu.py, whose check compares the served
tokens' logits with the reference's and reads the StepLog's counters.)"""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import xing4 as reference        # noqa: E402
from benchmarks.systems import xing4_serving               # noqa: E402
from paddle_infer_tpu.models import latent_moe             # noqa: E402
from paddle_infer_tpu.nn import hyper_connections as HC    # noqa: E402
from paddle_infer_tpu.ops.pallas.mhc_maps import mhc_maps  # noqa: E402
from paddle_infer_tpu.serving.moe import dropless          # noqa: E402
from paddle_infer_tpu.serving.moe import stats as moe_stats  # noqa: E402

SEED = 2 ** 31 + 38
# float32 throughout: program and reference then differ by summation
# order alone (the absorbed attention's reassociation, the kernel's
# online softmax, the maps' divisions), which at these sizes stays under
# 2e-5 on every logit; 1e-4 leaves most of a decade.  The smallest
# planted fault (the maps in bfloat16) moves some logit by 1.5e-3 and
# more: fifteen times the tolerance.
TOL = 1e-4


def _data(name):
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", name)) as f:
        return json.load(f)


def tiny_config(**over):
    cfg = _data("tiny-xing4.json")
    cfg.update(torch_dtype="float32")
    cfg.update(over)
    return cfg


def _model_config(cfg):
    return latent_moe.LatentMoEConfig(**{
        k: v for k, v in cfg.items()
        if k not in xing4_serving.NOT_MODEL_KEYS})


@pytest.fixture(scope="module")
def system():
    s = xing4_serving.System(tiny_config(), jax.devices()[:1], SEED, False)
    s.build()
    yield s
    s.free()


def _logits_program(engine, tokens):
    """The mixed step's model call over its flat token axis, every slot's
    logits returned, both side channels open as the served step has
    them."""
    from paddle_infer_tpu.ops.pallas.ragged_paged_attention import \
        ragged_rows
    from paddle_infer_tpu.serving.programs import (_layer_caches,
                                                   _layer_pools)

    def run(params, ids, qlens, ctx, tables, scratch, k_pages, v_pages):
        caches = _layer_caches(engine, k_pages, v_pages, tables, ctx, qlens,
                               scratch)
        _, row, offset, valid = ragged_rows(qlens, tokens)
        pos = jnp.where(valid, ctx[row] + offset, 0)
        with HC.collect_stats(valid) as res, \
                moe_stats.collect(valid, max_valid=tokens) as col:
            logits, caches = engine._model_step(params, ids[None], pos[None],
                                                None, caches)
        return (logits[0], *res.totals(), col.totals()[0],
                *_layer_pools(engine, caches))

    return jax.jit(run, donate_argnums=(6, 7))


@pytest.fixture(scope="module")
def program_logits(system):
    """Three rows through the latent cache — chunked prefill, then decode,
    rows of different lengths and kinds in one step — and every slot's
    logits: ``[(sequence, logits [len, vocab])]``, the counters of each
    step beside them."""
    eng, cfg = system.engine, system.config
    b, t = 4, 32
    max_pages = system.core._max_pages
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (45, 9, 30)]
    tables = np.full((b, max_pages), system.core._scratch, np.int32)
    for r in range(3):
        tables[r] = 1 + r * max_pages + np.arange(max_pages)
    done, got, counters = [0, 0, 0], [[] for _ in seqs], []
    plan = [(16, 9, 0), (16, 0, 7), (1, 0, 7), (1, 0, 7), (1, 0, 7),
            (1, 0, 2), (1, 0, 0)] + [(1, 0, 0)] * 4
    for step in plan:
        ids = np.zeros((t,), np.int32)
        qlens = np.zeros((b,), np.int32)
        ctx = np.zeros((b,), np.int32)
        for r, n in enumerate(step):
            n = min(n, len(seqs[r]) - done[r])
            at = int(qlens.sum())
            ids[at:at + n] = seqs[r][done[r]:done[r] + n]
            qlens[r], ctx[r] = n, done[r]
        logits, gap, streams, nbytes, assigned = eng.run_paged_program(
            ("test-hc-logits", b, t), lambda: _logits_program(eng, t),
            ids, qlens, ctx, tables,
            np.asarray(system.core._scratch, np.int32))
        counters.append((float(gap), int(streams), int(nbytes),
                         int(assigned), int(qlens.sum())))
        starts = np.cumsum(qlens) - qlens
        for r in range(3):
            got[r].append(np.asarray(
                logits[starts[r]:starts[r] + qlens[r]]))
            done[r] += int(qlens[r])
    return [(seq[:done[r]], np.concatenate(got[r]))
            for r, seq in enumerate(seqs)], counters


def _reference(cfg, seq, precision="float32"):
    return np.asarray(reference.served_logits(cfg, SEED, seq,
                                              np.arange(len(seq)),
                                              precision))


def test_program_logits_match_the_reference_through_the_latent_cache(
        system, program_logits):
    cases, counters = program_logits
    for seq, mine in cases:
        assert len(mine) == len(seq) >= 9
        np.testing.assert_allclose(mine, _reference(system.config, seq),
                                   atol=TOL, rtol=0)
    for gap, streams, nbytes, assigned, tokens in counters:
        # 20 rounds leave the columns within a few float32 roundings of
        # the rows; the streams are [4, 64] float32 here
        assert 0.0 <= gap < 1e-4
        assert (streams, nbytes) == (4, 4 * 64 * 4)
        assert assigned == tokens * 2 * 2       # top-2 in 2 expert layers


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_fails_the_same_comparison(system, program_logits,
                                                   fault):
    """The reference with one piece of the mathematics altered — one
    Sinkhorn round for twenty, the row step left out, ``H_post`` without
    its factor 2, the bias dropped from the choice, the bias leaking into
    the weights, the maps in bfloat16 — is NOT what the program computes:
    the comparison above fails on every sequence."""
    cases, _ = program_logits
    for seq, mine in cases:
        low = _reference(system.config, seq, fault)
        assert np.abs(mine - low).max() > 10 * TOL, fault


def _plain_maps(z, scale, bias, n, iters, eps, lo, hi):
    a = z * scale + bias
    m = np.exp(np.clip(a[:, 2 * n:], lo, hi)).reshape(-1, n, n)
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)
        m = m / (m.sum(2, keepdims=True) + eps)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    return sig(a[:, :n]), 2.0 * sig(a[:, n:2 * n]), m


@pytest.mark.parametrize("tokens,n,iters", [(70, 4, 20), (128, 4, 20),
                                            (9, 2, 20), (600, 4, 3)])
def test_mhc_maps_kernel_equals_a_plain_loop(tokens, n, iters):
    rng = np.random.default_rng(tokens)
    w = n * n + 2 * n
    # logits wide enough that both clamps are hit on purpose
    z = (rng.normal(size=(tokens, w)) * 15).astype(np.float32)
    scale = rng.uniform(0.5, 3.0, size=w).astype(np.float32)
    bias = rng.normal(size=w).astype(np.float32)
    a_res = (z * scale + bias)[:, 2 * n:]
    assert (a_res > 30).any() and (a_res < -30).any()
    got = np.asarray(mhc_maps(jnp.asarray(z), jnp.asarray(scale),
                              jnp.asarray(bias), n, iters, 1e-6, -30.0, 30.0))
    assert got.shape == (tokens, w) and np.isfinite(got).all()
    pre, post, res = _plain_maps(z.astype(np.float64), scale, bias, n, iters,
                                 1e-6, -30.0, 30.0)
    np.testing.assert_allclose(got[:, :n], pre, atol=2e-6)
    np.testing.assert_allclose(got[:, n:2 * n], post, atol=4e-6)
    h_res = got[:, 2 * n:].reshape(tokens, n, n)
    np.testing.assert_allclose(h_res, res, atol=2e-5)
    assert (h_res >= 0).all()
    col_gap = np.abs(h_res.sum(1) - 1).max()
    assert col_gap == pytest.approx(np.abs(res.sum(1) - 1).max(), abs=1e-5)
    # logits as the model makes them (a standard deviation of 2.6): the
    # row step is the last, so rows sum to one but for hc_eps in the
    # divisor; columns to what the rounds leave, which is what the
    # StepLog's counter reads
    mild = np.asarray(mhc_maps(jnp.asarray(z / 6), jnp.asarray(scale / scale),
                               jnp.asarray(bias), n, iters, 1e-6, -30.0,
                               30.0))[:, 2 * n:].reshape(tokens, n, n)
    assert np.abs(mild.sum(2) - 1).max() < 1e-5
    want = _plain_maps(z.astype(np.float64) / 6, 1.0, bias, n, iters, 1e-6,
                       -30.0, 30.0)[2]
    assert np.abs(mild.sum(1) - 1).max() == pytest.approx(
        np.abs(want.sum(1) - 1).max(), abs=1e-5)
    if iters >= 20:
        # twenty rounds are not convergence: some token in a hundred keeps
        # a column a few hundredths off
        assert np.abs(mild.sum(1) - 1).max() < 0.2


def test_bias_decides_the_choice_and_not_the_weights():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 8)) * 0.5, jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.3, jnp.float32)
    ids0, w0 = dropless.route(x, gate, 2, 2.0)
    ids, w = dropless.route(x, gate, 2, 2.0, bias)
    s = np.asarray(jax.nn.sigmoid(x @ gate))
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :2]
    assert (np.sort(np.asarray(ids), 1) == np.sort(want, 1)).all()
    changed = (np.sort(np.asarray(ids), 1)
               != np.sort(np.asarray(ids0), 1)).any(1)
    assert changed.mean() > 0.2
    chosen = np.take_along_axis(s, np.asarray(ids), 1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(1, keepdims=True) * 2.0,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.0, rtol=1e-6)
    # no bias: the function is what it was
    ids1, w1 = dropless.route(x, gate, 2, 2.0, None)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))


REFUSED = {
    "noaux_tc with n_group 2": (dict(topk_method="noaux_tc", n_group=2,
                                     topk_group=1), "n_group=2"),
    "noaux_tc with topk_group 2": (dict(topk_method="noaux_tc", n_group=1,
                                        topk_group=2), "topk_group=2"),
    "group_limited_greedy": (dict(topk_method="group_limited_greedy"),
                             "group_limited_greedy"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_grouped_routing_is_refused_by_name(what):
    kw, says = REFUSED[what]
    with pytest.raises(NotImplementedError, match=says):
        latent_moe.LatentMoEConfig(**kw)
    # one group is the bias alone; no method at all is today's
    latent_moe.LatentMoEConfig(topk_method="noaux_tc", n_group=1,
                               topk_group=1)
    latent_moe.LatentMoEConfig(topk_method="noaux_tc")
    latent_moe.LatentMoEConfig(topk_method="none", n_group=8, topk_group=4)


@functools.lru_cache(maxsize=None)
def _layer_program(hc_mult=None):
    """(jaxpr text, lowered text) of one expert layer's eager forward."""
    cfg_over = {} if hc_mult is None else {"hc_mult": hc_mult}
    from paddle_infer_tpu.core.tensor import Tensor
    from paddle_infer_tpu.nn.initializer import abstract_parameters

    cfg = _data("tiny-axk1.json")
    cfg.update(torch_dtype="float32", **cfg_over)
    mcfg = _model_config(cfg)
    with abstract_parameters():
        layer = latent_moe.LatentMoEDecoderLayer(mcfg, 1)
    names = [n for n, _ in layer.named_parameters()]
    shapes = [jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)
              for _, p in layer.named_parameters()]
    streams = (mcfg.hc_mult,) if mcfg.hc_mult > 1 else ()

    def run(params, x):
        out = layer.functional_call(dict(zip(names, params)), Tensor(x))
        return out._data

    x = jax.ShapeDtypeStruct((1, 8) + streams + (64,), jnp.float32)
    return (str(jax.make_jaxpr(run)(shapes, x)),
            jax.jit(run).lower(shapes, x).as_text())


@pytest.mark.parametrize("hc_mult", [None, 1],
                         ids=["hc_mult absent", "hc_mult 1"])
def test_the_plain_residual_builds_no_map_and_no_stream(hc_mult):
    """What switches the residual is ``hc_mult`` and nothing else: absent
    or 1 (with ``topk_method: "none"``) the layer holds no ``mhc_maps``
    call and no four-stream array, and is the same program either way."""
    jaxpr, text = _layer_program(hc_mult)
    assert "name=mhc_maps" not in jaxpr
    assert "x4x64x" not in text and "[1,8,4,64]" not in jaxpr
    jaxpr4, text4 = _layer_program(4)
    assert jaxpr4.count("name=mhc_maps") == 2        # one a sub-layer
    assert "x4x64x" in text4 and "[1,8,4,64]" in jaxpr4
    assert text == _layer_program()[1]


def test_auto_model_builds_the_sources_config(tmp_path):
    """The catalog row's keys as published (``model_type: xing4_0``, no
    "architecture"), at the tiny widths: ``AutoModel`` resolves the family,
    the hyper-connections and the router's bias are parameters, and the
    eager forward (expanded attention, no cache) is the reference's."""
    from paddle_infer_tpu.core.tensor import Tensor
    from paddle_infer_tpu.models import AutoConfig, AutoModel

    cfg = tiny_config()
    model = latent_moe.LatentMoEForCausalLM(_model_config(cfg))
    model.save_pretrained(str(tmp_path))
    source_keys = {k: v for k, v in cfg.items()
                   if k not in xing4_serving.NOT_MODEL_KEYS}
    assert source_keys["model_type"] == "xing4_0"
    with open(tmp_path / "config.json", "w") as f:
        json.dump(source_keys, f)
    loaded = AutoModel.from_pretrained(str(tmp_path))
    assert type(loaded) is latent_moe.LatentMoEForCausalLM
    auto = AutoConfig.from_pretrained(str(tmp_path))
    assert (auto.hc_mult, auto.topk_method) == (4, "noaux_tc")
    assert auto.num_nextn_predict_layers == 1        # carried, not built
    names = {n for n, _ in loaded.named_parameters()}
    assert "model.layers.0.hc_attn.phi" in names
    assert "model.layers.3.hc_ffn.bias" in names
    assert "model.layers.2.mlp.experts.e_score_correction_bias" in names
    assert not [n for n in names if "nextn" in n or "mtp" in n]
    info = HC.hyper_connection_info(loaded)
    assert info == {"streams": 4, "hidden": 64, "sublayers": 8}
    ids = Tensor(jnp.arange(7, dtype=jnp.int32)[None])
    np.testing.assert_array_equal(np.asarray(loaded(ids)._data),
                                  np.asarray(model(ids)._data))
