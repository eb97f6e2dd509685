"""Latent attention over an indexer's selection (``model_type:
glm_moe_dsa``) on the serving path, at a small size on the CPU with
``index_topk`` 8 to 16 against contexts of 3 to 10 times that: the two
kernels and the chunk composition against plain ``jax.numpy``, the
program through the two-pool paged cache against the plain reference
(benchmarks/reference/glm5.py), the controls that must NOT pass, a
prefix-cache hit that serves both pools, share against whole."""
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

from benchmarks import weights_glm5                          # noqa: E402
from benchmarks.reference import glm5 as reference           # noqa: E402
from benchmarks.systems import glm5_serving                  # noqa: E402
from paddle_infer_tpu.core.tensor import Tensor              # noqa: E402
from paddle_infer_tpu.inference.cache_layout import (        # noqa: E402
    LayerCache, has_index, layout_of)
from paddle_infer_tpu.models import latent_moe               # noqa: E402
from paddle_infer_tpu.ops.pallas import latent_attention as LA   # noqa: E402
from paddle_infer_tpu.ops.pallas import \
    sparse_latent_attention as SA                            # noqa: E402

SEED = 2 ** 31 + 44
# float32 throughout: the program and the reference then differ by
# summation order alone (2e-7 at these sizes); 1e-4 leaves room for the
# absorbed form's reassociation and the kernels' online softmax, and is
# two thousand times under what either control reads
TOL = 1e-4


def tiny_config(**over):
    with open(os.path.join(ROOT, "tests", "benchmarks", "data",
                           "tiny-glm5.json")) as f:
        cfg = json.load(f)
    cfg.update(torch_dtype="float32", n_routed_experts=16,
               n_routed_experts_published=16, experts_held_first=0)
    cfg.update(over)
    return cfg


def _model_config(cfg):
    return latent_moe.LatentMoEConfig(**{
        k: v for k, v in cfg.items()
        if k not in glm5_serving.NOT_MODEL_KEYS})


@pytest.fixture(scope="module")
def system():
    s = glm5_serving.System(tiny_config(), jax.devices()[:1], SEED, False)
    s.build()
    yield s
    s.free()


# ------------------------------------------------------------ the kernels

PAGE, MAX_PAGES, LANES = 4, 12, 128            # a window of 48 tokens


def _pools(rng, rows, width, index_width):
    """Pools whose pages a row's table names in a scrambled order."""
    n = rows * MAX_PAGES + 1
    pool = np.zeros((n, PAGE, LANES), np.float32)
    pool[..., :width] = rng.normal(size=(n, PAGE, width))
    ipool = np.zeros((n, PAGE, LANES), np.float32)
    ipool[..., :index_width] = rng.normal(size=(n, PAGE, index_width))
    tables = 1 + rng.permutation(rows * MAX_PAGES).reshape(rows, MAX_PAGES)
    return (jnp.asarray(pool), jnp.asarray(ipool),
            jnp.asarray(tables, jnp.int32))


def _row_tokens(pool, tables, b, n):
    """The first ``n`` cached vectors of row ``b``, in position order."""
    return np.asarray(pool)[np.asarray(tables)[b]].reshape(
        MAX_PAGES * PAGE, -1)[:n]


def _plain_scores(q_idx, w_idx, keys):
    """[Hi, d], [Hi], [n, d] -> [n]"""
    return (np.maximum(keys @ q_idx.T, 0.0) * w_idx[None]).sum(-1)


def _plain_order(scores, k):
    """Positions of the k largest, best first, ties to the earlier
    position."""
    return sorted(range(len(scores)), key=lambda s: (-scores[s], s))[:k]


def _plain_topk(scores, k):
    """The same positions, in position order."""
    return sorted(_plain_order(scores, k))


def _plain_attention(q, rows, scale, value_width):
    """[H, w], [n, w] -> [H, value_width]"""
    s = q @ rows.T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :value_width]


# contexts below, at and above the top-k of 8; a row ending mid-page (21,
# 47) and one on a page's edge (8, 48); a dead row
LENGTHS = (5, 8, 21, 0, 47, 48)
# dead rows BETWEEN live ones, as a step's decode rows lie among chunk and
# idle rows; a step with no decode row at all
SCATTERED = (47, 0, 5, 0, 0, 30, 0, 12)
NO_DECODE_ROW = (0, 0, 0, 0)


def test_index_scores_kernel_against_plain_numpy():
    rng = np.random.default_rng(1)
    b, hi, di = len(LENGTHS), 4, 16
    _, ipool, tables = _pools(rng, b, 24, di)
    q = jnp.asarray(rng.normal(size=(b, hi, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, hi)), jnp.float32)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    # four pages a grid step: the walk of the longest row is three steps
    got = np.asarray(SA.dsa_index_scores(q, w, ipool, tables, lengths,
                                         pages_per_step=4))
    assert got.shape == (b, MAX_PAGES * PAGE)
    for r, n in enumerate(LENGTHS):
        want = _plain_scores(np.asarray(q[r]), np.asarray(w[r]),
                             _row_tokens(ipool, tables, r, n)[:, :di])
        np.testing.assert_allclose(got[r, :n], want, atol=1e-5)
        assert np.isneginf(got[r, n:]).all()
    # a grid step of more pages than the table has is the whole table
    whole = np.asarray(SA.dsa_index_scores(q, w, ipool, tables, lengths))
    np.testing.assert_allclose(whole, got, atol=1e-5)


@pytest.mark.parametrize("lens", [LENGTHS, SCATTERED, NO_DECODE_ROW],
                         ids=["live-first", "dead-between", "no-decode-row"])
def test_selection_and_sparse_decode_against_plain_numpy(lens):
    rng = np.random.default_rng(2)
    b, h, width, vw, k = len(lens), 4, 24, 16, 8
    pool, _, tables = _pools(rng, b, width, 16)
    q = jnp.asarray(rng.normal(size=(b, h, width)), jnp.float32)
    scores = rng.normal(size=(b, MAX_PAGES * PAGE)).astype(np.float32)
    if 47 in lens:
        # tied scores across the k-th place: positions 3, 9, 10 and 30 of
        # the 47-token row share the value that ranks 7th to 10th, so 3
        # and 9 are kept and 10 and 30 are not
        row = lens.index(47)
        top = np.sort(scores[row, :47])[::-1]
        scores[row, [3, 9, 10, 30]] = (top[5] + top[6]) / 2
    lengths = jnp.asarray(lens, jnp.int32)
    masked = jnp.where(jnp.arange(scores.shape[1])[None] < lengths[:, None],
                       jnp.asarray(scores), -jnp.inf)
    rows, counts, pos = SA.select_rows(masked, lengths, pool, tables, k)
    assert rows.shape == (b, k, LANES) and pos.shape == (b, k)
    assert list(np.asarray(counts)) == [min(n, k) for n in lens]
    out = np.asarray(SA.dsa_sparse_decode(q, rows, counts, 0.3, vw))
    rows, pos = np.asarray(rows), np.asarray(pos)
    alive = [r for r, n in enumerate(lens) if n]
    # the gathered buffer holds the live rows at the front, in row order,
    # and nothing behind them: the loop made one trip a live row
    assert not rows[len(alive):].any()
    for r, n in enumerate(lens):
        if not n:
            assert not out[r].any()
            continue
        kept = min(n, k)
        want = _plain_order(scores[r, :n], k)
        assert list(pos[r, :kept]) == want
        chosen = _row_tokens(pool, tables, r, n)[want]
        np.testing.assert_array_equal(rows[alive.index(r), :kept], chosen)
        np.testing.assert_allclose(
            out[r], _plain_attention(np.asarray(q[r]), chosen[:, :width],
                                     0.3, vw), atol=1e-5)
    if 47 in lens:
        kept = set(pos[row, :k])
        assert {3, 9} <= kept and not {10, 30} & kept
    # against dense attention: the same where the context fits the top-k,
    # another answer where it does not
    dense = np.asarray(LA.latent_paged_decode(q, pool, tables, lengths, 0.3,
                                              vw))
    for r, n in enumerate(lens):
        if 0 < n <= k:
            np.testing.assert_allclose(out[r], dense[r], atol=1e-5)
        elif n > k:
            assert np.abs(out[r] - dense[r]).max() > 1e-2


def test_selection_mask_keeps_exactly_k_and_the_earlier_of_equals():
    scores = jnp.asarray([[1.0, 5.0, 5.0, 2.0, 5.0, 5.0, 0.0, 9.0],
                          [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
                          [3.0, 1.0, 2.0, -jnp.inf, -jnp.inf, -jnp.inf,
                           -jnp.inf, -jnp.inf]])
    valid = jnp.isfinite(scores)
    got = np.asarray(SA.selection_mask(scores, valid, 4))
    assert got.tolist() == [
        [False, True, True, False, True, False, False, True],
        [True, True, True, True, False, False, False, False],
        [True, True, True, False, False, False, False, False]]


@pytest.mark.parametrize("ctx, qlens", [
    ([3, 30, 0, 19], [9, 1, 0, 11]),
    # decode rows among idle ones and a chunk row, one of them under the
    # top-k: the gathered buffer's slots are not the rows' numbers
    ([0, 40, 0, 3, 4, 0, 20], [0, 1, 0, 9, 1, 0, 1]),
], ids=["two-chunks", "scattered-decode-rows"])
def test_chunk_rows_select_per_query_token_and_tile_the_window(ctx, qlens):
    """The mixed step's composition against plain numpy: chunk rows beside
    decode rows and dead ones, contexts that start below the top-k and
    end above it, tiles narrower than the context."""
    rng = np.random.default_rng(3)
    b, h, width, vw, hi, di, k, t = len(ctx), 4, 24, 16, 4, 16, 8, 24
    pool, ipool, tables = _pools(rng, b, width, di)
    q = jnp.asarray(rng.normal(size=(t, h, width)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(t, hi, di)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(t, hi)), jnp.float32)
    ctx = jnp.asarray(ctx, jnp.int32)
    qlens = jnp.asarray(qlens, jnp.int32)
    got = np.asarray(SA.dsa_ragged_attention(
        q, qi, wi, pool, ipool, tables, ctx, qlens, 0.3, vw, k))
    tiled = np.asarray(SA.dsa_chunk_attention(
        q, qi, wi, pool, ipool, tables, ctx, qlens, 0.3, vw, k, tile=8))
    at = 0
    for r in range(b):
        for i in range(int(qlens[r])):
            n = int(ctx[r]) + i + 1
            keys = _row_tokens(ipool, tables, r, n)[:, :di]
            want = _plain_topk(_plain_scores(np.asarray(qi[at]),
                                             np.asarray(wi[at]), keys), k)
            rows = _row_tokens(pool, tables, r, n)[want][:, :width]
            o = _plain_attention(np.asarray(q[at]), rows, 0.3, vw)
            np.testing.assert_allclose(got[at], o, atol=1e-5)
            if qlens[r] > 1:
                np.testing.assert_allclose(tiled[at], o, atol=1e-5)
            at += 1
    assert not got[at:].any()


# ------------------------------------------------- the cache's description

def test_a_latent_layer_with_an_index_states_two_pools_under_one_table():
    c = LayerCache.latent(width=576, index_width=128)
    assert c.pool_shapes(7, 16) == ((7, 16, 640), (7, 16, 128))
    assert (c.values_per_token(), c.stored_per_token()) == (704, 768)
    assert c.head_axis() is None
    first, second, rest = object(), object(), (1, 2, 3)
    assert c.step_cache(first, second, *rest) == (first, second, *rest)
    assert c.pools_of((first, second, *rest)) == (first, second)
    # without an index width: what the layer was before
    plain = LayerCache.latent(576)
    assert plain.pool_shapes(7, 16) == ((7, 16, 640), None)
    assert plain.step_cache(first, None, *rest) == (first, *rest)
    assert (plain.values_per_token(), plain.stored_per_token()) == (576, 640)
    assert has_index([c]) and not has_index([plain, LayerCache.kv(4, 8)])


REFUSALS = {
    "mp": (dict(mp=2), "one vector a token for every index head"),
    "int8": (dict(kv_dtype="int8"), "index-key pool is stored in the served"),
    "speculate": (dict(speculate=True), "index scores, sparse decode"),
    "host tier": (dict(kv_host_pages=8), "index-key pool of another width"),
    "handoff": (dict(handoff=True), "index-key pool of another width"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_the_pair_of_pools_cannot_do_is_refused_at_start_up(what):
    from paddle_infer_tpu.serving.sharded import (ShardedConfigError,
                                                  validate_cache_layout)

    kw, says = REFUSALS[what]
    pair = [LayerCache.latent(24, index_width=16)]
    with pytest.raises(ShardedConfigError, match=says):
        validate_cache_layout(pair, **kw)
    validate_cache_layout(pair)


# ------------------------------------------------- program against reference

def _logits_program(engine, tokens):
    """The mixed step's model call over its flat token axis, returning
    every slot's logits."""
    from paddle_infer_tpu.ops.pallas.ragged_paged_attention import \
        ragged_rows
    from paddle_infer_tpu.serving.programs import (_layer_caches,
                                                   _layer_pools)

    def run(params, ids, qlens, ctx, tables, scratch, k_pages, v_pages):
        caches = _layer_caches(engine, k_pages, v_pages, tables, ctx, qlens,
                               scratch)
        _, row, offset, valid = ragged_rows(qlens, tokens)
        pos = jnp.where(valid, ctx[row] + offset, 0)
        logits, caches = engine._model_step(params, ids[None], pos[None],
                                            None, caches)
        return (logits[0], *_layer_pools(engine, caches))

    return jax.jit(run, donate_argnums=(6, 7))


def _through_the_cache(system, seqs, plan, t=32):
    """Drive the step program by hand: ``plan`` gives each row's query
    tokens a step.  -> per row, the logits of every position fed."""
    eng, b = system.engine, 4
    max_pages = system.core._max_pages
    tables = np.full((b, max_pages), system.core._scratch, np.int32)
    for r in range(len(seqs)):
        tables[r] = 1 + r * max_pages + np.arange(max_pages)
    done = [0] * len(seqs)
    got = [[] for _ in seqs]
    for step in plan:
        ids = np.zeros((t,), np.int32)
        qlens = np.zeros((b,), np.int32)
        ctx = np.zeros((b,), np.int32)
        for r, n in enumerate(step):
            n = min(n, len(seqs[r]) - done[r])
            at = int(qlens.sum())
            ids[at:at + n] = seqs[r][done[r]:done[r] + n]
            qlens[r], ctx[r] = n, done[r]
        logits = eng.run_paged_program(
            ("test-logits", b, t), lambda: _logits_program(eng, t),
            ids, qlens, ctx, tables,
            np.asarray(system.core._scratch, np.int32))[0]
        starts = np.cumsum(qlens) - qlens
        for r in range(len(seqs)):
            got[r].append(np.asarray(logits[starts[r]:starts[r] + qlens[r]]))
            done[r] += int(qlens[r])
    return [np.concatenate(g) for g in got], done


# row 0 prefills 120 tokens in chunks of 16 and then decodes; row 1 is one
# short chunk (under the top-k of 12 throughout) and then decodes; row 2
# joins late with chunks of 7 and decodes from 70 on, to 78
PLAN = ([(16, 9, 0)] + [(16, 0, 7)] * 6 + [(7, 0, 7)] + [(1, 0, 7)] * 3
        + [(1, 0, 1)] * 8)


def test_program_logits_match_the_reference_through_both_pools(system):
    """Chunked prefill, then decode through the paged cache, contexts of
    up to ten times ``index_topk``: tight logits against the reference's
    full forward, and both controls far outside the tolerance."""
    cfg = system.config
    assert cfg["index_topk"] == 12 and has_index(system.engine._cache_layout)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (128, 9, 78)]
    got, done = _through_the_cache(system, seqs, PLAN)
    assert done == [128, 9, 78]
    for r, seq in enumerate(seqs):
        rows = np.arange(done[r])
        ref = np.asarray(reference.served_logits(cfg, SEED, seq, rows))
        np.testing.assert_allclose(got[r], ref, atol=TOL, rtol=0)
        for fault in reference.FAULTS:
            bad = np.asarray(reference.served_logits(cfg, SEED, seq, rows,
                                                     fault))
            gap = np.abs(bad - ref).max(-1)
            if done[r] <= cfg["index_topk"]:
                # the indexer chooses everything: the dense layer
                assert gap.max() <= TOL
            else:
                # the selection left out, or a random set of the same size
                # in its place: two thousand tolerances away at the widest
                # (0.27 to 0.41), a thousand at the median position
                assert gap[cfg["index_topk"] + 4:].max() > 2000 * TOL
                assert np.median(gap[cfg["index_topk"] + 4:]) > 1000 * TOL
    # absorbed and selected by (page, slot) (the served path) against
    # expanded under a dense mask (the eager forward)
    eager = system.engine._model(Tensor(jnp.asarray(seqs[2][None])))._data[0]
    np.testing.assert_allclose(got[2], np.asarray(eager), atol=TOL, rtol=0)


def _layer0_selection(model, x, dtype):
    """The program's own choice in its first layer for the sequence whose
    embeddings are ``x`` [T, hidden]: bool [T, T]."""
    layer = model.model.layers[0]
    attn = layer.self_attn
    h = layer.input_layernorm(Tensor(x[None].astype(dtype)))
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    rope = (attn.inv_freq, attn.rope_mscale)
    q_idx, w_idx = attn.indexer.queries(h, attn._query_latent(h), pos, rope)
    k_idx = attn.indexer.keys(h, pos, rope)
    sc = jnp.einsum("qhd,kd->qhk", q_idx[0], k_idx[0],
                    preferred_element_type=jnp.float32)
    sc = jnp.sum(jnp.maximum(sc, 0.0) * w_idx[0][..., None], axis=1)
    valid = pos[0][None, :] <= pos[0][:, None]
    return np.asarray(SA.selection_mask(jnp.where(valid, sc, -jnp.inf),
                                        valid, attn.indexer.topk))


def _reference_selection(cfg, seed, x):
    """The reference's choice in its first layer, by its own top-k."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         weights_glm5.layer_weights(cfg, seed, 0, jnp.float32).items()}
    eps, rope = float(cfg["rms_norm_eps"]), cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    t = x.shape[0]
    h = reference._rms_norm(jnp.asarray(x, jnp.float32), eps)
    c_q = reference._rms_norm(h @ w["w_qa"], eps)
    qi = (c_q @ w["idx_wq"]).reshape(t, hi, di)
    qi = jnp.concatenate([reference._rope(qi[..., :rope], theta),
                          qi[..., rope:]], -1)
    ki = reference._layer_norm(h @ w["idx_wk"], w["idx_norm_w"],
                               w["idx_norm_b"], reference.INDEX_LN_EPS)
    ki = jnp.concatenate([reference._rope(ki[:, :rope], theta),
                          ki[:, rope:]], -1)
    wi = (h @ w["idx_ww"]) * (hi ** -0.5 * di ** -0.5)
    sc = jnp.sum(jnp.maximum(jnp.einsum("qjd,kd->qjk", qi, ki), 0.0)
                 * wi[:, :, None], axis=1)
    valid = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return np.asarray(reference._best(jnp.where(valid, sc, -jnp.inf), valid,
                                      cfg["index_topk"]))


def test_float32_program_and_reference_choose_the_same_sets(system):
    cfg = system.config
    rng = np.random.default_rng(11)
    ids = rng.integers(0, cfg["vocab_size"], 120)
    x = np.asarray(weights_glm5.outer_weights(cfg, SEED, jnp.float32)
                   ["embed"])[ids]
    mine = _layer0_selection(system.engine._model, jnp.asarray(x),
                             jnp.float32)
    ref = _reference_selection(cfg, SEED, x)
    assert (mine.sum(1) == np.minimum(np.arange(120) + 1, 12)).all()
    assert (mine == ref).all()


def test_bfloat16_program_stays_inside_its_stated_tolerance():
    """bfloat16 as served against the float32 reference.  With 12 tokens
    kept, one token near the 12th place that changes sides on rounding
    moves that position's logits as a control would (0.19 at the widest),
    so the stated tolerance is on what such flips cannot move: the widest
    logit difference of the MEDIAN position under 0.02 and the mean
    difference under 0.015 (read: 0.003 to 0.006 and 0.001 to 0.007;
    either control 0.16 to 0.23 and 0.044 to 0.060), and of the sets the
    first layer's indexer chooses in bfloat16 at least 95 in 100 in common
    with the reference's (read: 99)."""
    cfg = tiny_config(torch_dtype="bfloat16")
    s = glm5_serving.System(cfg, jax.devices()[:1], SEED, False)
    s.build()
    try:
        rng = np.random.default_rng(3)
        seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
                for n in (128, 9, 78)]
        got, done = _through_the_cache(s, seqs, PLAN)
        for r, seq in enumerate(seqs):
            ref = np.asarray(reference.served_logits(
                cfg, SEED, seq, np.arange(done[r])))
            d = np.abs(got[r].astype(np.float32) - ref)
            assert np.median(d.max(-1)) < 0.02 and d.mean() < 0.015
        x = np.asarray(weights_glm5.outer_weights(cfg, SEED, jnp.bfloat16)
                       ["embed"].astype(jnp.float32))[seqs[0]]
        mine = _layer0_selection(s.engine._model, jnp.asarray(x),
                                 jnp.bfloat16)
        ref = _reference_selection(cfg, SEED, x)
        assert (mine & ref).sum() >= 0.95 * ref.sum()
    finally:
        s.free()


def test_served_tokens_through_engine_core_are_the_references_best(system):
    """Several rows of different lengths admitted together through
    EngineCore, prefix cache on: every served greedy token is the
    reference's argmax up to TOL, and the StepLog books what the indexer
    scored and attention read."""
    cfg = system.config
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (140, 6, 66, 37)]
    before = len(system.steplog.records())
    reqs = [system.submit(p, 6) for p in prompts]
    for p, r in zip(prompts, reqs):
        toks = np.asarray(r.result(timeout=600), np.int32)
        assert len(toks) == 6
        seq = np.concatenate([p, toks[:-1]])
        rows = np.arange(len(p) - 1, len(seq))
        ref = np.asarray(reference.served_logits(cfg, SEED, seq, rows))
        gap = ref.max(-1) - ref[np.arange(len(toks)), toks]
        assert gap.max() <= TOL
    k = cfg["index_topk"]
    steps = [s for s in system.steplog.records()[before:]
             if s["kind"] in ("mixed", "decode", "prefill")]
    assert steps
    for s in steps:
        assert s["index_scored_keys"] == s["attended_keys"]
        assert s["index_decode_scored_keys"] == s["decode_keys"]
        assert s["index_selected_keys"] <= s["index_scored_keys"]
        assert s["index_decode_selected_keys"] <= k * s["active_rows"]
        assert s["decode_grid_steps"] == 0
        # 3 layers of a 128-lane latent row and a 128-lane index key, f32
        assert s["cache_bytes_per_token"] == 3 * 256 * 4
        assert s["index_cache_bytes_per_token"] == 3 * 128 * 4
        assert s["latent_cache_bytes_per_token"] == 3 * (24 + 16) * 4
    # one row alone, of a known context: 20 prompt tokens in one chunk
    # (the first 12 keep all they see, the other 8 keep 12 each), then
    # decode steps that score 21, 22, ... and keep 12
    before = len(system.steplog.records())
    system.submit(rng.integers(0, cfg["vocab_size"], 20).astype(np.int32),
                  4).result(timeout=600)
    alone = [s for s in system.steplog.records()[before:]
             if s["kind"] in ("mixed", "decode", "prefill")]
    assert [(s["index_scored_keys"], s["index_selected_keys"])
            for s in alone] == [(210, 78 + 8 * 12), (21, 12), (22, 12),
                                (23, 12)]
    snap = system.core.metrics_snapshot()
    assert snap["indexer"]["scored_keys"] >= snap["indexer"]["selected_keys"]
    assert snap["kv_pool"]["index_cache_bytes_per_token"] == 3 * 128 * 4


def test_steplog_books_the_rows_the_selection_gathers(system, monkeypatch):
    """StepLog ``index_gathered_rows``: the step's live decode rows by
    the padded ``index_topk``, idle and chunk rows beside them; the trips
    ``select_rows``' loop makes of the lengths the same step hands the
    program, by the same width, through the function they share."""
    core, eng, log = system.core, system.engine, system.steplog
    k, page, max_pages = core._index_topk, core._page, core._max_pages
    window = page * max_pages
    width = SA.selection_width(k, window)[1]
    assert (k, width) == (12, 12)
    assert SA.selection_width(2048, 16384) == (2048, 2048)
    assert SA.selection_width(700, 16384) == (700, 1024)
    assert SA.gathered_rows([], k, window) == 0
    seen = []
    launch = eng.run_paged_program

    def spy(key, build, *args):
        if key[0] == "serve-step":
            f = core._step_fields
            seen.append((f["qlens"].copy(), f["ctx"].copy()))
        return launch(key, build, *args)

    monkeypatch.setattr(eng, "run_paged_program", spy)
    rng = np.random.default_rng(21)
    before = len(log.records())
    # four slots: three requests that end at different steps, so decode
    # rows lie beside a chunk row first and idle rows later
    reqs = [system.submit(rng.integers(0, system.config["vocab_size"],
                                       n).astype(np.int32), new)
            for n, new in ((70, 9), (5, 3), (40, 6))]
    for r in reqs:
        r.result(timeout=600)
    steps = [s for s in log.records()[before:]
             if s["kind"] in ("mixed", "decode", "prefill")]
    assert len(steps) == len(seen)
    pool = jnp.asarray(1.0 + rng.random((4 * max_pages + 1, page, LANES)),
                       jnp.float32)
    tables = jnp.asarray(1 + np.arange(4 * max_pages).reshape(4, max_pages),
                         jnp.int32)
    scores = jnp.asarray(rng.normal(size=(4, window)), jnp.float32)
    select = jax.jit(lambda sc, n: SA.select_rows(sc, n, pool, tables, k)[0])
    booked = set()
    for s, (qlens, ctx) in zip(steps, seen):
        n = np.where(qlens == 1, ctx + 1, 0)
        trips = int(np.asarray(select(scores, jnp.asarray(n))).any(
            axis=(1, 2)).sum())
        assert trips == int((n > 0).sum())
        assert s["index_gathered_rows"] == trips * width
        assert s["index_gathered_rows"] == SA.gathered_rows(n[n > 0], k,
                                                            window)
        booked.add((int((qlens > 0).sum()), s["index_gathered_rows"] // width))
    # steps with fewer decode rows than occupied rows (a chunk beside
    # them), with idle rows beside the decode rows, and with none
    assert {(1, 0), (3, 2), (2, 2), (1, 1)} <= booked, booked


def test_a_prefix_cache_hit_serves_both_pools(system):
    """A second request whose prompt repeats the first's pages is served
    its latent rows AND its index keys from the shared blocks: its tokens
    are the reference's best, which with stale or missing index keys (a
    selection over zeros) they would not be."""
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

def test_the_ep_shares_of_one_layer_add_up_to_the_uncut_reference():
    """16 published experts over 4 shares of 4: each share's program layer
    computes its held experts' part plus the shared expert; the parts,
    with the shared expert counted once, add up to what the reference
    gives for the whole layer."""
    from paddle_infer_tpu.nn.initializer import abstract_parameters

    whole = tiny_config()
    rng = np.random.default_rng(13)
    y = jnp.asarray(rng.normal(size=(1, 40, whole["hidden_size"])),
                    jnp.float32)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         weights_glm5.layer_weights(whole, SEED, 1, jnp.float32).items()}
    r = lambda v: v
    want = np.asarray(reference._experts(y[0], w, dict(
        whole, experts_held_first=0), r))
    shared = np.asarray(reference._swiglu(y[0], w["s_gate"], w["s_up"],
                                          w["s_down"], r))
    parts = []
    for first in range(0, 16, 4):
        cfg = tiny_config(n_routed_experts=4, experts_held_first=first)
        with abstract_parameters():
            layer = latent_moe.SharedExpertMoE(_model_config(cfg))
        lw = weights_glm5.layer_weights(cfg, SEED, 1, jnp.float32)
        names = dict(glm5_serving.EXPERT, **glm5_serving.ROUTER_BIAS)
        params = dict(layer.named_parameters())
        for key, name in names.items():
            params[name[len("mlp."):]]._data = lw[key]
        parts.append(np.asarray(layer(Tensor(y))._data[0]) - shared)
        # this share's experts are the whole's, by their published index
        np.testing.assert_array_equal(np.asarray(lw["e_up"]),
                                      np.asarray(w["e_up"][first:first + 4]))
    np.testing.assert_allclose(sum(parts) + shared, want, atol=TOL)
    assert min(np.abs(p).max() for p in parts) > 10 * TOL


# ------------------------------------------------------------ the front door

def test_auto_model_builds_it_from_the_sources_config_keys(tmp_path):
    from paddle_infer_tpu.models import AutoConfig, AutoModel

    cfg = tiny_config()
    model = latent_moe.LatentMoEForCausalLM(_model_config(cfg))
    model.save_pretrained(str(tmp_path))
    # a directory as the source publishes it: no "architecture" key
    source_keys = {k: v for k, v in cfg.items()
                   if k not in glm5_serving.NOT_MODEL_KEYS}
    assert source_keys["model_type"] == "glm_moe_dsa"
    with open(tmp_path / "config.json", "w") as f:
        json.dump(source_keys, f)
    loaded = AutoModel.from_pretrained(str(tmp_path))
    assert type(loaded) is latent_moe.LatentMoEForCausalLM
    auto = AutoConfig.from_pretrained(str(tmp_path))
    assert (auto.index_topk, auto.index_n_heads, auto.index_head_dim,
            auto.rope_theta) == (12, 4, 16, 1000000)
    assert layout_of(loaded) == [LayerCache.latent(24, index_width=16)] * 3
    ids = Tensor(jnp.arange(40, dtype=jnp.int32)[None])
    np.testing.assert_array_equal(np.asarray(loaded(ids)._data),
                                  np.asarray(model(ids)._data))
    # the selection is part of the model: no key turns it off, and what
    # the indexer cannot be built from is refused by name
    base = {k: v for k, v in source_keys.items()}
    with pytest.raises(ValueError, match="index_n_heads"):
        latent_moe.LatentMoEConfig(**dict(base, index_n_heads=None))
    with pytest.raises(NotImplementedError, match="rope_type"):
        latent_moe.LatentMoEConfig(**dict(
            base, rope_parameters={"rope_type": "yarn", "rope_theta": 1e6}))
    with pytest.raises(NotImplementedError, match="indexer_rope_interleave"):
        latent_moe.LatentMoEConfig(**dict(base,
                                          indexer_rope_interleave=False))
    # a model of the family without the keys builds no indexer and keeps
    # its one pool
    plain = latent_moe.LatentMoEForCausalLM(latent_moe.LatentMoEConfig(**{
        k: v for k, v in base.items() if not k.startswith("index")}))
    assert plain.model.layers[0].self_attn.indexer is None
    assert layout_of(plain) == [LayerCache.latent(24)] * 3


def test_tools_serve_serves_it_over_http_with_the_prefix_cache_on(tmp_path):
    """The front door: ``tools/serve.py`` over a directory holding the
    source's own config keys, prefix cache on; greedy ``/generate`` over a
    prompt five times ``index_topk`` long gives the tokens of the eager
    model's own greedy loop, twice (the second time from shared pages)."""
    import socket
    import subprocess
    import time
    import urllib.request

    cfg = tiny_config()
    model = latent_moe.LatentMoEForCausalLM(_model_config(cfg))
    model.eval()
    model.save_pretrained(str(tmp_path))
    with open(tmp_path / "config.json", "w") as f:
        json.dump({k: v for k, v in cfg.items()
                   if k not in glm5_serving.NOT_MODEL_KEYS}, f)
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
        assert snap["indexer"]["scored_keys"] > snap["indexer"][
            "selected_keys"] > 0
        # the second request was served its first pages, both pools of
        # them, from the first's
        assert snap["prefix_cache"]["hits"] >= 1
        assert snap["prefix_cache"]["cached_tokens"] >= 48
    finally:
        proc.terminate()
        proc.wait(timeout=30)
