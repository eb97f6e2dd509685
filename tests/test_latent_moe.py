"""The latent-attention + dropless-MoE decoder on the serving path, at a
small size in float32 on the CPU: against the plain reference
(benchmarks/reference/axk1.py), form against form, kernel against
composition, share against whole."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import weights_axk1                        # noqa: E402
from benchmarks.reference import axk1 as reference         # noqa: E402
from benchmarks.systems import latent_moe_serving          # noqa: E402
from paddle_infer_tpu.core.tensor import Tensor            # noqa: E402
from paddle_infer_tpu.inference.cache_layout import (      # noqa: E402
    LayerCache, layout_of)
from paddle_infer_tpu.models import latent_moe             # noqa: E402
from paddle_infer_tpu.ops.pallas import latent_attention as LA  # noqa: E402
from paddle_infer_tpu.ops.pallas.grouped_matmul import (   # noqa: E402
    grouped_matmul)
from paddle_infer_tpu.serving.moe import dropless          # noqa: E402
from paddle_infer_tpu.serving.moe import stats as moe_stats  # noqa: E402

SEED = 2 ** 31 + 21
# float32 throughout: the program and the reference then differ by
# summation order alone, which at these sizes stays under 1e-5; the
# tolerance of 1e-4 leaves a decade for the absorbed form's reassociation
# (q·(c W)ᵀ against (q Wᵀ)·c) and the online softmax of the kernel
TOL = 1e-4


def tiny_config(**over):
    with open(os.path.join(ROOT, "tests", "benchmarks", "data",
                           "tiny-axk1.json")) as f:
        cfg = json.load(f)
    cfg.update(torch_dtype="float32", n_routed_experts=16,
               n_routed_experts_published=16, experts_held_first=0)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def system():
    s = latent_moe_serving.System(tiny_config(), jax.devices()[:1], SEED,
                                  False)
    s.build()
    yield s
    s.free()


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
        with moe_stats.collect(valid, max_valid=tokens) as col:
            logits, caches = engine._model_step(params, ids[None], pos[None],
                                                None, caches)
        return (logits[0], *col.totals(),
                *_layer_pools(engine, caches))

    return jax.jit(run, donate_argnums=(6, 7))


def test_program_logits_match_the_reference_through_the_latent_cache(system):
    """Chunked prefill, then decode through the latent cache, rows of
    different lengths and kinds in one step."""
    eng, cfg = system.engine, system.config
    b, t = 4, 32            # four rows laid end to end on 32 token slots
    max_pages = system.core._max_pages
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (45, 9, 30)]
    tables = np.full((b, max_pages), system.core._scratch, np.int32)
    for r in range(3):
        tables[r] = 1 + r * max_pages + np.arange(max_pages)
    done = [0, 0, 0]
    got = [[] for _ in seqs]
    # row 0 prefills in chunks of 16 to 32 and then decodes; row 1 is one
    # short chunk and then decodes beside the others' chunks; row 2 joins
    # late with chunks of 7
    plan = [(16, 9, 0), (16, 0, 7), (1, 0, 7), (1, 0, 7), (1, 0, 7),
            (1, 0, 2), (1, 0, 0)]
    for step in plan + [(1, 0, 0)] * 8:
        ids = np.zeros((t,), np.int32)
        qlens = np.zeros((b,), np.int32)
        ctx = np.zeros((b,), np.int32)
        for r, n in enumerate(step):
            n = min(n, len(seqs[r]) - done[r])
            at = int(qlens.sum())
            ids[at:at + n] = seqs[r][done[r]:done[r] + n]
            qlens[r], ctx[r] = n, done[r]
        logits, total, held, _, _ = eng.run_paged_program(
            ("test-logits", b, t), lambda: _logits_program(eng, t),
            ids, qlens, ctx, tables, np.asarray(system.core._scratch,
                                                np.int32))
        # every expert is held here: nothing routed is left out
        assert int(total) == int(held) == int(qlens.sum()) * 4 * 2
        starts = np.cumsum(qlens) - qlens
        for r in range(3):
            got[r].append(np.asarray(
                logits[starts[r]:starts[r] + qlens[r]]))
            done[r] += int(qlens[r])
    for r, seq in enumerate(seqs):
        mine = np.concatenate(got[r])
        assert len(mine) == done[r] >= min(len(seq), 24)
        ref = np.asarray(reference.served_logits(
            cfg, SEED, seq[:done[r]], np.arange(done[r])))
        np.testing.assert_allclose(mine, ref, atol=TOL, rtol=0)
    # absorbed (the served path) against expanded (the eager forward)
    eager = system.engine._model(Tensor(jnp.asarray(seqs[1][None])))._data[0]
    np.testing.assert_allclose(np.concatenate(got[1]),
                               np.asarray(eager)[:done[1]], atol=TOL, rtol=0)


def test_served_tokens_through_engine_core_are_the_references_best(system):
    """Several rows of different lengths admitted together: every served
    greedy token is the reference's argmax up to TOL."""
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
        gap = ref.max(-1) - ref[np.arange(len(toks)), toks]
        assert gap.max() <= TOL
    for s in system.steplog.records():
        if s["kind"] in ("mixed", "decode", "prefill"):
            tokens = s["decode_rows"] + s["prefill_chunk_tokens"]
            assert s["moe_assignments_total"] == tokens * 4 * 2
            # read from the allocated pools: 3 layers of one 128-lane
            # float32 row a token, of which 24 lanes are cached numbers
            assert s["cache_bytes_per_token"] == 3 * 128 * 4
            assert s["latent_cache_bytes_per_token"] == 3 * 24 * 4


def test_steplog_books_the_grid_the_decode_kernel_runs(system, monkeypatch):
    """StepLog ``decode_grid_steps``: live decode rows x the walk of the
    longest one, from the packer's own arrays and the kernel's geometry;
    0 on a step with no decode row; what ``decode_grid`` makes of the
    lengths the step hands the kernel."""
    core, eng, log = system.core, system.engine, system.steplog
    _, span, steps = LA.walk_geometry(core._page, core._max_pages)
    assert (span, steps) == (128, 2)
    serving = ("mixed", "decode", "prefill")
    rng = np.random.default_rng(7)
    prompt = lambda n: rng.integers(0, system.config["vocab_size"],
                                    n).astype(np.int32)
    # one row alone, of known contexts: its decode steps read 125..131
    # keys, one grid step up to 128 and two past it
    before = len(log.records())
    system.submit(prompt(span - 4), 8).result(timeout=600)
    alone = [s for s in log.records()[before:] if s["kind"] in serving]
    assert [s["decode_grid_steps"] for s in alone if s["decode_rows"]] == [
        1, 1, 1, 1, 2, 2, 2]
    chunks = [s for s in alone if not s["decode_rows"]]
    assert chunks and all(s["decode_grid_steps"] == 0 for s in chunks)
    # no indexer, no selection: nothing gathered for one
    assert all(s["index_gathered_rows"] == 0 for s in alone)
    # rows of different lengths together, beside what the kernel computes
    # from the lengths the same step hands it
    seen = []
    launch = eng.run_paged_program

    def spy(key, build, *args):
        if key[0] == "serve-step":
            f = core._step_fields
            seen.append((f["qlens"].copy(), f["ctx"].copy()))
        return launch(key, build, *args)

    monkeypatch.setattr(eng, "run_paged_program", spy)
    before = len(log.records())
    for r in [system.submit(prompt(n), 10) for n in (span - 6, 5, 40)]:
        r.result(timeout=600)
    together = [s for s in log.records()[before:] if s["kind"] in serving]
    assert len(together) == len(seen)
    booked = set()
    for s, (qlens, ctx) in zip(together, seen):
        n = np.where(qlens == 1, ctx + 1, 0)
        _, rows, walk = LA.decode_grid(jnp.asarray(n), core._page,
                                       core._max_pages)
        assert s["decode_grid_steps"] == (
            int(rows) * int(walk) if n.any() else 0)
        # a chunk of one token is a decode row to the kernel too
        assert s["decode_rows"] <= int((qlens == 1).sum())
        assert s["decode_grid_steps"] <= int((qlens == 1).sum()) * steps
        booked.add((s["decode_rows"], s["decode_grid_steps"]))
    # three rows at one grid step each, then at two: the longest row's
    # walk is every row's
    assert {(3, 3), (3, 6)} <= booked


def test_cache_layout_is_one_description_for_both_kinds(system):
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    eng = system.engine
    assert eng._cache_layout == [LayerCache.latent(24)] * 3
    k_pages, v_pages = eng._ensure_pages()
    pool = eng._pool.num_blocks
    # 24 numbers cached a token, in rows of one 128-lane tile
    assert [p.shape for p in k_pages] == [(pool, 16, 128)] * 3
    assert v_pages == [None] * 3            # no V pool, no head axis
    assert eng.cache_bytes_per_token() == 3 * 128 * 4
    assert eng.cache_bytes_per_token("latent", padding=False) == 3 * 24 * 4
    assert LayerCache.latent(576).pool_shapes(9, 16)[0] == (9, 16, 640)
    llama = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
        max_position_embeddings=64))
    assert layout_of(llama) == [LayerCache.kv(4, 8)] * 2
    leng = PagedGenerationEngine(llama, page_size=8, num_pages=5)
    leng.serving_pool(5)
    k_pages, v_pages = leng._ensure_pages()
    assert [p.shape for p in k_pages + v_pages] == [(5, 4, 8, 8)] * 4


def test_yarn_frequencies_and_scale_for_the_published_keys():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "a.x-k1-ep16-d7.json")) as f:
        cfg = json.load(f)
    sc = cfg["rope_scaling"]
    inv = latent_moe.yarn_inv_freq(64, 10000.0, sc)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 ->
    # 10, and 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    base = lambda i: 10000.0 ** (-2.0 * i / 64)
    assert inv[0] == 1.0 and inv[10] == pytest.approx(base(10))
    assert inv[23] == pytest.approx(base(23) / 32)
    assert inv[31] == pytest.approx(base(31) / 32)
    ramp = (16 - 10) / 13
    assert inv[16] == pytest.approx(base(16) * (1 - ramp + ramp / 32))
    np.testing.assert_allclose(inv, reference.yarn_inv_freq(64, 10000.0, sc),
                               rtol=1e-12)
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.3466, abs=1e-4)
    scale = latent_moe.attention_scale(latent_moe.LatentMoEConfig(**{
        k: v for k, v in cfg.items()
        if k not in latent_moe_serving.NOT_MODEL_KEYS}))
    assert scale == pytest.approx(192 ** -0.5 * m * m)
    assert scale == pytest.approx(reference.softmax_scale(cfg))
    # cos/sin carry mscale / mscale_all_dim = 1
    assert latent_moe.yarn_mscale(32, 1) / latent_moe.yarn_mscale(32, 1) == 1


def _latent_case(rng, b, h, width, page, max_pages, dtype=jnp.float32):
    pool = 1 + b * max_pages
    # lanes past the cached width hold zeros, as the writer leaves them
    pages = LA.pad_lanes(jnp.asarray(
        rng.normal(size=(pool, page, width)), dtype), 128)
    tables = (1 + np.arange(b * max_pages, dtype=np.int32)
              ).reshape(b, max_pages)
    rng.shuffle(tables.reshape(-1))
    return pages, jnp.asarray(tables)


def _static_grid_decode(q, pages, block_tables, lengths, scale, value_width,
                        pages_per_step):
    """The launch ``latent_paged_decode`` had until its grid followed the
    step's live rows: every row by the whole table's walk, both static
    (the row list is the identity).  Kept here as the reference the live
    grid must equal bit for bit."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _ = q.shape
    _, page_size, lanes = pages.shape
    max_pages = block_tables.shape[1]
    g, _, steps = LA.walk_geometry(page_size, max_pages, pages_per_step)

    def q_map(b_, j_, lengths_s, tables_s, live_s):
        return (b_, 0, 0)

    def page_map(i):
        def index(b_, j_, lengths_s, tables_s, live_s):
            last = jnp.clip(lengths_s[b_] - 1, 0,
                            max_pages * page_size - 1) // page_size
            return (tables_s[b_, jnp.minimum(j_ * g + i, last)], 0, 0)
        return index

    return pl.pallas_call(
        functools.partial(LA._decode_kernel, scale=float(scale),
                          page_size=page_size, pages_per_step=g,
                          value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, steps),
            in_specs=[pl.BlockSpec((1, h, lanes), q_map)] + [
                pl.BlockSpec((1, page_size, lanes), page_map(i))
                for i in range(g)],
            out_specs=pl.BlockSpec((1, h, value_width), q_map),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, value_width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        interpret=True,
    )(lengths, block_tables, jnp.arange(b, dtype=jnp.int32),
      LA.pad_lanes(q, lanes), *([pages] * g))


# lengths of five rows over a table of 96 keys, as functions of the keys
# a grid step walks (8, 32 or 64 here; 128 at the served sizes)
LENGTH_SETS = {
    "mixed": lambda span: [1, 8, 9, 51, 96],
    "dead_between_live": lambda span: [5, 0, 33, 0, 70],
    "last_row_only": lambda span: [0, 0, 0, 0, 17],
    "every_row_dead": lambda span: [0, 0, 0, 0, 0],
    "every_row_full": lambda span: [96, 96, 96, 96, 96],
    "on_a_step_border_and_one_past": lambda span: [span, 0, span + 1, 1, 0],
    "longest_on_a_step_border": lambda span: [0, span, 3, 0, span - 1],
}


@pytest.mark.parametrize("lengths", sorted(LENGTH_SETS))
@pytest.mark.parametrize("pages_per_step", [1, 4, 8])
def test_latent_decode_kernel_equals_the_chunk_composition(pages_per_step,
                                                           lengths):
    rng = np.random.default_rng(11)
    b, h, width, value, page, max_pages = 5, 4, 24, 16, 8, 12
    pages, tables = _latent_case(rng, b, h, width, page, max_pages)
    q = jnp.asarray(rng.normal(size=(b, h, width)), jnp.float32)
    _, span, steps = LA.walk_geometry(page, max_pages, pages_per_step)
    n = jnp.asarray(LENGTH_SETS[lengths](span), jnp.int32)
    alive = np.asarray(n) > 0
    # both bounds of the grid are traced values of one executable
    dec = np.asarray(jax.jit(
        lambda n: LA.latent_paged_decode(q, pages, tables, n, 0.3, value,
                                         pages_per_step=pages_per_step))(n))
    # the composition treats the same query as the first of a chunk of
    # two, the chunks end to end on its flat token axis
    q2 = jnp.stack([q, jnp.zeros_like(q)], axis=1).reshape(2 * b, h, width)
    comp = LA.latent_chunk_attention(q2, pages, tables, jnp.maximum(n - 1, 0),
                                     jnp.full((b,), 2, jnp.int32), 0.3,
                                     value)[0::2]
    np.testing.assert_allclose(dec[alive], np.asarray(comp)[alive],
                               atol=1e-5)
    # a row of length zero is skipped and reads zero
    assert not dec[~alive].any()
    # the grid is the live rows by the longest one's walk, and what it
    # leaves out never added anything: bit for bit the table-wide grid
    _, rows, walk = LA.decode_grid(n, page, max_pages, pages_per_step)
    assert int(rows) == max(int(alive.sum()), 1)
    assert int(walk) == min(max(-(-int(n.max()) // span), 1), steps)
    assert LA.decode_grid_steps(np.asarray(n)[alive], page, max_pages,
                                pages_per_step) == (
        int(rows) * int(walk) if alive.any() else 0)
    np.testing.assert_array_equal(dec, np.asarray(_static_grid_decode(
        q, pages, tables, n, 0.3, value, pages_per_step)))


def test_latent_writer_touches_the_rows_own_slots_only():
    rng = np.random.default_rng(12)
    pages, tables = _latent_case(rng, 3, 1, 24, 8, 6)
    rows = jnp.asarray(rng.normal(size=(3, 10, 24)), jnp.float32)
    ctx = jnp.asarray([5, 0, 20], jnp.int32)
    qlens = jnp.asarray([10, 0, 1], jnp.int32)
    out = np.asarray(LA.write_latent_pages(pages, tables, rows, ctx, qlens))
    want = np.asarray(pages).copy()
    for r in range(3):
        for i in range(int(qlens[r])):
            pos = int(ctx[r]) + i
            want[int(tables[r, pos // 8]), pos % 8, :24] = \
                np.asarray(rows[r, i])
            want[int(tables[r, pos // 8]), pos % 8, 24:] = 0
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("sizes", [[3, 0, 5, 0], [0, 0, 0, 0], [16, 0, 0, 0],
                                   [1, 1, 1, 1], [0, 7, 0, 9], [2, 9, 1, 0]])
def test_grouped_matmul_equals_a_loop_over_groups(sizes):
    rng = np.random.default_rng(13)
    m, k, n = 16, 32, 24
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
    out = np.asarray(grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                                    tm=8))
    want, r = np.zeros((m, n), np.float32), 0
    for g, s in enumerate(sizes):
        want[r:r + s] = np.asarray(lhs[r:r + s]) @ np.asarray(rhs[g])
        r += s
    np.testing.assert_allclose(out, want, atol=1e-5)


def _expert_layer_case(seed=14):
    cfg = tiny_config()
    w = {k: np.asarray(v, np.float32) for k, v in
         weights_axk1.layer_weights(cfg, seed, 1, jnp.float32).items()}
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(40, 64)),
                    jnp.float32)
    return cfg, w, x


def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the shares' routed parts plus the
    shared expert counted once are the whole layer (the guide's share
    test)."""
    cfg, w, x = _expert_layer_case()
    whole = reference._experts(x, {k: jnp.asarray(v) for k, v in w.items()},
                               cfg, lambda a: a)
    shared = reference._swiglu(x, w["s_gate"], w["s_up"], w["s_down"],
                               lambda a: a)
    ids, wts = dropless.route(x, jnp.asarray(w["router"]), 4, 2.5)
    valid = jnp.ones((40,), bool)
    total, counted = shared, 0
    for first in (0, 4, 8, 12):
        part, counts = dropless.dropless_experts(
            x, ids, wts, valid, *(jnp.asarray(w[k][first:first + 4])
                                  for k in ("e_gate", "e_up", "e_down")),
            first)
        total = total + part
        counted += int(counts.sum())
        # the reference given the same share computes the same part
        ref_part = reference._experts(
            x, {k: jnp.asarray(v[first:first + 4] if k.startswith("e_")
                               else v) for k, v in w.items()},
            dict(cfg, experts_held_first=first), lambda a: a) - shared
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part),
                                   atol=TOL)
    assert counted == 40 * 4                 # every assignment, once
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=TOL)


def test_pad_slots_route_nowhere_and_a_skewed_routing_drops_nothing():
    cfg, w, x = _expert_layer_case(15)
    # every token's largest score is expert 1's
    router = w["router"].copy()
    router[:, 1] = 0.0
    x1 = jnp.concatenate([x, jnp.ones((40, 1))], axis=1)
    router1 = np.concatenate([router, np.zeros((1, 16), np.float32)])
    router1[-1, 1] = 50.0
    pad = lambda m: np.concatenate([m, np.zeros((m.shape[0], 1) + m.shape[2:]
                                                if m.ndim == 3 else (1,)
                                                + m.shape[1:], np.float32)],
                                   axis=1 if m.ndim == 3 else 0)
    valid = jnp.asarray(np.arange(40) % 5 != 0)          # 32 of 40
    ids, wts = dropless.route(x1, jnp.asarray(router1), 4, 2.5)
    assert (np.asarray(ids)[:, 0] == 1).all()
    e_gate, e_up = pad(w["e_gate"][:4]), pad(w["e_up"][:4])
    e_down = w["e_down"][:4]
    e_down1 = np.concatenate([e_down, np.zeros((4, 32, 1), np.float32)], 2)
    for bound in (None, 32):
        y, counts = dropless.dropless_experts(
            x1, ids, wts, valid, jnp.asarray(e_gate), jnp.asarray(e_up),
            jnp.asarray(e_down1), 0, max_valid=bound)
        counts = np.asarray(counts)
        assert counts[1] == 32               # all of them, none dropped
        held = (np.asarray(ids) < 4) & np.asarray(valid)[:, None]
        assert counts.sum() == held.sum()
        want = np.zeros((40, 65), np.float32)
        for t in range(40):
            for j in range(4):
                e = int(ids[t, j])
                if e < 4 and bool(valid[t]):
                    hid = np.asarray(jax.nn.silu(x1[t] @ e_gate[e])) \
                        * np.asarray(x1[t] @ e_up[e])
                    want[t] += float(wts[t, j]) * (hid @ e_down1[e])
        np.testing.assert_allclose(np.asarray(y), want, atol=TOL)
        assert not np.asarray(y)[~np.asarray(valid)].any()


REFUSALS = {
    "mp": (dict(mp=2), "no head axis to split"),
    "int8": (dict(kv_dtype="int8"), "no heads to scale over"),
    "int4": (dict(kv_dtype="int4"), "no heads to scale over"),
    "speculate": (dict(speculate=True), "verify lanes"),
    "host tier": (dict(kv_host_pages=8), "host KV tier"),
    "handoff": (dict(handoff=True), "KV handoff"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_a_latent_layer_cannot_do_is_refused_at_start_up(what):
    from paddle_infer_tpu.serving.sharded import (ShardedConfigError,
                                                  validate_cache_layout)

    kw, says = REFUSALS[what]
    with pytest.raises(ShardedConfigError, match=says):
        validate_cache_layout([LayerCache.latent(24)], **kw)
    validate_cache_layout([LayerCache.kv(4, 8)], **kw)      # kv: silent
    validate_cache_layout([LayerCache.latent(24)])


def test_engine_core_and_the_offline_programs_refuse_a_latent_model():
    from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                       PagedGenerationEngine)
    from paddle_infer_tpu.serving import EngineCore, ServingMesh
    from paddle_infer_tpu.serving.sharded import (ShardedConfigError,
                                                  build_sharded_engine)

    cfg = tiny_config()
    model = latent_moe.LatentMoEForCausalLM(latent_moe.LatentMoEConfig(**{
        k: v for k, v in cfg.items()
        if k not in latent_moe_serving.NOT_MODEL_KEYS}))
    with pytest.raises(ShardedConfigError, match="no head axis"):
        build_sharded_engine(model, ServingMesh(mp=2),
                             devices=jax.devices()[:2])
    with pytest.raises(ShardedConfigError, match="no heads to scale"):
        build_sharded_engine(model, ServingMesh(), kv_dtype="int8")
    with pytest.raises(ShardedConfigError, match="no heads to scale"):
        EngineCore(PagedGenerationEngine(model, kv_dtype="int8"),
                   max_batch=2, max_model_len=64)
    eng = PagedGenerationEngine(model)
    for kw, says in ((dict(speculate=True), "verify lanes"),
                     (dict(kv_host_pages=4), "host KV tier")):
        with pytest.raises(ShardedConfigError, match=says):
            EngineCore(eng, max_batch=2, max_model_len=64, **kw)
    ids = np.arange(5, dtype=np.int32)[None]
    with pytest.raises(NotImplementedError, match="mixed step"):
        eng.generate(ids, GenerationConfig(max_new_tokens=2))
    with pytest.raises(NotImplementedError, match="mixed step"):
        next(iter(eng.stream(ids, GenerationConfig(max_new_tokens=2))))


def test_auto_model_builds_it_from_the_sources_config_keys(tmp_path):
    from paddle_infer_tpu.models import AutoConfig, AutoModel

    cfg = tiny_config()
    model = latent_moe.LatentMoEForCausalLM(latent_moe.LatentMoEConfig(**{
        k: v for k, v in cfg.items()
        if k not in latent_moe_serving.NOT_MODEL_KEYS}))
    model.save_pretrained(str(tmp_path))
    # a directory as the source publishes it: no "architecture" key
    source_keys = {k: v for k, v in cfg.items()
                   if k not in latent_moe_serving.NOT_MODEL_KEYS}
    assert source_keys["model_type"] == "axk1"
    with open(tmp_path / "config.json", "w") as f:
        json.dump(source_keys, f)
    loaded = AutoModel.from_pretrained(str(tmp_path))
    assert type(loaded) is latent_moe.LatentMoEForCausalLM
    assert AutoConfig.from_pretrained(str(tmp_path)).kv_lora_rank == 16
    ids = Tensor(jnp.arange(7, dtype=jnp.int32)[None])
    np.testing.assert_array_equal(np.asarray(loaded(ids)._data),
                                  np.asarray(model(ids)._data))
    # the score-correction bias is built for one group; grouped routing
    # is refused by name (tests/test_hyper_connections.py has the rest)
    with pytest.raises(NotImplementedError, match="grouped routing"):
        latent_moe.LatentMoEConfig(topk_method="noaux_tc", n_group=8,
                                   topk_group=4)


def test_earlier_per_layer_entries_did_not_move():
    """BENCHMARK.json is append-only, and what this file owns of it is
    found BY NAME: the per-layer entries the first two cells were entered
    with (PR 24's nine phase metrics among them) and PR 27's ``.axk1``
    block are there, each lists its own cell, and the cell and its
    configuration are there.  No place, no length and no list is held to a
    literal: later PRs append cells, entries and names to lists, and a
    ``benchmark`` PR may fold an entry under a suffix into the plain entry
    of the same name (then the plain entry lists the cell)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])

    def reads(name, cell):
        """The entry ``name``, or once folded the plain entry of that
        name, lists ``cell``."""
        m = by_name.get(name) or by_name.get(name.rsplit(".", 1)[0])
        return m is not None and cell in m["workloads"]

    nine = ["loop_gap_ms_per_step.chat", "admit_ms_per_step.chat",
            "pack_ms_per_step.chat", "launch_ms_per_step.chat",
            "readback_wait_ms_p50.chat", "host_serial_ms_per_step.chat",
            "h2d_kb_per_step.chat", "step_roofline_share_counted.chat",
            "step_temp_share.chat"]
    chat = ["gen_lateness_p99_ms", "queue_wait_mean_ms", "ttft_p50_ms",
            "ttft_mean_ms", "itl_mean_ms", "itl_p99_ms", "ttft_p90_ms",
            "batch_rows_mean.chat", "padded_slot_share.chat",
            "step_ms_p50.chat", "compiles_in_window.chat",
            "device_idle_share.chat", "hbm_peak_share.chat"] + nine
    train = ["compiles_in_window.train", "step_ms_p50.train",
             "mfu_share.train", "device_idle_share.train",
             "hbm_peak_share.train", "step_temp_share.train"]
    for name in chat:
        assert reads(name, "mistral-d12.chat"), name
    for name in train:
        assert reads(name, "ernie-base.pretrain"), name
    axk1 = ["step_ms_p50", "batch_rows_mean", "chunk_step_gap_share",
            "compiles_in_window", "host_serial_ms_per_step",
            "readback_wait_ms_p50", "device_idle_share", "hbm_peak_share",
            "step_temp_share", "ttft_mean_ms", "ttft_p90_ms", "itl_mean_ms",
            "queue_wait_mean_ms", "gen_lateness_p99_ms",
            "moe_assignments_held_mean", "moe_held_expert_max_p95",
            "moe_experts_touched_mean", "latent_cache_bytes_per_token",
            "latent_decode_roofline_share",
            "moe_grouped_matmul_roofline_share",
            "step_roofline_share_counted"]
    for name in axk1:
        assert reads(name + ".axk1", "axk1-ep16.ragchat"), name
    # an entry still under this file's suffix moves the judged metric and
    # reads its own cell
    for m in bench["per_layer"]:
        if m["name"].endswith(".axk1"):
            assert "axk1-ep16.ragchat" in m["workloads"], m["name"]
            assert m["moves"] == "itl_p95_ms", m["name"]
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    for cell, config in (("ernie-base.pretrain", "ernie-3.0-base-pretrain"),
                         ("mistral-d12.chat", "mistral-7b-v0.1-d12"),
                         ("axk1-ep16.ragchat", "a.x-k1-ep16-d7")):
        assert cells[cell]["config"] == config and config in configs
