"""Ragged mixed-batch paged attention + chunked prefill scheduling
(paddle_infer_tpu/ops/pallas/ragged_paged_attention.py + the ragged
EngineCore scheduler).

Three layers of coverage:

* kernel level — ``write_ragged_pages`` pad handling; the served
  single-launch Pallas kernel vs the plain reference composition
  (allclose at float32 rounding: the online softmax reassociates), for
  bf16 and int8 pools over every row type; and its schedule
  independence, bitwise;
* parity — served token streams against oracles that are not the
  served schedule: greedy streams (cold, and prompts cut into chunks at
  lengths that straddle a page and a chunk boundary) against a direct
  ``generate()`` of the offline paged engine, an independent program
  whose logits agree with the mixed step's to float32 rounding (on this
  tiny float32 model no argmax sits that close to a tie); seeded-sampled
  streams against the SAME executable under another co-schedule (the
  requests served one at a time), warm prefix-cache hits against a core
  without the cache, and supervisor replay after KV loss against the
  uninterrupted stream — equal bit for bit, because the kernel is
  schedule independent (above) and every other operation of the step
  is row-wise.  Sampled comparisons pin the request-id counter:
  per-request sampling keys are ``fold_in(PRNGKey(seed), rid)``, so the
  two runs must hand out the same rids;
* composition fuzz — 160+ scheduler steps of random arrivals (chunked
  long prompts, decode, mixed, drained-idle) with pool invariants
  checked every step and ZERO new XLA compiles after the one-step
  warmup: the whole point of the ragged executable is that batch
  composition is data, not shape.
"""
import itertools
import random

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                      FaultPlane, FaultSpec, RequestState)
from paddle_infer_tpu.serving import request as request_mod


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """The parity tests compare tokens across differently-shaped
    executables (the mixed step and ``generate()``'s), which is steady
    only when both run unsharded — clear any hybrid mesh a failing test
    in another module leaked behind (ops consult
    ``topology.get_current_mesh()`` at call time)."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
    """Process-singleton CompileLog: warm marks left by other modules'
    cores (same site/key shapes, different engines) would count this
    module's first compiles as post-warmup recompiles — and vice
    versa."""
    from paddle_infer_tpu.observability import get_compile_log
    get_compile_log().reset()
    yield
    get_compile_log().reset()


@pytest.fixture(scope="module")
def model():
    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    return PagedGenerationEngine(model, page_size=8)


@pytest.fixture(scope="module")
def ref(model):
    """Separate reference engine — direct generate() on a core-owned
    engine would corrupt its slot reservations."""
    return PagedGenerationEngine(model, page_size=8)


# Every core in this module runs the same (max_batch, max_model_len,
# token_budget) so the handful of serving executables (and the one page
# pool size) compile once and every later test reuses them — the module
# exercises scheduling and parity, not shape coverage.
CORE_SHAPE = dict(max_batch=3, max_model_len=48, token_budget=16,
                  prefill_chunk=16)


@pytest.fixture
def make_core(engine):
    cores = []

    def make(**kw):
        for k, v in CORE_SHAPE.items():
            kw.setdefault(k, v)
        core = EngineCore(engine, **kw)
        cores.append(core)
        return core

    yield make
    for c in cores:
        c.close()


def _drive(core, reqs, max_iters=400):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


# ------------------------------------------------------------------ kernel

def test_write_ragged_pages_pads_touch_no_live_or_unmapped_page():
    """Valid positions land at each row's absolute slots; pad positions
    (i >= query_len, including whole inactive rows) touch no live and
    no unmapped page — never clamped into a live page.  Whether they are
    parked on the scratch page or written nowhere is the writer's
    business (the page-granular writer drops them)."""
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas.ragged_paged_attention import (
        write_ragged_pages)

    page, h, d, c = 4, 1, 2, 6
    pages = jnp.zeros((6, h, page, d), jnp.float32)
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    scratch = 5
    ctx = jnp.asarray([2, 0], jnp.int32)
    qlens = jnp.asarray([3, 0], jnp.int32)
    kv = jnp.arange(2 * c * h * d, dtype=jnp.float32).reshape(2, c, h, d)

    out = np.asarray(write_ragged_pages(pages, tables, kv, ctx, qlens,
                                        scratch))
    # row 0 positions 2, 3, 4 -> page 0 slots 2, 3 then page 1 slot 0
    np.testing.assert_array_equal(out[0, 0, 2], np.asarray(kv[0, 0, 0]))
    np.testing.assert_array_equal(out[0, 0, 3], np.asarray(kv[0, 1, 0]))
    np.testing.assert_array_equal(out[1, 0, 0], np.asarray(kv[0, 2, 0]))
    # no other live page/slot was touched
    live = out[:4].copy()
    live[0, 0, 2] = live[0, 0, 3] = live[1, 0, 0] = 0.0
    assert not live.any(), "pad tokens leaked into live pages"
    assert not out[4].any()               # unmapped page untouched


def test_ragged_kernel_allclose_reference():
    """The single-launch Pallas kernel (online softmax, page-walk skip)
    vs the plain reference composition, on a float32 pool the writer
    filled, in a batch mixing decode (qlen 1), chunk (qlen > 1), and
    inactive (qlen 0) rows."""
    import jax
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    b, c, h, d, page, max_pages = 4, 8, 2, 8, 4, 4
    num_pages = b * max_pages + 1
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, c, h, d), jnp.float32)
    k_pages = jnp.zeros((num_pages, h, page, d), jnp.float32)
    v_pages = jnp.zeros((num_pages, h, page, d), jnp.float32)
    tables = jnp.arange(b * max_pages, dtype=jnp.int32).reshape(
        b, max_pages)
    scratch = num_pages - 1
    ctx = jnp.asarray([7, 3, 0, 0], jnp.int32)
    qlens = jnp.asarray([1, 5, 0, 8], jnp.int32)
    # context KV that was already resident before this step
    kc = jax.random.normal(kk, (b, max_pages * page, h, d), jnp.float32)
    span = jnp.arange(max_pages * page, dtype=jnp.int32)[None]
    k_pages = RPA.write_ragged_pages(
        k_pages, tables, kc, jnp.zeros((b,), jnp.int32),
        jnp.minimum(ctx, max_pages * page), scratch)
    v_pages = RPA.write_ragged_pages(
        v_pages, tables, kc[..., ::-1], jnp.zeros((b,), jnp.int32),
        jnp.minimum(ctx, max_pages * page), scratch)
    del span
    # this step's own chunk KV at positions ctx .. ctx+qlen-1
    kn = jax.random.normal(kv_, (b, c, h, d), jnp.float32)
    k_pages = RPA.write_ragged_pages(k_pages, tables, kn, ctx, qlens,
                                     scratch)
    v_pages = RPA.write_ragged_pages(v_pages, tables, kn[..., ::-1], ctx,
                                     qlens, scratch)

    want = RPA._ragged_reference(q, k_pages, v_pages, tables, ctx, qlens)
    got = RPA.ragged_paged_attention(q, k_pages, v_pages, tables, ctx,
                                     qlens, interpret=True)
    valid = (np.arange(c)[None] < np.asarray(qlens)[:, None])
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(want)[valid],
        rtol=2e-5, atol=2e-5)


# One pool geometry for the kernel cases below: 4 rows x 4 table pages of
# 4 slots, capacity 8, 2 heads of 8.  Queries are float32 so the output
# is float32 and the comparison sees more than a bf16 result's 8 bits.
_KB, _KC, _KH, _KD, _KPAGE, _KMAXP = 4, 8, 2, 8, 4, 4

# (context_lens, query_lens, verify_rows) per case
_KERNEL_CASES = {
    "decode_only": ([7, 3, 12, 15], [1, 1, 1, 1], None),
    "chunk_only": ([0, 4, 2, 8], [8, 5, 3, 8], None),
    "mixed_with_inactive_row": ([7, 3, 0, 8], [1, 5, 0, 8], None),
    "chunk_starting_mid_page": ([3, 6, 0, 9], [6, 2, 0, 7], None),
    "window_ends_on_last_table_page": ([8, 15, 0, 12], [8, 1, 0, 4], None),
    "verify_rows_window_4": ([7, 3, 0, 8], [1, 5, 0, 8],
                             [False, True, False, True]),
}


def _kernel_pools(pool, seed):
    """Every slot of every page filled with seeded values (so slots past
    a row's window hold finite garbage, as a recycled page does)."""
    import jax
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas import paged_attention as PA

    num_pages = _KB * _KMAXP + 1
    kk, kv_ = jax.random.split(jax.random.PRNGKey(seed))
    shape = (num_pages, _KH, _KPAGE, _KD)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv_, shape, jnp.float32)
    if pool == "int8":
        return PA.quantize_pages(k), PA.quantize_pages(v)
    return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_served_kernel_allclose_reference_composition(pool, case):
    """The served entry point (one Pallas launch, work by row type)
    against the plain composition, at every valid position.

    Tolerance 2e-5: both sides read the same stored K/V (bf16, or int8 x
    the page's scale) and keep scores, softmax statistics and the PV
    accumulator in float32, so they differ by the online softmax's
    reassociation alone — a few float32 ulps of values of order 1, about
    1e-6.  Probabilities rounded to bf16 (2**-9 relative each) move the
    output by about 1e-3, K/V read through fp8 by more: either fails
    this by two orders of magnitude."""
    import jax
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas import paged_attention as PA
    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    ctx, qlens, verify = _KERNEL_CASES[case]
    ctx = jnp.asarray(ctx, jnp.int32)
    qlens = jnp.asarray(qlens, jnp.int32)
    kw = {} if verify is None else dict(
        verify_rows=jnp.asarray(verify), verify_window=4)
    q = jax.random.normal(jax.random.PRNGKey(7), (_KB, _KC, _KH, _KD),
                          jnp.float32)
    k_pages, v_pages = _kernel_pools(pool, seed=11)
    tables = jnp.arange(_KB * _KMAXP, dtype=jnp.int32).reshape(_KB, _KMAXP)

    want = np.asarray(RPA._ragged_reference(q, k_pages, v_pages, tables,
                                            ctx, qlens, **kw))
    got = np.asarray(RPA.ragged_paged_attention(q, k_pages, v_pages,
                                                tables, ctx, qlens, **kw))
    assert np.isfinite(got).all(), "a skipped row or page stored garbage"
    valid = np.arange(_KC)[None] < np.asarray(qlens)[:, None]
    assert valid.any()
    np.testing.assert_allclose(got[valid], want[valid], rtol=2e-5,
                               atol=2e-5)
    # decode rows take the decode kernel's own page step: same bits
    dec = np.asarray(PA.paged_attention_decode(q[:, 0], k_pages, v_pages,
                                               tables, ctx + 1))
    rows = np.asarray(qlens) == 1
    if verify is None:
        np.testing.assert_array_equal(got[rows, 0], dec[rows])


def test_served_kernel_bf16_queries_feed_the_mxu_the_same_numbers():
    """bf16 queries against a bf16 pool go to the MXU as bf16 with a
    float32 result: products of two bf16 numbers are exact in float32,
    so the scores are those of the float32 contraction and the output,
    rounded to bf16, is within one bf16 ulp (2**-8 relative) of the
    float32-query result rounded the same way."""
    import jax
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    ctx, qlens, _ = _KERNEL_CASES["mixed_with_inactive_row"]
    ctx = jnp.asarray(ctx, jnp.int32)
    qlens = jnp.asarray(qlens, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(7), (_KB, _KC, _KH, _KD),
                          jnp.float32).astype(jnp.bfloat16)
    k_pages, v_pages = _kernel_pools("bf16", seed=11)
    tables = jnp.arange(_KB * _KMAXP, dtype=jnp.int32).reshape(_KB, _KMAXP)
    narrow = RPA.ragged_paged_attention(q, k_pages, v_pages, tables, ctx,
                                        qlens)
    wide = RPA.ragged_paged_attention(q.astype(jnp.float32), k_pages,
                                      v_pages, tables, ctx, qlens)
    assert narrow.dtype == jnp.bfloat16
    valid = np.arange(_KC)[None] < np.asarray(qlens)[:, None]
    np.testing.assert_allclose(
        np.asarray(narrow.astype(jnp.float32))[valid],
        np.asarray(wide.astype(jnp.bfloat16).astype(jnp.float32))[valid],
        rtol=2.0 ** -7, atol=1e-6)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_served_kernel_is_schedule_independent_bitwise(pool):
    """A position's output is a function of its own query and of the
    page bytes up to its position, whatever else the launch carries:
    positions 5..16 of one sequence computed (a) as one chunk, (b) as
    two chunks in two rows of one launch, (c) each as the second query
    of a two-token row (the chunk body with the fewest queries it
    takes), and (d) over a pool whose every slot past the queries'
    horizons holds other finite garbage — equal bits.  This is
    what lets chunk splits, warm prefix hits, replay, park/resume and
    handoff re-run a position and land on the same token."""
    import jax
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    c, h, d, page, maxp = 12, _KH, _KD, _KPAGE, 6
    lo, hi = 5, 17                      # positions computed: lo..hi-1
    k_pages, v_pages = _kernel_pools(pool, seed=3)
    qs = jax.random.normal(jax.random.PRNGKey(5), (hi, h, d), jnp.float32)

    def launch(rows, k_pages=k_pages, v_pages=v_pages):
        """rows: (ctx, qlen) each; every row walks the same table and
        carries the sequence's own queries for its positions."""
        b = len(rows)
        q = np.zeros((b, c, h, d), np.float32)
        for r, (ctx, qlen) in enumerate(rows):
            q[r, :qlen] = np.asarray(qs[ctx:ctx + qlen])
        tables = jnp.tile(jnp.arange(maxp, dtype=jnp.int32)[None], (b, 1))
        out = RPA.ragged_paged_attention(
            jnp.asarray(q), k_pages, v_pages, tables,
            jnp.asarray([r[0] for r in rows], jnp.int32),
            jnp.asarray([r[1] for r in rows], jnp.int32))
        return np.asarray(out)

    one = launch([(lo, hi - lo)])[0, :hi - lo]
    two = launch([(lo, 7), (lo + 7, hi - lo - 7)])
    two = np.concatenate([two[0, :7], two[1, :hi - lo - 7]])
    np.testing.assert_array_equal(two, one)
    pairs = launch([(p - 1, 2) for p in range(lo, hi)])[:, 1]
    np.testing.assert_array_equal(pairs, one)

    def garbage(pages, start):
        """Other finite values in every slot from position ``start`` on
        (the sequence's table is pages 0..maxp-1, in order)."""
        payload = pages[0] if isinstance(pages, tuple) else pages
        noise = jnp.clip(50 * jax.random.normal(
            jax.random.PRNGKey(99), payload.shape, jnp.float32), -127, 127)
        slot = (jnp.arange(payload.shape[0])[:, None] * page
                + jnp.arange(page)[None])[:, None, :, None]
        dirty = jnp.where(slot >= start, noise.astype(payload.dtype),
                          payload)
        return (dirty, pages[1]) if isinstance(pages, tuple) else dirty

    np.testing.assert_array_equal(
        launch([(lo, hi - lo)], garbage(k_pages, hi),
               garbage(v_pages, hi))[0, :hi - lo], one)
    # the first chunk of (b) alone, over what a real first chunk sees:
    # whatever its pages held before, from its own last position on —
    # inside pages the row still walks
    np.testing.assert_array_equal(
        launch([(lo, 7)], garbage(k_pages, lo + 7),
               garbage(v_pages, lo + 7))[0, :7], one[:7])


# ------------------------------------------------------------------ parity

def _serve(engine, prompts, cfgs, rid_base, together=True, **kw):
    """Run the requests through a fresh core with the rid counter
    pinned — all submitted at once, or one at a time (each alone in the
    batch, same rids) — returning the emitted streams."""
    for k, v in CORE_SHAPE.items():
        kw.setdefault(k, v)
    request_mod._rid_counter = itertools.count(rid_base)
    core = EngineCore(engine, **kw)
    try:
        if together:
            reqs = [core.submit(p, g)[0] for p, g in zip(prompts, cfgs)]
            _drive(core, reqs)
        else:
            reqs = []
            for p, g in zip(prompts, cfgs):
                reqs.append(core.submit(p, g)[0])
                _drive(core, reqs[-1:])
        assert all(r.state is RequestState.DONE for r in reqs)
        return [np.asarray(r.padded_result()) for r in reqs]
    finally:
        core.close()


def test_greedy_stream_equals_offline_generate(engine, ref):
    """Three prompts admitted together (their chunks and decode rows
    share mixed steps) emit, each, the greedy stream a direct paged
    ``generate()`` of the prompt alone does."""
    prompts = [_prompt(1, 11), _prompt(2, 21), _prompt(3, 5)]
    cfgs = [GenerationConfig(max_new_tokens=8),
            GenerationConfig(max_new_tokens=6),
            GenerationConfig(max_new_tokens=7)]
    served = _serve(engine, prompts, cfgs, rid_base=5000)
    for ids, g, got in zip(prompts, cfgs, served):
        np.testing.assert_array_equal(got, ref.generate(ids[None], g)[0])


def test_sampled_stream_is_co_schedule_independent_bitwise(engine):
    """Seeded-sampled requests emit the same streams whether they share
    their mixed steps (chunks cut by the shared token budget, decode
    rows beside other rows) or are served one at a time under the same
    rids: the stream-level face of the kernel's schedule independence."""
    prompts = [_prompt(1, 11), _prompt(2, 21), _prompt(3, 5)]
    cfgs = [GenerationConfig(max_new_tokens=8, do_sample=True,
                             temperature=0.8, top_k=12, top_p=0.9,
                             seed=7),
            GenerationConfig(max_new_tokens=6, do_sample=True,
                             temperature=1.2, seed=11),
            GenerationConfig(max_new_tokens=7, do_sample=True,
                             top_k=5, seed=3)]
    together = _serve(engine, prompts, cfgs, rid_base=5000)
    alone = _serve(engine, prompts, cfgs, rid_base=5000, together=False)
    for tg, al in zip(together, alone):
        np.testing.assert_array_equal(tg, al)


_PAGE = 8
_CHUNK = CORE_SHAPE["prefill_chunk"]
_MAX_NEW = 8


@pytest.mark.parametrize("length", [
    _PAGE - 1, _PAGE, _PAGE + 1, _CHUNK - 1, _CHUNK, _CHUNK + 1,
    2 * _CHUNK + 1, CORE_SHAPE["max_model_len"] - _MAX_NEW])
def test_chunked_prompt_matches_offline_generate(engine, ref, length):
    """Prompts whose lengths straddle a page and a chunk boundary, up to
    the longest the window admits (three mixed steps of prefill): the
    stream equals a direct paged generate(), which pads the prompt to
    its bucket and prefills it in one program."""
    ids = _prompt(4, length)
    g = GenerationConfig(max_new_tokens=_MAX_NEW)
    (served,) = _serve(engine, [ids], [g], rid_base=5100)
    np.testing.assert_array_equal(served, ref.generate(ids[None], g)[0])


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_warm_prefix_hit_bitwise_equals_cold(engine, sampled):
    """Warm prefix-cache hits (full and partial-tail) emit the streams a
    core without the cache does: the warm path stages the matched pages
    and chunks only the uncached suffix, whose positions the kernel
    computes as it would have in a cold chunk."""
    base = _prompt(5, 24)
    tail = np.concatenate([base[:16], _prompt(6, 6)])
    if sampled:
        g = GenerationConfig(max_new_tokens=6, do_sample=True,
                             temperature=0.8, top_k=12, seed=13)
    else:
        g = GenerationConfig(max_new_tokens=6)

    def run(enable_prefix_cache):
        request_mod._rid_counter = itertools.count(5200)
        core = EngineCore(engine, enable_prefix_cache=enable_prefix_cache,
                          **CORE_SHAPE)
        try:
            outs = []
            for ids in (base, base, tail):   # cold, full hit, partial
                (r,) = core.submit(ids, g)
                _drive(core, [r])
                outs.append(np.asarray(r.padded_result()))
            if enable_prefix_cache:
                stats = core.prefix_cache.stats_snapshot()
                assert stats["hits"] >= 2, "warm admissions never hit"
            return outs
        finally:
            core.close()

    cold, warm = run(False), run(True)
    for cd, wm in zip(cold, warm):
        np.testing.assert_array_equal(wm, cd)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_replay_after_kv_loss_equals_uninterrupted_stream(engine, sampled):
    """Supervisor replay parity: a mid-decode crash that loses the KV
    pools replays the in-flight row; the recovered stream equals the
    uninterrupted one (same rid, so sampled rows resume at the original
    fold_in offsets)."""
    ids = _prompt(7, 10)
    if sampled:
        g = GenerationConfig(max_new_tokens=12, do_sample=True,
                             temperature=0.8, top_k=12, seed=17)
    else:
        g = GenerationConfig(max_new_tokens=12)
    (want,) = _serve(engine, [ids], [g], rid_base=5300)

    request_mod._rid_counter = itertools.count(5300)
    plane = FaultPlane([FaultSpec("decode.step", at=4, lose_kv=True)])
    core = EngineCore(engine, fault_plane=plane, **CORE_SHAPE)
    sup = EngineSupervisor(core)
    try:
        (req,) = core.submit(ids, g)
        for _ in range(400):
            if req.done:
                break
            sup.run_once()
        assert req.state is RequestState.DONE
        assert req.retries == 1
        np.testing.assert_array_equal(req.padded_result(), want)
    finally:
        sup.close()


# -------------------------------------------------------------------- fuzz

def test_composition_fuzz_invariants_and_zero_compiles(engine, ref):
    """160+ scheduler steps of random mixed traffic: long chunked
    prompts, decode-only stretches, mixed steps, idle drains.  Pool
    conservation holds at every step, every greedy stream matches a
    direct generate(), and — after a one-request warmup — the whole run
    performs ZERO new XLA compilations: composition is data."""
    from paddle_infer_tpu.observability import get_compile_log

    log = get_compile_log()
    core = EngineCore(engine, **CORE_SHAPE)
    try:
        total = core._pool.num_blocks
        (w,) = core.submit(_prompt(900, 20), GenerationConfig(
            max_new_tokens=4))
        _drive(core, [w])
        warm_compiles = log.summary()["compile_count"]

        rng = random.Random(0)
        live, finished = [], []
        steps = 0
        arrivals = 0
        while steps < 160 or any(not r.done for r, _ in live):
            if (arrivals < 32 and core.queue_depth < 3
                    and rng.random() < 0.4):
                n = rng.choice([3, 5, 11, 17, 26, 40])
                if rng.random() < 0.4:
                    g = GenerationConfig(
                        max_new_tokens=rng.randint(2, 8), do_sample=True,
                        temperature=0.9, top_k=20,
                        seed=rng.randint(0, 999))
                else:
                    g = GenerationConfig(
                        max_new_tokens=rng.randint(2, 8))
                ids = _prompt(100 + arrivals, n)
                (r,) = core.submit(ids, g)
                live.append((r, (ids, g)))
                arrivals += 1
            core.run_once()
            steps += 1
            used = total - core._pool.free_blocks
            assert 0 <= used <= total, "pool accounting broke mid-run"
            assert steps < 3000, "fuzz traffic never drained"
        finished = [(r, meta) for r, meta in live]

        assert steps >= 160 and arrivals >= 16
        for r, _ in finished:
            assert r.state is RequestState.DONE, (r.rid, r.error)
        # greedy rows are rid-independent: each must match generate()
        greedy = [(r, ids, g) for r, (ids, g) in finished
                  if not g.do_sample]
        assert greedy
        for r, ids, g in greedy:
            np.testing.assert_array_equal(
                r.padded_result(), ref.generate(ids[None], g)[0])
        # every row drained: only the scratch page stays resident
        assert total - core._pool.free_blocks == 1
        # the tentpole invariant: nothing compiled after warmup
        assert log.summary()["compile_count"] == warm_compiles, \
            "batch composition leaked into executable shapes"
        assert log.summary()["post_warmup_decode_compiles"] == 0
        summary = core.steplog.summary()
        kinds = set(summary["by_kind"])
        assert {"mixed", "prefill", "decode"} & kinds
        assert summary["by_kernel"].get("ragged", 0) > 0
        assert summary["prefill_chunk_tokens_total"] > 0
    finally:
        core.close()


def test_steplog_records_kernel_and_chunk_fields(make_core):
    """StepLog satellite: ragged steps record kernel="ragged" and
    chunked-prefill token counts; the summary aggregates both."""
    core = make_core(prefill_chunk=8)
    (r,) = core.submit(_prompt(8, 20), GenerationConfig(max_new_tokens=4))
    _drive(core, [r])
    records = core.steplog.records()
    assert records and all(rec["kernel"] == "ragged" for rec in records
                           if rec["kind"] in ("mixed", "prefill",
                                              "decode"))
    chunked = [rec for rec in records if rec["prefill_chunk_tokens"] > 0]
    assert len(chunked) >= 3              # 20-token prompt, chunk 8
    assert sum(rec["prefill_chunk_tokens"] for rec in chunked) == 20
    summary = core.steplog.summary()
    assert summary["prefill_chunk_tokens_total"] == 20
    assert summary["by_kernel"]["ragged"] == len(
        [rec for rec in records if rec["kind"] != "evict"])
