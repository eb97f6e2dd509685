"""In-engine speculative decoding: batched draft/verify rows inside the
ragged mixed step (paddle_infer_tpu/serving/engine_core.py speculate=True
+ ops/pallas paged_attention_verify).

Coverage layers:

* kernel — ``paged_attention_verify`` lane (b, w) is BITWISE the
  single-query decode kernel at ``lengths[b, w]``: the verify step's
  one-page-walk-per-row construction reproduces W sequential decode
  steps exactly;
* parity — greedy repeat traffic through a ``speculate=True`` core is
  bitwise-identical to the plain core's streams, drafts accepted and
  all (speculation is a throughput knob, never a correctness knob);
* rollback — an injected ``decode.step`` fault that loses the KV pools
  mid-verify replays to the exact unfaulted stream, and rejected draft
  tails never leak pool blocks (refcount accounting balances to the
  scratch page + tree-retained blocks after every drain);
* fuzz — 200+ scheduler steps mixing speculating decode rows, plain
  decode rows, sampled rows and chunked prefills, with pool/tree
  refcount invariants checked every step and ZERO post-warmup XLA
  compiles: the draft window is in the executable key, so draft count
  per row is data, not shape.
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
    """Spec-vs-plain parity compares tokens across differently-shaped
    executables, which is bitwise only when both run unsharded."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
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


# One shape for every core in the module so the serving executables and
# the ONE page-pool size compile once.  Retention headroom is uniform
# (speculate=False cores included): the pool size is part of the
# executable key, and the headroom is what lets the radix tree — the
# draft source — survive next to a fully occupied batch.
CORE_SHAPE = dict(max_batch=3, max_model_len=48, token_budget=16,
                  prefill_chunk=16,
                  enable_prefix_cache=True,
                  prefix_cache_headroom_pages=12)


def _core(engine, **kw):
    for k, v in CORE_SHAPE.items():
        kw.setdefault(k, v)
    return EngineCore(engine, **kw)


def _drive(core, reqs, max_iters=400):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _assert_pool_tree_balance(core):
    """Every pool block's refcount agrees with the free-list, and with
    no live rows exactly the scratch reservation plus the tree-retained
    blocks stay resident — a leaked (or double-freed) draft tail cannot
    satisfy both."""
    pool = core._pool
    total = pool.num_blocks
    held = sum(1 for i in range(total) if pool.block_refcount(i) > 0)
    assert held == total - pool.free_blocks, \
        "refcounts disagree with the free list"
    assert total - pool.free_blocks == 1 + core.prefix_cache.cached_blocks


# ------------------------------------------------------------------ kernel

def test_verify_kernel_lanes_bitwise_match_decode():
    """paged_attention_verify lane (b, w) == paged_attention_decode at
    lengths[b, w], bit for bit — the greedy-parity foundation."""
    import jax.numpy as jnp

    from paddle_infer_tpu.ops.pallas.paged_attention import (
        paged_attention_decode, paged_attention_verify)

    rng = np.random.RandomState(0)
    b, w, h, d, page, max_pages, num_pages = 4, 5, 2, 16, 8, 5, 24
    q = jnp.asarray(rng.randn(b, w, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(num_pages, h, page, d), jnp.float32)
    v = jnp.asarray(rng.randn(num_pages, h, page, d), jnp.float32)
    tables = jnp.asarray(rng.randint(0, num_pages, (b, max_pages)),
                         jnp.int32)
    ctx = rng.randint(1, max_pages * page - w - 1, (b,))
    # position j attends ctx + j + 1 — nondecreasing, the kernel's gate
    lens = jnp.asarray(ctx[:, None] + np.arange(w)[None] + 1, jnp.int32)

    out = np.asarray(paged_attention_verify(q, k, v, tables, lens))
    for j in range(w):
        want = np.asarray(paged_attention_decode(q[:, j], k, v, tables,
                                                 lens[:, j]))
        np.testing.assert_array_equal(out[:, j], want)


# ------------------------------------------------------------------ parity

def _serve_twice(engine, prompts, cfgs, rid_base, **kw):
    """Cold pass (retains every stream into the radix tree) then a warm
    repeat pass — the speculation traffic shape.  Returns both passes'
    streams and the final metrics snapshot."""
    request_mod._rid_counter = itertools.count(rid_base)
    core = _core(engine, **kw)
    try:
        passes = []
        for _ in range(2):
            reqs = [core.submit(p, g)[0] for p, g in zip(prompts, cfgs)]
            _drive(core, reqs)
            assert all(r.state is RequestState.DONE for r in reqs)
            passes.append([np.asarray(r.padded_result()) for r in reqs])
        snap = core.metrics_snapshot()
        _assert_pool_tree_balance(core)
        return passes, snap
    finally:
        core.close()


def test_spec_greedy_streams_bitwise_equal_plain(engine):
    """Acceptance bar: with real drafts flowing (tree lookahead on the
    repeat pass), every greedy stream from the speculative core is
    BITWISE the plain core's — and the cold pass (no tree yet) too."""
    prompts = [_prompt(31, 9), _prompt(32, 17), _prompt(33, 5)]
    cfgs = [GenerationConfig(max_new_tokens=10),
            GenerationConfig(max_new_tokens=8),
            GenerationConfig(max_new_tokens=12)]
    plain, _ = _serve_twice(engine, prompts, cfgs, rid_base=7000,
                            speculate=False)
    spec, snap = _serve_twice(engine, prompts, cfgs, rid_base=7000,
                              speculate=True, num_draft_tokens=4)
    for p_pass, s_pass in zip(plain, spec):
        for pl, sp in zip(p_pass, s_pass):
            np.testing.assert_array_equal(sp, pl)
    # the comparison is vacuous unless the spec core actually
    # speculated: the warm pass must accept real draft tokens
    s = snap["speculation"]
    assert s["rows"] > 0 and s["drafts_accepted"] > 0
    assert s["drafts_accepted"] <= s["drafts_proposed"]


def test_spec_sampled_streams_complete_and_account(engine):
    """Sampled rows under speculation are exactly distributed but NOT
    bitwise-comparable to the plain stream (verify grouping changes RNG
    consumption); what must hold: requests complete, draft accounting
    is sane, and nothing leaks."""
    prompts = [_prompt(41, 7), _prompt(42, 13)]
    cfgs = [GenerationConfig(max_new_tokens=8, do_sample=True,
                             temperature=0.9, top_k=20, seed=5),
            GenerationConfig(max_new_tokens=6, do_sample=True,
                             temperature=1.1, seed=9)]
    passes, snap = _serve_twice(engine, prompts, cfgs, rid_base=7100,
                                speculate=True, num_draft_tokens=4)
    for stream, g in zip(passes[1], cfgs):
        assert stream.size <= len(prompts[0]) + 64
    s = snap["speculation"]
    assert s["drafts_accepted"] <= s["drafts_proposed"]


# ---------------------------------------------------------------- rollback

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_spec_replay_after_decode_fault_equals_plain(engine, sampled):
    """Rollback acceptance: a decode.step fault that loses the KV pools
    mid-speculation replays the row; the recovered stream equals the
    plain core's uninterrupted one (same rid), and no draft-tail block
    survives the crash-and-drain."""
    ids = _prompt(51, 10)
    if sampled:
        g = GenerationConfig(max_new_tokens=12, do_sample=True,
                             temperature=0.8, top_k=12, seed=17)
    else:
        g = GenerationConfig(max_new_tokens=12)
    request_mod._rid_counter = itertools.count(7200)
    plain = _core(engine, speculate=False)
    try:
        # warm the tree so the faulted run's first pass has drafts
        (w0,) = plain.submit(ids, g)
        _drive(plain, [w0])
        (w1,) = plain.submit(ids, g)
        _drive(plain, [w1])
        want = np.asarray(w1.padded_result())
    finally:
        plain.close()

    request_mod._rid_counter = itertools.count(7200)
    plane = FaultPlane([FaultSpec("decode.step", at=4, lose_kv=True)])
    core = _core(engine, speculate=True, num_draft_tokens=4,
                 fault_plane=plane)
    sup = EngineSupervisor(core)
    try:
        (w0,) = core.submit(ids, g)
        for _ in range(400):
            if w0.done:
                break
            sup.run_once()
        assert w0.state is RequestState.DONE
        (req,) = core.submit(ids, g)
        for _ in range(400):
            if req.done:
                break
            sup.run_once()
        assert req.state is RequestState.DONE
        assert w0.retries + req.retries >= 1, "fault never fired"
        if not sampled:
            np.testing.assert_array_equal(req.padded_result(), want)
        _assert_pool_tree_balance(core)
    finally:
        sup.close()


# -------------------------------------------------------------------- fuzz

def test_spec_fuzz_invariants_and_zero_compiles(engine, ref):
    """200+ scheduler steps of random mixed traffic through a
    speculative core: repeat-family prompts (tree drafts), fresh
    prompts (ngram or no drafts), sampled rows (deterministic-only
    proposals), long chunked prompts.  Pool/tree refcount invariants
    hold at every step, greedy streams match a direct generate(), and
    after warmup the run performs ZERO new XLA compilations — draft
    count per row is data, not shape."""
    from paddle_infer_tpu.observability import get_compile_log

    log = get_compile_log()
    request_mod._rid_counter = itertools.count(7300)
    core = _core(engine, speculate=True, num_draft_tokens=4)
    try:
        pool = core._pool
        total = pool.num_blocks
        # warmup: one long chunked prompt (prefill program) driven
        # twice — the repeat admission stages a prefix hit, compiling
        # the page-copy program, and its decode steps carry real drafts
        # through the W-window mixed executable
        warm_ids = _prompt(901, 20)
        g_warm = GenerationConfig(max_new_tokens=4)
        (w,) = core.submit(warm_ids, g_warm)
        _drive(core, [w])
        (w,) = core.submit(warm_ids, g_warm)
        _drive(core, [w])
        warm_compiles = log.summary()["compile_count"]

        rng = random.Random(0)
        families = [_prompt(910 + f, n)
                    for f, n in enumerate([5, 9, 14, 26, 40])]
        live = []
        steps = 0
        arrivals = 0
        while steps < 200 or any(not r.done for r, _ in live):
            if (arrivals < 40 and core.queue_depth < 3
                    and rng.random() < 0.45):
                if rng.random() < 0.6:     # repeat family: tree drafts
                    ids = families[rng.randrange(len(families))]
                else:                      # fresh prompt: cold path
                    ids = _prompt(950 + arrivals, rng.choice([4, 7, 12]))
                if rng.random() < 0.35:
                    g = GenerationConfig(
                        max_new_tokens=rng.randint(2, 8), do_sample=True,
                        temperature=0.9, top_k=20,
                        seed=rng.randint(0, 999))
                else:
                    g = GenerationConfig(max_new_tokens=rng.randint(2, 8))
                (r,) = core.submit(ids, g)
                live.append((r, (ids, g)))
                arrivals += 1
            core.run_once()
            steps += 1
            used = total - pool.free_blocks
            assert 0 <= used <= total, "pool accounting broke mid-run"
            held = sum(1 for i in range(total)
                       if pool.block_refcount(i) > 0)
            assert held == used, "refcounts disagree with the free list"
            assert core.prefix_cache.cached_blocks <= used
            assert steps < 3000, "fuzz traffic never drained"

        # the tentpole invariant: draft windows never leaked into
        # executable shapes.  Captured BEFORE the ref.generate()
        # comparisons below — the reference engine's own first-use
        # compiles land in the same process-wide log
        assert log.summary()["compile_count"] == warm_compiles, \
            "speculation leaked into executable shapes"
        assert log.summary()["post_warmup_decode_compiles"] == 0

        assert steps >= 200 and arrivals >= 20
        for r, _ in live:
            assert r.state is RequestState.DONE, (r.rid, r.error)
        greedy = [(r, ids, g) for r, (ids, g) in live if not g.do_sample]
        assert greedy
        for r, ids, g in greedy:
            np.testing.assert_array_equal(
                r.padded_result(), ref.generate(ids[None], g)[0])
        _assert_pool_tree_balance(core)
        # the run must have genuinely speculated
        s = core.metrics_snapshot()["speculation"]
        assert s["rows"] > 0 and s["drafts_accepted"] > 0
    finally:
        core.close()


# ----------------------------------------------------------- observability

def test_spec_steplog_and_metrics_accounting(engine):
    """Per-step draft accounting: StepLog records carry
    draft_tokens/draft_accepted/spec_rows, the summary totals them, and
    the metrics snapshot's speculation block agrees."""
    request_mod._rid_counter = itertools.count(7400)
    core = _core(engine, speculate=True, num_draft_tokens=4)
    try:
        ids = _prompt(61, 9)
        g = GenerationConfig(max_new_tokens=10)
        (r,) = core.submit(ids, g)
        _drive(core, [r])
        core.steplog.clear()
        core.metrics.reset()
        (r,) = core.submit(ids, g)      # warm repeat: drafts flow
        _drive(core, [r])
        recs = [rec for rec in core.steplog.records()
                if rec["kind"] in ("decode", "mixed")]
        spec_recs = [rec for rec in recs if rec["spec_rows"] > 0]
        assert spec_recs, "no step recorded speculating rows"
        for rec in spec_recs:
            assert 0 <= rec["draft_accepted"] <= rec["draft_tokens"]
        summary = core.steplog.summary()
        assert summary["draft_tokens_total"] == \
            sum(rec["draft_tokens"] for rec in recs)
        assert summary["draft_accepted_total"] == \
            sum(rec["draft_accepted"] for rec in recs)
        snap = core.metrics_snapshot()["speculation"]
        assert snap["drafts_proposed"] == summary["draft_tokens_total"]
        assert snap["drafts_accepted"] == summary["draft_accepted_total"]
        assert snap["acceptance_rate"] == pytest.approx(
            summary["draft_accepted_total"]
            / max(summary["draft_tokens_total"], 1))
    finally:
        core.close()
