"""Prefix KV-cache manager (paddle_infer_tpu/serving/prefix_cache/):
radix-tree block reuse, copy-on-write tails, LRU eviction, and the
correctness bar — warm (cached-prefix) logits bitwise-equal to cold.

The fuzz test drives the tree + native pool through random
admit/finish/evict interleavings with structural invariants checked at
every step (refcount consistency, no double-retain, free + used ==
num_blocks).  The parity tests run the REAL windowed prefill programs
and assert exact equality, including a partial-tail match that forces a
copy-on-write."""
import math
import random

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu import native
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.serving import EngineCore
from paddle_infer_tpu.serving.prefix_cache import PrefixCache


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
    """The CompileLog is a process singleton: warm marks left by this
    module's cores would flag later modules' first decode compiles
    (identical site/key, different engine) as post-warmup recompiles."""
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
    return PagedGenerationEngine(model, page_size=8, prompt_bucket=16)


@pytest.fixture
def make_core(engine):
    cores = []

    def make(**kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("enable_prefix_cache", True)
        core = EngineCore(engine, **kw)
        cores.append(core)
        return core

    yield make
    for c in cores:
        c.close()


def _drive(core, reqs, max_iters=200):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=20):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


# --------------------------------------------------------------- native
def test_block_ops_refcount_lifecycle():
    pool = native.KVBlockPool(8, 4)
    b = pool.alloc_block()
    assert pool.block_refcount(b) == 1
    pool.ref_block(b)
    assert pool.block_refcount(b) == 2
    assert pool.unref_block(b) == 1
    assert pool.unref_block(b) == 0          # freed
    assert pool.free_blocks == 8
    with pytest.raises(ValueError):
        pool.unref_block(b)                  # double-free guard
    with pytest.raises(ValueError):
        pool.ref_block(b)                    # can't revive a free block


def test_assign_takes_per_sequence_refs():
    pool = native.KVBlockPool(8, 4)
    pool.reserve(0, 8)                       # seq 0: 2 blocks
    t0 = [int(x) for x in pool.block_table(0)]
    pool.assign(1, t0, 8)                    # seq 1 shares them
    assert all(pool.block_refcount(b) == 2 for b in t0)
    pool.free(0)
    assert all(pool.block_refcount(b) == 1 for b in t0)
    assert pool.num_blocks - pool.free_blocks == 2
    pool.free(1)
    assert pool.free_blocks == 8
    with pytest.raises(ValueError):          # dead block rejected whole
        pool.assign(2, t0, 8)
    assert pool.free_blocks == 8


def test_matched_partial_tail_pinned_against_eviction():
    """Regression (tpulint self-application): a matched partial tail
    entry must be pinned from match() to release() — eviction pressure
    in that window used to recycle the tail block while the consumer
    still planned to CoW-copy it, aliasing another request's KV."""
    pool = native.KVBlockPool(8, 4)
    cache = PrefixCache(pool, page_size=4, watermark=1.0)
    pool.reserve(0, 6)                       # 1 full page + 2-token tail
    table = [int(x) for x in pool.block_table(0)]
    cache.insert(list(range(6)), table)
    pool.free(0)                             # tree holds the only refs
    m = cache.match([0, 1, 2, 3, 4, 99])     # full page + 1-token tail
    assert m.partial_block == table[1] and m.partial_len == 1
    # demand more free blocks than can exist: everything unpinned would
    # be evicted — the matched tail (and matched node) must survive
    assert not cache.ensure_free(pool.num_blocks)
    assert pool.block_refcount(m.partial_block) == 1
    blk = m.partial_block
    cache.release(m)                         # consumer left the slot
    assert cache.ensure_free(pool.num_blocks)
    assert pool.free_blocks == pool.num_blocks
    with pytest.raises(ValueError):          # truly freed now
        pool.ref_block(blk)


# ----------------------------------------------------------------- fuzz
def _tree_blocks(cache):
    out = []
    stack = list(cache._roots.values())
    while stack:
        n = stack.pop()
        stack.extend(n.children.values())
        if n.block is not None:
            out.append(n.block)
        for entry in n.partials.values():
            out.append(entry[0])
    return out


def _check_invariants(pool, cache, active_seqs):
    tb = _tree_blocks(cache)
    assert len(tb) == len(set(tb)), "tree retains a block twice"
    assert cache.cached_blocks == len(tb), "cached_blocks gauge drifted"
    for b in tb:
        assert pool.block_refcount(b) >= 1, "tree holds a freed block"
    live = set(tb)
    for s in active_seqs:
        live.update(int(x) for x in pool.block_table(s))
    used = pool.num_blocks - pool.free_blocks
    assert used == len(live), (
        f"pool accounting drifted: used={used} live={len(live)} "
        f"(free + used must equal num_blocks with no leaked blocks)")


def test_prefix_cache_fuzz():
    """Random admit/finish/evict interleavings against a real native
    pool, mirroring the engine's staging protocol (match -> ensure_free
    -> CoW alloc -> assign -> reserve), with invariants after every
    op: refcount consistency, no double-free, free + used ==
    num_blocks."""
    page = 4
    pool = native.KVBlockPool(48, page)
    cache = PrefixCache(pool, page, watermark=0.75)
    rng = random.Random(0)
    active = {}
    next_seq = 0
    for _ in range(400):
        op = rng.choice(["admit", "admit", "finish", "finish", "evict"])
        if op == "admit" and len(active) < 6:
            tokens = [rng.randrange(5)
                      for _ in range(rng.randrange(2, 30))]
            m = cache.match(tokens)
            seq = next_seq
            next_seq += 1
            reserve = len(tokens) + rng.randrange(0, 8)
            total_pages = math.ceil(reserve / page)
            cache.ensure_free(total_pages - len(m.blocks))
            try:
                cow = None
                if m.partial_block is not None:
                    cow = pool.alloc_block()
                    cache.on_cow()
                blocks = list(m.blocks)
                ntok = len(blocks) * page
                if cow is not None:
                    blocks.append(cow)
                    ntok += m.partial_len
                try:
                    if blocks:
                        pool.assign(seq, blocks, ntok)
                finally:
                    if cow is not None:
                        pool.unref_block(cow)
                pool.reserve(seq, reserve)
                active[seq] = (m, tokens)
            except MemoryError:
                pool.free(seq)
                cache.release(m)
        elif op == "finish" and active:
            seq = rng.choice(sorted(active))
            m, tokens = active.pop(seq)
            if rng.random() < 0.7:       # DONE: retain-on-finish
                cache.insert(tokens, pool.block_table(seq))
            pool.free(seq)
            cache.release(m)
            cache.enforce_watermark()
        elif op == "evict":
            cache.ensure_free(rng.randrange(0, 12))
        _check_invariants(pool, cache, active)
    for seq in sorted(active):
        m, _ = active.pop(seq)
        pool.free(seq)
        cache.release(m)
    cache.clear()
    assert pool.free_blocks == pool.num_blocks   # nothing leaked
    snap = cache.stats_snapshot()
    assert snap["cached_blocks"] == 0 and snap["nodes"] == 0


def test_evictions_follow_the_brute_force_lru_and_count_their_pops():
    """The victim comes off a heap, never a walk of the tree: under
    random inserts, matches, trims and releases every eviction takes an
    entry of the least ``last_used`` a walk of the whole tree
    (``_candidates``) finds evictable at that moment, leaf first;
    ``evict_scanned_nodes`` counts the heap entries popped, a few a
    victim and not the tree's size; the loop's seconds add up beside
    it; ``clear`` leaves nothing behind."""
    page = 4
    pool = native.KVBlockPool(96, page)
    cache = PrefixCache(pool, page, watermark=1.0)
    rng = random.Random(3)
    real = cache._pop_victim
    checked = []

    def checking():
        want = min((c[0] for c in cache._candidates()), default=None)
        victim = real()
        if victim is None:
            assert want is None
            return None
        kind, node, key = victim
        got = node.partials[key][1] if kind == "partial" else node.last_used
        assert got == want
        checked.append(kind)
        return victim

    cache._pop_victim = checking
    held = []
    for seq in range(60):                # branching prefixes and tails
        tokens = [rng.randrange(3) for _ in range(rng.randrange(5, 23))]
        m = cache.match(tokens)
        if rng.random() < 0.3:
            cache.trim(m, rng.randrange(0, 12))
        held.append(m)
        need = -(-len(tokens) // page)
        assert cache.ensure_free(need) or pool.free_blocks >= need
        pool.reserve(seq, len(tokens))
        cache.insert(tokens, pool.block_table(seq))
        pool.free(seq)
        while len(held) > 3 or (held and rng.random() < 0.5):
            cache.release(held.pop(rng.randrange(len(held))))
        if rng.random() < 0.4:
            cache.ensure_free(pool.free_blocks + rng.randrange(1, 4))
        assert cache.cached_blocks == len(_tree_blocks(cache))
    assert cache.insert_seconds > 0.0
    assert cache.evicted_blocks == len(checked) >= 30
    assert {"node", "partial"} <= set(checked)
    # a pop a victim and the stale pushes between: not the tree's size
    assert cache.evicted_blocks <= cache.evict_scanned_nodes \
        <= 6 * cache.evicted_blocks
    snap = cache.stats_snapshot()
    assert snap["evict_scanned_nodes"] == cache.evict_scanned_nodes
    assert snap["evicted_blocks"] == cache.evicted_blocks
    # a call with nothing to evict pops nothing and times nothing
    seconds, popped = cache.evict_seconds, cache.evict_scanned_nodes
    assert seconds > 0.0 and cache.ensure_free(0)
    cache.enforce_watermark()
    assert (cache.evict_seconds, cache.evict_scanned_nodes) == (seconds,
                                                                popped)
    for m in held:
        cache.release(m)
    cache.clear()
    assert pool.free_blocks == pool.num_blocks and not cache._evictable
    assert cache.cached_blocks == 0 and not _tree_blocks(cache)


def test_admission_eviction_lands_in_the_step_record(make_core):
    """Watermark 1.0 evicts nothing at a release, so what the pool lacks
    is evicted by ``ensure_free`` at admission: the step record of that
    iteration carries the walk, and no evict record does."""
    core = make_core(prefix_cache_watermark=1.0)
    cache = core.prefix_cache
    g = GenerationConfig(max_new_tokens=4)
    for seed in range(30, 42):
        (r,) = core.submit(_prompt(seed, 20), g)
        _drive(core, [r])
    assert cache.evicted_blocks > 0
    recs = core.steplog.records()
    steps = [r for r in recs if r["t_begin"] > 0.0]
    evicts = [r for r in recs if r["kind"] == "evict"]
    assert len(evicts) == 12
    assert all(e["evicted_blocks"] == 0 and e["evict_s"] == 0.0
               and e["insert_s"] > 0.0 for e in evicts)
    walked = [r for r in steps if r["evicted_blocks"]]
    assert walked and all(
        r["finished_rows"] == 0 and r["release_s"] == 0.0
        and 0.0 < r["evict_s"] <= r["admit_s"]
        and r["evict_scanned_nodes"] >= r["evicted_blocks"] for r in walked)
    assert sum(r["evicted_blocks"] for r in steps) == cache.evicted_blocks
    assert sum(r["evict_scanned_nodes"] for r in steps) == \
        cache.evict_scanned_nodes
    assert sum(r["evict_s"] for r in steps) == pytest.approx(
        cache.evict_seconds, rel=1e-9)
    assert core.metrics_snapshot()["prefix_cache"]["evict_scanned_nodes"] \
        == cache.evict_scanned_nodes


# --------------------------------------------------------------- parity
def test_chunk_logits_bitwise_equal_warm_and_cold(model):
    """Cold full prefill vs warm suffix prefill over shared blocks,
    through the mixed step's cache tuple: the ragged kernel walks each
    row's pages the same way whatever the chunk carries, so logits at
    the same absolute positions are EXACTLY equal (np.array_equal on
    raw float32), not just allclose — across two executables of
    different chunk widths."""
    import jax
    import jax.numpy as jnp

    eng = PagedGenerationEngine(model, page_size=8, prompt_bucket=16)
    pool = eng.serving_pool(17)
    L = eng._num_layers
    max_pages = 4
    scratch = np.asarray(16, np.int32)
    prompt = _prompt(7, 20)

    def logits_builder(width):
        def build():
            def run(params, ids, qlens, ctx, tables, k_pages, v_pages):
                b = ids.shape[0]
                caches = [(k_pages[i], v_pages[i], tables, ctx, qlens,
                           scratch) for i in range(L)]
                i2d = jnp.broadcast_to(
                    jnp.arange(width, dtype=jnp.int32)[None], (b, width))
                pos2d = jnp.where(i2d < qlens[:, None],
                                  ctx[:, None] + i2d, 0)
                logits, caches = eng._model_step(params, ids, pos2d,
                                                 None, caches)
                return (logits, [c[0] for c in caches],
                        [c[1] for c in caches])
            return jax.jit(run, donate_argnums=(5, 6))
        return build

    pool.reserve(0, 32)
    t0 = pool.block_table(0)
    tables0 = np.full((1, max_pages), 16, np.int32)
    tables0[0, :len(t0)] = t0
    ids0 = np.zeros((1, 32), np.int32)
    ids0[0, :20] = prompt
    (cold,) = eng.run_paged_program(
        ("px-parity-cold", 32), logits_builder(32), ids0,
        np.full((1,), 20, np.int32), np.zeros((1,), np.int32), tables0)
    cold = np.asarray(cold)

    c = 16                                    # 2 shared full pages
    pool.reserve(1, 32)
    t1 = [int(x) for x in pool.block_table(1)]
    pool.assign(1, [int(t0[0]), int(t0[1])] + t1[2:], 32)
    t1 = pool.block_table(1)
    tables1 = np.full((1, max_pages), 16, np.int32)
    tables1[0, :len(t1)] = t1
    ids1 = np.zeros((1, 16), np.int32)
    ids1[0, :4] = prompt[c:20]
    (warm,) = eng.run_paged_program(
        ("px-parity-warm", 16), logits_builder(16), ids1,
        np.full((1,), 4, np.int32), np.full((1,), c, np.int32), tables1)
    warm = np.asarray(warm)

    assert np.array_equal(warm[0, :4], cold[0, c:20])
    pool.free(0)
    pool.free(1)


def test_warm_token_stream_identical_with_cow(make_core, engine):
    """Cold vs warm token streams through the full engine must be
    byte-identical.  The resubmitted identical prompt matches 2 full
    pages + a 3-token partial of a cached page, forcing the CoW path;
    the extended prompt reuses full pages only."""
    prompt = _prompt(1, 20)
    g = GenerationConfig(max_new_tokens=6)

    # no-cache reference stream first (cores share the engine's pool,
    # so never run two cores concurrently)
    ref = EngineCore(engine, max_batch=2)
    try:
        (r0,) = ref.submit(prompt, g)
        _drive(ref, [r0])
        reference = np.asarray(r0.tokens)
    finally:
        ref.close()

    core = make_core()
    (r1,) = core.submit(prompt, g)
    _drive(core, [r1])
    cold = np.asarray(r1.tokens)
    s1 = core.prefix_cache.stats_snapshot()
    assert s1["inserts"] == 1 and s1["cached_blocks"] > 0

    (r2,) = core.submit(prompt, g)            # identical -> partial CoW
    _drive(core, [r2])
    s2 = core.prefix_cache.stats_snapshot()
    assert s2["hits"] == 1 and s2["cow_copies"] == 1
    assert s2["cached_tokens"] == 19          # capped at len - 1
    assert np.array_equal(np.asarray(r2.tokens), cold)

    longer = np.concatenate([prompt, _prompt(2, 6)])
    (r3,) = core.submit(longer, g)            # full-page reuse
    _drive(core, [r3])
    s3 = core.prefix_cache.stats_snapshot()
    assert s3["hits"] == 2

    # cached-path streams identical to the no-cache reference
    assert np.array_equal(cold, reference)

    # pool invariant once everything finished: used == retained + scratch
    pool = core._pool
    held = core.prefix_cache.stats_snapshot()["cached_blocks"]
    assert pool.num_blocks - pool.free_blocks == held + 1


def test_cache_salt_isolates_tenants(make_core):
    core = make_core()
    prompt = _prompt(3, 20)
    g = GenerationConfig(max_new_tokens=4)
    (r1,) = core.submit(prompt, g, cache_salt="tenant-a")
    _drive(core, [r1])
    (r2,) = core.submit(prompt, g, cache_salt="tenant-b")
    _drive(core, [r2])
    snap = core.prefix_cache.stats_snapshot()
    assert snap["queries"] == 2 and snap["hits"] == 0
    assert np.array_equal(np.asarray(r2.tokens), np.asarray(r1.tokens))
    (r3,) = core.submit(prompt, g, cache_salt="tenant-a")
    _drive(core, [r3])
    assert core.prefix_cache.stats_snapshot()["hits"] == 1
    assert np.array_equal(np.asarray(r3.tokens), np.asarray(r1.tokens))


# --------------------------------------------------------- failure paths
def test_mid_decode_failure_releases_all_blocks(make_core, engine,
                                                monkeypatch):
    """A failed fused decode chunk fails every in-flight row through the
    single shared release path: no block may leak, and the cache (whose
    device pages would be stale after a donated-call failure) drops its
    retained blocks."""
    core = make_core()
    pool = core._pool
    prompt = _prompt(4, 20)
    (warm,) = core.submit(prompt, GenerationConfig(max_new_tokens=4))
    _drive(core, [warm])                     # populate the tree

    real = engine.run_paged_program

    def boom(key, builder, *args):
        if isinstance(key, tuple) and key and key[0] == "serve-step":
            raise RuntimeError("injected decode failure")
        return real(key, builder, *args)

    monkeypatch.setattr(engine, "run_paged_program", boom)
    reqs = core.submit(np.stack([_prompt(5, 12), _prompt(6, 12)]),
                       GenerationConfig(max_new_tokens=8))
    core.run_once()                          # admit both, decode blows up
    for r in reqs:
        assert r.done and r.error is not None
    assert core.active_count == 0
    assert core.prefix_cache.stats_snapshot()["cached_blocks"] == 0
    # free + used == num_blocks with only the scratch page held
    assert pool.num_blocks - pool.free_blocks == 1
    monkeypatch.setattr(engine, "run_paged_program", real)
    (again,) = core.submit(prompt, GenerationConfig(max_new_tokens=4))
    _drive(core, [again])                    # core survives and readmits
    assert again.error is None


def test_prefill_failure_releases_match(make_core):
    """A prefill failure on a warm-hit admission must release the
    request's pins while leaving the tree intact.  Injected via the
    ``prefill.run`` fault site, which fires at KV staging."""
    from paddle_infer_tpu.serving import FaultPlane, FaultSpec

    core = make_core(fault_plane=FaultPlane(
        [FaultSpec("prefill.run", at=2)]))
    prompt = _prompt(8, 20)
    (warm,) = core.submit(prompt, GenerationConfig(max_new_tokens=4))
    _drive(core, [warm])
    held = core.prefix_cache.stats_snapshot()["cached_blocks"]
    (req,) = core.submit(prompt, GenerationConfig(max_new_tokens=4))
    core.run_once()
    assert req.done and req.error is not None
    pool = core._pool
    snap = core.prefix_cache.stats_snapshot()
    assert snap["cached_blocks"] == held     # pins released, tree intact
    assert pool.num_blocks - pool.free_blocks == held + 1


# ------------------------------------------------------------ recompile
def test_no_new_executables_after_warmup(make_core):
    """Once the plen buckets, the page-copy program and the decode chunk
    are warm, further admissions — hits, partial-CoW hits and misses in
    covered buckets — must not compile anything."""
    from paddle_infer_tpu.observability import get_compile_log

    core = make_core()
    g = GenerationConfig(max_new_tokens=4)
    base = _prompt(9, 20)
    # warmup: cold bucket 32, warm suffix bucket 16, page-copy, decode
    (a,) = core.submit(base, g)
    _drive(core, [a])
    (b,) = core.submit(base, g)
    _drive(core, [b])
    warm_count = get_compile_log().summary()["compile_count"]

    for seed in (10, 11, 12):
        tail = _prompt(seed, 8)
        (r,) = core.submit(np.concatenate([base, tail]), g)
        _drive(core, [r])
    (r,) = core.submit(_prompt(13, 20), g)   # cold miss, covered bucket
    _drive(core, [r])
    assert get_compile_log().summary()["compile_count"] == warm_count
    assert core.prefix_cache.stats_snapshot()["hits"] >= 4


# -------------------------------------------------------------- metrics
def test_snapshot_and_prometheus_carry_cache_stats(make_core):
    core = make_core()
    g = GenerationConfig(max_new_tokens=4)
    prompt = _prompt(14, 20)
    (r1,) = core.submit(prompt, g)
    _drive(core, [r1])
    (r2,) = core.submit(prompt, g)
    _drive(core, [r2])
    snap = core.metrics_snapshot()
    px = snap["prefix_cache"]
    assert px["queries"] == 2 and px["hits"] == 1
    assert 0.0 < px["hit_rate"] <= 1.0
    assert px["cached_tokens"] > 0
    text = core.metrics.to_prometheus(snap)
    for family in ("prefix_cache_queries_total", "prefix_cache_hits_total",
                   "prefix_cache_hit_rate", "prefix_cache_token_ratio",
                   "prefix_cache_blocks", "prefix_cache_cow_copies_total"):
        assert f"\n{family} " in text or text.startswith(f"{family} ")


def test_disabled_core_has_no_cache_section(make_core):
    core = make_core(enable_prefix_cache=False)
    assert core.prefix_cache is None
    snap = core.metrics_snapshot()
    assert "prefix_cache" not in snap
    assert "prefix_cache_hits_total" not in core.metrics.to_prometheus(snap)
