"""Quantized paged KV cache (int8 payload + per-(page, head) float32
scales) and weight-only serving checkpoints.

Coverage layers:

* protocol — quantize/dequantize round-trip error bounded by the
  analytic ``kv_dequant_error_bound``, and the slot-0 scale protocol's
  write-order invariance: aligned prompt scatter, chunked scatter, and
  token-at-a-time scatter produce byte-identical pages;
* config matrix — ``validate_kv_quant_combo`` one test per row, the
  EngineCore kv_dtype/engine agreement check, and the int4 storage
  fast-fail;
* cost model — StepCostModel prices a KV page at the configured dtype
  width (int8 payload + f32 scale overhead), not fp;
* serving identity — warm prefix hits bitwise-equal to cold through
  the radix tree, fleet handoff packets carrying the scales and the
  handed-off stream identical to a non-migrated run (greedy AND
  sampled), quantized<->fp replica pairs refused;
* composition fuzz — 200+ mixed-traffic scheduler steps at
  kv_dtype="int8" with pool/refcount invariants each step and ZERO
  post-warmup compiles;
* observability — headroom reported in pages plus the kv_quant_* /
  weight_only_* snapshot sections rendered as Prometheus families.
"""
import itertools
import random

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.observability.steplog import StepCostModel
from paddle_infer_tpu.ops.pallas.paged_attention import (
    KV_SCALE_EPS, dequantize_pages, is_quantized, kv_dequant_error_bound,
    quantize_pages, write_chunk_pages, write_prompt_pages,
    write_token_page)
from paddle_infer_tpu.serving import (EngineCore, HandoffError,
                                      ReplicaHandle, ReplicaRole,
                                      RequestState, ShardedConfigError,
                                      validate_kv_quant_combo)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.fleet import migrate, ready_for_handoff


@pytest.fixture(scope="module", autouse=True)
def _meshless():
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


# replicas never share an engine; all quantized engines share the model
@pytest.fixture(scope="module")
def q_engines(model):
    return [PagedGenerationEngine(model, page_size=8, kv_dtype="int8")
            for _ in range(4)]


@pytest.fixture(scope="module")
def fp_engine(model):
    return PagedGenerationEngine(model, page_size=8)


CORE_SHAPE = dict(max_batch=3, max_model_len=48, token_budget=16,
                  prefill_chunk=16)


@pytest.fixture
def make_core(q_engines):
    cores = []
    pool = list(q_engines)

    def make(engine=None, **kw):
        for k, v in CORE_SHAPE.items():
            kw.setdefault(k, v)
        core = EngineCore(engine if engine is not None else pool.pop(0),
                          **kw)
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


# ------------------------------------------------------------ protocol

def test_roundtrip_error_within_analytic_bound():
    """dequant(quant(x)) stays inside the bound computed from the
    realized slot-0 scales — and the bound is not vacuous (well under
    the data's own magnitude)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(6, 4, 8, 16).astype(np.float32) * 3.0)
    payload, scales = quantize_pages(pool)
    assert payload.dtype == jnp.int8 and scales.dtype == jnp.float32
    assert float(np.min(np.asarray(scales))) >= KV_SCALE_EPS
    err = float(np.max(np.abs(
        np.asarray(dequantize_pages((payload, scales))) - np.asarray(pool))))
    bound = kv_dequant_error_bound(np.asarray(pool), np.asarray(scales))
    assert err <= bound
    assert bound < float(np.max(np.abs(np.asarray(pool))))


def test_slot0_scale_protocol_is_write_order_invariant():
    """Aligned prompt scatter, two offset chunks, and sixteen
    token-at-a-time scatters land byte-identical payloads AND scales:
    the page scale depends only on the token at slot 0, never on how
    the rest of the page arrived.  This is the property that makes
    warm prefix hits and handed-off continuations bitwise."""
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    kv = jnp.asarray(rng.randn(1, 16, 2, 4).astype(np.float32))
    tables = jnp.asarray([[0, 1]], jnp.int32)

    def fresh():
        return (jnp.zeros((3, 2, 8, 4), jnp.int8),
                jnp.full((3, 2), KV_SCALE_EPS, jnp.float32))

    q_prompt = write_prompt_pages(fresh(), tables, kv)
    q_chunk = write_chunk_pages(fresh(), tables, kv[:, :8],
                                jnp.zeros((1,), jnp.int32))
    q_chunk = write_chunk_pages(q_chunk, tables, kv[:, 8:],
                                jnp.full((1,), 8, jnp.int32))
    q_tok = fresh()
    for i in range(16):
        q_tok = write_token_page(q_tok, tables, kv[:, i],
                                 jnp.full((1,), i, jnp.int32))

    for other in (q_chunk, q_tok):
        np.testing.assert_array_equal(np.asarray(q_prompt[0][:2]),
                                      np.asarray(other[0][:2]))
        np.testing.assert_array_equal(np.asarray(q_prompt[1][:2]),
                                      np.asarray(other[1][:2]))


# ------------------------------------------------------- config matrix

@pytest.mark.parametrize("kv_dtype,flags", [
    (None, {}),
    (None, dict(speculate=True, enable_prefix_cache=True)),
    ("int8", dict(enable_prefix_cache=True)),
    ("int8", dict(speculate=True)),
    ("int8", dict(speculate=True, enable_prefix_cache=True)),
    ("int4", {}),
    ("int4", dict(enable_prefix_cache=True)),
    ("int4", dict(speculate=True, spec_accept_threshold=0.1)),
])
def test_kv_quant_combo_allowed(kv_dtype, flags):
    validate_kv_quant_combo(kv_dtype, **flags)


@pytest.mark.parametrize("kv_dtype,flags", [
    ("fp8", {}),
    ("int2", {}),
    ("int4", dict(speculate=True)),
    ("int8", dict(spec_accept_threshold=0.0)),
    ("int8", dict(spec_accept_threshold=1.5)),
])
def test_kv_quant_combo_rejected(kv_dtype, flags):
    with pytest.raises(ShardedConfigError):
        validate_kv_quant_combo(kv_dtype, **flags)


def test_core_kv_dtype_must_match_engine(fp_engine, make_core):
    with pytest.raises(ShardedConfigError):
        EngineCore(fp_engine, kv_dtype="int8", **CORE_SHAPE)
    core = make_core(kv_dtype="int8")          # agreement is silent
    assert core._kv_dtype == "int8"


def test_engine_rejects_int4_storage(model):
    with pytest.raises(NotImplementedError):
        PagedGenerationEngine(model, page_size=8, kv_dtype="int4")


def test_beam_search_rejected_on_quantized_pool(q_engines):
    g = GenerationConfig(max_new_tokens=4, num_beams=2)
    with pytest.raises(ValueError):
        q_engines[0].generate(_prompt(7)[None], g)


# --------------------------------------------------------- cost model

def test_cost_model_prices_kv_page_at_configured_dtype(make_core,
                                                       fp_engine):
    """Satellite: KV-byte pricing uses the int8 payload width plus the
    per-page scale overhead, not the fp itemsize — and the per-page
    cost arithmetic (evict, page_copy) scales from that figure."""
    core = make_core()
    cm = StepCostModel(core._engine, core._pool)
    # 2 layers * (K+V) * 4 heads * (page 8 * head_dim 8 * 1 byte
    # payload + 4-byte scale)
    expected = 2 * 2 * 4 * (8 * 8 * 1) + 2 * 2 * 4 * 4
    assert cm.page_kv_bytes == pytest.approx(expected)
    b, f, src = cm.estimate("evict", pages_touched=3)
    assert (b, f, src) == (3 * cm.page_kv_bytes, 0.0, "analytic")
    b, _, src = cm.estimate("page_copy", pages_touched=2)
    assert (b, src) == (2 * 2 * cm.page_kv_bytes, "analytic")
    # fp engine prices the same page 4x the payload, no scale term
    fp_cm = StepCostModel(fp_engine, core._pool)
    assert fp_cm.page_kv_bytes == pytest.approx(2 * 2 * 4 * 8 * 8 * 4)


# ----------------------------------------------------- serving identity

def test_warm_prefix_stream_identical_to_cold_int8(make_core):
    """Warm (radix-tree hit, including the CoW partial tail) streams
    bitwise-equal to cold on the quantized pool: the suffix prefill
    reads exactly the int8 bytes + scales the cold pass wrote."""
    prompt = _prompt(11, 20)
    g = GenerationConfig(max_new_tokens=6)
    core = make_core(enable_prefix_cache=True, max_batch=2)

    (r1,) = core.submit(prompt, g)
    _drive(core, [r1])
    cold = np.asarray(r1.tokens)

    (r2,) = core.submit(prompt, g)             # identical -> CoW tail
    _drive(core, [r2])
    snap = core.prefix_cache.stats_snapshot()
    assert snap["hits"] == 1 and snap["cow_copies"] == 1
    np.testing.assert_array_equal(np.asarray(r2.tokens), cold)

    longer = np.concatenate([prompt, _prompt(12, 6)])
    (r3,) = core.submit(longer, g)             # full-page reuse
    _drive(core, [r3])
    assert core.prefix_cache.stats_snapshot()["hits"] == 2
    np.testing.assert_array_equal(np.asarray(r3.tokens)[:0], cold[:0])


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_quantized_handoff_stream_bitwise_equal(make_core, sampled):
    """Prefill on one int8 replica, decode on another: the packet's
    per-layer gathers are (payload, scales) pairs and the continued
    stream is identical to a never-migrated run."""
    g = (GenerationConfig(max_new_tokens=10, do_sample=True,
                          temperature=0.9, top_p=0.9, seed=3)
         if sampled else GenerationConfig(max_new_tokens=10))
    prompt = _prompt(41, n=24)                 # 2 prefill chunks

    base = 7100 if sampled else 7000
    request_mod._rid_counter = itertools.count(base)
    ref = make_core()
    req_ref = ref.submit(prompt, g)[0]
    _drive(ref, [req_ref])
    want = np.asarray(req_ref.result(timeout=60))

    request_mod._rid_counter = itertools.count(base)   # same rid
    src = ReplicaHandle("p0", make_core(), ReplicaRole.PREFILL)
    dst = ReplicaHandle("d0", make_core(), ReplicaRole.DECODE)
    req = src.core.submit(prompt, g)[0]
    for _ in range(400):
        if ready_for_handoff(src.core, req):
            break
        src.core.run_once()
    else:
        raise AssertionError("request never became handoff-ready")

    packet = src.core.export_handoff(req)
    # the scales travel: every per-layer entry is a (payload, scales)
    # host pair whose geometries match the quantized pool
    for entry in packet["k_host"] + packet["v_host"]:
        assert isinstance(entry, tuple) and len(entry) == 2
        payload, scales = entry
        assert payload.dtype == np.int8
        assert scales.dtype == np.float32
        assert scales.shape == payload.shape[:2]
    src.handoffs_out += 1

    dst.core.import_handoff(packet)
    dst.handoffs_in += 1
    _drive(dst.core, [req])
    got = np.asarray(req.result(timeout=60))
    np.testing.assert_array_equal(got, want)


def test_handoff_refused_between_quantized_and_fp_pools(make_core,
                                                        fp_engine):
    """A quantized source and an fp target (or vice versa) must refuse
    the packet whole — different pool geometries can never silently
    exchange page bytes."""
    g = GenerationConfig(max_new_tokens=8)
    src = ReplicaHandle("p0", make_core(), ReplicaRole.PREFILL)
    dst_core = EngineCore(fp_engine, **CORE_SHAPE)
    try:
        dst = ReplicaHandle("d0", dst_core, ReplicaRole.DECODE)
        req = src.core.submit(_prompt(43, 24), g)[0]
        for _ in range(400):
            if ready_for_handoff(src.core, req):
                break
            src.core.run_once()
        else:
            raise AssertionError("request never became handoff-ready")
        assert not migrate(req, src, dst)      # refused, no side effects
        assert dst.core.active_count == 0
        # the request stays live on the source and finishes there
        _drive(src.core, [req])
        assert req.state is RequestState.DONE
    finally:
        dst_core.close()


# ---------------------------------------------------------------- fuzz

def test_mixed_traffic_fuzz_int8_invariants_and_zero_compiles(
        make_core, q_engines):
    """200+ scheduler steps of random mixed traffic on the int8 pool:
    chunked long prompts, decode stretches, sampled rows, idle drains.
    Pool accounting and block refcounts hold at every step, greedy
    streams match a direct generate() on a second quantized engine,
    and after a one-request warmup the run performs ZERO new XLA
    compiles — quantization lives in the executables' dtypes, not in
    their shapes."""
    from paddle_infer_tpu.observability import get_compile_log

    log = get_compile_log()
    # earlier tests in this module warm-marked the serving sites on
    # OTHER engines; this test's own warmup would otherwise count as
    # post-warmup decode recompiles
    log.reset()
    core = make_core()
    ref = q_engines[-1]                        # never core-owned
    total = core._pool.num_blocks
    # warmup: one request per prompt-length bucket, greedy and sampled,
    # so every executable shape the fuzz can reach compiles up front —
    # the fuzz itself must then compile NOTHING
    warm = []
    for i, n in enumerate([3, 5, 11, 17, 26, 40]):
        warm += core.submit(_prompt(900 + i, n),
                            GenerationConfig(max_new_tokens=4))
        warm += core.submit(_prompt(950 + i, n), GenerationConfig(
            max_new_tokens=4, do_sample=True, temperature=0.9,
            top_k=20, seed=i))
    _drive(core, warm, max_iters=800)
    warm_compiles = log.summary()["compile_count"]

    rng = random.Random(0)
    live = []
    steps = 0
    arrivals = 0
    while steps < 200 or any(not r.done for r, _ in live):
        if (arrivals < 36 and core.queue_depth < 3
                and rng.random() < 0.4):
            n = rng.choice([3, 5, 11, 17, 26, 40])
            if rng.random() < 0.4:
                g = GenerationConfig(
                    max_new_tokens=rng.randint(2, 8), do_sample=True,
                    temperature=0.9, top_k=20,
                    seed=rng.randint(0, 999))
            else:
                g = GenerationConfig(max_new_tokens=rng.randint(2, 8))
            ids = _prompt(300 + arrivals, n)
            (r,) = core.submit(ids, g)
            live.append((r, (ids, g)))
            arrivals += 1
        core.run_once()
        steps += 1
        used = total - core._pool.free_blocks
        assert 0 <= used <= total, "pool accounting broke mid-run"
        # refcount invariant: every live slot's table rows are alive
        for sid in range(core._max_batch):
            for blk in core._pool.block_table(sid):
                assert core._pool.block_refcount(int(blk)) >= 1
        assert steps < 3000, "fuzz traffic never drained"

    assert steps >= 200 and arrivals >= 16
    for r, _ in live:
        assert r.state is RequestState.DONE, (r.rid, r.error)
    # drained: only the ragged scratch page stays resident
    assert total - core._pool.free_blocks == 1
    # the serving claim first (ref.generate below compiles its own
    # engine's programs): the fuzz traffic itself compiled nothing
    assert log.summary()["compile_count"] == warm_compiles, \
        "kv quantization leaked into executable shapes"
    assert log.summary()["post_warmup_decode_compiles"] == 0
    greedy = [(r, ids, g) for r, (ids, g) in live if not g.do_sample]
    assert greedy
    for r, ids, g in greedy:
        np.testing.assert_array_equal(
            r.padded_result(), ref.generate(ids[None], g)[0])


# ------------------------------------------------------- observability

def test_snapshot_reports_pages_and_kv_quant_families(make_core):
    """Capacity gauges are page-denominated (headroom included) and the
    kv_quant section's byte arithmetic matches the engine geometry;
    the whole snapshot renders the new Prometheus families."""
    from paddle_infer_tpu.observability import get_compile_log
    from paddle_infer_tpu.observability.prometheus import (
        render_prometheus, validate_exposition)

    core = make_core(enable_prefix_cache=True,
                     prefix_cache_headroom_pages=4, max_batch=2)
    (r,) = core.submit(_prompt(61, 20), GenerationConfig(max_new_tokens=4))
    _drive(core, [r])
    snap = core.metrics_snapshot()

    kv = snap["kv_pool"]
    assert kv["headroom_pages"] == 4
    assert kv["total_blocks"] == core._pool.num_blocks   # pages, not bytes

    kq = snap["kv_quant"]
    assert kq["kv_dtype"] == "int8"
    # 2 layers * (K+V) * 4 heads * (page 8 * head_dim 8 + f32 scale)
    assert kq["bytes_per_page"] == 2 * 2 * 4 * (8 * 8 + 4)
    assert kq["fp_bytes_per_page"] == 2 * 2 * 4 * 8 * 8 * 4
    assert kq["scale_bytes_per_page"] == 2 * 2 * 4 * 4
    assert kq["resident_page_ratio"] == pytest.approx(
        kq["fp_bytes_per_page"] / kq["bytes_per_page"])
    assert kq["resident_page_ratio"] >= 1.9

    text = render_prometheus(snap, get_compile_log().summary())
    assert validate_exposition(text) == []
    for fam in ("serving_kv_pool_headroom_pages", "kv_quant_info",
                "kv_quant_bytes_per_page",
                "kv_quant_scale_bytes_per_page",
                "kv_quant_resident_page_ratio"):
        assert f"# TYPE {fam} " in text, fam
    assert 'kv_dtype="int8"' in text


def test_weight_only_checkpoint_serves_and_reports():
    """Tentpole prong B: a weight-only int8 checkpoint loads through
    the engine as buffers (donated beside params), the stream is
    deterministic across calls, and the weight_only snapshot section
    prices the resident payload under half the fp checkpoint."""
    from paddle_infer_tpu.quantization.weight_only import (
        WeightOnlyLinear, quantize_model, weight_only_summary)

    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    m.eval()
    quantize_model(m, algo="weight_only_int8")
    assert any(isinstance(s, WeightOnlyLinear)
               for _, s in m.named_sublayers())

    eng = PagedGenerationEngine(m, page_size=8, kv_dtype="int8")
    g = GenerationConfig(max_new_tokens=6)
    first = np.asarray(eng.generate(_prompt(71, 12)[None], g))
    again = np.asarray(eng.generate(_prompt(71, 12)[None], g))
    np.testing.assert_array_equal(first, again)

    core = EngineCore(eng, **CORE_SHAPE)
    try:
        (r,) = core.submit(_prompt(72, 12), g)
        _drive(core, [r])
        wo = core.metrics_snapshot()["weight_only"]
    finally:
        core.close()
    assert wo["layers"] > 0
    assert wo["algos"] == ["weight_only_int8"]
    assert wo == weight_only_summary(m)
    assert 0.0 < wo["hbm_traffic_ratio"] < 0.5
