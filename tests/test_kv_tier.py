"""Host-RAM KV tier with priority preemption (serving/kv_tier/):
park-don't-drop overload handling, prefix-block demotion/promotion,
and bounded-retry swap fault tolerance.

The acceptance property is BITWISE park/resume parity: a request that
is preempted into the host tier mid-flight and later resumed must emit
exactly the stream it would have emitted uninterrupted, across the
whole serving matrix — greedy and sampled rows, mid-prefill and
mid-decode victims, int8-quantized pools, warm prefix-cache prompts,
speculative decoding, LoRA-bound rows (pin released while parked,
re-pinned on resume), and an engine restart with a row parked in
flight (host packets survive the restart verbatim).

Request ids feed the per-row sampling RNG (``fold_in(key, rid)``), so
parity runs pin the process-wide rid counter to the same start — the
same idiom as tests/test_resilience.py.
"""
import itertools
import time

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.observability.compilelog import get_compile_log
from paddle_infer_tpu.serving import (AdapterStore, DeadlineExceededError,
                                      EngineCore, EngineSupervisor,
                                      FaultPlane, FaultSpec, HealthState,
                                      RequestState, adapter_layer_spec,
                                      make_random_adapter)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.kv_tier import HostKVTier


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """Park/resume parity compares tokens across executables, which is
    bitwise only when both runs are unsharded — clear any hybrid mesh a
    failing test in another module leaked behind."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


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
def engine_int8(model):
    return PagedGenerationEngine(model, page_size=8, kv_dtype="int8")


CORE_KW = dict(max_batch=2, max_model_len=48)
TIER_PAGES = 64


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _run_jobs(engine_obj, jobs, rid_base, park_at=(), core_kw=None,
              plane=None, sup_kw=None, max_iters=800):
    """Drive ``jobs`` (``(prompt, gen)`` or ``(prompt, gen, adapter_id)``)
    on a fresh tier-enabled core, invoking ``park_for_pressure()`` after
    the step indices in ``park_at``.  Returns (requests, padded outputs,
    metrics snapshot, park results)."""
    request_mod._rid_counter = itertools.count(rid_base)
    kw = dict(CORE_KW, kv_host_pages=TIER_PAGES, fault_plane=plane)
    kw.update(core_kw or {})
    core = EngineCore(engine_obj, **kw)
    sup = EngineSupervisor(core, **sup_kw) if sup_kw is not None else None
    parked = []
    try:
        reqs = [core.submit(*j[:2], adapter_id=(j[2] if len(j) > 2
                                                else None))[0]
                for j in jobs]
        stepper = sup if sup is not None else core
        for step in range(1, max_iters + 1):
            if all(r.done for r in reqs):
                break
            stepper.run_once()
            if step in park_at:
                parked.append(core.park_for_pressure())
        assert all(r.done for r in reqs), "requests did not finish"
        outs = [np.asarray(r.padded_result())
                if r.state is RequestState.DONE else None for r in reqs]
        snap = core.metrics_snapshot()
        return reqs, outs, snap, parked
    finally:
        if sup is not None:
            sup.close()
        else:
            core.close()


# ------------------------------------------------------------- tier unit

class TestHostKVTier:
    def test_validation(self):
        with pytest.raises(ValueError):
            HostKVTier(0)
        with pytest.raises(ValueError):
            HostKVTier(8, park_watermark=0.5, resume_watermark=0.7)
        with pytest.raises(ValueError):
            HostKVTier(8, park_watermark=1.2, resume_watermark=0.7)
        t = HostKVTier(8, park_watermark=0.9, resume_watermark=0.6)
        # watermark gap in device pages, floored at zero
        assert t.hysteresis_pages(100) == 30
        assert t.hysteresis_pages(0) == 0

    def test_park_capacity_and_accounting(self):
        t = HostKVTier(4)
        assert t.can_park(4) and not t.can_park(5)
        t.park(1, {"req": None}, 3, step=2)
        assert t.parked_count == 1 and t.resident_pages == 3
        with pytest.raises(MemoryError):
            t.park(2, {"req": None}, 2)
        rid, packet, n_pages, step = t.peek_parked()
        assert (rid, n_pages, step) == (1, 3, 2)
        t.complete_resume(1)
        assert t.resident_pages == 0 and t.resumes_total == 1
        t.park(3, {"req": None}, 2)
        assert t.drop(3) and not t.drop(3)
        assert t.resident_pages == 0

    def test_park_evicts_demoted_lru_oldest_first(self):
        t = HostKVTier(4)
        for i in range(4):
            assert t.demote(("s", i), {"blk": i})
        # parked state takes priority: 3 pages evict the 3 oldest
        t.park(9, {"req": None}, 3)
        assert t.demoted_evicted_total == 3
        assert t.promote(("s", 0)) is None
        assert t.promote(("s", 3)) == {"blk": 3}
        # arena fully parked and nothing evictable: demote stores nothing
        t.park(10, {"req": None}, 1)
        assert not t.demote(("s", 4), {"blk": 4})

    def test_restore_demoted_reverses_promote(self):
        t = HostKVTier(4, page_kv_bytes=100.0)
        t.demote("k", {"b": 1})
        got = t.promote("k")
        assert got == {"b": 1} and t.promotes_total == 1
        t.restore_demoted("k", got)
        assert t.promotes_total == 0 and t.swap_in_bytes_total == 0
        assert t.promote("k") == {"b": 1}

    def test_reconcile_and_drain(self):
        t = HostKVTier(8)
        t.park(1, {"req": "a"}, 2)
        t.park(2, {"req": "b"}, 3)
        assert t.reconcile_after_restart() == 2
        assert t.restart_reconciles_total == 1
        assert sorted(rid for rid, _ in t.drain_parked()) == [1, 2]
        assert t.parked_count == 0 and t.resident_pages == 0


# --------------------------------------------------- bitwise parity matrix

def test_park_resume_parity_greedy(engine):
    jobs = [(_prompt(1), GenerationConfig(max_new_tokens=12)),
            (_prompt(2, n=12), GenerationConfig(max_new_tokens=12))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8000)
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=8000,
                                     park_at=(3,))
    assert parked == [True]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    kt = snap["kv_tier"]
    assert kt["parks_total"] == 1 and kt["resumes_total"] == 1
    assert kt["parked_requests"] == 0 and kt["host_pages_resident"] == 0
    assert kt["swap_out_bytes_total"] > 0
    assert kt["swap_in_bytes_total"] == kt["swap_out_bytes_total"]


def test_park_resume_parity_sampled(engine):
    jobs = [(_prompt(3), GenerationConfig(max_new_tokens=12,
                                          do_sample=True, temperature=0.8,
                                          top_k=12, seed=11)),
            (_prompt(4), GenerationConfig(max_new_tokens=12,
                                          do_sample=True, temperature=0.9,
                                          top_k=20, seed=12))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8100)
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=8100,
                                     park_at=(2, 5))
    assert any(parked)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    assert snap["kv_tier"]["resumes_total"] == \
        snap["kv_tier"]["parks_total"] >= 1


def test_park_resume_parity_mid_prefill(engine):
    """A victim parked with prompt chunks still pending serializes only
    the consumed prefix (kv_len == ctx) and finishes the prefill after
    resume — the packet's ``pending`` round-trips."""
    jobs = [(_prompt(5, n=24), GenerationConfig(max_new_tokens=8))]
    kw = dict(token_budget=8, prefill_chunk=8)
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8200, core_kw=kw)
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=8200,
                                     core_kw=kw, park_at=(1,))
    assert parked == [True]
    np.testing.assert_array_equal(got[0], want[0])
    assert snap["kv_tier"]["parks_total"] == 1


def test_park_resume_parity_int8_kv(engine, engine_int8):
    jobs = [(_prompt(6), GenerationConfig(max_new_tokens=12)),
            (_prompt(7, n=12), GenerationConfig(max_new_tokens=10))]
    kw = dict(kv_dtype="int8")
    _, want, _, _ = _run_jobs(engine_int8, jobs, rid_base=8300, core_kw=kw)
    _, got, snap, parked = _run_jobs(engine_int8, jobs, rid_base=8300,
                                     core_kw=kw, park_at=(3,))
    assert parked == [True]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    assert snap["kv_tier"]["parks_total"] == 1
    # int8 pools swap (payload, scale) pairs at roughly half the host
    # bytes of the fp pool — the calibrated per-page byte constant the
    # tier prices traffic with must reflect that
    fp = EngineCore(engine, kv_host_pages=8, **CORE_KW)
    i8 = EngineCore(engine_int8, kv_host_pages=8, kv_dtype="int8",
                    **CORE_KW)
    try:
        assert i8._kv_tier.page_kv_bytes < 0.6 * fp._kv_tier.page_kv_bytes
    finally:
        fp.close()
        i8.close()


def test_park_resume_parity_warm_prefix(engine):
    """Parking a request admitted off a warm radix-tree match retains
    its prefix pages (release-with-retain) and resumes bitwise."""
    shared = np.random.RandomState(42).randint(0, 96, (16,)).astype(
        np.int32)
    tail_a = np.concatenate([shared, _prompt(8, n=4)])
    tail_b = np.concatenate([shared, _prompt(9, n=4)])
    jobs = [(tail_a, GenerationConfig(max_new_tokens=10)),
            (tail_b, GenerationConfig(max_new_tokens=10))]
    kw = dict(enable_prefix_cache=True)
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8400, core_kw=kw)
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=8400,
                                     core_kw=kw, park_at=(4,))
    assert parked == [True]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    assert snap["kv_tier"]["parks_total"] == 1


def test_park_resume_parity_speculative(engine):
    jobs = [(_prompt(10), GenerationConfig(max_new_tokens=12)),
            (_prompt(11), GenerationConfig(max_new_tokens=12))]
    kw = dict(speculate=True, num_draft_tokens=4)
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8500, core_kw=kw)
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=8500,
                                     core_kw=kw, park_at=(3,))
    assert parked == [True]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    assert snap["kv_tier"]["resumes_total"] == 1


def test_lora_park_releases_pin_and_resume_repins(model, engine):
    """A LoRA-bound victim drops its adapter pin for the parked wait
    (the slot-LRU can evict the adapter meanwhile) and re-pins before
    re-entering the batch — stream bitwise vs the uninterrupted run."""
    spec = adapter_layer_spec(model)
    factors, scale = make_random_adapter(spec, 4, 17, amplitude=0.6)

    def fresh_store():
        store = AdapterStore(spec, rank=4)
        store.add("t0", factors, scale=scale)
        return store

    jobs = [(_prompt(12), GenerationConfig(max_new_tokens=12), "t0")]
    _, want, _, _ = _run_jobs(
        engine, jobs, rid_base=8600,
        core_kw=dict(adapter_store=fresh_store(), adapter_slots=4))

    request_mod._rid_counter = itertools.count(8600)
    core = EngineCore(engine, adapter_store=fresh_store(), adapter_slots=4,
                      kv_host_pages=TIER_PAGES, **CORE_KW)
    try:
        (req,) = core.submit(_prompt(12),
                             GenerationConfig(max_new_tokens=12),
                             adapter_id="t0")
        core.run_once()
        core.run_once()
        assert core._adapters.pinned_count == 1
        assert core.park_for_pressure()
        # parked: pin released, KV bytes in host RAM
        assert core._adapters.pinned_count == 0
        assert core._kv_tier.parked_count == 1
        for _ in range(200):
            if req.done:
                break
            core.run_once()
        assert req.state is RequestState.DONE
        np.testing.assert_array_equal(np.asarray(req.padded_result()),
                                      want[0])
        assert core._kv_tier.resumes_total == 1
        assert core._adapters.pinned_count == 0      # unpinned on finish
    finally:
        core.close()


def test_supervisor_restart_with_row_parked_in_flight(engine):
    """KV loss mid-decode with a row parked: the parked packet is
    host-side and survives the restart verbatim (reconciled, never
    replayed); active rows replay as usual; every stream is exact."""
    jobs = [(_prompt(13), GenerationConfig(max_new_tokens=12)),
            (_prompt(14), GenerationConfig(max_new_tokens=20)),
            (_prompt(15), GenerationConfig(max_new_tokens=20))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8700,
                              sup_kw=dict(backoff_base_s=0.0))

    request_mod._rid_counter = itertools.count(8700)
    plane = FaultPlane([FaultSpec("decode.step", at=5, lose_kv=True)])
    # a maximal watermark gap: while other rows keep the engine busy
    # the hysteresis gate holds the victim parked (it resumes once the
    # engine idles or after aging), so the restart lands mid-park
    core = EngineCore(engine, kv_host_pages=TIER_PAGES, fault_plane=plane,
                      kv_park_watermark=0.99, kv_resume_watermark=0.01,
                      **CORE_KW)
    sup = EngineSupervisor(core, backoff_base_s=0.0)
    try:
        reqs = [core.submit(p, g)[0] for p, g in jobs]
        sup.run_once()
        sup.run_once()
        assert core.park_for_pressure()      # parks reqs[0] (slot order)
        restarts = 0
        for _ in range(100):
            sup.run_once()
            restarts = core.metrics_snapshot()["resilience"][
                "engine_restarts"]
            if restarts:
                break
        assert restarts == 1
        # the parked row rode out the restart inside the tier
        assert core._kv_tier.parked_count == 1
        assert core._kv_tier.restart_reconciles_total == 1
        for _ in range(400):
            if all(r.done for r in reqs):
                break
            sup.run_once()
        assert all(r.state is RequestState.DONE for r in reqs)
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.padded_result()), w)
        assert reqs[0].retries == 0          # parked == never replayed
        assert core._kv_tier.resumes_total == 1
    finally:
        sup.close()


def test_deadline_expires_while_parked(engine):
    request_mod._rid_counter = itertools.count(8800)
    core = EngineCore(engine, kv_host_pages=TIER_PAGES, **CORE_KW)
    try:
        (req,) = core.submit(_prompt(16),
                             GenerationConfig(max_new_tokens=24),
                             timeout_s=0.2)
        core.run_once()
        core.run_once()
        assert core.park_for_pressure()
        time.sleep(0.25)
        for _ in range(10):
            if req.done:
                break
            core.run_once()
        assert req.state is RequestState.CANCELLED
        with pytest.raises(DeadlineExceededError):
            req.result()
        assert core._kv_tier.parked_count == 0
        assert core._kv_tier.resident_pages == 0
    finally:
        core.close()


# --------------------------------------------------- park-before-shed ladder

def test_memory_pressure_parks_before_shedding(engine):
    """The supervisor's degradation ladder tries the tier first: a
    pressure event parks one row (reversible) instead of shrinking the
    batch, and the ladder only advances when the tier is absent."""
    jobs = [(_prompt(17), GenerationConfig(max_new_tokens=16)),
            (_prompt(18), GenerationConfig(max_new_tokens=16))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=8900, sup_kw={})

    request_mod._rid_counter = itertools.count(8900)
    core = EngineCore(engine, kv_host_pages=TIER_PAGES, **CORE_KW)
    sup = EngineSupervisor(core)
    try:
        reqs = [core.submit(p, g)[0] for p, g in jobs]
        sup.run_once()
        sup.run_once()
        sup.on_memory_pressure()
        assert core._kv_tier.parked_count == 1
        assert core.effective_max_batch == 2     # ladder did not advance
        assert sup.health.state is HealthState.DEGRADED
        for _ in range(200):
            if all(r.done for r in reqs):
                break
            sup.run_once()
        assert all(r.state is RequestState.DONE for r in reqs)
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.padded_result()), w)
        snap = core.metrics_snapshot()
        assert snap["resilience"]["requests_shed"] == 0
    finally:
        sup.close()


def test_oversubscribed_burst_parks_never_sheds(engine):
    """Satellite regression: an oversubscribed deadline-less burst with
    injected allocation pressure completes every request by parking —
    zero sheds, zero failures, streams exact."""
    jobs = [(_prompt(20 + i, n=6 + 2 * (i % 4)),
             GenerationConfig(max_new_tokens=8 + 2 * (i % 3)))
            for i in range(8)]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=9000, sup_kw={})

    plane = FaultPlane([FaultSpec("kv.alloc", at=3, exc="MemoryError"),
                        FaultSpec("kv.alloc", at=6, exc="MemoryError")])
    reqs, got, snap, _ = _run_jobs(engine, jobs, rid_base=9000,
                                   plane=plane, sup_kw={})
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    assert snap["resilience"]["requests_shed"] == 0
    assert snap["sched"]["predictive_sheds"] == 0
    assert snap["kv_tier"]["parks_total"] >= 2
    assert snap["kv_tier"]["resumes_total"] == snap["kv_tier"]["parks_total"]
    assert all(r.retries == 0 for r in reqs)     # parked, never replayed


# ------------------------------------------------------- swap-site chaos

def test_swap_out_fault_exhaustion_leaves_slot_intact(engine):
    """kv.swap_out failing through every bounded retry aborts the park
    with the victim slot untouched — the request streams on as if the
    park was never attempted."""
    jobs = [(_prompt(30), GenerationConfig(max_new_tokens=12))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=9100)

    plane = FaultPlane([FaultSpec("kv.swap_out", p=1.0, times=3)])
    request_mod._rid_counter = itertools.count(9100)
    core = EngineCore(engine, kv_host_pages=TIER_PAGES, fault_plane=plane,
                      **CORE_KW)
    try:
        baseline = core._pool.free_blocks
        (req,) = core.submit(*jobs[0])
        core.run_once()
        core.run_once()
        assert not core.park_for_pressure()      # retries exhausted
        tier = core._kv_tier
        assert tier.swap_retries_total == 3
        assert tier.swap_fails_total == 1
        assert tier.parks_total == 0 and tier.parked_count == 0
        for _ in range(200):
            if req.done:
                break
            core.run_once()
        assert req.state is RequestState.DONE
        np.testing.assert_array_equal(np.asarray(req.padded_result()),
                                      want[0])
        assert core._pool.free_blocks == baseline
    finally:
        core.close()


def test_swap_out_transient_fault_retries_and_parks(engine):
    """A single kv.swap_out fault is absorbed by the bounded retry loop:
    the park proceeds on the second attempt and parity holds."""
    jobs = [(_prompt(31), GenerationConfig(max_new_tokens=12)),
            (_prompt(32), GenerationConfig(max_new_tokens=12))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=9200)

    plane = FaultPlane([FaultSpec("kv.swap_out", at=1)])
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=9200,
                                     plane=plane, park_at=(3,))
    assert parked == [True]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    kt = snap["kv_tier"]
    assert kt["swap_retries_total"] == 1 and kt["swap_fails_total"] == 0
    assert kt["parks_total"] == 1 and kt["resumes_total"] == 1


def test_swap_in_fault_exhaustion_falls_back_to_replay(engine):
    """kv.swap_in failing through every retry drops the tier entry and
    routes the row through the existing replay ladder — the client
    still sees the exact stream (per-(seed, rid) sampling keys), the
    tier accounting returns to zero, and nothing wedges."""
    jobs = [(_prompt(33), GenerationConfig(max_new_tokens=12,
                                           do_sample=True,
                                           temperature=0.8, top_k=12,
                                           seed=21))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=9300, sup_kw={})

    plane = FaultPlane([FaultSpec("kv.swap_in", p=1.0, times=3)])
    request_mod._rid_counter = itertools.count(9300)
    core = EngineCore(engine, kv_host_pages=TIER_PAGES, fault_plane=plane,
                      **CORE_KW)
    sup = EngineSupervisor(core, backoff_base_s=0.0)
    try:
        baseline = core._pool.free_blocks
        (req,) = core.submit(*jobs[0])
        sup.run_once()
        sup.run_once()
        assert core.park_for_pressure()
        for _ in range(200):
            if req.done:
                break
            sup.run_once()
        assert req.state is RequestState.DONE
        np.testing.assert_array_equal(np.asarray(req.padded_result()),
                                      want[0])
        assert req.retries == 1                   # replayed, not parked
        tier = core._kv_tier
        assert tier.swap_retries_total == 3
        assert tier.swap_fails_total == 1
        assert tier.parked_count == 0 and tier.resident_pages == 0
        assert core._pool.free_blocks == baseline
    finally:
        sup.close()


def test_swap_hang_is_latency_not_failure(engine, monkeypatch):
    """A hang at kv.swap_out is a latency spike, not a failure: the
    park completes after the stall and parity holds."""
    from paddle_infer_tpu.serving.resilience import faultplane
    slept = []
    monkeypatch.setattr(faultplane, "time_sleep", slept.append)

    jobs = [(_prompt(34), GenerationConfig(max_new_tokens=12))]
    _, want, _, _ = _run_jobs(engine, jobs, rid_base=9400)
    plane = FaultPlane([FaultSpec("kv.swap_out", action="hang", at=1,
                                  delay_s=0.7)])
    _, got, snap, parked = _run_jobs(engine, jobs, rid_base=9400,
                                     plane=plane, park_at=(2,))
    assert parked == [True]
    assert slept == [0.7]
    np.testing.assert_array_equal(got[0], want[0])
    kt = snap["kv_tier"]
    assert kt["parks_total"] == 1 and kt["swap_fails_total"] == 0


# ------------------------------------------------- demotion / promotion

def test_prefix_demote_promote_roundtrip(engine):
    """Evicting warm full blocks demotes them to host; a later request
    on the same prefix promotes them back instead of re-prefilling.
    ``clear()`` (restart path) drops pages WITHOUT demoting — lost
    device state must never be preserved."""
    request_mod._rid_counter = itertools.count(9500)
    core = EngineCore(engine, enable_prefix_cache=True,
                      kv_host_pages=32, **CORE_KW)
    try:
        prompt = _prompt(35, n=24)
        g = GenerationConfig(max_new_tokens=8)
        (r1,) = core.submit(prompt, g)
        for _ in range(200):
            if r1.done:
                break
            core.run_once()
        want = np.asarray(r1.padded_result())
        tier = core._kv_tier
        # force full eviction: every retained FULL block demotes (the
        # partial tail page does not — only whole pages round-trip)
        core.prefix_cache.ensure_free(10 ** 9)
        assert tier.demotes_total == 3
        assert tier.demoted_count == 3
        (r2,) = core.submit(prompt, g)
        for _ in range(200):
            if r2.done:
                break
            core.run_once()
        np.testing.assert_array_equal(np.asarray(r2.padded_result()), want)
        # usable prefix caps at len(prompt)-1 = 23 tokens -> 2 full pages
        assert tier.promotes_total == 2
        demotes_before = tier.demotes_total
        core.prefix_cache.clear()
        assert tier.demotes_total == demotes_before
    finally:
        core.close()


# ----------------------------------------------------------- fuzz sweep

def test_park_resume_fuzz_invariants(engine):
    """~300-step seeded random submit/park schedule over a prefix-cached
    core: per-step tier/pool invariants hold, every request completes
    with the stream its no-park twin emitted, the pool returns to
    baseline, and replaying parked rows compiles nothing new."""
    rng = np.random.RandomState(0)
    arrivals = {}
    for i in range(24):
        step = int(rng.randint(0, 200))
        n = int(rng.randint(6, 21))
        max_new = int(rng.randint(4, 17))
        sampled = bool(rng.randint(0, 3) == 0)
        g = GenerationConfig(max_new_tokens=max_new, do_sample=sampled,
                             temperature=0.9, top_k=16, seed=100 + i)
        arrivals.setdefault(step, []).append(
            (_prompt(300 + i, n=n), g))
    park_steps = set(int(s) for s in rng.randint(0, 280, (70,)))

    def run(do_park):
        request_mod._rid_counter = itertools.count(9600)
        core = EngineCore(engine, enable_prefix_cache=True,
                          kv_host_pages=48, max_batch=4,
                          max_model_len=48)
        try:
            baseline = core._pool.free_blocks
            (w,) = core.submit(_prompt(299), GenerationConfig(
                max_new_tokens=4))
            for _ in range(50):
                if w.done:
                    break
                core.run_once()
            warm_compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            reqs = []
            for step in range(300):
                for prompt, g in arrivals.get(step, ()):
                    reqs.append(core.submit(prompt, g)[0])
                core.run_once()
                if do_park and step in park_steps:
                    core.park_for_pressure()
                kt = core._kv_tier.summary()
                assert kt["host_pages_resident"] <= kt["host_pages_total"]
                assert kt["parked_requests"] <= len(reqs)
                assert 0 <= core._pool.free_blocks <= core._pool.num_blocks
                assert core.active_count <= 4
            for _ in range(600):
                if all(r.done for r in reqs):
                    break
                core.run_once()
            assert all(r.state is RequestState.DONE for r in reqs)
            outs = [np.asarray(r.padded_result()) for r in reqs]
            compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - warm_compiles
            snap = core.metrics_snapshot()
            # refcount discipline: drop retained + demoted pages and the
            # pool must return to baseline, the tier to empty
            core.prefix_cache.clear()
            core._kv_tier.clear_demoted()
            assert core._pool.free_blocks == baseline
            assert core._kv_tier.resident_pages == 0
            return outs, snap, compiles
        finally:
            core.close()

    want, _, _ = run(do_park=False)
    got, snap, compiles = run(do_park=True)
    assert snap["kv_tier"]["parks_total"] >= 5
    assert snap["kv_tier"]["parks_total"] == \
        snap["kv_tier"]["resumes_total"]
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")
    assert compiles == 0      # park/resume reuses the warmed executables


# -------------------------------------------------------- metrics wiring

def test_kv_tier_metrics_steplog_and_prometheus(engine):
    jobs = [(_prompt(36), GenerationConfig(max_new_tokens=12))]
    _, _, snap, parked = _run_jobs(engine, jobs, rid_base=9700,
                                   park_at=(2,))
    assert parked == [True]
    kt = snap["kv_tier"]
    assert kt["parks_total"] == 1 and kt["resumes_total"] == 1
    assert kt["host_pages_total"] == TIER_PAGES
    assert kt["host_pages_peak"] >= 1

    request_mod._rid_counter = itertools.count(9700)
    core = EngineCore(engine, kv_host_pages=TIER_PAGES, **CORE_KW)
    try:
        (req,) = core.submit(*jobs[0])
        core.run_once()
        core.run_once()
        assert core.park_for_pressure()
        for _ in range(200):
            if req.done:
                break
            core.run_once()
        snap = core.metrics_snapshot()
        text = core.metrics.to_prometheus(snap)
        assert "kv_tier_parks_total 1" in text
        assert "kv_tier_resumes_total 1" in text
        assert 'kv_tier_host_pages{state="total"} 64' in text
        assert "kv_tier_parked_requests 0" in text
        kinds = [r["kind"] for r in core.steplog.records()]
        assert "park" in kinds and "resume" in kinds
        park_rec = next(r for r in core.steplog.records()
                        if r["kind"] == "park")
        assert park_rec["parked_rows"] == 1
        assert park_rec["host_pages"] >= 1
        assert park_rec["pages_freed"] >= 1
        resume_rec = next(r for r in core.steplog.records()
                          if r["kind"] == "resume")
        assert resume_rec["parked_rows"] == 0
    finally:
        core.close()
