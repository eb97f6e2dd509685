"""Continuous-batching serving engine (paddle_infer_tpu/serving/):
EngineCore step loop, admission control, deadlines, streaming and
metrics.  Tests drive ``run_once()`` directly on unstarted cores so the
schedule is deterministic; only the streaming test runs the background
thread."""
import logging
import threading
import time

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.serving import (DeadlineExceededError, EngineCore,
                                      QueueFullError, RejectedError,
                                      RequestState)


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
    """The engine the cores own (compile cache shared across tests)."""
    return PagedGenerationEngine(model, page_size=8)


@pytest.fixture(scope="module")
def ref(model):
    """Separate reference engine — direct generate() on the core-owned
    engine would corrupt its slot reservations."""
    return PagedGenerationEngine(model, page_size=8)


@pytest.fixture
def make_core(engine):
    cores = []

    def make(**kw):
        kw.setdefault("max_batch", 2)
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


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def test_single_request_matches_paged_engine(make_core, ref):
    core = make_core()
    ids = _prompt(0)
    g = GenerationConfig(max_new_tokens=6)
    (req,) = core.submit(ids, g)
    _drive(core, [req])
    want = ref.generate(ids[None], g)[0]
    np.testing.assert_array_equal(req.padded_result(), want)
    assert req.state is RequestState.DONE


def test_late_arrival_joins_inflight_batch(make_core, ref):
    """A request enqueued AFTER another started decoding must decode in
    the same fused step (continuous batching, not stop-the-world) —
    asserted via the step trace — and both rows stay correct."""
    core = make_core()
    g = GenerationConfig(max_new_tokens=8)
    (ra,) = core.submit(_prompt(1), g)
    core.run_once()                      # admit A + first decode step
    core.run_once()                      # A decoding alone
    assert ra.emitted >= 2 and not ra.done
    (rb,) = core.submit(_prompt(2), g)   # late arrival
    _drive(core, [ra, rb])
    joint = [t for t in core.step_trace
             if ra.rid in t["active"] and rb.rid in t["active"]]
    assert joint, "late request never shared a decode step"
    # and there were A-only steps before B arrived
    solo = [t for t in core.step_trace
            if ra.rid in t["active"] and rb.rid not in t["active"]]
    assert solo
    np.testing.assert_array_equal(
        ra.padded_result(), ref.generate(_prompt(1)[None], g)[0])
    np.testing.assert_array_equal(
        rb.padded_result(), ref.generate(_prompt(2)[None], g)[0])


def test_queue_backpressure_rejects(make_core):
    core = make_core(max_queue=2)
    g = GenerationConfig(max_new_tokens=4)
    core.submit(_prompt(3), g)
    core.submit(_prompt(4), g)
    with pytest.raises(QueueFullError):
        core.submit(_prompt(5), g)
    snap = core.metrics_snapshot()
    assert snap["counters"]["rejected_queue_full"] == 1
    assert snap["queue_depth"] == 2


def test_submit_many_is_all_or_nothing(make_core):
    core = make_core(max_queue=3)
    core.submit(_prompt(6), GenerationConfig(max_new_tokens=4))
    ids = np.stack([_prompt(7), _prompt(8), _prompt(9)])
    with pytest.raises(QueueFullError):
        core.submit(ids, GenerationConfig(max_new_tokens=4))
    assert core.queue_depth == 1        # none of the 3 was admitted


def test_oversized_prompt_rejected(make_core):
    core = make_core(max_model_len=64)
    with pytest.raises(RejectedError):
        core.submit(_prompt(10), GenerationConfig(max_new_tokens=60))
    assert core.metrics_snapshot()["counters"]["rejected"] == 1


def test_queued_deadline_expires_without_cost(make_core):
    core = make_core()
    baseline = core._pool.free_blocks
    (req,) = core.submit(_prompt(11), GenerationConfig(max_new_tokens=4),
                         timeout_s=0.01)
    time.sleep(0.05)
    core.run_once()
    with pytest.raises(DeadlineExceededError):
        req.result()
    assert req.state is RequestState.CANCELLED
    assert core._pool.free_blocks == baseline    # never reserved KV


def test_active_deadline_frees_kv_blocks(make_core):
    core = make_core()
    baseline = core._pool.free_blocks
    (req,) = core.submit(_prompt(12), GenerationConfig(max_new_tokens=32),
                         timeout_s=0.3)
    core.run_once()                     # admit + first decode chunk
    assert core.active_count == 1
    assert core._pool.free_blocks < baseline
    time.sleep(0.35)
    core.run_once()                     # deadline sweep evicts the row
    with pytest.raises(DeadlineExceededError):
        req.result()
    assert req.state is RequestState.CANCELLED
    assert core.active_count == 0
    assert core._pool.free_blocks == baseline


def test_streaming_tokens_arrive_incrementally(make_core, ref):
    core = make_core().start()
    ids = _prompt(13)
    g = GenerationConfig(max_new_tokens=6)
    (req,) = core.submit(ids, g)
    chunks = list(req.stream(timeout=120))
    assert len(chunks) >= 2             # prefill token + >=1 decode chunk
    got = np.concatenate(chunks)
    want = ref.generate(ids[None], g)[0]
    np.testing.assert_array_equal(got, want[:len(got)])
    core.stop()


def test_burst_metrics_and_eviction_backfill(make_core, ref):
    """Burst of 5 single-row requests through 2 slots: completions free
    slots that are backfilled from the queue, and the metrics snapshot
    adds up."""
    core = make_core(max_batch=2)
    g = GenerationConfig(max_new_tokens=6)
    reqs = [core.submit(_prompt(20 + i), g)[0] for i in range(5)]
    _drive(core, reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            r.padded_result(), ref.generate(_prompt(20 + i)[None], g)[0])
    snap = core.metrics_snapshot()
    c = snap["counters"]
    assert c["submitted"] == 5 and c["completed"] == 5
    assert c["tokens_generated"] == sum(r.emitted for r in reqs) == 30
    assert c["prefills"] == 5 and c["decode_steps"] >= 3
    assert snap["ttft_s"]["count"] == 5
    assert snap["ttft_s"]["p99_recent"] >= 0
    assert snap["inter_token_latency_s"]["count"] >= 1
    assert 0 < snap["occupancy"]["mean"] <= 1.0
    assert snap["queue_depth"] == 0 and snap["active"] == 0
    # every decode step ran at most 2 rows, and some step interleaved 2
    assert all(len(t["active"]) <= 2 for t in core.step_trace)
    assert any(len(t["active"]) == 2 for t in core.step_trace)


def test_mixed_sampling_and_greedy_share_a_step(make_core, ref):
    """Per-row sampling params live in arrays: a sampled row and a
    greedy row decode in one fused step, and the greedy row's tokens
    are unaffected by its neighbour."""
    core = make_core()
    greedy = GenerationConfig(max_new_tokens=6)
    sampled = GenerationConfig(max_new_tokens=6, do_sample=True,
                               temperature=0.8, top_k=5, top_p=0.9,
                               seed=7)
    (rg,) = core.submit(_prompt(30), greedy)
    (rs,) = core.submit(_prompt(31), sampled)
    _drive(core, [rg, rs])
    joint = [t for t in core.step_trace
             if rg.rid in t["active"] and rs.rid in t["active"]]
    assert joint
    np.testing.assert_array_equal(
        rg.padded_result(), ref.generate(_prompt(30)[None], greedy)[0])
    toks = rs.result()
    assert len(toks) == 6 and ((toks >= 0) & (toks < 96)).all()


def test_eos_parity_with_engine(make_core, ref):
    """A config with eos_token_id must stop exactly where the paged
    engine stops (the eos token itself is emitted, then pad)."""
    ids = _prompt(32)
    free_run = ref.generate(ids[None], GenerationConfig(max_new_tokens=6))
    eos = int(free_run[0, 2])           # greedy will hit it at step 3
    g = GenerationConfig(max_new_tokens=6, eos_token_id=eos,
                         pad_token_id=0)
    core = make_core()
    (req,) = core.submit(ids, g)
    _drive(core, [req])
    np.testing.assert_array_equal(req.padded_result(),
                                  ref.generate(ids[None], g)[0])


def test_exclusive_requests_run_on_scheduler(make_core):
    core = make_core()
    req = core.submit_exclusive(lambda: {"answer": 42})
    core.run_once()
    assert req.done and req.value == {"answer": 42}
    assert req.state is RequestState.DONE
    tr = core.tracer.get(req.rid)
    assert tr.state == "done"
    assert {"queue_wait", "exclusive"} <= {s.name for s in tr.spans}


def test_trace_spans_cover_request_wall_time(make_core):
    """Acceptance: every request's trace attributes >=95% of its
    end-to-end wall time to explicit spans — queue_wait, prefill, one
    decode span per fused chunk, evict — stitched edge-to-edge."""
    core = make_core()
    g = GenerationConfig(max_new_tokens=8)
    reqs = [core.submit(_prompt(40 + i), g)[0] for i in range(3)]
    _drive(core, reqs)
    for r in reqs:
        tr = core.tracer.get(r.rid)
        assert tr is not None and tr.state == "done"
        names = [s.name for s in tr.ordered()]
        assert names[0] == "queue_wait" and names[1] == "prefill"
        assert names[-1] == "evict"
        # 8 tokens, first from prefill, chunk=2 -> >=3 decode chunks
        assert names.count("decode") >= 3
        assert tr.coverage() >= 0.95, (r.rid, tr.to_dict())
    # dropped-in-queue requests trace too (one queue_wait, state set)
    (rd,) = core.submit(_prompt(44), g, timeout_s=0.01)
    time.sleep(0.05)
    core.run_once()
    tr = core.tracer.get(rd.rid)
    assert tr.state == "cancelled"
    assert [s.name for s in tr.spans] == ["queue_wait"]
    assert tr.spans[0].attrs["outcome"] == "deadline-in-queue"


def test_idle_scheduler_does_not_starve_readers(make_core):
    """An idle core's loop waits for work OUTSIDE the step lock.  When it
    slept holding it and re-took it at once, a /metrics snapshot (which
    reads slot occupancy under the same lock) waited tens of seconds on
    an idle server — Python's locks are not fair."""
    import time

    core = make_core().start()
    (r,) = core.submit(_prompt(12), GenerationConfig(max_new_tokens=3))
    r.result(timeout=120)
    worst = 0.0
    for _ in range(20):
        t0 = time.monotonic()
        snap = core.metrics_snapshot()
        worst = max(worst, time.monotonic() - t0)
    assert snap["counters"]["completed"] == 1
    assert worst < 2.0, f"metrics_snapshot took {worst:.1f}s on an idle core"


def test_decode_loop_compile_free_after_warmup(make_core, ref):
    """Acceptance: the fused decode loop performs ZERO XLA compilations
    after warmup.  Three batches with heterogeneous configs (greedy,
    sampled hot, sampled cold+top_k, mixed eos/lengths) run after the
    first decode chunk marked the loop warm; the serving-decode compile
    counter must stay flat and post_warmup_decode_compiles must be 0."""
    from paddle_infer_tpu.observability import get_compile_log

    log = get_compile_log()
    core = make_core()
    warm = GenerationConfig(max_new_tokens=4)
    (r0,) = core.submit(_prompt(50), warm)
    _drive(core, [r0])                   # warmup: compiles are expected
    dkey = ("serve-step", core._max_batch, core._token_budget,
            core._max_pages, core._pool.num_blocks)
    assert log.is_warm("serving-decode", dkey)
    # the compile log is process-global and other tests of this worker
    # (recompile-detector tests among them) write to it: every check
    # below is a DELTA over this test's own window, and "the warmup
    # compile was seen" means this engine's own signature record — the
    # executable may have been compiled by an earlier test that shares
    # the module-scoped engine
    assert core._engine._compiled_sigs.get(dkey)
    baseline = log.count("serving-decode")
    post_warm0 = log.summary()["post_warmup_decode_compiles"]

    batches = [
        [GenerationConfig(max_new_tokens=6),
         GenerationConfig(max_new_tokens=3, do_sample=True,
                          temperature=1.3, seed=11)],
        [GenerationConfig(max_new_tokens=5, do_sample=True,
                          temperature=0.2, top_k=3, top_p=0.8, seed=5),
         GenerationConfig(max_new_tokens=6, eos_token_id=1,
                          pad_token_id=0)],
        [GenerationConfig(max_new_tokens=7, min_length=2),
         GenerationConfig(max_new_tokens=4, do_sample=True, top_p=0.5,
                          seed=3)],
    ]
    for i, cfgs in enumerate(batches):
        reqs = [core.submit(_prompt(60 + 10 * i + j), cfg)[0]
                for j, cfg in enumerate(cfgs)]
        _drive(core, reqs)
        assert all(r.state is RequestState.DONE for r in reqs)
    assert log.count("serving-decode") == baseline, \
        "heterogeneous configs recompiled the fused decode loop"
    assert log.summary()["post_warmup_decode_compiles"] == post_warm0
    snap = core.metrics_snapshot()
    assert snap["counters"]["completed"] == 7
    # the StepLog flight recorder observed every step — including its
    # per-executable cost_analysis capture — without tripping the
    # compile-free invariant above
    records = core.steplog.records()
    kinds = {r["kind"] for r in records}
    assert {"prefill", "decode", "evict"} <= kinds
    post_warm = [r for r in records
                 if r["kind"] == "decode" and r["seq"] > records[0]["seq"]]
    assert all(r["compile_events"] == 0 for r in post_warm[1:]), \
        "StepLog saw compile events on warmed decode steps"
    assert all(r["bytes_est"] > 0 for r in records
               if r["kind"] in ("prefill", "decode"))
    assert snap["steplog"]["records"] == len(records)


def test_close_rejects_queued_and_cancels_active(make_core):
    core = make_core()
    g = GenerationConfig(max_new_tokens=16)
    (ra,) = core.submit(_prompt(33), g)
    core.run_once()                     # A active
    (rb,) = core.submit(_prompt(34), g)  # B still queued (slot free tho)
    core.close()
    assert ra.state is RequestState.CANCELLED
    assert rb.state is RequestState.REJECTED
    with pytest.raises(RejectedError):
        core.submit(_prompt(35), g)


def test_mid_decode_failure_frees_blocks(make_core, engine, monkeypatch):
    """A decode-chunk exception fails every in-flight row through the
    shared release path (``_release_slot_kv``); no per-request block
    accounting may be dropped — the pool returns to its baseline."""
    core = make_core()
    baseline = core._pool.free_blocks
    real = engine.run_paged_program

    def boom(key, builder, *args):
        if isinstance(key, tuple) and key and key[0] == "serve-step":
            raise RuntimeError("injected decode failure")
        return real(key, builder, *args)

    monkeypatch.setattr(engine, "run_paged_program", boom)
    reqs = core.submit(np.stack([_prompt(70), _prompt(71)]),
                       GenerationConfig(max_new_tokens=8))
    core.run_once()                     # admit both, decode chunk raises
    assert all(r.state is RequestState.FAILED for r in reqs)
    assert core.active_count == 0
    assert core._pool.free_blocks == baseline
    monkeypatch.setattr(engine, "run_paged_program", real)
    (again,) = core.submit(_prompt(72), GenerationConfig(max_new_tokens=4))
    _drive(core, [again])               # core stays usable afterwards
    assert again.state is RequestState.DONE


def test_close_evicts_under_step_lock(make_core):
    """Regression (tpulint lock-discipline): close() used to drain the
    queue and evict active slots without ``_step_lock``, racing a
    concurrent ``run_once``.  Probe that every eviction during close()
    now happens with the lock held."""
    core = make_core()
    (req,) = core.submit(_prompt(50), GenerationConfig(max_new_tokens=16))
    core.run_once()                     # admit, still active
    assert core.active_count == 1
    held = []
    orig = core._evict

    def probe(slot, state, err=None):
        held.append(core._step_lock._is_owned())
        return orig(slot, state, err)

    core._evict = probe
    core.close()
    assert held and all(held)
    assert req.state is RequestState.CANCELLED


def test_active_count_acquires_step_lock(make_core):
    """Regression (tpulint lock-discipline): ``active_count`` read the
    slot dict without ``_step_lock`` (which is why the lock is now an
    RLock — the locked step path reads it too)."""
    core = make_core()
    orig = core._step_lock
    entered = []

    class Probe:
        def __enter__(self):
            entered.append(True)
            return orig.__enter__()

        def __exit__(self, *exc):
            return orig.__exit__(*exc)

    core._step_lock = Probe()
    try:
        assert core.active_count == 0
    finally:
        core._step_lock = orig
    assert entered


def test_stop_returns_bool_and_reports_wedged_thread(make_core):
    """stop(timeout) -> bool: True when the loop thread is down (clean
    join, or never started), False when it is still wedged in a step —
    the signal close() uses to decide whether pool teardown is safe."""
    core = make_core()
    assert core.stop() is True          # never started: trivially down
    core.start()
    (req,) = core.submit(_prompt(90), GenerationConfig(max_new_tokens=4))
    req.result(timeout=60)
    assert core.stop() is True          # clean join
    assert core.stop() is True          # idempotent

    wedged = make_core()
    entered = threading.Event()
    release = threading.Event()

    def stuck(wait_s=0.0):
        entered.set()
        release.wait(10.0)
        return False

    wedged.run_once = stuck
    wedged.start()
    assert entered.wait(2.0)
    assert wedged.stop(timeout=0.2) is False   # still stuck in a "step"
    release.set()


def test_close_escalates_past_wedged_external_step(make_core):
    """close() racing an in-flight external run_once(): the wedged step
    holds ``_step_lock`` forever, so close() must time out its bounded
    acquire and escalate — unblocking every result()/stream() consumer
    without touching the pool the step still owns."""
    core = make_core(max_batch=1)
    entered = threading.Event()
    release = threading.Event()
    orig_step = core._mixed_step

    def slow_step():
        entered.set()
        release.wait(20.0)
        return orig_step()

    core._mixed_step = slow_step
    (ra,) = core.submit(_prompt(91), GenerationConfig(max_new_tokens=8))

    def worker():
        try:
            while not entered.is_set():
                core.run_once()
        except Exception:
            pass

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    assert entered.wait(5.0)            # ra admitted, step now wedged
    (rb,) = core.submit(_prompt(92), GenerationConfig(max_new_tokens=8))

    t0 = time.monotonic()
    core.close(timeout=0.3)             # lock held by the wedged step
    assert time.monotonic() - t0 < 5.0  # bounded, did not deadlock

    assert rb.state is RequestState.REJECTED
    with pytest.raises(RejectedError, match="scheduler wedged"):
        rb.result()
    assert ra.state is RequestState.FAILED
    with pytest.raises(RejectedError, match="step was wedged"):
        ra.result(timeout=5.0)          # consumer unblocked, not stranded
    release.set()
    t.join(10.0)


def test_loop_exceptions_counted_logged_once_with_backoff(make_core, caplog):
    """A scheduler-loop exception must be counted per occurrence, logged
    once per distinct traceback (not once per spin), and spaced by an
    exponential backoff so a wedged engine can't spin hot."""
    core = make_core()
    calls = []

    def bad(wait_s=0.0):
        calls.append(time.monotonic())
        raise RuntimeError("injected loop failure")

    core.run_once = bad
    with caplog.at_level(logging.ERROR,
                         logger="paddle_infer_tpu.serving.engine_core"):
        core.start()
        deadline = time.monotonic() + 5.0
        while (core.metrics_snapshot()["resilience"]["loop_exceptions"] < 4
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert core.stop() is True
    snap = core.metrics_snapshot()["resilience"]
    assert snap["loop_exceptions"] >= 4
    logged = [r for r in caplog.records
              if "serving loop step failed" in r.getMessage()]
    assert len(logged) == 1             # same traceback -> one log line
    gaps = [b - a for a, b in zip(calls, calls[1:])]
    assert gaps and gaps[-1] > gaps[0]  # backoff grew between spins
