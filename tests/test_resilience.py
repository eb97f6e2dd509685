"""Fault-tolerant serving (paddle_infer_tpu/serving/resilience/):
deterministic fault injection, supervised retry/replay recovery, and
health-gated degradation.

The acceptance test is the seeded chaos run: one workload driven twice
— fault-free for the expected per-request token streams, then under a
scripted schedule of MemoryError, engine crashes (with and without KV
loss), non-finite logits and a hung step, across >= 200 engine steps.
Every non-quarantined request must finish with EXACTLY its expected
stream (no loss, no duplicates), the KV pool must return to its
baseline, and replay must compile nothing new after warmup
(CompileLog-asserted).

Request ids feed the per-row sampling RNG (``fold_in(key, rid)``), so
both runs pin the process-wide rid counter to the same start — equal
submission order then yields equal rids, making even sampled rows
bit-comparable across runs.
"""
import itertools
import threading
import time

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.observability.compilelog import get_compile_log
from paddle_infer_tpu.serving import (DeadlineExceededError, EngineCore,
                                      EngineSupervisor, FaultPlane,
                                      FaultSpec, HealthMonitor,
                                      HealthState, LoadShedError,
                                      QuarantinedError, RequestState)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.resilience import (NULL_PLANE, InjectedFault,
                                                 InjectedMemoryError)
from paddle_infer_tpu.serving.resilience.faultplane import SITES


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """Replay parity compares tokens across the prefill and decode
    executables, which is bitwise only when both run unsharded — clear
    any hybrid mesh a failing test in another module leaked behind
    (ops consult ``topology.get_current_mesh()`` at call time)."""
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
    """The engine the supervised cores own (compile cache shared across
    tests — restart recovery rebuilds its pools in place)."""
    return PagedGenerationEngine(model, page_size=8)


@pytest.fixture(scope="module")
def ref(model):
    """Separate reference engine — direct generate() on the core-owned
    engine would corrupt its slot reservations."""
    return PagedGenerationEngine(model, page_size=8)


@pytest.fixture
def make_sup(engine):
    """(core, sup) factory: core kwargs are split from supervisor
    kwargs, every supervisor is closed on teardown."""
    sups = []

    def make(plane=None, **kw):
        core_kw = {"max_batch": kw.pop("max_batch", 2),
                   "max_model_len": kw.pop("max_model_len", 48),
                   "enable_prefix_cache": kw.pop("enable_prefix_cache",
                                                 False),
                   "fault_plane": plane}
        if "max_queue" in kw:
            core_kw["max_queue"] = kw.pop("max_queue")
        core = EngineCore(engine, **core_kw)
        sup = EngineSupervisor(core, **kw)
        sups.append(sup)
        return core, sup

    yield make
    for s in sups:
        s.close()


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _drive(sup, reqs, max_iters=400):
    steps = 0
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return steps
        sup.run_once()
        steps += 1
    raise AssertionError("requests did not finish")


# --------------------------------------------------------------- fault plane

def test_faultplane_scripted_and_probabilistic_are_deterministic():
    def pattern(seed):
        plane = FaultPlane([FaultSpec("decode.step", at=3),
                            FaultSpec("kv.alloc", p=0.3, times=2,
                                      exc="MemoryError")], seed=seed)
        fired = []
        for i in range(40):
            for site, err in (("decode.step", InjectedFault),
                              ("kv.alloc", InjectedMemoryError)):
                try:
                    plane.fire(site)
                except err as e:
                    fired.append((site, i, e.seq))
        return fired, plane.counts()

    a, ca = pattern(7)
    b, cb = pattern(7)
    assert a == b and ca == cb               # same seed -> same schedule
    assert ("decode.step", 2, 3) in a        # scripted fire at seq 3
    assert ca["kv.alloc"] == 2               # p-spec honoured its budget
    c, _ = pattern(8)
    assert [x for x in c if x[0] == "kv.alloc"] != \
        [x for x in a if x[0] == "kv.alloc"]


def test_faultplane_from_spec_json_and_null_plane():
    plane = FaultPlane.from_spec(
        '[{"site": "prefill.run", "at": 1, "exc": "MemoryError", '
        '"lose_kv": true}]')
    with pytest.raises(MemoryError) as ei:
        plane.fire("prefill.run")
    assert ei.value.lose_kv and ei.value.site == "prefill.run"
    assert plane.counts() == {"prefill.run": 1}
    with pytest.raises(ValueError):
        FaultSpec("not.a.site")
    with pytest.raises(ValueError):
        FaultSpec("decode.step", action="explode")
    # the disabled plane: no effects, no counts, at every site
    for site in SITES:
        assert NULL_PLANE.fire(site) is None
    assert NULL_PLANE.counts() == {}


def test_faultplane_latency_spec_sleeps(monkeypatch):
    from paddle_infer_tpu.serving.resilience import faultplane
    slept = []
    monkeypatch.setattr(faultplane, "time_sleep", slept.append)
    plane = FaultPlane([FaultSpec("decode.step", action="hang", at=2,
                                  delay_s=0.5)])
    plane.fire("decode.step")
    assert slept == []
    plane.fire("decode.step")
    assert slept == [0.5]


# ------------------------------------------------------------- health machine

def test_health_transitions_are_guarded():
    h = HealthMonitor()
    assert h.state is HealthState.HEALTHY and h.is_serving()
    assert not h.to_healthy("noop")          # only DEGRADED -> HEALTHY
    assert h.to_degraded("failure")
    assert not h.to_degraded("again")        # already degraded
    assert h.to_healthy("recovered")
    assert h.to_draining("admin")
    assert not h.is_serving()
    assert not h.to_degraded("late failure")  # draining is sticky
    assert h.resume() and h.state is HealthState.DEGRADED
    assert h.to_down("crash loop")
    assert h.state.code == 3
    assert h.resume() and h.state is HealthState.DEGRADED
    reasons = [t["reason"] for t in h.transitions()]
    assert "crash loop" in reasons


# ----------------------------------------------------------- replay recovery

def test_replay_after_kv_loss_preserves_greedy_stream(make_sup, ref):
    """A mid-decode crash that loses the device pools: the supervisor
    restarts the engine and replays the in-flight request; the client
    sees the exact uninterrupted stream."""
    ids = _prompt(1)
    g = GenerationConfig(max_new_tokens=12)
    want = ref.generate(ids[None], g)[0]

    # decode fire #3 (after prefill + two clean chunks of 4) crashes
    plane = FaultPlane([FaultSpec("decode.step", at=3, lose_kv=True)])
    core, sup = make_sup(plane)
    (req,) = core.submit(ids, g)
    _drive(sup, [req])
    np.testing.assert_array_equal(req.padded_result(), want)
    assert req.retries == 1
    res = core.metrics_snapshot()["resilience"]
    assert res["engine_restarts"] == 1
    assert res["request_retries"] == 1
    assert res["faults_injected"] == {"decode.step": 1}
    assert res["health_state"] == "degraded"


def test_replay_sampled_row_draws_the_same_stream(make_sup):
    """Replay resumes sampling at the original per-(rid, step) fold_in
    offset — a SAMPLED row's replayed stream equals its uninterrupted
    one.  Both runs pin the rid counter so the request keys match."""
    ids = _prompt(2)
    g = GenerationConfig(max_new_tokens=12, do_sample=True,
                         temperature=0.8, top_k=12, seed=11)

    def run(plane):
        request_mod._rid_counter = itertools.count(7000)
        core, sup = make_sup(plane)
        (req,) = core.submit(ids, g)
        _drive(sup, [req])
        return req

    want = run(None).result()
    got = run(FaultPlane([FaultSpec("decode.step", at=2)]))
    np.testing.assert_array_equal(got.result(), want)
    assert got.retries == 1


def test_retry_budget_exhaustion_quarantines_poison_request(make_sup):
    """A request that crashes the engine on every decode chunk burns
    its replay budget and is quarantined instead of crash-looping."""
    plane = FaultPlane([FaultSpec("decode.step", p=1.0)])
    core, sup = make_sup(plane, max_retries=2, crash_threshold=100)
    (req,) = core.submit(_prompt(3), GenerationConfig(max_new_tokens=8))
    for _ in range(40):
        if req.done:
            break
        sup.run_once()
    assert req.state is RequestState.FAILED
    with pytest.raises(QuarantinedError):
        req.result()
    assert req.retries == 2
    res = core.metrics_snapshot()["resilience"]
    assert res["requests_quarantined"] == 1
    assert res["request_retries"] == 2
    assert core.active_count == 0 and core.queue_depth == 0


def test_crash_loop_goes_down_and_resume_recovers(make_sup):
    plane = FaultPlane([FaultSpec("decode.step", p=1.0)])
    core, sup = make_sup(plane, max_retries=50, crash_threshold=3)
    (req,) = core.submit(_prompt(4), GenerationConfig(max_new_tokens=8))
    for _ in range(40):
        if req.done:
            break
        sup.run_once()
    assert sup.health.state is HealthState.DOWN
    # DOWN disables replay: the in-flight request failed rather than
    # retrying forever against a wedged engine
    assert req.state is RequestState.FAILED
    assert sup.consume_backoff() > 0.0
    assert sup.resume() and sup.health.state is HealthState.DEGRADED


def test_expired_request_is_cancelled_not_replayed(make_sup):
    plane = FaultPlane([FaultSpec("decode.step", at=2)])
    core, sup = make_sup(plane)
    (req,) = core.submit(_prompt(5), GenerationConfig(max_new_tokens=12),
                         timeout_s=0.05)
    sup.run_once()                       # admit + first chunk
    time.sleep(0.08)                     # deadline passes mid-decode
    for _ in range(5):
        if req.done:
            break
        sup.run_once()                   # crash/deadline -> no replay
    assert req.state is RequestState.CANCELLED
    with pytest.raises(DeadlineExceededError):
        req.result()
    assert req.retries == 0              # no budget spent on a dead row
    assert core.metrics_snapshot()["resilience"]["request_retries"] == 0


# ------------------------------------------------------- degradation ladder

def test_memory_pressure_halves_batch_then_ladder_recovers(make_sup):
    plane = FaultPlane([FaultSpec("kv.alloc", at=1, exc="MemoryError")])
    core, sup = make_sup(plane, max_batch=4,
                         recover_after=1)
    assert core.effective_max_batch == 4
    reqs = [core.submit(_prompt(10 + i), GenerationConfig(
        max_new_tokens=20))[0] for i in range(2)]
    _drive(sup, reqs)
    for r in reqs:                       # the OOM victim was requeued
        assert r.state is RequestState.DONE
    assert core.metrics_snapshot()["resilience"]["request_retries"] == 1
    # ladder: halved to 2 on pressure, then grown back one slot per
    # clean chunk, and DEGRADED -> HEALTHY at full width
    assert core.effective_max_batch == 4
    assert sup.health.state is HealthState.HEALTHY


def test_second_pressure_sheds_queued_low_headroom(make_sup):
    specs = [FaultSpec("kv.alloc", at=1, exc="MemoryError"),
             FaultSpec("kv.alloc", at=2, exc="MemoryError")]
    core, sup = make_sup(FaultPlane(specs), max_batch=1,
                         shed_headroom_s=5.0, recover_after=100)
    g = GenerationConfig(max_new_tokens=8)
    # the OOM magnet has no deadline (never shed); the doomed request
    # waits in the queue with less headroom than the ladder demands
    (victim,) = core.submit(_prompt(20), g)
    (doomed,) = core.submit(_prompt(21), g, timeout_s=2.0)
    for _ in range(10):
        if doomed.done:
            break
        sup.run_once()                   # 2nd consecutive OOM -> shed
    assert doomed.state is RequestState.REJECTED
    with pytest.raises(LoadShedError):
        doomed.result()
    _drive(sup, [victim])                # the magnet itself replays fine
    assert victim.state is RequestState.DONE
    res = core.metrics_snapshot()["resilience"]
    assert res["requests_shed"] == 1
    assert res["request_retries"] == 2
    assert core.effective_max_batch == 1


def test_nan_logits_quarantine_only_the_offending_row(make_sup, ref):
    """Non-finite logits on one row: that row alone is quarantined; its
    batch-mate keeps decoding and stays bit-exact."""
    ga = GenerationConfig(max_new_tokens=12)
    ids_a, ids_b = _prompt(30), _prompt(31)
    request_mod._rid_counter = itertools.count(7100)
    plane = FaultPlane([FaultSpec("decode.step", action="nan_rows",
                                  at=2, rid=7100)])
    core, sup = make_sup(plane)
    (ra,) = core.submit(ids_a, ga)
    (rb,) = core.submit(ids_b, ga)
    _drive(sup, [ra, rb])
    assert ra.state is RequestState.FAILED
    with pytest.raises(QuarantinedError):
        ra.result()
    np.testing.assert_array_equal(rb.padded_result(),
                                  ref.generate(ids_b[None], ga)[0])
    res = core.metrics_snapshot()["resilience"]
    assert res["requests_quarantined"] == 1
    assert res["engine_restarts"] == 0   # row fault, not an engine fault
    assert res["request_retries"] == 0


# ------------------------------------------------------ watchdog + draining

def test_watchdog_trips_on_hung_step(make_sup):
    plane = FaultPlane([FaultSpec("decode.step", action="hang", at=2,
                                  delay_s=0.25)])
    core, sup = make_sup(plane, watchdog_s=0.1)
    (req,) = core.submit(_prompt(40), GenerationConfig(max_new_tokens=8))
    sup.run_once()                       # admit + first (clean) chunk
    trips0 = core.metrics.watchdog_trips
    sup.run_once()                       # hung chunk
    assert core.metrics.watchdog_trips == trips0 + 1
    assert sup.health.state is HealthState.DEGRADED
    _drive(sup, [req])
    assert req.state is RequestState.DONE
    info = sup.health_info()
    assert info["watchdog_s"] == 0.1 and info["stalled_for_s"] == 0.0


def test_live_watchdog_flags_step_still_in_flight(make_sup):
    """The sidecar thread must trip WHILE a step is wedged (not only
    post-hoc), and exactly once per stall."""
    core, sup = make_sup(watchdog_s=0.05)
    started, release = threading.Event(), threading.Event()

    def wedged(wait_s=0.0):
        started.set()
        release.wait(5.0)
        return False

    core.run_once = wedged
    sup.start()
    assert started.wait(2.0)
    deadline = time.monotonic() + 2.0
    while (core.metrics.watchdog_trips < 1
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert core.metrics.watchdog_trips == 1   # deduped while stalled
    assert sup.stalled_for() > 0.05
    assert sup.health.state is HealthState.DEGRADED
    release.set()
    assert sup.stop(timeout=5.0)


def test_drain_resume_gate_admission(make_sup):
    core, sup = make_sup()
    g = GenerationConfig(max_new_tokens=4)
    assert sup.drain()
    assert core.draining and not sup.health.is_serving()
    with pytest.raises(LoadShedError):
        core.submit(_prompt(41), g)
    assert core.metrics_snapshot()["counters"]["rejected"] == 1
    assert sup.resume()
    (req,) = core.submit(_prompt(41), g)
    _drive(sup, [req])
    assert req.state is RequestState.DONE
    assert core.metrics_snapshot()["resilience"]["draining"] is False


def test_supervisor_background_thread_and_stop(make_sup):
    core, sup = make_sup()
    sup.start()
    (req,) = core.submit(_prompt(42), GenerationConfig(max_new_tokens=8))
    req.result(timeout=60)
    assert sup.stop(timeout=5.0) is True
    assert sup.stop(timeout=5.0) is True     # idempotent


# --------------------------------------------------------------- chaos run

def test_seeded_chaos_exact_streams_across_200_steps(model):
    """THE acceptance scenario: >= 200 supervised engine steps under a
    seeded schedule of MemoryError, engine crashes (with and without KV
    loss), non-finite logits, a hung step, and admission-path faults on
    every remaining site.  Every non-quarantined request must complete
    with exactly its fault-free token stream, the pool must drain back
    to baseline, and replay must not compile any new decode executable
    after warmup."""
    n_req, max_new = 32, 24
    shared = np.random.RandomState(99).randint(0, 96, (12,)).astype(
        np.int32)
    prompts = []
    for i in range(n_req):
        if i % 4 == 0:    # every 4th request shares a 12-token prefix
            tail = np.random.RandomState(200 + i).randint(
                0, 96, (4,)).astype(np.int32)
            prompts.append(np.concatenate([shared, tail]))
        else:
            prompts.append(_prompt(100 + i, n=8 if i % 2 else 16))
    configs = [GenerationConfig(max_new_tokens=max_new, do_sample=True,
                                temperature=0.9, top_k=20, seed=3 + i)
               if i % 8 == 5 else
               GenerationConfig(max_new_tokens=max_new)
               for i in range(n_req)]
    chaos_engine = PagedGenerationEngine(model, page_size=8,
                                         prompt_bucket=16)

    def run(plane):
        request_mod._rid_counter = itertools.count(5000)
        core = EngineCore(chaos_engine, max_batch=4,
                          max_queue=64, max_model_len=40,
                          enable_prefix_cache=True, fault_plane=plane)
        sup = EngineSupervisor(core, watchdog_s=0.5, max_retries=3,
                               crash_threshold=10, recover_after=10,
                               backoff_base_s=0.0)
        try:
            pool_baseline = core._pool.free_blocks
            (w,) = core.submit(_prompt(98), GenerationConfig(
                max_new_tokens=4))
            _drive(sup, [w])             # warmup: compile + mark_warm
            warm_compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            reqs = [core.submit(p, g)[0]
                    for p, g in zip(prompts, configs)]
            steps = _drive(sup, reqs, max_iters=2000)
            # phase 2 — sequential identical-prompt resubmissions: with
            # the fleet drained the retained pages survive, so the
            # 16-token prompt matches its capped len-1 = 15-token prefix
            # (1 full page + a 7-token partial) and admission takes the
            # copy-on-write path the saturated pool above never reaches
            for _ in range(3):
                (e,) = core.submit(prompts[0], GenerationConfig(
                    max_new_tokens=max_new))
                steps += _drive(sup, [e])
                reqs.append(e)
            outs = []
            for r in reqs:
                try:
                    outs.append(r.result().tolist())
                except Exception:
                    outs.append(None)
            snap = core.metrics_snapshot()
            decode_compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - warm_compiles
            # refcount discipline: queue empty, no active rows; dropping
            # the retained cache pages must return the pool to baseline
            assert core.active_count == 0 and core.queue_depth == 0
            core.prefix_cache.clear()
            assert core._pool.free_blocks == pool_baseline
        finally:
            sup.close()
        return reqs, outs, snap, steps, decode_compiles

    _, expected, _, _, _ = run(None)
    assert all(o is not None for o in expected)

    # schedule indices are absolute per-site fire counts; the warmup
    # request burns decode.step x3 (chunk=1, max_new=4), and one fire
    # each of kv.alloc / prefill.run / prefix.match
    plane = FaultPlane([
        FaultSpec("decode.step", at=23, lose_kv=True),     # restart
        FaultSpec("decode.step", at=63),                   # crash, KV ok
        FaultSpec("decode.step", action="hang", at=110, delay_s=0.8),
        FaultSpec("decode.step", action="nan_rows", at=150),
        FaultSpec("kv.alloc", at=9, exc="MemoryError"),
        FaultSpec("kv.alloc", at=20, exc="MemoryError"),
        FaultSpec("prefill.run", at=16),
        FaultSpec("page.copy", at=3),
        FaultSpec("prefix.match", at=25),
    ], seed=0)
    reqs, got, snap, steps, decode_compiles = run(plane)

    assert steps >= 200
    res = snap["resilience"]
    counts = res["faults_injected"]
    assert counts["decode.step"] == 4
    assert counts["kv.alloc"] == 2
    assert counts["prefill.run"] == 1
    assert counts["page.copy"] == 1
    assert counts["prefix.match"] == 1
    assert res["engine_restarts"] == 1
    assert res["watchdog_trips"] >= 1
    assert res["requests_quarantined"] == 1
    assert res["request_retries"] >= 6

    quarantined = [i for i, r in enumerate(reqs)
                   if r.state is RequestState.FAILED
                   and isinstance(r.error, QuarantinedError)]
    assert len(quarantined) == 1
    for i, (want, out) in enumerate(zip(expected, got)):
        if i in quarantined:
            # tokens delivered before the quarantine are an uncorrupted
            # prefix of the expected stream (never a wrong token)
            delivered = reqs[i].tokens
            assert delivered == want[:len(delivered)]
            continue
        assert out is not None, f"request {i} did not complete"
        assert out == want, f"request {i} stream diverged"

    # replay reused the warmed decode executable throughout
    assert decode_compiles == 0
    assert res["health_state"] in ("healthy", "degraded")


# ------------------------------------------------------------ metrics wiring

def test_resilience_counters_render_as_prometheus_families(make_sup):
    core, sup = make_sup()
    core.metrics.on_engine_restart()
    core.metrics.on_watchdog_trip(2)
    sup.drain()
    text = core.metrics.to_prometheus(core.metrics_snapshot())
    assert 'engine_health_state{state="draining"} 1' in text
    assert 'engine_health_state{state="healthy"} 0' in text
    assert "engine_restarts_total 1" in text
    assert "watchdog_trips_total 2" in text
    assert "serving_effective_max_batch 2" in text
    assert 'faults_injected_total{site="none"} 0' in text
    sup.resume()


def test_fault_counts_reach_metrics_snapshot(make_sup):
    plane = FaultPlane([FaultSpec("decode.step", at=1)])
    core, sup = make_sup(plane)
    (req,) = core.submit(_prompt(60), GenerationConfig(max_new_tokens=8))
    _drive(sup, [req])
    text = core.metrics.to_prometheus(core.metrics_snapshot())
    assert 'faults_injected_total{site="decode.step"} 1' in text
    assert req.state is RequestState.DONE
