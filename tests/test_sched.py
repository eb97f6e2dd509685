"""SLO-aware scheduler (paddle_infer_tpu/serving/sched/ +
tools/loadgen.py): trace-replay determinism, schedule-independent token
streams across admission policies, predictive-shed accounting, planner
calibration gates and dynamic chunk planning.  Engine tests drive
``run_once()`` directly on unstarted cores so the schedule is
deterministic."""
import itertools
import math
import time

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.serving import (EngineCore, LoadShedError,
                                      RequestState, make_policy)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.sched import SlackPolicy, StepPlanner
from paddle_infer_tpu.serving.sched.planner import (MIN_FIT_SAMPLES,
                                                    StepCalibration)
from tools import loadgen


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


def _drive(core, reqs, max_iters=300):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _calibrate(core, n=2):
    """Drive a few requests to completion so the steplog holds enough
    clean decode + prefill records for ``admission_ready``."""
    g = GenerationConfig(max_new_tokens=MIN_FIT_SAMPLES + 4)
    reqs = [core.submit(_prompt(70 + i, 12), g)[0] for i in range(n)]
    _drive(core, reqs)
    cal = core._planner.calibration(refresh=True)
    assert cal.admission_ready, cal.as_dict()
    return cal


# --------------------------------------------------------------- loadgen
def test_trace_seed_determinism(tmp_path):
    a = loadgen.generate_trace(3, 2.0, 10.0)
    b = loadgen.generate_trace(3, 2.0, 10.0)
    assert a == b
    assert a != loadgen.generate_trace(4, 2.0, 10.0)
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    loadgen.write_trace(str(pa), a)
    loadgen.write_trace(str(pb), b)
    assert pa.read_bytes() == pb.read_bytes()     # byte-identical JSONL
    assert loadgen.read_trace(str(pa)) == a       # lossless round trip


def test_trace_tenant_classes():
    events = loadgen.generate_trace(0, 4.0, 12.0)
    tenants = {e["tenant"] for e in events}
    assert tenants <= {"chat", "rag", "batch"}
    # deadline mix: chat/rag carry deadlines, batch never does
    for e in events:
        if e["tenant"] == "batch":
            assert e["timeout_s"] is None
        else:
            assert e["timeout_s"] > 0
    # shared-prefix tenants repeat their leading tokens + cache salt
    rag = [e for e in events if e["tenant"] == "rag"]
    if len(rag) >= 2:
        head = rag[0]["prompt"][:8]
        assert all(e["prompt"][:8] == head for e in rag)
        assert all(e["cache_salt"] == "tenant-rag" for e in rag)
    # arrivals are time-sorted with stable indices
    assert [e["i"] for e in events] == list(range(len(events)))
    assert all(events[i]["t"] <= events[i + 1]["t"]
               for i in range(len(events) - 1))


# -------------------------------------------------------------- policies
class _FakeCfg:
    def __init__(self, max_new):
        self.max_new_tokens = max_new


class _FakeReq:
    def __init__(self, plen, max_new, deadline):
        self.prompt = np.zeros((plen,), np.int32)
        self.config = _FakeCfg(max_new)
        self.deadline = deadline
        self.sched_predicted_done = None
        self.sched_predicted_slack = None


_READY = StepCalibration(scale_s_per_byte=1e-9, decode_step_s=0.01,
                         prefill_s_per_token=0.001,
                         n_decode=MIN_FIT_SAMPLES, n_prefill=2)


def test_make_policy():
    assert make_policy("fifo").name == "fifo"
    assert make_policy("slack").reorders is True
    with pytest.raises(ValueError, match="unknown sched policy"):
        make_policy("bogus")


def test_fifo_policy_is_identity():
    reqs = [_FakeReq(8, 4, None), _FakeReq(8, 4, 1.0)]
    kept, shed = make_policy("fifo").schedule(reqs, 0.0, _READY, 0)
    assert kept == reqs and shed == []


def test_slack_policy_cold_fit_degrades_to_fifo():
    reqs = [_FakeReq(8, 4, 0.001), _FakeReq(8, 4, None)]
    cold = StepCalibration()
    kept, shed = SlackPolicy().schedule(reqs, 0.0, cold, 0)
    assert kept == reqs and shed == []   # never sheds on a cold fit


def test_slack_policy_edf_order_and_shed():
    now = 100.0
    tight = _FakeReq(10, 5, now + 1.0)
    loose = _FakeReq(10, 5, now + 9.0)
    never = _FakeReq(10, 5, None)
    # predicted done ~ now + plen*0.001 + 5*0.01 = now + 0.06 for each,
    # doomed's deadline is already behind the prediction
    doomed = _FakeReq(10, 5, now + 0.01)
    kept, shed = SlackPolicy().schedule(
        [never, loose, doomed, tight], now, _READY, 0)
    assert shed == [doomed]
    assert kept == [tight, loose, never]      # EDF, deadline-less last
    assert doomed.sched_predicted_done > doomed.deadline
    assert doomed.sched_predicted_slack < 0
    assert tight.sched_predicted_slack > 0
    # cumulative accounting: the later admit sees the earlier prompts
    assert loose.sched_predicted_done > tight.sched_predicted_done


def test_slack_policy_backlog_delays_predictions():
    now = 0.0
    r1 = _FakeReq(10, 5, now + 10.0)
    (k0, _) = SlackPolicy().schedule([r1], now, _READY, 0)
    done_no_backlog = r1.sched_predicted_done
    (k1, _) = SlackPolicy().schedule([r1], now, _READY, 500)
    assert r1.sched_predicted_done > done_no_backlog


# --------------------------------------------------------------- planner
def test_calibration_gates():
    assert not StepCalibration().fit_ready
    assert not StepCalibration(
        scale_s_per_byte=1e-9,
        n_decode=MIN_FIT_SAMPLES - 1).fit_ready
    fit = StepCalibration(scale_s_per_byte=1e-9,
                          n_decode=MIN_FIT_SAMPLES)
    assert fit.fit_ready and not fit.admission_ready
    assert _READY.admission_ready
    d = _READY.as_dict()
    assert d["fit_ready"] and d["admission_ready"]


class _FlatCost:
    """Cost model pricing 1 byte per packed token — makes predicted
    wall proportional to planned tokens so the halving loop is exact."""

    def estimate(self, kind, key=None, *, rows, max_rows, pages_touched,
                 tokens):
        return float(tokens), 0.0, "analytic"


class _FixedLog:
    def __init__(self, cal):
        self._cal = cal

    def calibration(self):
        return dict(self._cal)


def _mk_planner(scale, slo_itl_s, dynamic=True, prefill_chunk=16):
    log = _FixedLog({"scale_s_per_byte": scale, "decode_step_s": 0.01,
                     "prefill_s_per_token": 0.001,
                     "n_decode": MIN_FIT_SAMPLES, "n_prefill": 2})
    return StepPlanner(_FlatCost(), log, max_batch=4, token_budget=32,
                       prefill_chunk=prefill_chunk, slo_itl_s=slo_itl_s,
                       dynamic=dynamic)


def test_planner_static_modes_keep_configured_chunk():
    # dynamic=False (fifo), no decode rows, or no pending prompts all
    # yield the static cap — packing identical to the pre-sched engine
    for plan in [
        _mk_planner(1.0, 0.001, dynamic=False).plan(
            n_decode=2, pending=[40], pages=4),
        _mk_planner(1.0, 0.001).plan(n_decode=0, pending=[40], pages=4),
        _mk_planner(1.0, 0.001).plan(n_decode=2, pending=[], pages=4),
    ]:
        assert plan.chunk_cap == 16 and not plan.limited
    # prediction is still made in static mode
    p = _mk_planner(1.0, None, dynamic=False).plan(
        n_decode=2, pending=[40], pages=4)
    assert p.predicted_wall_s > 0


def test_planner_shrinks_chunk_cap_to_fit_itl_slo():
    # scale 1 s/byte, 1 byte/token: step wall == packed tokens.  With 2
    # decode rows an SLO of 6 "seconds" admits 4 prompt tokens → the
    # 16-token cap halves to 4
    planner = _mk_planner(1.0, 6.0)
    plan = planner.plan(n_decode=2, pending=[40], pages=4)
    assert plan.chunk_cap == 4
    assert plan.limited
    assert plan.planned_tokens == 2 + 4
    assert plan.predicted_wall_s <= 6.0
    snap = planner.snapshot()
    assert snap["calibration"]["fit_ready"]
    assert snap["plans"] == 1 and snap["chunk_limited_steps"] == 1


def test_planner_chunk_cap_floors_at_one():
    # impossible SLO: the cap floors at 1 so prefill still progresses
    plan = _mk_planner(1.0, 1e-9).plan(n_decode=2, pending=[40], pages=4)
    assert plan.chunk_cap == 1
    assert plan.planned_tokens == 3


def test_planner_cold_fit_plans_static():
    log = _FixedLog({"scale_s_per_byte": None, "decode_step_s": None,
                     "prefill_s_per_token": None, "n_decode": 0,
                     "n_prefill": 0})
    planner = StepPlanner(_FlatCost(), log, max_batch=4, token_budget=32,
                          prefill_chunk=16, slo_itl_s=0.001, dynamic=True)
    plan = planner.plan(n_decode=2, pending=[40], pages=4)
    assert plan.chunk_cap == 16 and not plan.limited
    assert plan.predicted_wall_s == 0.0     # no prediction while cold


# ----------------------------------------------- engine: stream identity
def test_fifo_core_bitwise_matches_default_core(make_core):
    """sched_policy="fifo" must be byte-identical to a core built
    without any sched argument — same rids, same streams."""
    g = GenerationConfig(max_new_tokens=8, do_sample=True, seed=11)
    outs = []
    for kw in ({}, {"sched_policy": "fifo"}):
        request_mod._rid_counter = itertools.count(7000)
        core = make_core(**kw)
        reqs = [core.submit(_prompt(i, 10), g)[0] for i in range(3)]
        _drive(core, reqs)
        outs.append([r.padded_result() for r in reqs])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_fifo_vs_slack_identical_streams(make_core):
    """The admission policy reorders and interleaves differently but
    NEVER changes a request's tokens: per-row sampling keys are
    fold_in(PRNGKey(seed), rid), so pinned rids ⇒ bitwise streams."""
    g = GenerationConfig(max_new_tokens=8, do_sample=True, seed=5)
    outs = []
    for policy in ("fifo", "slack"):
        request_mod._rid_counter = itertools.count(8000)
        core = make_core(sched_policy=policy, slo_itl_s=10.0)
        _calibrate(core)
        request_mod._rid_counter = itertools.count(8500)
        # mixed deadlines (all generous enough to finish) so the slack
        # run actually reorders: deadline-less first in arrival order
        reqs = [core.submit(_prompt(40 + i, 10), g,
                            timeout_s=(None, 60.0, 30.0, None)[i])[0]
                for i in range(4)]
        _drive(core, reqs)
        assert all(r.state is RequestState.DONE for r in reqs)
        outs.append([r.padded_result() for r in reqs])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_slack_reorders_admission_by_deadline(make_core):
    core = make_core(max_batch=1, sched_policy="slack")
    _calibrate(core, n=1)
    g = GenerationConfig(max_new_tokens=4)
    # saturate the single slot so the next submissions queue up
    (hog,) = core.submit(_prompt(90, 10), GenerationConfig(
        max_new_tokens=16))
    core.run_once()
    late = core.submit(_prompt(91, 10), g, timeout_s=120.0)[0]
    tight = core.submit(_prompt(92, 10), g, timeout_s=30.0)[0]
    _drive(core, [hog, late, tight])
    # EDF: the tighter deadline (submitted later) prefills first
    assert tight.first_token_at < late.first_token_at


def test_predictive_shed_accounting(make_core):
    """A shed request must (a) fail with LoadShedError, (b) bump the
    sched counters, and (c) leak nothing — it never reserved KV, and
    the pool refcounts return to the post-warmup baseline."""
    core = make_core(sched_policy="slack")
    cal = _calibrate(core)
    baseline = core._pool.free_blocks
    # occupy both slots with long decodes so new arrivals must queue
    busy = [core.submit(_prompt(95 + i, 10), GenerationConfig(
        max_new_tokens=24))[0] for i in range(2)]
    core.run_once()
    # deadline tighter than the predicted decode time alone: the
    # prediction says doomed while the deadline itself is still in the
    # future when the next sweep's admission pass runs
    need_s = 24 * cal.decode_step_s
    doomed = core.submit(_prompt(99, 12), GenerationConfig(
        max_new_tokens=24), timeout_s=need_s / 2)[0]
    core.run_once()
    assert doomed.state is RequestState.REJECTED
    with pytest.raises(LoadShedError, match="shed predictively"):
        doomed.result(timeout=1)
    _drive(core, busy)
    snap = core.metrics_snapshot()
    assert snap["sched"]["predictive_sheds"] == 1
    assert snap["sched"]["requests_shed_predicted"] == 1
    assert snap["sched"]["policy"] == "slack"
    assert core._pool.free_blocks == baseline     # nothing leaked
    assert len(core._queue) == 0


def test_cold_slack_never_sheds(make_core):
    """Before the fit is admission-ready the slack policy must behave
    exactly like fifo: nothing shed, everything served."""
    core = make_core(sched_policy="slack")
    assert not core._planner.calibration(refresh=True).admission_ready
    g = GenerationConfig(max_new_tokens=4)
    reqs = [core.submit(_prompt(60 + i, 8), g, timeout_s=60.0)[0]
            for i in range(3)]
    _drive(core, reqs)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert core.metrics_snapshot()["sched"]["predictive_sheds"] == 0


# ------------------------------------------------ engine: observability
def test_steplog_calibration_and_planner_model(make_core):
    core = make_core(sched_policy="fifo")
    g = GenerationConfig(max_new_tokens=MIN_FIT_SAMPLES + 6)
    # two waves: the fit warms during the first and the planner's
    # periodic calibration refresh (every 16 plans) picks it up, so
    # second-wave records carry non-zero predictions
    for wave in range(2):
        reqs = [core.submit(_prompt(30 + 2 * wave + i, 12), g)[0]
                for i in range(2)]
        _drive(core, reqs)
    cal = core.steplog.calibration()
    assert cal["n_decode"] >= MIN_FIT_SAMPLES
    assert cal["scale_s_per_byte"] > 0
    assert cal["decode_step_s"] > 0
    assert cal["prefill_s_per_token"] > 0
    # fifo cores predict too (planner error is reported for both
    # policies) once the fit warms mid-run
    pm = core.steplog.summary()["planner_model"]
    assert pm["n"] > 0
    assert pm["mean_abs_rel_err"] >= 0
    rec = core.steplog.records()[-1]
    assert {"planned_tokens", "planned_chunk_cap",
            "predicted_wall_s"} <= set(rec)


def test_sched_metrics_snapshot_shape(make_core):
    core = make_core(sched_policy="slack", slo_ttft_s=1.0,
                     slo_itl_s=0.5)
    sc = core.metrics_snapshot()["sched"]
    assert sc["policy"] == "slack" and sc["reorders"] is True
    assert sc["slo_ttft_s"] == 1.0 and sc["slo_itl_s"] == 0.5
    assert sc["planner"]["dynamic"] is True
    assert sc["slack_err"]["n"] == 0
    fifo_sc = make_core().metrics_snapshot()["sched"]
    assert fifo_sc["policy"] == "fifo" and fifo_sc["reorders"] is False
    assert fifo_sc["planner"]["dynamic"] is False


def test_slack_err_recorded_on_completion(make_core):
    core = make_core(sched_policy="slack")
    _calibrate(core)
    # keep one slot busy so the scored request spends a sweep queued
    busy = core.submit(_prompt(55, 10), GenerationConfig(
        max_new_tokens=16))[0]
    busy2 = core.submit(_prompt(56, 10), GenerationConfig(
        max_new_tokens=16))[0]
    core.run_once()
    scored = core.submit(_prompt(57, 10), GenerationConfig(
        max_new_tokens=4), timeout_s=120.0)[0]
    _drive(core, [busy, busy2, scored])
    assert scored.sched_predicted_done is not None
    sc = core.metrics_snapshot()["sched"]
    assert sc["slack_err"]["n"] >= 1
    assert sc["slack_err"]["mean_abs_err_s"] >= 0


# ------------------------------------------------------- trace replay
def test_replay_streams_schedule_independent(make_core):
    """Full loop: one recorded trace replayed under fifo and slack —
    per-request token streams must be bitwise identical wherever both
    runs delivered tokens, with zero policy-induced recompiles."""
    from paddle_infer_tpu.observability.compilelog import get_compile_log

    tenants = (
        {"name": "chat", "weight": 2.0, "prompt_len": (4, 10),
         "max_new": (4, 8), "timeout_s": (30.0, 60.0),
         "shared_prefix_len": 0, "cache_salt": None},
        {"name": "batch", "weight": 1.0, "prompt_len": (12, 20),
         "max_new": (6, 10), "timeout_s": None,
         "shared_prefix_len": 4, "cache_salt": "t"},
    )
    events = loadgen.generate_trace(1, 1.0, 10.0, tenants=tenants,
                                    vocab_size=96, do_sample=True)
    assert events, "empty trace"
    streams = {}
    for policy in ("fifo", "slack"):
        request_mod._rid_counter = itertools.count(20_000)
        core = make_core(max_batch=3, sched_policy=policy)
        _calibrate(core)
        request_mod._rid_counter = itertools.count(21_000)
        c0 = get_compile_log().summary()["post_warmup_decode_compiles"]
        # time_scale=0: every arrival is due immediately — replay
        # degenerates to deterministic drive-to-drain
        handles = loadgen.replay(core, events, time_scale=0.0,
                                 timeout_s=120.0)
        assert get_compile_log().summary()[
            "post_warmup_decode_compiles"] == c0
        assert all(r.done for r in handles.values())
        streams[policy] = {i: np.asarray(r.tokens, np.int32)
                           for i, r in handles.items()}
        # replay drained: every page either free or retained by the
        # prefix cache (no slot leaks)
        assert core.active_count == 0 and len(core._queue) == 0
    assert set(streams["fifo"]) == set(streams["slack"])
    for i, a in streams["fifo"].items():
        b = streams["slack"][i]
        n = min(a.size, b.size)
        np.testing.assert_array_equal(a[:n], b[:n])
