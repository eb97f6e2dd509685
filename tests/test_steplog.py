"""Step-level flight recorder (observability/steplog) and the native
Prometheus histograms (observability/histogram) feeding ``GET /steps``
and the ``/metrics`` histogram families."""
import json

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.observability import (StepLog, render_prometheus,
                                            validate_exposition)
from paddle_infer_tpu.observability.histogram import (Histogram,
                                                      log_bounds, quantile)
from paddle_infer_tpu.observability.steplog import SCHEMA_KEYS, StepCostModel


# --------------------------------------------------------------- histogram
def test_log_bounds_are_1_2_5_series():
    bs = log_bounds(1e-3, 1.0)
    assert bs[0] == pytest.approx(1e-3)
    assert bs[-1] == pytest.approx(1.0)
    mantissas = {round(b / (10 ** np.floor(np.log10(b))), 6) for b in bs}
    assert mantissas <= {1.0, 2.0, 5.0}
    assert all(a < b for a, b in zip(bs, bs[1:]))


def test_histogram_cumulative_and_exact_counts():
    h = Histogram(bounds=[0.1, 0.2, 0.5, 1.0])
    samples = [0.05, 0.1, 0.15, 0.3, 0.7, 2.0, 2.0]
    for s in samples:
        h.observe(s)
    snap = h.snapshot()
    # value <= le semantics: 0.1 lands in the 0.1 bucket
    assert [c for _, c in snap["buckets"]] == [2, 3, 4, 5, 7]
    assert snap["buckets"][-1][0] == "+Inf"
    assert snap["count"] == len(h) == 7
    assert snap["sum"] == pytest.approx(sum(samples))
    # cumulative counts never decrease
    cums = [c for _, c in snap["buckets"]]
    assert all(a <= b for a, b in zip(cums, cums[1:]))


def test_histogram_quantile_tracks_numpy_percentile():
    rng = np.random.RandomState(7)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=4000)
    h = Histogram()                      # default 1-2-5 bounds
    for s in samples:
        h.observe(float(s))
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        ref = float(np.percentile(samples, q * 100))
        # bucket resolution is <= 2.5x between bounds; interpolation
        # keeps the estimate within one bucket of the true percentile
        assert est == pytest.approx(ref, rel=1.5), (q, est, ref)


def test_histogram_quantile_edge_cases():
    assert quantile(None, 0.5) is None
    assert quantile({"buckets": [], "sum": 0.0, "count": 0}, 0.5) is None
    h = Histogram(bounds=[1.0, 2.0])
    h.observe(50.0)                      # overflow bucket only
    assert h.quantile(0.99) == pytest.approx(2.0)  # clamps to last finite
    # snapshot round-trips through strict JSON (le "+Inf" is a string)
    json.dumps(h.snapshot())


def test_validate_exposition_histogram_contract():
    ok = ("# TYPE h histogram\n"
          'h_bucket{le="0.1"} 1\nh_bucket{le="+Inf"} 3\n'
          "h_sum 0.5\nh_count 3\n")
    assert validate_exposition(ok) == []
    # non-cumulative buckets
    bad = ok.replace('h_bucket{le="0.1"} 1', 'h_bucket{le="0.1"} 7')
    assert any("cumulative" in p for p in validate_exposition(bad))
    # missing +Inf terminal
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="0.1"} 1\nh_sum 0.5\nh_count 1\n')
    assert any("+Inf" in p for p in validate_exposition(bad))
    # _count disagrees with the +Inf bucket
    bad = ok.replace("h_count 3", "h_count 9")
    assert validate_exposition(bad)
    # bare sample on a histogram-typed family
    bad = ok + "h 1\n"
    assert any("bare" in p for p in validate_exposition(bad))


# ----------------------------------------------------------------- steplog
def test_steplog_schema_defaults_and_rejection():
    sl = StepLog()
    rec = sl.record("decode", wall_s=0.01, decode_rows=2)
    assert set(rec) == set(SCHEMA_KEYS)
    assert rec["seq"] == 1 and rec["kind"] == "decode"
    assert rec["cost_source"] == "none" and rec["bytes_est"] == 0.0
    with pytest.raises(ValueError, match="unknown StepLog fields"):
        sl.record("decode", walls=0.01)


def test_steplog_ring_bound_and_jsonl():
    sl = StepLog(capacity=8)
    for i in range(20):
        sl.record("decode", wall_s=0.001 * (i + 1), bytes_est=1.0)
    assert len(sl) == 8
    recs = sl.records()
    assert [r["seq"] for r in recs] == list(range(13, 21))  # oldest first
    assert len(sl.records(limit=3)) == 3
    assert sl.records(limit=0) == []
    lines = sl.to_jsonl(limit=5).splitlines()
    assert len(lines) == 5
    parsed = [json.loads(ln) for ln in lines]
    assert all(set(p) == set(SCHEMA_KEYS) for p in parsed)
    assert sl.to_jsonl().endswith("\n")
    assert StepLog().to_jsonl() == ""
    s = sl.summary()
    assert s["records"] == 20 and s["ring"] == 8 and s["capacity"] == 8
    assert s["by_kind"] == {"decode": 20}
    assert s["bytes_est_total"] == pytest.approx(20.0)


def test_steplog_model_fit_and_clear():
    sl = StepLog()
    # wall exactly proportional to bytes -> zero error, r == 1
    for b in (1e6, 2e6, 3e6, 5e6):
        sl.record("decode", wall_s=b * 2e-9, bytes_est=b)
    # failed / zero-byte records must not pollute the fit
    sl.record("decode", wall_s=9.9, bytes_est=4e6, failed=True)
    sl.record("decode", wall_s=9.9, bytes_est=0.0)
    m = sl.summary()["decode_model"]
    assert m["n"] == 4
    assert m["scale_s_per_byte"] == pytest.approx(2e-9)
    assert m["mean_abs_rel_err"] == pytest.approx(0.0, abs=1e-9)
    assert m["pearson_r"] == pytest.approx(1.0)
    sl.clear()
    assert len(sl) == 0
    assert sl.summary()["decode_model"]["n"] == 0
    # seq keeps rising across clear() — records stay globally ordered
    assert sl.record("evict")["seq"] > 4


def test_steplog_model_degenerate_cases():
    sl = StepLog()
    sl.record("decode", wall_s=0.01, bytes_est=1e6)
    assert sl.summary()["decode_model"]["scale_s_per_byte"] is None  # n<2
    sl.record("decode", wall_s=0.02, bytes_est=1e6)
    m = sl.summary()["decode_model"]
    assert m["scale_s_per_byte"] is not None
    assert m["pearson_r"] is None        # zero variance in bytes


def test_render_prometheus_steplog_and_device_memory():
    from paddle_infer_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.on_prefill(0.05)
    m.on_tokens(4, itl_s=0.01)
    m.on_step(2.0, active=1, max_batch=2)
    m.on_queue_wait(0.003)
    m.on_completed(0.2)
    sl = StepLog()
    sl.record("decode", wall_s=0.01, bytes_est=1e6, cost_source="analytic")
    sl.record("decode", wall_s=0.03, bytes_est=2e6, cost_source="analytic")
    snap = m.snapshot(steplog=sl.summary(),
                      device_memory={"bytes_in_use": 4096,
                                     "num_allocs": 3})
    text = render_prometheus(snap)
    assert validate_exposition(text) == []
    for fam in ("serving_ttft_seconds", "serving_inter_token_latency_seconds",
                "serving_e2e_latency_seconds", "serving_step_wall_seconds",
                "serving_queue_wait_seconds"):
        assert f"# TYPE {fam} histogram" in text, fam
        assert f'{fam}_bucket{{le="+Inf"}}' in text, fam
    assert 'steplog_records_total{kind="decode"} 2' in text
    assert "steplog_bytes_estimated_total 3e+06" in text
    assert "steplog_model_abs_rel_error" in text
    # byte-valued allocator keys only; counts are not byte gauges
    assert 'device_memory_bytes{kind="bytes_in_use"} 4096' in text
    assert "num_allocs" not in text


# ------------------------------------------------- cost model + integration
@pytest.fixture(scope="module")
def core():
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import EngineCore

    pit.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    c = EngineCore(PagedGenerationEngine(model, page_size=8),
                   max_batch=2)
    yield c
    c.close()


def _run(core, reqs, max_iters=200):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def test_cost_model_estimates(core):
    cm = StepCostModel(core._engine, core._pool)
    # 2 layers * (K+V) * 4 heads * page 8 * head_dim 8 * fp32
    assert cm.page_kv_bytes == pytest.approx(2 * 2 * 4 * 8 * 8 * 4)
    b, f, src = cm.estimate("evict", pages_touched=3)
    assert (b, f, src) == (3 * cm.page_kv_bytes, 0.0, "analytic")
    b, f, src = cm.estimate("page_copy", pages_touched=1)
    assert (b, src) == (2 * cm.page_kv_bytes, "analytic")
    # no program key -> analytic roofline, still nonzero
    b, f, src = cm.estimate("decode", None, rows=2, max_rows=2,
                            pages_touched=4, tokens=8)
    assert src == "analytic" and b > 0 and f > 0


def test_steplog_records_every_bench_style_step(core):
    """Acceptance: a bench-style serving run produces one record per
    step with nonzero bytes_est, the decode model fits, and the whole
    snapshot renders to a valid exposition with >= 5 histogram
    families."""
    from paddle_infer_tpu.inference.generation import GenerationConfig

    rng = np.random.RandomState(0)
    g = GenerationConfig(max_new_tokens=6)
    for n in (8, 16, 8, 16):
        prompt = rng.randint(0, 96, (n,)).astype(np.int32)
        (r,) = core.submit(prompt, g)
        _run(core, [r])
    recs = core.steplog.records()
    kinds = {r["kind"] for r in recs}
    assert {"prefill", "decode", "evict"} <= kinds
    for r in recs:
        if r["kind"] in ("prefill", "decode"):
            assert r["bytes_est"] > 0, r
            assert r["flops_est"] > 0, r
            assert r["cost_source"] in ("xla+pages", "analytic")
        if r["kind"] == "decode":
            assert r["dispatch_s"] <= r["wall_s"] + 1e-9
            # a mixed step emits one token per decode row
            assert r["chunk_steps"] == 1
    assert {r["kernel"] for r in recs
            if r["kind"] in ("prefill", "decode")} == {"ragged"}
    model = core.steplog.summary()["decode_model"]
    assert model["n"] >= 2 and model["scale_s_per_byte"] > 0
    assert model["mean_abs_rel_err"] is not None

    snap = core.metrics_snapshot()
    assert snap["steplog"]["records"] == len(recs)
    hists = snap["histograms"]
    assert {"ttft", "itl", "e2e", "step_wall", "queue_wait"} <= set(hists)
    assert all(h["count"] > 0 for k, h in hists.items()
               if k in ("ttft", "e2e", "step_wall", "queue_wait"))
    text = render_prometheus(snap)
    assert validate_exposition(text) == []
    n_hist_families = sum(
        1 for ln in text.splitlines()
        if ln.startswith("# TYPE") and ln.endswith(" histogram"))
    assert n_hist_families >= 5
