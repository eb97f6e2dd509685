#!/usr/bin/env python
"""Metrics-exposition CI check.

Three sync points must agree or dashboards silently break:

  1. the Prometheus text the server renders must be syntactically valid
     (metric/label name syntax, typed samples, no duplicate series —
     label-sets compare order-insensitively, and OpenMetrics exemplar
     suffixes are syntax-checked too);
  2. the renderer source and the metric catalog in
     docs/OBSERVABILITY.md must agree — checked by tpulint's
     metric-sync rule (paddle_infer_tpu/analysis/rules/metric_sync.py)
     so each drift is reported with its file:line (the ``w.family``
     call or the catalog table row), not as a bare name-set diff;
  3. every latency-series key in ``ServingMetrics.snapshot()`` must
     have a renderer mapping (``prometheus.SERIES_FAMILIES`` for the
     stat-gauge series, ``prometheus.HISTOGRAM_SERIES`` for the ones
     whose exposure moved to native histogram families) — a new series
     added to the snapshot but not the renderer would be invisible to
     scrapers.  Histogram families must count once: ``_bucket``/
     ``_sum``/``_count`` are samples of the one typed family, never
     families of their own.

Runs on a FABRICATED snapshot (every counter/series/gauge populated —
including multi-tenant journey accounting, fleet per-replica stats and
the router section, so every LABELED multi-series family renders with
several label values — plus a compile-log summary with a recompile) so
the exposition exercises every family the renderer can emit.  A labeled
family still counts ONCE in the 3-way sync: one ``w.family`` call, one
TYPE line, one catalog row, however many label-sets it carries.
Exit 0 = all checks pass.

Usage:
  env PYTHONPATH=. python tools/check_metrics.py [--docs PATH]
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def fabricated_exposition():
    """(snapshot, compile_summary, rendered_text) with every family the
    renderer can emit populated."""
    from paddle_infer_tpu.observability.compilelog import CompileLog
    from paddle_infer_tpu.observability.prometheus import render_prometheus
    from paddle_infer_tpu.serving.metrics import ServingMetrics

    from paddle_infer_tpu.observability.steplog import StepLog

    steplog = StepLog()
    steplog.record("prefill", wall_s=0.08, dispatch_s=0.07,
                   bytes_est=2.0e6, flops_est=5.0e6,
                   cost_source="xla+pages", emitted_tokens=1,
                   kernel="ragged")
    steplog.record("decode", wall_s=0.010, dispatch_s=0.008,
                   bytes_est=1.0e6, flops_est=3.0e6,
                   cost_source="xla+pages", decode_rows=2, chunk_steps=4,
                   kernel="ragged")
    steplog.record("decode", wall_s=0.021, dispatch_s=0.017,
                   bytes_est=2.1e6, flops_est=6.0e6,
                   cost_source="xla+pages", decode_rows=4, chunk_steps=4,
                   kernel="ragged")
    steplog.record("mixed", wall_s=0.015, dispatch_s=0.012,
                   bytes_est=1.6e6, flops_est=4.5e6,
                   ici_bytes_est=4.0e4, ici_bytes_saved_est=1.2e5,
                   cost_source="xla+pages", decode_rows=3,
                   prefill_chunk_tokens=16, emitted_tokens=4,
                   planned_tokens=19, planned_chunk_cap=16,
                   predicted_wall_s=0.014, kernel="ragged")
    steplog.record("mixed", wall_s=0.017, dispatch_s=0.013,
                   bytes_est=1.8e6, flops_est=5.0e6,
                   cost_source="xla+pages", decode_rows=3,
                   emitted_tokens=7, draft_tokens=6, draft_accepted=4,
                   spec_rows=2, kernel="ragged")
    steplog.record("mixed", wall_s=0.016, dispatch_s=0.012,
                   bytes_est=1.7e6, flops_est=4.8e6,
                   ici_bytes_est=9.0e4, ici_bytes_saved_est=5.0e4,
                   cost_source="xla+pages", decode_rows=3,
                   emitted_tokens=3, moe_tokens_routed=24,
                   moe_tokens_dropped=2, moe_aux_loss=1.02,
                   adapter_rows=2, grammar_rows=2, masked_tokens=150,
                   kernel="ragged")
    steplog.record("evict", pages_freed=3, bytes_est=3.0e5,
                   cost_source="analytic")

    m = ServingMetrics()
    m.on_submitted(4)
    m.on_rejected()
    m.on_rejected_queue_full()
    m.on_deadline()
    m.on_failed()
    m.on_prefill(0.050)
    m.on_prefill(0.071)
    m.on_tokens(4, itl_s=0.010)
    m.on_tokens(3, itl_s=0.012)
    m.on_step(3.5, active=2, max_batch=4)
    m.on_spec(rows=2, proposed=6, accepted=4)
    m.on_moe([14, 6, 3, 1], dropped=2, aux_loss=1.02)
    m.on_queue_wait(0.004)
    m.on_queue_wait(0.020)
    m.on_completed(0.5)
    m.on_engine_restart()
    m.on_retry(2)
    m.on_watchdog_trip()
    m.on_quarantined()
    m.on_shed()
    m.on_predictive_shed(2)
    m.on_loop_exception()
    # per-tenant SLO accounting (journey plane): two named tenants plus
    # the None->"default" mapping so every tenant_* family renders as a
    # labeled multi-series family with journey_id exemplars
    m.on_journey(tenant="gold", e2e_s=0.42, tokens=64, attained=True,
                 buckets={"queue_wait": 0.01, "sched_reorder": 0.005,
                          "prefill_compute": 0.15,
                          "decode_compute": 0.22, "parked": 0.03,
                          "other": 0.005},
                 coverage=0.988, journey_id="j101")
    m.on_journey(tenant="gold", e2e_s=1.31, tokens=128, attained=False,
                 buckets={"queue_wait": 0.2, "prefill_compute": 0.4,
                          "decode_compute": 0.66, "handoff": 0.03,
                          "other": 0.02},
                 coverage=0.985, journey_id="j102")
    m.on_journey(tenant=None, e2e_s=0.09, tokens=16, attained=True,
                 buckets={"queue_wait": 0.01, "prefill_compute": 0.03,
                          "decode_compute": 0.05},
                 coverage=1.0, journey_id="j103")
    snap = m.snapshot(queue_depth=1, active=2, max_batch=4,
                      # JourneyStore.summary() shape (fleet-wide
                      # journey aggregates)
                      journeys={"count": 3, "hops_total": 2,
                                "attribution_coverage": 0.991,
                                "bucket_seconds": {
                                    "queue_wait": 0.22,
                                    "sched_reorder": 0.005,
                                    "adapter_wait": 0.0,
                                    "prefill_compute": 0.58,
                                    "handoff": 0.03, "parked": 0.03,
                                    "resume": 0.0,
                                    "decode_compute": 0.93,
                                    "detok": 0.002,
                                    "replay_retry": 0.0,
                                    "other": 0.025},
                                "live": 1},
                      # EngineCore._sched_snapshot() shape: policy +
                      # planner + predicted-vs-actual slack error
                      sched={"policy": "slack", "reorders": True,
                             "slo_ttft_s": 0.5, "slo_itl_s": 0.05,
                             "predictive_sheds": 2,
                             "last_min_slack_s": 0.31,
                             "slack_err": {"n": 3,
                                           "mean_abs_err_s": 0.04,
                                           "max_abs_err_s": 0.09},
                             "planner": {"plans": 40,
                                         "chunk_limited_steps": 5,
                                         "dynamic": True,
                                         "slo_itl_s": 0.05,
                                         "token_budget": 64,
                                         "prefill_chunk": 16,
                                         "calibration": {
                                             "fit_ready": True,
                                             "admission_ready": True,
                                             "scale_s_per_byte": 9e-9,
                                             "decode_step_s": 0.015,
                                             "prefill_s_per_token":
                                                 9.4e-4,
                                             "n_decode": 12,
                                             "n_prefill": 3}}},
                      resilience={"health_state": "degraded",
                                  "health_code": 1, "draining": False,
                                  "effective_max_batch": 2,
                                  "faults_injected": {"decode.step": 3,
                                                      "kv.alloc": 1}},
                      kv_pool={"total_blocks": 32, "used_blocks": 8,
                               "free_blocks": 24, "occupancy": 0.25,
                               "headroom_pages": 6},
                      kv_quant={"kv_dtype": "int8",
                                "bytes_per_page": 8256,
                                "fp_bytes_per_page": 32768,
                                "scale_bytes_per_page": 64,
                                "resident_page_ratio": 3.97},
                      weight_only={"layers": 8,
                                   "algos": ["weight_only_int8"],
                                   "qweight_bytes": 5.4e6,
                                   "fp_equiv_bytes": 2.1e7,
                                   "hbm_traffic_ratio": 0.257},
                      prefix_cache={"queries": 6, "hits": 4,
                                    "hit_rate": 4 / 6, "peeks": 12,
                                    "cached_tokens": 96,
                                    "prompt_tokens": 160,
                                    "token_ratio": 0.6, "inserts": 5,
                                    "evicted_blocks": 2, "cow_copies": 1,
                                    "cached_blocks": 7, "nodes": 6},
                      steplog=steplog.summary(),
                      moe={"num_experts": 4, "top_k": 2,
                           "gate": "gshard", "capacity_factor": 1.0,
                           "capacity": 8, "ep": 2,
                           "algo": "weight_only_int8", "layers": 2,
                           "expert_hbm_bytes": 3.2e6},
                      # AdapterCache.summary() shape (multi-LoRA plane)
                      adapters={"slots": 8, "rank": 8, "layers": 8,
                                "pool_hbm_bytes": 1.6e6, "resident": 5,
                                "pinned": 2, "hits": 21, "misses": 9,
                                "hit_rate": 0.7, "uploads": 9,
                                "upload_bytes": 7.3e5, "evictions": 3,
                                "store": {"adapters": 12, "rank": 8,
                                          "page_bytes": 65536,
                                          "pages_total": 4096,
                                          "pages_used": 24,
                                          "bytes_used": 1.5e6}},
                      # EngineCore._structured_snapshot() shape
                      # (constrained decoding: grammar cache + tallies)
                      structured={"active_rows": 2, "entries": 3,
                                  "hits": 11, "misses": 3,
                                  "compile_seconds": 0.021,
                                  "vocab_size": 96, "violations": 0,
                                  "incomplete": 1, "rejected": 2},
                      # HostKVTier.summary() shape (park, don't drop)
                      kv_tier={"parked_requests": 2,
                               "host_pages_total": 256,
                               "host_pages_resident": 18,
                               "host_pages_peak": 40,
                               "demoted_blocks": 6,
                               "parks_total": 9,
                               "resumes_total": 7,
                               "predictive_parks_total": 3,
                               "demotes_total": 11,
                               "promotes_total": 5,
                               "demoted_evicted_total": 1,
                               "swap_out_bytes_total": 2.4e6,
                               "swap_in_bytes_total": 1.9e6,
                               "swap_retries_total": 2,
                               "swap_fails_total": 1,
                               "park_watermark": 0.95,
                               "resume_watermark": 0.70},
                      device_memory={"bytes_in_use": 1 << 20,
                                     "peak_bytes_in_use": 1 << 21,
                                     "bytes_limit": 1 << 30,
                                     "largest_alloc_size": 1 << 18,
                                     "num_allocs": 12},
                      sharding={"mesh_axes": {"mp": 2, "dp": 2, "ep": 2},
                                "devices": 8,
                                "params_total": 26,
                                "sharded_params": 16,
                                "replicated_params": 1,
                                "replicated_names": ["lm_head.weight"],
                                "quantized_allreduce": "int8",
                                "collectives": {
                                    "calls": 9,
                                    "by_op_dtype": {
                                        "mp_allreduce": {"int8": 5.1e5},
                                        "ep_alltoall": {"int8": 3.2e5},
                                        "all_gather": {"float32": 2.0e5}},
                                    "bytes_total": 7.1e5,
                                    "bytes_saved_total": 1.4e6}})

    # fleet router section (FleetRouter.snapshot() shape): two replicas
    # so every per-replica family renders multiple label values
    snap["router"] = {
        "replicas": [
            {"name": "prefill0", "role": "prefill",
             "configured_role": "prefill",
             "health": {"state": "healthy", "code": 0, "serving": True,
                        "transitions": 0},
             "active": 1, "queued": 2,
             "predicted_load_bytes": 2.5e6, "dispatched": 9,
             "affinity_hits": 4, "handoffs_out": 3, "handoffs_in": 0,
             "role_flips": 0},
            {"name": "decode1", "role": "decode",
             "configured_role": "mixed",
             "health": {"state": "draining", "code": 2,
                        "serving": False, "transitions": 1},
             "active": 2, "queued": 0,
             "predicted_load_bytes": 1.1e6, "dispatched": 14,
             "affinity_hits": 2, "handoffs_out": 0, "handoffs_in": 3,
             "role_flips": 1},
        ],
        "dispatched": 23, "affinity_hits": 6,
        "affinity_hit_rate": 6 / 23, "handoffs": 3, "requeued": 2,
        "no_replica_rejects": 1, "pending_handoffs": 1, "inflight": 3,
        "prefill_threshold": 25,
        "shadow": {"replicas": 2, "nodes": 11},
        "elastic": {"prefill_fraction": 0.41, "window": 12,
                    "high": 0.65, "low": 0.25},
    }

    # fleet-mode per-replica key stats (tools/serve.py /metrics builds
    # this in fleet mode): every fleet_replica_* family renders with
    # two replica label values
    snap["fleet"] = {"replicas": [
        {"replica": "prefill0", "role": "prefill", "submitted": 9,
         "completed": 7, "tokens_generated": 310, "queued": 2,
         "active": 1},
        {"replica": "decode1", "role": "decode", "submitted": 14,
         "completed": 14, "tokens_generated": 702, "queued": 0,
         "active": 2},
    ]}

    # local CompileLog (not the process singleton): one page copy, one
    # warmed step, one post-warmup recompile so the recompile/storm
    # families render with non-trivial values
    logging.getLogger("paddle_infer_tpu.observability").disabled = True
    try:
        log = CompileLog()
        dkey = ("serve-step", 4, 4, 8, 33)
        log.record("serving-page-copy", ("serve-page-copy", 33),
                   (((1,), "int32"),), 0.25)
        log.record("serving-decode", dkey, (((4,), "int32"),), 0.40)
        log.mark_warm("serving-decode", dkey)
        log.record("serving-decode", dkey, (((4,), "int32"),), 0.40)
        summary = log.summary()
    finally:
        logging.getLogger("paddle_infer_tpu.observability").disabled = False
    return snap, summary, render_prometheus(snap, summary)


def metric_sync_problems(docs_path: str):
    """Code ↔ docs drift via tpulint's metric-sync rule: each problem
    carries the file:line of the offending ``w.family`` call or catalog
    table row (headingless docs fall back to every ``| `name` |``
    row — the rule handles that too)."""
    from paddle_infer_tpu.analysis import Analyzer
    from paddle_infer_tpu.analysis.rules import MetricSyncRule

    analyzer = Analyzer(
        [MetricSyncRule()], root=ROOT,
        config={"metric_docs": os.path.abspath(docs_path)})
    findings, _ = analyzer.run(
        [os.path.join(ROOT, "paddle_infer_tpu", "observability"),
         os.path.join(ROOT, "paddle_infer_tpu", "serving")])
    return [f"{f.path}:{f.line}: {f.message}" for f in findings]


def run_checks(docs_path: str):
    from paddle_infer_tpu.observability.prometheus import (
        HISTOGRAM_SERIES, SERIES_FAMILIES, family_names,
        validate_exposition)

    problems = []
    snap, summary, text = fabricated_exposition()

    problems += validate_exposition(text)

    families = family_names(text)
    if len(set(families)) != len(families):
        problems.append("duplicate TYPE declarations in exposition")
    # count-once: a histogram's _bucket/_sum/_count are samples, not
    # families — a TYPE line for "<family>_bucket" (etc.) when
    # "<family>" is TYPE'd histogram means the same metric counts
    # twice.  (Stat-gauge series legitimately ship a separate
    # "<family>_count" gauge family, so only histogram bases count.)
    kinds = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) >= 4:
                kinds[parts[2]] = parts[3]
    for fam in families:
        for suffix in ("_bucket", "_sum", "_count"):
            if fam.endswith(suffix) \
                    and kinds.get(fam[:-len(suffix)]) == "histogram":
                problems.append(
                    f"family {fam!r} shadows histogram family "
                    f"{fam[:-len(suffix)]!r} — suffixed names are "
                    "samples, not families")
    problems += metric_sync_problems(docs_path)

    # snapshot <-> renderer mapping: every reservoir series in the
    # snapshot must be rendered either as a stat gauge
    # (SERIES_FAMILIES) or as a native histogram (HISTOGRAM_SERIES)
    for key, val in snap.items():
        if isinstance(val, dict) and "p50_recent" in val \
                and key not in SERIES_FAMILIES \
                and key not in HISTOGRAM_SERIES:
            problems.append(f"snapshot series {key!r} has no renderer "
                            "mapping in prometheus.SERIES_FAMILIES / "
                            "HISTOGRAM_SERIES")
    for key in SERIES_FAMILIES:
        if key not in snap:
            problems.append(f"SERIES_FAMILIES key {key!r} absent from "
                            "ServingMetrics.snapshot()")
    hist_snap = snap.get("histograms") or {}
    for key, hist_key in HISTOGRAM_SERIES.items():
        if key not in snap:
            problems.append(f"HISTOGRAM_SERIES key {key!r} absent from "
                            "ServingMetrics.snapshot()")
        if hist_key not in hist_snap:
            problems.append(f"HISTOGRAM_SERIES target {hist_key!r} "
                            "absent from snapshot['histograms']")
    return problems, len(families)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs",
                    default=os.path.join(ROOT, "docs", "OBSERVABILITY.md"))
    args = ap.parse_args(argv)
    problems, n_families = run_checks(args.docs)
    if problems:
        for p in problems:
            print(f"FAIL: {p}")
        return 1
    print(f"metrics exposition OK: {n_families} families valid and "
          f"in sync with {os.path.relpath(args.docs, ROOT)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
