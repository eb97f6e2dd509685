"""CI tooling parity (SURVEY §2.13): API signature guard
(API.spec + check_api_compatible analog) and the CrossStackProfiler
trace merger."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


def _env():
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    return env


def test_api_spec_check_passes_against_committed():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "api_spec.py"),
         "--check"], capture_output=True, text=True, env=_env(),
        timeout=600)
    assert r.returncode == 0, r.stderr[-800:]
    assert "API surface stable" in r.stdout


def test_api_spec_detects_drift(tmp_path):
    import api_spec

    spec = api_spec.collect()
    assert "paddle_infer_tpu.sequence.sequence_pad" in spec
    assert any(k.startswith("paddle_infer_tpu.models.LlamaForCausalLM")
               for k in spec)
    # simulate a removed + changed symbol
    old = dict(spec)
    k = "paddle_infer_tpu.sequence.sequence_pad"
    old["paddle_infer_tpu.gone_symbol"] = "(x)"
    old[k] = "(totally, different)"
    removed = sorted(set(old) - set(spec))
    changed = [kk for kk in set(old) & set(spec)
               if old[kk].strip() != spec[kk].strip()]
    assert removed == ["paddle_infer_tpu.gone_symbol"]
    assert k in changed


def test_merge_profiles(tmp_path):
    import merge_profiles

    a = tmp_path / "host0.json"
    b = tmp_path / "host1.json"
    a.write_text(json.dumps({"traceEvents": [
        {"name": "step", "ph": "X", "pid": 1, "tid": 1, "ts": 0,
         "dur": 5}]}))
    b.write_text(json.dumps([
        {"name": "step", "ph": "X", "pid": 1, "tid": 1, "ts": 2,
         "dur": 5}]))
    out = merge_profiles.merge([str(a), str(b)])
    evs = out["traceEvents"]
    names = [e for e in evs if e.get("ph") == "M"]
    assert {n["args"]["name"] for n in names} == {"host0/pid1",
                                                 "host1/pid1"}
    xs = [e for e in evs if e.get("ph") == "X"]
    assert len({e["pid"] for e in xs}) == 2     # distinct row groups


def test_check_metrics_passes():
    """The Prometheus exposition must validate and stay in sync with
    the docs/OBSERVABILITY.md metric catalog."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_metrics.py")],
        capture_output=True, text=True, env=_env(), timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    assert "metrics exposition OK" in r.stdout


def test_check_metrics_detects_stale_docs(tmp_path):
    """A catalog entry the renderer doesn't emit (or a family the docs
    don't list) must fail the check."""
    import check_metrics

    docs = tmp_path / "OBS.md"
    docs.write_text("| `serving_queue_depth` | gauge | requests | q |\n"
                    "| `made_up_family` | gauge | x | stale |\n")
    problems, _ = check_metrics.run_checks(str(docs))
    assert any("made_up_family" in p and "not emitted" in p
               for p in problems)
    assert any("missing from the catalog" in p for p in problems)


def test_check_metrics_covers_moe_families():
    """The MoE serving families must be exercised by the fabricated
    snapshot (3-way sync: renderer ↔ docs catalog ↔ check_metrics) —
    a moe family dropped from any leg fails here, not on a dashboard."""
    import check_metrics

    _, _, text = check_metrics.fabricated_exposition()
    for fam in ("moe_info", "moe_expert_tokens_total",
                "moe_tokens_dropped_total", "moe_utilization_skew",
                "steplog_moe_tokens_routed_total"):
        assert f"# TYPE {fam} " in text, f"{fam} not rendered"
    problems, _ = check_metrics.run_checks(
        os.path.join(ROOT, "docs", "OBSERVABILITY.md"))
    assert problems == []


def test_check_metrics_covers_sched_families():
    """The SLO-scheduler families must be exercised by the fabricated
    snapshot (3-way sync: renderer ↔ docs catalog ↔ check_metrics)."""
    import check_metrics

    _, _, text = check_metrics.fabricated_exposition()
    for fam in ("sched_policy_info", "sched_predictive_sheds_total",
                "sched_planner_plans_total",
                "sched_planner_chunk_limited_total",
                "sched_planner_pred_wall_abs_rel_err",
                "sched_slack_pred_err_seconds",
                "sched_last_min_slack_seconds"):
        assert f"# TYPE {fam} " in text, f"{fam} not rendered"
    problems, _ = check_metrics.run_checks(
        os.path.join(ROOT, "docs", "OBSERVABILITY.md"))
    assert problems == []


def test_check_metrics_covers_kv_tier_families():
    """The host-KV-tier families must be exercised by the fabricated
    snapshot (3-way sync: renderer ↔ docs catalog ↔ check_metrics)."""
    import check_metrics

    _, _, text = check_metrics.fabricated_exposition()
    for fam in ("kv_tier_parked_requests", "kv_tier_host_pages",
                "kv_tier_demoted_blocks", "kv_tier_parks_total",
                "kv_tier_predictive_parks_total",
                "kv_tier_resumes_total", "kv_tier_demotes_total",
                "kv_tier_promotes_total",
                "kv_tier_swap_out_bytes_total",
                "kv_tier_swap_in_bytes_total",
                "kv_tier_swap_retries_total",
                "kv_tier_swap_fails_total"):
        assert f"# TYPE {fam} " in text, f"{fam} not rendered"
    problems, _ = check_metrics.run_checks(
        os.path.join(ROOT, "docs", "OBSERVABILITY.md"))
    assert problems == []


def test_check_metrics_covers_journey_families():
    """The journey/tenant/fleet families must be exercised by the
    fabricated snapshot (3-way sync: renderer ↔ docs catalog ↔
    check_metrics), including the labeled multi-series ones."""
    import check_metrics

    _, _, text = check_metrics.fabricated_exposition()
    for fam in ("journeys_total", "journey_hops_total",
                "journey_live_requests",
                "journey_attribution_coverage",
                "journey_attribution_seconds_total",
                "tenant_requests_total", "tenant_slo_attained_total",
                "tenant_slo_attainment", "tenant_tokens_total",
                "tenant_parked_seconds_total", "tenant_e2e_seconds",
                "tenant_attribution_seconds_total",
                "fleet_replica_submitted_total",
                "fleet_replica_completed_total",
                "fleet_replica_tokens_total",
                "fleet_replica_queue_depth",
                "fleet_replica_active_requests"):
        assert f"# TYPE {fam} " in text, f"{fam} not rendered"
    # the fabricated snapshot carries a journey_id exemplar on the
    # tenant e2e histogram; it must survive rendering
    assert '# {journey_id="' in text
    problems, _ = check_metrics.run_checks(
        os.path.join(ROOT, "docs", "OBSERVABILITY.md"))
    assert problems == []


def test_validator_labeled_series_dedup():
    """Duplicate label-sets on one family are rejected — including
    when the duplicate permutes label ORDER — while genuinely distinct
    label-sets pass."""
    from paddle_infer_tpu.observability.prometheus import \
        validate_exposition

    ok = ('# TYPE tenant_requests_total counter\n'
          'tenant_requests_total{tenant="gold"} 3\n'
          'tenant_requests_total{tenant="free"} 9\n')
    assert validate_exposition(ok) == []

    dup = ('# TYPE tenant_requests_total counter\n'
           'tenant_requests_total{tenant="gold"} 3\n'
           'tenant_requests_total{tenant="gold"} 4\n')
    assert any("duplicate series" in p for p in validate_exposition(dup))

    reordered = (
        '# TYPE j_seconds_total counter\n'
        'j_seconds_total{tenant="gold",bucket="decode_compute"} 1.5\n'
        'j_seconds_total{bucket="decode_compute",tenant="gold"} 2.5\n')
    assert any("duplicate series" in p
               for p in validate_exposition(reordered))


def test_validator_exemplars():
    """OpenMetrics exemplar suffixes are tolerated and syntax-checked:
    a well-formed one passes, malformed labels or values fail."""
    from paddle_infer_tpu.observability.prometheus import \
        validate_exposition

    good = ('# TYPE tenant_e2e_seconds histogram\n'
            'tenant_e2e_seconds_bucket{le="1",tenant="gold"} 2'
            ' # {journey_id="j42"} 0.73\n'
            'tenant_e2e_seconds_bucket{le="+Inf",tenant="gold"} 2\n'
            'tenant_e2e_seconds_sum{tenant="gold"} 1.4\n'
            'tenant_e2e_seconds_count{tenant="gold"} 2\n')
    assert validate_exposition(good) == []

    bad_label = ('# TYPE x_total counter\n'
                 'x_total 3 # {9bad="j42"} 0.73\n')
    assert any("bad exemplar label" in p
               for p in validate_exposition(bad_label))

    bad_value = ('# TYPE x_total counter\n'
                 'x_total 3 # {journey_id="j42"} notanumber\n')
    assert any("bad exemplar value" in p
               for p in validate_exposition(bad_value))

    malformed = ('# TYPE x_total counter\n'
                 'x_total 3 # journey_id="j42" 0.73\n')
    assert any("malformed exemplar" in p
               for p in validate_exposition(malformed))


def test_bench_diff_kv_tier_directions():
    """kv_tier keys carry a direction: goodput/parks/resumes up, sheds
    and abandoned swaps down, peak residency neutral."""
    import bench_diff

    assert bench_diff._direction("goodput_batch_tier") == 1
    assert bench_diff._direction("parks") == 1
    assert bench_diff._direction("resumes") == 1
    assert bench_diff._direction("sheds_tier") == -1
    assert bench_diff._direction("swap_fails") == -1
    assert bench_diff._direction("host_pages_peak") == 0


def test_bench_diff_multi_tenant_directions():
    """multi_tenant keys carry a direction: attainment/goodput up,
    shed rate and deadline misses down, planner diagnostics neutral."""
    import bench_diff

    assert bench_diff._direction("slo_attainment_slack") == 1
    assert bench_diff._direction("goodput_tok_per_s_fifo") == 1
    assert bench_diff._direction("shed_rate_slack") == -1
    assert bench_diff._direction("deadline_misses_fifo") == -1
    assert bench_diff._direction("planner_chunk_limited") == 0


def test_bench_diff_journey_directions():
    """journey-plane keys carry a direction: attribution coverage and
    per-tenant attainment up, parked seconds down."""
    import bench_diff

    assert bench_diff._direction("attribution_coverage") == 1
    assert bench_diff._direction("tenant_gold_attainment") == 1
    assert bench_diff._direction("tenant_gold_parked_seconds") == -1


@pytest.mark.slow
def test_moe_bench_child_imports_clean_without_mesh():
    """tools/bench_moe_child.py must import and fail soft on a
    single-device backend (CPU fallback prints a JSON error line, no
    traceback) — the bench parent relies on that contract."""
    env = _env()
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "bench_moe_child.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 1, r.stdout + r.stderr[-800:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert "devices" in out["error"]


def test_bench_diff_flags_regressions(tmp_path):
    """tools/bench_diff.py: direction-aware >10% regressions exit
    nonzero; improvements and unknown-direction metrics never do."""
    import bench_diff

    old = {"parsed": {"continuous_tokens_per_s": 100.0,
                      "ttft_p99_s": 0.10, "speedup": 2.0,
                      "clients": 8, "bench_wall_s": 30.0}}
    new_bad = {"parsed": {"continuous_tokens_per_s": 80.0,   # -20% thpt
                          "ttft_p99_s": 0.15,                # +50% lat
                          "speedup": 2.1, "clients": 8,
                          "bench_wall_s": 400.0}}            # skipped
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(old))
    b.write_text(json.dumps(new_bad))
    assert bench_diff.main([str(a), str(b)]) == 1
    res = bench_diff.diff(old["parsed"], new_bad["parsed"])
    flagged = {r[0] for r in res["regressions"]}
    assert flagged == {"continuous_tokens_per_s", "ttft_p99_s"}
    assert "bench_wall_s" not in {r[0] for r in res["rows"]}
    # same numbers both sides -> clean exit; small drift under the
    # threshold too
    assert bench_diff.main([str(a), str(a)]) == 0
    assert bench_diff.diff(old["parsed"], old["parsed"])["regressions"] \
        == []
    near = {"parsed": dict(old["parsed"],
                           continuous_tokens_per_s=95.0)}    # -5% < 10%
    b.write_text(json.dumps(near))
    assert bench_diff.main([str(a), str(b)]) == 0
    # tighter threshold flips it
    assert bench_diff.main([str(a), str(b), "--threshold", "0.02"]) == 1
    # a metric that disappeared is reported but not fatal
    res = bench_diff.diff(old["parsed"], {"clients": 8})
    assert "ttft_p99_s" in res["removed"]


def test_bench_needs_a_tpu():
    """bench.py is one process that needs the chip: on any other backend
    it stops before measuring, with the device it found in the message."""
    sys.path.insert(0, ROOT)
    import bench

    with pytest.raises(SystemExit, match="needs a TPU; JAX found cpu"):
        bench._require_tpu()


def test_bench_unknown_device_kind_has_no_peak(monkeypatch):
    """A device that is not in the peaks table is an error, never a
    default: an MFU divided by an assumed peak is not a measurement."""
    sys.path.insert(0, ROOT)
    import types

    import jax

    import bench

    known = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [known])
    assert bench._peak_flops() == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [unknown])
    with pytest.raises(KeyError, match="TPU v99"):
        bench._peak_flops()
    assert "cpu" not in bench.PEAK_BF16_FLOPS


def test_tpulint_repo_clean():
    """The tpulint gate: the shipped tree must analyze clean — zero
    non-baselined findings across every rule."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--json"], capture_output=True, text=True, env=_env(),
        timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    rep = json.loads(r.stdout)
    assert rep["new"] == []
    assert rep["files"] > 100          # really walked the package
    assert len(rep["rules"]) == 11


def test_faultplane_sites_documented():
    """Every fault-injection site the plane exposes must be documented
    (backticked) in docs/SERVING.md's fault-tolerance section — the
    chaos schedule is part of the operator contract."""
    from paddle_infer_tpu.serving.resilience import SITES

    assert SITES                        # the plane exports its site list
    with open(os.path.join(ROOT, "docs", "SERVING.md")) as f:
        doc = f.read()
    missing = [s for s in SITES if f"`{s}`" not in doc]
    assert not missing, f"undocumented fault sites: {missing}"


def test_tpulint_resilience_tree_clean():
    """The new resilience plane must gate clean on its own — zero
    findings, no baseline entries hiding anything."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--json", os.path.join(ROOT, "paddle_infer_tpu", "serving",
                                "resilience")],
        capture_output=True, text=True, env=_env(), timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    rep = json.loads(r.stdout)
    assert rep["new"] == []
    assert rep["baselined"] == []       # clean outright, not baselined
    assert rep["files"] >= 4            # __init__, faultplane, health, sup


def test_tpulint_lock_graph_gate():
    """The lock-graph gate: zero unsuppressed cycles, zero
    blocking-under-lock over serving/, and a graph byte-identical to
    the committed baseline (drift means a concurrency-relevant change
    shipped without re-reviewing the lock order)."""
    def run():
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
             "--lock-graph"], capture_output=True, text=True,
            env=_env(), timeout=600)
        return r, json.loads(r.stdout)

    r, rep = run()
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    assert rep["exit"] == 0 and rep["drift"] == []
    assert rep["findings"] == []
    g = rep["graph"]
    assert g["cycles"] == [] and g["blocking"] == []
    # the graph is real: the step lock orders ahead of the leaf locks
    edges = {(e["src"], e["dst"]) for e in g["edges"]}
    assert ("EngineCore._step_lock", "ServingMetrics._lock") in edges
    assert ("FleetRouter._lock", "ReplicaHandle._lock") in edges
    # the cross-replica handoff ordering survives only as bounded
    cross = [e for e in g["edges"]
             if e["src"] == e["dst"] == "EngineCore._step_lock"]
    assert cross and all(e["bounded"] and e["cross"] for e in cross)
    # deterministic: two runs, identical graph JSON
    _, rep2 = run()
    assert json.dumps(rep2["graph"], sort_keys=True) \
        == json.dumps(g, sort_keys=True)


def test_tpulint_lock_graph_dot():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--lock-graph", "--dot"], capture_output=True, text=True,
        env=_env(), timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    assert r.stdout.startswith("digraph")
    assert "EngineCore._step_lock" in r.stdout


def test_tpulint_key_provenance_gate():
    """The zero-recompile gate: every component of every executable
    key must classify as deployment provenance (no request-data), and
    the classified table must be byte-identical to the committed
    baseline — a new key component or a changed provenance class must
    be reviewed even when benign."""
    def run():
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
             "--key-provenance"], capture_output=True, text=True,
            env=_env(), timeout=600)
        return r, json.loads(r.stdout)

    r, rep = run()
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    assert rep["exit"] == 0 and rep["drift"] == []
    assert rep["findings"] == []
    table = rep["table"]
    assert table["version"] == 1
    # the table is real: the ragged mixed-step site keys the grammar
    # family on a literal and draws nothing request-shaped
    mixed = [s for s in table["sites"]
             if s["site"].endswith("::EngineCore._mixed_step")]
    assert len(mixed) == 1
    comps = {c["expr"]: c["classes"] for c in mixed[0]["components"]}
    assert comps["'grammar'"] == ["const"]
    assert all("request-data" not in cl for cl in comps.values())
    # no site of the tree keys an executable on anything request-shaped
    reqs = [(s["site"], c["expr"]) for s in table["sites"]
            for c in s["components"] if "request-data" in c["classes"]]
    assert reqs == []
    # deterministic: two runs, identical table JSON
    _, rep2 = run()
    assert json.dumps(rep2["table"], sort_keys=True) \
        == json.dumps(table, sort_keys=True)


def test_tpulint_key_provenance_dot():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--key-provenance", "--dot"], capture_output=True, text=True,
        env=_env(), timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    assert r.stdout.startswith("digraph key_provenance")
    # nothing request-shaped keys an executable of this tree (the
    # octagon's rendering is held by tests/test_dataflow.py's fixture)
    assert '"request-data"' not in r.stdout
    assert '"const"' in r.stdout and "serve-step" in r.stdout


def test_tpulint_key_provenance_update_deterministic(tmp_path):
    """--key-provenance-update must reproduce the committed baseline
    byte-for-byte (the gate's drift check depends on it)."""
    out = tmp_path / "key_provenance_baseline.json"

    def update():
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
             "--key-provenance-update",
             "--key-provenance-baseline", str(out)],
            capture_output=True, text=True, env=_env(), timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr[-800:]
        return out.read_bytes()

    first, second = update(), update()
    assert first == second
    committed = os.path.join(ROOT, "tools",
                             "key_provenance_baseline.json")
    with open(committed, "rb") as f:
        assert f.read() == first


def test_tpulint_determinism_clean():
    """The bitwise-replay gate: no nondeterminism source reaches token
    emission, handoff/park packets, or RNG-key construction anywhere
    in serving/ or observability/ — fixed or reason-suppressed at the
    sink, never baselined."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--determinism"], capture_output=True, text=True, env=_env(),
        timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    rep = json.loads(r.stdout)
    assert rep["exit"] == 0 and rep["findings"] == []
    assert rep["files"] > 100          # whole-package flow graph


def test_tpulint_help_contract():
    """CI scripts drive tpulint by flag name: --help must exit 0 and
    advertise every gate mode."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--help"], capture_output=True, text=True, env=_env(),
        timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
    for flag in ("--lock-graph", "--key-provenance",
                 "--key-provenance-update", "--determinism", "--dot",
                 "--baseline-update", "--list-rules"):
        assert flag in r.stdout, f"--help lost {flag}"


@pytest.mark.slow
@pytest.mark.lockcheck
def test_serving_suites_instrumented_clean():
    """The dynamic gate: the serving / fleet / resilience suites run
    under the instrumented-lock checker (PIT_LOCKCHECK=1 arms the
    session fixture in conftest.py) and must finish with zero
    violations and every observed lock edge present in the static
    graph."""
    env = _env()
    env["PIT_LOCKCHECK"] = "1"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
         "-p", "no:cacheprovider",
         os.path.join(ROOT, "tests", "test_serving_engine.py"),
         os.path.join(ROOT, "tests", "test_resilience.py"),
         os.path.join(ROOT, "tests", "test_fleet.py"),
         os.path.join(ROOT, "tests", "test_kv_tier.py"),
         os.path.join(ROOT, "tests", "test_structured.py")],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=3000)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-800:]


def test_tpulint_baseline_update_deterministic(tmp_path):
    """--baseline-update must be reproducible: identical bytes across
    runs, path-relative, sorted entries."""
    # name matches the lock rule's path_scope ("serving")
    bad = tmp_path / "serving_bad.py"
    bad.write_text(
        "import threading\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n\n"
        "    def add(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n\n"
        "    def peek(self):\n"
        "        return self.count\n")
    base = tmp_path / "baseline.json"

    def update():
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
             "--baseline-update", "--baseline", str(base), str(bad)],
            capture_output=True, text=True, env=_env(), timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr[-800:]
        return base.read_bytes()

    first, second = update(), update()
    assert first == second
    data = json.loads(first)
    entries = data["entries"]
    assert entries and entries == sorted(
        entries, key=lambda e: (e["rule"], e["path"], e["symbol"],
                                e["message"]))
    assert all(not os.path.isabs(e["path"]) for e in entries)
    # a baselined tree then gates clean...
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpulint.py"),
         "--json", "--baseline", str(base), str(bad)],
        capture_output=True, text=True, env=_env(), timeout=600)
    rep = json.loads(r.stdout)
    assert r.returncode == 0 and rep["new"] == [] and rep["baselined"]
