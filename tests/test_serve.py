"""HTTP serving front end (tools/serve.py — the paddle_serving-style
JSON-over-HTTP layer on top of the engines)."""
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tiny_model(save_dir):
    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    m.eval()
    m.save_pretrained(save_dir)
    return m


def _spawn_server(model_dir, *extra_args):
    """Start tools/serve.py, wait for /health, return (url, proc)."""
    port = _free_port()
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
         "--model_dir", model_dir, "--port", str(port),
         "--page_size", "8", *extra_args],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    url = f"http://127.0.0.1:{port}"
    for _ in range(120):
        try:
            with urllib.request.urlopen(url + "/health", timeout=2) as r:
                if json.load(r)["status"] == "ok":
                    return url, proc
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError(proc.stderr.read()[-1500:])
            time.sleep(1)
    proc.kill()
    raise RuntimeError("server never became healthy")


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("model") / "gpt")
    m = _tiny_model(d)
    url, proc = _spawn_server(d)
    yield url, m
    proc.terminate()
    proc.wait(timeout=30)


def _post(url, path, body):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=300)


def test_generate_endpoint_matches_engine(server):
    url, m = server
    ids = np.random.RandomState(0).randint(0, 96, (2, 8)).astype(np.int32)
    g = GenerationConfig(max_new_tokens=6)
    want = PagedGenerationEngine(m, page_size=8).generate(ids, g)
    with _post(url, "/generate", {"ids": ids.tolist(),
                                  "max_new_tokens": 6}) as r:
        got = np.asarray(json.load(r)["tokens"])
    np.testing.assert_array_equal(got, want)


def test_stream_endpoint_chunks_concatenate(server):
    url, m = server
    ids = np.random.RandomState(1).randint(0, 96, (1, 8)).astype(np.int32)
    g = GenerationConfig(max_new_tokens=7)
    want = PagedGenerationEngine(m, page_size=8).generate(ids, g)
    with _post(url, "/generate_stream",
               {"ids": ids.tolist(), "max_new_tokens": 7,
                "chunk_size": 3}) as r:
        lines = [json.loads(line)
                 for line in r.read().decode().strip().splitlines()]
    # first line is the request-id preamble, the rest are token chunks
    assert lines[0]["request_ids"] and "tokens" not in lines[0]
    chunks = [np.asarray(line["tokens"]) for line in lines[1:]]
    assert len(chunks) >= 2            # prefill token + >=1 decode chunk
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), want)


def test_health_reports_what_the_server_runs_on(server):
    """/health carries the server's own view of device, parameter dtype,
    native library and compile cache — what chip_smoke.py prints."""
    url, m = server
    with urllib.request.urlopen(url + "/health", timeout=30) as r:
        rt = json.load(r)["runtime"]
    assert rt["device"] == {"platform": "cpu", "kind": "cpu",
                            "count": rt["device"]["count"]}
    assert rt["param_dtypes"] == ["float32"]
    assert rt["native"] in ("built", "found")
    assert os.path.isabs(rt["compile_cache_dir"])
    assert [d["id"] for d in rt["memory"]] == list(
        range(rt["device"]["count"]))


def test_bad_request_400(server):
    url, _ = server
    try:
        _post(url, "/generate", {"nope": 1})
        raise AssertionError("expected 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_metrics_endpoint(server):
    url, _ = server
    # generate something first so the counters are non-trivial
    ids = np.random.RandomState(2).randint(0, 96, (1, 8)).astype(np.int32)
    with _post(url, "/generate", {"ids": ids.tolist(),
                                  "max_new_tokens": 4}) as r:
        json.load(r)
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        snap = json.load(r)
    assert snap["counters"]["submitted"] >= 1
    assert snap["counters"]["completed"] >= 1
    assert snap["counters"]["tokens_generated"] >= 4
    assert snap["ttft_s"]["count"] >= 1
    assert "tokens_per_second" in snap and "occupancy" in snap
    assert snap["max_batch"] >= 1


def test_trace_endpoint_covers_request(server):
    """A served request yields a retrievable span trace whose top-level
    spans cover >=95% of its end-to-end wall time (the acceptance
    metric), plus Chrome export and ring summaries."""
    url, _ = server
    ids = np.random.RandomState(3).randint(0, 96, (1, 8)).astype(np.int32)
    with _post(url, "/generate", {"ids": ids.tolist(),
                                  "max_new_tokens": 6}) as r:
        body = json.load(r)
    rids = body["request_ids"]
    assert len(rids) == 1
    with urllib.request.urlopen(f"{url}/trace/{rids[0]}", timeout=30) as r:
        tr = json.load(r)
    assert tr["request_id"] == rids[0]
    assert tr["state"] == "done"
    names = [s["name"] for s in tr["spans"]]
    assert "queue_wait" in names and "prefill" in names
    assert "decode" in names and "evict" in names
    assert "detokenize" in names       # appended by the HTTP layer
    assert tr["coverage"] >= 0.95
    with urllib.request.urlopen(f"{url}/trace/{rids[0]}?format=chrome",
                                timeout=30) as r:
        chrome = json.load(r)
    evs = chrome["traceEvents"]
    assert any(e.get("ph") == "M" for e in evs)       # thread_name meta
    assert any(e.get("ph") == "X" and e.get("dur", 0) >= 0 for e in evs)
    with urllib.request.urlopen(url + "/traces", timeout=30) as r:
        summaries = json.load(r)["traces"]
    assert any(s["request_id"] == rids[0] for s in summaries)
    # unknown rid -> 404
    try:
        urllib.request.urlopen(url + "/trace/999999", timeout=30)
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_steps_endpoint_flight_recorder(server):
    """GET /steps returns the StepLog ring: schema-complete records with
    nonzero analytic cost on prefill/decode, plus the model summary;
    ?format=jsonl streams the same records as NDJSON."""
    url, _ = server
    ids = np.random.RandomState(4).randint(0, 96, (1, 8)).astype(np.int32)
    with _post(url, "/generate", {"ids": ids.tolist(),
                                  "max_new_tokens": 6}) as r:
        json.load(r)
    with urllib.request.urlopen(url + "/steps", timeout=30) as r:
        body = json.load(r)
    steps, summary = body["steps"], body["summary"]
    kinds = {s["kind"] for s in steps}
    assert "prefill" in kinds and "decode" in kinds
    for s in steps:
        if s["kind"] in ("prefill", "decode"):
            assert s["bytes_est"] > 0, s
            assert s["cost_source"] in ("xla+pages", "analytic")
    assert summary["records"] >= len(steps)
    assert "decode_model" in summary
    with urllib.request.urlopen(url + "/steps?format=jsonl&limit=4",
                                timeout=30) as r:
        assert r.headers["Content-Type"].startswith("application/x-ndjson")
        lines = r.read().decode().strip().splitlines()
    assert 0 < len(lines) <= 4
    assert all("kind" in json.loads(ln) for ln in lines)
    # bad limit -> 400
    try:
        urllib.request.urlopen(url + "/steps?limit=banana", timeout=30)
        raise AssertionError("expected 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_metrics_content_negotiation(server):
    """Accept: text/plain renders Prometheus 0.0.4 exposition; the JSON
    default gains kv_pool gauges and the compile-log section."""
    url, _ = server
    req = urllib.request.Request(
        url + "/metrics", headers={"Accept": "text/plain"})
    with urllib.request.urlopen(req, timeout=30) as r:
        ctype = r.headers.get("Content-Type", "")
        text = r.read().decode()
    assert "text/plain" in ctype
    assert "# TYPE serving_queue_depth gauge" in text
    assert 'serving_kv_pool_blocks{state="total"}' in text
    assert "# TYPE compile_count_total counter" in text
    from paddle_infer_tpu.observability import validate_exposition
    assert validate_exposition(text) == []
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        snap = json.load(r)
    assert "kv_pool" in snap and snap["kv_pool"]["total_blocks"] > 0
    assert "compile" in snap and snap["compile"]["compile_count"] >= 1


def test_concurrent_posts_share_the_batch(server):
    """Concurrent clients must all come back correct (they ride the
    same continuous batch) and the occupancy metric must show fused
    steps that hosted more than one row."""
    import threading

    url, m = server
    eng = PagedGenerationEngine(m, page_size=8)
    g = GenerationConfig(max_new_tokens=12)
    prompts = [np.random.RandomState(10 + i).randint(0, 96, (8,))
               .astype(np.int32) for i in range(4)]
    want = [eng.generate(p[None], g) for p in prompts]
    got = [None] * 4
    errs = []

    def client(i):
        try:
            with _post(url, "/generate",
                       {"ids": prompts[i][None].tolist(),
                        "max_new_tokens": 12}) as r:
                got[i] = np.asarray(json.load(r)["tokens"])
        except Exception as e:          # pragma: no cover - diagnostics
            errs.append((i, e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errs, errs
    for i in range(4):
        np.testing.assert_array_equal(got[i], want[i])
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        snap = json.load(r)
    assert snap["counters"]["completed"] >= 4
    assert snap["occupancy"]["max_recent"] is not None


def test_queue_full_maps_to_429(tmp_path):
    d = str(tmp_path / "gpt")
    _tiny_model(d)
    url, proc = _spawn_server(d, "--max_queue", "0")
    try:
        ids = [[1, 2, 3, 4]]
        try:
            _post(url, "/generate", {"ids": ids, "max_new_tokens": 4})
            raise AssertionError("expected 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            # backpressure is actionable: clients get a retry hint
            assert int(e.headers["Retry-After"]) >= 1
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            snap = json.load(r)
        assert snap["counters"]["rejected_queue_full"] >= 1
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_speculative_serving_path(tmp_path):
    """--draft_dir routes greedy bs1 requests through SpeculativeEngine;
    tokens must match the non-draft paged response (self-draft →
    acceptance 1.0)."""
    d = str(tmp_path / "gpt")
    m = _tiny_model(d)
    url, proc = _spawn_server(d, "--draft_dir", d,
                              "--num_draft_tokens", "3")
    try:
        ids = np.random.RandomState(5).randint(0, 96, (1, 8)) \
            .astype(np.int32)
        g = GenerationConfig(max_new_tokens=6)
        want = PagedGenerationEngine(m, page_size=8).generate(ids, g)
        with _post(url, "/generate", {"ids": ids.tolist(),
                                      "max_new_tokens": 6}) as r:
            body = json.load(r)
        assert body.get("speculative") is True
        assert body.get("acceptance") == 1.0       # self-draft
        np.testing.assert_array_equal(np.asarray(body["tokens"]), want)
        # batched requests ride the speculative path too (round-5
        # lockstep batching) and stay token-identical to the paged engine
        ids2 = np.random.RandomState(6).randint(0, 96, (2, 8)) \
            .astype(np.int32)
        g2 = GenerationConfig(max_new_tokens=4)
        want2 = PagedGenerationEngine(m, page_size=8).generate(ids2, g2)
        with _post(url, "/generate", {"ids": ids2.tolist(),
                                      "max_new_tokens": 4}) as r:
            body2 = json.load(r)
        assert body2.get("speculative") is True
        np.testing.assert_array_equal(np.asarray(body2["tokens"]), want2)
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_health_probes_and_drain_resume(server):
    """/healthz (liveness) and /readyz (readiness) are wired to the
    supervisor's state machine; POST /admin/drain flips readiness to 503
    + Retry-After and sheds new work with 503, /admin/resume re-enters
    service.  Runs last against the shared server: it leaves the health
    state DEGRADED (resume never jumps straight to HEALTHY)."""
    url, _ = server
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        body = json.load(r)
    assert body["status"] == "ok"
    assert body["health_state"] in ("healthy", "degraded")
    assert "crash_streak" in body
    with urllib.request.urlopen(url + "/readyz", timeout=30) as r:
        assert json.load(r)["ready"] is True
    # drain: readiness drops to 503 + Retry-After; liveness stays 200
    with _post(url, "/admin/drain", {}) as r:
        assert json.load(r)["status"] == "draining"
    try:
        urllib.request.urlopen(url + "/readyz", timeout=30)
        raise AssertionError("expected 503")
    except urllib.error.HTTPError as e:
        assert e.code == 503
        assert int(e.headers["Retry-After"]) >= 1
        assert json.load(e)["ready"] is False
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        assert json.load(r)["health_state"] == "draining"
    # a draining engine sheds new submissions: 503 + Retry-After
    ids = [[1, 2, 3, 4]]
    try:
        _post(url, "/generate", {"ids": ids, "max_new_tokens": 4})
        raise AssertionError("expected 503")
    except urllib.error.HTTPError as e:
        assert e.code == 503
        assert int(e.headers["Retry-After"]) >= 1
    # resume re-enters service (via DEGRADED) and generation works again
    with _post(url, "/admin/resume", {}) as r:
        assert json.load(r)["status"] in ("degraded", "healthy")
    with urllib.request.urlopen(url + "/readyz", timeout=30) as r:
        assert json.load(r)["ready"] is True
    with _post(url, "/generate", {"ids": ids, "max_new_tokens": 4}) as r:
        assert np.asarray(json.load(r)["tokens"]).shape == (1, 4)


def test_speculative_budget_falls_back(tmp_path):
    """A request whose prompt+max_new fits the paged engine but not the
    speculative chunk budget must FALL BACK, not 500 (supports() owns
    the eligibility rules)."""
    d = str(tmp_path / "gpt")
    m = _tiny_model(d)
    url, proc = _spawn_server(d, "--draft_dir", d,
                              "--num_draft_tokens", "4")
    try:
        # max_position_embeddings=64: 8 + 56 fits plain decode, but
        # 8 + 56 + gamma(4) does not
        ids = np.random.RandomState(7).randint(0, 96, (1, 8)) \
            .astype(np.int32)
        with _post(url, "/generate", {"ids": ids.tolist(),
                                      "max_new_tokens": 56}) as r:
            body = json.load(r)
        assert "speculative" not in body
        assert len(body["tokens"][0]) == 56
        # flat 1-D prompt still rides the fast path
        with _post(url, "/generate", {"ids": ids[0].tolist(),
                                      "max_new_tokens": 6}) as r:
            body2 = json.load(r)
        assert body2.get("speculative") is True
    finally:
        proc.terminate()
        proc.wait(timeout=30)


@pytest.fixture(scope="module")
def adapter_server(tmp_path_factory):
    """Server with two LoRA adapters loaded from an npz directory."""
    from paddle_infer_tpu.serving import (adapter_layer_spec,
                                          make_random_adapter)
    d = str(tmp_path_factory.mktemp("adapter_model") / "gpt")
    m = _tiny_model(d)
    adir = tmp_path_factory.mktemp("adapters")
    spec = adapter_layer_spec(m)
    made = {}
    for aid, seed in (("tenant-a", 11), ("tenant-b", 12)):
        factors, scale = make_random_adapter(spec, 4, seed,
                                             amplitude=0.6)
        arrays = {}
        for path, (a, b) in factors.items():
            arrays[path + ".a"] = a
            arrays[path + ".b"] = b
        arrays["scale"] = np.float32(scale)
        np.savez(str(adir / f"{aid}.npz"), **arrays)
        made[aid] = (factors, scale)
    url, proc = _spawn_server(d, "--adapter_dir", str(adir),
                              "--adapter_rank", "4")
    yield url, m, made
    proc.terminate()
    proc.wait(timeout=30)


def test_adapter_request_matches_merged_weights(adapter_server):
    """End to end through HTTP: the adapter stream is bitwise the
    stream of an engine whose weights were merged offline, and the
    base (no adapter_id) stream is untouched."""
    url, m, made = adapter_server
    ids = np.random.RandomState(9).randint(0, 96, (1, 8)).astype(np.int32)
    base = PagedGenerationEngine(m, page_size=8).generate(
        ids, GenerationConfig(max_new_tokens=6))
    factors, scale = made["tenant-a"]
    pit.seed(0)
    mm = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    mm.eval()
    for path, (a, b) in factors.items():
        obj = mm
        for part in path.split("."):
            obj = getattr(obj, part)
        w = obj.weight
        w.set_value(np.asarray(w.numpy() + scale * (a @ b), np.float32))
    want = PagedGenerationEngine(mm, page_size=8).generate(
        ids, GenerationConfig(max_new_tokens=6))
    with _post(url, "/generate", {"ids": ids.tolist(),
                                  "max_new_tokens": 6,
                                  "adapter_id": "tenant-a"}) as r:
        body = json.load(r)
    got = np.asarray(body["tokens"])
    assert body["adapter_id"] == "tenant-a"
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got, base)
    with _post(url, "/generate", {"ids": ids.tolist(),
                                  "max_new_tokens": 6}) as r:
        got_base = np.asarray(json.load(r)["tokens"])
    np.testing.assert_array_equal(got_base, base)


def test_unknown_adapter_maps_to_400(adapter_server):
    url, _, _ = adapter_server
    ids = np.random.RandomState(10).randint(0, 96, (1, 6)).astype(np.int32)
    try:
        _post(url, "/generate", {"ids": ids.tolist(),
                                 "max_new_tokens": 4,
                                 "adapter_id": "nope"})
        raise AssertionError("expected 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "unknown adapter" in json.load(e)["error"]


def test_adapter_metrics_exposed(adapter_server):
    url, _, _ = adapter_server
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        snap = json.load(r)
    assert snap["adapters"]["store"]["adapters"] == 2
    req = urllib.request.Request(
        url + "/metrics", headers={"Accept": "text/plain"})
    with urllib.request.urlopen(req, timeout=30) as r:
        text = r.read().decode()
    assert "adapter_slots_resident" in text
    assert 'adapter_store_pages{state="total"}' in text
