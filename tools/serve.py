"""HTTP serving front end over the continuous-batching engine.

Reference context: the fork's deployment story pairs Paddle Inference
with a serving layer (paddle_serving / fastdeploy) speaking JSON over
HTTP.  This is the stdlib-only equivalent for this framework — but all
generation now flows through ``paddle_infer_tpu.serving.EngineCore``:
one background scheduler thread owns the paged engine and runs the
continuous-batching step loop; HTTP handler threads only enqueue
requests and stream their tokens, so concurrent clients share fused
decode steps instead of serializing behind a lock.

  POST /generate          {"ids": [[...]], "max_new_tokens": N, ...}
                          -> {"tokens": [[...]], "request_ids": [...]}
  POST /generate_stream   same body -> chunked response: one JSON line
                          {"request_ids": [...]} then one line per
                          decoded chunk
  GET  /metrics           -> ServingMetrics snapshot (queue depth, batch
                          occupancy, KV-pool gauges, TTFT/ITL
                          percentiles, tokens/s, rejection counts,
                          compile log); with ``Accept: text/plain`` the
                          same data renders as Prometheus 0.0.4 text
                          exposition
  GET  /trace/<rid>       -> span trace of one (recent) request;
                          ``?format=chrome`` exports Chrome-trace JSON
                          mergeable with profiler captures
  GET  /traces            -> one-line summaries of the completed-trace
                          ring (id, state, duration, span coverage);
                          fleet-wide (every replica's ring) in fleet
                          mode
  GET  /journeys          -> finished request-journey summaries (one
                          per request, stitched across every replica
                          it touched: hops, latency-attribution
                          buckets, coverage) plus fleet aggregates
  GET  /journey/<id>      -> one journey by journey id ("j<rid>") or
                          raw request id: summary + per-replica span
                          dumps + hop events; ``?format=chrome``
                          renders the multi-replica journey as ONE
                          Chrome trace with per-replica process lanes
  GET  /steps             -> recent StepLog flight-recorder ring (one
                          record per scheduler step: kind, batch
                          composition, resident KV pages, analytic
                          bytes/FLOPs, dispatch-vs-host wall) plus the
                          model-vs-measured summary; ``?limit=N``
                          bounds the ring slice, ``?format=jsonl``
                          streams raw JSONL for offline analysis
  GET  /health            -> {"status": "ok", "model": ..., "runtime":
                          {...}} (process-liveness probe; always ok
                          once up).  ``runtime`` says what the process
                          runs on and serves: device platform / kind /
                          count as JAX reports them, parameter dtypes,
                          whether the native library was built or
                          found, the compile-cache directory, and
                          bytes in use / peak per device
  GET  /healthz           -> engine health (supervisor state machine):
                          200 while HEALTHY/DEGRADED/DRAINING, 503 +
                          Retry-After when DOWN; includes crash streak
                          and live hung-step stall seconds
  GET  /readyz            -> readiness: 200 only while the engine
                          accepts new work (HEALTHY/DEGRADED), 503 +
                          Retry-After while DRAINING/DOWN
  POST /admin/drain       -> stop admitting (health -> DRAINING);
                          in-flight requests finish; the JSON response
                          reports {"in_flight", "queued"} so operators
                          (and the fleet router) can poll drain progress
  POST /admin/resume      -> leave DRAINING/DOWN back into service

With ``--fleet_roles prefill,decode,...`` the process runs a
disaggregated fleet: one supervised EngineCore per role behind a
prefix-affinity FleetRouter with cross-replica KV page handoff
(docs/SERVING.md "Disaggregated serving"); admin endpoints then act
fleet-wide and /metrics carries the ``router_*`` families.

With ``--adapter_dir`` the process serves multi-LoRA tenants: every
``<id>.npz`` checkpoint in the directory registers adapter ``<id>`` in
a validated AdapterStore, and generation bodies may carry a per-request
``"adapter_id"`` field (docs/SERVING.md "Multi-LoRA serving").  An
unknown adapter_id is a client error -> 400, never a 500.

With ``--structured`` the process serves grammar-constrained requests:
generation bodies may carry a per-request ``"grammar"`` spec
(json_schema / regex / json), compiled to a token-level FSM at
admission and applied as a per-row logit mask inside the one mixed-step
executable (docs/SERVING.md "Constrained decoding").  A malformed,
unsupported or unsatisfiable grammar is a client error -> 400 with a
structured error body ({"error", "error_type"}), rejected BEFORE any
KV page is reserved or adapter pinned.

Admission control maps to HTTP codes: queue full -> 429 + Retry-After,
draining/load-shed -> 503 + Retry-After, deadline exceeded -> 504,
unbatchable/oversized/unknown-adapter/bad-grammar -> 400.  Retry-After is derived from queue depth
x recent step time (health state overrides while DRAINING/DOWN).
Requests the batch can't host (beams, repetition penalty) and
speculative-eligible requests run exclusively on the scheduler thread
via a separate dense engine, FIFO with everything else.  The scheduler
runs under a resilience supervisor (serving/resilience/): step
watchdog, crash-loop backoff, bounded retry/replay of in-flight
requests, and a seedable fault-injection plane (--fault_script).

Usage:
  env PYTHONPATH=. python tools/serve.py --model_dir DIR --port 8800
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np

_STATE = {"lock": threading.Lock()}


def _runtime_info() -> dict:
    """What this process runs on and what it serves — the facts a
    bring-up check needs from the server's own point of view."""
    import jax

    from paddle_infer_tpu import native

    devs = jax.devices()
    memory = []
    for d in devs:
        stats = d.memory_stats() or {}
        memory.append({"id": d.id,
                       "bytes_in_use": stats.get("bytes_in_use"),
                       "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return {
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "param_dtypes": sorted({str(p.dtype) for p in
                                _STATE["model"].parameters()}),
        "native": native.build_status(),
        "compile_cache_dir": _STATE["compile_cache_dir"],
        "memory": memory,
    }


def _build_fleet(roles):
    """Disaggregated fleet (--fleet_roles): one EngineCore + supervisor
    per role, each owning its OWN engine, KV pool and span tracer
    (pools are strictly per-engine; per-replica tracers keep one
    replica's 256-ring from evicting another's traces), all sharing one
    StepLog and ONE JourneyStore — the journey plane stitches the
    per-replica traces back into fleet-wide request journeys
    (``GET /journeys``), behind a FleetRouter.  The router thread only
    routes — supervisors own the scheduler threads."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.observability import JourneyStore, Tracer
    from paddle_infer_tpu.observability.steplog import StepLog
    from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                          FleetRouter, ReplicaHandle)

    steplog = StepLog()
    journeys = JourneyStore()
    handles, sups = [], []
    for i, role in enumerate(roles):
        name = f"{role.value}{i}"
        engine = PagedGenerationEngine(
            _STATE["model"], page_size=_STATE["page_size"],
            kv_dtype=_STATE.get("kv_dtype"))
        core = EngineCore(
            engine,
            max_batch=_STATE["max_batch"],
            max_queue=_STATE["max_queue"],
            default_timeout_s=_STATE["request_timeout"],
            max_model_len=_STATE["max_model_len"],
            tracer=Tracer(), steplog=steplog,
            journeys=journeys, replica_name=name,
            enable_prefix_cache=_STATE.get("enable_prefix_cache", False),
            prefix_cache_watermark=_STATE.get(
                "prefix_cache_watermark", 0.5),
            prefix_cache_headroom_pages=_STATE.get(
                "prefix_cache_headroom_pages", 0),
            prefill_chunk=_STATE.get("prefill_chunk"),
            token_budget=_STATE.get("token_budget"),
            sched_policy=_STATE.get("sched_policy", "fifo"),
            slo_ttft_s=_STATE.get("slo_ttft_s"),
            slo_itl_s=_STATE.get("slo_itl_s"),
            kv_host_pages=_STATE.get("kv_host_pages", 0),
            kv_park_watermark=_STATE.get("kv_park_watermark", 0.95),
            kv_resume_watermark=_STATE.get("kv_resume_watermark", 0.70),
            grammar_vocab=_STATE.get("grammar_vocab"))
        sup = EngineSupervisor(
            core,
            watchdog_s=_STATE.get("watchdog_s", 5.0),
            max_retries=_STATE.get("max_retries", 2)).start()
        handles.append(ReplicaHandle(name, core, role, supervisor=sup))
        sups.append(sup)
    router = FleetRouter(
        handles,
        prefix_affinity=_STATE.get("prefix_affinity", True))
    router.start(start_cores=False)
    _STATE["handles"] = handles
    _STATE["sups"] = sups
    _STATE["sup"] = sups[0]
    _STATE["router"] = router
    _STATE["core"] = handles[0].core
    _STATE["journeys"] = journeys


def _core():
    """The continuous-batching scheduler (owns the paged engine).  The
    stepping thread belongs to the resilience supervisor, which wires
    its recovery protocol (watchdog, retry/replay, degradation ladder)
    into the core's failure paths.  In fleet mode (--fleet_roles) this
    is the PRIMARY replica's core — exclusives and the trace/step
    surfaces go through it; batchable generation routes via
    ``_STATE["router"]``."""
    with _STATE["lock"]:
        if "core" not in _STATE:
            from paddle_infer_tpu.serving import (EngineCore,
                                                  EngineSupervisor,
                                                  FaultPlane, ServingMesh,
                                                  build_sharded_engine)

            if _STATE.get("fleet_roles"):
                _build_fleet(_STATE["fleet_roles"])
                return _STATE["core"]
            smesh = _STATE.get("serving_mesh") or ServingMesh()
            engine = build_sharded_engine(
                _STATE["model"], smesh, page_size=_STATE["page_size"],
                kv_dtype=_STATE.get("kv_dtype"))
            # this process serves the model through this engine: drop
            # the single-device copies the shards were placed from
            engine.adopt_placement()
            plane = None
            script = _STATE.get("fault_script")
            if script:
                plane = FaultPlane.from_spec(
                    script, seed=_STATE.get("fault_seed", 0))
            core = EngineCore(
                engine,
                max_batch=_STATE["max_batch"],
                max_queue=_STATE["max_queue"],
                default_timeout_s=_STATE["request_timeout"],
                max_model_len=_STATE["max_model_len"],
                enable_prefix_cache=_STATE.get("enable_prefix_cache",
                                               False),
                prefix_cache_watermark=_STATE.get(
                    "prefix_cache_watermark", 0.5),
                prefix_cache_headroom_pages=_STATE.get(
                    "prefix_cache_headroom_pages", 0),
                prefill_chunk=_STATE.get("prefill_chunk"),
                token_budget=_STATE.get("token_budget"),
                sched_policy=_STATE.get("sched_policy", "fifo"),
                slo_ttft_s=_STATE.get("slo_ttft_s"),
                slo_itl_s=_STATE.get("slo_itl_s"),
                adapter_store=_STATE.get("adapter_store"),
                adapter_slots=_STATE.get("adapter_slots", 8),
                speculate=_STATE.get("speculate", False),
                num_draft_tokens=_STATE.get("num_draft_tokens", 4),
                draft_source=_STATE.get("draft_source", "auto"),
                spec_accept_threshold=_STATE.get("spec_accept_threshold"),
                fault_plane=plane,
                serving_mesh=(smesh if smesh.n_devices > 1
                              or smesh.quantized_allreduce else None),
                kv_host_pages=_STATE.get("kv_host_pages", 0),
                kv_park_watermark=_STATE.get("kv_park_watermark", 0.95),
                kv_resume_watermark=_STATE.get("kv_resume_watermark",
                                               0.70),
                grammar_vocab=_STATE.get("grammar_vocab"))
            _STATE["sup"] = EngineSupervisor(
                core,
                watchdog_s=_STATE.get("watchdog_s", 5.0),
                max_retries=_STATE.get("max_retries", 2)).start()
            _STATE["core"] = core
            _STATE["journeys"] = core._journeys
        return _STATE["core"]


def _sup():
    _core()
    return _STATE["sup"]


def _journeys():
    """The fleet-wide JourneyStore: shared across all replica cores in
    fleet mode, the single core's own store otherwise."""
    _core()
    return _STATE["journeys"]


def _tracers():
    """Every live tracer, primary replica first.  Fleet replicas carry
    per-replica tracers, so the /traces and /trace/<rid> surfaces (and
    the post-finish detokenize span) scan all of them."""
    _core()
    handles = _STATE.get("handles")
    if handles:
        return [h.core.tracer for h in handles]
    return [_STATE["core"].tracer]


def _retry_after_s() -> int:
    """Retry-After seconds for 429/503: health state overrides
    (DRAINING -> short, DOWN -> long); otherwise the time to drain the
    current queue at the recent per-chunk step rate."""
    sup = _STATE.get("sup")
    if sup is not None:
        state = sup.health.state.value
        if state == "down":
            return 30
        if state == "draining":
            return 5
    core = _STATE.get("core")
    if core is None:
        return 1
    p50 = core.metrics.snapshot().get(
        "decode_step_ms", {}).get("p50_recent")
    step_s = ((p50 or 50.0) / 1000.0)
    est = core.queue_depth * step_s / max(1, core.max_batch)
    return max(1, min(30, int(est) + 1))


def _dense():
    """Dense-cache fallback engine for exclusive requests.  Deliberately
    NOT the paged engine: a direct generate() there would free/reserve
    the slot sequence ids the scheduler holds for in-flight rows."""
    with _STATE["lock"]:
        if "dense" not in _STATE:
            from paddle_infer_tpu.inference.generation import (
                GenerationEngine)

            _STATE["dense"] = GenerationEngine(_STATE["model"])
        return _STATE["dense"]


def _spec_engine():
    with _STATE["lock"]:
        if "spec_engine" not in _STATE:
            from paddle_infer_tpu.inference.speculative import (
                SpeculativeEngine)

            _STATE["spec_engine"] = SpeculativeEngine(
                _STATE["model"], _STATE["draft_model"],
                num_draft_tokens=_STATE["num_draft_tokens"])
        return _STATE["spec_engine"]


def _speculatable(ids, g):
    """Requests the draft-accelerated path can serve — the ENGINE owns
    the eligibility rules (greedy within the position budget);
    everything else falls through to the batching core."""
    return (_STATE.get("draft_model") is not None
            and _spec_engine().supports(ids, g))


def _gen_config(body):
    from paddle_infer_tpu.inference.generation import GenerationConfig

    kw = {k: body[k] for k in
          ("max_new_tokens", "min_length", "do_sample", "temperature",
           "top_k", "top_p", "num_beams", "length_penalty",
           "repetition_penalty", "eos_token_id", "pad_token_id", "seed")
          if k in body}
    return GenerationConfig(**kw)


def _error_code(e) -> int:
    from paddle_infer_tpu.serving import (DeadlineExceededError,
                                          LoadShedError, QueueFullError,
                                          RejectedError)

    if isinstance(e, QueueFullError):
        return 429
    if isinstance(e, LoadShedError):
        return 503           # draining / shed — retry another replica
    if isinstance(e, (DeadlineExceededError, TimeoutError)):
        return 504
    if isinstance(e, RejectedError):
        return 400
    return 500


def _submit_batch(core, ids, g, timeout_s, cache_salt, adapter_id=None,
                  tenant=None, grammar=None):
    """Batchable admission: per-row through the fleet router when one
    is up (role/affinity/health-aware placement), else the single
    core's all-or-nothing submit."""
    router = _STATE.get("router")
    if router is None:
        return core.submit(ids, g, timeout_s=timeout_s,
                           cache_salt=cache_salt, adapter_id=adapter_id,
                           tenant=tenant, grammar=grammar)
    ids = np.asarray(ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None, :]
    return [router.submit(row, g, timeout_s=timeout_s,
                          cache_salt=cache_salt, adapter_id=adapter_id,
                          tenant=tenant, grammar=grammar)
            for row in ids]


def _generate(ids, g, timeout_s, cache_salt=None, adapter_id=None,
              tenant=None, grammar=None):
    """Route one /generate body; returns (tokens [b, max_new], extra).
    ``extra["request_ids"]`` always carries the engine request ids so
    the client can fetch the span trace via ``GET /trace/<rid>``."""
    core = _core()
    if adapter_id is not None or grammar is not None:
        # adapter deltas and grammar masks live only in the serving
        # core's mixed step — the dense exclusive /
        # separate-spec-engine bypasses would silently serve the BASE
        # model / an unconstrained stream, so these must be batchable
        if not core.batchable(g):
            from paddle_infer_tpu.serving import RejectedError

            raise RejectedError(
                "adapter_id/grammar requires a batchable request (no "
                "beams / repetition penalty): the exclusive dense path "
                "serves the base model only, unconstrained")
        reqs = _submit_batch(core, ids, g, timeout_s, cache_salt,
                             adapter_id=adapter_id, tenant=tenant,
                             grammar=grammar)
        extra = {"request_ids": [r.rid for r in reqs]}
        if adapter_id is not None:
            extra["adapter_id"] = adapter_id
        return (np.stack([r.padded_result(timeout=None) for r in reqs]),
                extra)
    if _speculatable(ids, g):
        def call():
            eng = _spec_engine()
            toks = eng.generate(ids, g)
            return np.asarray(toks), eng.last_acceptance

        req = core.submit_exclusive(call, timeout_s=timeout_s)
        req.result(timeout=None)
        toks, acceptance = req.value
        return toks, {"speculative": True, "acceptance": acceptance,
                      "request_ids": [req.rid]}
    if core.batchable(g):
        reqs = _submit_batch(core, ids, g, timeout_s, cache_salt,
                             tenant=tenant)
        return (np.stack([r.padded_result(timeout=None) for r in reqs]),
                {"request_ids": [r.rid for r in reqs]})
    # beams / repetition penalty: exclusive dense-engine call
    req = core.submit_exclusive(lambda: _dense().generate(ids, g),
                                timeout_s=timeout_s)
    req.result(timeout=None)
    return np.asarray(req.value), {"request_ids": [req.rid]}


def _merge_tenants(a: dict, b: dict) -> dict:
    """Merge two per-tenant accounting sections (metrics snapshot
    shape) for the fleet-wide /metrics view.  Requests finish on — and
    are accounted by — exactly one replica, so sections are disjoint
    per request and counters simply add; histograms share DEFAULT_BOUNDS
    so their cumulative bucket counts add position-wise."""
    out = {name: json.loads(json.dumps(t)) for name, t in a.items()}
    for name, t in b.items():
        cur = out.get(name)
        if cur is None:
            out[name] = json.loads(json.dumps(t))
            continue
        for k in ("requests", "attained", "tokens"):
            cur[k] = cur.get(k, 0) + t.get(k, 0)
        cur["parked_seconds"] = (cur.get("parked_seconds", 0.0)
                                 + t.get("parked_seconds", 0.0))
        cur["attainment"] = (cur["attained"] / cur["requests"]
                             if cur.get("requests") else 0.0)
        for bk, v in (t.get("buckets") or {}).items():
            cur.setdefault("buckets", {})
            cur["buckets"][bk] = cur["buckets"].get(bk, 0.0) + v
        eh, th = cur.get("e2e") or {}, t.get("e2e") or {}
        if eh and th:
            eh["sum"] = eh.get("sum", 0.0) + th.get("sum", 0.0)
            eh["count"] = eh.get("count", 0) + th.get("count", 0)
            tb = {str(le): c for le, c in th.get("buckets", [])}
            eh["buckets"] = [[le, c + tb.get(str(le), 0)]
                             for le, c in eh.get("buckets", [])]
        elif th:
            cur["e2e"] = json.loads(json.dumps(th))
        ex = dict(t.get("exemplars") or {})
        ex.update(cur.get("exemplars") or {})
        cur["exemplars"] = ex
    return out


def _stream_chunks(reqs, g, chunk_size):
    """Yield [b, <=chunk_size] token blocks as the batch rows decode.
    Rows finish at different steps; slots past a finished row's last
    token are pad, matching the engines' [b, max_new] output layout."""
    b = len(reqs)
    emitted = 0
    while True:
        # early-stop once every row is done (engine.stream semantics)
        limit = (g.max_new_tokens if not all(r.done for r in reqs)
                 else max(r.emitted for r in reqs))
        if emitted >= limit:
            break
        n = min(chunk_size, limit - emitted)
        for r in reqs:
            while r.emitted < emitted + n and not r.done:
                try:
                    r.wait_tokens(emitted + n, timeout=1.0)
                except TimeoutError:
                    continue
            if r.done and r.error is not None:
                raise r.error
        block = np.full((b, n), g.pad_token_id, np.int32)
        for i, r in enumerate(reqs):
            part = r.tokens[emitted:emitted + n]
            block[i, :len(part)] = part
        yield block
        emitted += n


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"     # chunked transfer needs >= 1.1

    def log_message(self, fmt, *args):      # quiet
        pass

    def _json(self, code, obj, headers=None):
        payload = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(payload)

    def _text(self, code, text, content_type):
        payload = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def do_GET(self):
        from paddle_infer_tpu.observability import get_compile_log

        url = urlparse(self.path)
        if url.path == "/health":
            self._json(200, {"status": "ok",
                             "model": type(_STATE["model"]).__name__,
                             "runtime": _runtime_info()})
        elif url.path == "/healthz":
            # liveness: wired to the supervisor's state machine — 503
            # only when the engine is DOWN (crash-looping).  Does not
            # force engine init: a warming server is simply "starting".
            sup = _STATE.get("sup")
            if sup is None:
                self._json(200, {"status": "starting",
                                 "health_state": "healthy"})
                return
            info = sup.health_info()
            down = info["health_state"] == "down"
            self._json(503 if down else 200,
                       {"status": "down" if down else "ok", **info},
                       headers=({"Retry-After": _retry_after_s()}
                                if down else None))
        elif url.path == "/readyz":
            # readiness: 200 only while new work is accepted
            sup = _STATE.get("sup")
            if sup is None:
                self._json(200, {"status": "starting", "ready": True})
                return
            info = sup.health_info()
            ready = sup.health.is_serving()
            self._json(200 if ready else 503,
                       {"status": "ready" if ready else "not-ready",
                        "ready": ready, **info},
                       headers=(None if ready
                                else {"Retry-After": _retry_after_s()}))
        elif url.path == "/metrics":
            core = _core()
            snap = core.metrics_snapshot()
            router = _STATE.get("router")
            if router is not None:
                snap["router"] = router.snapshot()
            handles = _STATE.get("handles")
            if handles:
                # fleet aggregation: the shared JourneyStore already
                # makes snap["journeys"] fleet-wide; tenants finish on
                # whichever replica served them, so their per-replica
                # metric sections merge here, and per-replica key stats
                # ride a "fleet" section rendered with replica labels
                reps = []
                merged = dict(snap.get("tenants") or {})
                for h in handles:
                    hsnap = (snap if h.core is core
                             else h.core.metrics_snapshot())
                    c = hsnap.get("counters", {})
                    reps.append({
                        "replica": h.name,
                        "role": h.role.value,
                        "submitted": c.get("submitted", 0),
                        "completed": c.get("completed", 0),
                        "tokens_generated": c.get("tokens_generated", 0),
                        "queued": hsnap.get("queue_depth", 0),
                        "active": hsnap.get("active", 0),
                    })
                    if h.core is not core:
                        merged = _merge_tenants(
                            merged, hsnap.get("tenants") or {})
                snap["fleet"] = {"replicas": reps}
                if merged:
                    snap["tenants"] = merged
            compile_summary = get_compile_log().summary()
            accept = self.headers.get("Accept", "")
            # content negotiation: Prometheus scrapers say text/plain
            # (or openmetrics); dashboards/tests default to JSON
            if "text/plain" in accept or "openmetrics" in accept:
                self._text(200, core.metrics.to_prometheus(
                    snap, compile_summary),
                    "text/plain; version=0.0.4; charset=utf-8")
            else:
                snap["compile"] = compile_summary
                self._json(200, snap)
        elif url.path == "/traces":
            out = []
            for tracer in _tracers():
                out.extend(tracer.summaries())
            self._json(200, {"traces": out})
        elif url.path == "/journeys":
            self._json(200, {"journeys": _journeys().summaries(),
                             "summary": _journeys().summary()})
        elif url.path.startswith("/journey/"):
            key = url.path[len("/journey/"):]
            fmt = parse_qs(url.query).get("format", ["json"])[0]
            store = _journeys()
            out = (store.to_chrome(key) if fmt == "chrome"
                   else store.get(key))
            if out is None:
                self._json(404, {"error": f"no journey {key!r} "
                                          "(evicted or never submitted)"})
            else:
                self._json(200, out)
        elif url.path == "/steps":
            core = _core()
            q = parse_qs(url.query)
            try:
                limit = int(q.get("limit", ["128"])[0])
            except ValueError:
                self._json(400, {"error": "limit must be an integer"})
                return
            if q.get("format", ["json"])[0] == "jsonl":
                self._text(200, core.steplog.to_jsonl(limit=limit),
                           "application/x-ndjson")
            else:
                self._json(200, {"steps": core.steplog.records(limit),
                                 "summary": core.steplog.summary()})
        elif url.path.startswith("/trace/"):
            try:
                rid = int(url.path[len("/trace/"):])
            except ValueError:
                self._json(400, {"error": "trace id must be an integer"})
                return
            tr = None
            for tracer in _tracers():
                tr = tracer.get(rid)
                if tr is not None:
                    break
            if tr is None:
                self._json(404, {"error": f"no trace for request {rid} "
                                          "(evicted or never submitted)"})
                return
            fmt = parse_qs(url.query).get("format", ["json"])[0]
            if fmt == "chrome":
                self._json(200, tr.to_chrome())
            else:
                self._json(200, tr.to_dict())
        else:
            self._json(404, {"error": "unknown path"})

    def do_POST(self):
        if self.path in ("/admin/drain", "/admin/resume"):
            # operator endpoints take no generation body
            try:
                length = int(self.headers.get("Content-Length", 0))
                if length:
                    self.rfile.read(length)
                sup = _sup()
                sups = _STATE.get("sups") or [sup]
                for s in sups:
                    if self.path == "/admin/drain":
                        s.drain()
                    else:
                        s.resume()
                # drain progress: operators (and the fleet router) poll
                # this count down to zero before taking the node out
                cores = ([h.core for h in _STATE.get("handles", [])]
                         or [_core()])
                self._json(200, {
                    "status": sup.health.state.value,
                    "in_flight": sum(c.active_count for c in cores),
                    "queued": sum(c.queue_depth for c in cores)})
            except Exception as e:
                self._json(500, {"error": repr(e)[:400]})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            ids = np.asarray(body["ids"], np.int32)
            g = _gen_config(body)
            timeout_s = body.get("timeout_s", _STATE["request_timeout"])
            # per-request prefix-cache isolation domain; clients that
            # must never share cached KV (multi-tenant) set a tenant
            # salt — docs/SERVING.md "Prefix caching"
            cache_salt = body.get("cache_salt")
            if cache_salt is not None:
                cache_salt = str(cache_salt)
            # per-request LoRA tenant binding; validated at submit time
            # against the adapter store (unknown -> 400)
            adapter_id = body.get("adapter_id")
            if adapter_id is not None:
                adapter_id = str(adapter_id)
            # accounting tenant for the per-tenant SLO families and the
            # journey plane; pure observability — never part of the
            # cache/routing salt (use cache_salt for KV isolation)
            tenant = body.get("tenant")
            if tenant is not None:
                tenant = str(tenant)
            # constrained decoding: a grammar SPEC dict ({"type":
            # "json_schema"|"regex"|"json", ...}).  Structural/size
            # validation and FSM compilation happen at engine
            # admission — BEFORE any KV page is reserved or adapter
            # pinned — and reject with 400 + a structured error body.
            grammar = body.get("grammar")
            if grammar is not None and not isinstance(grammar, dict):
                raise TypeError("grammar must be a JSON object")
        except Exception as e:
            self._json(400, {"error": f"bad request: {e!r}",
                             "error_type": type(e).__name__})
            return
        headers_sent = False

        def send_chunk(payload: dict):
            data = (json.dumps(payload) + "\n").encode()
            self.wfile.write(f"{len(data):x}\r\n".encode())
            self.wfile.write(data + b"\r\n")

        try:
            if self.path == "/generate":
                toks, extra = _generate(ids, g, timeout_s,
                                        cache_salt=cache_salt,
                                        adapter_id=adapter_id,
                                        tenant=tenant, grammar=grammar)
                # detokenize/serialize span appended post-finish (the
                # tracer ring keeps completed traces mutable for this);
                # recorded BEFORE the response bytes go out so the trace
                # is complete the moment the client can fetch it.  Every
                # tracer is offered the span — add_span no-ops on the
                # replicas that never saw the rid.
                t_ser = time.monotonic()
                payload = {"tokens": np.asarray(toks).tolist(), **extra}
                tracers = _tracers()
                now = time.monotonic()
                for rid in extra.get("request_ids", []):
                    for tracer in tracers:
                        tracer.add_span(rid, "detokenize", t_ser, now)
                self._json(200, payload)
            elif self.path == "/generate_stream":
                if g.num_beams > 1:
                    self._json(400, {"error": "streaming supports "
                                              "sampling/greedy only"})
                    return
                # submit BEFORE headers so admission errors (429/504/400)
                # still map to status codes
                reqs = _submit_batch(_core(), ids, g, timeout_s,
                                     cache_salt, adapter_id=adapter_id,
                                     tenant=tenant, grammar=grammar)
                chunks = _stream_chunks(
                    reqs, g, chunk_size=int(body.get("chunk_size", 8)))
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                headers_sent = True
                send_chunk({"request_ids": [r.rid for r in reqs]})
                for chunk in chunks:
                    send_chunk({"tokens": np.asarray(chunk).tolist()})
                self.wfile.write(b"0\r\n\r\n")
            else:
                self._json(404, {"error": "unknown path"})
        except Exception as e:
            try:
                if headers_sent:
                    # mid-stream failure: error rides as a final chunk +
                    # proper terminator (re-sending headers would corrupt
                    # the chunked body)
                    send_chunk({"error": repr(e)[:400]})
                    self.wfile.write(b"0\r\n\r\n")
                else:
                    code = _error_code(e)
                    # backpressure responses tell the client when to come
                    # back instead of letting it hammer a loaded server
                    hdrs = ({"Retry-After": _retry_after_s()}
                            if code in (429, 503) else None)
                    # structured error body: the exception class names
                    # the admission failure (GrammarError,
                    # UnknownAdapterError, QueueFullError, ...) so
                    # clients can branch without parsing repr text
                    self._json(code, {"error": repr(e)[:400],
                                      "error_type": type(e).__name__},
                               headers=hdrs)
            except Exception:
                pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model_dir", required=True,
                    help="save_pretrained directory (AutoModel-loadable)")
    ap.add_argument("--port", type=int, default=8800)
    ap.add_argument("--page_size", type=int, default=16)
    ap.add_argument("--max_batch", type=int, default=8,
                    help="continuous-batching slots (KV reservations)")
    ap.add_argument("--max_queue", type=int, default=64,
                    help="admission-control queue depth (beyond -> 429)")
    ap.add_argument("--request_timeout", type=float, default=None,
                    help="per-request deadline in seconds (beyond -> 504)")
    ap.add_argument("--max_model_len", type=int, default=None,
                    help="bound on prompt+generated length per request; "
                         "sizes each slot's KV reservation (defaults to "
                         "the model's max positions — set it lower to "
                         "shrink the pool the decode step drags along)")
    ap.add_argument("--enable_prefix_cache", action="store_true",
                    help="retain finished sequences' KV pages in a radix "
                         "tree and reuse them for shared prompt prefixes "
                         "(docs/SERVING.md); per-request opt-out via a "
                         "\"cache_salt\" body field")
    ap.add_argument("--prefix_cache_watermark", type=float, default=0.5,
                    help="retained cache blocks are LRU-evicted down to "
                         "this fraction of the KV pool after each "
                         "request release")
    ap.add_argument("--prefix_cache_headroom_pages", type=int, default=0,
                    help="extra KV pool pages beyond the live-slot "
                         "reservations, reachable only by prefix-cache "
                         "retention — keeps the radix tree (and the "
                         "tree-backed speculative draft source) resident "
                         "under a full batch (docs/SERVING.md)")
    ap.add_argument("--token_budget", type=int, default=None,
                    help="per-step token budget for the ragged mixed "
                         "step: decode rows take one token each, the "
                         "remainder goes to prefill chunks (default "
                         "min(slot window, max(4*page_size, 32)))")
    ap.add_argument("--prefill_chunk", type=int, default=None,
                    help="max prompt tokens a single request contributes "
                         "to one mixed step (defaults to the token "
                         "budget); smaller chunks tighten decode ITL "
                         "under long-prompt arrivals at the cost of "
                         "prefill latency")
    ap.add_argument("--sched_policy", default="fifo",
                    choices=["fifo", "slack"],
                    help="admission policy (serving/sched/): fifo keeps "
                         "arrival order (bitwise-compat default); slack "
                         "orders queued requests by predicted deadline "
                         "slack and predictively sheds requests whose "
                         "predicted completion already misses their "
                         "deadline (docs/SERVING.md \"SLO-aware "
                         "scheduling\")")
    ap.add_argument("--slo_ttft_ms", type=float, default=None,
                    help="target time-to-first-token (ms) the slack "
                         "policy budgets admission against")
    ap.add_argument("--slo_itl_ms", type=float, default=None,
                    help="target inter-token latency (ms): the step "
                         "planner shrinks per-step prompt chunking so "
                         "the predicted mixed-step wall stays under it "
                         "when decode rows share the step")
    ap.add_argument("--draft_dir", default=None,
                    help="optional draft model for speculative decoding "
                         "of greedy requests")
    ap.add_argument("--num_draft_tokens", type=int, default=4,
                    help="draft tokens proposed per speculating row "
                         "(verify rows ride the mixed step with "
                         "query_len up to num_draft_tokens+1)")
    ap.add_argument("--speculate", action="store_true",
                    help="in-engine speculative decoding: draft/verify "
                         "rows inside the mixed step")
    ap.add_argument("--draft_source", default="auto",
                    choices=("auto", "ngram", "prefix_cache"),
                    help="where draft tokens come from: prompt-lookup "
                         "ngrams, the prefix-cache radix tree, or auto "
                         "(tree when cached, ngram fallback)")
    ap.add_argument("--watchdog_s", type=float, default=5.0,
                    help="supervisor hung-step threshold in seconds "
                         "(trips DEGRADED + watchdog_trips_total)")
    ap.add_argument("--max_retries", type=int, default=2,
                    help="per-request replay budget after engine "
                         "failures; beyond it the request is "
                         "quarantined")
    ap.add_argument("--fault_script", default=None,
                    help="chaos testing: JSON list of fault specs for "
                         "the injection plane (or @path to a JSON "
                         "file); see docs/SERVING.md 'Fault tolerance'")
    ap.add_argument("--fault_seed", type=int, default=0,
                    help="seed for probabilistic fault specs")
    ap.add_argument("--mp", type=int, default=1,
                    help="tensor-parallel degree: attention heads / MLP "
                         "splits and the KV page pool shard over an "
                         "'mp' mesh axis (docs/SERVING.md 'Sharded "
                         "serving')")
    ap.add_argument("--dp_replicas", type=int, default=1,
                    help="data-parallel replica groups; batch rows "
                         "split across replicas (needs mp*dp_replicas "
                         "visible devices)")
    ap.add_argument("--quantized_allreduce", default=None,
                    choices=["int8"],
                    help="blockwise-int8 wire format for the mp "
                         "all-reduces (~4x fewer interconnect bytes, "
                         "approximate logits); incompatible with "
                         "--speculate and --enable_prefix_cache")
    ap.add_argument("--kv_dtype", default=None, choices=["int8", "int4"],
                    help="paged-KV pool storage dtype: pages hold "
                         "quantized payloads with per-page-per-head "
                         "float32 scales, dequantized on read by every "
                         "page consumer (docs/SERVING.md 'Quantized KV "
                         "cache'); int8 roughly doubles resident "
                         "concurrency at equal pool bytes, int4 is "
                         "config-validated but not yet served")
    ap.add_argument("--weight_only", default=None,
                    choices=["int8", "int4"],
                    help="serve the checkpoint through weight-only "
                         "quantization: linear/MoE weights stored "
                         "int8/int4 and dequantized inline into the "
                         "matmul, halving (quartering) weight HBM "
                         "traffic for bs=1 decode")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel degree: stacked MoE expert "
                         "payloads shard over an 'ep' mesh axis (needs "
                         "a MoE checkpoint with num_experts divisible "
                         "by ep, and mp*dp_replicas*ep visible devices; "
                         "docs/SERVING.md 'MoE serving')")
    ap.add_argument("--num_experts", type=int, default=None,
                    help="deploy-time assertion on the checkpoint's "
                         "expert count (the value itself comes from the "
                         "model config) — a mismatch aborts startup "
                         "instead of serving the wrong model")
    ap.add_argument("--moe_top_k", type=int, default=None,
                    help="override the routing top_k baked into the "
                         "checkpoint config for this deployment "
                         "(routing changes data, never shapes — the "
                         "mixed-step executable is unaffected)")
    ap.add_argument("--capacity_factor", type=float, default=None,
                    help="override the MoE capacity factor for this "
                         "deployment: scales the fixed per-expert "
                         "buffer C = capacity(max_batch*token_budget); "
                         "lower trades dropped tokens for less padding "
                         "FLOPs/HBM (docs/SERVING.md 'MoE serving')")
    ap.add_argument("--moe_weight_only", default=None,
                    choices=["int8", "int4", "act_int8"],
                    help="quantize ONLY the stacked expert payloads: "
                         "int8/int4 weight-only (dequantized inline "
                         "into the expert einsum), or act_int8 "
                         "(int8 weights AND activations — also shrinks "
                         "the ep all-to-all dispatch leg; requires "
                         "--spec_accept_threshold under --speculate); "
                         "composes with --weight_only for the dense "
                         "linears")
    ap.add_argument("--spec_accept_threshold", type=float, default=None,
                    help="explicit speculative-acceptance margin in "
                         "(0, 1); required to combine kv_dtype=int4 "
                         "with --speculate (4-bit KV dequant error can "
                         "flip near-tie verify comparisons)")
    ap.add_argument("--adapter_dir", default=None,
                    help="multi-LoRA tenancy: directory of per-tenant "
                         "adapter checkpoints, one <id>.npz each with "
                         "arrays '<layer_path>.a' [d_in, r] / "
                         "'<layer_path>.b' [r, d_out] and an optional "
                         "scalar 'scale'; requests bind a tenant via a "
                         "per-request \"adapter_id\" body field "
                         "(docs/SERVING.md 'Multi-LoRA serving')")
    ap.add_argument("--adapter_rank", type=int, default=None,
                    help="the deployment's fixed LoRA rank r (required "
                         "with --adapter_dir): every adapter checkpoint "
                         "must carry exactly this rank — rank is part "
                         "of the mixed-step executable key, so it is a "
                         "deploy constant, never per-adapter")
    ap.add_argument("--adapter_slots", type=int, default=8,
                    help="device-resident adapter slots (slot 0 is the "
                         "reserved identity): bounds how many tenants "
                         "share HBM concurrently; the slot-LRU evicts "
                         "unpinned tenants beyond it")
    ap.add_argument("--kv_host_pages", type=int, default=0,
                    help="host-RAM KV tier capacity in KV pages (0 = "
                         "disabled): memory pressure PARKS victim rows "
                         "— KV pages + scheduler state swap to host, "
                         "resume bitwise later — instead of shedding, "
                         "and prefix-cache evictions demote full pages "
                         "for promote-on-hit (docs/SERVING.md 'KV "
                         "tiering and preemption')")
    ap.add_argument("--kv_park_watermark", type=float, default=0.95,
                    help="device-pool occupancy at or above which the "
                         "scheduler preemptively parks (predictive "
                         "park); actual allocation failures park "
                         "regardless")
    ap.add_argument("--kv_resume_watermark", type=float, default=0.70,
                    help="parked rows resume once the pool drains so "
                         "their reservation fits with the park/resume "
                         "watermark gap to spare (hysteresis — must be "
                         "< --kv_park_watermark; anti-starvation aging "
                         "lifts the gate after 16 scheduler steps)")
    ap.add_argument("--structured", action="store_true",
                    help="serve grammar-constrained requests: bodies "
                         "may carry grammar={'type': 'json_schema'|"
                         "'regex'|'json', ...}; specs compile to "
                         "token-level FSMs at admission (cached by "
                         "spec digest) and apply as per-row logit "
                         "masks inside the one mixed-step executable "
                         "(docs/SERVING.md 'Constrained decoding').  "
                         "The demo "
                         "token vocabulary is printable ASCII "
                         "(serving.default_vocab) — real deployments "
                         "wire their tokenizer's token strings here")
    ap.add_argument("--fleet_roles", default=None,
                    help="disaggregated fleet: comma-separated replica "
                         "roles, e.g. 'prefill,decode,mixed' — one "
                         "EngineCore + supervisor per role behind a "
                         "prefix-affinity FleetRouter with KV page "
                         "handoff at chunk boundaries (docs/SERVING.md "
                         "'Disaggregated serving'); incompatible with "
                         "--mp/--dp_replicas/--speculate/"
                         "--fault_script")
    ap.add_argument("--prefix_affinity", default="on",
                    choices=("on", "off"),
                    help="fleet routing: steer each request to the "
                         "replica whose radix tree holds its longest "
                         "prefix (confirmed via the read-only "
                         "PrefixCache.peek); 'off' leaves pure "
                         "least-predicted-load dispatch")
    args = ap.parse_args(argv)

    from paddle_infer_tpu.utils.compile_cache import \
        configure_compile_cache

    _STATE["compile_cache_dir"] = configure_compile_cache()

    from paddle_infer_tpu.models import AutoModel
    from paddle_infer_tpu.serving import (ServingMesh, ShardedConfigError,
                                          parse_fleet_roles,
                                          validate_serving_config)

    fleet_roles = None
    if args.fleet_roles:
        try:
            fleet_roles = parse_fleet_roles(args.fleet_roles)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr, flush=True)
            return 2
        incompatible = [name for name, on in (
            ("--mp > 1", args.mp > 1),
            ("--dp_replicas > 1", args.dp_replicas > 1),
            ("--ep > 1", args.ep > 1),
            ("--quantized_allreduce", bool(args.quantized_allreduce)),
            ("--speculate", args.speculate),
            ("--fault_script", bool(args.fault_script)),
            # fleet replicas share one model object; per-replica
            # AdapterCaches would fight over the same slot pools
            ("--adapter_dir", bool(args.adapter_dir))) if on]
        if incompatible:
            print("error: --fleet_roles is incompatible with "
                  + ", ".join(incompatible)
                  + " (fleet replicas are single-device cores)",
                  file=sys.stderr, flush=True)
            return 2
    _STATE["fleet_roles"] = fleet_roles
    _STATE["prefix_affinity"] = args.prefix_affinity == "on"

    # model first: the MoE validation inputs (expert count, expert
    # arithmetic) come from the loaded checkpoint, not from flags
    _STATE["model"] = AutoModel.from_pretrained(args.model_dir)
    if args.moe_weight_only:
        # expert stacks only, BEFORE --weight_only so the dense pass
        # below finds no bare MoELayer left to double-convert
        from paddle_infer_tpu.parallel.moe import MoELayer
        from paddle_infer_tpu.quantization.moe import (Int8MoELayer,
                                                       WeightOnlyMoELayer)
        from paddle_infer_tpu.quantization.slim import _swap

        def _make(sub):
            if args.moe_weight_only == "act_int8":
                return Int8MoELayer.from_moe(sub)
            return WeightOnlyMoELayer.from_moe(
                sub, algo=f"weight_only_{args.moe_weight_only}")

        _swap(_STATE["model"], (MoELayer,), _make, None)
    if args.weight_only:
        from paddle_infer_tpu.quantization.weight_only import \
            quantize_model

        quantize_model(_STATE["model"],
                       algo=f"weight_only_{args.weight_only}")

    _STATE["adapter_store"] = None
    _STATE["adapter_slots"] = args.adapter_slots
    if args.adapter_dir:
        import glob
        import os

        from paddle_infer_tpu.serving import (AdapterError, AdapterStore,
                                              adapter_layer_spec)

        if not args.adapter_rank:
            print("error: --adapter_dir needs --adapter_rank (the "
                  "deployment's fixed LoRA rank)",
                  file=sys.stderr, flush=True)
            return 2
        spec = adapter_layer_spec(_STATE["model"])
        try:
            store = AdapterStore(spec, rank=args.adapter_rank)
            paths = sorted(glob.glob(
                os.path.join(args.adapter_dir, "*.npz")))
            for ckpt in paths:
                aid = os.path.splitext(os.path.basename(ckpt))[0]
                data = np.load(ckpt)
                factors = {}
                for key in data.files:
                    if key.endswith(".a"):
                        lp = key[:-len(".a")]
                        factors[lp] = (data[key], data[lp + ".b"])
                scale = (float(data["scale"])
                         if "scale" in data.files else 1.0)
                store.add(aid, factors, scale=scale)
        except (AdapterError, KeyError, MemoryError, ValueError) as e:
            print(f"error: bad adapter checkpoint in "
                  f"{args.adapter_dir}: {e}", file=sys.stderr, flush=True)
            return 2
        if not store.adapter_ids():
            print(f"error: --adapter_dir {args.adapter_dir} holds no "
                  "*.npz adapter checkpoints",
                  file=sys.stderr, flush=True)
            return 2
        _STATE["adapter_store"] = store
        print(f"adapters: {len(store.adapter_ids())} registered "
              f"(rank {store.rank}, {args.adapter_slots} device slots)",
              flush=True)

    from paddle_infer_tpu.serving import moe_serving_info

    try:
        moe = moe_serving_info(_STATE["model"])
    except ShardedConfigError as e:
        print(f"error: unservable MoE checkpoint: {e}",
              file=sys.stderr, flush=True)
        return 2
    if moe is None and (args.moe_weight_only or args.num_experts
                        or args.moe_top_k or args.capacity_factor):
        print("error: --moe_* / --num_experts / --capacity_factor need "
              "a MoE checkpoint; this model has no MoE layers",
              file=sys.stderr, flush=True)
        return 2
    if moe is not None:
        if args.num_experts and args.num_experts != moe["num_experts"]:
            print(f"error: --num_experts {args.num_experts} does not "
                  f"match the checkpoint ({moe['num_experts']} experts)",
                  file=sys.stderr, flush=True)
            return 2
        if args.moe_top_k or args.capacity_factor:
            from paddle_infer_tpu.serving.moe.layer import \
                _iter_moe_layers

            for lay in _iter_moe_layers(_STATE["model"]):
                if args.moe_top_k:
                    lay.top_k = int(args.moe_top_k)
                if args.capacity_factor:
                    lay.capacity_factor = float(args.capacity_factor)
            moe = moe_serving_info(_STATE["model"])

    serving_mesh = ServingMesh(
        mp=args.mp, dp_replicas=args.dp_replicas,
        quantized_allreduce=args.quantized_allreduce, ep=args.ep)
    try:
        import jax

        validate_serving_config(
            serving_mesh, speculate=args.speculate,
            enable_prefix_cache=args.enable_prefix_cache,
            max_batch=args.max_batch,
            available_devices=len(jax.devices()),
            kv_dtype=args.kv_dtype,
            spec_accept_threshold=args.spec_accept_threshold,
            num_experts=moe["num_experts"] if moe else None,
            moe_quant=moe["algo"] if moe else None)
    except ShardedConfigError as e:
        print(f"error: invalid sharded-serving config: {e}",
              file=sys.stderr, flush=True)
        return 2
    _STATE["serving_mesh"] = serving_mesh
    if args.kv_dtype == "int4":
        print("error: kv_dtype=int4 validates at config level but the "
              "engine does not serve int4 pools yet — use kv_dtype=int8",
              file=sys.stderr, flush=True)
        return 2
    _STATE["kv_dtype"] = args.kv_dtype
    if args.kv_host_pages < 0:
        print(f"error: --kv_host_pages must be >= 0, got "
              f"{args.kv_host_pages}", file=sys.stderr, flush=True)
        return 2
    if args.kv_host_pages and not (
            0.0 < args.kv_resume_watermark
            < args.kv_park_watermark <= 1.0):
        print("error: watermarks must satisfy 0 < --kv_resume_watermark "
              "< --kv_park_watermark <= 1 (hysteresis gap), got "
              f"resume={args.kv_resume_watermark} "
              f"park={args.kv_park_watermark}",
              file=sys.stderr, flush=True)
        return 2
    _STATE["kv_host_pages"] = args.kv_host_pages
    _STATE["kv_park_watermark"] = args.kv_park_watermark
    _STATE["kv_resume_watermark"] = args.kv_resume_watermark
    _STATE["spec_accept_threshold"] = args.spec_accept_threshold
    _STATE["page_size"] = args.page_size
    _STATE["max_batch"] = args.max_batch
    _STATE["max_queue"] = args.max_queue
    _STATE["request_timeout"] = args.request_timeout
    _STATE["max_model_len"] = args.max_model_len
    _STATE["enable_prefix_cache"] = args.enable_prefix_cache
    _STATE["prefix_cache_watermark"] = args.prefix_cache_watermark
    _STATE["prefix_cache_headroom_pages"] = args.prefix_cache_headroom_pages
    _STATE["grammar_vocab"] = None
    if args.structured:
        from paddle_infer_tpu.serving import default_vocab

        mcfg = _STATE["model"].config
        specials = tuple(
            s for s in (getattr(mcfg, "eos_token_id", None),
                        getattr(mcfg, "pad_token_id", None))
            if s is not None)
        _STATE["grammar_vocab"] = default_vocab(
            int(mcfg.vocab_size), specials=specials)
    _STATE["token_budget"] = args.token_budget
    _STATE["prefill_chunk"] = args.prefill_chunk
    _STATE["sched_policy"] = args.sched_policy
    _STATE["slo_ttft_s"] = (args.slo_ttft_ms / 1e3
                            if args.slo_ttft_ms is not None else None)
    _STATE["slo_itl_s"] = (args.slo_itl_ms / 1e3
                           if args.slo_itl_ms is not None else None)
    _STATE["draft_model"] = (AutoModel.from_pretrained(args.draft_dir)
                             if args.draft_dir else None)
    _STATE["num_draft_tokens"] = args.num_draft_tokens
    _STATE["speculate"] = args.speculate
    _STATE["draft_source"] = args.draft_source
    _STATE["watchdog_s"] = args.watchdog_s
    _STATE["max_retries"] = args.max_retries
    fault_script = args.fault_script
    if fault_script and fault_script.startswith("@"):
        with open(fault_script[1:]) as f:
            fault_script = f.read()
    _STATE["fault_script"] = fault_script
    _STATE["fault_seed"] = args.fault_seed
    server = ThreadingHTTPServer(("127.0.0.1", args.port), Handler)
    print(f"serving {type(_STATE['model']).__name__} on "
          f"127.0.0.1:{args.port} runtime={json.dumps(_runtime_info())}",
          flush=True)
    server.serve_forever()


if __name__ == "__main__":
    sys.exit(main())
