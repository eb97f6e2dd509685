"""Serving metrics registry.

Lock-protected counters plus bounded reservoirs for the latency
distributions the serving loop cares about: time-to-first-token,
inter-token latency, end-to-end latency, decode-step wall time, and
batch occupancy.  ``snapshot()`` renders everything to a plain dict so
``tools/serve.py`` can dump it as the ``GET /metrics`` JSON body and
``bench.py`` can read TTFT percentiles without scraping logs.

Percentiles come from a fixed-size tail reservoir (last N samples, not
a sketch) — good enough for a serving dashboard and O(1) memory.
Token throughput is measured over a sliding window of recent
(timestamp, count) emission events so the reported tokens/s reflects
steady state rather than lifetime average.

Each latency series the Prometheus exposition cares about (TTFT, ITL,
e2e, step wall, queue wait) is additionally fed into a log-bucketed
``observability.histogram.Histogram`` so ``/metrics`` can render native
``_bucket``/``_sum``/``_count`` families — mergeable across replicas,
re-quantileable server-side — while the reservoir ``*_recent`` keys
stay in the JSON snapshot for bench.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Optional

from bisect import bisect_left

from ..observability.histogram import DEFAULT_BOUNDS, Histogram
from ..observability.stable import sorted_tree
from ..observability.journey import BUCKETS as _JOURNEY_BUCKETS

_RESERVOIR = 2048        # samples kept per latency series
_RATE_WINDOW_S = 30.0    # sliding window for tokens/s


def _percentile(samples, q: float) -> Optional[float]:
    if not samples:
        return None
    s = sorted(samples)
    idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return float(s[idx])


class _Series:
    """Bounded sample reservoir (keeps the most recent samples).

    Two windows coexist in one summary and dashboards must not mix them
    up: ``count``/``mean`` are LIFETIME aggregates over every sample
    ever added, while the percentiles/max are computed over only the
    most recent ``window`` samples (≤ ``_RESERVOIR``) still in the
    reservoir — hence the explicit ``*_recent`` key names.  A p99 that
    looks great while the lifetime mean is bad means the bad tail has
    already been evicted from the reservoir."""

    def __init__(self, maxlen: int = _RESERVOIR):
        self._d: deque = deque(maxlen=maxlen)
        self.count = 0
        self.total = 0.0

    def add(self, v: float):
        self._d.append(float(v))
        self.count += 1
        self.total += float(v)

    def summary(self) -> Dict[str, Optional[float]]:
        d = list(self._d)
        return {
            "count": self.count,                      # lifetime
            "mean": (self.total / self.count) if self.count else None,
            "window": len(d),        # samples behind the *_recent stats
            "p50_recent": _percentile(d, 0.50),
            "p99_recent": _percentile(d, 0.99),
            "max_recent": max(d) if d else None,
        }


class ServingMetrics:
    """Thread-safe registry shared by EngineCore and the HTTP layer."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        # ``_lock`` is always bound before reset() can run (first
        # statement of __init__) — a getattr fallback here would
        # silently guard with a throwaway lock
        with self._lock:
            self.submitted = 0
            self.completed = 0
            self.failed = 0
            self.rejected_queue_full = 0
            self.rejected = 0               # other admission rejections
            self.cancelled_deadline = 0
            self.tokens_generated = 0
            self.prefills = 0
            self.decode_steps = 0
            # in-engine speculative decoding (EngineCore speculate=True)
            self.spec_rows = 0              # row-steps that carried drafts
            self.spec_drafts_proposed = 0
            self.spec_drafts_accepted = 0
            # MoE routing counters (serving/moe/): per-expert valid
            # token-expert assignments kept, capacity-overflow drops,
            # and the latest gate aux loss — fed once per mixed step
            self.moe_expert_tokens: list = []
            self.moe_tokens_dropped = 0
            self.moe_aux_loss_last = 0.0
            # a model whose attention reads an indexer's selection:
            # query-key pairs one layer's index scored, and of those
            # the pairs its attention then read
            self.index_scored_keys = 0
            self.index_selected_keys = 0
            # a model whose router also scores identity experts: valid
            # assignments its dropless layers made, and of those the
            # ones to an identity expert (they compute nothing)
            self.moe_assignments = 0
            self.moe_assignments_identity = 0
            # resilience counters (serving/resilience/) — rendered as
            # their own Prometheus families (engine_restarts_total, …),
            # NOT through the auto-named serving_*_total counters block
            self.engine_restarts = 0
            self.request_retries = 0
            self.watchdog_trips = 0
            self.requests_quarantined = 0
            self.requests_shed = 0
            # SLO scheduler (serving/sched/): queued requests shed
            # because their PREDICTED completion missed the deadline
            # (distinct from requests_shed, the headroom ladder)
            self.requests_shed_predicted = 0
            self.loop_exceptions = 0
            self.ttft = _Series()
            self.itl = _Series()            # inter-token latency (s)
            self.e2e = _Series()
            self.step_ms = _Series()        # one fused decode step (ms)
            self.occupancy = _Series()      # active rows / max_batch
            self._emits: deque = deque()    # (t, ntokens) rate window
            # native-histogram twins of the latency series (seconds
            # throughout; Histogram has its own inner lock)
            self.ttft_hist = Histogram()
            self.itl_hist = Histogram()
            self.e2e_hist = Histogram()
            self.step_wall_hist = Histogram()
            self.queue_wait_hist = Histogram()
            # per-tenant SLO accounting (observability/journey.py):
            # tenant -> counters + a native e2e histogram + attribution
            # bucket sums + exemplar journey_ids keyed by the histogram
            # bucket each observation landed in, so a p99 spike links
            # directly to the journeys that caused it
            self._tenants: Dict[str, dict] = {}

    # ------------------------------------------------ recording hooks
    def on_submitted(self, n: int = 1):
        with self._lock:
            self.submitted += n

    def on_rejected_queue_full(self, n: int = 1):
        with self._lock:
            self.rejected_queue_full += n

    def on_rejected(self, n: int = 1):
        with self._lock:
            self.rejected += n

    def on_deadline(self, n: int = 1):
        with self._lock:
            self.cancelled_deadline += n

    def on_failed(self, n: int = 1):
        with self._lock:
            self.failed += n

    def on_prefill(self, ttft_s: Optional[float] = None):
        with self._lock:
            self.prefills += 1
            if ttft_s is not None:
                self.ttft.add(ttft_s)
                self.ttft_hist.observe(ttft_s)

    def on_tokens(self, n: int, itl_s: Optional[float] = None):
        now = time.monotonic()
        with self._lock:
            self.tokens_generated += n
            self._emits.append((now, n))
            while self._emits and now - self._emits[0][0] > _RATE_WINDOW_S:
                self._emits.popleft()
            if itl_s is not None and n > 0:
                self.itl.add(itl_s)
                self.itl_hist.observe(itl_s)

    def on_step(self, wall_ms: float, active: int, max_batch: int):
        with self._lock:
            self.decode_steps += 1
            self.step_ms.add(wall_ms)
            self.step_wall_hist.observe(wall_ms / 1e3)
            if max_batch > 0:
                self.occupancy.add(active / max_batch)

    def on_spec(self, rows: int, proposed: int, accepted: int):
        """One mixed step verified ``proposed`` draft tokens across
        ``rows`` speculating rows and accepted ``accepted`` of them."""
        with self._lock:
            self.spec_rows += rows
            self.spec_drafts_proposed += proposed
            self.spec_drafts_accepted += accepted

    def on_moe(self, routed_per_expert, dropped: int, aux_loss: float):
        """One mixed step routed ``routed_per_expert[e]`` valid
        token-expert assignments into expert ``e`` (summed over MoE
        layers), dropped ``dropped`` to capacity overflow, and measured
        gate aux loss ``aux_loss``."""
        with self._lock:
            if len(self.moe_expert_tokens) < len(routed_per_expert):
                self.moe_expert_tokens.extend(
                    [0] * (len(routed_per_expert)
                           - len(self.moe_expert_tokens)))
            for e, n in enumerate(routed_per_expert):
                self.moe_expert_tokens[e] += int(n)
            self.moe_tokens_dropped += int(dropped)
            self.moe_aux_loss_last = float(aux_loss)

    def on_index(self, scored: int, selected: int):
        """One mixed step's indexer scored ``scored`` query-key pairs a
        layer and its attention read ``selected`` of them."""
        with self._lock:
            self.index_scored_keys += int(scored)
            self.index_selected_keys += int(selected)

    def on_identity_experts(self, total: int, identity: int):
        """One mixed step's dropless layers made ``total`` valid
        assignments, ``identity`` of them to identity experts."""
        with self._lock:
            self.moe_assignments += int(total)
            self.moe_assignments_identity += int(identity)

    def on_queue_wait(self, wait_s: float):
        """One request left the admission queue after ``wait_s``."""
        with self._lock:
            self.queue_wait_hist.observe(max(0.0, wait_s))

    def on_completed(self, e2e_s: Optional[float] = None):
        with self._lock:
            self.completed += 1
            if e2e_s is not None:
                self.e2e.add(e2e_s)
                self.e2e_hist.observe(e2e_s)

    def on_journey(self, tenant: Optional[str], e2e_s: float,
                   tokens: int, attained: bool, buckets: Dict[str, float],
                   coverage: float, journey_id: str):
        """One request's journey finished: fold its attribution summary
        into the per-tenant SLO families.  ``tenant`` is the accounting
        label from ``submit(tenant=)`` (untenanted traffic lands under
        ``"default"``); ``buckets`` is the journey's bucket-seconds
        decomposition and ``journey_id`` becomes the exemplar on the
        tenant e2e histogram bucket this observation lands in."""
        key = "default" if tenant is None else str(tenant)
        with self._lock:
            t = self._tenants.get(key)
            if t is None:
                t = self._tenants[key] = {
                    "requests": 0, "attained": 0, "tokens": 0,
                    "parked_seconds": 0.0,
                    "e2e_hist": Histogram(),
                    "buckets": {b: 0.0 for b in _JOURNEY_BUCKETS},
                    "exemplars": {},
                }
            t["requests"] += 1
            if attained:
                t["attained"] += 1
            t["tokens"] += int(tokens)
            t["parked_seconds"] += float(buckets.get("parked", 0.0))
            t["e2e_hist"].observe(e2e_s)
            for b, v in buckets.items():
                if b in t["buckets"]:
                    t["buckets"][b] += float(v)
            # latest exemplar per landing bucket; +Inf for overflow
            i = bisect_left(DEFAULT_BOUNDS, float(e2e_s))
            le = ("+Inf" if i >= len(DEFAULT_BOUNDS)
                  else str(DEFAULT_BOUNDS[i]))
            t["exemplars"][le] = {"journey_id": journey_id,
                                  "value": float(e2e_s)}

    # --------------------------------------------- resilience hooks
    def on_engine_restart(self, n: int = 1):
        with self._lock:
            self.engine_restarts += n

    def on_retry(self, n: int = 1):
        with self._lock:
            self.request_retries += n

    def on_watchdog_trip(self, n: int = 1):
        with self._lock:
            self.watchdog_trips += n

    def on_quarantined(self, n: int = 1):
        with self._lock:
            self.requests_quarantined += n

    def on_shed(self, n: int = 1):
        with self._lock:
            self.requests_shed += n

    def on_predictive_shed(self, n: int = 1):
        with self._lock:
            self.requests_shed_predicted += n

    def on_loop_exception(self, n: int = 1):
        with self._lock:
            self.loop_exceptions += n

    # ------------------------------------------------------ rendering
    def tokens_per_second(self) -> float:
        now = time.monotonic()
        with self._lock:
            while self._emits and now - self._emits[0][0] > _RATE_WINDOW_S:
                self._emits.popleft()
            if not self._emits:
                return 0.0
            span = max(now - self._emits[0][0], 1e-6)
            return sum(n for _, n in self._emits) / span

    def snapshot(self, queue_depth: int = 0, active: int = 0,
                 max_batch: int = 0,
                 kv_pool: Optional[Dict] = None,
                 prefix_cache: Optional[Dict] = None,
                 kv_quant: Optional[Dict] = None,
                 weight_only: Optional[Dict] = None,
                 resilience: Optional[Dict] = None,
                 steplog: Optional[Dict] = None,
                 device_memory: Optional[Dict] = None,
                 sharding: Optional[Dict] = None,
                 moe: Optional[Dict] = None,
                 adapters: Optional[Dict] = None,
                 sched: Optional[Dict] = None,
                 kv_tier: Optional[Dict] = None,
                 journeys: Optional[Dict] = None,
                 structured: Optional[Dict] = None) -> Dict:
        """Render everything to a plain dict (the ``GET /metrics`` JSON
        body).  Latency series carry lifetime ``count``/``mean`` plus
        reservoir-window ``p50_recent``/``p99_recent``/``max_recent``
        (see ``_Series``); ``histograms`` carries their native
        cumulative-bucket twins.  ``kv_pool`` is the block-pool
        occupancy gauge set supplied by ``EngineCore`` (total/used/free
        blocks); ``prefix_cache`` is ``PrefixCache.stats_snapshot()``
        when the core runs with prefix caching enabled; ``kv_quant`` is
        the core's quantized-KV-pool byte accounting and
        ``weight_only`` the model's weight-only payload summary, each
        present only when the feature is active; ``resilience``
        is the core's health/fault context (effective batch, health
        state, injected-fault tallies), merged here with this
        registry's own resilience counters; ``steplog`` is
        ``StepLog.summary()`` and ``device_memory`` the device
        allocator's ``memory_stats()`` dict when available;
        ``sharding`` is ``serving.sharded.sharding_snapshot`` (mesh
        shape, param placement tallies, collective-bytes ledger) when
        the core serves over a mesh; ``moe`` is the core's MoE plane
        info dict (``moe_serving_info`` + capacity/ep) — the section
        merges it with this registry's routing counters (per-expert
        utilization shares, skew = max share × E so 1.0 is perfectly
        balanced, dropped ratio over routed+dropped); ``sched`` is the
        core's SLO-scheduler section (policy, planner calibration,
        predictive sheds, predicted-vs-actual slack error), merged
        with this registry's predictive-shed counter; ``adapters`` is
        ``AdapterCache.summary()`` (slot residency/pins, hit rate,
        upload/eviction counters, host store stats) when the core
        serves multi-LoRA tenants; ``kv_tier`` is
        ``HostKVTier.summary()`` (parked requests, host-page residency,
        park/resume/demote/promote and swap-byte counters) when the
        core runs with a host-RAM KV tier; ``journeys`` is
        ``JourneyStore.summary()`` (finished-journey count, hop total,
        mean attribution coverage, aggregate bucket seconds) — the
        per-tenant SLO section is internal (fed by ``on_journey``) and
        rides along whenever any tenant finished a request;
        ``structured`` is the core's constrained-decoding section
        (grammar cache entries/hits/misses/compile seconds, active
        constrained rows, violation/incomplete/rejected tallies) when
        the core serves grammars."""
        tps = self.tokens_per_second()
        with self._lock:
            out = {
                "queue_depth": queue_depth,
                "active": active,
                "max_batch": max_batch,
                "batch_occupancy": (active / max_batch) if max_batch else 0.0,
                "counters": {
                    "submitted": self.submitted,
                    "completed": self.completed,
                    "failed": self.failed,
                    "rejected_queue_full": self.rejected_queue_full,
                    "rejected": self.rejected,
                    "cancelled_deadline": self.cancelled_deadline,
                    "tokens_generated": self.tokens_generated,
                    "prefills": self.prefills,
                    "decode_steps": self.decode_steps,
                    "spec_rows": self.spec_rows,
                    "spec_drafts_proposed": self.spec_drafts_proposed,
                    "spec_drafts_accepted": self.spec_drafts_accepted,
                },
                "speculation": {
                    "rows": self.spec_rows,
                    "drafts_proposed": self.spec_drafts_proposed,
                    "drafts_accepted": self.spec_drafts_accepted,
                    "acceptance_rate": (
                        self.spec_drafts_accepted
                        / self.spec_drafts_proposed
                        if self.spec_drafts_proposed else 0.0),
                    "wasted_ratio": (
                        (self.spec_drafts_proposed
                         - self.spec_drafts_accepted)
                        / self.spec_drafts_proposed
                        if self.spec_drafts_proposed else 0.0),
                },
                "tokens_per_second": tps,
                "ttft_s": self.ttft.summary(),
                "inter_token_latency_s": self.itl.summary(),
                "e2e_latency_s": self.e2e.summary(),
                "decode_step_ms": self.step_ms.summary(),
                "occupancy": self.occupancy.summary(),
                "histograms": {
                    "ttft": self.ttft_hist.snapshot(),
                    "itl": self.itl_hist.snapshot(),
                    "e2e": self.e2e_hist.snapshot(),
                    "step_wall": self.step_wall_hist.snapshot(),
                    "queue_wait": self.queue_wait_hist.snapshot(),
                },
            }
            if moe is not None:
                tokens = list(self.moe_expert_tokens)
                n_exp = int(moe.get("num_experts", len(tokens)) or 0)
                if len(tokens) < n_exp:
                    tokens.extend([0] * (n_exp - len(tokens)))
                routed = sum(tokens)
                dropped = self.moe_tokens_dropped
                util = [t / routed if routed else 0.0 for t in tokens]
                out["moe"] = dict(moe)
                out["moe"].update({
                    "expert_tokens": tokens,
                    "tokens_routed": routed,
                    "tokens_dropped": dropped,
                    "dropped_ratio": (dropped / (routed + dropped)
                                      if routed + dropped else 0.0),
                    "expert_utilization": util,
                    "utilization_skew": (max(util) * len(util)
                                         if util and routed else 0.0),
                    "gate_aux_loss": self.moe_aux_loss_last,
                })
            if self.index_scored_keys:
                out["indexer"] = {
                    "scored_keys": self.index_scored_keys,
                    "selected_keys": self.index_selected_keys,
                    "keep_share": (self.index_selected_keys
                                   / self.index_scored_keys)}
            if self.moe_assignments_identity:
                out["identity_experts"] = {
                    "assignments": self.moe_assignments,
                    "identity_assignments": self.moe_assignments_identity,
                    "identity_share": (self.moe_assignments_identity
                                       / self.moe_assignments)}
            if adapters is not None:
                out["adapters"] = dict(adapters)
            if kv_tier is not None:
                out["kv_tier"] = dict(kv_tier)
            if journeys is not None:
                out["journeys"] = dict(journeys)
            if structured is not None:
                out["structured"] = dict(structured)
            if self._tenants:
                out["tenants"] = {
                    name: {
                        "requests": t["requests"],
                        "attained": t["attained"],
                        "attainment": (t["attained"] / t["requests"]
                                       if t["requests"] else 0.0),
                        "tokens": t["tokens"],
                        "parked_seconds": t["parked_seconds"],
                        "e2e": t["e2e_hist"].snapshot(),
                        "buckets": dict(t["buckets"]),
                        "exemplars": {le: dict(ex) for le, ex
                                      in t["exemplars"].items()},
                    }
                    for name, t in sorted(self._tenants.items())}
            if sched is not None:
                # the core's scheduler section (policy, planner,
                # predicted-vs-actual slack), plus this registry's
                # predictive-shed counter so the Prometheus renderer
                # reads one self-contained dict
                out["sched"] = dict(sched)
                out["sched"].setdefault(
                    "requests_shed_predicted",
                    self.requests_shed_predicted)
            if steplog is not None:
                out["steplog"] = dict(steplog)
            if sharding is not None:
                out["sharding"] = dict(sharding)
            if device_memory:
                out["device_memory"] = dict(device_memory)
            if kv_pool is not None:
                out["kv_pool"] = dict(kv_pool)
            if prefix_cache is not None:
                out["prefix_cache"] = dict(prefix_cache)
            if kv_quant is not None:
                out["kv_quant"] = dict(kv_quant)
            if weight_only is not None:
                out["weight_only"] = dict(weight_only)
            res = dict(resilience) if resilience is not None else {
                "health_state": "healthy", "health_code": 0,
                "effective_max_batch": max_batch,
                "faults_injected": {}}
            res.update({
                "engine_restarts": self.engine_restarts,
                "request_retries": self.request_retries,
                "watchdog_trips": self.watchdog_trips,
                "requests_quarantined": self.requests_quarantined,
                "requests_shed": self.requests_shed,
                "loop_exceptions": self.loop_exceptions,
            })
            out["resilience"] = res
            # canonical key order at every level: the /metrics JSON
            # body is byte-stable across replicas and restarts
            return sorted_tree(out)

    def to_prometheus(self, snapshot: Optional[Dict] = None,
                      compile_summary: Optional[Dict] = None) -> str:
        """Prometheus text exposition of a snapshot (taken fresh when
        not given).  The renderer lives in ``observability.prometheus``;
        this is the convenience entry the HTTP layer calls."""
        from ..observability.prometheus import render_prometheus

        return render_prometheus(snapshot or self.snapshot(),
                                 compile_summary)
