"""FleetRouter: one front end over N EngineCore replicas.

Dispatch pipeline (``submit``):

  1. **health gate** — only replicas whose HealthMonitor ``is_serving()``
     (HEALTHY/DEGRADED) are dispatch candidates; DRAINING/DOWN replicas
     keep stepping their in-flight work but receive nothing new, and
     their queued-not-yet-slotted admissions are reclaimed and rerouted
     by the router tick (``run_once``).
  2. **role gate** — prompts at/above ``prefill_threshold`` go to
     prefill-capable replicas (and, when the chosen replica is a
     dedicated ``prefill`` role, are registered for KV handoff to a
     decode replica once their prompt finishes prefilling); shorter
     prompts go to decode-capable replicas.  If no role-matching
     replica is serving, any serving replica takes the request — roles
     are policy, not capability.
  3. **prefix affinity** — the shadow radix index ranks candidates by
     predicted longest-prefix match; the top predictions are confirmed
     with the read-only ``PrefixCache.peek()`` (no pins, no LRU
     movement) and the longest confirmed match of at least one page
     wins.  Affinity compounds: handoff exports retain the prompt
     prefix in the PREFILL replica's tree, so related prompts keep
     landing where their prefix lives.
  4. **load fallback** — no confirmed prefix: the replica with the
     least predicted next-step bytes (StepCostModel analytic estimate)
     takes it.

The router tick (``run_once``) steps the replicas (when not running
their own threads), performs due handoffs, applies the elastic role
policy to ``mixed``-configured replicas, and reroutes admissions
stranded on non-serving replicas.  All router state is process-local;
replicas are in-process cores each owning its own engine and KV pool.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...inference.generation import GenerationConfig
from ..request import (LoadShedError, RejectedError, Request,
                       effective_salt)
from .elastic import ElasticRolePolicy
from .handoff import migrate, ready_for_handoff
from .roles import ReplicaHandle, ReplicaRole
from .shadow import ShadowPrefixIndex


class FleetRouter:
    """Prefix-affinity, health-gated, role-aware dispatch over replica
    handles.  Thread-safe: ``submit`` may race the router tick."""

    def __init__(self, replicas: Sequence[ReplicaHandle], *,
                 prefix_affinity: bool = True,
                 prefill_threshold: Optional[int] = None,
                 elastic: Optional[ElasticRolePolicy] = None):
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        names = [h.name for h in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names: {names}")
        from ..sharded import validate_cache_layout

        # a dedicated prefill role hands its rows' KV to a decode
        # replica; what cannot be handed off is refused here
        handoff = any(h.role == ReplicaRole.PREFILL for h in replicas)
        for h in replicas:
            validate_cache_layout(
                getattr(h.core._engine, "_cache_layout", None),
                handoff=handoff)
        self._replicas: List[ReplicaHandle] = list(replicas)
        self._by_name: Dict[str, ReplicaHandle] = {
            h.name: h for h in replicas}
        self._page = int(max(h.core._page for h in replicas))
        self._affinity = bool(prefix_affinity)
        self._shadow = ShadowPrefixIndex(self._page)
        # a prompt longer than one prefill chunk cannot finish in one
        # step — that is the interference the prefill tier absorbs
        self._prefill_threshold = int(
            prefill_threshold if prefill_threshold is not None
            else max(h.core._prefill_chunk for h in replicas) + 1)
        self._elastic = elastic
        self._lock = threading.Lock()
        # rid -> (request, owning handle); pruned as requests finish
        self._inflight: Dict[int, Tuple[Request, ReplicaHandle]] = {}
        # rid set registered for prefill->decode handoff
        self._want_handoff: Dict[int, None] = {}
        self._emitted_seen: Dict[int, int] = {}
        # last-observed serving state per replica, so the tick can drop
        # a replica's shadow entries the moment it stops serving
        self._was_serving: Dict[str, bool] = {
            h.name: h.is_serving() for h in self._replicas}
        self._tick_prefill_tokens = 0
        # fleet-wide counters for the router_* families
        self.requeued = 0
        self.handoffs = 0
        self.no_replica_rejects = 0
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        # chunk-boundary handoff: each core calls back from its OWN
        # stepping thread the step a prompt finishes prefilling, so the
        # migration happens exactly at the boundary.  The router tick's
        # _do_handoffs scan stays as the fallback (e.g. the destination
        # lock was contended at the boundary).
        for h in self._replicas:
            h.core.on_prefill_complete = (
                lambda req, _h=h: self._boundary_handoff(_h, req))

    # --------------------------------------------------------- topology
    @property
    def replicas(self) -> List[ReplicaHandle]:
        return list(self._replicas)

    def replica(self, name: str) -> ReplicaHandle:
        return self._by_name[name]

    def _serving(self) -> List[ReplicaHandle]:
        return [h for h in self._replicas if h.is_serving()]

    # --------------------------------------------------------- dispatch
    def submit(self, prompt, config: GenerationConfig = None,
               timeout_s: Optional[float] = None,
               cache_salt: Optional[str] = None,
               adapter_id: Optional[str] = None,
               tenant: Optional[str] = None,
               grammar: Optional[dict] = None) -> Request:
        """Route ONE prompt (1-D token array) to a replica and return
        its ``Request`` handle.  Raises ``LoadShedError`` (a
        ``RejectedError``, but retryable — a fully draining fleet is an
        availability condition, not a bad request, so serve.py maps it
        to 503 + Retry-After like single-core draining) when no replica
        is serving; replica-level admission errors (queue full, too
        long, unknown adapter) propagate from the chosen core.
        ``adapter_id`` joins the routing salt — affinity never steers an
        adapter tenant onto another tenant's cached prefix — and rides
        handoff packets so the binding survives migration.  ``grammar``
        compiles (or cache-hits) on the chosen replica at admission and
        its per-row FSM state rides handoff packets as plain data."""
        ids = np.asarray(prompt, np.int32).reshape(-1)
        g = config or GenerationConfig()
        serving = self._serving()
        if not serving:
            self.no_replica_rejects += 1
            raise LoadShedError("no serving replica in the fleet")
        long_prompt = int(ids.size) >= self._prefill_threshold
        want = (ReplicaHandle.accepts_prefill if long_prompt
                else ReplicaHandle.accepts_decode)
        candidates = [h for h in serving if want(h)] or serving
        t0 = time.monotonic()
        # the same composed salt the replicas key their radix trees on
        # (Request.route_salt) — shadow, peek and tree must agree
        salt = effective_salt(cache_salt, adapter_id)
        handle, reason, match = self._pick(candidates, ids, salt)
        req = handle.core.submit(ids, g, timeout_s=timeout_s,
                                 cache_salt=cache_salt,
                                 adapter_id=adapter_id,
                                 tenant=tenant, grammar=grammar)[0]
        handle.dispatched += 1
        if reason == "affinity":
            handle.affinity_hits += 1
        # the finished sequence retains prompt + tokens[:-1]; the prompt
        # is the durable part worth shadowing now
        self._shadow.observe(handle.name, ids, salt)
        # the replica's stepping thread may finish (and end) this trace
        # before the router stamps the route span; add_span lands on the
        # 256-ring copy in that case, which is exactly what we want
        # tpulint: disable-next-line=tracer-leak -- add_span is ring-safe after end() by design
        handle.core.tracer.add_span(
            req.rid, "route", t0, time.monotonic(), replica=handle.name,
            role=handle.role.value, reason=reason, prefix_match=match)
        with self._lock:
            self._inflight[req.rid] = (req, handle)
            self._emitted_seen[req.rid] = 0
            self._tick_prefill_tokens += int(ids.size)
            if (long_prompt and handle.role is ReplicaRole.PREFILL
                    and any(h is not handle and h.accepts_decode()
                            for h in serving)):
                self._want_handoff[req.rid] = None
        return req

    def _pick(self, candidates: List[ReplicaHandle], ids,
              salt) -> Tuple[ReplicaHandle, str, int]:
        """(handle, reason, confirmed_prefix_len) for one dispatch.
        ``salt`` is the COMPOSED routing salt (``effective_salt`` of
        cache_salt and adapter_id) — the key the replicas' radix trees
        and the shadow index both use."""
        by_load = sorted(candidates,
                         key=lambda h: h.predicted_load_bytes())
        if self._affinity and ids.size > 1:
            ranked = self._shadow.rank([h.name for h in by_load], ids,
                                       salt)
            # confirm only replicas the shadow predicts hold at least
            # one page, and at most the top two — peek() takes the
            # candidate's tree lock, and probing every replica per
            # dispatch would serialize the router on N locks (the exact
            # cost the shadow exists to avoid)
            best_h, best_len, probed = None, 0, 0
            for name, pred in ranked:
                if pred < self._page or probed >= 2:
                    break
                h = self._by_name[name]
                cache = h.core.prefix_cache
                if cache is None:
                    continue
                probed += 1
                confirmed = cache.peek(ids, salt=salt)
                if confirmed > best_len:
                    best_h, best_len = h, confirmed
                if confirmed >= self._page:
                    # a confirmed hit refreshes the shadow (peek feeds
                    # the index; stale entries self-correct here)
                    self._shadow.observe(name, ids[:confirmed], salt)
            if best_h is not None and best_len >= self._page:
                return best_h, "affinity", best_len
        return by_load[0], "load", 0

    # ------------------------------------------------------ router tick
    def run_once(self, wait_s: float = 0.0) -> bool:
        """One router iteration: step replicas (tests drive unstarted
        cores directly), perform due handoffs, apply the elastic
        policy, reroute stranded admissions, prune finished requests.
        Returns True when anything progressed."""
        progressed = False
        threaded = self._thread is not None
        for h in self._replicas:
            if not threaded and not h.core._closed:
                # DRAINING replicas keep stepping: their in-flight
                # requests finish in place, only dispatch stops
                progressed |= bool(h.core.run_once(wait_s=0.0))
        progressed |= self._do_handoffs()
        progressed |= self._reroute_stranded()
        self._forget_unserving()
        self._apply_elastic()
        self._prune_and_observe()
        if not progressed and wait_s > 0.0:
            time.sleep(min(wait_s, 0.005))
        return progressed

    def _boundary_handoff(self, src: ReplicaHandle, req: Request) -> None:
        """Migrate ``req`` off ``src`` the step its prompt finishes
        prefilling.  Runs in src's STEPPING thread under src's step
        RLock (the ``on_prefill_complete`` hook), so readiness cannot
        decay between the check and the export.  The destination's step
        lock is acquired with a bound: two cores hooking into each
        other at the same instant back off instead of deadlocking, and
        the router tick retries the move opportunistically."""
        with self._lock:
            if req.rid not in self._want_handoff:
                return
        dst = self._handoff_target(src)
        if dst is None:
            return
        if not dst.core._step_lock.acquire(timeout=0.1):
            return
        try:
            ok = migrate(req, src, dst)
        finally:
            dst.core._step_lock.release()
        with self._lock:
            self._want_handoff.pop(req.rid, None)
            if ok:
                self._inflight[req.rid] = (req, dst)
                self.handoffs += 1

    def _do_handoffs(self) -> bool:
        with self._lock:
            due = [(rid, *self._inflight[rid])
                   for rid in list(self._want_handoff)
                   if rid in self._inflight]
        moved = False
        req: Request
        src: ReplicaHandle
        for rid, req, src in due:
            if req.done:
                with self._lock:
                    self._want_handoff.pop(rid, None)
                continue
            dst = self._handoff_target(src)
            if dst is None:
                continue
            # one step-lock win covers the ready check AND the export
            # (RLock): the source's stepping thread holds this lock
            # nearly back-to-back, so a second acquisition can land
            # many steps later — or after the request finished, turning
            # a due handoff into a silent miss.  BOTH step locks are
            # taken with a bound: the tick thread ordering src-then-dst
            # against stepping threads ordering own-then-other is a
            # lock-order cycle, and a contended boundary just means the
            # next tick retries the move.
            if not src.core._step_lock.acquire(timeout=0.1):
                continue
            try:
                if not ready_for_handoff(src.core, req):
                    continue
                if not dst.core._step_lock.acquire(timeout=0.1):
                    continue
                try:
                    ok = migrate(req, src, dst)
                finally:
                    dst.core._step_lock.release()
            finally:
                src.core._step_lock.release()
            with self._lock:
                self._want_handoff.pop(rid, None)
                if ok:
                    self.handoffs += 1
                    self._inflight[rid] = (req, dst)
            moved = moved or ok
        return moved

    def _handoff_target(self,
                        src: ReplicaHandle) -> Optional[ReplicaHandle]:
        # approx_active_count / raw _effective_max_batch on purpose:
        # this scan runs on src's stepping thread (boundary hook) under
        # src's step lock — the exact, LOCKED ``active_count`` property
        # here would acquire every candidate's step lock, and two cores
        # hooking into each other at the same instant would deadlock.
        cands = [h for h in self._serving()
                 if h is not src and h.accepts_decode()
                 and h.core.approx_active_count()
                 < h.core._effective_max_batch]
        if not cands:
            return None
        return min(cands, key=lambda h: h.predicted_load_bytes())

    def _reroute_stranded(self) -> bool:
        """Reclaim queued-not-yet-slotted admissions from non-serving
        replicas and re-admit them elsewhere (rid is preserved, so the
        sampled stream is bitwise wherever the request lands).  In-slot
        requests are left alone: DRAINING finishes them in place, DOWN
        goes through the supervisor's replay/quarantine path."""
        any_moved = False
        for h in self._replicas:
            if h.is_serving() or h.core.queue_depth == 0:
                continue
            stranded = h.core._queue.drain()
            keep = [r for r in stranded if r.kind != "batch"]
            for r in keep:
                # exclusives can't be rerouted (their fn closes over
                # this replica's engine) — they finish during drain
                h.core._queue.push_front(r)
            for r in [r for r in stranded if r.kind == "batch"]:
                target = self._route_requeue(r)
                if target is None:
                    h.core._queue.push_front(r)
                    continue
                try:
                    target.core.enqueue(r)
                except RejectedError:
                    # the target filled or started draining between the
                    # _serving() check and the enqueue; back to the
                    # source HEAD (push_front bypasses the depth bound)
                    # so a drained request is never lost — the next
                    # tick retries against a fresh target
                    h.core._queue.push_front(r)
                    continue
                target.dispatched += 1
                self.requeued += 1
                with self._lock:
                    if r.rid in self._inflight:
                        self._inflight[r.rid] = (r, target)
                any_moved = True
        return any_moved

    def _route_requeue(self, req: Request) -> Optional[ReplicaHandle]:
        serving = self._serving()
        if not serving:
            return None
        long_prompt = int(req.prompt.size) >= self._prefill_threshold
        want = (ReplicaHandle.accepts_prefill if long_prompt
                else ReplicaHandle.accepts_decode)
        cands = [h for h in serving if want(h)] or serving
        return min(cands, key=lambda h: h.predicted_load_bytes())

    def _forget_unserving(self):
        """Drop shadow entries for replicas that stopped serving.  A
        DRAINING/DOWN replica's retained prefixes are unroutable, and a
        restarted core comes back with an EMPTY tree — stale shadow
        entries would keep attracting affinity probes (wasted peeks,
        skewed routing) until the node budget happened to clear them."""
        for h in self._replicas:
            serving = h.is_serving()
            if self._was_serving.get(h.name, True) and not serving:
                self._shadow.forget(h.name)
            self._was_serving[h.name] = serving

    def _apply_elastic(self):
        if self._elastic is None:
            return
        with self._lock:
            prefill_toks = self._tick_prefill_tokens
            self._tick_prefill_tokens = 0
            decode_toks = 0
            for rid, (req, _h) in self._inflight.items():
                seen = self._emitted_seen.get(rid, 0)
                now = req.emitted
                if now > seen:
                    decode_toks += now - seen
                    self._emitted_seen[rid] = now
        self._elastic.observe(prefill_toks, decode_toks)
        # one flip per tick, and never one that would leave the fleet
        # without a serving prefill- or decode-capable replica
        for h in self._replicas:
            if h.configured_role is not ReplicaRole.MIXED:
                continue
            target = self._elastic.decide(h.role)
            if target is None or target is h.role:
                continue
            others = [o for o in self._serving() if o is not h]
            if (target is ReplicaRole.PREFILL
                    and not any(o.accepts_decode() for o in others)):
                continue
            if (target is ReplicaRole.DECODE
                    and not any(o.accepts_prefill() for o in others)):
                continue
            h.set_role(target)
            # the dwell clock starts at the COMMITTED flip, not at
            # decide() — a coverage-guard rejection above must not
            # suppress later flips for min_dwell_s
            self._elastic.committed()
            if not h.accepts_prefill():
                # flipped away from prefill: the tree stops
                # accumulating the fleet's prefixes, so the shadow
                # re-learns this replica from live traffic
                self._shadow.forget(h.name)
            break

    def _prune_and_observe(self):
        with self._lock:
            done = [rid for rid, (req, _h) in self._inflight.items()
                    if req.done]
            for rid in done:
                req, handle = self._inflight.pop(rid)
                self._emitted_seen.pop(rid, None)
                self._want_handoff.pop(rid, None)

    # ---------------------------------------------------------- threads
    def start(self, start_cores: bool = True) -> "FleetRouter":
        """Run every replica's scheduler thread plus one router thread
        (handoffs / elastic / rerouting).  Streams stay bitwise under
        threading — schedule independence is the serving plane's core
        parity invariant.  ``start_cores=False`` spins only the router
        thread, for deployments where supervisors own the scheduler
        threads (tools/serve.py)."""
        if self._thread is not None:
            return self
        self._started_cores = bool(start_cores)
        if start_cores:
            for h in self._replicas:
                h.core.start()
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._loop, name="fleet-router", daemon=True)
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop_evt.is_set():
            try:
                self.run_once()
            except Exception:       # pragma: no cover - belt and braces
                import logging
                logging.getLogger(__name__).exception("router tick")
            self._stop_evt.wait(0.002)

    def stop(self):
        if self._thread is not None:
            self._stop_evt.set()
            self._thread.join(timeout=5.0)
            self._thread = None
        if getattr(self, "_started_cores", True):
            for h in self._replicas:
                h.core.stop()

    def close(self):
        self.stop()
        for h in self._replicas:
            h.core.close()

    # ---------------------------------------------------------- metrics
    def snapshot(self) -> dict:
        """The ``router`` section of a metrics snapshot — everything the
        ``router_*`` Prometheus families render from."""
        reps = [h.snapshot() for h in self._replicas]
        dispatched = sum(r["dispatched"] for r in reps)
        hits = sum(r["affinity_hits"] for r in reps)
        with self._lock:
            pending_handoffs = len(self._want_handoff)
            inflight = len(self._inflight)
            handoffs = self.handoffs
        snap = {
            "replicas": reps,
            "dispatched": dispatched,
            "affinity_hits": hits,
            "affinity_hit_rate": hits / dispatched if dispatched else 0.0,
            "handoffs": handoffs,
            "requeued": self.requeued,
            "no_replica_rejects": self.no_replica_rejects,
            "pending_handoffs": pending_handoffs,
            "inflight": inflight,
            "prefill_threshold": self._prefill_threshold,
            "shadow": self._shadow.stats(),
        }
        if self._elastic is not None:
            snap["elastic"] = self._elastic.snapshot()
        return snap
