"""EngineCore — the continuous-batching scheduler.

Each ``run_once()`` iteration (the loop body; a background thread just
repeats it):

  1. sweep deadlines — expired queued requests are cancelled before they
     cost a prefill; expired ACTIVE rows are evicted and their KV blocks
     freed immediately;
  2. run any exclusive requests at the queue head (engine calls the
     continuous batch can't host — beams, repetition penalty,
     speculative — executed on this thread so they never race the pool);
  3. admit queued requests into free KV-block slots: admission only
     stages KV (prefix-cache match, page reservation) and queues the
     uncached prompt suffix as ``pending`` token slices;
  4. run ONE mixed step for all active rows: live decode rows (one
     token each, packed first) plus up to ``prefill_chunk`` pending
     prompt tokens per row under a per-step ``token_budget``, in a
     single executable (serving/programs.build_mixed_step, backed by
     ops/pallas/ragged_paged_attention) — a long prompt interleaves
     with decode instead of stalling it, and a row's first token is
     emitted the step its last chunk runs (that's the TTFT sample);
  5. evict finished rows, free their pages, and loop — freed slots are
     backfilled at the next iteration's step 3, so a late-arriving
     request joins the SAME step as requests admitted long before it
     (``step_trace`` records the per-step active set to prove it).

There is no stop-the-world: admission, prefill chunks, decode and
eviction interleave at step granularity, and per-row sampling
parameters live in arrays (serving/programs.py), so one executable
serves every batch composition and nothing recompiles the hot loop.

Slot/pool layout: slot ``s`` (0..max_batch-1) reserves native-pool
sequence id ``s``; a one-page scratch reservation (seq id max_batch)
backs every table entry of inactive rows, so their garbage writes land
where no live row's attention can see them.
"""
from __future__ import annotations

import logging
import threading
import time
import traceback
from collections import deque
from typing import List, Optional

import jax
import numpy as np

from ..inference.cache_layout import has_index, has_latent
from ..inference.generation import (GenerationConfig, PagedGenerationEngine,
                                    _round_up)
from ..observability import Tracer, get_compile_log
from ..observability.journey import JourneyStore
from ..observability.stepclock import GcWatch, Span, StepClock
from ..observability.steplog import StepCostModel, StepLog
from ..ops.pallas.latent_attention import decode_grid_steps
from ..ops.pallas.sparse_latent_attention import gathered_rows
from .adapters import UnknownAdapterError
from .kv_tier import HostKVTier
from .metrics import ServingMetrics
from .prefix_cache import PrefixCache
from .programs import (DROPLESS_COUNTERS, IDENTITY_COUNTERS,
                       RESIDUAL_COUNTERS, build_mixed_step,
                       build_page_copy, sampling_rows,
                       step_input_layout, step_output_layout)
from .request import (DeadlineExceededError, GrammarError,
                      GrammarIncompleteError, HandoffError, LoadShedError,
                      QuarantinedError, QueueFullError, RejectedError,
                      Request, RequestQueue, RequestState)
from .resilience.faultplane import (InjectedFault, InjectedMemoryError,
                                    NULL_PLANE)
from .structured import GrammarCache
from .structured import runtime as grammar_rt

_log = logging.getLogger(__name__)

_TRACE_STATE = {RequestState.DONE: "done", RequestState.FAILED: "failed",
                RequestState.CANCELLED: "cancelled",
                RequestState.REJECTED: "rejected"}


def _host_bytes(args) -> int:
    """Bytes of the host arrays in ``args`` (a step program's inputs:
    what the launch puts on the device)."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(args))


class EngineCore:
    """Continuous-batching scheduler over a ``PagedGenerationEngine``.

    The engine instance is OWNED by the core for the core's lifetime:
    direct ``generate()`` calls on it would free/re-reserve the slot
    sequence ids and corrupt in-flight rows.  Requests the batch can't
    host go through ``submit_exclusive`` with a *different* engine
    (``tools/serve.py`` uses the dense ``GenerationEngine``)."""

    def __init__(self, engine: PagedGenerationEngine, max_batch: int = 8,
                 max_queue: int = 64,
                 default_timeout_s: Optional[float] = None,
                 max_model_len: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 tracer: Optional[Tracer] = None,
                 enable_prefix_cache: bool = False,
                 prefix_cache_watermark: float = 0.5,
                 prefix_cache_headroom_pages: int = 0,
                 fault_plane=None,
                 steplog: Optional[StepLog] = None,
                 prefill_chunk: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 speculate: bool = False,
                 num_draft_tokens: int = 4,
                 draft_source="auto",
                 kv_dtype: Optional[str] = None,
                 spec_accept_threshold: Optional[float] = None,
                 serving_mesh=None,
                 sched_policy: str = "fifo",
                 slo_ttft_s: Optional[float] = None,
                 slo_itl_s: Optional[float] = None,
                 adapter_store=None,
                 adapter_slots: int = 8,
                 kv_host_pages: int = 0,
                 kv_park_watermark: float = 0.95,
                 kv_resume_watermark: float = 0.70,
                 journeys: Optional[JourneyStore] = None,
                 replica_name: Optional[str] = None,
                 grammar_vocab=None):
        # sharded serving plane (serving/sharded/): when a ServingMesh is
        # handed in, re-validate it against THIS core's feature flags so
        # incompatible combos (quantized wire + speculation/prefix cache)
        # die here with an actionable message, never mid-step; also catch
        # an engine whose mesh/quantization disagrees with the config
        from .sharded import (ShardedConfigError, validate_cache_layout,
                              validate_kv_quant_combo,
                              validate_moe_quant_combo,
                              validate_serving_config)
        from .moe import (moe_serving_info, prepare_moe_serving,
                          serving_capacity)
        from .moe.dropless import dropless_moe_info

        # what the model's cache kinds cannot do yet dies here, before
        # any pool is sized (inference/cache_layout.py)
        validate_cache_layout(
            getattr(engine, "_cache_layout", None),
            mp=int(getattr(serving_mesh, "mp", 1) or 1),
            kv_dtype=getattr(engine, "_kv_dtype", None),
            speculate=speculate, kv_host_pages=int(kv_host_pages))
        # dropless expert layers (serving/moe/dropless.py) need no
        # conversion and no capacity: the mixed step only threads them
        # its valid mask and returns their counters
        self._dropless = dropless_moe_info(engine._model)
        # hyper-connected residual streams (nn/hyper_connections.py):
        # likewise only their counters ride out of the step
        from ..nn.hyper_connections import hyper_connection_info

        self._residual = hyper_connection_info(engine._model)

        # KV-pool quantization rides in on the ENGINE (it owns the
        # pools); the kwarg here is a config affordance that must agree
        # with what the engine was built with
        engine_kv = getattr(engine, "_kv_dtype", None)
        if kv_dtype is not None and kv_dtype != engine_kv:
            raise ShardedConfigError(
                f"EngineCore kv_dtype={kv_dtype!r} disagrees with the "
                f"engine's kv_dtype={engine_kv!r} — pass kv_dtype to "
                "PagedGenerationEngine (it owns the pools) or drop it "
                "here")
        self._kv_dtype = engine_kv
        self._spec_accept_threshold = spec_accept_threshold

        # MoE serving plane (serving/moe/): detect the model's MoE
        # layers up front — the expert config feeds the validation
        # matrix (ep divisibility, quantized experts × speculation) and,
        # further down, the in-place conversion to static-capacity
        # serving layers that must precede the engine's param snapshot
        self._moe = moe_serving_info(engine._model)

        engine_quant = getattr(engine, "_quant_allreduce", None)
        if serving_mesh is not None:
            validate_serving_config(
                serving_mesh, speculate=speculate,
                enable_prefix_cache=enable_prefix_cache,
                max_batch=int(max_batch), num_heads=engine._num_heads,
                kv_dtype=engine_kv,
                spec_accept_threshold=spec_accept_threshold,
                num_experts=(self._moe["num_experts"]
                             if self._moe else None),
                moe_quant=self._moe["algo"] if self._moe else None)
            if serving_mesh.n_devices > 1 and engine._mesh is None:
                raise ShardedConfigError(
                    f"{serving_mesh.describe()} given but the engine has "
                    "no mesh — build it with "
                    "serving.sharded.build_sharded_engine")
            if (serving_mesh.quantized_allreduce or None) != engine_quant:
                raise ShardedConfigError(
                    f"{serving_mesh.describe()} disagrees with the "
                    f"engine's quantized_allreduce={engine_quant!r}")
        elif engine_quant and (speculate or enable_prefix_cache):
            raise ShardedConfigError(
                "engine serves with quantized_allreduce="
                f"{engine_quant!r}, which is incompatible with "
                "speculate/prefix-cache (exact-logit invariants); see "
                "serving.sharded.validate_serving_config")
        else:
            # single-device path: the quantization matrices still apply
            validate_kv_quant_combo(
                engine_kv, speculate=speculate,
                enable_prefix_cache=enable_prefix_cache,
                spec_accept_threshold=spec_accept_threshold)
            validate_moe_quant_combo(
                self._moe["algo"] if self._moe else None,
                speculate=speculate,
                spec_accept_threshold=spec_accept_threshold)
        self._serving_mesh = serving_mesh
        self._engine = engine
        self._max_batch = int(max_batch)
        # resilience plumbing (serving/resilience/): the fault plane is
        # the NULL no-op unless a chaos schedule is attached; a recovery
        # protocol (EngineSupervisor) may be wired in via
        # attach_recovery() to enable retry/replay on engine failure
        self._fault = fault_plane if fault_plane is not None else NULL_PLANE
        self._recovery = None
        self._drain_evt = threading.Event()
        self._loop_tb_seen: set = set()
        self._default_timeout = default_timeout_s
        self._metrics = metrics or ServingMetrics()
        # span-based request tracing: every request's wall time is
        # attributed edge-to-edge (queue_wait → prefill → decode chunks
        # → evict); completed traces live in the tracer's ring buffer
        # and serve.py exposes them as GET /trace/<rid>
        self.tracer = tracer or Tracer()
        # fleet-wide journey plane (observability/journey.py): a fleet
        # passes ONE shared store so a request migrating across replicas
        # stitches into a single journey; standalone cores get a private
        # store so attribution/tenant accounting work identically
        self.replica_name = replica_name or "core0"
        self._journeys = journeys if journeys is not None else JourneyStore()
        self._journeys.register(self.replica_name, self.tracer)
        self._decode_warm = False
        self._queue = RequestQueue(max_depth=max_queue)

        page = engine.page_size
        self._page = page
        cap = engine._max_positions
        self._max_model_len = min(int(max_model_len or cap), cap)
        # every slot's page table has one fixed width, covering the
        # worst-case reservation (page-padded prompt or prompt+max_new)
        self._max_pages = _round_up(self._max_model_len, page) // page
        window = self._max_pages * page
        # whether the step launches the latent decode kernel, whose grid
        # the packer books as StepLog ``decode_grid_steps``
        layout = getattr(engine, "_cache_layout", None) or ()
        # a layer with an indexer runs its own kernels in that one's
        # place; the packer books what they score and select
        self._index_topk = int(getattr(
            engine._model.config, "index_topk", 0) or 0) \
            if has_index(layout) else 0
        self._latent_pages = has_latent(layout) and not self._index_topk

        # mixed-step scheduling: ONE executable keyed by (max_batch,
        # token_budget, max_pages) serves every batch composition — each
        # row of a step carries its own (query_len, context_len), so
        # decode rows and prompt chunks share a launch and nothing is
        # ever padded to a prompt bucket.  Prompts longer than
        # ``prefill_chunk`` are admitted as token slices spread over
        # successive steps under the per-step ``token_budget``, so a
        # long prompt arrival does not stall streaming decode rows
        # (docs/SERVING.md "Ragged attention and chunked prefill").
        budget = int(token_budget or min(window, max(4 * page, 32)))
        # every active row must at least fit its decode token
        budget = max(2, self._max_batch, min(budget, window))
        self._token_budget = budget
        chunk = int(prefill_chunk or budget)
        self._prefill_chunk = max(1, min(chunk, budget))

        if self._moe is not None:
            # convert the MoE FFNs in place BEFORE the param snapshot so
            # the serving wrappers' (unchanged) params/buffers are what
            # the engine captures.  The capacity is fixed from
            # deployment config — part of the executable's config key,
            # never of the data — and with the default capacity_factor
            # the routing is bitwise the unconverted fused path over the
            # same token_budget token slots.
            cap = serving_capacity(self._token_budget, self._moe)
            prepare_moe_serving(engine._model, cap)
            self._moe = dict(
                self._moe, capacity=int(cap),
                ep=int(getattr(serving_mesh, "ep", 1) or 1))

        self._lora = None
        self._adapters = None
        if adapter_store is not None:
            # convert the target projections in place BEFORE the param
            # snapshot, like the MoE plane: the stacked slot pools are
            # registered buffers, so the engine snapshot carries them
            # into the executable as arguments and the AdapterCache can
            # swap slot contents without recompiling.  (slots, rank)
            # are deployment constants — part of the executable's
            # config key, never of the data.
            from .adapters import (AdapterCache, AdapterError,
                                   prepare_lora_serving)
            n_lora = prepare_lora_serving(
                engine._model, slots=int(adapter_slots),
                rank=int(adapter_store.rank))
            if n_lora == 0:
                raise AdapterError(
                    "adapter_store given but the model exposes no LoRA "
                    "target projections (qkv_proj/out_proj/fc1/fc2)")

        engine.refresh_params()
        if adapter_store is not None:
            self._adapters = AdapterCache(engine, adapter_store)
            self._lora = {"slots": self._adapters.slots,
                          "rank": self._adapters.rank,
                          "layers": n_lora}
        # constrained decoding (serving/structured/): grammars compile
        # host-side at ADMISSION into token-level FSMs cached by spec
        # digest; per-row fsm_state is plain int DATA and the mixed
        # step gains exactly one [max_batch, vocab] mask input.  The
        # vocab (token id -> surface string) is a deployment constant,
        # so the executable key only grows the static "grammar" marker
        # — never a per-grammar shape (analysis/rules/recompile_hazard
        # enforces this).
        self._grammar: Optional[GrammarCache] = None
        if grammar_vocab is not None:
            vs = getattr(getattr(engine._model, "config", None),
                         "vocab_size", None)
            if vs is not None and len(grammar_vocab) != int(vs):
                raise ValueError(
                    f"grammar_vocab has {len(grammar_vocab)} entries but "
                    f"the model's vocab_size is {int(vs)}")
            self._grammar = GrammarCache(grammar_vocab)
        # engine-lifetime structured counters: violations/incomplete
        # mutate under the step lock; admission rejects are counted by
        # the submitting thread (int += is GIL-coherent for gauges)
        self._grammar_violations = 0
        self._grammar_incomplete = 0
        self._grammar_rejected = 0

        # prefix_cache_headroom_pages widens the pool BEYOND the
        # worst-case live reservations (slots x max_pages) without
        # widening any slot's page table: live rows can never reach the
        # extra pages, so they exist purely as retention room for the
        # prefix-cache radix tree.  Without headroom a fully occupied
        # batch evicts retained sequences on admission, which blinds
        # prefix hits AND the tree-backed speculative draft source.
        headroom = max(0, int(prefix_cache_headroom_pages)) \
            if enable_prefix_cache else 0
        self._headroom_pages = headroom
        self._pool = engine.serving_pool(
            self._max_batch * self._max_pages + 1 + headroom)
        # scratch page: inactive rows' writes land here, reads of live
        # rows never reach it (attention masks by per-row position)
        self._pool.free(self._max_batch)
        self._pool.reserve(self._max_batch, 1)
        self._scratch = int(self._pool.block_table(self._max_batch)[0])

        # automatic prefix caching: finished sequences' pages are
        # retained in a radix tree and matched against new prompts at
        # admission (docs/SERVING.md "Prefix caching").
        self._prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self._pool, page, prefix_cache_watermark)
            if enable_prefix_cache else None)

        # in-engine speculative decoding (docs/SERVING.md "Speculative
        # decoding"): each decode row may pack up to num_draft_tokens
        # proposed continuation tokens and ride the SAME mixed step as a
        # query_len = k+1 verify row under the token budget — drafts
        # spend only budget LEFT OVER after decode and prefill-chunk
        # packing, so scheduling and prefill pacing are unchanged.  One
        # executable (keyed with the static window) serves every
        # composition, exactly like the plain mixed step.
        self._speculate = bool(speculate)
        if self._speculate:
            if int(num_draft_tokens) < 1:
                raise ValueError("num_draft_tokens must be >= 1")
            self._spec_window = max(
                2, min(int(num_draft_tokens) + 1, self._token_budget))
            from .speculation import resolve_draft_source
            self._draft_source = resolve_draft_source(
                draft_source, cache=self._prefix_cache)
        else:
            self._spec_window = 1
            self._draft_source = None

        # the step's host interface (serving/programs.StepLayout): ONE
        # preallocated int32 buffer in, written through per-field views
        # by the packer and put on the device as it is, and ONE int32
        # array back.  Both tables are deployment constants.
        self._step_in = step_input_layout(
            self._max_batch, self._token_budget, self._max_pages,
            self._spec_window)
        self._step_buf = np.zeros((self._step_in.size,), np.int32)
        self._step_fields = self._step_in.views(self._step_buf)
        # a request's key is fold_in(PRNGKey(seed), rid): the layout's
        # keys row has to hold the PRNG implementation's key as it is
        key_row, prng_key = (self._step_fields["keys"][0],
                             jax.eval_shape(jax.random.PRNGKey, 0))
        if (key_row.shape, key_row.dtype) != (prng_key.shape,
                                              prng_key.dtype):
            raise ValueError(
                f"the step layout carries keys as {key_row.dtype}"
                f"{list(key_row.shape)}; this PRNG implementation's are "
                f"{prng_key.dtype}{list(prng_key.shape)}")
        self._step_out = step_output_layout(
            self._max_batch, self._spec_window,
            moe=(self._moe["num_experts"] if self._moe is not None
                 else None if self._dropless is None
                 else "dropless_identity"
                 if self._dropless["identity_experts"] else "dropless"),
            residual=self._residual is not None)

        # step-level flight recorder: every scheduler step event
        # (admission / mixed step / page copy / evict) appends one
        # schema-fixed record with an analytic bytes/FLOPs estimate from
        # the cost model (observability/steplog.py; GET /steps)
        self.steplog = steplog if steplog is not None else StepLog()
        self._cost_model = StepCostModel(engine, self._pool)
        # host-RAM KV tier (serving/kv_tier/): a page-accounted host
        # arena under the device pool.  Overload parks whole in-flight
        # rows (the handoff serialization retargeted at a host buffer)
        # instead of shedding them, and prefix-tree eviction demotes
        # full blocks there instead of dropping them.  Constructed
        # after the cost model: its calibrated per-page byte constant
        # prices swap traffic (int8 pools halve host bytes for free).
        self._kv_tier: Optional[HostKVTier] = None
        if int(kv_host_pages) > 0:
            self._kv_tier = HostKVTier(
                int(kv_host_pages),
                park_watermark=float(kv_park_watermark),
                resume_watermark=float(kv_resume_watermark),
                page_kv_bytes=self._cost_model.page_kv_bytes)
            if self._prefix_cache is not None:
                # direct assignment, not a setter: the static lock walk
                # binds the tree's eviction-hook fire site to
                # _demote_block through this form, so the
                # PrefixCache._lock -> HostKVTier._lock edge lands in
                # the committed lock graph
                self._prefix_cache._tier_demote = self._demote_block

        # SLO-aware scheduling (serving/sched/): the admission policy
        # reorders/sheds the queue from predicted completion; the step
        # planner caps prompt chunking from predicted step wall.  Both
        # are pure data decisions calibrated by the steplog fit — the
        # fifo default keeps admission and packing byte-identical to
        # the pre-sched engine.
        from .sched import StepPlanner, make_policy
        self._sched = make_policy(sched_policy, slo_ttft_s=slo_ttft_s,
                                  slo_itl_s=slo_itl_s)
        self._planner = StepPlanner(
            self._cost_model, self.steplog,
            max_batch=self._max_batch,
            token_budget=self._token_budget,
            prefill_chunk=self._prefill_chunk,
            slo_itl_s=slo_itl_s,
            dynamic=self._sched.reorders)
        self._predictive_sheds = 0
        # rolling |predicted - actual| completion error for requests
        # the slack policy scored (reads/writes under the step lock)
        self._slack_err: deque = deque(maxlen=256)
        self._last_min_slack_s: Optional[float] = None

        self._slots: List[Optional[dict]] = [None] * self._max_batch
        # degradation ladder: memory pressure shrinks the batch the
        # scheduler will actually fill; recovery grows it back
        self._effective_max_batch = self._max_batch
        # per-step active sets (tests prove late admission joins the same
        # step with it): bounded like the StepLog ring it sits beside
        self.step_trace: deque = deque(maxlen=self.steplog.capacity)
        self._step_idx = 0
        # the running iteration's clock (observability/stepclock.py) and
        # the previous serving step's end, for the next record's gap_s
        self._clock: Optional[StepClock] = None
        self._last_step_end: Optional[float] = None
        # the interpreter's collections (on the list of gc's callbacks
        # from the first iteration to close()), and what the last step
        # record had seen of the prefix cache's eviction counters: a step
        # record carries the deltas
        self._gc = GcWatch()
        self._cache_seen = self._cache_counters()
        # chunk-boundary notification (fleet handoff): called with the
        # Request, by the stepping thread under the step lock, the step
        # its prompt finishes prefilling.  Must be fast and reentrant-
        # safe with respect to THIS core's step lock (it is an RLock).
        self.on_prefill_complete = None
        # RLock: the locked step path reads ``active_count``, which now
        # takes the lock itself so unlocked readers (HTTP metrics
        # threads) see a consistent slot table
        self._step_lock = threading.RLock()
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._closed = False

    # ------------------------------------------------------------ intake
    @staticmethod
    def batchable(g: GenerationConfig) -> bool:
        """Configs the shared decode executable can host as one row.
        Repetition penalty needs full token history (per-row widths the
        fused step can't carry); beams need W rows + reorder."""
        return g.num_beams == 1 and g.repetition_penalty == 1.0

    @property
    def metrics(self) -> ServingMetrics:
        return self._metrics

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        with self._step_lock:
            return sum(s is not None for s in self._slots)

    def approx_active_count(self) -> int:
        """Lock-free occupancy estimate for CROSS-core readers.  The
        fleet's handoff paths run on one core's stepping thread while
        scanning OTHER cores as candidates; taking each candidate's
        step lock there (as the exact ``active_count`` property does)
        makes two cores handing off to each other acquire each other's
        step locks — a lock-order cycle.  Slot-list reads are atomic
        under the GIL; a one-step-stale count only mis-ranks a
        candidate, which the bounded destination-lock acquire already
        tolerates."""
        # tpulint: disable-next-line=lock-discipline -- lock-free by design: cross-core readers on the handoff path must not take another core's step lock (lock-order cycle); staleness only mis-ranks a candidate
        slots = self._slots
        return sum(s is not None for s in list(slots))

    @property
    def prefix_cache(self) -> Optional[PrefixCache]:
        return self._prefix_cache

    # ------------------------------------------------ resilience surface
    @property
    def max_batch(self) -> int:
        return self._max_batch

    @property
    def effective_max_batch(self) -> int:
        """Slots the scheduler will currently fill (≤ max_batch; shrunk
        by the degradation ladder under memory pressure)."""
        with self._step_lock:
            return self._effective_max_batch

    def set_effective_max_batch(self, n: int):
        with self._step_lock:
            self._effective_max_batch = max(1, min(int(n),
                                                   self._max_batch))

    @property
    def fault_plane(self):
        return self._fault

    def attach_recovery(self, recovery):
        """Wire a recovery protocol (resilience.EngineSupervisor) into
        the failure paths: engine failures then replay in-flight
        requests under a retry budget instead of failing them."""
        self._recovery = recovery

    def set_draining(self, draining: bool):
        """While draining, ``submit`` rejects with ``LoadShedError``
        (HTTP 503 + Retry-After); in-flight requests keep decoding."""
        if draining:
            self._drain_evt.set()
        else:
            self._drain_evt.clear()

    @property
    def draining(self) -> bool:
        return self._drain_evt.is_set()

    def shed_queued(self, min_headroom_s: float) -> int:
        """Degradation-ladder load shedding: reject queued requests whose
        deadline headroom is below ``min_headroom_s`` — under a degraded
        engine they would burn a prefill and miss their deadline anyway."""
        shed = self._queue.shed_low_headroom(time.monotonic(),
                                             min_headroom_s)
        for r in shed:
            self._metrics.on_shed()
            r._finish(RequestState.REJECTED, LoadShedError(
                f"request {r.rid} shed: deadline headroom below "
                f"{min_headroom_s:.2f}s under degraded engine"))
            self._trace_queue_drop(r, RequestState.REJECTED, "load-shed")
        return len(shed)

    def _schedule_admission(self, now: float) -> int:
        """Run the admission policy over the queued batch requests:
        reorder by predicted deadline slack and finish predictive
        sheds.  Called on the stepping thread under the step lock; the
        queue transaction itself is atomic under the queue condition."""
        if not len(self._queue):
            return 0
        cal = self._planner.calibration()
        if not cal.admission_ready:
            return 0        # cold fit: stay FIFO, never mispredict
        # prefill work still pending on already-active rows delays
        # every queued request's first chunk
        backlog = 0
        for s in self._slots:
            if s is not None:
                backlog += int(s["pending"].size)
        captured = {}

        def fn(batch):
            kept, shed = self._sched.schedule(batch, now, cal, backlog)
            captured["kept"] = kept
            captured["batch"] = batch
            return kept, shed

        shed = self._queue.schedule(fn)
        kept = captured.get("kept", [])
        if shed or kept != captured.get("batch", kept):
            # latency attribution: this pass actually changed the queue,
            # so waiting time from here on is scheduler-induced — _admit
            # splits the queue_wait span at this stamp (sched_reorder
            # bucket, observability/journey.py)
            for r in kept:
                if r.sched_reorder_at is None:
                    r.sched_reorder_at = now
        slacks = [r.sched_predicted_slack for r in kept
                  if r.sched_predicted_slack is not None]
        self._last_min_slack_s = min(slacks) if slacks else None
        for r in shed:
            # predictive PARK before predictive shed: preempting a
            # deadline-rich victim into the host tier frees its pages
            # and slot, which usually flips the doomed forecast.  The
            # would-be-shed request re-enters at the queue head; only
            # when no victim can park does the shed go through.
            if (self._kv_tier is not None
                    and self._park_for_pressure(predictive=True)):
                self._queue.push_front(r)
                continue
            self._predictive_sheds += 1
            self._metrics.on_predictive_shed()
            miss = ((r.sched_predicted_done - r.deadline)
                    if (r.sched_predicted_done is not None
                        and r.deadline is not None) else 0.0)
            r._finish(RequestState.REJECTED, LoadShedError(
                f"request {r.rid} shed predictively: predicted "
                f"completion misses its deadline by {miss:.3f}s"))
            self._trace_queue_drop(r, RequestState.REJECTED,
                                   "predictive-shed")
        return len(shed)

    def _sched_snapshot(self) -> dict:
        """The ``sched`` section of the metrics snapshot — always
        present so dashboards can tell "fifo by choice" from "engine
        predates the scheduler"."""
        with self._step_lock:
            errs = list(self._slack_err)
            sheds = self._predictive_sheds
            min_slack = self._last_min_slack_s
        out = {
            "policy": self._sched.name,
            "reorders": self._sched.reorders,
            "slo_ttft_s": self._sched.slo_ttft_s,
            "slo_itl_s": self._sched.slo_itl_s,
            "predictive_sheds": sheds,
            "last_min_slack_s": min_slack,
            "slack_err": {
                "n": len(errs),
                "mean_abs_err_s": (sum(errs) / len(errs)) if errs
                else None,
                "max_abs_err_s": max(errs) if errs else None,
            },
        }
        out["planner"] = self._planner.snapshot()
        return out

    def _kv_quant_info(self) -> Optional[dict]:
        """The ``kv_quant`` section of the metrics snapshot: per-page
        byte accounting for the quantized pool vs the fp pool the same
        config would have allocated.  None (section omitted) on fp
        pools."""
        if self._kv_dtype is None:
            return None
        eng = self._engine
        H, D, page, L = (eng._num_heads, eng._head_dim, self._page,
                        eng._num_layers)
        fp_item = np.dtype(eng._cache_dtype).itemsize
        # k+v per layer: int8 payload plus one f32 scale per (page, head)
        payload = 2 * H * page * D
        scale = 2 * H * 4
        q_page = L * (payload + scale)
        fp_page = L * 2 * H * page * D * fp_item
        return {"kv_dtype": self._kv_dtype,
                "bytes_per_page": int(q_page),
                "fp_bytes_per_page": int(fp_page),
                "scale_bytes_per_page": int(L * scale),
                "resident_page_ratio": fp_page / q_page}

    def metrics_snapshot(self) -> dict:
        total = self._pool.num_blocks
        free = self._pool.free_blocks
        resilience = {"effective_max_batch": self.effective_max_batch,
                      "draining": self._drain_evt.is_set(),
                      "faults_injected": self._fault.counts()}
        rec = self._recovery
        if rec is not None:
            resilience.update(rec.health_info())
        else:
            resilience.update({"health_state": "healthy",
                               "health_code": 0})
        # the one device-memory probe in the tree (profiler.statistic;
        # evidence bundles use the same one) — None on backends whose
        # allocator exposes no counters (CPU)
        from ..profiler.statistic import memory_stats

        from ..quantization.weight_only import weight_only_summary
        from .sharded import sharding_snapshot

        return self._metrics.snapshot(
            queue_depth=len(self._queue),
            active=self.active_count,
            max_batch=self._max_batch,
            # capacity is reported in PAGES (the pool's native unit) —
            # bytes-derived counts would silently halve under kv_dtype
            # int8 and lie about admission headroom
            kv_pool={"total_blocks": int(total),
                     "free_blocks": int(free),
                     "used_blocks": int(total - free),
                     "headroom_pages": int(self._headroom_pages),
                     # only once a step has made the pools: a scrape
                     # must not be what allocates them
                     **(self._cache_bytes_fields()
                        if self._engine._k_pages is not None else {}),
                     "occupancy": (total - free) / total if total else 0.0},
            prefix_cache=(self._prefix_cache.stats_snapshot()
                          if self._prefix_cache is not None else None),
            kv_quant=self._kv_quant_info(),
            weight_only=weight_only_summary(self._engine._model),
            resilience=resilience,
            steplog=self.steplog.summary(),
            device_memory=memory_stats(),
            sharding=sharding_snapshot(self._engine),
            moe=self._moe,
            adapters=(self._adapters.summary()
                      if self._adapters is not None else None),
            kv_tier=(self._kv_tier.summary()
                     if self._kv_tier is not None else None),
            sched=self._sched_snapshot(),
            journeys=self._journeys.summary(),
            structured=self._structured_snapshot())

    def _structured_snapshot(self) -> Optional[dict]:
        """The ``structured`` metrics section: engine counters under
        the step lock, cache counters under the cache's own leaf lock
        (taken strictly AFTER the step lock is released — the compile
        cache must never nest inside the step path)."""
        if self._grammar is None:
            return None
        with self._step_lock:
            out = {
                "active_rows": sum(
                    1 for s in self._slots
                    if s is not None and s.get("fsm") is not None),
                "violations": int(self._grammar_violations),
                "incomplete": int(self._grammar_incomplete),
                "rejected": int(self._grammar_rejected),
            }
        out.update(self._grammar.summary())
        return out

    # ------------------------------------------------------- trace hooks
    def _trace_end(self, req: Request, state: RequestState):
        st = _TRACE_STATE.get(state, state.value)
        self.tracer.end(req.rid, st)
        # journey finalize: stitch this rid's spans across every replica
        # that saw it and decompose the e2e wall into attribution
        # buckets; the summary feeds the per-tenant SLO families
        summary = self._journeys.finalize(req.rid, st)
        if summary is not None:
            attained = (state == RequestState.DONE
                        and (req.deadline is None
                             or (req.finished_at or req.arrival)
                             <= req.deadline))
            self._metrics.on_journey(
                tenant=req.tenant, e2e_s=summary["e2e_s"],
                tokens=len(req.tokens), attained=attained,
                buckets=summary["buckets"],
                coverage=summary["coverage"],
                journey_id=summary["journey_id"])

    def _trace_queue_drop(self, req: Request, state: RequestState,
                          reason: str):
        """A request that dies in the queue still gets a full trace:
        one queue_wait span covering its whole life."""
        now = time.monotonic()
        self.tracer.add_span(req.rid, "queue_wait", req.arrival, now,
                             outcome=reason)
        self._trace_end(req, state)

    def _validate_adapter_id(self, adapter_id: Optional[str]):
        """Submit-time adapter validation: unknown or unconfigured
        adapter bindings die HERE (RejectedError → HTTP 4xx), never
        after burning a queue slot or a prefill."""
        if adapter_id is None:
            return
        if self._adapters is None:
            self._metrics.on_rejected()
            raise RejectedError(
                f"request binds adapter {adapter_id!r} but this engine "
                "serves no adapters (construct EngineCore with "
                "adapter_store=)")
        if not self._adapters.has(adapter_id):
            self._metrics.on_rejected()
            raise UnknownAdapterError(
                f"unknown adapter {adapter_id!r}: not registered in the "
                "adapter store")

    def _validate_grammar(self, grammar):
        """Submit-time grammar validation + compile: malformed,
        unsupported or unsatisfiable specs die HERE (GrammarError →
        HTTP 400) before the request costs a queue slot, a KV page or
        an adapter pin — nothing to unwind on rejection.  Returns the
        cached ``CompiledGrammar`` (None for unconstrained requests).
        The compile runs on the SUBMITTING thread, never under the
        step lock."""
        if grammar is None:
            return None
        if self._grammar is None:
            self._grammar_rejected += 1
            self._metrics.on_rejected()
            raise GrammarError(
                "request carries grammar= but this engine serves no "
                "grammars (construct EngineCore with grammar_vocab=)")
        try:
            return self._grammar.get_or_compile(grammar)
        except GrammarError:
            self._grammar_rejected += 1
            self._metrics.on_rejected()
            raise

    def submit(self, input_ids, config: GenerationConfig = None,
               attention_mask=None,
               timeout_s: Optional[float] = None,
               cache_salt: Optional[str] = None,
               adapter_id: Optional[str] = None,
               tenant: Optional[str] = None,
               grammar: Optional[dict] = None) -> List[Request]:
        """Enqueue one request per row of ``input_ids`` ([b, plen] or
        [plen]).  All-or-nothing: admission errors (too long, queue
        full, not batchable) reject the whole call.  Returns the per-row
        ``Request`` handles immediately — stream or ``result()`` them."""
        if self._closed:
            raise RejectedError("serving engine is closed")
        if self._drain_evt.is_set():
            self._metrics.on_rejected()
            raise LoadShedError("serving engine is draining; retry "
                                "against another replica")
        self._validate_adapter_id(adapter_id)
        grammar_fsm = self._validate_grammar(grammar)
        g = config or GenerationConfig()
        if not self.batchable(g):
            self._metrics.on_rejected()
            raise RejectedError(
                "config not batchable (beams/repetition_penalty); route "
                "through submit_exclusive")
        if grammar is not None and g.min_length > 0:
            # the min-length EOS ban and the FSM's EOS-only-in-accept
            # rule can contradict (a complete grammar with a banned EOS
            # has no legal token) — refuse the combination up front
            self._grammar_rejected += 1
            self._metrics.on_rejected()
            raise GrammarError(
                "grammar= with min_length > 0 is unsupported: the "
                "min-length EOS ban can contradict the grammar's "
                "accept-state EOS rule")
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None, :]
        mask = (np.ones_like(ids) if attention_mask is None
                else np.asarray(attention_mask).astype(np.int32))
        rows = []
        for i in range(ids.shape[0]):
            real = np.flatnonzero(mask[i])
            row = ids[i, real] if len(real) else \
                np.asarray([g.pad_token_id], np.int32)
            if len(row) + g.max_new_tokens > self._max_model_len:
                self._metrics.on_rejected()
                raise RejectedError(
                    f"prompt {len(row)} + max_new {g.max_new_tokens} "
                    f"exceeds max_model_len {self._max_model_len}")
            rows.append(row)
        timeout_s = self._default_timeout if timeout_s is None else timeout_s
        reqs = [Request(row, g, timeout_s=timeout_s, cache_salt=cache_salt,
                        adapter_id=adapter_id, tenant=tenant,
                        grammar=grammar)
                for row in rows]
        for req in reqs:
            req.grammar_fsm = grammar_fsm
        try:
            self._queue.submit_many(reqs)
        except QueueFullError:
            self._metrics.on_rejected_queue_full(len(reqs))
            raise
        self._metrics.on_submitted(len(reqs))
        for req in reqs:
            self.tracer.begin(req.rid, kind="batch",
                              prompt_len=int(req.prompt.size),
                              max_new_tokens=g.max_new_tokens)
            self._journeys.begin(req.rid, self.replica_name,
                                 tenant=tenant)
        return reqs

    def submit_exclusive(self, fn,
                         timeout_s: Optional[float] = None) -> Request:
        """Enqueue an arbitrary engine call to run alone on the
        scheduler thread (FIFO with batch requests).  The result lands
        in ``req.value``."""
        if self._closed:
            raise RejectedError("serving engine is closed")
        if self._drain_evt.is_set():
            self._metrics.on_rejected()
            raise LoadShedError("serving engine is draining; retry "
                                "against another replica")
        timeout_s = self._default_timeout if timeout_s is None else timeout_s
        req = Request(None, GenerationConfig(), timeout_s=timeout_s,
                      kind="exclusive", exclusive_fn=fn)
        try:
            self._queue.submit(req)
        except QueueFullError:
            self._metrics.on_rejected_queue_full()
            raise
        self._metrics.on_submitted()
        self.tracer.begin(req.rid, kind="exclusive")
        self._journeys.begin(req.rid, self.replica_name)
        return req

    def enqueue(self, req: Request) -> Request:
        """Admit an EXISTING ``Request`` into this core's queue — the
        fleet router's requeue path when the replica that originally
        accepted the request drains or goes down before slotting it.
        The request keeps its rid (per-request sampling keys are
        ``fold_in(PRNGKey(seed), rid)``, so the stream is bitwise the
        same wherever it lands) and its original arrival clock, so
        queue-wait spans the whole journey, not just the last hop."""
        if self._closed:
            raise RejectedError("serving engine is closed")
        if self._drain_evt.is_set():
            self._metrics.on_rejected()
            raise LoadShedError("serving engine is draining; retry "
                                "against another replica")
        if req.kind != "batch":
            raise RejectedError("only batch requests can be rerouted")
        g = req.config
        if not self.batchable(g):
            self._metrics.on_rejected()
            raise RejectedError(
                "config not batchable (beams/repetition_penalty); route "
                "through submit_exclusive")
        if int(req.prompt.size) + g.max_new_tokens > self._max_model_len:
            self._metrics.on_rejected()
            raise RejectedError(
                f"prompt {int(req.prompt.size)} + max_new "
                f"{g.max_new_tokens} exceeds max_model_len "
                f"{self._max_model_len}")
        self._validate_adapter_id(req.adapter_id)
        # re-validate + re-compile on THIS replica's cache: the fleet
        # ships the grammar spec as plain data, never FSM objects
        req.grammar_fsm = self._validate_grammar(req.grammar)
        req._requeue()
        self._queue.submit(req)
        self._metrics.on_submitted()
        if self.tracer.get(req.rid) is None:
            self.tracer.begin(req.rid, kind="batch",
                              prompt_len=int(req.prompt.size),
                              max_new_tokens=g.max_new_tokens)
        # idempotent: a rerouted request keeps its original journey
        # (origin replica, hop count) in a fleet-shared store
        self._journeys.begin(req.rid, self.replica_name,
                             tenant=req.tenant)
        return req

    # ------------------------------------------------------ the step loop
    def run_once(self, wait_s: float = 0.0) -> bool:
        """One scheduler iteration (see module docstring).  Returns True
        when any request made progress; otherwise blocks up to
        ``wait_s`` for new submissions.  Thread-safe but serialized —
        tests drive it directly on an unstarted core."""
        with self._step_lock:
            progressed = self._run_once_locked()
        # idle wait OUTSIDE the step lock: a loop that sleeps holding it
        # and re-takes it at once starves every other taker (metrics
        # snapshots, admission probes) for as long as the core is idle —
        # Python's locks are not fair
        if not progressed and wait_s > 0:
            self._queue.wait(wait_s)
        return progressed

    def _run_once_locked(self) -> bool:
        # one clock for the iteration: each phase boundary is read once,
        # written into the step's StepLog record and mirrored as an
        # engine.* span into the profiler's trace (no-op without one)
        if not self._closed:
            self._gc.install()
        clock = self._clock = StepClock("engine", self._step_idx + 1,
                                        "admit")
        try:
            return self._iteration(clock.t_begin)
        finally:
            clock.close()

    def _iteration_fields(self, clock: StepClock, end: float) -> dict:
        """The iteration as fields of this step's one StepLog record.
        ``t_begin`` and the phases' durations tile the iteration up to
        ``end``, ``gap_s`` reaches back to the previous step's end; a
        step that failed in its launch never waited: it records what it
        reached.  The clock's children are parts of those phases; the
        thread's own CPU seconds say how much of the host phases it ran.
        The prefix cache's eviction loops are deltas since the previous
        step's record, the interpreter's collections what passed between
        that record's ``end`` and this one's."""
        d = clock.durations(end)
        last, self._last_step_end = self._last_step_end, end
        wait_s = d.get("wait", 0.0)
        cpu_s = time.thread_time() - clock.cpu_begin
        seen, self._cache_seen = self._cache_seen, self._cache_counters()
        evict_s, blocks, scanned, insert_s = (
            b - a for a, b in zip(seen, self._cache_seen))
        gc_s, gc_gen2 = self._gc.book(
            clock.t_begin if last is None else last, end)
        return dict(
            t_begin=clock.t_begin, step=clock.step_num,
            gap_s=clock.t_begin - last if last is not None else 0.0,
            admit_s=d.get("admit", 0.0), pack_s=d.get("pack", 0.0),
            launch_s=d.get("launch", 0.0), wait_s=wait_s,
            host_s=d.get("emit", 0.0),
            ready_s=clock.child_seconds("ready"),
            cpu_s=cpu_s,
            off_cpu_s=max(0.0, end - clock.t_begin - wait_s - cpu_s),
            release_s=clock.child_seconds("release"),
            finished_rows=clock.child_count("release"),
            insert_s=insert_s, evict_s=evict_s, evicted_blocks=blocks,
            evict_scanned_nodes=scanned, gc_s=gc_s, gc_gen2=gc_gen2)

    def _child(self, name: str) -> Span:
        """A timed part of the running phase: a child span of the
        iteration's clock; without one alive (``close()``) the same two
        reads and no span."""
        clock = self._clock
        return clock.child(name) if clock is not None else Span()

    def _cache_counters(self):
        """The prefix cache's cumulative eviction seconds, evicted blocks,
        scanned entries and insert seconds (zeros without a cache)."""
        cache = self._prefix_cache
        if cache is None:
            return (0.0, 0, 0, 0.0)
        return (cache.evict_seconds, cache.evicted_blocks,
                cache.evict_scanned_nodes, cache.insert_seconds)

    def _program_temp_bytes(self, key) -> int:
        """The compiled step's temporaries, from the memory analysis the
        engine keeps beside the cost analysis (a dict look-up per step)."""
        mem = self._engine.program_memory(key)
        return int(mem["temp"]) if mem else 0

    def _cache_bytes_fields(self) -> dict:
        """The allocated pools' bytes per token of capacity, of those
        what the ``latent`` layers cache (lane padding taken off) and
        what their index-key pools hold: the engine reads them from the
        arrays once — on the step record because that is what a reader
        of the StepLog is handed, and under ``kv_pool`` in the
        snapshot."""
        eng = self._engine
        return dict(
            cache_bytes_per_token=eng.cache_bytes_per_token(),
            latent_cache_bytes_per_token=eng.cache_bytes_per_token(
                "latent", padding=False),
            index_cache_bytes_per_token=eng.cache_bytes_per_token(
                "latent", index_only=True))

    def _index_fields(self, ql, cx, attended_keys: int,
                      decode_lengths) -> dict:
        """What one layer's indexer scores and its attention then reads
        this step, from the packer's arrays (``ql`` query tokens a row
        from position ``cx`` on); nothing on a model without one."""
        k = self._index_topk
        if not k:
            return {}
        # a row's queries at positions cx .. cx + ql - 1 keep
        # min(k, position + 1) keys each
        last = cx + ql
        tri = lambda n: n * (n + 1) // 2
        below = tri(np.minimum(last, k)) - tri(np.minimum(cx, k))
        selected = int((below + k * (np.maximum(last, k)
                                     - np.maximum(cx, k))).sum())
        return dict(
            index_scored_keys=attended_keys, index_selected_keys=selected,
            index_decode_scored_keys=int(decode_lengths.sum()),
            index_decode_selected_keys=int(
                np.minimum(decode_lengths, k).sum()),
            index_gathered_rows=gathered_rows(
                decode_lengths, k, self._max_pages * self._page))

    def _iteration(self, now: float) -> bool:
        progressed = False

        for r in self._queue.remove_expired(now):
            self._metrics.on_deadline()
            r._finish(RequestState.CANCELLED, DeadlineExceededError(
                f"request {r.rid} expired after "
                f"{now - r.arrival:.3f}s in queue"))
            self._trace_queue_drop(r, RequestState.CANCELLED,
                                   "deadline-in-queue")
            progressed = True

        for s in list(self._slots):
            if s is not None and s["req"].expired(now):
                self._metrics.on_deadline()
                self._evict(s, RequestState.CANCELLED,
                            DeadlineExceededError(
                                f"request {s['req'].rid} deadline "
                                f"exceeded mid-decode"))
                progressed = True

        while True:
            head = self._queue.peek()
            if head is None or head.kind != "exclusive":
                break
            self._run_exclusive(self._queue.pop())
            progressed = True

        # SLO admission policy: reorder the queued batch requests by
        # predicted slack and finish predictive sheds BEFORE the FIFO
        # pop loop below consumes the (possibly re-ordered) head.  The
        # fifo policy never reorders, so this is a no-op on the
        # default path.
        if self._sched.reorders:
            progressed = bool(self._schedule_admission(now)) or progressed

        # parked requests re-enter AHEAD of the queue (queue-head
        # semantics): resume into freed slots under the watermark
        # hysteresis before any new request is admitted
        if self._kv_tier is not None:
            progressed = self._resume_parked(now) or progressed

        # admission honors the degradation ladder: under memory pressure
        # the supervisor shrinks effective_max_batch below the physical
        # slot count and the surplus slots stay empty
        while (None in self._slots
               and self.active_count < self._effective_max_batch):
            head = self._queue.peek()
            if head is None or head.kind != "batch":
                break
            req = self._queue.pop()
            if req.expired():
                self._metrics.on_deadline()
                req._finish(RequestState.CANCELLED, DeadlineExceededError(
                    f"request {req.rid} expired in queue"))
                self._trace_queue_drop(req, RequestState.CANCELLED,
                                       "deadline-in-queue")
                continue
            if self._admit(req, self._slots.index(None)) is False:
                # adapter-slot backpressure parked the head request:
                # admitting rows behind it would reorder tenants, and
                # re-popping it this step would spin — the mixed step
                # below is what frees a pin
                break
            progressed = True

        if self.active_count:
            self._mixed_step()
            progressed = True
        return progressed

    # --------------------------------------------------------- admission
    def _match_prefix(self, req: Request, tokens: np.ndarray):
        """Query the radix tree for the longest cached prefix of
        ``tokens`` (the prompt; on replay, prompt + delivered tokens).
        The match always fits the fixed table window: nothing is padded,
        so cached + suffix is ``len(tokens)``, and both intake paths
        (``submit``, ``enqueue``) hold prompt + max_new to
        ``max_model_len``, which the ``max_pages`` wide table covers."""
        self._fault.fire("prefix.match", rid=req.rid)
        cache = self._prefix_cache
        # route_salt composes the tenant salt with the adapter binding:
        # KV written under one fine-tune is never warm for another
        match = cache.match(tokens, salt=req.route_salt())
        if self._kv_tier is not None and self._kv_tier.demoted_count:
            self._promote_into_match(req, tokens, match)
        return match

    def _used_pages(self) -> int:
        """Pool pages currently held by any sequence (slots, scratch,
        retained cache) — the resident-KV gauge StepLog records."""
        return int(self._pool.num_blocks - self._pool.free_blocks)

    def _copy_page(self, src: int, dst: int):
        """Device-side copy of one physical page across every layer's
        pools (the CoW step for a shared partial tail block)."""
        self._fault.fire("page.copy")
        eng = self._engine
        ckey = ("serve-page-copy", self._pool.num_blocks)
        clog = get_compile_log()
        c0 = clog.count()
        t0 = time.monotonic()
        eng.run_paged_program(
            ckey, lambda: build_page_copy(eng),
            np.asarray([src], np.int32), np.asarray([dst], np.int32))
        wall = time.monotonic() - t0
        bts, fl, src_tag = self._cost_model.estimate("page_copy",
                                                     pages_touched=1)
        self.steplog.record(
            "page_copy", wall_s=wall, dispatch_s=wall,
            active_rows=self.active_count,
            resident_kv_pages=self._used_pages(),
            bytes_est=bts, flops_est=fl, cost_source=src_tag,
            compile_events=clog.count() - c0)

    def _stage_prefix(self, sid: int, match, length: int, max_new: int):
        """Map a match onto slot ``sid``'s sequence: copy-on-write the
        partial tail into a fresh private block, ``assign`` the shared
        blocks (the sequence takes its own refs — tree eviction can
        never yank them) and reserve fresh pages for the suffix.  Under
        pool pressure the match degrades page by page (evicting LRU
        cache entries first) down to a cold reserve.  Returns the final
        ``(cached_tokens, reserve)``."""
        cache = self._prefix_cache
        pool = self._pool
        page = self._page
        while True:
            cached = match.cached_tokens
            reserve = length + max_new
            total_pages = -(-reserve // page)
            cache.ensure_free(total_pages - len(match.blocks))
            try:
                cow_dst = None
                if match.partial_block is not None:
                    cow_dst = pool.alloc_block()
                    try:
                        self._copy_page(match.partial_block, cow_dst)
                    except BaseException:
                        pool.unref_block(cow_dst)
                        raise
                    cache.on_cow()
                blocks = list(match.blocks)
                ntok = len(blocks) * page
                if cow_dst is not None:
                    blocks.append(cow_dst)
                    ntok += match.partial_len
                try:
                    if blocks:
                        pool.assign(sid, blocks, ntok)
                finally:
                    if cow_dst is not None:
                        # drop the allocation ref: on success the
                        # sequence holds its own; on failure this frees
                        pool.unref_block(cow_dst)
                pool.reserve(sid, reserve)
                return cached, reserve
            except MemoryError:
                pool.free(sid)
                if match.cached_tokens == 0:
                    cache.ensure_free(total_pages)
                    pool.reserve(sid, reserve)
                    return 0, reserve
                cache.trim(match, match.cached_tokens - 1)

    def _release_slot_kv(self, sid: int, match,
                         retain_tokens=None, salt=None):
        """The ONE path KV blocks leave a slot — every admit-failure,
        eviction and close goes through here so per-request block
        accounting can never be dropped.  Optionally retains the
        finished sequence's pages in the prefix cache (the tree takes
        its refs BEFORE the sequence drops its own), frees the pool
        reservation, unpins the request's match and enforces the cache
        watermark."""
        cache = self._prefix_cache
        if (cache is not None and retain_tokens is not None
                and len(retain_tokens) > 0):
            cache.insert(retain_tokens, self._pool.block_table(sid),
                         salt=salt)
        self._pool.free(sid)
        if cache is not None:
            if match is not None:
                cache.release(match)
            cache.enforce_watermark()

    def _release_adapter(self, s: dict):
        """Drop the slot's adapter pin — the partner of the pin in
        ``_admit``/``import_handoff``.  Every path a slot leaves the
        batch (evict, replay, handoff export) goes through here; slot 0
        (base model) is a no-op, so the call is unconditional."""
        if self._adapters is not None:
            self._adapters.unpin(int(s.get("adapter_slot", 0)))

    def _admit(self, req: Request, sid: int):
        admit_t = time.monotonic()
        queued_at = req.requeued_at if req.retries else req.arrival
        mark = req.sched_reorder_at
        if mark is not None and queued_at < mark < admit_t:
            # an admission-policy pass reordered the queue while this
            # request waited: split the wait so post-reorder time lands
            # in the sched_reorder attribution bucket
            self.tracer.add_span(req.rid, "queue_wait", queued_at, mark)
            self.tracer.add_span(req.rid, "sched_reorder", mark, admit_t,
                                 policy=self._sched.name)
        else:
            self.tracer.add_span(req.rid, "queue_wait", queued_at, admit_t)
        req.sched_reorder_at = None
        self._metrics.on_queue_wait(admit_t - queued_at)
        clog = get_compile_log()
        c0 = clog.count()
        g = req.config
        # replay (req.retries > 0, tokens already delivered): the row
        # resumes from prompt + delivered tokens.  The full sequence
        # re-prefills — with the prefix cache holding the pages retained
        # at failure time, only the uncached suffix runs through the
        # model — and the NEXT token samples at generation step
        # ``already`` (same fold_in stream the lost decode would have
        # used), so the consumer's stream continues without loss,
        # duplication or divergence.
        already = req.emitted
        # req.tokens is a host-side list — no device readback here
        full = (req.prompt if already == 0 else np.concatenate(
            # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
            [req.prompt, np.asarray(req.tokens, np.int32)]))
        length = int(full.size)
        budget = g.max_new_tokens - already
        cache = self._prefix_cache
        # adapter pinning precedes KV staging: the row must never enter
        # the batch without its fine-tune resident.  ``pin`` makes the
        # adapter resident (LRU-evicting an unpinned slot if it has to,
        # uploading from the host store) and pins the slot for the
        # row's lifetime.  MemoryError — every slot pinned by in-flight
        # rows — is BACKPRESSURE, not a failure: a pin frees as soon as
        # any active row exits, so the request parks at the queue head
        # without burning a retry, and the degradation ladder is fed
        # once per wait episode (shrink/shed) rather than once per
        # parked step.
        aslot = 0
        if self._adapters is not None and req.adapter_id is not None:
            try:
                aslot = self._adapters.pin(req.adapter_id)
                req._adapter_wait = False
            except UnknownAdapterError as e:
                # registered at submit time, dropped from the store
                # since — reject cleanly, nothing to unwind
                self._metrics.on_rejected()
                req._finish(RequestState.REJECTED, e)
                self._trace_queue_drop(req, RequestState.REJECTED,
                                       "unknown-adapter")
                return
            except MemoryError:
                if not getattr(req, "_adapter_wait", False):
                    req._adapter_wait = True
                    rec = self._recovery
                    if rec is not None:
                        rec.on_memory_pressure()
                    self.tracer.add_span(
                        req.rid, "adapter_wait", admit_t,
                        time.monotonic(), cause="slots-pinned")
                self._queue.push_front(req)
                return False
        match = None
        try:
            self._fault.fire("kv.alloc", rid=req.rid)
            self._pool.free(sid)
            if cache is not None:
                match = self._match_prefix(req, full)
                cached, reserve = self._stage_prefix(
                    sid, match, length, budget)
                prefill_t = time.monotonic()
                self.tracer.add_span(
                    req.rid, "prefix_match", admit_t, prefill_t,
                    cached_tokens=cached, blocks=len(match.blocks),
                    cow=int(match.partial_block is not None))
            else:
                cached = 0
                prefill_t = admit_t
                reserve = length + budget
                self._pool.reserve(sid, reserve)
        except Exception as e:
            if aslot:
                self._adapters.unpin(aslot)
            self._release_slot_kv(sid, match)
            now = time.monotonic()
            self.tracer.add_span(req.rid, "prefill", admit_t, now,
                                 slot=sid, outcome="failed")
            self.steplog.record(
                "prefill", wall_s=now - admit_t, host_s=now - admit_t,
                kernel="ragged",
                active_rows=self.active_count,
                resident_kv_pages=self._used_pages(),
                compile_events=clog.count() - c0, failed=True,
                retries=req.retries,
                degraded=self._effective_max_batch < self._max_batch)
            self._admit_failure(req, e)
            return
        suffix = length - cached
        table = np.full((self._max_pages,), self._scratch, np.int32)
        t = self._pool.block_table(sid)[:self._max_pages]
        # intentional host work at admission: the block table and the
        # per-request fold_in key are tiny, fetched once per admit
        # tpulint: disable-next-line=host-sync -- host-side page-table/cache-key staging buffer, built before dispatch
        table[:len(t)] = np.asarray(t, np.int32)
        # tpulint: disable-next-line=host-sync -- host-side page-table/cache-key staging buffer, built before dispatch
        key = np.asarray(
            jax.random.fold_in(jax.random.PRNGKey(g.seed), req.rid))  # tpulint: disable=determinism -- the rng key derives from (seed, rid) only; the time taint is a container-coarse read of the packet dict whose journey metadata carries wall-clocks
        # admission stages KV only: the uncached suffix waits in
        # ``pending`` and enters the NEXT mixed steps as
        # prefill_chunk-sized slices sharing launches with live decode
        # rows.  The prefill.run fault site still fires at admission so
        # injected prefill faults keep routing through the
        # admission-failure/replay path.
        try:
            self._fault.fire("prefill.run", rid=req.rid)
        except Exception as e:
            if aslot:
                self._adapters.unpin(aslot)
            self._release_slot_kv(sid, match)
            now = time.monotonic()
            self.tracer.add_span(req.rid, "prefill", admit_t, now,
                                 slot=sid, outcome="failed")
            self.steplog.record(
                "prefill", wall_s=now - admit_t, host_s=now - admit_t,
                prefill_tokens=suffix, kernel="ragged",
                active_rows=self.active_count,
                resident_kv_pages=self._used_pages(),
                prefix_hit_pages=len(match.blocks) if match else 0,
                compile_events=clog.count() - c0, failed=True,
                retries=req.retries,
                degraded=self._effective_max_batch < self._max_batch)
            self._admit_failure(req, e)
            return
        req._mark_active()
        # per-row FSM state is a pure function of the emitted
        # stream: advance from start through req.tokens (skipping
        # EOS).  Fresh admissions start at the start state; replays
        # recompute the exact state the lost slot held.
        fsm_state = None
        gfsm = getattr(req, "grammar_fsm", None)
        if gfsm is not None:
            fsm_state, _ = grammar_rt.advance_many(
                gfsm, gfsm.start, req.tokens, g.eos_token_id)
        self._slots[sid] = {
            "req": req, "sid": sid, "g": g,
            "length": int(req.prompt.size), "plen": suffix,
            "emitted": already, "steps_base": already,
            "last_tok": 0, "last_emit": admit_t,
            "table": table, "key": key, "match": match,
            "adapter_slot": aslot, "fsm": fsm_state,
            "span_end": prefill_t, "full": full,
            # host-side numpy slice of the staged prompt, no device sync
            # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
            "pending": np.asarray(full[cached:], np.int32),
            "ctx": int(cached)}

    # ---------------------------------------------------- failure paths
    def _admit_failure(self, req: Request, err: BaseException):
        """An admission (reservation/prefix/prefill) failed AFTER the
        slot's KV was released.  Route it through the recovery protocol:
        memory pressure feeds the degradation ladder, KV loss restarts
        the engine and replays every in-flight row, and the request
        itself is requeued under its retry budget or failed.

        Park-before-shed: a MemoryError first tries to preempt a victim
        into the host KV tier (cheap and reversible — nothing is lost);
        the degradation ladder only advances when the tier is exhausted
        or disabled."""
        rec = self._recovery
        if getattr(err, "lose_kv", False):
            self._engine.drop_kv_state()
        if (isinstance(err, MemoryError)
                and not self._engine.kv_state_lost()
                and self._park_for_pressure()):
            # a victim's pages and slot are free now: the request
            # re-enters at the queue head and retries this same pass,
            # without burning its replay budget or advancing the ladder
            self._queue.push_front(req)
            return
        if rec is not None:
            if isinstance(err, MemoryError):
                # its own ladder — not a crash-streak event
                rec.on_memory_pressure()
            else:
                rec.on_engine_failure(err)
        if self._engine.kv_state_lost():
            self._recover_lost_state(err)
        self._replay_or_fail(req, err)

    def _recover_lost_state(self, err: BaseException):
        """The device page pools were consumed by a failed donated call:
        count an engine restart, drop every retained cache page (the
        pools rebuild zeroed — their contents are garbage now) and
        replay or fail every in-flight row."""
        self._metrics.on_engine_restart()
        rec = self._recovery
        if rec is not None:
            rec.on_engine_restart()
        # release every slot BEFORE clearing the cache: clear() keeps
        # nodes pinned by live match references, and a node surviving
        # into the rebuilt (zeroed) pool would hand replayed rows stale
        # pages — silently corrupting their token streams
        for s in list(self._slots):
            if s is not None:
                self._replay_or_fail_slot(s, err, kv_intact=False)
        if self._prefix_cache is not None:
            self._prefix_cache.clear()
        # the loss is serviced: rebuild the pools (zeroed) NOW so a
        # later admission failure doesn't read the stale lost flag and
        # re-enter recovery (ragged admissions stage host-side state
        # only, so no dispatch clears it in between)
        self._engine.rebuild_kv_state()
        if self._kv_tier is not None:
            # parked packets are host-side and self-contained: they
            # survive the restart verbatim and later resume against the
            # rebuilt pools.  Reconciliation audits the tier's page
            # accounting against the parked set it carried across.
            n = self._kv_tier.reconcile_after_restart()
            if n:
                _log.info("engine restart: %d parked request(s) carried "
                          "across in the host KV tier", n)

    def _replay_or_fail(self, req: Request, err: BaseException):
        """Requeue ``req`` for replay at the queue head if the recovery
        protocol grants a retry; otherwise finish it FAILED (quarantined
        when a retry budget existed and is spent)."""
        rec = self._recovery
        if req.expired():
            self._metrics.on_deadline()
            req._finish(RequestState.CANCELLED, DeadlineExceededError(
                f"request {req.rid} deadline exceeded during recovery"))
            self._trace_end(req, RequestState.CANCELLED)
            return
        if rec is not None and rec.request_should_replay(req, err):
            req._requeue()
            self._metrics.on_retry()
            now = time.monotonic()
            self.tracer.add_span(req.rid, "recovery", now, now,
                                 retry=req.retries,
                                 cause=type(err).__name__)
            self._queue.push_front(req)
            return
        ferr = err
        if rec is not None:
            self._metrics.on_quarantined()
            ferr = QuarantinedError(
                f"request {req.rid} quarantined after {req.retries} "
                f"retries: {err!r}")
        self._metrics.on_failed()
        req._finish(RequestState.FAILED, ferr)
        self._trace_end(req, RequestState.FAILED)

    def _replay_or_fail_slot(self, s: dict, err: BaseException,
                             kv_intact: bool):
        """Slot-holding variant of ``_replay_or_fail``: releases the
        slot's KV first — retaining prompt + delivered tokens in the
        prefix cache when the pages are still valid, so the replay
        re-prefills only the uncached suffix."""
        req = s["req"]
        rec = self._recovery
        if req.expired():
            self._metrics.on_deadline()
            self._evict(s, RequestState.CANCELLED, DeadlineExceededError(
                f"request {req.rid} deadline exceeded during recovery"))
            return
        if rec is not None and rec.request_should_replay(req, err):
            self._slots[s["sid"]] = None
            # unpin the adapter for the replay wait: re-admission
            # re-pins (the adapter likely stays resident — only
            # unpinned slots are LRU candidates)
            self._release_adapter(s)
            retain = None
            pending = s.get("pending")
            mid_prefill = pending is not None and len(pending) > 0
            if kv_intact and self._prefix_cache is not None:
                if mid_prefill:
                    # ragged row mid-prefill: only the cached prefix plus
                    # the chunks consumed so far have valid KV — the
                    # pending suffix was never written
                    retain = (s["full"][:s["ctx"]]
                              if s.get("ctx", 0) > 0 else None)
                else:
                    # KV for prompt + all-but-the-last delivered token is
                    # valid in the row's pages (the last token's KV is
                    # never written until its decode step runs)
                    retain = np.concatenate(
                        # req.tokens is a host-side list — no readback
                        # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
                        [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
            self._release_slot_kv(s["sid"], s.get("match"),
                                  retain_tokens=retain,
                                  salt=req.route_salt())
            req._requeue()
            self._metrics.on_retry()
            now = time.monotonic()
            self.tracer.add_span(req.rid, "recovery",
                                 s.get("span_end", now), now,
                                 retry=req.retries,
                                 cause=type(err).__name__)
            self._queue.push_front(req)
            return
        if rec is not None:
            self._metrics.on_quarantined()
            ferr: BaseException = QuarantinedError(
                f"request {req.rid} quarantined after {req.retries} "
                f"retries: {err!r}")
        else:
            ferr = RejectedError(f"in-flight KV state lost: {err!r}")
        self._evict(s, RequestState.FAILED, ferr)

    # -------------------------------------------------- ragged mixed step
    def _mixed_step(self):
        """ONE ragged launch per scheduler step, whatever the batch
        composition: decode rows feed their last token (query_len 1),
        prompt rows feed their next ``prefill_chunk``-sized slice, all
        under the per-step ``token_budget``.  Decode rows are packed
        first so a long prompt arrival can never starve streaming
        clients — the prompt takes whatever budget is left each step.
        The executable key is composition-independent, so after one
        warmup compile every mix of cold chunks, warm-prefix suffixes
        and decode rows reuses it (CompileLog proves it in the
        composition fuzz)."""
        clock = self._clock
        clock.phase("pack")
        active = [s for s in self._slots if s is not None]
        b = self._max_batch
        C = self._token_budget
        # the step's one host buffer (serving/programs.step_input_layout),
        # reset to an all-inactive step: every table entry at the
        # scratch page, the sampling fields at their pass-through
        # values.  The packer writes each field through its view; bools
        # ride as 0 / 1 words.
        f = self._step_fields
        self._step_buf[:] = 0
        f["tables"][:] = self._scratch
        f["temperature"][:] = 1.0
        f["top_p"][:] = 1.0
        f["eos"][:] = -1
        f["scratch"][()] = self._scratch
        ids, qlens, ctx, steps0, sample_now = (
            f["ids"], f["qlens"], f["ctx"], f["steps0"], f["sample_now"])
        # per-row LoRA slot selection: slot 0 (all-zero identity) for
        # base-model rows and every inactive lane — pure data, so a
        # batch mixing 8 different fine-tunes runs the SAME executable
        aslots = f["adapter_slots"]
        # each row's tokens as the packer deals them; the program takes
        # them end to end on one flat [C] axis (``ids``, below)
        row_ids = np.zeros((b, C), np.int32)

        def pack_row(i, s):
            """Row ``i``'s fields that are the request's own, whatever
            it feeds this step: table, adapter slot, key, sampling."""
            g = s["g"]
            aslots[i] = s.get("adapter_slot", 0)
            f["tables"][i] = s["table"]
            f["keys"][i] = s["key"]
            f["temperature"][i] = g.temperature
            f["top_k"][i] = g.top_k or 0
            f["top_p"][i] = g.top_p
            f["min_len"][i] = g.min_length
            f["eos"][i] = -1 if g.eos_token_id is None else g.eos_token_id
            f["do_sample"][i] = g.do_sample
            f["pad"][i] = g.pad_token_id

        decode_rows = [s for s in active if s["pending"].size == 0]
        chunk_rows = [s for s in active if s["pending"].size > 0]
        eng = self._engine
        W = self._spec_window
        mkey = ("serve-step", b, C, self._max_pages,
                self._pool.num_blocks)
        if W > 1:
            # the speculative executable has its own static window in
            # the key — still ONE executable per core, warmed once
            mkey = mkey + (W,)
        moe = self._moe
        # one switch: the step threads its valid mask to whatever expert
        # layers the model has and returns what they counted
        moe_stats = moe is not None or self._dropless is not None
        residual = self._residual is not None
        if moe is not None:
            # the [E, C_cap] routing buffers are deployment config, so
            # they join the key — routing changes data, never shapes
            mkey = mkey + (moe["num_experts"], moe["capacity"])
        if self._lora is not None:
            # (slot count, rank) size the stacked pools — deployment
            # constants in the key; which adapter a row decodes under
            # stays per-row data and never recompiles
            mkey = mkey + (self._lora["slots"], self._lora["rank"])
        grammar_on = self._grammar is not None
        if grammar_on:
            # grammars are per-row DATA: the key records only the
            # static fact that this deployment threads a mask input.
            # Which grammar (if any) each row decodes under never
            # touches the key — 32 distinct grammars churn through one
            # executable (the churn fuzz proves it)
            mkey = mkey + ("grammar",)
        # StepPlanner: this step's per-row prompt-chunk cap + predicted
        # wall.  Static plans (fifo policy, cold fit, or no ITL SLO)
        # return cap == self._prefill_chunk, keeping the packing below
        # byte-identical to the pre-sched engine.
        plan = self._planner.plan(
            n_decode=len(decode_rows),
            pending=[int(s["pending"].size) for s in chunk_rows],
            pages=self._used_pages(), key=mkey)
        budget = C
        chunk_taken = {}
        for s in decode_rows:
            i = s["sid"]
            row_ids[i, 0] = s["last_tok"]
            qlens[i] = 1
            # the fed token's KV lands at length + emitted - 1
            ctx[i] = s["length"] + s["emitted"] - 1
            steps0[i] = s["emitted"]
            sample_now[i] = True
            pack_row(i, s)
            budget -= 1
        for s in chunk_rows:
            i = s["sid"]
            n = min(plan.chunk_cap, budget, int(s["pending"].size))
            if n <= 0:
                continue        # budget spent: the row waits this step
            row_ids[i, :n] = s["pending"][:n]
            qlens[i] = n
            ctx[i] = s["ctx"]
            steps0[i] = s["emitted"]
            # only the chunk holding the prompt's last token samples;
            # mid-prompt chunks return the pad id and emit nothing
            sample_now[i] = n == int(s["pending"].size)
            pack_row(i, s)
            budget -= n
            chunk_taken[i] = n
        # speculative drafts: ONLY leftover budget, so decode packing
        # and prefill pacing are byte-identical to speculate=False.  A
        # row's drafts stay inside its pool reservation
        # (k <= remaining - 1) and inside the window (k <= W - 1);
        # sampled rows take deterministic-by-history proposals only, so
        # supervisor replay regenerates the identical stream.
        spec = f.get("spec")         # a field of the W > 1 layout alone
        drafted = {}
        if self._speculate and budget > 0:
            for s in decode_rows:
                if budget <= 0:
                    break
                i = s["sid"]
                req = s["req"]
                remaining = s["g"].max_new_tokens - s["emitted"]
                k_cap = min(W - 1, remaining - 1, budget)
                if k_cap <= 0:
                    continue
                # host-side history (prompt + delivered tokens) feeds
                # the draft source; req.tokens is a host list
                tok_hist = req.tokens
                # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
                history = np.concatenate(
                    # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
                    [req.prompt, np.asarray(tok_hist, np.int32)])
                # drafts come from the row's OWN isolation domain: the
                # composed salt keeps one tenant's fine-tuned outputs
                # from seeding another tenant's speculation
                proposal = self._draft_source.propose(
                    history, k_cap, salt=req.route_salt(),
                    deterministic_only=bool(s["g"].do_sample))
                if s.get("fsm") is not None:
                    # constrained row: truncate the proposal at the
                    # first FSM-invalid token (and before any draft
                    # that EXHAUSTS the grammar — the finishing token
                    # must be the verified cut token so no lane ever
                    # samples from a no-continuation state).  The
                    # verify-side per-lane masks reject violations
                    # anyway; filtering just stops wasting budget.
                    proposal = grammar_rt.filter_drafts(
                        req.grammar_fsm, s["fsm"], proposal,
                        s["g"].eos_token_id)
                k_row = min(len(proposal), k_cap)
                if k_row <= 0:
                    continue
                # proposals are host ints from the draft source
                # tpulint: disable-next-line=host-sync -- speculative scratch readback at the verification boundary; verification is a host decision
                row_ids[i, 1:1 + k_row] = np.asarray(proposal[:k_row],
                                                     np.int32)
                qlens[i] = 1 + k_row
                spec[i] = True
                budget -= k_row
                drafted[i] = k_row
        # grammar masks: one [b, V] ([b, W, V] speculative) additive
        # f32 buffer gathered host-side from each constrained row's FSM
        # state — lane j masked by the state advanced through drafts
        # 0..j-1; plain rows replicate their current-state mask across
        # lanes; unconstrained rows ride all-zero rows.  Shape depends
        # only on deployment constants, so the executable never sees
        # which grammars are in the batch.
        gmask = None
        grammar_rows_step = 0
        masked_tokens_step = 0
        if grammar_on:
            V = len(self._grammar.vocab)
            gmask = np.zeros((b, V) if W <= 1 else (b, W, V), np.float32)
            for s in active:
                i = s["sid"]
                if s.get("fsm") is None or qlens[i] == 0:
                    continue
                gf = s["req"].grammar_fsm
                eos_id = s["g"].eos_token_id
                if W > 1:
                    if spec[i]:
                        lanes = grammar_rt.lane_masks(
                            gf, s["fsm"],
                            [int(t) for t in row_ids[i, 1:qlens[i]]],
                            W, eos_id)
                    else:
                        lanes = np.broadcast_to(
                            grammar_rt.mask_row(gf, s["fsm"], eos_id),
                            (W, V))
                    gmask[i] = lanes
                else:
                    gmask[i] = grammar_rt.mask_row(gf, s["fsm"], eos_id)
                if sample_now[i]:
                    grammar_rows_step += 1
                    masked_tokens_step += grammar_rt.masked_count(
                        gf, s["fsm"], eos_id)
        # the step's token axis: rows end to end in slot order (the
        # budget above bounds their sum by C), the tail padded
        ids[:int(qlens.sum())] = row_ids[np.arange(C)[None]
                                         < qlens[:, None]]
        draft_tokens_step = sum(drafted.values())
        prefill_tokens_step = sum(chunk_taken.values())
        n_decode = len(decode_rows)
        # rows carrying a non-identity adapter this step: each one adds
        # the 2*r*(d_in+d_out) LoRA factor walk the cost model prices
        adapter_rows_step = int(np.count_nonzero(aslots[qlens > 0]))
        # what the step's attention has to do, from the packer's own
        # arrays: row i's qlens[i] queries sit at ctx[i].. and each
        # attends to everything before it and itself
        ql = qlens.astype(np.int64)
        cx = np.where(ql > 0, ctx, 0).astype(np.int64)
        attended_keys_step = int((ql * cx + ql * (ql + 1) // 2).sum())
        resident_tokens_step = int((cx + ql).sum())
        decode_lengths = cx[ql == 1] + 1
        decode_keys_step = int(decode_lengths.sum())
        # the grid one layer's latent decode launch runs over them
        decode_grid_steps_step = decode_grid_steps(
            decode_lengths, self._page,
            self._max_pages) if self._latent_pages else 0
        index_fields = self._index_fields(ql, cx, attended_keys_step,
                                          decode_lengths)
        # what the sampling tail will do, by the rule the traced step
        # branches on: no row that filters, no sort; no row that draws,
        # no draw
        draw_rows_step, filter_rows_step = (
            int(np.count_nonzero(m)) for m in sampling_rows(f, sample_now))
        h2d_bytes_step = h2d_arrays_step = 0
        clog = get_compile_log()
        c0 = clog.count()
        t0 = clock.phase("launch")
        try:
            fault = self._fault.fire(
                "decode.step", rids=[s["req"].rid for s in active])
            # the host arrays handed to the step program: the packed
            # buffer, and on a grammar deployment the mask behind it (2
            # MB at a 32,000-token vocabulary: copying it into the
            # buffer would cost more than its own put)
            step_args = (self._step_buf,) + ((gmask,) if grammar_on else ())
            h2d_bytes_step = _host_bytes(step_args)
            h2d_arrays_step = len(step_args)
            step_outs = eng.run_paged_program(
                mkey, lambda: build_mixed_step(eng, b, C, self._max_pages,
                                               spec_window=W,
                                               moe_stats=moe_stats,
                                               grammar=grammar_on,
                                               residual_stats=residual),
                *step_args)
        except Exception as e:
            self._metrics.on_failed(0)
            # only a fault-plane injection raised BEFORE dispatch leaves
            # the pools provably intact; any exception out of the real
            # donated call may have consumed them (their contents —
            # every row's KV and every retained cache page — are then
            # garbage), so KV-intact replay is reserved for injections
            injected = isinstance(e, (InjectedFault, InjectedMemoryError))
            t_fail = clock.phase("emit")    # failed in the launch: no wait
            end = time.monotonic()
            self.steplog.record(
                "mixed" if chunk_taken and n_decode else
                ("prefill" if chunk_taken else "decode"),
                wall_s=end - t0, dispatch_s=t_fail - t0,
                attended_keys=attended_keys_step,
                resident_tokens=resident_tokens_step,
                h2d_bytes=h2d_bytes_step, h2d_arrays=h2d_arrays_step,
                draw_rows=draw_rows_step, filter_rows=filter_rows_step,
                **self._iteration_fields(clock, end),
                active_rows=len(active), decode_rows=n_decode,
                chunk_steps=1, prefill_tokens=prefill_tokens_step,
                prefill_chunk_tokens=prefill_tokens_step, token_slots=C,
                kernel="ragged",
                resident_kv_pages=self._used_pages(),
                compile_events=clog.count() - c0, faults=injected,
                retries=sum(s["req"].retries for s in active),
                failed=True,
                degraded=self._effective_max_batch < self._max_batch,
                draft_tokens=draft_tokens_step, spec_rows=len(drafted))
            if getattr(e, "lose_kv", False) or not injected:
                self._engine.drop_kv_state()
            rec = self._recovery
            if rec is not None:
                rec.on_engine_failure(e)
            if self._engine.kv_state_lost():
                self._recover_lost_state(e)
            else:
                for s in list(self._slots):
                    if s is not None:
                        self._replay_or_fail_slot(s, e, kv_intact=True)
            return
        # the launch has returned: from here the host only waits for the
        # device (JAX dispatch is asynchronous) until the read-back below
        clock.phase("wait")
        if not self._decode_warm:
            # one executable for EVERY composition: after this, any
            # compile on the serving-decode site is a recompile
            get_compile_log().mark_warm("serving-decode", mkey)
            self._decode_warm = True
        # the one designed sync per step: every host-bound output of the
        # program is read here (ONE array, step_output_layout), the
        # fields sliced out of it as views.  The wait is read in two
        # halves: engine.ready until the device's result is there, the
        # rest of the phase until it is on the host.  The copy is asked
        # for BEFORE the first half's wait, as np.asarray alone would:
        # asked for after it, every step pays the host's wake-up and the
        # copy's own latency one after the other (0.1-0.35 ms a step on
        # the chip, PERF.md section 6)
        with clock.child("ready"):
            for o in step_outs:
                o.copy_to_host_async()
            # tpulint: disable-next-line=host-sync -- the first half of the one per-step sync point: the array the next line reads anyway
            jax.block_until_ready(step_outs)
        # tpulint: disable-next-line=host-sync -- the sampled step output must reach Python for emission; this is the deliberate per-step sync point
        host_outs = [np.asarray(o) for o in step_outs]
        out = self._step_out.views(host_outs[0])
        tok, fin_out, n_emit = out["tok"], out["fin"], out.get("n_emit")
        moe_kw = {}
        if DROPLESS_COUNTERS[0] in out:
            moe_kw = {name: int(out[name])
                      for name in DROPLESS_COUNTERS + IDENTITY_COUNTERS
                      if name in out}
            if IDENTITY_COUNTERS[0] in moe_kw:
                self._metrics.on_identity_experts(
                    moe_kw[DROPLESS_COUNTERS[0]],
                    moe_kw[IDENTITY_COUNTERS[0]])
        elif "moe_routed" in out:
            m_routed = out["moe_routed"]
            m_dropped = int(out["moe_dropped"])
            m_aux = float(out["moe_aux"])
            moe_kw = dict(moe_tokens_routed=int(m_routed.sum()),
                          moe_tokens_dropped=m_dropped,
                          moe_aux_loss=m_aux)
            self._metrics.on_moe([int(x) for x in m_routed],
                                 m_dropped, m_aux)
        if residual:
            moe_kw.update(
                (name, (float if dtype == "float32" else int)(out[name]))
                for name, dtype in RESIDUAL_COUNTERS)
        t_sync = clock.phase("emit")
        # the step as the device ran it, launch to read-back: what the
        # server's step-time and ITL histograms report (the launch alone
        # returns in milliseconds, before the device has finished)
        synced = t_sync - t0
        resident = self._used_pages()
        prefix_hits = sum(len(s["match"].blocks)
                          if s.get("match") is not None else 0
                          for s in active)
        poisoned = set()
        if fault is not None and fault.get("nan_rids"):
            # injected NaN/inf logits poison the whole row (sampled or
            # mid-chunk) — quarantine it below
            poisoned = set(fault["nan_rids"])
        self._step_idx += 1
        emitted_decode = 0
        emitted_prefill = 0
        draft_accepted_step = 0
        evicted = []
        prefill_done: List[Request] = []
        now = time.monotonic()
        span_name = ("prefill" if self._prefix_cache is None
                     else "suffix_prefill")
        # the per-row loop is one child span; a row that leaves the batch
        # inside it (engine.release, nested) delays the rows behind it
        released_before = clock.child_seconds("release")
        with clock.child("emit_rows") as rows_span:
            for s in active:
                i = s["sid"]
                req = s["req"]
                if qlens[i] == 0:
                    continue            # starved chunk row: untouched
                was_chunk = i in chunk_taken
                if was_chunk:
                    n = chunk_taken[i]
                    s["pending"] = s["pending"][n:]
                    s["ctx"] += n
                sampled = bool(sample_now[i])
                if n_emit is None:
                    t_row = (np.asarray([int(tok[i])], np.int32) if sampled
                             else np.zeros((0,), np.int32))
                else:
                    # speculative step: row i emits its accepted window
                    # prefix (always >= 1 token when it sampled) — the one
                    # intended host readback of this step's tokens
                    # tpulint: disable-next-line=host-sync -- the sampled step output must reach Python for emission; this is the deliberate per-step sync point
                    t_row = np.asarray(tok[i, :int(n_emit[i])], np.int32)
                bad = t_row.size > 0 and int(t_row.min()) < 0
                if req.rid in poisoned or (sampled and bad):
                    self._metrics.on_quarantined()
                    self._evict(s, RequestState.FAILED, QuarantinedError(
                        f"request {req.rid} quarantined: non-finite logits "
                        f"in mixed step {self._step_idx}"))
                    evicted.append(req.rid)
                    continue
                if was_chunk:
                    self.tracer.add_span(
                        req.rid, span_name, s.get("span_end", t0), now,
                        slot=i, plen=chunk_taken[i],
                        cached_tokens=int(s["ctx"]) - chunk_taken[i],
                        replay=req.retries)
                    s["span_end"] = now
                    if sampled:
                        # prefill complete: this chunk held the prompt's
                        # last token and sampled the row's next token
                        if s["steps_base"] == 0:
                            self._metrics.on_prefill(now - req.arrival)
                        # tpulint: disable-next-line=determinism -- container-coarse slot read: t_row is the device step output; the slot dict's wall-clock bookkeeping (last_emit, span ends) is sibling metadata
                        req._emit(t_row)
                        self._metrics.on_tokens(int(t_row.size))
                        s["emitted"] += int(t_row.size)
                        s["last_tok"] = int(t_row[-1])
                        s["last_emit"] = now
                        emitted_prefill += int(t_row.size)
                        prefill_done.append(req)
                else:
                    # tpulint: disable-next-line=determinism -- container-coarse slot read: t_row is the device step output; the slot dict's wall-clock bookkeeping (last_emit, span ends) is sibling metadata
                    req._emit(t_row)
                    s["emitted"] += int(t_row.size)
                    s["last_tok"] = int(t_row[-1])
                    s["last_emit"] = now
                    emitted_decode += int(t_row.size)
                    if i in drafted:
                        draft_accepted_step += max(int(t_row.size) - 1, 0)
                    self.tracer.add_span(req.rid, "decode",
                                         s.get("span_end", t0), now,
                                         step=self._step_idx, chunk_steps=1,
                                         tokens=int(t_row.size))
                    s["span_end"] = now
                if sampled and s.get("fsm") is not None:
                    gf = req.grammar_fsm
                    if t_row.size:
                        # FSM state stays a pure function of emitted tokens:
                        # re-fold the accepted row output (masking makes
                        # violations impossible; count defensively anyway)
                        s["fsm"], viol = grammar_rt.advance_many(
                            gf, s["fsm"], t_row, s["g"].eos_token_id)
                        self._grammar_violations += viol
                    if bool(fin_out[i]) or gf.complete(s["fsm"]):
                        # EOS (mask-legal only in accept states) or the
                        # grammar has no continuation: stream is complete
                        self._evict(s, RequestState.DONE)
                        evicted.append(req.rid)
                    elif s["emitted"] >= s["g"].max_new_tokens:
                        if gf.accepting(s["fsm"]):
                            self._evict(s, RequestState.DONE)
                        else:
                            self._grammar_incomplete += 1
                            self._evict(s, RequestState.FAILED,
                                        GrammarIncompleteError(
                                            f"request {req.rid} exhausted "
                                            f"max_new_tokens="
                                            f"{s['g'].max_new_tokens} in "
                                            f"non-accepting FSM state "
                                            f"{int(s['fsm'])}"))
                        evicted.append(req.rid)
                elif sampled and (bool(fin_out[i])
                                  or s["emitted"] >= s["g"].max_new_tokens):
                    self._evict(s, RequestState.DONE)
                    evicted.append(req.rid)
        emit_rows_s = rows_span.seconds - (
            clock.child_seconds("release") - released_before)
        if emitted_decode:
            self._metrics.on_tokens(emitted_decode, itl_s=synced)
        self._metrics.on_step(synced * 1e3, len(active), b)
        if index_fields:
            self._metrics.on_index(index_fields["index_scored_keys"],
                                   index_fields["index_selected_keys"])
        self.step_trace.append({
            "step": self._step_idx, "batch_steps": 1,
            "active": [s["req"].rid for s in active],
            "evicted": evicted})
        kind = ("mixed" if chunk_taken and n_decode else
                ("prefill" if chunk_taken else "decode"))
        # verify rows are priced at their true query_len: each draft
        # token is one more processed position (KV walk + weight pass)
        bts, fl, src_tag = self._cost_model.estimate(
            kind, mkey, rows=len(active), max_rows=b,
            pages_touched=resident,
            tokens=n_decode + prefill_tokens_step + draft_tokens_step,
            adapter_rows=adapter_rows_step)
        ici, ici_saved = self._cost_model.interconnect(
            n_decode + prefill_tokens_step + draft_tokens_step)
        if drafted:
            self._metrics.on_spec(rows=len(drafted),
                                  proposed=draft_tokens_step,
                                  accepted=draft_accepted_step)
        end = time.monotonic()
        self.steplog.record(
            kind, wall_s=end - t0, dispatch_s=t_sync - t0,
            **self._iteration_fields(clock, end),
            emit_rows_s=emit_rows_s,
            attended_keys=attended_keys_step,
            resident_tokens=resident_tokens_step,
            decode_keys=decode_keys_step,
            decode_grid_steps=decode_grid_steps_step, **index_fields,
            draw_rows=draw_rows_step, filter_rows=filter_rows_step,
            h2d_bytes=h2d_bytes_step, h2d_arrays=h2d_arrays_step,
            d2h_arrays=len(host_outs),
            program_temp_bytes=self._program_temp_bytes(mkey),
            active_rows=len(active),
            decode_rows=n_decode, chunk_steps=1,
            prefill_tokens=prefill_tokens_step,
            prefill_chunk_tokens=prefill_tokens_step, token_slots=C,
            kernel="ragged",
            emitted_tokens=emitted_decode + emitted_prefill,
            resident_kv_pages=resident,
            prefix_hit_pages=prefix_hits, bytes_est=bts, flops_est=fl,
            ici_bytes_est=ici, ici_bytes_saved_est=ici_saved,
            cost_source=src_tag, compile_events=clog.count() - c0,
            faults=fault is not None,
            retries=sum(s["req"].retries for s in active),
            degraded=self._effective_max_batch < self._max_batch,
            draft_tokens=draft_tokens_step,
            draft_accepted=draft_accepted_step,
            spec_rows=len(drafted),
            adapter_rows=adapter_rows_step,
            planned_tokens=plan.planned_tokens,
            planned_chunk_cap=plan.chunk_cap,
            # price the composition actually packed (drafts included),
            # not the planner's pre-packing simulation
            predicted_wall_s=self._planner.predict_wall(bts),
            parked_rows=(self._kv_tier.parked_count
                         if self._kv_tier is not None else 0),
            host_pages=(self._kv_tier.resident_pages
                        if self._kv_tier is not None else 0),
            grammar_rows=grammar_rows_step,
            masked_tokens=masked_tokens_step,
            **moe_kw, **self._cache_bytes_fields())
        if self._recovery is not None:
            self._recovery.on_step_ok()
        # chunk-boundary hook: fired by the stepping thread itself (still
        # under the step RLock) the step a row's prompt finishes
        # prefilling.  The fleet router migrates here synchronously — an
        # external thread polling for this moment loses the step-lock
        # race on a busy core and can miss the whole decode phase.
        if self.on_prefill_complete is not None:
            for _req in prefill_done:
                if _req.done:
                    continue
                try:
                    self.on_prefill_complete(_req)
                except Exception:       # pragma: no cover - hook safety
                    _log.exception(
                        "on_prefill_complete hook failed for rid=%d",
                        _req.rid)

    # ---------------------------------------------------------- eviction
    def _evict(self, slot: dict, state: RequestState,
               err: Optional[BaseException] = None):
        self._slots[slot["sid"]] = None
        req = slot["req"]
        self._release_adapter(slot)
        # retain-on-finish: a DONE row's prompt + emitted tokens (minus
        # the last — its KV is never written) have valid KV in the
        # row's pages; donate them to the prefix cache instead of
        # freeing.  Cancelled/failed rows may hold partial or garbage
        # KV and are never retained.
        retain = None
        if state == RequestState.DONE and self._prefix_cache is not None:
            # req.tokens is a host-side list — no device readback here
            retain = np.concatenate(
                [req.prompt,
                 # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
                 np.asarray(req.tokens[:-1], np.int32)])
        try:
            pages = len(self._pool.block_table(slot["sid"]))
        except Exception:
            pages = 0
        before = self._cache_counters()
        with self._child("release") as release:
            self._release_slot_kv(slot["sid"], slot.get("match"),
                                  retain_tokens=retain,
                                  salt=req.route_salt())
        wall = release.seconds
        evict_s, blocks, scanned, insert_s = (
            b - a for a, b in zip(before, self._cache_counters()))
        cache = self._prefix_cache
        bts, fl, src_tag = self._cost_model.estimate("evict",
                                                     pages_touched=pages)
        self.steplog.record(
            "evict", wall_s=wall, host_s=wall,
            insert_s=insert_s, evict_s=evict_s, evicted_blocks=blocks,
            evict_scanned_nodes=scanned,
            retained_blocks=(cache.cached_blocks
                             if cache is not None else 0),
            active_rows=self.active_count, pages_freed=pages,
            resident_kv_pages=self._used_pages(),
            bytes_est=bts, flops_est=fl, cost_source=src_tag,
            failed=state == RequestState.FAILED,
            retries=req.retries,
            degraded=self._effective_max_batch < self._max_batch)
        req._finish(state, err)
        now = time.monotonic()
        self.tracer.add_span(req.rid, "evict", slot.get("span_end", now),
                             now,
                             outcome=_TRACE_STATE.get(state, state.value))
        self._trace_end(req, state)
        if state == RequestState.DONE:
            self._metrics.on_completed(time.monotonic() - req.arrival)
            if req.sched_predicted_done is not None:
                # score the slack policy's completion prediction against
                # the actual finish (both on the monotonic clock)
                self._slack_err.append(
                    abs(req.finished_at - req.sched_predicted_done))
        elif state == RequestState.FAILED:
            self._metrics.on_failed()

    def _run_exclusive(self, req: Request):
        if req.expired():
            self._metrics.on_deadline()
            req._finish(RequestState.CANCELLED, DeadlineExceededError(
                f"request {req.rid} expired in queue"))
            self._trace_queue_drop(req, RequestState.CANCELLED,
                                   "deadline-in-queue")
            return
        start = time.monotonic()
        self.tracer.add_span(req.rid, "queue_wait", req.arrival, start)
        self._metrics.on_queue_wait(start - req.arrival)
        req._mark_active()
        try:
            req.value = req.exclusive_fn()
            req._finish(RequestState.DONE)
            self._metrics.on_completed(time.monotonic() - req.arrival)
            self.tracer.add_span(req.rid, "exclusive", start,
                                 time.monotonic())
            self._trace_end(req, RequestState.DONE)
        except Exception as e:
            self._metrics.on_failed()
            req._finish(RequestState.FAILED, e)
            self.tracer.add_span(req.rid, "exclusive", start,
                                 time.monotonic(), outcome="failed")
            self._trace_end(req, RequestState.FAILED)

    # ------------------------------------------------- host KV tier
    # Park/resume preemption (serving/kv_tier/): the handoff
    # serialization below, retargeted at a host buffer instead of a
    # peer replica.  Parking releases a victim row's slot, pages and
    # adapter pin while its KV bytes and scheduler state wait in host
    # RAM; resuming reconstructs the slot bitwise, so sustained load
    # beyond device-pool capacity time-slices instead of shedding.

    _SWAP_ATTEMPTS = 3      # bounded retries per swap fault site

    def _gather_blocks(self, blocks: np.ndarray):
        """Device->host gather of ``blocks``'s page contents across
        every layer's K/V pools.  Quantized pools gather (payload,
        scale) pairs so the bytes round-trip bitwise — and at half the
        host footprint of an fp pool."""
        k_pages, v_pages = self._engine._ensure_pages()

        def gather(pages):
            if isinstance(pages, tuple):
                payload, scales = pages
                # tpulint: disable-next-line=host-sync -- KV tiering serializes pages to host RAM by design; the swap traffic IS the feature
                hp = np.asarray(payload[blocks])
                # tpulint: disable-next-line=host-sync -- KV tiering serializes pages to host RAM by design; the swap traffic IS the feature
                hs = np.asarray(scales[blocks])
                return (hp, hs)
            # tpulint: disable-next-line=host-sync -- KV tiering serializes pages to host RAM by design; the swap traffic IS the feature
            return np.asarray(pages[blocks])

        return ([gather(kp) for kp in k_pages],
                [gather(vp) for vp in v_pages])

    def _scatter_blocks(self, dst, k_host, v_host):
        """Host->device scatter into pages ``dst`` — the inverse of
        ``_gather_blocks``.  ``.at[].set`` is out-of-place, so the
        rebound arrays replace the engine's pools atomically."""
        eng = self._engine
        k_pages, v_pages = eng._ensure_pages()

        def scatter(pages, h):
            if isinstance(pages, tuple):
                payload, scales = pages
                hp, hs = h
                return (payload.at[dst].set(hp), scales.at[dst].set(hs))
            return pages.at[dst].set(h)

        eng._k_pages = [scatter(kp, h) for kp, h in zip(k_pages, k_host)]
        eng._v_pages = [scatter(vp, h) for vp, h in zip(v_pages, v_host)]

    def park_for_pressure(self) -> bool:
        """Public park-before-shed hook: preempt ONE victim row into
        the host KV tier, freeing its pages, slot and adapter pin.  The
        supervisor's degradation ladder calls this before shrinking the
        batch or shedding; only a False return (tier disabled, full, or
        no parkable victim) should advance the ladder."""
        with self._step_lock:
            return self._park_for_pressure()

    def _park_for_pressure(self, predictive: bool = False) -> bool:
        if self._kv_tier is None:
            return False
        from .sched.policy import park_victim_order
        active = [s for s in self._slots if s is not None]
        for s in park_victim_order(active, time.monotonic()):
            if self._park_slot(s, reason=("predictive" if predictive
                                          else "memory-pressure"),
                               predictive=predictive):
                return True
        return False

    def _park_slot(self, s: dict, reason: str,
                   predictive: bool = False) -> bool:
        """Preempt one active row into the host KV tier (the handoff
        export retargeted at a host buffer).  On success the slot is
        free, the adapter pin dropped, and the row's prefix pages stay
        warm in the radix tree; the request remains ACTIVE and resumes
        bitwise later.  Returns False — slot fully intact — when the
        tier can't hold the row or the ``kv.swap_out`` fault site
        exhausts its bounded retries (callers fall back to the existing
        shed/replay ladder)."""
        tier = self._kv_tier
        req = s["req"]
        t0 = time.monotonic()
        sid = s["sid"]
        page = self._page
        if s["pending"].size:
            # mid-prefill: KV covers the consumed prompt only
            kv_len = int(s["ctx"])
            # tpulint: disable-next-line=host-sync -- s["full"] is the host-side token staging buffer, never a device array
            kv_tokens = np.asarray(s["full"][:kv_len], np.int32)
        else:
            # decode phase: prompt + emitted minus the last token (its
            # KV is written by the NEXT step, wherever that runs)
            kv_len = int(s["length"]) + int(s["emitted"]) - 1
            kv_tokens = np.concatenate(
                # req.tokens is a host-side list — no device readback
                # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
        n_pages = -(-kv_len // page) if kv_len > 0 else 0
        if not tier.can_park(n_pages):
            return False
        # bounded-retry swap-out: a transport fault here must leave the
        # slot untouched — nothing has been gathered or released yet
        err = None
        for _ in range(self._SWAP_ATTEMPTS):
            try:
                self._fault.fire("kv.swap_out", rid=req.rid)
                err = None
                break
            except (InjectedFault, InjectedMemoryError) as e:
                err = e
                tier.on_swap_retry()
        if err is not None:
            tier.on_swap_fail()
            return False
        # tpulint: disable-next-line=host-sync -- the pool's block table is host-side bookkeeping, not a device array
        blocks = np.asarray(
            self._pool.block_table(sid)[:n_pages], np.int32)
        k_host, v_host = self._gather_blocks(blocks)
        packet = {
            "req": req, "g": s["g"], "full": s["full"],
            "pending": s["pending"], "ctx": int(s["ctx"]),
            "emitted": int(s["emitted"]),
            "steps_base": int(s["steps_base"]),
            "last_tok": int(s["last_tok"]), "plen": int(s["plen"]),
            "kv_len": kv_len, "kv_tokens": kv_tokens,
            "k_host": k_host, "v_host": v_host, "page": page,
            "salt": req.cache_salt, "adapter_id": req.adapter_id,
            # FSM state is a plain int riding the packet as data —
            # resume re-attaches it without recompiling the grammar
            "grammar": req.grammar,
            "fsm_state": (int(s["fsm"]) if s.get("fsm") is not None
                          else None),
            # journey context rides the packet as plain data so a
            # parked row keeps its cross-replica identity (the tier
            # stores packets opaquely; drain/inspection tools see it)
            "journey": self._journeys.context(req.rid, self.replica_name),
        }
        try:
            # tpulint: disable-next-line=determinism -- the park packet carries journey wall-clock metadata by design (latency attribution across the park); the replay fields (salt, tokens, fsm_state) are time-free
            tier.park(req.rid, packet, n_pages, step=self._step_idx,
                      predictive=predictive)
        except MemoryError:     # raced capacity check; slot untouched
            return False
        # anti-starvation aging input: victims with prior parks sort
        # last, so repeated pressure rotates across rows (time-slicing)
        req.park_count += 1
        self._slots[sid] = None
        # unpin for the parked wait: resume re-pins (the adapter stays
        # resident as an LRU candidate meanwhile)
        self._release_adapter(s)
        self._release_slot_kv(
            sid, s.get("match"),
            retain_tokens=kv_tokens if kv_tokens.size else None,
            salt=req.route_salt())
        wall = time.monotonic() - t0
        bts, fl, src_tag = self._cost_model.estimate(
            "page_copy", pages_touched=n_pages)
        self.steplog.record(
            "park", wall_s=wall, host_s=wall,
            active_rows=self.active_count, pages_freed=n_pages,
            resident_kv_pages=self._used_pages(),
            parked_rows=tier.parked_count,
            host_pages=tier.resident_pages,
            bytes_est=bts, flops_est=fl, cost_source=src_tag,
            retries=req.retries,
            degraded=self._effective_max_batch < self._max_batch)
        now = time.monotonic()
        self.tracer.add_span(req.rid, "park", s.get("span_end", t0),
                             now, pages=n_pages, kv_tokens=kv_len,
                             cause=reason)
        return True

    def _resume_parked(self, now: float) -> bool:
        """Re-enter parked requests ahead of queue admission.  Watermark
        hysteresis: while other work keeps the engine busy, a parked row
        resumes only once its reservation fits with the park/resume
        watermark gap to spare, so park and resume can never thrash; a
        row parked for ``aging_steps`` scheduler steps bypasses the gate
        (anti-starvation — sustained oversubscription degrades into
        round-robin time-slicing, not permanent preemption)."""
        tier = self._kv_tier
        progressed = False
        while True:
            entry = tier.peek_parked()
            if entry is None:
                break
            rid, packet, n_pages, parked_step = entry
            req = packet["req"]
            if req.expired(now):
                tier.drop(rid)
                self._metrics.on_deadline()
                req._finish(RequestState.CANCELLED, DeadlineExceededError(
                    f"request {rid} deadline exceeded while parked"))
                self._trace_end(req, RequestState.CANCELLED)
                progressed = True
                continue
            if (None not in self._slots
                    or self.active_count >= self._effective_max_batch):
                break
            g = packet["g"]
            reserve = int(req.prompt.size) + g.max_new_tokens
            need = -(-reserve // self._page)
            busy = self.active_count > 0 or len(self._queue) > 0
            aged = (self._step_idx - parked_step) >= tier.aging_steps
            if (busy and not aged
                    and self._pool.free_blocks < need
                    + tier.hysteresis_pages(self._pool.num_blocks)):
                break
            if not self._resume_slot(rid, packet, n_pages,
                                     self._slots.index(None)):
                break
            progressed = True
        return progressed

    def _resume_slot(self, rid: int, packet: dict, n_pages: int,
                     sid: int) -> bool:
        """Install one parked packet back into slot ``sid`` (the
        handoff import retargeted at the host tier).  Returns True when
        the tier entry was consumed — resumed into the slot, or dropped
        to the replay ladder after ``kv.swap_in`` exhausted its bounded
        retries — and False when the row must stay parked (adapter pin
        or page reservation unavailable right now)."""
        tier = self._kv_tier
        req: Request = packet["req"]
        g = packet["g"]
        t0 = time.monotonic()
        # re-pin the adapter BEFORE pool ops, exactly like admission:
        # the row must never re-enter the batch without its fine-tune
        aslot = 0
        if req.adapter_id is not None and self._adapters is not None:
            try:
                aslot = self._adapters.pin(req.adapter_id)
            except (MemoryError, UnknownAdapterError):
                return False    # pins free as active rows exit
        length = int(req.prompt.size)
        full = packet["full"]
        reserve = length + g.max_new_tokens
        self._pool.free(sid)
        try:
            if self._prefix_cache is not None:
                self._prefix_cache.ensure_free(-(-reserve // self._page))
            self._pool.reserve(sid, reserve)
        except MemoryError:
            self._pool.free(sid)
            if aslot:
                self._adapters.unpin(aslot)
            return False
        # bounded-retry swap-in: a fault that survives every retry
        # unwinds the reservation and pin, then falls back to the
        # existing shed/replay ladder — replay regenerates the stream
        # exactly (per-request (seed, rid) sampling keys)
        err = None
        for _ in range(self._SWAP_ATTEMPTS):
            try:
                self._fault.fire("kv.swap_in", rid=req.rid)
                err = None
                break
            except (InjectedFault, InjectedMemoryError) as e:
                err = e
                tier.on_swap_retry()
        if err is not None:
            tier.on_swap_fail()
            self._pool.free(sid)
            if aslot:
                self._adapters.unpin(aslot)
            tier.drop(rid)
            self._replay_or_fail(req, err)
            return True
        table = np.full((self._max_pages,), self._scratch, np.int32)
        t = self._pool.block_table(sid)[:self._max_pages]
        # tpulint: disable-next-line=host-sync -- host-side page-table/cache-key staging buffer, built before dispatch
        table[:len(t)] = np.asarray(t, np.int32)
        if n_pages:
            self._scatter_blocks(table[:n_pages], packet["k_host"],
                                 packet["v_host"])
        # tpulint: disable-next-line=host-sync -- host-side page-table/cache-key staging buffer, built before dispatch
        key = np.asarray(
            jax.random.fold_in(jax.random.PRNGKey(g.seed), req.rid))  # tpulint: disable=determinism -- the rng key derives from (seed, rid) only; the time taint is a container-coarse read of the packet dict whose journey metadata carries wall-clocks
        now = time.monotonic()
        self._slots[sid] = {
            "req": req, "sid": sid, "g": g, "length": length,
            "plen": int(packet["plen"]),
            "emitted": int(packet["emitted"]),
            "steps_base": int(packet["steps_base"]),
            "last_tok": int(packet["last_tok"]), "last_emit": now,
            "table": table, "key": key, "match": None,
            "adapter_slot": aslot, "span_end": now, "full": full,
            "pending": packet["pending"], "ctx": int(packet["ctx"]),
            "fsm": packet.get("fsm_state")}
        tier.complete_resume(rid)
        wall = now - t0
        bts, fl, src_tag = self._cost_model.estimate(
            "page_copy", pages_touched=n_pages)
        self.steplog.record(
            "resume", wall_s=wall, host_s=wall,
            active_rows=self.active_count,
            resident_kv_pages=self._used_pages(),
            parked_rows=tier.parked_count,
            host_pages=tier.resident_pages,
            bytes_est=bts, flops_est=fl, cost_source=src_tag,
            retries=req.retries,
            degraded=self._effective_max_batch < self._max_batch)
        self.tracer.add_span(req.rid, "resume", t0, now, pages=n_pages,
                             kv_tokens=int(packet["kv_len"]))
        return True

    def _demote_block(self, salt, path, block) -> None:
        """Prefix-tree eviction hook: gather the evicted full block's
        pages to host BEFORE the tree drops its ref, so a later miss on
        the same prefix promotes the bytes back instead of re-running
        the prefill.  Skipped while the device pools are lost — their
        contents are garbage and must not be preserved."""
        tier = self._kv_tier
        if tier is None or self._engine.kv_state_lost():
            return
        k_host, v_host = self._gather_blocks(
            np.asarray([int(block)], np.int32))
        tier.demote((salt, tuple(path)), {"k": k_host, "v": v_host})

    def _promote_into_match(self, req: Request, tokens: np.ndarray,
                            match) -> None:
        """Promote-on-hit: extend a radix-tree match from the host tier.
        Each demoted full page whose exact token path continues the
        match is scattered into a freshly allocated device block and
        grafted back into the tree (which takes ownership of the
        allocation ref), making the tree's effective capacity
        host-RAM-sized."""
        cache = self._prefix_cache
        tier = self._kv_tier
        page = self._page
        # same usable cap as the tree's own matcher: at least one
        # suffix token must run through the model
        usable = int(tokens.size) - 1
        salt = req.route_salt()
        # tpulint: disable-next-line=host-sync -- prompt tokens are host-side int32 (cache-key material), never a device array
        toks = [int(t) for t in np.asarray(tokens)]
        while (len(match.blocks) + 1) * page <= usable:
            depth = len(match.blocks)
            path = tuple(toks[:(depth + 1) * page])
            payload = tier.promote((salt, path))
            if payload is None:
                return
            try:
                cache.ensure_free(1)
                blk = self._pool.alloc_block()
            except MemoryError:
                tier.restore_demoted((salt, path), payload)
                return
            try:
                self._scatter_blocks(np.asarray([blk], np.int32),
                                     payload["k"], payload["v"])
            except BaseException:
                self._pool.unref_block(blk)
                tier.restore_demoted((salt, path), payload)
                raise
            # a full promoted page supersedes any partial tail the
            # original match carried
            cache.trim(match, depth * page)
            if not cache.graft(match, path[depth * page:], blk):
                # tree already grew this child meanwhile; keep its copy
                self._pool.unref_block(blk)

    # ---------------------------------------------- cross-replica handoff
    # Disaggregated serving (serving/fleet/): a prefill replica runs a
    # prompt's chunked prefill, then streams the row's KV pages to a
    # decode replica at the chunk boundary.  Export serializes the
    # slot's scheduler state plus the physical page contents and
    # releases the slot (retaining the prefix in this replica's radix
    # tree — that is what keeps prefix-affinity routing warm); import
    # reserves pages in the TARGET pool, writes the contents back and
    # reconstructs the slot bitwise: the per-request sampling key
    # depends only on (seed, rid), decode positions only on
    # (length, emitted), and attention only on the page CONTENTS the
    # table maps — none of which change across the move.

    def export_handoff(self, req: Request) -> dict:
        """Serialize ``req``'s in-flight KV state out of this core and
        release its slot.  Legal at any point between scheduler steps
        (the step lock serializes against a running step); the natural
        call site is the chunk boundary where the prompt finished
        prefilling.  Returns the handoff packet ``import_handoff``
        consumes.  Raises ``HandoffError`` without side effects when
        the request holds no slot here."""
        with self._step_lock:
            s = None
            for cand in self._slots:
                if cand is not None and cand["req"] is req:
                    s = cand
                    break
            if s is None:
                raise HandoffError(
                    f"request {req.rid} holds no slot on this replica")
            t0 = time.monotonic()
            sid = s["sid"]
            page = self._page
            if s["pending"].size:
                # mid-prefill boundary: KV covers the consumed prompt
                kv_len = int(s["ctx"])
                kv_tokens = np.asarray(s["full"][:kv_len], np.int32)
            else:
                # decode phase: prompt + emitted tokens minus the last
                # (its KV is written by the NEXT step, wherever it runs)
                kv_len = int(s["length"]) + int(s["emitted"]) - 1
                kv_tokens = np.concatenate(
                    # req.tokens is a host-side list — no device readback
                    # tpulint: disable-next-line=host-sync -- host-side prompt/token-history assembly; req.tokens are already-emitted Python ints, not device arrays
                    [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
            n_pages = -(-kv_len // page) if kv_len > 0 else 0
            blocks = np.asarray(
                self._pool.block_table(sid)[:n_pages], np.int32)
            k_pages, v_pages = self._engine._ensure_pages()

            # the intended bulk sync of a handoff: one gather per layer
            # pulls the row's pages off the device (a real deployment
            # DMAs pool-to-pool over ICI; the host hop keeps this exact).
            # Quantized pools gather (payload rows, scale rows) pairs so
            # the importer reconstructs the pages bitwise.
            def gather(pages):
                if isinstance(pages, tuple):
                    payload, scales = pages
                    # tpulint: disable-next-line=host-sync -- handoff export serializes KV to host bytes; the request is off the hot path by definition
                    return (np.asarray(payload[blocks]),
                            np.asarray(scales[blocks]))
                # tpulint: disable-next-line=host-sync -- handoff export serializes KV to host bytes; the request is off the hot path by definition
                return np.asarray(pages[blocks])

            k_host = [gather(kp) for kp in k_pages]
            v_host = [gather(vp) for vp in v_pages]
            packet = {
                "req": req, "g": s["g"], "full": s["full"],
                "pending": s["pending"], "ctx": int(s["ctx"]),
                "emitted": int(s["emitted"]),
                "steps_base": int(s["steps_base"]),
                "last_tok": int(s["last_tok"]), "plen": int(s["plen"]),
                "kv_len": kv_len, "kv_tokens": kv_tokens,
                "k_host": k_host, "v_host": v_host, "page": page,
                "salt": req.cache_salt,
                # adapter binding travels WITH the KV: the importer must
                # pin the same fine-tune before the row decodes there
                "adapter_id": req.adapter_id,
                # grammar + FSM state travel as plain data; the importer
                # recompiles (or cache-hits) the grammar and re-attaches
                # the int state — the stream stays bitwise-identical
                "grammar": req.grammar,
                "fsm_state": (int(s["fsm"]) if s.get("fsm") is not None
                              else None),
            }
            self._slots[sid] = None
            # unpin here, re-pin on the importer: the source keeps the
            # adapter resident only as an LRU candidate once the row
            # leaves
            self._release_adapter(s)
            # retain the exported prefix here: the whole point of role
            # disaggregation is that the PREFILL replica's radix tree
            # accumulates the fleet's prompt prefixes
            self._release_slot_kv(
                sid, s.get("match"),
                retain_tokens=kv_tokens if kv_tokens.size else None,
                salt=req.route_salt())
            wall = time.monotonic() - t0
            bts, fl, src_tag = self._cost_model.estimate(
                "page_copy", pages_touched=n_pages)
            self.steplog.record(
                "handoff", wall_s=wall, host_s=wall,
                active_rows=self.active_count, pages_freed=n_pages,
                resident_kv_pages=self._used_pages(),
                bytes_est=bts, flops_est=fl, cost_source=src_tag,
                retries=req.retries,
                degraded=self._effective_max_batch < self._max_batch)
            now = time.monotonic()
            self.tracer.add_span(req.rid, "handoff",
                                 s.get("span_end", t0), now,
                                 direction="export", pages=n_pages,
                                 kv_tokens=kv_len)
            # journey context travels WITH the KV: the importer stitches
            # this hop (export end -> import start) into one journey
            packet["journey"] = self._journeys.context(
                req.rid, self.replica_name, export_end=now)
            # tpulint: disable-next-line=determinism -- the handoff packet carries journey wall-clock metadata by design (export_end stitches the cross-replica hop); the replay fields are time-free
            return packet

    def import_handoff(self, packet: dict) -> Request:
        """Install an exported request into this core: reserve pages in
        this pool, write the packet's page contents into them and
        reconstruct the slot so the next scheduler step continues the
        stream bitwise-identically to the replica it left.  Raises
        ``HandoffError`` (target untouched) when no slot/pages are
        available or the pool geometry differs."""
        req: Request = packet["req"]
        g = packet["g"]
        # re-attach (cache hit) or recompile the grammar binding BEFORE
        # taking the step lock: FSM compilation is host work that must
        # never stall the decode loop, and a target that can't serve
        # the grammar refuses the whole handoff with the source slot
        # still intact
        if packet.get("grammar") is not None:
            if self._grammar is None:
                raise HandoffError(
                    f"request {req.rid} is grammar-constrained but "
                    "the target replica serves no grammars")
            try:
                req.grammar_fsm = self._grammar.get_or_compile(
                    packet["grammar"])
            except GrammarError as e:
                raise HandoffError(
                    f"target replica cannot compile grammar for "
                    f"request {req.rid}: {e}") from e
        with self._step_lock:
            if self._closed:
                raise HandoffError("serving engine is closed")
            if self._drain_evt.is_set():
                raise HandoffError("target replica is draining")
            if int(packet["page"]) != self._page:
                raise HandoffError(
                    f"page-size mismatch: source {packet['page']} vs "
                    f"target {self._page}")
            eng = self._engine
            k_pages, v_pages = eng._ensure_pages()

            def geom(entry):
                """Page geometry net of the pool axis; (payload, scale)
                geometries for quantized entries so a quantized<->fp
                replica pair can never silently exchange pages."""
                if isinstance(entry, tuple):
                    return (entry[0].shape[1:], entry[1].shape[1:])
                return entry.shape[1:]

            if (len(packet["k_host"]) != len(k_pages)
                    or (packet["k_host"]
                        and geom(packet["k_host"][0])
                        != geom(k_pages[0]))):
                raise HandoffError("KV pool geometry mismatch between "
                                   "replicas")
            kv_len = int(packet["kv_len"])
            n_pages = -(-kv_len // self._page) if kv_len > 0 else 0
            length = int(req.prompt.size)
            full = packet["full"]
            if length + g.max_new_tokens > self._max_model_len:
                raise HandoffError(
                    f"prompt {length} + max_new {g.max_new_tokens} "
                    f"exceeds target max_model_len {self._max_model_len}")
            if self.active_count >= self._effective_max_batch:
                raise HandoffError("no batch capacity on target replica")
            sid = next((i for i, sl in enumerate(self._slots)
                        if sl is None), None)
            if sid is None:
                raise HandoffError("no free slot on target replica")
            # pin the adapter binding BEFORE touching the pool: a
            # target that can't make the fine-tune resident must refuse
            # the whole handoff with the source slot still intact
            aslot = 0
            if req.adapter_id is not None:
                if self._adapters is None:
                    raise HandoffError(
                        f"request {req.rid} is bound to adapter "
                        f"{req.adapter_id!r} but the target replica "
                        "serves no adapters")
                try:
                    aslot = self._adapters.pin(req.adapter_id)
                except (MemoryError, UnknownAdapterError) as e:
                    raise HandoffError(
                        f"target replica cannot pin adapter "
                        f"{req.adapter_id!r}: {e}") from e
            t0 = time.monotonic()
            reserve = length + g.max_new_tokens
            self._pool.free(sid)
            try:
                if self._prefix_cache is not None:
                    self._prefix_cache.ensure_free(-(-reserve // self._page))
                self._pool.reserve(sid, reserve)
            except MemoryError as e:
                self._pool.free(sid)
                if aslot:
                    self._adapters.unpin(aslot)
                raise HandoffError(
                    "target pool has no pages for the handoff") from e
            table = np.full((self._max_pages,), self._scratch, np.int32)
            t = self._pool.block_table(sid)[:self._max_pages]
            # host-side table/key bookkeeping, once per import
            # tpulint: disable-next-line=host-sync -- host-side page-table/cache-key staging buffer, built before dispatch
            table[:len(t)] = np.asarray(t, np.int32)
            if n_pages:
                dst = table[:n_pages]

                # one scatter per layer lands the imported pages in this
                # pool; .at[].set is out-of-place, so the rebound arrays
                # replace the engine's pools atomically.  Quantized
                # entries scatter payload and scale rows together.
                def scatter(pages, h):
                    if isinstance(pages, tuple):
                        payload, scales = pages
                        hp, hs = h
                        return (payload.at[dst].set(hp),
                                scales.at[dst].set(hs))
                    return pages.at[dst].set(h)

                eng._k_pages = [scatter(kp, h) for kp, h
                                in zip(k_pages, packet["k_host"])]
                eng._v_pages = [scatter(vp, h) for vp, h
                                in zip(v_pages, packet["v_host"])]
            # tpulint: disable-next-line=host-sync -- host-side page-table/cache-key staging buffer, built before dispatch
            key = np.asarray(
                jax.random.fold_in(jax.random.PRNGKey(g.seed), req.rid))  # tpulint: disable=determinism -- the rng key derives from (seed, rid) only; the time taint is a container-coarse read of the packet dict whose journey metadata carries wall-clocks
            now = time.monotonic()
            self._slots[sid] = {
                "req": req, "sid": sid, "g": g, "length": length,
                "plen": int(packet["plen"]),
                "emitted": int(packet["emitted"]),
                "steps_base": int(packet["steps_base"]),
                "last_tok": int(packet["last_tok"]), "last_emit": now,
                "table": table, "key": key, "match": None,
                "adapter_slot": aslot,
                "span_end": now, "full": full,
                "pending": packet["pending"], "ctx": int(packet["ctx"]),
                "fsm": packet.get("fsm_state")}
            wall = now - t0
            bts, fl, src_tag = self._cost_model.estimate(
                "page_copy", pages_touched=n_pages)
            self.steplog.record(
                "handoff", wall_s=wall, host_s=wall,
                active_rows=self.active_count,
                resident_kv_pages=self._used_pages(),
                bytes_est=bts, flops_est=fl, cost_source=src_tag,
                retries=req.retries,
                degraded=self._effective_max_batch < self._max_batch)
            # a fleet may give each replica its own Tracer: the imported
            # rid has no trace here yet, and add_span on a missing rid
            # silently drops the import span
            if self.tracer.get(req.rid) is None:
                self.tracer.begin(req.rid, kind="batch",
                                  prompt_len=length,
                                  max_new_tokens=g.max_new_tokens,
                                  imported=True)
            self.tracer.add_span(req.rid, "handoff", t0, now,
                                 direction="import", pages=n_pages,
                                 kv_tokens=kv_len)
            # hop edge: bump the journey's hop count and record the
            # transfer interval (source export end -> this import start)
            self._journeys.record_import(
                req.rid, packet.get("journey"), self.replica_name,
                t0, now, pages=n_pages, kv_tokens=kv_len)
            return req

    # ---------------------------------------------------- thread control
    def start(self) -> "EngineCore":
        if self._thread is None:
            self._stop_evt.clear()
            self._thread = threading.Thread(
                target=self._loop, name="serving-engine-core", daemon=True)
            self._thread.start()
        return self

    def _loop(self):
        backoff = 0.01
        while not self._stop_evt.is_set():
            try:
                self.run_once(wait_s=0.02)
                backoff = 0.01
            except Exception:
                # requests are failed individually; the scheduler itself
                # must outlive any one bad program — but not silently:
                # count it, log each distinct traceback once, and back
                # off exponentially so a wedged engine can't spin hot
                self._metrics.on_loop_exception()
                tb = traceback.format_exc()
                sig = hash(tb)
                if sig not in self._loop_tb_seen \
                        and len(self._loop_tb_seen) < 256:
                    self._loop_tb_seen.add(sig)
                    _log.exception(
                        "serving loop step failed (backing off %.3fs)",
                        backoff)
                self._stop_evt.wait(backoff)
                backoff = min(backoff * 2.0, 1.0)

    def stop(self, timeout: float = 10.0) -> bool:
        """Signal and join the loop thread.  Returns True when the
        thread is down (or was never started) — False means it is still
        wedged in a step after ``timeout`` and teardown must not assume
        exclusive ownership of the pool."""
        if self._thread is None:
            return True
        self._stop_evt.set()
        t, self._thread = self._thread, None
        t.join(timeout)
        return not t.is_alive()

    def close(self, timeout: float = 10.0):
        """Stop the loop, cancel everything in flight, and release every
        pool reservation (incl. scratch) so the engine can be reused.
        If the loop thread can't be joined (a step is wedged), escalate:
        fail the queue and every in-flight request directly — without
        touching the pool the wedged step still owns."""
        if self._closed:
            return
        self._closed = True
        self._gc.remove()
        stopped = self.stop(timeout)
        # the loop thread is joined, but callers driving run_once()
        # from their own threads may still be mid-step — hold the step
        # lock so teardown can't interleave with a decode chunk.  A
        # wedged step (loop join timed out, or an external run_once()
        # caller stuck in a device call) may hold the lock forever, so
        # the wait is always bounded before escalating.
        acquired = self._step_lock.acquire(
            timeout=(max(timeout, 0.1) if stopped else 2.0))
        if acquired:
            try:
                # re-entrant: already held via acquire() above — the
                # ``with`` makes the lock scope explicit for teardown
                with self._step_lock:
                    for r in self._queue.drain():
                        r._finish(RequestState.REJECTED,
                                  RejectedError("serving engine closed"))
                        self._trace_queue_drop(r, RequestState.REJECTED,
                                               "engine-closed")
                    for s in list(self._slots):
                        if s is not None:
                            self._evict(s, RequestState.CANCELLED,
                                        RejectedError(
                                            "serving engine closed"))
                    if self._kv_tier is not None:
                        # parked requests hold no pool pages — their KV
                        # lives in the tier — but their consumers still
                        # block on result(); finish them like the queue
                        for _, packet in self._kv_tier.drain_parked():
                            packet["req"]._finish(
                                RequestState.REJECTED,
                                RejectedError("serving engine closed"))
                            self._trace_end(packet["req"],
                                            RequestState.REJECTED)
                        self._kv_tier.clear_demoted()
                    if self._prefix_cache is not None:
                        self._prefix_cache.clear()
                    self._pool.free(self._max_batch)
            finally:
                self._step_lock.release()
            return
        # escalation path: no lock, no pool ops — just unblock every
        # consumer so close() can't strand callers of result()/stream()
        for r in self._queue.drain():
            r._finish(RequestState.REJECTED, RejectedError(
                "serving engine closed (scheduler wedged)"))
            self._trace_queue_drop(r, RequestState.REJECTED,
                                   "engine-closed")
        # tpulint: disable-next-line=lock-discipline -- close() escalation after a bounded step-lock acquire timed out: the stepping thread is wedged, last-resort cleanup reads slots lock-free on purpose
        for s in list(self._slots):
            if s is not None:
                s["req"]._finish(RequestState.FAILED, RejectedError(
                    "serving engine closed while a step was wedged"))
                self._trace_end(s["req"], RequestState.FAILED)
        if self._kv_tier is not None:
            # host-only bookkeeping: safe even while a step is wedged
            for _, packet in self._kv_tier.drain_parked():
                packet["req"]._finish(RequestState.FAILED, RejectedError(
                    "serving engine closed while a step was wedged"))
                self._trace_end(packet["req"], RequestState.FAILED)
