"""StepLog — the step-level flight recorder for the serving scheduler.

Request traces (``observability/tracing``) attribute one *request's*
wall time; nothing records what one *scheduler step* cost and why.
That per-step view — batch composition, resident KV pages, bytes the
step analytically must move, measured wall split into device dispatch
vs host bookkeeping — is exactly the feature set a per-step cost model
trains on ("A Learned Performance Model for TPUs", PAPERS.md), and the
ROADMAP's cost-model-driven-scheduling item starts from it.

``serving.EngineCore`` appends one record per step event (mixed step /
page copy / evict) into a bounded ring with a
fixed schema (``SCHEMA_KEYS``; the table in docs/OBSERVABILITY.md).
``GET /steps`` serves the recent ring, ``to_jsonl()`` exports it, and
``summary()`` folds the ring into Prometheus-ready aggregates plus a
rolling model-vs-measured error: the analytic bytes estimate is fitted
to measured decode walls by a single least-bias scale (Σwall/Σbytes —
the one free parameter a bandwidth model has), then scored by mean
absolute relative error and Pearson correlation.

The analytic estimate composes two sources (``StepCostModel``):

  * per-executable ``compiled.cost_analysis()`` — flops and
    "bytes accessed" of the whole program at its padded shapes, AOT
    lowered once per program key and cached by
    ``PagedGenerationEngine.program_cost``.  The AOT compile is
    invisible to the CompileLog (which counts first-call signatures in
    ``run_paged_program``), so enabling StepLog cannot trip the
    zero-post-warmup-decode-compile invariant;
  * per-step page counts — the static analysis assumes the worst-case
    pool window, so its KV traffic (2 × pool bytes, read + write) is
    rescaled to the pages actually resident this step, and the non-KV
    remainder (weights, activations) to the occupied rows.

When the backend offers no cost analysis the model falls back to an
analytic roofline (weight bytes per scan step + resident KV page
bytes); either way every decode/prefill record carries a nonzero
``bytes_est``.
"""
from __future__ import annotations

import json
import math
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .stable import sorted_tree

# one entry per record field: (key, default).  Every record carries
# every key — consumers (JSONL, /steps, bench) never need .get chains.
_SCHEMA = (
    ("seq", 0),                  # monotone record index (process-local)
    ("ts", 0.0),                 # wall-clock capture time (time.time())
    ("kind", ""),                # prefill | decode | mixed | page_copy
                                 # | evict
    ("kernel", ""),              # ragged (step-serving records)
    ("wall_s", 0.0),             # whole step event, edge to edge
    ("dispatch_s", 0.0),         # device dispatch + readback sync
                                 # (== launch_s + wait_s)
    ("host_s", 0.0),             # wall_s - dispatch_s (host bookkeeping:
                                 # emission, eviction, cost model, record)
    # the scheduler iteration's phases, taken once by
    # observability.stepclock.StepClock (seconds, time.monotonic()) and
    # mirrored as engine.* spans into the profiler's trace.  For a
    # serving step: t_begin + admit_s + pack_s + launch_s + wait_s +
    # host_s is the step's end, and the next step's gap_s reaches its
    # t_begin.  Page-copy / evict / park / resume records keep 0.
    ("t_begin", 0.0),            # monotonic time the iteration that ran
                                 # this step entered _run_once_locked
    ("step", 0),                 # the engine's step index: the step_num
                                 # its engine.step profiler span carries
    ("gap_s", 0.0),              # previous serving step's end -> t_begin
                                 # (loop turn, step lock, supervisor)
    ("admit_s", 0.0),            # t_begin -> the step method entered
                                 # (expiry, exclusive, admission, _admit)
    ("pack_s", 0.0),             # rows to arrays, planner, drafts, masks
    ("launch_s", 0.0),           # signature, host-to-device puts, enqueue
    ("wait_s", 0.0),             # blocked in the read-back: the device's
                                 # run and the device-to-host copy
    # parts of the phases above, timed by StepClock.child and by the
    # prefix cache on the same two reads as the engine.ready,
    # engine.emit_rows, engine.release, prefix.insert, prefix.evict spans
    # of the trace (children of wait_s and host_s, not added to them)
    ("ready_s", 0.0),            # of wait_s, until the device's result
                                 # was ready (block_until_ready); the rest
                                 # is the copy to the host and whatever
                                 # kept the thread from it
    ("emit_rows_s", 0.0),        # the per-row loop after the read-back
                                 # (tokens to their streams, spans, the
                                 # grammar), less the release_s inside it
    ("release_s", 0.0),          # step record: the iteration's
                                 # _release_slot_kv calls of rows leaving
                                 # the batch, summed (each is its evict
                                 # record's wall_s)
    ("finished_rows", 0),        # step record: how many rows left the
                                 # batch this iteration (its evict records)
    ("insert_s", 0.0),           # PrefixCache.insert of finished
                                 # sequences' pages: evict record, this
                                 # release's; step record, the sum since
                                 # the previous step's record
    ("evict_s", 0.0),            # the prefix cache's loops over
                                 # _evict_one (enforce_watermark at a
                                 # release, ensure_free wherever it is
                                 # called): evict record, this release's;
                                 # step record, all since the previous
                                 # step's record, admission's included
    ("evicted_blocks", 0),       # blocks those loops evicted (same deltas)
    ("evict_scanned_nodes", 0),  # tree entries their searches for a
                                 # victim examined (same deltas)
    ("retained_blocks", 0),      # evict record: blocks the prefix cache
                                 # holds after the release
    ("gc_s", 0.0),               # step record: seconds of the
                                 # interpreter's collections of generation
                                 # 1 or 2, on any thread, that passed
                                 # between the previous step record's end
                                 # and this one's (one still running at
                                 # the record is split between the two)
    ("gc_gen2", 0),              # generation-2 collections that began in
                                 # that interval
    ("cpu_s", 0.0),              # step record: the engine thread's own
                                 # CPU seconds (time.thread_time) from
                                 # t_begin to the record; where the kernel
                                 # accounts by the tick, in steps of 10 ms
    ("off_cpu_s", 0.0),          # step record: the iteration's wall less
                                 # wait_s less cpu_s, not under 0: seconds
                                 # of the host phases in which the thread
                                 # did not run (the GIL, the scheduler)
    ("attended_keys", 0),        # query-key pairs the step's attention
                                 # must compute (sum qlen*ctx + tri(qlen))
    ("resident_tokens", 0),      # cached tokens the step reads (sum over
                                 # rows with qlen > 0 of ctx + qlen)
    ("decode_keys", 0),          # of attended_keys, those of the decode
                                 # rows (sum over rows with qlen == 1 of
                                 # ctx + 1)
    ("decode_grid_steps", 0),    # grid steps of one layer's latent decode
                                 # launch: live decode rows x the walk
                                 # of the longest one (ceil((ctx + 1) /
                                 # keys a grid step), the kernel's own
                                 # decode_grid); 0 with no decode row or
                                 # no latent pages; at most max_batch x
                                 # ceil(max_pages / pages a grid step),
                                 # a full batch of full-length rows
    # a layer whose attention reads an indexer's selection (models/
    # latent_moe.py ``index_topk``); 0 on a model without one.  Counted
    # for ONE layer: every layer of the step does the same
    ("index_scored_keys", 0),    # query-key pairs the index scores: all
                                 # of attended_keys (the indexer scores
                                 # every cached token a query may read)
    ("index_selected_keys", 0),  # of those, the pairs attention then
                                 # reads: sum over query tokens of
                                 # min(index_topk, position + 1)
    ("index_decode_scored_keys", 0),    # the same two over the decode
    ("index_decode_selected_keys", 0),  # rows alone (what the kernels
                                        # dsa_index_scores and
                                        # dsa_sparse_decode work on)
    ("index_gathered_rows", 0),  # latent rows the selection copies out
                                 # of the pool: live decode rows x
                                 # index_topk rounded up to the sparse
                                 # decode's blocks (select_rows' loop,
                                 # one trip a live row); over max_batch
                                 # x that width, the share of a full
                                 # batch's gather the step paid
    ("draw_rows", 0),            # rows that drew their token this step
                                 # (sample_now and do_sample); a step
                                 # with none ran no categorical draw
    ("filter_rows", 0),          # of those, the rows with top_k set or
                                 # top_p under 1; a step with none ran
                                 # no vocabulary-wide sort
    ("h2d_bytes", 0),            # bytes of the host arrays handed to the
                                 # step program this step
    ("h2d_arrays", 0),           # how many host arrays that was: 1, the
                                 # packed buffer (2 with a grammar mask);
                                 # 0 on records that launch no step
    ("d2h_arrays", 0),           # device arrays the step read back,
                                 # counted as read: 1, the packed output
    ("program_temp_bytes", 0),   # the compiled step's temporaries
                                 # (memory_analysis; 0 where not offered)
    ("active_rows", 0),          # occupied slots at capture
    ("decode_rows", 0),          # rows that fed a decode token
    ("prefill_tokens", 0),       # uncached suffix tokens prefetched
    ("prefill_chunk_tokens", 0),  # prompt tokens chunked into this
                                  # ragged mixed step
    ("token_slots", 0),          # length of the step program's flat
                                 # token axis (the token budget): the
                                 # slots decode_rows + prefill_chunk_
                                 # tokens + draft_tokens fill; 0 on
                                 # records that launch no step
    ("chunk_steps", 0),          # fused scan steps (decode) / 1
    ("emitted_tokens", 0),       # tokens delivered to consumers
    ("resident_kv_pages", 0),    # pool pages in use at capture
    ("prefix_hit_pages", 0),     # pages served from the prefix cache
    ("pages_freed", 0),          # pages released (evict records)
    ("bytes_est", 0.0),          # analytic bytes-moved estimate
    ("flops_est", 0.0),          # analytic FLOPs estimate
    ("ici_bytes_est", 0.0),      # analytic interconnect bytes (mp
                                 # all-reduces; 0 single-device)
    ("ici_bytes_saved_est", 0.0),  # interconnect bytes the quantized
                                   # wire format saved vs fp
    ("cost_source", "none"),     # xla+pages | analytic | none
    ("compile_events", 0),       # CompileLog events during the step
    ("faults", False),           # fault plane fired during the step
    ("retries", 0),              # replayed rows involved in the step
    ("degraded", False),         # effective_max_batch < max_batch
    ("failed", False),           # the step raised / the row failed
    ("draft_tokens", 0),         # speculative draft tokens verified
    ("draft_accepted", 0),       # drafts accepted (extra tokens won)
    ("spec_rows", 0),            # rows that carried drafts this step
    ("adapter_rows", 0),         # rows decoding under a non-identity
                                 # LoRA adapter slot this step
    ("moe_tokens_routed", 0),    # valid token-expert assignments kept
                                 # this step (summed over moe layers)
    ("moe_tokens_dropped", 0),   # valid assignments lost to capacity
                                 # overflow (NEVER silent)
    ("moe_aux_loss", 0.0),       # gate load-balance aux loss (mean
                                 # across moe layers)
    # dropless expert layers (serving/moe/dropless.py): nothing is ever
    # dropped there, so these count what was routed, over valid slots
    ("moe_assignments_total", 0),  # valid tokens x top-k x expert layers
    ("moe_assignments_held", 0),   # of those, to experts held on this
                                   # chip (summed over expert layers)
    ("moe_held_expert_max", 0),  # largest count any held expert got in
                                 # any layer this step
    ("moe_experts_touched", 0),  # held experts with at least one token,
                                 # summed over expert layers
    # ... of a model whose router also scores identity experts (an
    # assignment to one computes nothing); 0 on a model without them
    ("moe_assignments_identity", 0),  # valid tokens' assignments to
                                      # identity experts, summed over
                                      # expert layers
    ("moe_real_per_token_max", 0),  # the most computing experts one
                                    # valid token chose in a layer, the
                                    # step's largest
    # hyper-connected residual streams (nn/hyper_connections.py); 0 on a
    # model with the plain residual
    ("mhc_col_sum_gap_max", 0.0),  # largest |colsum(H_res) - 1| over the
                                   # step's valid slots and every
                                   # sub-layer: what Sinkhorn's rounds
                                   # leave of the constraint
    ("residual_streams", 0),     # streams the residual carries (hc_mult)
    ("residual_stream_bytes", 0),  # bytes a token of the streams as
                                   # stored (n x hidden x itemsize), read
                                   # from the array
    ("cache_bytes_per_token", 0),  # the allocated pools' bytes over their
                                   # token capacity, all layers, scales
                                   # and a latent row's lane padding
                                   # included (read from the arrays once;
                                   # on every ragged step)
    ("latent_cache_bytes_per_token", 0),  # of the layers of cache kind
                                          # "latent", the same with the
                                          # lanes past their stated width
                                          # taken off: what is cached
    ("index_cache_bytes_per_token", 0),   # of those pools, the latent
                                          # layers' index-key pools (the
                                          # arrays' own bytes; 0 without
                                          # an indexer)
    ("planned_tokens", 0),       # tokens the StepPlanner chose to pack
    ("planned_chunk_cap", 0),    # per-row prompt-chunk cap this step
    ("predicted_wall_s", 0.0),   # planner's predicted step wall (0.0
                                 # while the fit is cold)
    ("parked_rows", 0),          # requests parked in the host KV tier
                                 # at capture
    ("host_pages", 0),           # host-tier pages resident at capture
                                 # (parked KV + demoted prefix blocks)
    ("grammar_rows", 0),         # grammar-constrained rows that sampled
                                 # through a mask this step
    ("masked_tokens", 0),        # vocab entries the grammar masks banned
                                 # across those rows this step
)
SCHEMA_KEYS = tuple(k for k, _ in _SCHEMA)
_SCHEMA_KEYSET = frozenset(SCHEMA_KEYS)   # built once: record() checks every call


class StepCostModel:
    """Analytic per-step cost estimates for one engine's programs.

    Composes the cached per-executable ``cost_analysis()`` (static, at
    padded shapes) with per-step page/row counts; falls back to a
    weights+KV roofline when the backend has no cost analysis.  All
    sizing constants come from the engine at construction time."""

    def __init__(self, engine, pool):
        self._engine = engine
        self._pool_pages = int(pool.num_blocks)
        try:
            import numpy as np

            itemsize = int(np.dtype(engine._cache_dtype).itemsize)
        except Exception:
            itemsize = 2
        # one physical page across every layer's K and V pools.  A
        # quantized pool prices the CONFIGURED payload width (int8 = 1
        # byte) plus the per-page float32 scales (one per page per head,
        # k and v) — pricing fp bytes would overstate decode-step HBM
        # traffic ~2-4x and skew the router's load-balance signal.
        kv_dtype = getattr(engine, "_kv_dtype", None)
        if kv_dtype is not None:
            payload_itemsize = int(np.dtype(kv_dtype).itemsize)
            scale_bytes = engine._num_layers * 2 * engine._num_heads * 4
        else:
            payload_itemsize = itemsize
            scale_bytes = 0
        layout = getattr(engine, "_cache_layout", None)
        per_token = (sum(c.stored_per_token() for c in layout) if layout
                     else engine._num_layers * 2 * engine._num_heads
                     * engine._head_dim)
        self._page_kv_bytes = float(
            per_token * engine.page_size * payload_itemsize + scale_bytes)
        self._pool_bytes = self._page_kv_bytes * self._pool_pages
        self._weight_bytes: Optional[float] = None
        self._n_params: Optional[float] = None
        # interconnect model: tensor-parallel serving runs 2 mp
        # all-reduces per layer (attention out-proj + MLP fc2), each
        # moving one [tokens, hidden] activation over the ring
        self._hidden = int(engine._num_heads * engine._head_dim)
        self._layers = int(engine._num_layers)
        self._quant = getattr(engine, "_quant_allreduce", None)
        self._mp = 1
        mesh = getattr(engine, "_mesh", None)
        if mesh is not None:
            try:
                from ..parallel.topology import axis_if_divides

                if axis_if_divides(mesh, "mp", self._hidden):
                    self._mp = int(dict(mesh.shape).get("mp", 1))
            except Exception:
                pass
        try:
            import numpy as np

            self._act_itemsize = int(np.dtype(next(
                iter(engine._params.values())).dtype).itemsize)
        except Exception:
            self._act_itemsize = 4
        # expert-parallel interconnect: each serving MoE layer moves its
        # [E, C, d] dispatched buffer over the ep axis twice per step
        # (dispatch + combine all-to-all), (ep-1)/ep of the payload
        # leaving each rank.  Sized at construction — EngineCore builds
        # the cost model after prepare_moe_serving, so the converted
        # layers' static capacity is what gets priced.
        self._moe_a2a = None
        model = getattr(engine, "_model", None)
        if model is not None:
            try:
                from ..serving.moe import ServingMoELayer
                from ..serving.moe.layer import _algo_of

                moes = [lay for _, lay in model.named_sublayers()
                        if isinstance(lay, ServingMoELayer)]
                if moes:
                    ep = 1
                    if mesh is not None:
                        from ..parallel.topology import axis_if_divides

                        if axis_if_divides(mesh, "ep",
                                           moes[0].num_experts):
                            ep = int(dict(mesh.shape).get("ep", 1))
                    self._moe_a2a = {
                        "layers": len(moes),
                        "elems": int(moes[0].num_experts
                                     * moes[0].capacity * self._hidden),
                        "algo": _algo_of(moes[0].inner),
                        "ep": ep,
                    }
            except Exception:
                self._moe_a2a = None
        # multi-LoRA adapter pricing: a row bound to a non-identity
        # slot gathers its per-layer (A, B) factors — 4*r*(d_in+d_out)
        # bytes per converted layer — on top of the base weight pass.
        # Sized at construction like the MoE term: EngineCore builds
        # the cost model after prepare_lora_serving.
        self._lora_row_bytes = 0.0
        if model is not None:
            try:
                from ..serving.adapters.layer import lora_layers

                self._lora_row_bytes = float(sum(
                    4 * lay.rank * (lay.in_features + lay.out_features)
                    for _, lay in lora_layers(model)))
            except Exception:
                self._lora_row_bytes = 0.0

    @property
    def page_kv_bytes(self) -> float:
        return self._page_kv_bytes

    def _weights(self):
        if self._weight_bytes is None:
            try:
                import jax

                leaves = jax.tree_util.tree_leaves(self._engine._params)
                self._weight_bytes = float(
                    sum(getattr(p, "nbytes", 0) for p in leaves))
                self._n_params = float(
                    sum(getattr(p, "size", 0) for p in leaves))
            except Exception:
                self._weight_bytes = 1.0
                self._n_params = 1.0
        return self._weight_bytes, self._n_params

    def interconnect(self, tokens: int):
        """``(ici_bytes_est, ici_bytes_saved_est)`` for one step that
        computed ``tokens`` query tokens: 2 mp all-reduces per layer of
        a [tokens, hidden] activation, ring model 2(r-1)/r of the
        payload per rank.  Saved is the fp-vs-int8 wire delta when the
        engine serves with the quantized format; (0, 0) single-device.

        The estimate is also fed into the collective-bytes ledger under
        op "mp_allreduce" — these reductions are GSPMD-inserted (or
        hidden inside the mp_quant_matmul shard_map), so no explicit
        ``collective.*`` call ever accounts for them.  Under expert
        parallelism each serving MoE layer adds its dispatch + combine
        all-to-alls (ledger op "ep_alltoall"): the payload is the fixed
        [E, C, d] routing buffer, so the term is per-STEP, not
        per-token — int8-activation experts move 1-byte dispatch
        payloads and the fp-vs-int8 delta lands in ``saved``."""
        if tokens is None or tokens <= 0:
            return 0.0, 0.0
        from ..parallel.collective import LEDGER, quantized_wire_bytes

        moved_total = 0.0
        saved_total = 0.0
        if self._mp > 1:
            n_elems = int(tokens) * self._hidden
            per_reduce_q, per_reduce_fp = quantized_wire_bytes(
                n_elems, self._mp, self._act_itemsize)
            n_reduces = 2.0 * self._layers
            if self._quant:
                moved = n_reduces * per_reduce_q
                saved = n_reduces * max(per_reduce_fp - per_reduce_q,
                                        0.0)
                LEDGER.record("mp_allreduce", "int8", moved, saved=saved)
            else:
                moved = n_reduces * per_reduce_fp
                saved = 0.0
                LEDGER.record("mp_allreduce",
                              f"float{8 * self._act_itemsize}", moved)
            moved_total += moved
            saved_total += saved
        a2a = self._moe_a2a
        if a2a is not None and a2a["ep"] > 1:
            off_rank = a2a["elems"] * (a2a["ep"] - 1) / a2a["ep"]
            fp_leg = off_rank * self._act_itemsize
            if a2a["algo"] == "int8_act":
                # dispatch leg carries the quantized buffer (1 byte per
                # element); the combine leg returns fp expert outputs
                per_layer = off_rank + fp_leg
                saved = fp_leg - off_rank
                dtype = "int8"
            else:
                per_layer = 2.0 * fp_leg
                saved = 0.0
                dtype = f"float{8 * self._act_itemsize}"
            moved = per_layer * a2a["layers"]
            saved = saved * a2a["layers"]
            LEDGER.record("ep_alltoall", dtype, moved, saved=saved)
            moved_total += moved
            saved_total += saved
        return moved_total, saved_total

    def static_cost(self, key) -> Optional[dict]:
        getter = getattr(self._engine, "program_cost", None)
        if getter is None or key is None:
            return None
        return getter(key)

    def estimate(self, kind: str, key=None, *, rows: int = 1,
                 max_rows: int = 1, pages_touched: int = 0,
                 tokens: Optional[int] = None,
                 adapter_rows: int = 0):
        """Return ``(bytes_est, flops_est, cost_source)`` for one step
        event.  ``pages_touched`` is the KV pages the step reads or
        writes (resident pages for a serving step; freed pages for
        evict).  ``tokens`` is the step's query tokens (one a row when
        left out).
        ``adapter_rows`` prices the per-row LoRA factor gathers of the
        multi-adapter mixed step on top of the base weight pass."""
        pages = max(0, int(pages_touched))
        if kind == "evict":
            # host-only: no HBM traffic, but the freed KV bytes are the
            # memory-attribution signal the record exists to carry
            return pages * self._page_kv_bytes, 0.0, "analytic"
        if kind == "page_copy":
            # one page read + one page written, across all layers
            return 2.0 * max(pages, 1) * self._page_kv_bytes, 0.0, \
                "analytic"
        if kind == "mixed":
            # ragged mixed launch: every query token (decode rows
            # contribute 1, prefill rows their chunk) streams its row's
            # resident page window once — price it as query tokens ×
            # per-row resident pages (the even split of the step's
            # resident set across occupied rows)
            per_row_pages = pages / max(rows, 1)
            kv_moved = (max(int(tokens if tokens is not None else rows), 1)
                        * per_row_pages * self._page_kv_bytes)
        elif kind == "decode":
            # every query token re-streams its row's page window, so
            # decode is priced per token: tokens / rows positions per
            # row.  Speculative steps pass decode + draft tokens, pricing
            # a verify row at its true query_len.
            ntok_kv = float(tokens if tokens is not None else rows)
            kv_moved = (pages * self._page_kv_bytes
                        * max(ntok_kv, 1.0) / max(rows, 1))
        else:
            kv_moved = pages * self._page_kv_bytes
        # adapter-bound rows stream their slot's stacked (A, B) factors
        # in addition to the shared base weights — count it with the KV
        # term so both cost sources carry it
        kv_moved += max(0, int(adapter_rows)) * self._lora_row_bytes
        frac = (rows / max_rows) if max_rows > 0 else 1.0
        static = self.static_cost(key)
        if static is not None:
            # the static figure read+writes the whole pool at worst
            # case; swap that for the pages actually touched and scale
            # the non-KV remainder to the occupied rows
            non_kv = max(static["bytes_accessed"] - 2.0 * self._pool_bytes,
                         0.0)
            bytes_est = non_kv * frac + kv_moved
            flops_est = static["flops"] * frac
            if bytes_est > 0.0:
                return bytes_est, flops_est, "xla+pages"
        wb, n_params = self._weights()
        ntok = float(tokens if tokens is not None else rows)
        bytes_est = wb + kv_moved
        flops_est = 2.0 * n_params * ntok
        return bytes_est, flops_est, "analytic"


def _model_summary(pairs: List[tuple]) -> Dict:
    """Fit analytic bytes to measured wall with one scale and score it.
    ``pairs`` is [(bytes_est, wall_s), ...] for clean decode steps."""
    n = len(pairs)
    out: Dict = {"n": n, "scale_s_per_byte": None,
                 "mean_abs_rel_err": None, "max_abs_rel_err": None,
                 "pearson_r": None}
    if n < 2:
        return out
    xs = [p[0] for p in pairs]
    ys = [p[1] for p in pairs]
    sx, sy = sum(xs), sum(ys)
    if sx <= 0.0 or sy <= 0.0:
        return out
    scale = sy / sx
    errs = [abs(x * scale - y) / y for x, y in pairs if y > 0.0]
    if errs:
        out["scale_s_per_byte"] = scale
        out["mean_abs_rel_err"] = sum(errs) / len(errs)
        out["max_abs_rel_err"] = max(errs)
    mx, my = sx / n, sy / n
    vxy = sum((x - mx) * (y - my) for x, y in pairs)
    vx = sum((x - mx) ** 2 for x in xs)
    vy = sum((y - my) ** 2 for y in ys)
    if vx > 0.0 and vy > 0.0:
        r = vxy / math.sqrt(vx * vy)
        out["pearson_r"] = min(1.0, max(-1.0, r))
    return out


class StepLog:
    """Bounded ring of per-step records with JSONL export and a rolling
    model-vs-measured summary.  Thread-safe: the scheduler appends from
    its step thread while HTTP handlers read ``records()``/``summary()``.
    """

    def __init__(self, capacity: int = 4096, model_window: int = 1024):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = 0
        self._total = 0
        self._by_kind: Dict[str, int] = {}
        self._bytes_total = 0.0
        self._flops_total = 0.0
        self._ici_bytes_total = 0.0
        self._ici_saved_total = 0.0
        self._compile_total = 0
        self._chunk_tokens_total = 0
        self._draft_tokens_total = 0
        self._draft_accepted_total = 0
        self._moe_routed_total = 0
        self._moe_dropped_total = 0
        self._adapter_rows_total = 0
        self._grammar_rows_total = 0
        self._masked_tokens_total = 0
        self._by_kernel: Dict[str, int] = {}
        # (bytes_est, wall_s) for clean decode chunks — the model fit
        self._model: deque = deque(maxlen=int(model_window))
        # (predicted_wall_s, wall_s) for clean planned steps — scores
        # the StepPlanner's per-step wall prediction
        self._planner: deque = deque(maxlen=int(model_window))
        # (prefill_chunk_tokens, wall_s) for clean prefill-carrying
        # steps — calibrates prefill s/token for admission predictions
        self._prefill: deque = deque(maxlen=int(model_window))

    def record(self, kind: str, **fields) -> dict:
        """Append one record; unknown fields are a programming error
        (the schema is a contract with /steps consumers and the docs
        table), missing fields take their schema defaults."""
        unknown = fields.keys() - _SCHEMA_KEYSET
        if unknown:
            raise ValueError(f"unknown StepLog fields: {sorted(unknown)}")
        rec = dict(_SCHEMA)
        rec.update(fields)
        rec["kind"] = str(kind)
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            rec["ts"] = time.time()
            self._ring.append(rec)
            self._total += 1
            self._by_kind[rec["kind"]] = \
                self._by_kind.get(rec["kind"], 0) + 1
            self._bytes_total += float(rec["bytes_est"])
            self._flops_total += float(rec["flops_est"])
            self._ici_bytes_total += float(rec["ici_bytes_est"])
            self._ici_saved_total += float(rec["ici_bytes_saved_est"])
            self._compile_total += int(rec["compile_events"])
            self._chunk_tokens_total += int(rec["prefill_chunk_tokens"])
            self._draft_tokens_total += int(rec["draft_tokens"])
            self._draft_accepted_total += int(rec["draft_accepted"])
            self._moe_routed_total += int(rec["moe_tokens_routed"])
            self._moe_dropped_total += int(rec["moe_tokens_dropped"])
            self._adapter_rows_total += int(rec["adapter_rows"])
            self._grammar_rows_total += int(rec["grammar_rows"])
            self._masked_tokens_total += int(rec["masked_tokens"])
            if rec["kernel"]:
                self._by_kernel[rec["kernel"]] = \
                    self._by_kernel.get(rec["kernel"], 0) + 1
            if rec["kind"] == "decode" and not rec["failed"] \
                    and rec["bytes_est"] > 0.0 and rec["wall_s"] > 0.0:
                self._model.append((float(rec["bytes_est"]),
                                    float(rec["wall_s"])))
            if not rec["failed"] and rec["predicted_wall_s"] > 0.0 \
                    and rec["wall_s"] > 0.0:
                self._planner.append((float(rec["predicted_wall_s"]),
                                      float(rec["wall_s"])))
            if not rec["failed"] and rec["prefill_chunk_tokens"] > 0 \
                    and rec["wall_s"] > 0.0:
                self._prefill.append((int(rec["prefill_chunk_tokens"]),
                                      float(rec["wall_s"])))
        return rec

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def records(self, limit: Optional[int] = None) -> List[dict]:
        """Most recent ``limit`` records, oldest first (the whole ring
        when limit is None)."""
        with self._lock:
            recs = list(self._ring)
        if limit is not None and limit >= 0:
            recs = recs[-limit:] if limit else []
        return [dict(r) for r in recs]

    def to_jsonl(self, limit: Optional[int] = None) -> str:
        recs = self.records(limit)
        if not recs:
            return ""
        return "\n".join(json.dumps(r, sort_keys=True)
                         for r in recs) + "\n"

    def clear(self):
        with self._lock:
            self._ring.clear()
            self._model.clear()
            self._planner.clear()
            self._prefill.clear()
            self._by_kind = {}
            self._total = 0
            self._bytes_total = 0.0
            self._flops_total = 0.0
            self._ici_bytes_total = 0.0
            self._ici_saved_total = 0.0
            self._compile_total = 0
            self._chunk_tokens_total = 0
            self._draft_tokens_total = 0
            self._draft_accepted_total = 0
            self._moe_routed_total = 0
            self._moe_dropped_total = 0
            self._adapter_rows_total = 0
            self._grammar_rows_total = 0
            self._masked_tokens_total = 0
            self._by_kernel = {}

    def calibration(self) -> Dict:
        """Rolling fits the scheduler plans and admits from: the decode
        Σwall/Σbytes scale, the mean clean decode step wall, and
        prefill seconds per chunked prompt token.  Keys are None until
        there are samples; the scheduler's readiness gates (see
        ``serving.sched.StepCalibration``) decide when to trust them."""
        with self._lock:
            model = list(self._model)
            prefill = list(self._prefill)
        out: Dict = {"scale_s_per_byte": None, "decode_step_s": None,
                     "prefill_s_per_token": None,
                     "n_decode": len(model), "n_prefill": len(prefill)}
        if model:
            sx = sum(p[0] for p in model)
            sy = sum(p[1] for p in model)
            if sx > 0.0 and sy > 0.0:
                out["scale_s_per_byte"] = sy / sx
            out["decode_step_s"] = sy / len(model)
        if prefill:
            st = sum(p[0] for p in prefill)
            sw = sum(p[1] for p in prefill)
            if st > 0 and sw > 0.0:
                out["prefill_s_per_token"] = sw / st
        return out

    def summary(self) -> Dict:
        with self._lock:
            pairs = list(self._model)
            planner = list(self._planner)
            out = {
                "records": self._total,
                "ring": len(self._ring),
                "capacity": self.capacity,
                "by_kind": dict(self._by_kind),
                "by_kernel": dict(self._by_kernel),
                "bytes_est_total": self._bytes_total,
                "flops_est_total": self._flops_total,
                "ici_bytes_est_total": self._ici_bytes_total,
                "ici_bytes_saved_total": self._ici_saved_total,
                "compile_events_total": self._compile_total,
                "prefill_chunk_tokens_total": self._chunk_tokens_total,
                "draft_tokens_total": self._draft_tokens_total,
                "draft_accepted_total": self._draft_accepted_total,
                "moe_tokens_routed_total": self._moe_routed_total,
                "moe_tokens_dropped_total": self._moe_dropped_total,
                "adapter_rows_total": self._adapter_rows_total,
                "grammar_rows_total": self._grammar_rows_total,
                "masked_tokens_total": self._masked_tokens_total,
            }
        out["decode_model"] = _model_summary(pairs)
        # predicted-vs-measured step wall for planner-annotated steps
        errs = [abs(p - w) / w for p, w in planner if w > 0.0]
        out["planner_model"] = {
            "n": len(errs),
            "mean_abs_rel_err": (sum(errs) / len(errs)) if errs else None,
            "max_abs_rel_err": max(errs) if errs else None,
        }
        return sorted_tree(out)
