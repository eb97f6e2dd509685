"""Autoregressive generation engine — the serving loop the fork builds its
fused_multi_transformer stack for.

Reference behavior covered here:
  - KV-cache decode: fused_multi_transformer_op.cu appends K/V into a
    max-seq CacheKV tensor and attends over the prefix
    (fused_multi_transformer_op.cc:103 cache shape checks).
  - beam_search_softmax (phi/kernels/fusion/gpu/beam_search_softmax.cu):
    fused softmax + beam top-k + finished-beam handling.
  - sampling decode (PaddleNLP top-k/top-p serving path).

TPU-first design: generation is ONE compiled XLA program per
(batch, prompt-bucket, cache-bucket, config) — prefill, then a
``lax.while_loop`` decode in which every step updates the static-shape KV
buffers via ``dynamic_update_slice`` and samples on-device.  No per-token
Python, no host↔device traffic until the loop exits, early-exit when every
row hit EOS.  Executables are cached by bucket key (the analog of the
reference predictor's shape-keyed TRT engine cache).
"""
from __future__ import annotations

import logging
import re
import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import no_grad
from ..core.tensor import Tensor
from . import sampling

_LOG = logging.getLogger(__name__)

# (param name, axis, dim) combos already warned about — the fallback is
# per-engine-lifetime news, not per-refresh_params noise
_FALLBACK_WARNED = set()


def serving_param_spec(arr, dist_attr, mesh, name=None, fallback=None):
    """Placement spec for one served parameter: the TP axes stamped by
    mp_layers (``dist_attr``), filtered to axes the serving mesh actually
    has and dims they divide.  Params without dist_attr (LN scales,
    biases of plain layers) replicate.

    A stamped axis the mesh HAS (size > 1) that does not divide its dim
    silently replicates the param — a TP-coverage regression if it hits
    a big weight — so each such fallback is logged once per param and
    appended to ``fallback`` (list of (axis, dim_index) tuples) for the
    ``serving_shard_replicated_params`` gauge."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.topology import axis_if_divides

    sizes = dict(mesh.shape) if mesh is not None else {}
    spec = []
    for i in range(arr.ndim):
        s = dist_attr[i] if dist_attr and i < len(dist_attr) else None
        if not s:
            spec.append(None)
            continue
        ax = axis_if_divides(mesh, s, arr.shape[i])
        spec.append(ax)
        if ax is None and sizes.get(s, 1) > 1:
            if fallback is not None:
                fallback.append((s, i))
            key = (name or "<unnamed>", s, i)
            if key not in _FALLBACK_WARNED:
                _FALLBACK_WARNED.add(key)
                _LOG.warning(
                    "serving_param_spec: replicating param %s dim %d "
                    "(shape %s) — mesh axis %r size %d does not divide %d",
                    name or "<unnamed>", i, tuple(arr.shape), s,
                    sizes.get(s, 1), arr.shape[i])
    return P(*spec)


class _MeshContext:
    """Temporarily make ``mesh`` the active hybrid mesh so the model's
    sharding_constraint ops and the paged kernel's shard_map wrap see it
    while the serving program traces/executes.  ``quantized`` pins the
    engine's quantized-allreduce mode for the same scope, so traces from
    one engine can never inherit another engine's wire format."""

    def __init__(self, mesh, quantized=None):
        self._mesh = mesh
        self._quant = quantized
        self._prev = None
        self._prev_quant = None

    def __enter__(self):
        from ..parallel import topology

        self._prev = topology.get_current_mesh()
        self._prev_quant = topology.get_quantized_allreduce()
        if self._mesh is not None:
            topology.set_current_mesh(self._mesh)
            topology.set_quantized_allreduce(self._quant)
        return self

    def __exit__(self, *exc):
        from ..parallel import topology

        topology.set_current_mesh(self._prev)
        topology.set_quantized_allreduce(self._prev_quant)
        return False


def _key_tag(key) -> str:
    """A program key's leading tag (``"serve-step"``), its name in logs."""
    return str(key[0]) if isinstance(key, tuple) and key else str(key)


def _device_ids(arrays) -> list:
    return sorted({d.id for a in arrays for d in a.sharding.device_set})


def _abstract_like(a):
    """Shape, dtype AND placement of a live array: an AOT lowering from
    these compiles the program the dispatch path runs, sharded where it
    is sharded — not a single-device stand-in for it."""
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _memory_bytes(compiled):
    """Temp / argument / output bytes of a compiled executable, or None
    where the backend's ``memory_analysis()`` has nothing to say."""
    try:
        m = compiled.memory_analysis()
    except Exception:
        return None
    if m is None:
        return None
    return {"temp": int(m.temp_size_in_bytes),
            "argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes)}


def _count_collectives(hlo_text: str) -> dict:
    """{collective: count} over a compiled module's text (async pairs
    count once, at their -start)."""
    out = {}
    for name in _COLLECTIVES:
        n = len(re.findall(rf"= [^=\n]*\b{name}(?:-start)?\(", hlo_text))
        if n:
            out[name] = n
    return out


@dataclass
class GenerationConfig:
    """Decode-time knobs (reference: PaddleNLP GenerationConfig + the
    sampling attrs of beam_search_softmax)."""

    max_new_tokens: int = 64
    min_length: int = 0
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    num_beams: int = 1
    length_penalty: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    seed: int = 0

    def cache_key(self):
        return (self.max_new_tokens, self.min_length, self.do_sample,
                self.temperature, self.top_k, self.top_p, self.num_beams,
                self.length_penalty, self.repetition_penalty,
                self.eos_token_id, self.pad_token_id)


def _round_up(n, mult):
    return ((n + mult - 1) // mult) * mult


class GenerationEngine:
    """Compiled generator over a causal-LM Layer (GPTForCausalLM-shaped:
    ``forward(input_ids, position_ids, attention_mask, caches)`` returning
    ``(logits, new_caches)`` when caches are given)."""

    def __init__(self, model, cache_bucket: int = 128,
                 prompt_bucket: int = 64, cache_dtype=None, mesh=None,
                 quantized_allreduce: Optional[str] = None):
        """``mesh``: a hybrid mesh (parallel.topology.create_hybrid_mesh)
        to serve over — TP weights placed by their mp_layers dist_attrs,
        caches sharded over heads, one SPMD decode program.  The TPU-first
        answer to the reference's multi-rank DistModel serving
        (fluid/distributed/fleet_executor/dist_model.cc:1).
        ``quantized_allreduce="int8"`` (mesh required) traces the model's
        row-parallel matmuls with the blockwise-int8 all-reduce wire
        format — approximate logits, ~4x fewer mp interconnect bytes."""
        model.eval()
        if quantized_allreduce is not None and mesh is None:
            raise ValueError(
                "quantized_allreduce requires a mesh (it only changes "
                "the mp all-reduce wire format)")
        self._model = model
        self._mesh = mesh
        # where host inputs go: replicated under the mesh, the default
        # device without one
        self._feed_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._feed_sharding = NamedSharding(mesh, PartitionSpec())
        self._quant_allreduce = quantized_allreduce
        self._placed = {}            # name -> (source array, placed array)
        self._shard_record = {}      # name -> sharded|replicated|fallback
        cfg = model.config
        # CACHE layers: a decoder layer with two attention sub-layers
        # states two (inference/cache_layout.py)
        from .cache_layout import layout_of

        self._cache_layout = layout_of(model)
        self._num_layers = len(self._cache_layout)
        self._num_heads = cfg.num_attention_heads
        self._head_dim = cfg.hidden_size // cfg.num_attention_heads
        self._max_positions = cfg.max_position_embeddings
        self._cache_bucket = cache_bucket
        self._prompt_bucket = prompt_bucket
        self._params = self._snapshot_params()
        # first FLOATING param decides the cache dtype: weight-only
        # serving checkpoints put int8 payloads in the snapshot, which
        # must never become the KV dtype
        self._cache_dtype = cache_dtype or next(
            (v.dtype for v in self._params.values()
             if jnp.issubdtype(v.dtype, jnp.floating)), jnp.float32)
        self._compiled = {}

    def _weight_only_buffers(self):
        """Serving-checkpoint buffers that must ride the param snapshot:
        weight-only layers register their (qweight, scale, bias) payloads
        as buffers, not Parameters — left out of the snapshot they would
        be traced as jit constants (re-uploaded per executable, invisible
        to refresh_params, unplaceable under a mesh).  LoRA serving
        wrappers register their stacked slot pools the same way: the
        AdapterCache swaps slot contents between steps by rebinding the
        buffer payload, which only reaches the executable because the
        pools ride here as jit ARGUMENTS, not trace constants."""
        from ..quantization.moe import Int8MoELayer, WeightOnlyMoELayer
        from ..quantization.weight_only import WeightOnlyLinear
        from ..serving.adapters.layer import LoRAServingLinear

        out = {}
        for lname, layer in self._model.named_sublayers():
            if isinstance(layer, (WeightOnlyLinear, WeightOnlyMoELayer,
                                  Int8MoELayer, LoRAServingLinear)):
                for bn, buf in layer.named_buffers(
                        prefix=lname, include_sublayers=False):
                    out[bn] = buf
        return out

    def _snapshot_params(self):
        """Re-snapshot parameters (honoring set_state_dict/dtype casts
        after construction) plus weight-only serving buffers; under a
        mesh, place each by its dist_attr spec, caching placements so
        repeat calls don't re-transfer."""
        bufs = self._weight_only_buffers()
        self._buffer_names = frozenset(bufs)
        named = list(self._model.named_parameters()) + list(bufs.items())
        if self._mesh is None:
            return {n: p._data for n, p in named}
        from jax.sharding import NamedSharding

        out = {}
        for n, p in named:
            cached = self._placed.get(n)
            if cached is not None and cached[0] is p._data:
                out[n] = cached[1]
                continue
            fell_back = []
            spec = serving_param_spec(p._data,
                                      getattr(p, "dist_attr", None),
                                      self._mesh, name=n,
                                      fallback=fell_back)
            self._shard_record[n] = (
                "fallback" if fell_back
                else "sharded" if any(s is not None for s in spec)
                else "replicated")
            placed = jax.device_put(p._data,
                                    NamedSharding(self._mesh, spec))
            self._placed[n] = (p._data, placed)
            out[n] = placed
        return out

    def adopt_placement(self):
        """Rebind the model's parameters to the arrays this engine placed
        over its mesh, so the single-device copies they were placed FROM
        can be freed.  For a caller that owns the model and serves it
        through this engine only (tools/serve.py): without it the default
        device keeps the whole checkpoint beside its shard.  Engines
        without a mesh have nothing to adopt."""
        if self._mesh is None:
            return
        named = dict(self._model.named_parameters())
        named.update(self._weight_only_buffers())
        for n, (src, placed) in self._placed.items():
            t = named.get(n)
            if t is not None and t._data is src:
                t._data = placed
                self._placed[n] = (placed, placed)

    def _mesh_ctx(self):
        return _MeshContext(self._mesh, self._quant_allreduce)

    def shard_report(self):
        """Placement summary for the serving snapshot: mesh shape, how
        many params sharded vs silently replicated (axis didn't divide),
        and the active quantized-allreduce mode.  None without a mesh."""
        if self._mesh is None:
            return None
        rec = self._shard_record
        fallbacks = sorted(n for n, v in rec.items() if v == "fallback")
        return {
            "mesh_axes": {a: int(s) for a, s in dict(self._mesh.shape).items()
                          if int(s) > 1},
            "devices": int(self._mesh.devices.size),
            "params_total": len(rec),
            "sharded_params": sum(1 for v in rec.values() if v == "sharded"),
            "replicated_params": len(fallbacks),
            "replicated_names": fallbacks[:8],
            "quantized_allreduce": self._quant_allreduce or "",
            # where the bytes actually are, as the runtime reports it:
            # a placement rule that silently left everything on device 0
            # shows here as a one-element list
            "param_devices": _device_ids(self._params.values()),
            "kv_pool_devices": _device_ids(
                jax.tree_util.tree_leaves(
                    getattr(self, "_k_pages", None) or [])),
            "step_collectives": {
                _key_tag(k): v for k, v in getattr(
                    self, "_program_collectives", {}).items()},
        }

    def _replicated(self, arr):
        """Pin a host input to an explicit replicated placement under the
        mesh (so GSPMD never guesses a layout for feeds)."""
        return jax.device_put(arr, self._feed_sharding)

    # ------------------------------------------------------------ plumbing
    def _empty_caches(self, batch, cache_len):
        from ..ops.distributed import _constrain

        shape = (batch, cache_len, self._num_heads, self._head_dim)
        zero_idx = jnp.zeros((), jnp.int32)
        # pin head sharding under a serving mesh (dormant without one)
        spec = ("data", None, "mp", None)
        return [(_constrain(jnp.zeros(shape, self._cache_dtype), spec),
                 _constrain(jnp.zeros(shape, self._cache_dtype), spec),
                 zero_idx)
                for _ in range(self._num_layers)]

    def _model_step(self, params, ids, position_ids, pad_mask_add, caches,
                    head_rows=None):
        """One forward over the Layer with traced arrays; returns raw
        logits + cache arrays.  The Layer runs under no_grad so dispatch
        skips tape recording inside the trace.

        ``head_rows`` (the served mixed step alone): the flat token slots
        whose logits the step reads; the model then runs its final norm
        and head over those rows only and returns ``[*head_rows.shape,
        vocab]`` (models/transformer_block.take_head_rows).

        Quantized paged pools ride as plain ``(payload, scales)`` tuples
        inside the cache — wrapped/unwrapped element-wise so the pytree
        shape is preserved.  Weight-only quantized payloads (registered
        as buffers, not Parameters) ride inside ``params`` and are split
        back out here so ``functional_call`` swaps them as buffers —
        without this they would be baked into the trace as constants."""
        def wrap(a):
            return tuple(Tensor(x) for x in a) if isinstance(a, tuple) \
                else Tensor(a)

        def unwrap(x):
            return tuple(t._data for t in x) if isinstance(x, tuple) \
                else x._data

        bnames = getattr(self, "_buffer_names", None)
        bufs = None
        if bnames:
            bufs = {n: params[n] for n in bnames if n in params}
            params = {n: a for n, a in params.items() if n not in bnames}
        tcaches = [tuple(wrap(a) for a in c) for c in caches]
        mask_t = Tensor(pad_mask_add) if pad_mask_add is not None else None
        head = {} if head_rows is None else {"head_rows": Tensor(head_rows)}
        with no_grad():
            logits, new = self._model.functional_call(
                params, Tensor(ids),
                position_ids=Tensor(position_ids),
                attention_mask=mask_t, caches=tcaches, buffers=bufs, **head)
        return logits._data, [tuple(unwrap(x) for x in c) for c in new]

    def _pad_mask_add(self, prompt_mask, cache_len):
        """[b, plen] 0/1 prompt mask → additive [b, 1, 1, cache_len] over
        the KV buffer (pad slots -inf; slots past the prompt are ruled by
        kv_cache_mask, so 0 here)."""
        b, plen = prompt_mask.shape
        pad = jnp.zeros((b, cache_len - plen), prompt_mask.dtype)
        full = jnp.concatenate([prompt_mask, 1 + pad], axis=1)
        add = jnp.where(full == 0, sampling.NEG_INF, 0.0).astype(jnp.float32)
        return add[:, None, None, :]

    # ----------------------------------------------------------- sampling
    def _build_sample(self, batch, plen, cache_len, g: GenerationConfig):
        """Build the fused prefill+decode program for greedy/sampling."""
        max_new = g.max_new_tokens

        def run(params, ids, prompt_mask, rng):
            lengths = jnp.sum(prompt_mask, axis=1).astype(jnp.int32)  # [b]
            pad_add = self._pad_mask_add(prompt_mask, cache_len)
            # prefill: positions = cumsum(mask)-1 (left/right padding safe)
            pos = jnp.clip(jnp.cumsum(prompt_mask, axis=1) - 1, 0, None)
            caches = self._empty_caches(batch, cache_len)
            logits, caches = self._model_step(
                params, ids, pos.astype(jnp.int32), pad_add, caches)
            # prompts are left-padded, so the last real token is the last
            # slot in every row
            last = logits[:, -1]

            out_buf = jnp.full((batch, max_new), g.pad_token_id, jnp.int32)
            finished = jnp.zeros((batch,), jnp.bool_)
            hist0 = jnp.concatenate(
                [jnp.where(prompt_mask > 0, ids, -1),
                 jnp.full((batch, max_new), -1, jnp.int32)], axis=1)

            pick = self._logits_picker(g)

            k0, rng = jax.random.split(rng)
            tok, tok_logp = pick(last, hist0, 0, k0)
            if g.eos_token_id is not None:
                finished = tok == g.eos_token_id
            out_buf = out_buf.at[:, 0].set(tok)
            hist0 = hist0.at[:, plen].set(tok)
            cum = tok_logp

            def cond(state):
                step = state[0]
                fin = state[3]
                return jnp.logical_and(step < max_new,
                                       jnp.logical_not(jnp.all(fin)))

            def body(state):
                step, tok, out, fin, hist, cum, caches, rng = state
                p = (lengths + step - 1)[:, None]
                logits, caches = self._model_step(
                    params, tok[:, None], p, pad_add, caches)
                key, rng = jax.random.split(rng)
                nxt, tok_logp = pick(logits[:, -1], hist, step, key)
                if g.eos_token_id is not None:
                    nxt = jnp.where(fin, g.pad_token_id, nxt)
                    cum = jnp.where(fin, cum, cum + tok_logp)
                    new_fin = jnp.logical_or(fin, nxt == g.eos_token_id)
                else:
                    cum = cum + tok_logp
                    new_fin = fin
                out = jax.lax.dynamic_update_slice(
                    out, nxt[:, None], (jnp.zeros((), jnp.int32), step))
                hist = jax.lax.dynamic_update_slice(
                    hist, nxt[:, None], (jnp.zeros((), jnp.int32),
                                         plen + step))
                return (step + 1, nxt, out, new_fin, hist, cum, caches, rng)

            state = (jnp.asarray(1, jnp.int32), tok, out_buf, finished,
                     hist0, cum, caches, rng)
            state = jax.lax.while_loop(cond, body, state)
            return state[2], state[5]

        return jax.jit(run)

    # -------------------------------------------------------- beam search
    def _build_beam(self, batch, plen, cache_len, g: GenerationConfig):
        """Fused beam search (reference beam_search_softmax semantics:
        per-step fused log-softmax + top-k over W·V with finished beams
        pinned to pad at unchanged score; length penalty applied at
        finalization)."""
        W = g.num_beams
        max_new = g.max_new_tokens
        pad = g.pad_token_id

        def run(params, ids, prompt_mask, rng):
            del rng
            b = batch
            lengths = jnp.sum(prompt_mask, axis=1).astype(jnp.int32)
            # expand to beam batch [b*W, ...]
            ids_w = jnp.repeat(ids, W, axis=0)
            mask_w = jnp.repeat(prompt_mask, W, axis=0)
            lengths_w = jnp.repeat(lengths, W, axis=0)
            pad_add = self._pad_mask_add(mask_w, cache_len)
            pos = jnp.clip(jnp.cumsum(mask_w, axis=1) - 1, 0, None)
            caches = self._empty_caches(b * W, cache_len)
            logits, caches = self._model_step(
                params, ids_w, pos.astype(jnp.int32), pad_add, caches)
            # left-padded prompts: last slot is the last real token
            last = logits[:, -1]
            logp = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
            if g.eos_token_id is not None and g.min_length > 0:
                logp = logp.at[:, g.eos_token_id].set(sampling.NEG_INF)
            vocab = logp.shape[-1]
            # first step: only beam 0 is live (identical prefixes)
            init_bias = jnp.where(jnp.arange(W) == 0, 0.0, sampling.NEG_INF)
            scores = logp.reshape(b, W, vocab) + init_bias[None, :, None]
            flat = scores.reshape(b, W * vocab)
            top_s, top_i = jax.lax.top_k(flat, W)        # [b, W]
            beam_src = top_i // vocab
            tok = (top_i % vocab).astype(jnp.int32)
            cum = top_s
            finished = (tok == g.eos_token_id) if g.eos_token_id is not None \
                else jnp.zeros((b, W), jnp.bool_)
            gen_len = jnp.ones((b, W), jnp.int32)
            out = jnp.full((b, W, max_new), pad, jnp.int32)
            out = out.at[:, :, 0].set(tok)

            def reorder(arr, src):
                """Gather beam-major [b*W, ...] rows by per-batch source
                beam indices [b, W]."""
                a = arr.reshape((b, W) + arr.shape[1:])
                a = jnp.take_along_axis(
                    a, src.reshape((b, W) + (1,) * (a.ndim - 2)), axis=1)
                return a.reshape((b * W,) + arr.shape[1:])

            def reorder_caches(caches, src):
                return [(reorder(k, src), reorder(v, src), i)
                        for k, v, i in caches]

            # tok/out are already target-ordered; only the caches (still in
            # source-beam order) need the gather
            caches = reorder_caches(caches, beam_src)

            def cond(state):
                step, fin = state[0], state[4]
                return jnp.logical_and(step < max_new,
                                       jnp.logical_not(jnp.all(fin)))

            def body(state):
                step, tok, out, cum, fin, gen_len, caches = state
                p = (lengths_w + step - 1)[:, None]
                logits, caches = self._model_step(
                    params, tok.reshape(b * W, 1), p, pad_add, caches)
                logp = jax.nn.log_softmax(
                    logits[:, -1].astype(jnp.float32), axis=-1)
                logp = logp.reshape(b, W, vocab)
                if g.eos_token_id is not None and g.min_length > 0:
                    logp = jnp.where(step < g.min_length,
                                     logp.at[:, :, g.eos_token_id].set(
                                         sampling.NEG_INF), logp)
                # finished beams: only pad continues, at unchanged score
                pad_row = jnp.full((vocab,), sampling.NEG_INF,
                                   jnp.float32).at[pad].set(0.0)
                logp = jnp.where(fin[:, :, None], pad_row[None, None, :],
                                 logp)
                flat = (cum[:, :, None] + logp).reshape(b, W * vocab)
                top_s, top_i = jax.lax.top_k(flat, W)
                src = top_i // vocab
                nxt = (top_i % vocab).astype(jnp.int32)
                caches = reorder_caches(caches, src)
                out = jnp.take_along_axis(out, src[:, :, None], axis=1)
                fin = jnp.take_along_axis(fin, src, axis=1)
                gen_len = jnp.take_along_axis(gen_len, src, axis=1)
                gen_len = gen_len + jnp.logical_not(fin)
                if g.eos_token_id is not None:
                    fin = jnp.logical_or(fin, nxt == g.eos_token_id)
                out = jax.lax.dynamic_update_slice(
                    out, nxt[:, :, None],
                    (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                     step))
                return (step + 1, nxt, out, top_s, fin, gen_len, caches)

            state = (jnp.asarray(1, jnp.int32), tok, out, cum, finished,
                     gen_len, caches)
            state = jax.lax.while_loop(cond, body, state)
            _, _, out, cum, _, gen_len, _ = state
            # finalize: length-penalized best beam per batch row
            norm = cum / (gen_len.astype(jnp.float32) ** g.length_penalty)
            best = jnp.argmax(norm, axis=1)
            seq = jnp.take_along_axis(out, best[:, None, None], axis=1)[:, 0]
            score = jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0]
            return seq, score

        return jax.jit(run)

    # ---------------------------------------------------- shared sampling
    def _logits_picker(self, g: GenerationConfig):
        """process-logits + sample closure shared by the dense and paged
        decode loops."""

        def pick(logits_row, hist, step, key):
            proc = sampling.process_logits(
                logits_row, temperature=g.temperature, top_k=g.top_k,
                top_p=g.top_p, token_history=hist,
                repetition_penalty=g.repetition_penalty,
                eos_token_id=g.eos_token_id, cur_len=step,
                min_length=g.min_length)
            tok = sampling.sample_token(proc, key, g.do_sample)
            logp = jax.nn.log_softmax(proc, axis=-1)
            tok_logp = jnp.take_along_axis(
                logp, tok[:, None], axis=-1)[:, 0]
            return tok, tok_logp

        return pick

    def _prepare(self, input_ids, attention_mask, g: GenerationConfig,
                 budget: Optional[int] = None):
        """Shared prompt preprocessing: coerce to [b, plen] int32,
        canonicalize to LEFT padding (compiled programs read next-token
        logits from the final slot), bucket the prompt length, and size
        the KV cache.  ``budget`` = tokens the cache must hold past the
        prompt (defaults to max_new_tokens; SpeculativeEngine adds its
        chunk overshoot).  Returns (ids, mask, plen, cache_len)."""
        budget = g.max_new_tokens if budget is None else budget
        ids = np.asarray(input_ids._data if isinstance(input_ids, Tensor)
                         else input_ids).astype(np.int32)
        if ids.ndim == 1:
            ids = ids[None, :]
        b, plen_raw = ids.shape
        mask = (np.ones_like(ids) if attention_mask is None
                else np.asarray(attention_mask).astype(np.int32))
        for i in range(b):
            real = np.flatnonzero(mask[i])
            if len(real) and real[-1] != plen_raw - 1:
                n = len(real)
                row = ids[i, real]
                ids[i] = g.pad_token_id
                mask[i] = 0
                ids[i, plen_raw - n:] = row
                mask[i, plen_raw - n:] = 1
        # bucket the prompt so executables are reused across nearby
        # lengths, clamped so prompt + budget still fits the position table
        assert plen_raw + budget <= self._max_positions, (
            f"prompt {plen_raw} + generation budget {budget} exceeds "
            f"max_position_embeddings {self._max_positions}")
        plen = _round_up(max(plen_raw, 1), self._prompt_bucket)
        plen = max(plen_raw, min(plen, self._max_positions - budget))
        if plen > plen_raw:  # left-pad to the bucket
            padw = plen - plen_raw
            ids = np.pad(ids, ((0, 0), (padw, 0)),
                         constant_values=g.pad_token_id)
            mask = np.pad(mask, ((0, 0), (padw, 0)), constant_values=0)
        cache_len = min(_round_up(plen + budget, self._cache_bucket),
                        self._max_positions)
        cache_len = max(cache_len, plen + budget)
        return ids, mask, plen, cache_len

    # ------------------------------------------------------------- public
    def generate(self, input_ids, generation_config: GenerationConfig = None,
                 attention_mask=None, return_scores: bool = False):
        """Generate continuations.  ``input_ids`` [b, plen] (np/jax/Tensor),
        optional 0/1 ``attention_mask`` marking real prompt tokens.
        Returns np.ndarray [b, <=max_new_tokens] of generated ids (padded
        with pad_token_id after EOS)."""
        g = generation_config or GenerationConfig()
        if g.num_beams > 1 and (g.do_sample or g.temperature != 1.0
                                or g.top_k or g.top_p < 1.0
                                or g.repetition_penalty != 1.0):
            import warnings

            warnings.warn(
                "beam search ignores do_sample/temperature/top_k/top_p/"
                "repetition_penalty (reference beam_search_softmax is "
                "deterministic)", UserWarning)
        # re-snapshot parameters so set_state_dict / dtype casts after
        # engine construction are honored
        self._params = self._snapshot_params()
        ids, mask, plen, cache_len = self._prepare(input_ids,
                                                   attention_mask, g)
        b = ids.shape[0]

        beam = g.num_beams > 1
        key = ("beam" if beam else "sample", b, plen, cache_len,
               g.cache_key())
        fn = self._compiled.get(key)
        if fn is None:
            builder = self._build_beam if beam else self._build_sample
            fn = builder(b, plen, cache_len, g)
            self._compiled[key] = fn
        rng = jax.random.PRNGKey(g.seed)
        with self._mesh_ctx():
            out = fn(self._params, self._replicated(ids),
                     self._replicated(mask), rng)
        seq, score = out
        seq = np.asarray(seq)
        return (seq, np.asarray(score)) if return_scores else seq


class PagedGenerationEngine(GenerationEngine):
    """Generation over a PAGED KV cache — the serving design the dense
    engine's docstring argues against static CacheKV buffers for.

    Reference semantics: fused_multi_transformer's CacheKV append + MMHA
    decode (fused_multi_transformer_op.cc:103-119), re-designed as a
    shared physical page pool [P, h, page, d] whose per-sequence page
    tables come from the native block allocator (native/kv_allocator.cc)
    and whose decode step is the Pallas paged-attention kernel
    (ops/pallas/paged_attention.py) — PAPERS.md ragged-paged-attention.

    Differences from the dense engine:
      * prompts are RIGHT-padded: real tokens sit at positions 0..len-1 so
        causal prefill never attends to pads and the decode kernel masks
        by true per-row length — no additive pad mask at all;
      * KV memory is allocated in pages by the native pool, so memory
        scales with actual tokens (rounded to a page), not with the
        bucketed max length, and sequences can share/CoW pages;
      * beam search forks pages (KVBlockPool.fork): all W beams of a row
        SHARE the row's prompt pages (prefill runs once per row, not once
        per beam like the dense engine), each beam owns
        ceil(max_new/page)+1 private decode pages, the partially-filled
        boundary page is copied-on-write into each beam's first private
        page at fork time, and the per-step beam reorder permutes only the
        private decode pages — the prompt (usually the bulk of the cache)
        is never gathered, unlike the dense engine's full-cache reorder.
    """

    def __init__(self, model, page_size: int = 16,
                 num_pages: Optional[int] = None, prompt_bucket: int = 64,
                 cache_dtype=None, mesh=None,
                 quantized_allreduce: Optional[str] = None,
                 kv_dtype: Optional[str] = None):
        """``kv_dtype="int8"`` stores KV pages as int8 payloads with
        per-page-per-head float32 scales (see the scale protocol in
        ops/pallas/paged_attention.py) — half the page bytes, so ~2x
        resident sequences per pool byte.  None keeps full-precision
        pages."""
        if kv_dtype not in (None, "int8"):
            if kv_dtype == "int4":
                raise NotImplementedError(
                    "kv_dtype='int4' is recognized by "
                    "validate_serving_config but the pool stores int8 "
                    "payloads only")
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        self._kv_dtype = kv_dtype
        super().__init__(model, cache_bucket=page_size,
                         prompt_bucket=prompt_bucket,
                         cache_dtype=cache_dtype, mesh=mesh,
                         quantized_allreduce=quantized_allreduce)
        self._cache_bytes_measured = {}
        self.page_size = page_size
        self._requested_pages = num_pages
        self._pool = None
        # per-program-key set of seen arg signatures (recompile detector)
        self._compiled_sigs = {}
        # per-program-key abstract call shapes + cached cost_analysis()
        # (observability.steplog's analytic bytes/FLOPs source)
        self._program_shapes = {}
        self._program_costs = {}
        # per-program-key memory_analysis() of the same compiled object
        # (temp / argument / output bytes; see program_memory)
        self._program_memory = {}
        # per-program-key {collective op: count} read off the compiled
        # text alongside the cost (shard_report's step_collectives)
        self._program_collectives = {}
        # persistent per-layer device pools [P, h, page, d]; donated into
        # every compiled call and rebound from its outputs, so the arrays
        # genuinely stay put in HBM across requests
        self._k_pages = None
        self._v_pages = None

    # ----------------------------------------------------------- plumbing
    def _ensure_pool(self, need_pages: int):
        from .. import native

        want = max(need_pages, self._requested_pages or 0)
        if self._pool is None or self._pool.num_blocks < want:
            self._pool = native.KVBlockPool(want, self.page_size)
            self._k_pages = self._v_pages = None     # resize device pools
        return self._pool

    def _ensure_pages(self):
        """The per-layer device pools, allocated from the model's cache
        layout (inference/cache_layout.py): a ``kv`` layer gets its two
        ``[P, h, page, d]`` pools, a ``latent`` layer ONE ``[P, page,
        lanes]`` pool and ``None`` in the second list."""
        layout = self._cache_layout
        shapes = [c.pool_shapes(self._pool.num_blocks, self.page_size)
                  for c in layout]

        def shape_of(p):            # quantized pools are (payload, scales)
            return p[0].shape if isinstance(p, tuple) else p.shape

        if self._k_pages is None or any(
                shape_of(p) != s[0] for p, s in zip(self._k_pages, shapes)):
            from ..ops.pallas.paged_attention import KV_SCALE_EPS

            # under a mesh the pool is head-sharded: each mp shard owns
            # its heads' pages, replicated over every other serving
            # axis.  Allocated IN that placement — a zeros on the default
            # device that is then moved would stage each layer's whole
            # pool on device 0
            def placement(cache):
                if self._mesh is None:
                    return None, None
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                from ..parallel.topology import axis_if_divides

                if cache.head_axis() is None:
                    return NamedSharding(self._mesh, P()), None
                hax = axis_if_divides(self._mesh, "mp", cache.heads)
                return (NamedSharding(self._mesh, P(None, hax, None, None)),
                        NamedSharding(self._mesh, P(None, hax)))

            def alloc(cache, pshape):
                if pshape is None:
                    return None
                payload_at, scales_at = placement(cache)
                quant = self._kv_dtype == "int8"
                z = jnp.zeros(pshape, jnp.int8 if quant
                              else self._cache_dtype, device=payload_at)
                if not quant:
                    return z
                # scales start at the eps floor (never zero): dequant of
                # a zeroed pool is zero and the scale > 0 invariant the
                # masked-max writer relies on holds from the first step
                return z, jnp.full(pshape[:2], KV_SCALE_EPS, jnp.float32,
                                   device=scales_at)

            self._k_pages = [alloc(c, s[0]) for c, s in zip(layout, shapes)]
            self._v_pages = [alloc(c, s[1]) for c, s in zip(layout, shapes)]
        return self._k_pages, self._v_pages

    def cache_bytes_per_token(self, kind=None, padding: bool = True,
                              index_only: bool = False) -> int:
        """Bytes of the allocated pools per token of their capacity, over
        the layers of cache ``kind`` (all layers when None): the arrays'
        own sizes, a quantized pool's scales and a latent row's lane
        padding included.  ``padding=False`` takes the lanes past a
        latent layer's stated ``width`` off again: what is cached.
        ``index_only`` counts the second pools alone (call it with
        ``kind="latent"``: the indexer's keys; 0 for a model without an
        indexer).  Measured once per engine (a token's bytes do not depend on how
        many pages the pool has); allocates the pools if nothing has."""
        key = (kind, padding, index_only)
        if key not in self._cache_bytes_measured:
            k_pages, v_pages = self._ensure_pages()
            total = 0
            for cache, first, second in zip(self._cache_layout, k_pages,
                                            v_pages):
                if kind not in (None, cache.kind):
                    continue
                nbytes = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(
                    second if index_only else (first, second)))
                if not padding:
                    nbytes = nbytes * cache.values_per_token() \
                        // cache.stored_per_token()
                total += nbytes
            self._cache_bytes_measured[key] = total // (
                self._pool.num_blocks * self.page_size)
        return self._cache_bytes_measured[key]

    def _refuse_latent(self, what: str):
        """The offline per-call programs walk ``[P, h, page, d]`` pools;
        a latent layer is served through the mixed step only."""
        from .cache_layout import has_latent

        if has_latent(self._cache_layout):
            raise NotImplementedError(
                f"{what} runs the per-call paged programs, which know "
                "key/value pools only; a model with a latent cache layer "
                "is served through serving.EngineCore's mixed step")

    # ------------------------------------------------------ serving hooks
    # The serving.EngineCore scheduler owns this engine's pool/pages
    # across requests (continuous batching never frees the whole batch at
    # once the way generate()/stream() do).  These three hooks are the
    # entire surface it needs: parameter refresh, pool sizing, and a
    # compile-cache + donated-pool wrapper for its own programs.

    def refresh_params(self):
        """Re-snapshot (and re-place, under a mesh) model parameters —
        what generate() does implicitly at the top of every call."""
        self._params = self._snapshot_params()
        return self._params

    def serving_pool(self, num_pages: int):
        """Size the native block pool for a serving session (slots ×
        pages-per-slot + scratch) and return it.  Resizing invalidates
        the device pools, so EngineCore calls this once up front."""
        return self._ensure_pool(num_pages)

    def run_paged_program(self, key, builder, *args):
        """Run a serving-owned compiled program over the persistent page
        pools.  ``builder()`` must return a jitted fn with signature
        ``fn(params, *args, k_pages, v_pages)`` whose LAST two outputs
        are the updated (donated) pools; the leading outputs are
        returned to the caller.  Pool choreography matches
        generate()/stream(): references are dropped before the call and
        rebound only from a successful call's outputs.  If the call
        raises, the donated pools are gone — ``kv_state_lost()`` then
        reports True until _ensure_pages rebuilds them (zeroed), and the
        scheduler must abort every in-flight row."""
        fn = self._compiled.get(key)
        if fn is None:
            fn = builder()
            self._compiled[key] = fn
        # observability: a first call with an unseen (shapes, dtypes)
        # argument signature is an XLA compilation.  The signature spans
        # only *args — params and pools are fixed per key (the pool is
        # resized once up front; resizing drops the compiled cache's
        # validity anyway), so the per-step cost is a few tuple builds.
        from ..observability.compilelog import (get_compile_log,
                                                signature_of)

        sigs = self._compiled_sigs.setdefault(key, set())
        sig = signature_of(args)
        is_compile = sig not in sigs
        k_pages, v_pages = self._ensure_pages()
        # one host-to-device put for the whole argument tree, the host
        # arrays as they are (the mixed step hands one packed buffer)
        args = jax.device_put(tuple(args), self._feed_sharding)
        if key not in self._program_shapes:
            # abstract (shape, dtype) trees for program_cost(): captured
            # before donation consumes the pools, costing only a
            # tree_map on the first call per key
            abstract = jax.tree_util.tree_map(
                _abstract_like, (args, k_pages, v_pages))
            self._program_shapes[key] = abstract
        self._k_pages = self._v_pages = None
        t0 = time.perf_counter() if is_compile else 0.0
        with self._mesh_ctx():
            out = fn(self._params, *args, k_pages, v_pages)
        if is_compile:
            sigs.add(sig)
            tag = _key_tag(key)
            site = ("serving-decode" if tag == "serve-step"
                    else "serving-page-copy" if tag == "serve-page-copy"
                    else f"serving-{tag}")
            get_compile_log().record(site, key, sig,
                                     time.perf_counter() - t0)
        *rest, new_k, new_v = out
        self._k_pages, self._v_pages = new_k, new_v
        return rest

    def program_cost(self, key):
        """Static XLA cost of one serving program: ``{"flops", "bytes_
        accessed"}`` floats from ``compiled.cost_analysis()`` at the
        shapes the program was first dispatched with, or None when the
        program hasn't run yet / the backend offers no analysis.

        The executable is AOT-lowered from ``ShapeDtypeStruct`` trees —
        no device buffers move — and cached per key, so the one-time
        compile amortizes across every StepLog record.  Crucially this
        path never goes through ``run_paged_program``'s signature
        tracking: the CompileLog cannot see it, so querying costs can
        never trip the zero-post-warmup-decode-compile invariant."""
        if key in self._program_costs:
            return self._program_costs[key]
        fn = self._compiled.get(key)
        shapes = self._program_shapes.get(key)
        if fn is None or shapes is None:
            return None
        args_s, k_s, v_s = shapes
        params_s = jax.tree_util.tree_map(_abstract_like, self._params)
        cost = None
        try:
            with self._mesh_ctx():
                lowered = fn.lower(params_s, *args_s, k_s, v_s)
                compiled = lowered.compile()
                analysis = compiled.cost_analysis()
            self._program_collectives[key] = _count_collectives(
                compiled.as_text())
            self._program_memory[key] = _memory_bytes(compiled)
            if isinstance(analysis, (list, tuple)):
                analysis = analysis[0] if analysis else {}
            if analysis:
                cost = {
                    "flops": float(analysis.get("flops", 0.0) or 0.0),
                    "bytes_accessed": float(
                        analysis.get("bytes accessed", 0.0) or 0.0),
                }
        except Exception:
            cost = None
        self._program_costs[key] = cost
        return cost

    def program_memory(self, key):
        """``{"temp", "argument", "output"}`` bytes of one serving
        program, from the ``memory_analysis()`` of the compiled object
        ``program_cost(key)`` built: a dict look-up, no compile of its
        own.  None until ``program_cost`` has run for the key, or where
        the backend offers no analysis."""
        return self._program_memory.get(key)

    def kv_state_lost(self) -> bool:
        """True when the device pools were consumed by a failed donated
        call (their contents — every in-flight row's KV — are gone)."""
        return self._k_pages is None

    def drop_kv_state(self):
        """Deliberately forget the device page pools — the fault-plane
        hook modeling a failure *inside* a donated call (serving/
        resilience/).  ``kv_state_lost()`` reports True until the next
        dispatch rebuilds the pools zeroed via ``_ensure_pages``."""
        self._k_pages = self._v_pages = None

    def rebuild_kv_state(self):
        """Eagerly rebuild the (zeroed) device page pools once serving
        recovery has replayed every in-flight row, so
        ``kv_state_lost()`` stops reporting a loss that was already
        serviced.  Schedulers whose admission only stages host-side
        state (the ragged mixed step) may not dispatch between the
        restart and the next failure — a stale lost flag there would
        re-enter recovery and double-count the restart."""
        self._ensure_pages()

    def _build_paged(self, batch, plen, g: GenerationConfig):
        max_new = g.max_new_tokens
        L = self._num_layers

        def run(params, ids, lengths, tables, k_pages, v_pages, rng):
            zero_pos = jnp.zeros((batch,), jnp.int32)
            caches = [(k_pages[i], v_pages[i], tables, zero_pos)
                      for i in range(L)]
            pos2d = jnp.broadcast_to(
                jnp.arange(plen, dtype=jnp.int32)[None], (batch, plen))
            logits, caches = self._model_step(params, ids, pos2d, None,
                                              caches)
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]

            out_buf = jnp.full((batch, max_new), g.pad_token_id, jnp.int32)
            finished = jnp.zeros((batch,), jnp.bool_)
            col = jnp.arange(plen, dtype=jnp.int32)[None]
            hist0 = jnp.concatenate(
                [jnp.where(col < lengths[:, None], ids, -1),
                 jnp.full((batch, max_new), -1, jnp.int32)], axis=1)
            pick = self._logits_picker(g)

            k0, rng = jax.random.split(rng)
            tok, tok_logp = pick(last, hist0, 0, k0)
            if g.eos_token_id is not None:
                finished = tok == g.eos_token_id
            out_buf = out_buf.at[:, 0].set(tok)
            hist0 = hist0.at[:, plen].set(tok)
            cum = tok_logp

            def set_positions(caches, pos):
                return [(kp, vp, tb, pos) for kp, vp, tb, _ in caches]

            def cond(state):
                step, fin = state[0], state[3]
                return jnp.logical_and(step < max_new,
                                       jnp.logical_not(jnp.all(fin)))

            def body(state):
                step, tok, out, fin, hist, cum, caches, rng = state
                # this step's token was sampled at per-row position
                # lengths + step - 1; it lands in that page slot
                pos = lengths + step - 1
                caches = set_positions(caches, pos)
                logits, caches = self._model_step(
                    params, tok[:, None], pos[:, None], None, caches)
                key, rng = jax.random.split(rng)
                nxt, tok_logp = pick(logits[:, -1], hist, step, key)
                if g.eos_token_id is not None:
                    nxt = jnp.where(fin, g.pad_token_id, nxt)
                    cum = jnp.where(fin, cum, cum + tok_logp)
                    new_fin = jnp.logical_or(fin, nxt == g.eos_token_id)
                else:
                    cum = cum + tok_logp
                    new_fin = fin
                out = jax.lax.dynamic_update_slice(
                    out, nxt[:, None], (jnp.zeros((), jnp.int32), step))
                hist = jax.lax.dynamic_update_slice(
                    hist, nxt[:, None],
                    (jnp.zeros((), jnp.int32), plen + step))
                return (step + 1, nxt, out, new_fin, hist, cum, caches, rng)

            state = (jnp.asarray(1, jnp.int32), tok, out_buf, finished,
                     hist0, cum, caches, rng)
            state = jax.lax.while_loop(cond, body, state)
            final_caches = state[6]
            return (state[2], state[5],
                    [c[0] for c in final_caches],
                    [c[1] for c in final_caches])

        # the page pools are donated: XLA updates them in place and the
        # engine rebinds the returned arrays
        return jax.jit(run, donate_argnums=(4, 5))

    # --------------------------------------------------- paged beam search
    def _build_paged_beam(self, batch, plen, n_priv, g: GenerationConfig):
        """Beam search over forked pages (reference beam_search_softmax +
        CacheKV beam reorder, fused_multi_transformer_op.cc — re-designed
        for paged KV): prefill once per row into SHARED prompt pages, give
        each beam ``n_priv`` private decode pages, copy the partial
        boundary page per beam at fork, and reorder beams by permuting
        only the private pages' contents."""
        W = g.num_beams
        max_new = g.max_new_tokens
        pad = g.pad_token_id
        L = self._num_layers
        page = self.page_size

        def run(params, ids, lengths, prompt_tables, priv_ids, k_pages,
                v_pages, rng):
            del rng                       # beam search is deterministic
            b = batch
            max_pages = prompt_tables.shape[1]

            # ---- prefill once over the b prompt rows (shared pages)
            zero_pos = jnp.zeros((b,), jnp.int32)
            caches = [(k_pages[i], v_pages[i], prompt_tables, zero_pos)
                      for i in range(L)]
            pos2d = jnp.broadcast_to(
                jnp.arange(plen, dtype=jnp.int32)[None], (b, plen))
            logits, caches = self._model_step(params, ids, pos2d, None,
                                              caches)
            k_pages = [c[0] for c in caches]
            v_pages = [c[1] for c in caches]
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]

            # ---- fork: each beam's first private page gets a copy of the
            # row's partially-filled boundary page (decode tokens land
            # mid-page when the true length isn't page-aligned)
            boundary = lengths // page                       # [b]
            bsrc = jnp.take_along_axis(
                prompt_tables, jnp.minimum(boundary, max_pages - 1)[:, None],
                axis=1)[:, 0]                                # [b]
            first_priv = priv_ids[:, :, 0].reshape(-1)       # [b*W]
            for i in range(L):
                k_pages[i] = k_pages[i].at[first_priv].set(
                    jnp.repeat(k_pages[i][bsrc], W, axis=0))
                v_pages[i] = v_pages[i].at[first_priv].set(
                    jnp.repeat(v_pages[i][bsrc], W, axis=0))

            # ---- per-beam tables: shared below the boundary page,
            # private from it on (never permuted — contents move instead)
            p_idx = jnp.arange(max_pages, dtype=jnp.int32)[None, None]
            rel = jnp.clip(p_idx - boundary[:, None, None], 0, n_priv - 1)
            priv_full = jnp.take_along_axis(
                priv_ids, jnp.broadcast_to(rel, (b, W, max_pages)), axis=2)
            shared_full = jnp.broadcast_to(prompt_tables[:, None],
                                           (b, W, max_pages))
            beam_tables = jnp.where(p_idx < boundary[:, None, None],
                                    shared_full, priv_full)
            beam_tables = beam_tables.reshape(b * W, max_pages)
            lengths_w = jnp.repeat(lengths, W, axis=0)       # [b*W]

            # ---- first beam step from the prompt logits (all beams of a
            # row share the prefix, so only beam 0 is live)
            logp = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
            if g.eos_token_id is not None and g.min_length > 0:
                logp = logp.at[:, g.eos_token_id].set(sampling.NEG_INF)
            vocab = logp.shape[-1]
            init_bias = jnp.where(jnp.arange(W) == 0, 0.0, sampling.NEG_INF)
            flat = (logp[:, None, :] + init_bias[None, :, None]) \
                .reshape(b, W * vocab)
            top_s, top_i = jax.lax.top_k(flat, W)            # [b, W]
            tok = (top_i % vocab).astype(jnp.int32)
            cum = top_s
            finished = (tok == g.eos_token_id) \
                if g.eos_token_id is not None \
                else jnp.zeros((b, W), jnp.bool_)
            gen_len = jnp.ones((b, W), jnp.int32)
            out = jnp.full((b, W, max_new), pad, jnp.int32)
            out = out.at[:, :, 0].set(tok)

            def permute_priv(pages, src):
                """Target beam w adopts source beam src[i, w]'s decode
                pages — a gather+scatter over n_priv pages per beam, NOT
                the dense engine's whole-cache reorder."""
                src_ids = jnp.take_along_axis(priv_ids, src[:, :, None],
                                              axis=1)       # [b, W, n_priv]
                return pages.at[priv_ids.reshape(-1)].set(
                    pages[src_ids.reshape(-1)])

            def cond(state):
                step, fin = state[0], state[4]
                return jnp.logical_and(step < max_new,
                                       jnp.logical_not(jnp.all(fin)))

            def body(state):
                step, tok, out, cum, fin, gen_len, k_pages, v_pages = state
                pos = lengths_w + step - 1                   # [b*W]
                caches = [(k_pages[i], v_pages[i], beam_tables, pos)
                          for i in range(L)]
                logits, caches = self._model_step(
                    params, tok.reshape(b * W, 1), pos[:, None], None,
                    caches)
                k_pages = [c[0] for c in caches]
                v_pages = [c[1] for c in caches]
                logp = jax.nn.log_softmax(
                    logits[:, -1].astype(jnp.float32), axis=-1)
                logp = logp.reshape(b, W, vocab)
                if g.eos_token_id is not None and g.min_length > 0:
                    logp = jnp.where(step < g.min_length,
                                     logp.at[:, :, g.eos_token_id].set(
                                         sampling.NEG_INF), logp)
                pad_row = jnp.full((vocab,), sampling.NEG_INF,
                                   jnp.float32).at[pad].set(0.0)
                logp = jnp.where(fin[:, :, None], pad_row[None, None, :],
                                 logp)
                flat = (cum[:, :, None] + logp).reshape(b, W * vocab)
                top_s, top_i = jax.lax.top_k(flat, W)
                src = top_i // vocab
                nxt = (top_i % vocab).astype(jnp.int32)
                k_pages = [permute_priv(kp, src) for kp in k_pages]
                v_pages = [permute_priv(vp, src) for vp in v_pages]
                out = jnp.take_along_axis(out, src[:, :, None], axis=1)
                fin = jnp.take_along_axis(fin, src, axis=1)
                gen_len = jnp.take_along_axis(gen_len, src, axis=1)
                gen_len = gen_len + jnp.logical_not(fin)
                if g.eos_token_id is not None:
                    fin = jnp.logical_or(fin, nxt == g.eos_token_id)
                out = jax.lax.dynamic_update_slice(
                    out, nxt[:, :, None],
                    (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                     step))
                return (step + 1, nxt, out, top_s, fin, gen_len, k_pages,
                        v_pages)

            state = (jnp.asarray(1, jnp.int32), tok, out, cum, finished,
                     gen_len, k_pages, v_pages)
            state = jax.lax.while_loop(cond, body, state)
            _, _, out, cum, _, gen_len, k_pages, v_pages = state
            norm = cum / (gen_len.astype(jnp.float32) ** g.length_penalty)
            best = jnp.argmax(norm, axis=1)
            seq = jnp.take_along_axis(out, best[:, None, None],
                                      axis=1)[:, 0]
            score = jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0]
            return seq, score, k_pages, v_pages

        return jax.jit(run, donate_argnums=(5, 6))

    def _generate_paged_beam(self, ids, lengths, plen, g, return_scores):
        """Pool choreography for the paged beam program: prompt rows own
        the shared pages; every beam is a KVBlockPool.fork of its row plus
        a reservation that appends its private decode pages."""
        if self._kv_dtype is not None:
            raise ValueError(
                "beam search over quantized KV pools is not supported "
                "(the fork/permute page choreography moves fp pages; "
                "the serving plane never batches beam requests)")
        b = ids.shape[0]
        W = g.num_beams
        page = self.page_size
        n_prompt = plen // page
        n_priv = -(-g.max_new_tokens // page) + 1
        max_pages = -(-(plen + g.max_new_tokens) // page)
        max_pages = max(max_pages, n_prompt + 1)

        pool = self._ensure_pool(b * (n_prompt + W * n_priv))
        prompt_sids = list(range(b))
        beam_sids = [b + i * W + w for i in range(b) for w in range(W)]
        for s in prompt_sids + beam_sids:
            pool.free(s)
        tables = np.zeros((b, max_pages), np.int32)
        priv_ids = np.zeros((b, W, n_priv), np.int32)
        for i in prompt_sids:
            pool.reserve(i, plen)
            t = pool.block_table(i)
            tables[i, :len(t)] = t
        for i in range(b):
            for w in range(W):
                sid = b + i * W + w
                pool.fork(i, sid)                  # share the prompt pages
                pool.reserve(sid, plen + (n_priv * page))
                t = pool.block_table(sid)
                priv_ids[i, w] = t[n_prompt:n_prompt + n_priv]

        k_pages, v_pages = self._ensure_pages()
        # sharing accounting (tested): W beams re-use each row's n_prompt
        # prompt pages; a fork-less design would copy them per beam
        self.last_beam_pool_stats = {
            "used_pages": pool.num_blocks - pool.free_blocks,
            "prompt_pages_shared": b * n_prompt,
            "private_pages": b * W * n_priv,
            "unshared_equivalent": b * W * (n_prompt + n_priv),
        }
        key = ("paged-beam", b, plen, max_pages, n_priv, pool.num_blocks,
               g.cache_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._build_paged_beam(b, plen, n_priv, g)
            self._compiled[key] = fn
        rng = jax.random.PRNGKey(g.seed)
        self._k_pages = self._v_pages = None
        with self._mesh_ctx():
            seq, score, k_pages, v_pages = fn(
                self._params, self._replicated(ids),
                self._replicated(lengths), self._replicated(tables),
                self._replicated(priv_ids), k_pages, v_pages, rng)
        self._k_pages, self._v_pages = k_pages, v_pages
        for s in prompt_sids + beam_sids:
            pool.free(s)
        seq = np.asarray(seq)
        return (seq, np.asarray(score)) if return_scores else seq

    # --------------------------------------------------- streaming decode
    def _build_stream_prefill(self, batch, plen, g: GenerationConfig):
        """Prefill + first token as its own program (the step-wise half
        of _build_paged; reference predictors decode token-by-token, so
        streaming falls out of their design — here it is an explicit
        second compiled program over the SAME persistent pools)."""
        L = self._num_layers

        def run(params, ids, lengths, tables, k_pages, v_pages, rng):
            zero_pos = jnp.zeros((batch,), jnp.int32)
            caches = [(k_pages[i], v_pages[i], tables, zero_pos)
                      for i in range(L)]
            pos2d = jnp.broadcast_to(
                jnp.arange(plen, dtype=jnp.int32)[None], (batch, plen))
            logits, caches = self._model_step(params, ids, pos2d, None,
                                              caches)
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
            col = jnp.arange(plen, dtype=jnp.int32)[None]
            hist = jnp.concatenate(
                [jnp.where(col < lengths[:, None], ids, -1),
                 jnp.full((batch, g.max_new_tokens), -1, jnp.int32)],
                axis=1)
            pick = self._logits_picker(g)
            k0, rng = jax.random.split(rng)
            tok, _ = pick(last, hist, 0, k0)
            fin = (tok == g.eos_token_id) if g.eos_token_id is not None \
                else jnp.zeros((batch,), jnp.bool_)
            hist = hist.at[:, plen].set(tok)
            return (tok, fin, hist, rng,
                    [c[0] for c in caches], [c[1] for c in caches])

        return jax.jit(run, donate_argnums=(4, 5))

    def _build_stream_chunk(self, batch, plen, chunk, g: GenerationConfig):
        """Decode ``chunk`` tokens from persistent pools: the body of
        _build_paged's while_loop as a fixed-length scan, resumable at
        any step offset."""
        L = self._num_layers

        def run(params, tok, fin, hist, step0, lengths, tables, k_pages,
                v_pages, rng):
            def body(carry, i):
                tok, fin, hist, caches, rng = carry
                step = step0 + i
                pos = lengths + step - 1
                caches = [(kp, vp, tb, pos) for kp, vp, tb, _ in caches]
                logits, caches = self._model_step(
                    params, tok[:, None], pos[:, None], None, caches)
                key, rng = jax.random.split(rng)
                pick = self._logits_picker(g)
                nxt, _ = pick(logits[:, -1], hist, step, key)
                if g.eos_token_id is not None:
                    nxt = jnp.where(fin, g.pad_token_id, nxt)
                    fin = jnp.logical_or(fin, nxt == g.eos_token_id)
                hist = jax.lax.dynamic_update_slice(
                    hist, nxt[:, None],
                    (jnp.zeros((), jnp.int32), plen + step))
                return (nxt, fin, hist, caches, rng), nxt

            caches = [(k_pages[i], v_pages[i], tables,
                       jnp.zeros((batch,), jnp.int32)) for i in range(L)]
            (tok, fin, hist, caches, rng), toks = jax.lax.scan(
                body, (tok, fin, hist, caches, rng), jnp.arange(chunk))
            return (toks.T, tok, fin, hist, rng,
                    [c[0] for c in caches], [c[1] for c in caches])

        return jax.jit(run, donate_argnums=(7, 8))

    def stream(self, input_ids, generation_config: GenerationConfig = None,
               attention_mask=None, chunk_size: int = 8):
        """Generator yielding decoded tokens in chunks (np [b, <=chunk])
        — the streaming serving mode: prefill compiles once, each chunk
        is one device round-trip over the persistent paged pools, and
        the stream stops early when every row hits EOS.  Beam search is
        not streamable (it finalizes globally)."""
        self._refuse_latent("stream()")
        g = generation_config or GenerationConfig()
        if g.num_beams > 1:
            raise ValueError("stream() supports sampling/greedy only")
        self._params = self._snapshot_params()
        ids, lengths, plen, pages_per_seq, pool, tables = \
            self._prepare_paged_inputs(input_ids, attention_mask, g)
        b = ids.shape[0]
        try:
            k_pages, v_pages = self._ensure_pages()
            key_p = ("stream-prefill", b, plen, pages_per_seq,
                     pool.num_blocks, g.cache_key())
            fn_p = self._compiled.get(key_p)
            if fn_p is None:
                fn_p = self._build_stream_prefill(b, plen, g)
                self._compiled[key_p] = fn_p
            rng = jax.random.PRNGKey(g.seed)
            # fixed per-stream feeds: place once, not per chunk
            lengths_d = self._replicated(lengths)
            tables_d = self._replicated(tables)
            # pools are donated into every call: drop our references
            # first, rebind ONLY from a successful call's outputs (a
            # failed call consumed them; _ensure_pages then rebuilds)
            self._k_pages = self._v_pages = None
            with self._mesh_ctx():
                tok, fin, hist, rng, k_pages, v_pages = fn_p(
                    self._params, self._replicated(ids), lengths_d,
                    tables_d, k_pages, v_pages, rng)
            self._k_pages, self._v_pages = k_pages, v_pages
            emitted = 1
            yield np.asarray(tok)[:, None]
            while emitted < g.max_new_tokens and not bool(
                    np.asarray(fin).all()):
                chunk = min(chunk_size, g.max_new_tokens - emitted)
                key_c = ("stream-chunk", b, plen, chunk, pages_per_seq,
                         pool.num_blocks, g.cache_key())
                fn_c = self._compiled.get(key_c)
                if fn_c is None:
                    fn_c = self._build_stream_chunk(b, plen, chunk, g)
                    self._compiled[key_c] = fn_c
                self._k_pages = self._v_pages = None
                with self._mesh_ctx():
                    toks, tok, fin, hist, rng, k_pages, v_pages = fn_c(
                        self._params, tok, fin, hist,
                        jnp.asarray(emitted, jnp.int32), lengths_d,
                        tables_d, k_pages, v_pages, rng)
                self._k_pages, self._v_pages = k_pages, v_pages
                emitted += chunk
                yield np.asarray(toks)
        finally:
            for s in range(b):
                pool.free(s)

    # ------------------------------------------------------------- public
    def _prepare_paged_inputs(self, input_ids, attention_mask, g):
        """Shared input canonicalization for generate() and stream():
        right-pad repack, page/bucket padding, pool reservation, page
        tables.  Returns (ids, lengths, plen, pages_per_seq, pool,
        tables)."""
        ids = np.asarray(input_ids._data if isinstance(input_ids, Tensor)
                         else input_ids).astype(np.int32)
        if ids.ndim == 1:
            ids = ids[None, :]
        b, plen_raw = ids.shape
        mask = (np.ones_like(ids) if attention_mask is None
                else np.asarray(attention_mask).astype(np.int32))
        # canonicalize to RIGHT padding (see class docstring)
        for i in range(b):
            real = np.flatnonzero(mask[i])
            row = ids[i, real]
            ids[i] = g.pad_token_id
            mask[i] = 0
            ids[i, :len(real)] = row
            mask[i, :len(real)] = 1
        lengths = np.maximum(mask.sum(axis=1), 1).astype(np.int32)
        assert plen_raw + g.max_new_tokens <= self._max_positions, (
            f"prompt {plen_raw} + max_new {g.max_new_tokens} exceeds "
            f"max_position_embeddings {self._max_positions}")
        # prompt padded to a bucket AND a page multiple
        plen = _round_up(max(plen_raw, 1), self._prompt_bucket)
        plen = _round_up(min(plen, self._max_positions), self.page_size)
        plen = max(plen, _round_up(plen_raw, self.page_size))
        if plen > plen_raw:
            ids = np.pad(ids, ((0, 0), (0, plen - plen_raw)),
                         constant_values=g.pad_token_id)
        pages_per_seq = -(-(plen + g.max_new_tokens) // self.page_size)
        pool = self._ensure_pool(pages_per_seq * b)
        for s in range(b):
            pool.free(s)
            pool.reserve(s, plen + g.max_new_tokens)
        tables = np.zeros((b, pages_per_seq), np.int32)
        for s in range(b):
            t = pool.block_table(s)[:pages_per_seq]
            tables[s, :len(t)] = t
        return ids, lengths, plen, pages_per_seq, pool, tables

    def generate(self, input_ids, generation_config: GenerationConfig = None,
                 attention_mask=None, return_scores: bool = False):
        self._refuse_latent("generate()")
        g = generation_config or GenerationConfig()
        self._params = self._snapshot_params()
        ids, lengths, plen, pages_per_seq, pool, tables = \
            self._prepare_paged_inputs(input_ids, attention_mask, g)
        b = ids.shape[0]
        seq_ids = list(range(b))

        if g.num_beams > 1:
            for s in seq_ids:       # beam path does its own reservations
                pool.free(s)
            return self._generate_paged_beam(ids, lengths, plen, g,
                                             return_scores)

        k_pages, v_pages = self._ensure_pages()

        key = ("paged", b, plen, pages_per_seq, pool.num_blocks,
               g.cache_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._build_paged(b, plen, g)
            self._compiled[key] = fn
        rng = jax.random.PRNGKey(g.seed)
        # donated arrays are consumed even if the call fails — drop our
        # references first and rebind from the outputs on success
        self._k_pages = self._v_pages = None
        with self._mesh_ctx():
            seq, score, k_pages, v_pages = fn(
                self._params, self._replicated(ids),
                self._replicated(lengths), self._replicated(tables),
                k_pages, v_pages, rng)
        self._k_pages, self._v_pages = k_pages, v_pages
        for s in seq_ids:
            pool.free(s)
        seq = np.asarray(seq)
        return (seq, np.asarray(score)) if return_scores else seq
