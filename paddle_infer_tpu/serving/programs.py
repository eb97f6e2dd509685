"""The compiled serving programs of the continuous-batching scheduler:
``build_mixed_step`` (the one step executable) and ``build_page_copy``
(the prefix cache's copy-on-write).

Design constraint: admitting a request must NEVER recompile the hot
loop, whatever its sampling config.  The dense/paged engines key
executables by ``GenerationConfig.cache_key()`` — fine when one call
serves one homogeneous batch, fatal for continuous batching where every
row can carry different knobs.  Here temperature / top-k / top-p /
min-length / eos / do_sample ride as **per-row arrays** (the ``samp``
fields of the step's packed input), so there is exactly one step
executable per
(batch, token-budget, table-width, pool-size) and heterogeneous requests
share it.  Greedy rows stay argmax-exact with ``GenerationEngine``
output: temperature scaling, top-k and top-p masking never change the
argmax (the top token always survives every filter).  Within that one
executable the sampling tail does what the step's rows ask for
(``sampling_rows``): the vocabulary-wide sort behind top-k and the
nucleus runs only on a step in which a row draws through a filter, the
categorical draw only on a step in which a row draws — two
``lax.cond`` on fields the packed input already carries, no second
executable.

Layout contract with ``EngineCore``:

  * nothing is padded to a prompt bucket: row ``b`` of a step carries
    ``qlens[b]`` real tokens starting at absolute position ``ctx[b]``;
  * the step's tokens ride ONE flat axis ``ids[token_budget]``: the
    rows' tokens end to end in slot order, row ``b``'s at ``starts[b] ..
    starts[b] + qlens[b] - 1`` with ``starts`` the exclusive cumulative
    sum of ``qlens`` (computed here, never shipped), the tail padded;
    every token-wise layer runs over those slots and nothing wider, and
    pad slots are written nowhere and never attended;
  * a decode row feeds its last emitted token, writes its KV at
    ``length + emitted - 1`` and samples the next token, with *per-row*
    lengths/offsets so rows at different generation depths coexist;
  * inactive batch rows point every table entry at the scratch page
    with ``qlens = 0`` — their writes land in garbage the attention mask
    never exposes to live rows.

Per-row RNG: each request owns a base key (``fold_in(PRNGKey(seed),
rid)``); step ``s`` uses ``fold_in(base, s)`` — independent streams per
row that do not depend on which step or beside which rows a token is
sampled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..inference import sampling
from ..ops.pallas.ragged_paged_attention import ragged_rows

# samp dict fields (all shaped [batch]):
#   temperature f32, top_k i32 (0 = off), top_p f32 (1.0 = off),
#   min_len i32, eos i32 (-1 = none), do_sample bool, pad i32
SAMP_FIELDS = (("temperature", "float32"), ("top_k", "int32"),
               ("top_p", "float32"), ("min_len", "int32"),
               ("eos", "int32"), ("do_sample", "bool"), ("pad", "int32"))

# the expert counters that ride out with the tokens (moe/stats.py
# ``MoEStatsCollector.totals``): a dropless model's four, in this order
DROPLESS_COUNTERS = ("moe_assignments_total", "moe_assignments_held",
                     "moe_held_expert_max", "moe_experts_touched")
# ... two more where its expert layers have identity experts
IDENTITY_COUNTERS = ("moe_assignments_identity", "moe_real_per_token_max")
# ... and the capacity path's three
CAPACITY_COUNTERS = ("moe_routed", "moe_dropped", "moe_aux")
# what a model of hyper-connected residual streams notes a step
# (nn/hyper_connections.py ``ResidualStatsCollector.totals``)
RESIDUAL_COUNTERS = (("mhc_col_sum_gap_max", "float32"),
                     ("residual_streams", "int32"),
                     ("residual_stream_bytes", "int32"))


class StepLayout:
    """One side of the step's host interface as ONE ``int32[size]``
    buffer: ``rows`` of ``(name, shape, dtype)`` laid end to end at
    static offsets, 4 bytes an element — ``int32`` as it is, ``float32``
    and ``uint32`` by bit pattern, ``bool`` widened to 0 / 1.  The host
    (``views``) and the traced program (``unpack`` / ``pack``) read the
    same table, so the two cannot drift."""

    def __init__(self, rows):
        self.rows = []
        off = 0
        for name, shape, dtype in rows:
            n = int(np.prod(shape, dtype=np.int64))
            self.rows.append((name, tuple(shape), np.dtype(dtype), off, n))
            off += n
        self.size = off

    def views(self, buf):
        """Host side: ``{name: view}`` into ``buf`` (numpy
        ``int32[size]``), each in its field's shape; a ``float32`` or
        ``uint32`` field is a view of that type over the same words, a
        ``bool`` field the ``int32`` words themselves (0 / 1)."""
        out = {}
        for name, shape, dtype, off, n in self.rows:
            v = buf[off:off + n].reshape(shape)
            out[name] = v if dtype == np.bool_ else v.view(dtype)
        return out

    def unpack(self, packed):
        """Traced side of an input: ``{name: array}`` in each field's
        own shape and dtype, by static slices of ``packed``."""
        out = {}
        for name, shape, dtype, off, n in self.rows:
            x = packed[off:off + n].reshape(shape)
            if dtype == np.bool_:
                x = x != 0
            elif dtype != np.int32:
                x = jax.lax.bitcast_convert_type(x, dtype)
            out[name] = x
        return out

    def pack(self, fields):
        """Traced side of an output: the fields of ``{name: array}``
        (each of its row's shape and dtype) as one ``int32[size]``."""
        parts = []
        for name, shape, dtype, _, _ in self.rows:
            x = fields[name]
            if x.shape != shape or x.dtype != dtype:
                raise ValueError(
                    f"step field {name!r} is {x.dtype}{list(x.shape)}, "
                    f"its layout row says {dtype}{list(shape)}")
            if dtype == np.bool_:
                x = x.astype(jnp.int32)
            elif dtype != np.int32:
                x = jax.lax.bitcast_convert_type(x, jnp.int32)
            parts.append(x.reshape(-1))
        return jnp.concatenate(parts)


def step_input_layout(max_batch, token_budget, max_pages, spec_window=1):
    """THE layout of the mixed step's packed input: every per-step host
    field but the grammar mask, a function of these four deployment
    constants and nothing else."""
    b = (int(max_batch),)
    rows = [("ids", (int(token_budget),), "int32"), ("qlens", b, "int32"),
            ("ctx", b, "int32"), ("steps0", b, "int32"),
            ("sample_now", b, "bool"), ("adapter_slots", b, "int32")]
    if int(spec_window) > 1:
        rows.append(("spec", b, "bool"))
    rows.append(("tables", b + (int(max_pages),), "int32"))
    rows.extend((name, b, dtype) for name, dtype in SAMP_FIELDS)
    rows += [("keys", b + (2,), "uint32"), ("scratch", (), "int32")]
    return StepLayout(rows)


def step_output_layout(max_batch, spec_window=1, moe=None, residual=False):
    """THE layout of the mixed step's packed output: the sampled tokens,
    the finished flags, the emit counts of a speculating step, then the
    expert counters — ``moe`` is None (no expert layer counted),
    ``"dropless"`` (the four ``DROPLESS_COUNTERS``), ``"dropless_identity"``
    (those and the two ``IDENTITY_COUNTERS``) or the capacity
    path's expert count ``E`` (``moe_routed[E]``, ``moe_dropped``,
    ``moe_aux`` float32 by bit pattern) — and, with ``residual``, the
    three ``RESIDUAL_COUNTERS`` of a model of hyper-connected streams."""
    b, W = int(max_batch), int(spec_window)
    rows = [("tok", (b,) if W <= 1 else (b, W), "int32"),
            ("fin", (b,), "bool")]
    if W > 1:
        rows.append(("n_emit", (b,), "int32"))
    if moe in ("dropless", "dropless_identity"):
        rows.extend((name, (), "int32") for name in DROPLESS_COUNTERS)
        if moe == "dropless_identity":
            rows.extend((name, (), "int32") for name in IDENTITY_COUNTERS)
    elif moe is not None:
        rows += [("moe_routed", (int(moe),), "int32"),
                 ("moe_dropped", (), "int32"), ("moe_aux", (), "float32")]
    if residual:
        rows.extend((name, (), dtype) for name, dtype in RESIDUAL_COUNTERS)
    return StepLayout(rows)


def sampling_rows(samp, sample_now):
    """``(draws, filters)``, ``[b]`` bools: the rows that draw their token
    this step (they sample now and ask for a sample), and of those the
    ones that draw through a filter (``top_k`` set or ``top_p`` under 1).
    The traced tail branches on them and the packer counts them (StepLog
    ``draw_rows``, ``filter_rows``) by this one rule, on ``jnp`` arrays
    and on the host's views alike (bools there are 0 / 1 words)."""
    draws = (sample_now != 0) & (samp["do_sample"] != 0)
    filters = draws & ((samp["top_k"] > 0) | (samp["top_p"] < 1.0))
    return draws, filters


def _filter_thresholds(logits, samp, filters):
    """``[b]`` thresholds ``t``: a filtering row's chain (top-k, then the
    nucleus over what top-k kept) sets to ``NEG_INF`` exactly its entries
    below ``t``.  ONE sort: top-k's mask is monotone, so the masked
    row's descending order is the sorted row with the entries under its
    k-th masked, ties at the k-th included.  A row outside ``filters``
    comes back with a threshold that masks nothing."""
    vocab = logits.shape[-1]
    # top-k off: k widens to the whole vocabulary, the k-th entry is the
    # row's minimum and nothing lies below it
    k = jnp.where(jnp.logical_and(filters, samp["top_k"] > 0),
                  jnp.clip(samp["top_k"], 1, vocab), vocab)
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    sorted_desc = jnp.where(sorted_desc < kth, sampling.NEG_INF, sorted_desc)

    # the nucleus over the post-top-k row (its top token is always kept)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < samp["top_p"][:, None]
    keep = keep.at[..., 0].set(True)
    thresh = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1)
    # nucleus off is OFF: the cumulative sum rounds to 1.0 before the
    # row's end, so at ``top_p`` 1.0 the comparison above would still
    # cut a tail (of some 1e-7 of the mass) that the row did not ask for
    thresh = jnp.where(jnp.logical_and(filters, samp["top_p"] < 1.0),
                       thresh, -jnp.inf)
    return jnp.maximum(kth[:, 0], thresh)


def _process_rows(logits, samp, steps, filters):
    """Per-row logits-processor chain (min-length eos ban → temperature
    → top-k → top-p), vectorized over rows with heterogeneous knobs.
    Same order as ``sampling.process_logits``.  The ban and the
    temperature are one elementwise pass on every step; the sort behind
    both filters runs only on a step in which a row draws through one
    (``filters`` of ``sampling_rows``, unbatched under a ``vmap``: a
    batched predicate would turn the conditional into a select that runs
    both sides).  What a row gets depends on its own fields alone: a
    greedy row's argmax survives every filter, so it is never filtered,
    whatever its neighbours ask for."""
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]

    eos = samp["eos"]
    banned = jnp.logical_and(eos >= 0, steps < samp["min_len"])
    eos_col = jax.nn.one_hot(jnp.maximum(eos, 0), vocab, dtype=jnp.bool_)
    logits = jnp.where(jnp.logical_and(banned[:, None], eos_col),
                       sampling.NEG_INF, logits)

    t = jnp.maximum(samp["temperature"].astype(jnp.float32), 1e-6)
    logits = logits / t[:, None]

    # only the [b] thresholds cross the conditional; both filters are
    # ``logits < t -> NEG_INF``, so the mask is one pass out here
    thresh = jax.lax.cond(
        jnp.any(filters),
        lambda: _filter_thresholds(logits, samp, filters),
        lambda: jnp.full(logits.shape[:1], -jnp.inf, jnp.float32))
    return jnp.where(logits < thresh[:, None], sampling.NEG_INF, logits)


def _pick_rows(proc, samp, steps, keys, draws):
    """Argmax, and on a step in which a row draws (``draws`` of
    ``sampling_rows``, unbatched under a ``vmap``) a sample for each
    such row from its own ``fold_in`` stream."""
    greedy = jnp.argmax(proc, axis=-1).astype(jnp.int32)

    def drawn():
        step_keys = jax.vmap(jax.random.fold_in)(keys, steps)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(step_keys, proc)
        return jnp.where(draws, sampled, greedy).astype(jnp.int32)

    return jax.lax.cond(jnp.any(draws), drawn, lambda: greedy)


def _layer_caches(engine, k_pages, v_pages, *rest):
    """One cache tuple a layer, through the engine's cache layout
    (inference/cache_layout.py): a ``kv`` layer's two pools or a
    ``latent`` layer's one, then the step's shared arrays."""
    return [c.step_cache(k_pages[i], v_pages[i], *rest)
            for i, c in enumerate(engine._cache_layout)]


def _layer_pools(engine, caches):
    """The ``(k_pages, v_pages)`` lists back out of the layers' returned
    tuples (``None`` in the second list for a one-pool layer)."""
    pools = [c.pools_of(x) for c, x in zip(engine._cache_layout, caches)]
    return [p[0] for p in pools], [p[1] for p in pools]


def build_mixed_step(engine, max_batch, token_budget, max_pages,
                     spec_window=1, moe_stats=False, grammar=False,
                     residual_stats=False):
    """THE serving step executable: one launch per scheduler step,
    whatever the batch composition.  Row ``b`` carries ``qlens[b]``
    query tokens starting at absolute position ``ctx[b]`` — 1 for a
    decode row (its last emitted token), >1 for a prefill chunk (a slice
    of the prompt), 0 for an inactive row (all table entries at the
    scratch page) — laid on the flat token axis ``ids[T]``, ``T =
    token_budget``, behind the rows before it (``ragged_rows``).  The
    model runs over ``[1, T]`` with ``position_ids = ctx[row] + offset``
    (pad slots at 0); the per-row ``[b, T, heads, d]`` view lives only
    inside the attention layers, around the cache writers and the
    kernel (models/transformer_block._forward_paged; the latent layer
    gathers one query a decode row and a chunk row's queries inside its
    own iteration, models/latent_moe.py), and the hidden state is
    gathered at each row's sampled slot before the final norm and the
    head (``head_rows``).  The executable's shape depends
    only on ``(max_batch, token_budget, max_pages, pool)``, so after ONE
    warmup compile every mix of cold chunks, warm-prefix suffixes and
    decode rows reuses it.

    ``run(params, packed, k_pages, v_pages)`` → ``(out, k_pages,
    v_pages)``; pools are donated.  The step's host interface is ONE
    ``int32`` buffer each way.  ``packed`` holds, by
    ``step_input_layout(max_batch, token_budget, max_pages, W)``:
    ``ids[T], qlens[b], ctx[b], steps0[b], sample_now[b],
    adapter_slots[b], tables[b, max_pages]``, the seven ``samp`` fields
    ``[b]`` each, ``keys[b, 2], scratch[]`` — ``float32`` and ``uint32``
    fields by bit pattern, bools as 0 / 1 — unpacked here by static
    slices; below the unpack the program is what separate arguments
    would give.  ``out`` holds, by ``step_output_layout``: ``tok[b],
    fin[b]`` and the expert counters of ``moe_stats``.  The engine puts
    the buffer with one ``device_put`` and reads ``out`` back with one
    ``np.asarray`` (StepLog ``h2d_arrays``, ``d2h_arrays``).

    ``adapter_slots`` is the per-row LoRA binding (slot 0 = identity):
    pure gather DATA over the stacked pools
    (serving/adapters/layer.py), threaded to the converted projections
    through the thread-local slot side-channel so the executable key
    stays deployment constants only.  Unconverted models ignore it —
    the engine always packs the array (zeros), so the signature is one
    shape for every deployment.

    Sampling: each row's next-token logits sit at flat slot ``starts +
    qlens - 1`` (for decode rows that is the row's one slot), and the
    head computes those ``b`` rows alone.  ``sample_now``
    is False for non-final prefill chunks: their row emits no token
    this step (the pad id is returned and the engine ignores it).
    ``steps0`` is the sampled token's generation-step index, so the
    ``fold_in`` RNG stream and the min-length window depend on the
    request and its step alone, never on how the prompt was chunked or
    which rows shared the launch.

    ``spec_window = W > 1`` builds the speculative draft/verify variant
    instead (EngineCore ``speculate=True``).  A speculating decode row packs
    ``[last_tok, d_1..d_k]`` (``k <= W - 1`` drafts, ``qlens = k + 1``)
    and its ``spec`` flag routes the first W query positions through
    per-position decode-kernel attention (the 7-element cache /
    ``verify_rows`` path), so position ``j``'s logits are bitwise what
    sequential step ``steps0 + j`` would compute.  Acceptance is the
    shared rule in ``inference/spec_accept.py``: greedy rows accept the
    longest draft prefix matching the per-position argmax chain —
    token-identical to ``speculate=False``; sampled rows accept ``d_j``
    with probability ``p_j(d_j)`` (point-mass proposal) and resample
    the first rejection from the draft-masked residual, so the emitted
    marginal is exactly the non-speculative sampling distribution.
    Accepted positions reuse the SAME ``fold_in(base, steps0 + j)``
    stream as sequential decode (accept tests / rejection resamples
    draw from the disjoint ``fold_in(fold_in(base, step), 1|2)``
    streams), so a non-spec row reproduces the plain step bit-for-bit.

    Spec signature: the same ``run(params, packed, k_pages,
    v_pages)``; ``packed`` gains ``spec[b]`` behind ``adapter_slots``,
    ``out`` is ``tok[b, W], fin[b], n_emit[b]`` then the counters — row
    ``i`` emits ``tok[i, :n_emit[i]]`` (truncated at its first eos; 0
    when ``sample_now`` is off).  Rejected-tail KV needs NO pool
    ops: stale entries at positions ``>= ctx + n_emit`` sit inside the
    row's reservation, are never attended (every read masks by the
    row's true length) and are overwritten before they become
    visible.

    ``moe_stats = True`` (EngineCore sets it when the model's FFNs were
    converted by ``serving.moe.prepare_moe_serving``) threads the
    step's valid-slot mask through the MoE stats side-channel
    (serving/moe/stats.py) and appends three fields to ``out`` —
    ``moe_routed[E] i32, moe_dropped i32, moe_aux f32`` (by bit
    pattern) — so capacity-overflow drops are surfaced per step, never
    silent.  A model of dropless expert layers
    (serving/moe/dropless.py) appends its four counters there instead
    (``DROPLESS_COUNTERS``, ``MoEStatsCollector.totals``).  The stats
    ride the same trace and the same read-back (data, no shape
    impact), so the one-executable invariant is untouched.

    ``residual_stats = True`` (EngineCore sets it when the model carries
    hyper-connected residual streams, nn/hyper_connections.py) opens
    that module's side-channel around the forward the same way and
    appends its three ``RESIDUAL_COUNTERS`` behind the expert counters.

    ``grammar = True`` (EngineCore sets it when constructed with a
    ``grammar_vocab``) threads ONE extra input behind ``packed`` —
    ``run(params, packed, gmask, k_pages, v_pages)`` — an additive
    logit mask: ``gmask[b, V]`` here, ``gmask[b, W, V]`` for the
    speculative variant, always f32 with 0 for allowed and
    ``sampling.NEG_INF`` for banned entries.  It stays an array of its
    own (2 MB at a 32,000-token vocabulary: copying it into the buffer
    on the host would cost more than its put).  The mask
    is pure per-row DATA gathered host-side from each row's FSM state
    (serving/structured/), applied to the last-position logits BEFORE
    the processor chain, so constrained greedy stays masked-argmax
    exact and constrained sampling draws from the renormalized masked
    distribution under the unchanged ``fold_in`` streams.  Speculative
    lane ``j`` is masked by its OWN advanced FSM state (the engine
    builds lane masks by advancing through drafts ``0..j-1``), which —
    together with accept/resample operating on the masked logits — is
    what makes constrained spec-vs-plain bitwise identical and keeps
    lanes from ever emitting a violating token.  Unconstrained rows
    carry all-zero mask rows.  Deployments without a grammar vocab get
    the ``grammar=False`` signature: no mask argument at all."""
    T = token_budget

    def _model_step(params, ids, qlens, ctx, adapter_slots, caches,
                    head_offset):
        """One model step over the flat token axis ``ids[T]`` under the
        adapter-slot side-channel, optionally collecting MoE routing
        stats masked to the step's valid (non-pad) token slots.  The
        hidden state is gathered at ``starts + head_offset`` (``[b]`` or
        ``[b, W]`` offsets inside each row) before the final norm and
        the head, so the logits come back as ``[b(, W), vocab]``.  The
        slot context is opened unconditionally: unconverted models never
        read it, and a converted model with an all-zero slot vector
        gathers the identity rows — same executable either way."""
        from .adapters import slots as lora_slots_mod

        starts, row, offset, valid = ragged_rows(qlens, T)
        # pad positions pin to 0: a replayed decode row near the window
        # edge would push ``ctx + i`` past max_position_embeddings,
        # where the embedding gather fills NaN — the pad K/V then plants
        # NaN in the scratch page and 0-weight * NaN poisons every row
        # whose table carries scratch filler.  Pad K/V is never
        # attended, so valid logits are bitwise unchanged.
        pos = jnp.where(valid, ctx[row] + offset, 0)
        head_rows = jnp.minimum(
            starts.reshape(starts.shape + (1,) * (head_offset.ndim - 1))
            + head_offset, T - 1)

        def model():
            return engine._model_step(params, ids[None], pos[None], None,
                                      caches, head_rows=head_rows)

        def counted_model():
            """-> (logits, caches, (moe, counters)) under whichever
            side-channels this deployment's model feeds."""
            if not moe_stats:
                return (*model(), (None, {}))
            from .moe import stats as moe_stats_mod

            with moe_stats_mod.collect(valid, max_valid=T) as col:
                logits, caches = model()
            totals = col.totals()
            if col.dropless:
                kind = "dropless_identity" if col.identity else "dropless"
                return logits, caches, (kind, dict(zip(
                    DROPLESS_COUNTERS + IDENTITY_COUNTERS, totals)))
            return logits, caches, (totals[0].shape[0],
                                    dict(zip(CAPACITY_COUNTERS, totals)))

        # LoRA slots follow the axis: one per token, its row's
        with lora_slots_mod.activate(adapter_slots[row]):
            if not residual_stats:
                return counted_model()
            from ..nn import hyper_connections

            with hyper_connections.collect_stats(valid) as res:
                logits, caches, (moe, counters) = counted_model()
            counters = dict(counters, **dict(zip(
                (name for name, _ in RESIDUAL_COUNTERS), res.totals())))
            return logits, caches, (moe, counters)

    W = int(spec_window)
    in_layout = step_input_layout(max_batch, T, max_pages, W)

    def _packed_out(fields, counted):
        """The step's one host-bound output: the sampled fields and
        whatever the expert layers counted (``_model_step``'s ``(moe,
        counters)``), by ``step_output_layout``."""
        moe, counters = counted
        return step_output_layout(max_batch, W, moe, residual_stats).pack(
            {**fields, **counters})

    def run(params, packed, *mask_and_pools):
        # positional tail: the mask of a grammar deployment, then the pools
        *gmask, k_pages, v_pages = mask_and_pools
        f = in_layout.unpack(packed)
        qlens, ctx, steps0, sample_now = (
            f["qlens"], f["ctx"], f["steps0"], f["sample_now"])
        samp = {name: f[name] for name, _ in SAMP_FIELDS}
        caches = _layer_caches(engine, k_pages, v_pages, f["tables"], ctx,
                               qlens, f["scratch"])
        last, caches, counted = _model_step(
            params, f["ids"], qlens, ctx, f["adapter_slots"], caches,
            jnp.maximum(qlens - 1, 0))
        # one scope with the head (models/llama.py): the sampling tail
        with jax.named_scope("lm_head_sample"):
            if grammar:
                last = last + gmask[0]
            draws, filters = sampling_rows(samp, sample_now)
            proc = _process_rows(last, samp, steps0, filters)
            tok = _pick_rows(proc, samp, steps0, f["keys"], draws)
            tok = jnp.where(sample_now, tok, samp["pad"])
            fin = jnp.logical_and(
                sample_now,
                jnp.logical_and(samp["eos"] >= 0, tok == samp["eos"]))
        return (_packed_out(dict(tok=tok, fin=fin), counted),
                *_layer_pools(engine, caches))

    def run_spec(params, packed, *mask_and_pools):
        from ..inference import spec_accept

        *gmask, k_pages, v_pages = mask_and_pools
        f = in_layout.unpack(packed)
        ids, qlens, ctx, steps0, sample_now, spec, keys = (
            f["ids"], f["qlens"], f["ctx"], f["steps0"], f["sample_now"],
            f["spec"], f["keys"])
        samp = {name: f[name] for name, _ in SAMP_FIELDS}
        b = qlens.shape[0]
        spec2d = jnp.broadcast_to(spec[:, None], (b, W))
        caches = _layer_caches(engine, k_pages, v_pages, f["tables"], ctx,
                               qlens, f["scratch"], spec2d)
        # per-window-position logits: spec rows read positions 0..W-1
        # (clamped to their qlen), plain rows replicate qlens-1 so
        # their column 0 is exactly the non-spec gather
        base = jnp.maximum(qlens - 1, 0)                       # [b]
        j = jnp.arange(W, dtype=jnp.int32)[None]               # [1, W]
        gidx = jnp.where(spec[:, None], jnp.minimum(j, base[:, None]),
                         base[:, None])                        # [b, W]
        lg_w, caches, counted = _model_step(
            params, ids, qlens, ctx, f["adapter_slots"], caches, gidx)
        if grammar:
            lg_w = lg_w + gmask[0]
        steps_w = steps0[:, None] + jnp.where(spec[:, None], j, 0)
        # the tail's two predicates are the step's, not a lane's: taken
        # once out here, the conditionals stay conditionals under the vmap
        draws, filters = sampling_rows(samp, sample_now)
        proc_w = jax.vmap(
            lambda lg, st: _process_rows(lg, samp, st, filters),
            in_axes=(1, 1), out_axes=1)(lg_w, steps_w)         # [b, W, V]
        chosen_w = jax.vmap(
            lambda p, st: _pick_rows(p, samp, st, keys, draws),
            in_axes=(1, 1), out_axes=1)(proc_w, steps_w)       # [b, W]

        # drafts ride behind the row's first token, at ids[starts + 1
        # + j]; position j carries one only on spec rows with
        # j < qlens - 1
        starts = jnp.cumsum(qlens) - qlens
        drafts = ids[jnp.minimum(starts[:, None] + 1 + j[:, :W - 1],
                                 T - 1)]                       # [b, W-1]
        has_draft = jnp.logical_and(spec[:, None],
                                    j[:, :W - 1] < base[:, None])

        # greedy accept: draft matches the per-position argmax chain;
        # sampled accept (point-mass proposal): u < p_j(d_j) under the
        # row's processed distribution, u from the disjoint
        # fold_in(fold_in(base, step), 1) stream
        greedy_acc = drafts == chosen_w[:, :W - 1]
        p_w = jax.nn.softmax(proc_w[:, :W - 1], axis=-1)
        p_draft = jnp.take_along_axis(
            p_w, drafts[:, :, None], axis=2)[:, :, 0]          # [b, W-1]
        u = jax.vmap(jax.vmap(
            lambda k, st: jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(k, st), 1)),
            in_axes=(None, 0)))(keys, steps_w[:, :W - 1])
        samp_acc = spec_accept.rejection_accept(
            u, p_draft, jnp.ones_like(p_draft))
        acc = jnp.where(samp["do_sample"][:, None], samp_acc,
                        greedy_acc)
        acc = jnp.logical_and(acc, has_draft)
        a = spec_accept.accepted_prefix_len(acc)               # [b]

        # token at the cut: greedy correction / bonus / plain token all
        # reuse the chain's own choice at position a; a sampled
        # REJECTION instead resamples from the residual (processed
        # logits with the draft masked — exact for a point mass)
        proc_a = jnp.take_along_axis(
            proc_w, a[:, None, None], axis=1)[:, 0]            # [b, V]
        draft_a = jnp.take_along_axis(
            drafts, jnp.minimum(a, W - 2)[:, None], axis=1)[:, 0]
        resid = spec_accept.residual_logits_point_mass(proc_a, draft_a)
        rkeys = jax.vmap(
            lambda k, st: jax.random.fold_in(
                jax.random.fold_in(k, st), 2))(keys, steps0 + a)
        resample = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(
                rkeys, resid).astype(jnp.int32)
        chain_a = jnp.take_along_axis(chosen_w, a[:, None], axis=1)[:, 0]
        rejected = jnp.logical_and(
            samp["do_sample"],
            jnp.logical_and(spec, a < base))                   # [b]
        pick = jnp.where(rejected, resample, chain_a)

        # window emit: accepted drafts, then the cut token, truncated
        # at the row's first eos
        jf = jnp.arange(W, dtype=jnp.int32)[None]              # [1, W]
        drafts_full = jnp.pad(drafts, ((0, 0), (0, 1)))        # [b, W]
        pad = samp["pad"][:, None]
        out = jnp.where(jf < a[:, None], drafts_full,
                        jnp.where(jf == a[:, None], pick[:, None], pad))
        r = a + 1
        is_eos = jnp.logical_and(
            jnp.logical_and(samp["eos"][:, None] >= 0,
                            out == samp["eos"][:, None]),
            jf < r[:, None])
        any_eos = jnp.any(is_eos, axis=1)
        r = jnp.where(any_eos, jnp.argmax(is_eos, axis=1) + 1, r)
        out = jnp.where(
            jnp.logical_and(sample_now[:, None], jf < r[:, None]),
            out, pad).astype(jnp.int32)
        n_emit = jnp.where(sample_now, r, 0).astype(jnp.int32)
        fin = jnp.logical_and(sample_now, any_eos)
        return (_packed_out(dict(tok=out, fin=fin, n_emit=n_emit), counted),
                *_layer_pools(engine, caches))

    # the pools sit behind the mask slot, which only a grammar
    # deployment's signature has
    pools = 2 + int(bool(grammar))
    return jax.jit(run_spec if W > 1 else run,
                   donate_argnums=(pools, pools + 1))


def build_page_copy(engine):
    """Copy one physical page across every layer's pools (the
    copy-on-write step for a shared partial tail block):
    ``run(params, src[1], dst[1], k_pages, v_pages)`` →
    ``(src, k_pages, v_pages)``; pools are donated.  One executable per
    pool shape, reused for every CoW.  Quantized pools copy the page's
    scale row along with its payload — the copy stays bitwise.  A page
    is the pool's leading index in every cache kind (inference/
    cache_layout.py), so a latent layer's one pool copies the same way."""
    def copy(pages, src, dst):
        if pages is None:           # a one-pool layer's second entry
            return None
        if isinstance(pages, tuple):
            payload, scales = pages
            return (payload.at[dst].set(payload[src]),
                    scales.at[dst].set(scales[src]))
        return pages.at[dst].set(pages[src])

    def run(params, src, dst, k_pages, v_pages):
        k_pages = [copy(kp, src[0], dst[0]) for kp in k_pages]
        v_pages = [copy(vp, src[0], dst[0]) for vp in v_pages]
        return (src, k_pages, v_pages)

    return jax.jit(run, donate_argnums=(3, 4))
