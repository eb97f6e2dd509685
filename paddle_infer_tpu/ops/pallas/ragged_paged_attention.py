"""Ragged mixed-batch paged attention (cf. PAPERS.md "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for
TPU").

One launch serves a batch where every row carries its own
``(query_len, context_len, block_table_row)``: decode rows have
``query_len == 1``, prefill rows carry a token chunk, inactive rows
carry ``query_len == 0``.  Each row's queries sit at absolute positions
``context_len + i`` and attend over the row's paged KV window under an
absolute-position causal mask — so there is no prompt bucketing and no
per-plen executable: the executable shape depends only on
``(batch, query_capacity, max_pages)``.

``ragged_paged_attention`` is what every served mixed step runs: ONE
Pallas launch on a ``(batch, pages)`` grid with the page walk
innermost and as long as the longest live row's window (a dynamic grid
bound, at most the table's ``max_pages``), block tables and per-row
lengths in scalar-prefetch SMEM and online-softmax state in VMEM
scratch.  Its work follows each row's real ``(query_len,
context_len)``: a row with ``query_len == 0`` and every page past
``context_len + query_len`` does nothing and moves nothing (skipped
grid steps repeat the row's last live page index, so no copy is issued
for them); a row with ``query_len == 1`` takes the decode
kernel's one-query page step on the VPU (``paged_attention.
_decode_page_step``, the same arithmetic as ``paged_attention_decode``);
a row with ``query_len > 1`` takes head-batched MXU contractions of its
``[H, C, D]`` queries against each live page.  Which body a row takes is
read from ``query_lens`` inside the kernel.  K and V are read as stored
(bf16, or int8 times the page's scale); scores, the running max and sum
and the PV accumulator are float32.

A query's output is a function of its own row of Q and of the bytes of
the pages up to its position: slots and pages past its causal horizon
contribute ``exp(-inf) = 0`` with ``alpha = 1`` exactly, and the rows of
a matmul do not see each other.  So a position computed in one chunk, in
two, after a warm prefix hit, on replay or after park/resume re-runs the
same arithmetic on the same bytes (tests/test_ragged_serving.py holds
the kernel to that bitwise).  It is NOT the arithmetic of the offline
``generate()`` programs (SDPA prefill) nor of the dense windowed
``prefix_prefill_attention``; against those and against the float32
reference it is close, not equal.

Speculative verify rows (``verify_rows``) keep their contract: the first
``verify_window`` positions of flagged rows are overlaid with
``paged_attention_verify``'s lanes, each bitwise a sequential decode
step.  Under an active mesh the launch runs under ``shard_map`` with
heads over ``mp`` and batch over ``dp`` where they divide.

``_ragged_reference`` is the plain composition the kernel's tests and
``chip_smoke.py`` compare it with (dense constant-window
``prefix_prefill_attention`` for chunk positions, the decode kernel for
``query_len == 1`` rows); nothing served runs it.

``write_ragged_pages`` is the matching writer: valid positions
(``i < query_len``) land at the row's absolute slots, everything else
is written nowhere (on an int8 pool the pads re-seed only the scale of
the scratch page, which no live row ever reads).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from .paged_attention import (NEG_INF, _current_mesh, _decode_page_step,
                              _page_scales, _quantized_scatter,
                              _scale_operands, _write_token_spans,
                              is_quantized,
                              paged_attention_decode,
                              paged_attention_verify,
                              prefix_prefill_attention)


def ragged_rows(query_lens, tokens: int):
    """Where a step's rows lie on its flat token axis of ``tokens``
    slots: row ``b``'s ``query_lens[b]`` tokens follow row ``b - 1``'s,
    the tail is padding.  Returns ``(starts [B], row [T], offset [T],
    valid [T])``: each row's first slot (the exclusive cumulative sum of
    ``query_lens``), and for every slot the row it belongs to, its index
    inside that row, and whether it holds a token at all.  A pad slot
    reads ``valid`` False with ``row`` and ``offset`` clamped into range,
    so it can index anything a real slot can."""
    query_lens = query_lens.astype(jnp.int32)
    ends = jnp.cumsum(query_lens)
    starts = ends - query_lens
    t = jnp.arange(tokens, dtype=jnp.int32)
    # rows whose span ends at or before t (a row of length 0 ends where
    # it starts, so it is stepped over)
    row = jnp.sum((t[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    row = jnp.minimum(row, query_lens.shape[0] - 1)
    valid = t < ends[-1]
    offset = jnp.where(valid, t - starts[row], 0)
    return starts, row, offset, valid


def rows_from_flat(x, starts, chunk: int):
    """The per-row view ``[B, chunk, ...]`` of a flat ``[T, ...]`` token
    axis: row ``b`` holds slots ``starts[b] + i``.  Positions past a
    row's ``query_len`` hold other rows' tokens (or the last slot's);
    the writers store them nowhere and the kernels never read them.
    Per-row results ``y [B, chunk, ...]`` go back on the flat axis as
    ``y[row, offset]`` (``ragged_rows``)."""
    i = jnp.arange(chunk, dtype=jnp.int32)[None]
    return x[jnp.minimum(starts[:, None] + i, x.shape[0] - 1)]


def write_ragged_pages(pages, block_tables, kv, context_lens, query_lens,
                       scratch_page):
    """Write a ragged batch's K or V ``[B, C, H, D]`` into the
    head-major pool, page by page (``_write_token_spans``).  Row ``b``'s
    token ``i`` lands at absolute position ``context_lens[b] + i`` when
    ``i < query_lens[b]``; pad positions (``i >= query_lens[b]``,
    including whole inactive rows) are written nowhere, so rows near the
    window edge can never clamp into their own live pages.  On an int8
    pool the pads still count as landing on ``scratch_page`` for the
    scale protocol (below).  The caller guarantees
    ``context_lens + query_lens`` stays inside each row's reserved
    table window."""
    if not is_quantized(pages):
        return _write_token_spans(pages, block_tables,
                                  kv.astype(pages.dtype), context_lens,
                                  query_lens)
    c = kv.shape[1]
    page = pages[0].shape[2]
    max_pages = block_tables.shape[1]
    i = jnp.arange(c, dtype=jnp.int32)[None]                 # [1, C]
    pos = context_lens[:, None] + i                          # [B, C]
    valid = i < query_lens[:, None]
    safe_pos = jnp.where(valid, pos, 0)
    page_idx = jnp.take_along_axis(
        block_tables, jnp.clip(safe_pos // page, 0, max_pages - 1), axis=1)
    page_idx = jnp.where(valid, page_idx,
                         jnp.asarray(scratch_page, jnp.int32))
    slot = jnp.where(valid, safe_pos % page, i % page)
    # pad tokens landing at scratch slot 0 only re-seed the scratch
    # page's scale (deterministically — masked max), which no live row
    # ever reads; their payload is dropped
    return _quantized_scatter(pages, page_idx, slot, kv, block_tables,
                              context_lens, query_lens)


def _overlay_verify_lanes(out, q, k_pages, v_pages, block_tables,
                          context_lens, query_lens, scale, verify_rows,
                          verify_window):
    """Replace the first ``verify_window`` positions of the rows flagged
    in ``verify_rows`` [B] bool with decode-kernel math at each
    position's own length.

    A verify row carries ``query_lens = k + 1`` tokens (last emitted +
    ``k`` drafts); position ``j`` attends exactly the window
    ``context_lens + j + 1`` a sequential decode step would have seen,
    over KV ``write_ragged_pages`` just scattered.  K/V at a position is
    a function of (token, position) only, so every verify lane
    reproduces the sequential step's inputs bit-for-bit and the verify
    logits are bitwise equal to the non-speculative stream — the
    greedy-parity guarantee.  The lanes ride ``paged_attention_verify``:
    ONE page walk per row (the decode kernel per lane) rather than a
    ``B*W``-row flattened launch."""
    w = int(verify_window)
    # clamping keeps non-verify / short rows inside their valid KV
    # (lanes discarded)
    j = jnp.arange(w, dtype=jnp.int32)[None]                  # [1, W]
    ctxv = context_lens[:, None] + j + 1                      # [B, W]
    ctxv = jnp.minimum(ctxv, (context_lens
                              + jnp.maximum(query_lens, 1))[:, None])
    decv = paged_attention_verify(q[:, :w], k_pages, v_pages,
                                  block_tables, ctxv, scale=scale)
    with jax.named_scope("paged_attention"):
        sel = verify_rows[:, None, None, None]
        return out.at[:, :w].set(jnp.where(sel, decv, out[:, :w]))


def _ragged_reference(q, k_pages, v_pages, block_tables, context_lens,
                      query_lens, scale=None, verify_rows=None,
                      verify_window=None):
    """The plain composition the kernel is tested against (module
    docstring): every position through the dense constant-window prefix
    math, the row's first position replaced by the decode kernel's
    output when ``query_lens == 1``, verify lanes overlaid as the served
    entry overlays them.  Positions ``i >= query_lens`` hold garbage."""
    out = prefix_prefill_attention(q, k_pages, v_pages, block_tables,
                                   context_lens, scale=scale)
    dec = paged_attention_decode(q[:, 0], k_pages, v_pages, block_tables,
                                 context_lens + 1, scale=scale)
    is_decode = (query_lens == 1)[:, None, None]
    out = out.at[:, 0].set(jnp.where(is_decode, dec, out[:, 0]))
    if verify_rows is None:
        return out
    return _overlay_verify_lanes(out, q, k_pages, v_pages, block_tables,
                                 context_lens, query_lens, scale,
                                 verify_rows, verify_window)


# ------------------------------------------------------------------ kernel

def _ragged_kernel(ctx_ref, qlen_ref, tables_ref,    # scalar prefetch
                   q_ref, q0_ref, k_ref, v_ref,      # blocks (VMEM)
                   *rest,                      # [ks, vs,] o, o0 + scratch
                   scale, page_size, quantized=False):
    if quantized:
        ks_ref, vs_ref, *rest = rest
    o_ref, o0_ref, m_ref, l_ref, acc_ref, m0_ref, l0_ref, acc0_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    ctx = ctx_ref[b]
    qlen = qlen_ref[b]
    is_chunk = qlen > 1
    is_decode = qlen == 1
    # the row's window after this step's writes is ctx + qlen tokens;
    # pages past it (and whole rows with qlen == 0) are skipped: the
    # page walk stops at the row's own length
    live = j * page_size < ctx + qlen
    first = j == 0
    last = j == pl.num_programs(1) - 1

    def both(x, y):
        return jnp.logical_and(x, y)

    def start(on, m, l, acc):
        @pl.when(both(first, on))
        def _():
            m[:] = jnp.full_like(m, NEG_INF)
            l[:] = jnp.zeros_like(l)
            acc[:] = jnp.zeros_like(acc)

    def finish(on, o, l, acc):
        # a row the body did not serve stores zeros: what lies past a
        # row's query_lens flows on through the layer's dense slots
        @pl.when(both(last, on))
        def _():
            o[0] = (acc[:] / jnp.maximum(l[:], 1e-20)).astype(o.dtype)

        @pl.when(both(last, jnp.logical_not(on)))
        def _():
            o[0] = jnp.zeros(o.shape[1:], o.dtype)

    start(is_chunk, m_ref, l_ref, acc_ref)
    start(is_decode, m0_ref, l0_ref, acc0_ref)

    @pl.when(both(live, is_chunk))
    def _():
        # a chunk row has C queries per head, so both contractions are
        # head-batched MXU matmuls; the broadcast-multiply form of the
        # one-query body would need a [C, H, page, D] intermediate
        # (16 MB at C=64, H=32, D=128: over scoped VMEM)
        q, k = q_ref[0], k_ref[0]            # [H, C, D], [H, page, D]
        # bf16 (or int8) keys against bf16 queries go to the MXU as
        # bf16 in one pass: a product of two bf16 numbers is exact in
        # float32, so this is the float32 contraction of the reference;
        # anything wider is contracted in full float32.  Both precisions
        # are stated: the caller's ambient matmul precision must neither
        # round float32 operands nor ask Mosaic for a float32
        # contraction of bf16 ones (which it refuses)
        narrow = (q.dtype == jnp.bfloat16
                  and k.dtype in (jnp.bfloat16, jnp.int8))
        mxu = jnp.bfloat16 if narrow else jnp.float32
        s = jax.lax.dot_general(
            q.astype(mxu), k.astype(mxu), (((2,), (2,)), ((0,), (0,))),
            precision=(jax.lax.Precision.DEFAULT if narrow
                       else jax.lax.Precision.HIGHEST),
            preferred_element_type=jnp.float32) * scale   # [H, C, page]
        if quantized:
            ks, vs = _page_scales(ks_ref, vs_ref, j)      # [H, 1]
            s = s * ks[:, :, None]
        # absolute-position causal mask: slot w visible to query i when
        # w <= ctx + i (the same predicate the reference uses)
        slot = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qpos = ctx + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot <= qpos, s, NEG_INF)

        m_prev = m_ref[:]                            # [H, C, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)                       # [H, C, page]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        # probabilities stay float32 (as the reference's), so PV is a
        # full float32 contraction
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)      # [H, C, D]
        if quantized:
            pv = pv * vs[:, :, None]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(both(live, is_decode))
    def _():
        # one query a head: the decode kernel's page step on the VPU
        # (paged_attention._decode_kernel), not a [H, C, D] matmul for
        # one real row of it
        _decode_page_step(
            q0_ref, k_ref, v_ref, (ks_ref, vs_ref) if quantized else None,
            j, ctx + 1, m0_ref, l0_ref, acc0_ref, scale, page_size)

    finish(is_chunk, o_ref, l_ref, acc_ref)
    finish(is_decode, o0_ref, l0_ref, acc0_ref)


def _ragged_local(q, k_pages, v_pages, block_tables, context_lens,
                  query_lens, scale=None, interpret=None):
    """The single-shard launch (see ``ragged_paged_attention``)."""
    interpret = _interpret() if interpret is None else interpret
    quantized = is_quantized(k_pages)
    if quantized:
        k_pages, k_scales = k_pages
        v_pages, v_scales = v_pages
    b, c, h, d = q.shape
    num_pages, kh, page_size, kd = k_pages.shape
    assert (kh, kd) == (h, d), (k_pages.shape, q.shape)
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    context_lens = context_lens.astype(jnp.int32)
    query_lens = query_lens.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def row_map(b_, j_, ctx_s, qlen_s, tables_s):
        return (b_, 0, 0, 0)

    def row0_map(b_, j_, ctx_s, qlen_s, tables_s):
        return (b_, 0, 0)

    def kv_map(b_, j_, ctx_s, qlen_s, tables_s):
        # steps past the row's window repeat its last live page, and the
        # pipeline issues no copy for a block index that did not change
        live = jnp.maximum(ctx_s[b_] + qlen_s[b_] - 1, 0) // page_size
        return (tables_s[b_, jnp.minimum(j_, live)], 0, 0, 0)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, page_size=page_size,
        quantized=quantized)
    # the page walk is as long as the longest live row's window, not
    # the table: a dynamic bound of the grid's inner dimension (a grid
    # step costs its ~0.2 us of index arithmetic live or skipped)
    window = jnp.where(query_lens > 0, context_lens + query_lens, 0)
    walk = jnp.clip(-(-jnp.max(window) // page_size), 1, max_pages)
    # head-major like the pool: the chunk body's matmuls batch over
    # heads, and the [B, C, H, D] <-> [B, H, C, D] swap is one XLA
    # transpose on each side of the launch instead of a relayout per
    # page inside it.  Scope "paged_attention" names those swaps; the
    # Pallas call stays outside it: the TPU compiler names a Mosaic
    # custom call after its innermost scope, and the benchmark's
    # breakdown keys on the kernel's own name
    with jax.named_scope("paged_attention"):
        qh = jnp.transpose(q, (0, 2, 1, 3))
        q0 = q[:, 0]
    in_specs = [
        pl.BlockSpec((1, h, c, d), row_map),
        pl.BlockSpec((1, h, d), row0_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
    ]
    operands = [qh, q0, k_pages, v_pages]
    if quantized:
        specs, ops = _scale_operands(k_scales, v_scales, block_tables)
        in_specs += specs
        operands += ops
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, walk),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, h, c, d), row_map),
                   pl.BlockSpec((1, h, d), row0_map)],
        scratch_shapes=[
            pltpu.VMEM((h, c, 1), f32), pltpu.VMEM((h, c, 1), f32),
            pltpu.VMEM((h, c, d), f32),
            pltpu.VMEM((h, 1), f32), pltpu.VMEM((h, 1), f32),
            pltpu.VMEM((h, d), f32),
        ],
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, c, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, d), q.dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ragged_paged_attention",
    )
    out, out0 = fn(context_lens, query_lens, block_tables, *operands)
    with jax.named_scope("paged_attention"):
        out = jnp.transpose(out, (0, 2, 1, 3))
        is_decode = (query_lens == 1)[:, None, None]
        return out.at[:, 0].set(jnp.where(is_decode, out0, out[:, 0]))


def ragged_paged_attention(q, k_pages, v_pages, block_tables,
                           context_lens, query_lens, scale=None,
                           interpret=None, verify_rows=None,
                           verify_window=None):
    """Mixed-batch ragged attention over paged KV (module docstring).

    q            [B, C, H, D]   — per-row query chunk (C = capacity;
                                  row b uses positions 0..query_lens[b])
    k_pages      [P, H, page, D] — shared head-major pool, or an
                                  ``(int8 payload, [P, H] scales)`` pair
    v_pages      [P, H, page, D]
    block_tables [B, max_pages] int32
    context_lens [B] int32      — tokens already cached per row
    query_lens   [B] int32      — 1 = decode, >1 = prefill chunk,
                                  0 = inactive row
    verify_rows  [B] bool       — optional: speculative verify rows
                                  whose first ``verify_window`` (static
                                  int) positions take per-position
                                  decode-kernel math (see
                                  ``_overlay_verify_lanes``)
    → [B, C, H, D]; positions past ``query_lens`` hold zeros or garbage.
    """
    inner = functools.partial(_ragged_local, scale=scale,
                              interpret=interpret)
    mesh = _current_mesh()
    bax = hax = None
    if mesh is not None:
        from ...parallel.topology import axis_if_divides

        bax = axis_if_divides(mesh, "dp", q.shape[0])
        hax = axis_if_divides(mesh, "mp", q.shape[2])
    if bax or hax:
        from jax.sharding import PartitionSpec as P

        from ...parallel.topology import shard_map_norep
        # heads are independent and the pool is head-major, so each
        # shard walks its local heads' pages (paged_attention_decode
        # says the same of its own launch)
        pspec = ((P(None, hax, None, None), P(None, hax))
                 if is_quantized(k_pages) else P(None, hax, None, None))
        inner = shard_map_norep(
            inner, mesh,
            in_specs=(P(bax, None, hax, None), pspec, pspec,
                      P(bax, None), P(bax), P(bax)),
            out_specs=P(bax, None, hax, None))
    out = inner(q, k_pages, v_pages, block_tables, context_lens,
                query_lens)
    if verify_rows is None:
        return out
    return _overlay_verify_lanes(out, q, k_pages, v_pages, block_tables,
                                 context_lens, query_lens, scale,
                                 verify_rows, verify_window)
