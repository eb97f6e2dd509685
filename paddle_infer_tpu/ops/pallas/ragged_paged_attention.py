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

Two implementations share the public entry point:

* ``_ragged_reference`` (the default) — the exactness path the serving
  engine runs.  Chunk positions go through the dense constant-window
  ``prefix_prefill_attention`` math and decode rows (``query_len == 1``)
  through the ``paged_attention_decode`` kernel — i.e. PRECISELY the two
  computations the legacy per-program serving path ran, selected per
  row.  That is what makes mixed-step logits bitwise-identical to the
  legacy cold prefill + fused decode path on every backend (PR 4's
  constant-window argument extends row-wise: masked slots contribute
  exactly zero and the reduce shapes are per-core constants).
* ``_ragged_kernel_call`` (``use_kernel=True``) — the single-launch
  Pallas kernel: grid ``(batch, max_pages)`` with the page walk
  innermost, block tables and per-row lengths in scalar-prefetch SMEM,
  online-softmax state in VMEM scratch.  One kernel launch covers every
  row type; decode rows simply have a one-row query block.  Numerically
  it is an online-softmax reassociation of the reference (allclose, not
  bitwise), so serving keeps it opt-in until TPU parity runs pin it.

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
from .paged_attention import (NEG_INF, _page_scales, _quantized_scatter,
                              _scale_operands, _write_token_spans,
                              is_quantized,
                              paged_attention_decode,
                              paged_attention_verify,
                              prefix_prefill_attention)


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


def _ragged_reference(q, k_pages, v_pages, block_tables, context_lens,
                      query_lens, scale=None, verify_rows=None,
                      verify_window=None):
    """Per-row-type exact composition (see module docstring): the row's
    first query position is replaced by the decode kernel's output when
    ``query_lens == 1``, all other positions keep the dense
    constant-window prefix math.  Positions ``i >= query_lens`` hold
    garbage the caller must never read (it samples at
    ``query_lens - 1``).

    ``verify_rows`` [B] bool marks speculative draft/verify rows: a
    verify row carries ``query_lens = k + 1`` tokens (last emitted +
    ``k`` drafts) whose first ``verify_window`` positions each go
    through DECODE-kernel math at their own length — position ``j``
    attends exactly the window ``context_lens + j + 1`` a sequential
    decode step would have seen, over KV ``write_ragged_pages`` just
    scattered.  K/V at a position is a function of (token, position)
    only, so every verify lane reproduces the sequential step's inputs
    bit-for-bit and the verify logits are bitwise equal to the
    non-speculative stream — the greedy-parity guarantee.  The lanes
    ride ``paged_attention_verify``: ONE page walk per row (the decode
    kernel per lane) rather than a ``B*W``-row flattened launch."""
    # scope "paged_attention" names the composition's XLA operations
    # (the pool gathers and the f32 window attention).  The Pallas calls
    # stay outside it: the TPU compiler names a Mosaic custom call after
    # its innermost scope, and the benchmark's breakdown keys on that
    # instruction name
    with jax.named_scope("paged_attention"):
        out = prefix_prefill_attention(q, k_pages, v_pages, block_tables,
                                       context_lens, scale=scale)
    dec = paged_attention_decode(q[:, 0], k_pages, v_pages, block_tables,
                                 context_lens + 1, scale=scale)
    with jax.named_scope("paged_attention"):
        is_decode = (query_lens == 1)[:, None, None]
        first = jnp.where(is_decode, dec, out[:, 0])
        out = out.at[:, 0].set(first)
    if verify_rows is None:
        return out
    w = int(verify_window)
    # one W-lane decode-kernel launch covers every (row, position) pair
    # in a SINGLE page walk per row (paged_attention_verify lane (b, j)
    # is bitwise paged_attention_decode at ctx + j + 1); clamping keeps
    # non-verify / short rows inside their valid KV (lanes discarded)
    j = jnp.arange(w, dtype=jnp.int32)[None]                  # [1, W]
    ctxv = context_lens[:, None] + j + 1                      # [B, W]
    ctxv = jnp.minimum(ctxv, (context_lens
                              + jnp.maximum(query_lens, 1))[:, None])
    decv = paged_attention_verify(q[:, :w], k_pages, v_pages,
                                  block_tables, ctxv, scale=scale)
    with jax.named_scope("paged_attention"):
        sel = verify_rows[:, None, None, None]
        return out.at[:, :w].set(jnp.where(sel, decv, out[:, :w]))


# ------------------------------------------------------------------ kernel

def _ragged_kernel(ctx_ref, qlen_ref, tables_ref,    # scalar prefetch
                   q_ref, k_ref, v_ref,              # blocks (VMEM)
                   *rest,                            # [ks, vs,] o + scratch
                   scale, page_size, max_pages, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    qlen = qlen_ref[b]

    # the row's window after this step's writes is ctx + qlen tokens;
    # pages past it (and whole rows with qlen == 0) are skipped — the
    # ragged win: the DMA walk stops at the row's own length
    @pl.when(jnp.logical_and(qlen > 0, j * page_size < ctx + qlen))
    def _():
        # a chunk row has C queries per head, so both contractions are
        # head-batched MXU matmuls; the broadcast-multiply form the
        # single-query decode kernel uses would need a [C, H, page, D]
        # intermediate (16 MB at C=64, H=32, D=128 — over scoped VMEM)
        q = q_ref[0].astype(jnp.float32)             # [H, C, D]
        k = k_ref[0].astype(jnp.float32)             # [H, page, D]
        v = v_ref[0].astype(jnp.float32)             # [H, page, D]
        # scores for every (head, query, slot): [H, C, page]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if quantized:
            ks, vs = _page_scales(ks_ref, vs_ref, j)  # [H, 1]
            s = s * ks[:, :, None]
        # absolute-position causal mask: slot w visible to query i when
        # w <= ctx + i (the same predicate the reference path uses)
        slot = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qpos = ctx + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot <= qpos, s, NEG_INF)

        m_prev = m_ref[:]                            # [H, C, 1]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [H, C, page]
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # [H, C, D]
        if quantized:
            pv = pv * vs[:, :, None]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new
        l_ref[:] = l_new

    @pl.when(j == max_pages - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-20)             # [H, C, 1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _ragged_kernel_call(q, k_pages, v_pages, block_tables, context_lens,
                        query_lens, scale=None, interpret=None):
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

    def q_map(b_, j_, ctx_s, qlen_s, tables_s):
        return (b_, 0, 0, 0)

    def kv_map(b_, j_, ctx_s, qlen_s, tables_s):
        return (tables_s[b_, j_], 0, 0, 0)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, page_size=page_size,
        max_pages=max_pages, quantized=quantized)
    # head-major like the pool: the kernel's matmuls batch over heads,
    # and the [B, C, H, D] <-> [B, H, C, D] swap is one XLA transpose on
    # each side of the launch instead of a relayout per page inside it
    in_specs = [
        pl.BlockSpec((1, h, c, d), q_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
    ]
    operands = [jnp.transpose(q, (0, 2, 1, 3)), k_pages, v_pages]
    if quantized:
        specs, ops = _scale_operands(k_scales, v_scales, block_tables)
        in_specs += specs
        operands += ops
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, c, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, c, 1), jnp.float32),
            pltpu.VMEM((h, c, 1), jnp.float32),
            pltpu.VMEM((h, c, d), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ragged_paged_attention",
    )
    out = fn(context_lens, query_lens, block_tables, *operands)
    return jnp.transpose(out, (0, 2, 1, 3))


def ragged_paged_attention(q, k_pages, v_pages, block_tables,
                           context_lens, query_lens, scale=None,
                           use_kernel=False, interpret=None,
                           verify_rows=None, verify_window=None):
    """Mixed-batch ragged attention over paged KV.

    q            [B, C, H, D]   — per-row query chunk (C = capacity;
                                  row b uses positions 0..query_lens[b])
    k_pages      [P, H, page, D] — shared head-major pool
    v_pages      [P, H, page, D]
    block_tables [B, max_pages] int32
    context_lens [B] int32      — tokens already cached per row
    query_lens   [B] int32      — 1 = decode, >1 = prefill chunk,
                                  0 = inactive row
    verify_rows  [B] bool       — optional: speculative verify rows
                                  whose first ``verify_window`` (static
                                  int) positions take per-position
                                  decode-kernel math (see
                                  ``_ragged_reference``)
    → [B, C, H, D]; positions past ``query_lens`` hold garbage.

    ``use_kernel=False`` (default) runs the bitwise-exact reference
    composition the serving engine's parity guarantee rests on;
    ``use_kernel=True`` runs the single-launch Pallas kernel (allclose
    to the reference — the TPU fast path)."""
    if use_kernel:
        if verify_rows is not None:
            raise NotImplementedError(
                "speculative verify rows require the reference "
                "composition (per-position decode-kernel parity)")
        return _ragged_kernel_call(q, k_pages, v_pages, block_tables,
                                   context_lens, query_lens, scale=scale,
                                   interpret=interpret)
    return _ragged_reference(q, k_pages, v_pages, block_tables,
                             context_lens, query_lens, scale=scale,
                             verify_rows=verify_rows,
                             verify_window=verify_window)
