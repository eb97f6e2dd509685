"""Pallas TPU paged-attention decode kernel + paged KV cache.

The TPU answer to the reference's decode-attention path: the
fused_multi_transformer masked-multihead-attention reads a dense
[2, b, h, max_seq, d] CacheKV (fused_multi_transformer_op.cc:103) — dense
max-seq buffers waste HBM when sequence lengths vary.  Here KV lives in a
block pool ([num_pages, h, page_size, d], head-major so the kernel never
relayouts) indexed by per-sequence page tables (cf. PAPERS.md "Ragged Paged Attention ... for TPU"); the native-side
allocator (native/kv_allocator.cc) owns the tables.

Kernel design: grid (batch, max_pages_per_seq) with the page dimension
innermost; the page table and sequence lengths ride in scalar-prefetch SMEM
so each grid step's index_map picks the right physical page — the K/V DMA
streams exactly the pages the sequence owns, no gather materialisation.
Online-softmax state (m, l, acc) persists in VMEM scratch across the page
walk; heads are the row dimension of the in-kernel matmuls.  Decode is
HBM-bandwidth-bound, so the win is reading only ceil(len/page) pages per
sequence instead of max_seq rows.

On the CPU backend the tests run the same kernel through the Pallas
interpreter (``ops.pallas.interpret``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

NEG_INF = -1e30


# ------------------------------------------------------------ quantized KV
# A quantized pool is a plain ``(payload, scales)`` tuple — payload int8
# [P, H, page, D], scales float32 [P, H] (one per page per head).  The pair
# is a pytree, so program signatures, donation argnums and cache-tuple
# arities are unchanged; every page consumer below branches on
# ``is_quantized``.
#
# Scale protocol: a page's scale is set ONLY by the write landing in slot 0
# (the page's lowest position) — amax over that token's D, divided by 127,
# floored at KV_SCALE_EPS.  Every later write into the page quantizes with
# the inherited scale (clipping at ±127).  Slot 0 is the lowest position,
# so a slot-0 rewrite can only happen when no earlier token of the page is
# live — which makes the (scales, payload) bits a pure function of the
# token stream, independent of how writes were chunked.  That write-order
# invariance is what keeps warm prefix hits, speculative re-writes and
# fleet handoffs bitwise-identical in the quantized domain.

KV_SCALE_EPS = 1e-8
_QMAX = 127.0


def is_quantized(pages) -> bool:
    """True when ``pages`` is an (int8 payload, float32 scales) pair."""
    return isinstance(pages, (tuple, list)) and len(pages) == 2


def quantize_pages(pages):
    """fp pool [P, H, page, D] → (int8 payload, [P, H] scales) under the
    slot-0 scale protocol (offline/test construction of quantized pools;
    matches what the incremental writers below would have produced)."""
    f = pages.astype(jnp.float32)
    tok0 = jnp.abs(f[:, :, 0, :])                       # [P, H, D]
    scales = jnp.maximum(jnp.max(tok0, axis=-1) / _QMAX, KV_SCALE_EPS)
    payload = jnp.clip(jnp.round(f / scales[:, :, None, None]),
                       -_QMAX, _QMAX).astype(jnp.int8)
    return payload, scales


def dequantize_pages(pages):
    """(payload, scales) → float32 pool; fp pools pass through."""
    if not is_quantized(pages):
        return pages
    payload, scales = pages
    return payload.astype(jnp.float32) * scales[:, :, None, None]


def kv_dequant_error_bound(fp_pages, scales) -> float:
    """Worst-case elementwise |dequantize(quantize(x)) - x| over a pool,
    from the REALIZED per-(page, head) scales the slot-0 protocol chose:
    scale/2 covers rounding, plus the clipping excess wherever a
    non-slot-0 token exceeds the representable range ``_QMAX * scale``.
    Both inputs are host-side ([P, H, page, D] fp reference, [P, H]
    scales); analytic in the same sense as
    ``parallel.collective.quantization_error_bound`` — exact given the
    data, no fitted constants."""
    import numpy as np

    fp = np.asarray(fp_pages, np.float32)
    sc = np.asarray(scales, np.float32)[:, :, None, None]
    clip = np.maximum(np.abs(fp) - _QMAX * sc, 0.0)
    return float(np.max(sc / 2.0 + clip)) if fp.size else 0.0


def _quantized_scatter(pages, page_idx, slot, kv, block_tables, starts,
                       counts):
    """Shared int8 token write: slot-0 landings re-seed their page's
    scale from the landing token, everything quantizes with the updated
    scales, and the payload goes into the pool through
    ``_write_token_spans`` (row ``b``'s first ``counts[b]`` tokens at
    positions ``starts[b] + i``).  ``page_idx``/``slot`` are the
    per-token [B, S] int32 landing sites the scale protocol keys on
    (pads included, wherever the caller parks them) and ``kv`` is
    [B, S, H, D].

    The scale update is a masked-max scatter, NOT ``.set``: pad rows may
    alias a live physical page (table filler points at page 0 / the
    scratch page), and duplicate-index ``.set`` order is unspecified.
    Candidates are -1.0 except at genuine slot-0 landings; ``.at[].max``
    over the -1 sentinel is order-independent, and scales are > 0 by the
    eps floor, so surviving -1 means "keep the old scale"."""
    payload, scales = pages
    kvf = kv.astype(jnp.float32)
    tok = jnp.maximum(jnp.max(jnp.abs(kvf), axis=-1) / _QMAX,
                      KV_SCALE_EPS)                      # [..., H]
    cand = jnp.where((slot == 0)[..., None], tok, -1.0)
    fresh = jnp.full(scales.shape, -1.0, jnp.float32) \
        .at[page_idx].max(cand)
    scales = jnp.where(fresh > 0, fresh, scales)
    sc = scales[page_idx]                                # [..., H]
    q = jnp.clip(jnp.round(kvf / sc[..., None]), -_QMAX, _QMAX) \
        .astype(jnp.int8)
    return _write_token_spans(payload, block_tables, q, starts,
                              counts), scales


# ------------------------------------------------------------------ kernel

def _page_scales(ks_ref, vs_ref, j):
    """Column ``j`` of the row's gathered ``[1, H, max_pages]`` K/V scale
    blocks as ``[H, 1]``.  A masked lane reduction, not a dynamic lane
    slice: one live term plus zeros, so the pick is exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 1)
    pick = lambda ref: jnp.sum(jnp.where(lane == j, ref[0], 0.0),
                               axis=1, keepdims=True)
    return pick(ks_ref), pick(vs_ref)


def _scale_operands(k_scales, v_scales, block_tables):
    """(in_specs, operands) for a quantized launch: ``[P, H]`` pool scales
    gathered to the ``[B, H, max_pages]`` rows the launch walks.  A block
    of one page over the pool-wide array has a second-minor dim of 1,
    which the TPU lowering refuses; the gathered copy is a few KB and its
    per-row block is the full trailing dims, resident across the walk."""
    h, max_pages = k_scales.shape[1], block_tables.shape[1]
    spec = pl.BlockSpec((1, h, max_pages),
                        lambda b_, j_, *prefetch: (b_, 0, 0))
    rows = lambda scales: jnp.transpose(scales[block_tables], (0, 2, 1))
    return [spec, spec], [rows(k_scales), rows(v_scales)]


def _decode_page_step(q_ref, k_ref, v_ref, scale_refs, j, length,
                      m_ref, l_ref, acc_ref, scale, page_size):
    """One page of a one-query-per-head walk: the online-softmax update of
    ``(m, l, acc)`` ([H, 1], [H, 1], [H, D] scratch) with page ``j`` of a
    row whose window is ``length`` tokens.  ``scale_refs`` is the row's
    ``(ks_ref, vs_ref)`` scale blocks on an int8 pool, else None.  The
    decode kernel's body, and the ragged kernel's for its decode rows."""
    # Decode attention is HBM-bound, not FLOP-bound, so scores/weights
    # are broadcast-multiply + reductions (VPU).  The head-major page
    # layout keeps every intermediate in [H, page|D] orientation — no
    # cross-lane relayouts, which Mosaic can't lower for these shapes.
    q = q_ref[0].astype(jnp.float32)            # [H, D]
    k = k_ref[0].astype(jnp.float32)            # [H, page, D]
    v = v_ref[0].astype(jnp.float32)            # [H, page, D]
    # scores over this page's slots: [H, page]
    s = jnp.sum(q[:, None, :] * k, axis=2) * scale
    if scale_refs is not None:
        # per-(page, head) dequant: the int8 payload is what the DMA
        # streamed, and a page's scale is constant over its slots and
        # D, so it factors out of both reductions — [H, 1] against
        # [H, page] / [H, D], heads on sublanes throughout
        ks, vs = _page_scales(*scale_refs, j)
        s = s * ks
    # mask slots beyond the sequence length
    slot = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(slot < length, s, NEG_INF)

    m_prev = m_ref[:]                            # [H, 1]
    l_prev = l_ref[:]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                       # [H, page]
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    # weighted values: [H, D]
    pv = jnp.sum(p[:, :, None] * v, axis=1)
    if scale_refs is not None:
        pv = pv * vs
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = m_new
    l_ref[:] = l_new


def _decode_kernel(lengths_ref, tables_ref,      # scalar prefetch (SMEM)
                   q_ref, k_ref, v_ref,          # blocks (VMEM)
                   *rest,                        # [ks, vs,] o + scratch
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

    length = lengths_ref[b]

    @pl.when(j * page_size < length)
    def _():
        _decode_page_step(
            q_ref, k_ref, v_ref, (ks_ref, vs_ref) if quantized else None,
            j, length, m_ref, l_ref, acc_ref, scale, page_size)

    @pl.when(j == max_pages - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-20)             # [H, 1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_attention_decode(q, k_pages, v_pages, block_tables, lengths,
                           scale=None, interpret=None):
    """One decode step of attention over paged KV.

    q            [B, H, D]      — the new token's queries
    k_pages      [P, H, page, D] — the shared physical pool (head-major)
    v_pages      [P, H, page, D]
    block_tables [B, max_pages] int32 — per-sequence page ids (pad 0)
    lengths      [B] int32      — tokens already in cache (incl. current)
    → [B, H, D]

    Quantized pools: ``k_pages``/``v_pages`` may each be an
    ``(int8 payload, [P, H] float32 scales)`` pair — the kernel DMAs the
    int8 page plus its scale row and dequantizes per (page, head) on the
    VPU feed, halving the page bytes decode is bound by.

    Mesh-sharded serving: when a hybrid mesh with mp>1 is active (the
    engines set it — parallel.topology), the kernel runs under shard_map
    with heads split over "mp" and (when divisible) batch over "dp".
    Heads are independent in decode attention, so each shard walks its
    local heads' pages; the page pool is head-major precisely so this
    split never relayouts.  This is the multi-rank serving answer to the
    reference's DistModel/FleetExecutor
    (fluid/distributed/fleet_executor/dist_model.cc:1) — one SPMD program
    instead of per-rank executors passing messages.
    """
    mesh = _current_mesh()
    if mesh is not None:
        from ...parallel.topology import axis_if_divides

        bax = axis_if_divides(mesh, "dp", q.shape[0])
        hax = axis_if_divides(mesh, "mp", q.shape[1])
        if bax or hax:
            from jax.sharding import PartitionSpec as P

            from ...parallel.topology import shard_map_norep
            inner = functools.partial(_decode_local, scale=scale,
                                      interpret=interpret)
            # pair pools shard as a pytree: payload over heads like the
            # fp pool, the [P, H] scale row over the same head axis
            pspec = ((P(None, hax, None, None), P(None, hax))
                     if is_quantized(k_pages)
                     else P(None, hax, None, None))
            return shard_map_norep(
                inner, mesh,
                in_specs=(P(bax, hax, None), pspec, pspec,
                          P(bax, None), P(bax)),
                out_specs=P(bax, hax, None),
            )(q, k_pages, v_pages, block_tables, lengths)
    return _decode_local(q, k_pages, v_pages, block_tables, lengths,
                         scale=scale, interpret=interpret)


def _current_mesh():
    from ...parallel import topology

    return topology.get_current_mesh()


def _decode_local(q, k_pages, v_pages, block_tables, lengths,
                  scale=None, interpret=None):
    """The single-shard kernel launch (see paged_attention_decode)."""
    interpret = _interpret() if interpret is None else interpret
    quantized = is_quantized(k_pages)
    if quantized:
        k_pages, k_scales = k_pages
        v_pages, v_scales = v_pages
    b, h, d = q.shape
    num_pages, kh, page_size, kd = k_pages.shape
    assert (kh, kd) == (h, d), (k_pages.shape, q.shape)
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_map(b_, j_, lengths_s, tables_s):
        return (b_, 0, 0)

    def kv_map(b_, j_, lengths_s, tables_s):
        return (tables_s[b_, j_], 0, 0, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=page_size,
        max_pages=max_pages, quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, h, d), q_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        specs, ops = _scale_operands(k_scales, v_scales, block_tables)
        in_specs += specs
        operands += ops
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )
    return fn(lengths, block_tables, *operands)


def _verify_kernel(lengths_ref, tables_ref,      # scalar prefetch (SMEM)
                   q_ref, k_ref, v_ref,          # blocks (VMEM)
                   *rest,                        # [ks, vs,] o + scratch
                   scale, page_size, max_pages, window, quantized=False):
    """W-query decode: ``_decode_kernel`` with an extra leading query
    lane.  Each lane ``w`` masks by its OWN length ``lengths[b, w]``;
    the per-page online-softmax update is the decode kernel's math per
    lane, so lane ``w`` accumulates bit-for-bit what a separate
    single-query launch at ``lengths[b, w]`` would have — one page walk
    per row instead of one per (row, position)."""
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

    # lane lengths are nondecreasing over w (position j attends
    # ctx + j + 1, clamped by a bound that is itself nondecreasing), so
    # the last lane gates the page walk for the whole row.  Pages past
    # a SHORTER lane's length are an exact no-op for that lane: the
    # masked page contributes m_cur = NEG_INF, alpha = 1, p = 0, which
    # leaves (m, l, acc) bitwise untouched — the same identity the
    # single-query kernel's own gate relies on.
    last = lengths_ref[b, window - 1]

    @pl.when(j * page_size < last)
    def _():
        q = q_ref[0].astype(jnp.float32)            # [W, H, D]
        k = k_ref[0].astype(jnp.float32)            # [H, page, D]
        v = v_ref[0].astype(jnp.float32)            # [H, page, D]
        # scores over this page's slots, per lane: [W, H, page]
        s = jnp.sum(q[:, :, None, :] * k[None], axis=3) * scale
        if quantized:
            # same post-reduction per-(page, head) dequant as the decode
            # kernel — lane (b, w) stays bitwise a single-query
            # quantized decode
            ks, vs = _page_scales(ks_ref, vs_ref, j)
            s = s * ks[None]
        slot = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        # lane lengths are SMEM scalars: one scalar load per lane,
        # selected onto the lane's major index (SMEM has no vector loads)
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        lens = jnp.zeros(s.shape, jnp.int32)
        for w in range(window):
            lens = jnp.where(lane == w, lengths_ref[b, w], lens)
        s = jnp.where(slot < lens, s, NEG_INF)

        m_prev = m_ref[:]                            # [W, H, 1]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [W, H, page]
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jnp.sum(p[:, :, :, None] * v[None], axis=2)   # [W, H, D]
        if quantized:
            pv = pv * vs[None]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new
        l_ref[:] = l_new

    @pl.when(j == max_pages - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-20)             # [W, H, 1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_attention_verify(q, k_pages, v_pages, block_tables, lengths,
                           scale=None, interpret=None):
    """Batched draft/verify decode attention over paged KV.

    q            [B, W, H, D]   — W query positions per row (last
                                  emitted token + W-1 drafts)
    lengths      [B, W] int32   — per-position window, nondecreasing
                                  over W (position j sees ctx + j + 1)
    → [B, W, H, D]

    Lane (b, w) is bitwise-identical to
    ``paged_attention_decode(q[:, w], ..., lengths[:, w])[b]`` — the
    verify step reproduces W sequential decode steps exactly, in ONE
    page walk per row instead of W (the flattened ``B*W`` construction
    multiplies grid cells by W; this kernel multiplies only the per-page
    VPU work, which decode never bottlenecks on).
    """
    mesh = _current_mesh()
    if mesh is not None:
        from ...parallel.topology import axis_if_divides

        bax = axis_if_divides(mesh, "dp", q.shape[0])
        hax = axis_if_divides(mesh, "mp", q.shape[2])
        if bax or hax:
            from jax.sharding import PartitionSpec as P

            from ...parallel.topology import shard_map_norep
            inner = functools.partial(_verify_local, scale=scale,
                                      interpret=interpret)
            pspec = ((P(None, hax, None, None), P(None, hax))
                     if is_quantized(k_pages)
                     else P(None, hax, None, None))
            return shard_map_norep(
                inner, mesh,
                in_specs=(P(bax, None, hax, None), pspec, pspec,
                          P(bax, None), P(bax, None)),
                out_specs=P(bax, None, hax, None),
            )(q, k_pages, v_pages, block_tables, lengths)
    return _verify_local(q, k_pages, v_pages, block_tables, lengths,
                         scale=scale, interpret=interpret)


def _verify_local(q, k_pages, v_pages, block_tables, lengths,
                  scale=None, interpret=None):
    """The single-shard kernel launch (see paged_attention_verify)."""
    interpret = _interpret() if interpret is None else interpret
    quantized = is_quantized(k_pages)
    if quantized:
        k_pages, k_scales = k_pages
        v_pages, v_scales = v_pages
    b, w, h, d = q.shape
    num_pages, kh, page_size, kd = k_pages.shape
    assert (kh, kd) == (h, d), (k_pages.shape, q.shape)
    assert lengths.shape == (b, w), (lengths.shape, q.shape)
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_map(b_, j_, lengths_s, tables_s):
        return (b_, 0, 0, 0)

    def kv_map(b_, j_, lengths_s, tables_s):
        return (tables_s[b_, j_], 0, 0, 0)

    kernel = functools.partial(
        _verify_kernel, scale=scale, page_size=page_size,
        max_pages=max_pages, window=w, quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, w, h, d), q_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
        pl.BlockSpec((1, h, page_size, d), kv_map),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        specs, ops = _scale_operands(k_scales, v_scales, block_tables)
        in_specs += specs
        operands += ops
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, w, h, d), q_map),
        scratch_shapes=[
            # stats keep a unit lane dim so heads stay on sublanes, the
            # orientation of the [W, H, page] scores they update
            pltpu.VMEM((w, h, 1), jnp.float32),
            pltpu.VMEM((w, h, 1), jnp.float32),
            pltpu.VMEM((w, h, d), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w, h, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )
    return fn(lengths, block_tables, *operands)


# --------------------------------------------------------- page utilities
# Pure-XLA writes.  The pool is head-major [P, H, page, D], so only a
# scatter whose one index dimension is the leading (page) dimension, with
# whole pages as its windows, updates the donated pool in place.  A
# per-token ``pages.at[page_idx, :, slot].set(kv)`` has the head slice
# between its two index dimensions: the TPU compiler copies the whole pool
# to a token-major layout, scatters, and copies it back (two pool-sized
# copies per call, a quarter of the chat step's device time in the ledger's
# PR 24 line).  Every token writer below therefore goes through
# ``_write_token_spans``; tests/test_chip_compile.py holds it to that.  The
# per-token bookkeeping (which page/slot) is the native allocator's job.

def _write_token_spans(pool, block_tables, vals, starts, counts):
    """Store row ``b``'s tokens ``vals[b, :counts[b]]`` at absolute
    positions ``starts[b] + i`` of its table window, page by page.

    pool [P, H, page, D] (an fp pool or an int8 payload), vals
    [B, C, H, D] in the pool's dtype, starts / counts [B] int32.  A
    row's span lies in at most ``n`` consecutive logical pages (``n``
    from the static ``C``); those are gathered whole, their touched
    slots replaced from ``vals``, and put back with a leading-dimension
    scatter.  Pages a row does not touch (``counts == 0``, the tail of
    a short span, anything past the table) carry the out-of-range index
    ``P``, which the scatter drops: nothing but the rows' own pages is
    written, so physical indices never collide (a shared prefix page is
    copied before its row writes)."""
    num_pages, h, page, d = pool.shape
    b, c = vals.shape[:2]
    max_pages = block_tables.shape[1]
    n = (c + page - 2) // page + 1
    t = starts[:, None] // page + jnp.arange(n, dtype=jnp.int32)[None]
    touched = ((t * page < (starts + counts)[:, None])
               & (counts[:, None] > 0) & (t < max_pages))      # [B, n]
    own = jnp.take_along_axis(block_tables,
                              jnp.minimum(t, max_pages - 1), axis=1)
    old = pool.at[own].get(mode="clip")
    # chunk index of every slot of the gathered pages: [B, n, page]
    rel = t[:, :, None] * page + jnp.arange(page, dtype=jnp.int32) \
        - starts[:, None, None]
    fresh = jnp.take_along_axis(
        vals, jnp.clip(rel, 0, c - 1).reshape(b, n * page, 1, 1), axis=1)
    fresh = fresh.reshape(b, n, page, h, d).transpose(0, 1, 3, 2, 4)
    written = (rel >= 0) & (rel < counts[:, None, None])
    merged = jnp.where(written[:, :, None, :, None], fresh, old)
    return pool.at[jnp.where(touched, own, num_pages).reshape(-1)].set(
        merged.reshape(b * n, h, page, d), mode="drop")


def write_prompt_pages(pages, block_tables, kv):
    """Scatter prompt K or V [B, S, H, D] into the head-major pool
    [P, H, page, D].  S must be a multiple of page_size; slots past a
    sequence's true length hold garbage — the decode kernel masks by
    length at read time."""
    b, s, h, d = kv.shape
    if is_quantized(pages):
        # route through the shared token scatter so the slot-0 scale
        # protocol is byte-identical to the chunked/decode writers
        # (write-order invariance is the warm/cold parity guarantee)
        page = pages[0].shape[2]
        assert s % page == 0, (s, page)
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                               (b, s))
        page_idx = jnp.take_along_axis(block_tables, pos // page, axis=1)
        return _quantized_scatter(pages, page_idx, pos % page, kv,
                                  block_tables, jnp.zeros((b,), jnp.int32),
                                  jnp.full((b,), s, jnp.int32))
    page = pages.shape[2]
    assert s % page == 0, (s, page)
    n = s // page
    chunks = kv.reshape(b, n, page, h, d).transpose(0, 1, 3, 2, 4)
    idx = block_tables[:, :n].reshape(-1)
    flat = chunks.reshape(b * n, h, page, d)
    return pages.at[idx].set(flat.astype(pages.dtype))


def gather_prompt_pages(pages, block_tables, s):
    """Read an aligned prompt's K or V back out of the pool as
    [B, S, H, D] — the read-your-writes companion of
    ``write_prompt_pages``.  On a quantized pool this dequantizes the
    page bytes, which is the whole point: monolithic prefill attention
    must consume exactly the values every later page reader (chunked
    prefill, ragged serving, decode) will see, or near-tie argmaxes
    diverge between generate() and the serving plane."""
    quantized = is_quantized(pages)
    page = pages[0].shape[2] if quantized else pages.shape[2]
    assert s % page == 0, (s, page)
    n = s // page
    idx = block_tables[:, :n]                          # [B, n]
    if quantized:
        payload, scales = pages
        g = payload[idx].astype(jnp.float32) \
            * scales[idx][:, :, :, None, None]
    else:
        g = pages[idx]
    # [B, n, H, page, D] -> [B, n, page, H, D] -> [B, S, H, D]
    return jnp.transpose(g, (0, 1, 3, 2, 4)).reshape(
        idx.shape[0], n * page, g.shape[2], g.shape[4])


def write_chunk_pages(pages, block_tables, kv, offsets):
    """Write a chunk's K or V [B, S, H, D] into the pool at absolute
    positions ``offsets[b] + i`` — the offset-aware generalisation of
    ``write_prompt_pages`` for suffix prefill over a cached prefix.
    Unlike the aligned writer, the chunk may start mid-page (the
    copy-on-write tail block), so the pages it touches are read,
    merged and written back whole (``_write_token_spans``).  The caller
    guarantees ``offsets + S`` stays inside the table window."""
    b, s = kv.shape[:2]
    counts = jnp.full((b,), s, jnp.int32)
    if is_quantized(pages):
        page = pages[0].shape[2]
        pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        page_idx = jnp.take_along_axis(block_tables, pos // page, axis=1)
        return _quantized_scatter(pages, page_idx, pos % page, kv,
                                  block_tables, offsets, counts)
    return _write_token_spans(pages, block_tables, kv.astype(pages.dtype),
                              offsets, counts)


def prefix_prefill_attention(q, k_pages, v_pages, block_tables, offsets,
                             scale=None):
    """Suffix-prefill attention: queries at absolute positions
    ``offsets[b] + i`` attend over the row's whole gathered page window
    (cached prefix + the just-written chunk) under an absolute-position
    causal mask.

    q            [B, S, H, D]   — the suffix chunk's queries
    k_pages      [P, H, page, D]
    v_pages      [P, H, page, D]
    block_tables [B, max_pages] int32
    offsets      [B] int32      — tokens already cached per row
    → [B, S, H, D]

    Every reduction here has a shape that is a per-core constant, whatever
    the chunk length ``S``: the window width is ``max_pages × page``, and
    the queries are walked in blocks of ``page`` positions, so both
    contractions and the softmax see ``[page, window]`` tiles in every
    prefill bucket.  That is what makes warm-path logits bitwise equal to
    the cold path on CPU (slots past a query's position mask to exactly
    zero weight, whatever garbage they hold).  The query blocking is part
    of that guarantee, not a tuning choice: one ``[S, window]`` dot lets
    XLA's CPU backend pick its strategy — and with it the accumulation
    order over the window — by ``S``, and a 16-token warm suffix then
    differs from the same positions of a 32-token cold chunk in the last
    bits.  A dense gather is fine for prefill (it is compute-bound
    already); a ragged Pallas variant is the TPU follow-up.
    """
    b, s, h, d = q.shape
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if is_quantized(k_pages):
        # gather int8 pages + their scale rows and dequantize in the
        # gathered window — the values every query sees are exactly
        # (payload * scale), the same floats the decode kernel reads
        (kp, ks), (vp, vs) = k_pages, v_pages
        page = kp.shape[2]
        W = max_pages * page
        kw = (kp[block_tables].astype(jnp.float32)
              * ks[block_tables][:, :, :, None, None]) \
            .transpose(0, 1, 3, 2, 4).reshape(b, W, h, d)
        vw = (vp[block_tables].astype(jnp.float32)
              * vs[block_tables][:, :, :, None, None]) \
            .transpose(0, 1, 3, 2, 4).reshape(b, W, h, d)
    else:
        page = k_pages.shape[2]
        W = max_pages * page
        kw = k_pages[block_tables].transpose(0, 1, 3, 2, 4) \
            .reshape(b, W, h, d).astype(jnp.float32)
        vw = v_pages[block_tables].transpose(0, 1, 3, 2, 4) \
            .reshape(b, W, h, d).astype(jnp.float32)
    slots = jnp.arange(W, dtype=jnp.int32)[None, None, :]

    def attend(block):
        qb, pos = block                      # [b, page, h, d], [b, page]
        scores = jnp.einsum("bshd,bwhd->bhsw", qb.astype(jnp.float32),
                            kw) * scale
        scores = jnp.where((slots <= pos[:, :, None])[:, None], scores,
                           NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhsw,bwhd->bshd", weights, vw)

    n = -(-s // page)
    pad = n * page - s
    pos = offsets[:, None] + jnp.arange(n * page, dtype=jnp.int32)[None]
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(attend, (
        jnp.moveaxis(qp.reshape(b, n, page, h, d), 1, 0),
        jnp.moveaxis(pos.reshape(b, n, page), 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, n * page, h, d)[:, :s]
    return out.astype(q.dtype)


def write_token_page(pages, block_tables, kv, positions):
    """Write one new token's K or V [B, H, D] at its (page, slot):
    positions [B] is the 0-based token index in each sequence.  A chunk
    of one token: its page is read, one slot replaced, and written
    back."""
    return write_chunk_pages(pages, block_tables, kv[:, None], positions)


class PagedKVCache:
    """Per-layer paged KV pool + the native page-table allocator
    (native/kv_allocator.cc).  The serving loop asks for reservations and
    hands the resulting tables to the kernel — the device arrays stay put.
    """

    def __init__(self, num_pages, page_size, num_heads, head_dim,
                 num_layers=1, dtype=jnp.bfloat16, pool=None):
        from ... import native

        self.page_size = page_size
        self.num_pages = num_pages
        self.pool = pool or native.KVBlockPool(num_pages, page_size)
        shape = (num_pages, num_heads, page_size, head_dim)
        self.k_pages = [jnp.zeros(shape, dtype) for _ in range(num_layers)]
        self.v_pages = [jnp.zeros(shape, dtype) for _ in range(num_layers)]
        self.num_layers = num_layers

    def reserve(self, seq_id, num_tokens):
        return self.pool.reserve(seq_id, num_tokens)

    def tables_for(self, seq_ids, max_pages=None):
        """Padded [B, max_pages] table + [B] lengths for a batch."""
        import numpy as np

        tables = [self.pool.block_table(s) for s in seq_ids]
        lengths = np.asarray([self.pool.length(s) for s in seq_ids],
                             np.int32)
        width = max_pages or max(len(t) for t in tables)
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, t in enumerate(tables):
            t = t[:width]        # a reused/forked seq may own more pages
            out[i, :len(t)] = t
        return jnp.asarray(out), jnp.asarray(lengths)

    def prefill(self, layer, seq_ids, k, v):
        """Write prompt KV (padded to a page multiple) for new sequences."""
        import numpy as np

        b, s, _, _ = k.shape
        for i, sid in enumerate(seq_ids):
            self.reserve(sid, int(s))
        tables, _ = self.tables_for(seq_ids,
                                    max_pages=s // self.page_size)
        self.k_pages[layer] = write_prompt_pages(
            self.k_pages[layer], tables, k)
        self.v_pages[layer] = write_prompt_pages(
            self.v_pages[layer], tables, v)
        self._tables_cache = None

    def append(self, layer, seq_ids, k, v, positions):
        """Write one decode token per sequence at `positions` (0-based).
        Page tables are refreshed once per decode step (at layer 0, where
        reservations can grow them) and reused for the other layers —
        no per-layer native traffic."""
        if layer == 0:
            for i, sid in enumerate(seq_ids):
                self.reserve(sid, int(positions[i]) + 1)
                # after pool.fork (beam search) the last page may be shared
                # with the parent; writing into it would corrupt the
                # parent's cache — copy-on-write it first, mirroring the
                # page across every layer's pools
                cow = self.pool.cow_last_block(sid)
                if cow is not None:
                    src, dst = cow
                    for lyr in range(self.num_layers):
                        self.k_pages[lyr] = self.k_pages[lyr].at[dst].set(
                            self.k_pages[lyr][src])
                        self.v_pages[lyr] = self.v_pages[lyr].at[dst].set(
                            self.v_pages[lyr][src])
            self._tables_cache = (tuple(seq_ids),
                                  self.tables_for(seq_ids))
        tables, _ = self._cached_tables(seq_ids)
        pos = jnp.asarray(positions, jnp.int32)
        self.k_pages[layer] = write_token_page(
            self.k_pages[layer], tables, k, pos)
        self.v_pages[layer] = write_token_page(
            self.v_pages[layer], tables, v, pos)

    def _cached_tables(self, seq_ids):
        cached = getattr(self, "_tables_cache", None)
        if cached is not None and cached[0] == tuple(seq_ids):
            return cached[1]
        result = self.tables_for(seq_ids)
        self._tables_cache = (tuple(seq_ids), result)
        return result

    def attend(self, layer, seq_ids, q, interpret=None):
        tables, lengths = self._cached_tables(seq_ids)
        return paged_attention_decode(
            q, self.k_pages[layer], self.v_pages[layer], tables, lengths,
            interpret=interpret)

    def free(self, seq_ids):
        for s in seq_ids:
            self.pool.free(s)
        self._tables_cache = None
