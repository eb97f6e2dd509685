"""Latent attention over the tokens a learned indexer chooses (DeepSeek
Sparse Attention, the "dsa" of ``model_type: glm_moe_dsa``): each query
scores every cached token of its sequence with a cheap multi-head index,

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s]),

keeps the ``topk`` tokens ``s <= t`` with the largest scores (ties to the
earlier token) and attends, in the absorbed latent form of
``latent_attention.py``, over those alone.  With ``t + 1 <= topk`` that
is the dense layer.

Two pools a layer under one block table (inference/cache_layout.py,
``latent`` with an ``index_width``): the latent rows ``[P, page, lanes]``
and the index keys ``[P, page, index_lanes]``, ONE key a token for all
index heads.  The pieces, composed by ``dsa_ragged_attention``:

* ``dsa_index_scores`` — the Pallas kernel for decode rows: a row's one
  query (all index heads) against its index-key pages,
  ``INDEX_PAGES_PER_STEP`` pages a grid step, one MXU product, the ReLU
  and the heads' weighted sum on the VPU, one lane-dense block of scores
  out.  The grid is ``latent_attention.decode_grid``: the live rows by
  the walk of the longest, so its time follows the context.
* ``select_rows`` (scope ``dsa_select``) — ``lax.top_k`` of each row's
  scores (lower index first among equals: the tie rule; the TPU's
  compiler sorts the whole window for it), then a loop of one trip a
  LIVE row, ``decode_grid``'s compaction: the row's chosen positions
  looked up in its table and their latent rows gathered by (page, slot)
  into slot ``n`` of ``[B, topk, lanes]``.  The sort's time follows
  ``B`` and the window's width, the rest ``topk`` by the step's live
  decode rows, none of it the context; a dead row costs its place in
  the sort and its slot's share of the buffer's zero fill.
* ``dsa_sparse_decode`` — ``latent_attention``'s decode kernel body over
  the gathered rows, slot ``n`` for the ``n``-th live row,
  ``SELECT_BLOCK`` of them a grid step; the grid is the live rows by the
  largest selection's blocks, so its time follows ``min(context, topk)``.
* ``dsa_chunk_attention`` — chunk rows, one row at a time: index scores
  and then attention in tiles of ``CHUNK_TILE`` keys over the row's own
  context only (``ceil((ctx + qlen) / tile)`` tiles, a traced bound),
  each query's selection an exact mask from its ``topk``-th score
  (found by bisection on the score's bits, no sort) with the tie rule
  applied, online softmax across the tiles.  The widest
  temporary is one tile's ``[heads, chunk, tile]`` float32 scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from .latent_attention import (_decode_kernel, decode_grid, pad_lanes,
                               walk_geometry)
from .paged_attention import NEG_INF

INDEX_PAGES_PER_STEP = 32
SELECT_BLOCK = 512
CHUNK_TILE = 1024


# ------------------------------------------------------------ index scores

def _index_kernel(lengths_ref, tables_ref, live_ref, q_ref, w_ref, *rest,
                  page_size, pages_per_step):
    page_refs, o_ref = rest[:pages_per_step], rest[pages_per_step]
    b = live_ref[pl.program_id(0)]
    j = pl.program_id(1)
    span = pages_per_step * page_size

    @pl.when(j * span < lengths_ref[b])
    def _():
        q = q_ref[0]                                      # [Hi, lanes]
        k = jnp.concatenate([r[0] for r in page_refs], axis=0)
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q, k, nt,
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0]                # [Hi, span]
        o_ref[0] = jnp.sum(s, axis=0, keepdims=True)


def dsa_index_scores(q_idx, w_idx, index_pages, block_tables, lengths,
                     pages_per_step=INDEX_PAGES_PER_STEP, interpret=None):
    """Index scores of one decode query a row over the row's cached keys.

    q_idx        [B, Hi, width]  index queries (rotated), ``width <= lanes``
    w_idx        [B, Hi] float32 head weights, constants folded in
    index_pages  [P, page, lanes]
    block_tables [B, max_pages] int32
    lengths      [B] int32       tokens in cache, the current included;
                                 0 skips the row
    → [B, max_pages * page] float32; ``-inf`` at and past ``lengths``.
    """
    interpret = _interpret() if interpret is None else interpret
    b, hi, _ = q_idx.shape
    _, page_size, lanes = index_pages.shape
    max_pages = block_tables.shape[1]
    g, span, steps = walk_geometry(page_size, max_pages, pages_per_step)
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    live, rows, walk = decode_grid(lengths, page_size, max_pages, g)

    def row_map(i_, j_, lengths_s, tables_s, live_s):
        return (live_s[i_], 0, 0)

    def page_map(i):
        def index(i_, j_, lengths_s, tables_s, live_s):
            b_ = live_s[i_]
            last = jnp.clip(lengths_s[b_] - 1, 0,
                            max_pages * page_size - 1) // page_size
            return (tables_s[b_, jnp.minimum(j_ * g + i, last)], 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, walk),
        in_specs=[pl.BlockSpec((1, hi, lanes), row_map),
                  pl.BlockSpec((1, hi, 1), row_map)] + [
            pl.BlockSpec((1, page_size, lanes), page_map(i))
            for i in range(g)],
        out_specs=pl.BlockSpec(
            (1, 1, span),
            lambda i_, j_, lengths_s, tables_s, live_s: (live_s[i_], 0, j_)),
    )
    fn = pl.pallas_call(
        functools.partial(_index_kernel, page_size=page_size,
                          pages_per_step=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, steps * span), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="dsa_index_scores",
    )
    out = fn(lengths, block_tables, live,
             pad_lanes(q_idx, lanes).astype(index_pages.dtype),
             w_idx.astype(jnp.float32)[:, :, None],
             *([index_pages] * g))[:, 0, :max_pages * page_size]
    slot = jnp.arange(out.shape[1], dtype=jnp.int32)
    # blocks no grid step visited hold whatever was there
    return jnp.where(slot[None, :] < lengths[:, None], out, -jnp.inf)


# --------------------------------------------------------------- selection

def selection_width(topk, window):
    """``(k, padded k)``: the tokens a row keeps at most, and that
    rounded up to whole ``SELECT_BLOCK``s (the sparse decode's grid)."""
    k = min(int(topk), int(window))
    return k, k + -k % min(SELECT_BLOCK, k)


def gathered_rows(decode_lengths, topk, window) -> int:
    """Latent rows ``select_rows`` copies out of the pool for a step's
    decode rows' lengths (host integers): its loop's trips, one a live
    row, by the padded ``k``; what the packer books as StepLog
    ``index_gathered_rows``."""
    return (int(np.count_nonzero(np.asarray(decode_lengths) > 0))
            * selection_width(topk, window)[1])


def select_rows(scores, lengths, pages, block_tables, topk):
    """The ``topk`` best-scored tokens of each row (all of a shorter
    row), and the live rows' latent rows gathered from the pool.

    scores [B, window] (``-inf`` past ``lengths``) → ``(rows [B, k,
    lanes], counts [B], positions [B, k])`` with ``k`` the padded
    ``selection_width``.  ``rows`` is compacted as ``decode_grid``
    compacts: slot ``n`` holds the chosen rows of the ``n``-th row with
    ``lengths > 0``, best first, and the slots past the live rows hold
    zeros; entries past a row's ``counts`` are other tokens' rows and
    are never read."""
    b, window = scores.shape
    page, lanes = pages.shape[1:]
    k, padded = selection_width(topk, window)
    # equal scores keep the lower index first: the tie rule
    _, pos = jax.lax.top_k(scores, k)
    pos = jnp.pad(pos, ((0, 0), (0, padded - k)))
    lengths = lengths.astype(jnp.int32)
    counts = jnp.minimum(lengths, k)
    live, _, _ = decode_grid(lengths, page, block_tables.shape[1], 1)
    pool = pages.reshape(-1, lanes)

    def gather(n, rows):
        r = live[n]
        p = pos[r]
        flat = block_tables[r][p // page] * page + p % page
        return jax.lax.dynamic_update_index_in_dim(rows, pool[flat], n, 0)

    # one trip a live row: a dead row's selection is never looked up
    rows = jax.lax.fori_loop(
        0, jnp.count_nonzero(lengths > 0), gather,
        jnp.zeros((b, padded, lanes), pages.dtype))
    return rows, counts, pos


def dsa_sparse_decode(q, rows, counts, scale, value_width,
                      block=SELECT_BLOCK, interpret=None):
    """Absorbed latent attention of one query a row over its gathered
    selection.

    q      [B, H, width]   queries in the latent space, ``width <= lanes``
    rows   [B, K, lanes]   the chosen tokens' cached rows as ``select_rows``
                           compacts them: slot ``n`` is the ``n``-th row
                           with ``counts > 0``
    counts [B] int32       how many of them are the row's; 0 skips it
    → [B, H, value_width] in q's dtype, zero for a skipped row.
    """
    interpret = _interpret() if interpret is None else interpret
    b, h, width = q.shape
    _, k, lanes = rows.shape
    block = min(int(block), k)
    assert 0 < value_width < width <= lanes and k % block == 0
    blocks = k // block
    counts = counts.astype(jnp.int32)
    # the decode kernel's grid with a "page" of ``block`` rows
    live, n_rows, walk = decode_grid(counts, block, blocks, 1)

    def q_map(i_, j_, counts_s, unused_s, live_s):
        return (live_s[i_], 0, 0)

    def rows_map(i_, j_, counts_s, unused_s, live_s):
        last = jnp.clip(counts_s[live_s[i_]] - 1, 0, k - 1) // block
        return (i_, jnp.minimum(j_, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_rows, walk),
        in_specs=[pl.BlockSpec((1, h, lanes), q_map),
                  pl.BlockSpec((1, block, lanes), rows_map)],
        out_specs=pl.BlockSpec((1, h, value_width), q_map),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, value_width), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale),
                          page_size=block, pages_per_step=1,
                          value_width=int(value_width)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="dsa_sparse_decode",
    )
    out = fn(counts, jnp.zeros((1, 1), jnp.int32), live,
             pad_lanes(q, lanes).astype(rows.dtype), rows)
    return jnp.where((counts > 0)[:, None, None], out, 0)


# ------------------------------------------------------------------- chunk

def _order_keys(x):
    """uint32 keys that order as the float32 values do (``-0.0`` with
    ``0.0``, as a sort has them)."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _kth_largest(keys, k):
    """The ``k``-th largest of each row of uint32 ``keys`` [T, W], exactly:
    the largest value that ``k`` of the row's keys reach, found bit by bit
    from the top in 32 counting passes.  No sort: a chunk's queries need
    their ``topk``-th score and no order among the rest."""
    def narrow(i, best):
        bit = jnp.uint32(1) << (31 - i).astype(jnp.uint32)
        reach = jnp.sum(keys >= (best | bit)[:, None], axis=1)
        return jnp.where(reach >= k, best | bit, best)

    return jax.lax.fori_loop(0, 32, narrow,
                             jnp.zeros(keys.shape[:1], jnp.uint32))


def selection_mask(scores, valid, topk):
    """Exactly the ``min(topk, valid count)`` best of each query's valid
    scores, ties at the last place to the lower index.

    scores [T, W] float32 (``-inf`` where not ``valid``) → bool [T, W].
    """
    k = min(int(topk), scores.shape[1])
    keys = _order_keys(scores)
    kth = _kth_largest(keys, k)[:, None]
    above = (keys > kth) & valid
    tied = (keys == kth) & valid
    room = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=1) <= room))


def dsa_chunk_attention(q, q_idx, w_idx, pages, index_pages, block_tables,
                        context_lens, query_lens, scale, value_width, topk,
                        tile=CHUNK_TILE):
    """Chunk rows (``query_lens > 1``) of a step's flat token axis, each
    query over its own selection; as ``latent_chunk_attention`` lays
    rows out, one row an iteration and only the chunk rows.

    q [T, H, width], q_idx [T, Hi, index_width], w_idx [T, Hi] float32
    → [T, H, value_width] in q's dtype; slots of rows with
    ``query_lens <= 1`` and the padded tail hold zeros."""
    t, h, _ = q.shape
    b, max_pages = block_tables.shape
    page, lanes = pages.shape[1:]
    ilanes = index_pages.shape[-1]
    tile_pages = max(1, min(int(tile) // page, max_pages))
    tile = tile_pages * page
    n_tiles = -(-max_pages // tile_pages)
    window = n_tiles * tile
    # a table a whole number of tiles wide; the filler is never attended
    tables = jnp.pad(block_tables, ((0, 0), (0, n_tiles * tile_pages
                                             - max_pages)))
    i = jnp.arange(t, dtype=jnp.int32)
    slots = jnp.arange(window, dtype=jnp.int32)
    query_lens = query_lens.astype(jnp.int32)
    starts = jnp.cumsum(query_lens) - query_lens
    is_chunk = query_lens > 1
    chunk_rows = jnp.nonzero(is_chunk, size=b, fill_value=0)[0]

    def attend(n, out):
        r = chunk_rows[n]
        idx = jnp.minimum(starts[r] + i, t - 1)
        pos = context_lens[r] + i
        tiles = jnp.clip(-(-(context_lens[r] + query_lens[r]) // tile), 1,
                         n_tiles)
        valid = slots[None, :] <= pos[:, None]                # [T, W]

        def tile_pages_of(j):
            return jax.lax.dynamic_slice_in_dim(tables[r], j * tile_pages,
                                                tile_pages)

        qi = pad_lanes(q_idx[idx], ilanes).astype(index_pages.dtype)
        wi = w_idx[idx].astype(jnp.float32)

        def score_tile(j, sc):
            kt = index_pages[tile_pages_of(j)].reshape(tile, ilanes)
            s = jnp.einsum("thd,kd->htk", qi, kt,
                           preferred_element_type=jnp.float32)
            s = jnp.sum(jnp.maximum(s, 0.0) * wi.T[:, :, None], axis=0)
            return jax.lax.dynamic_update_slice_in_dim(sc, s, j * tile, 1)

        with jax.named_scope("dsa_chunk_scores"):
            sc = jax.lax.fori_loop(
                0, tiles, score_tile,
                jnp.full((t, window), -jnp.inf, jnp.float32))
            sc = jnp.where(valid, sc, -jnp.inf)
        with jax.named_scope("dsa_select"):
            chosen = selection_mask(sc, valid, topk)

        qr = pad_lanes(q[idx], lanes).astype(pages.dtype)

        def attend_tile(j, carry):
            m, l, acc = carry
            kw = pages[tile_pages_of(j)].reshape(tile, lanes)
            s = jnp.einsum("chw,kw->hck", qr, kw,
                           preferred_element_type=jnp.float32) * scale
            keep = jax.lax.dynamic_slice_in_dim(chosen, j * tile, tile, 1)
            s = jnp.where(keep[None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(keep[None], jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            o = jnp.matmul(p.astype(kw.dtype).reshape(h * t, tile),
                           kw[:, :value_width],
                           preferred_element_type=jnp.float32)
            return (m_new, l * alpha + jnp.sum(p, axis=-1),
                    acc * alpha[..., None] + o.reshape(h, t, value_width))

        m, l, acc = jax.lax.fori_loop(
            0, tiles, attend_tile,
            (jnp.full((h, t), NEG_INF, jnp.float32),
             jnp.zeros((h, t), jnp.float32),
             jnp.zeros((h, t, value_width), jnp.float32)))
        o = (acc / jnp.maximum(l, 1e-20)[..., None]).transpose(1, 0, 2)
        # the row's own slots; what lies past its length goes nowhere
        return out.at[jnp.where(i < query_lens[r], starts[r] + i, t)].set(
            o.astype(q.dtype), mode="drop")

    return jax.lax.fori_loop(
        0, jnp.sum(is_chunk.astype(jnp.int32)), attend,
        jnp.zeros((t, h, value_width), q.dtype))


def dsa_ragged_attention(q, q_idx, w_idx, pages, index_pages, block_tables,
                         context_lens, query_lens, scale, value_width, topk):
    """The mixed step's attention for a layer with an indexer, over the
    two pools the step has just written, on the step's flat token axis:
    decode rows through index scores, selection and the sparse decode;
    chunk rows through the per-row composition; inactive rows nowhere.

    q [T, H, width], q_idx [T, Hi, index_width], w_idx [T, Hi]
    → [T, H, value_width]; the padded tail holds zeros."""
    t = q.shape[0]
    is_decode = query_lens == 1
    starts = jnp.cumsum(query_lens) - query_lens
    first = jnp.minimum(starts, t - 1)
    lengths = jnp.where(is_decode, context_lens + 1, 0)
    # both Pallas calls stay outside any scope: the TPU compiler names a
    # Mosaic custom call after its innermost scope, and readers key on
    # the kernels' own names
    scores = dsa_index_scores(q_idx[first], w_idx[first], index_pages,
                              block_tables, lengths)
    with jax.named_scope("dsa_select"):
        rows, counts, _ = select_rows(scores, lengths, pages, block_tables,
                                      topk)
    dec = dsa_sparse_decode(q[first], rows, counts, scale, value_width)
    with jax.named_scope("latent_attention"):
        out = dsa_chunk_attention(q, q_idx, w_idx, pages, index_pages,
                                  block_tables, context_lens, query_lens,
                                  scale, value_width, topk)
        return out.at[jnp.where(is_decode, starts, t)].set(
            dec.astype(out.dtype), mode="drop")
