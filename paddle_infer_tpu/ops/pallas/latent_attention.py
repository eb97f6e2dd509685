"""Attention over a paged LATENT cache: one row of ``width`` numbers per
token per layer, shared by every query head, whose first ``value_width``
lanes are also the value (multi-head latent attention in its absorbed
form: queries are projected into the latent space before the scores, the
output back out of it after the weighted sum, so the per-head keys and
values are never materialised).

Pool layout ``[P, page, lanes]`` — no head axis and no V pool; ``lanes``
is the cached ``width`` rounded up to whole 128-lane tiles, the rest
zeros (inference/cache_layout.py ``latent`` says why).  Queries are
zero-padded to the same lanes, so the padding adds nothing to a score.
Three pieces, composed by
``latent_ragged_attention`` the way ``ragged_paged_attention`` composes
its own:

* ``write_latent_pages`` — the page-granular read-modify-write of
  ``paged_attention._write_token_spans`` (the pool seen with a head axis
  of one; the reshape is a bitcast).
* ``latent_paged_decode`` — the Pallas kernel for decode rows: all query
  heads of a row against its pages, ``pages_per_step`` pages a grid step
  (the same pool passed that many times, each with its own page index
  from the scalar-prefetched table), both contractions on the MXU,
  online softmax in VMEM scratch.  The grid is the step's live work
  (``decode_grid``), both dimensions traced values of the one compiled
  step: the live rows, compacted to the front of a scalar-prefetched
  list the index maps read through, by the walk of the longest live
  row; a grid step costs its index arithmetic live or skipped, so a
  dead row gets none and no row walks the table past the longest.
  Inside the walk, pages past a row's length repeat the row's last
  valid block index, so the pipeline issues no copy for them.
* ``latent_chunk_attention`` — chunk rows (``query_len > 1``) against
  their gathered window, one row at a time in a loop over the chunk rows
  alone: a row that carries no chunk costs nothing, and the widest
  temporary is one row's ``[heads, chunk, window]`` scores, never
  ``[rows, window, heads, key+value]`` expanded keys and values.

Queries and outputs of the last two lie on the step's flat token axis
(rows end to end, ``[T, heads, width]``): decode rows need the one query
at their first slot, a chunk row's queries are gathered inside its own
iteration, so no ``[rows, T, heads, width]`` view is ever built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from .paged_attention import NEG_INF, _write_token_spans

PAGES_PER_STEP = 8


def pad_lanes(x, lanes: int):
    """Zero-pad the last axis to ``lanes``."""
    extra = lanes - x.shape[-1]
    if extra == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def write_latent_pages(pages, block_tables, rows, context_lens, query_lens):
    """Store row ``b``'s latent vectors ``rows[b, :query_lens[b]]``
    ([B, C, width]) at absolute positions ``context_lens[b] + i`` of the
    pool ``[P, page, lanes]``; pad positions are written nowhere."""
    rows = pad_lanes(rows.astype(pages.dtype), pages.shape[-1])
    out = _write_token_spans(pages[:, None], block_tables,
                             rows[:, :, None, :], context_lens, query_lens)
    return out[:, 0]


# ------------------------------------------------------------------ decode

def walk_geometry(page_size, max_pages, pages_per_step=PAGES_PER_STEP):
    """``(pages a grid step, keys a grid step, grid steps of a whole
    table)`` of the decode kernel's walk."""
    g = max(1, min(int(pages_per_step), int(max_pages)))
    return g, g * int(page_size), -(-int(max_pages) // g)


def decode_grid(lengths, page_size, max_pages,
                pages_per_step=PAGES_PER_STEP):
    """The decode kernel's grid for one step's ``lengths`` [B]:
    ``(live, rows, walk)`` — the rows with ``lengths > 0`` compacted to
    the front of ``live`` [B] (the tail repeats row 0), their count and
    the grid steps of the longest one's walk; both at least 1, so a step
    with no decode row launches one step over a dead row."""
    _, span, steps = walk_geometry(page_size, max_pages, pages_per_step)
    alive = lengths > 0
    live = jnp.nonzero(alive, size=lengths.shape[0], fill_value=0)[0]
    rows = jnp.maximum(jnp.sum(alive.astype(jnp.int32)), 1)
    walk = jnp.clip(-(-jnp.max(lengths) // span), 1, steps)
    return live.astype(jnp.int32), rows, walk.astype(jnp.int32)


def decode_grid_steps(decode_lengths, page_size, max_pages,
                      pages_per_step=PAGES_PER_STEP) -> int:
    """``rows x walk`` of ``decode_grid`` for a step's decode rows'
    lengths (host integers, every one > 0), 0 for none: what the packer
    books as StepLog ``decode_grid_steps``."""
    decode_lengths = np.asarray(decode_lengths)
    if not decode_lengths.size:
        return 0
    _, span, steps = walk_geometry(page_size, max_pages, pages_per_step)
    return decode_lengths.size * min(
        max(-(-int(decode_lengths.max()) // span), 1), steps)


def _decode_kernel(lengths_ref, tables_ref, live_ref, q_ref, *rest, scale,
                   page_size, pages_per_step, value_width):
    page_refs = rest[:pages_per_step]
    o_ref, m_ref, l_ref, acc_ref = rest[pages_per_step:]
    b = live_ref[pl.program_id(0)]
    j = pl.program_id(1)
    span = pages_per_step * page_size

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    @pl.when(j * span < length)
    def _():
        q = q_ref[0]                                      # [H, lanes]
        kv = jnp.concatenate([r[0] for r in page_refs], axis=0)
        v = kv[:, :value_width]                           # [span, value]
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q, kv, nt,
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # [H, span]
        slot = j * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < length, s, NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]               # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-20)
                    ).astype(o_ref.dtype)


def latent_paged_decode(q, pages, block_tables, lengths, scale,
                        value_width, pages_per_step=PAGES_PER_STEP,
                        interpret=None):
    """One decode step of absorbed latent attention.

    q            [B, H, width]   — queries already in the latent space
                                   (latent part ‖ rotated position part),
                                   ``width <= lanes``
    pages        [P, page, lanes]
    block_tables [B, max_pages] int32
    lengths      [B] int32       — tokens in cache, the current included;
                                   0 skips the row (its output is zero)
    → [B, H, value_width] in q's dtype

    The grid is ``decode_grid(lengths)``: live rows x the longest live
    row's walk, traced bounds of one executable.  A dead row's output
    block is never visited; the mask after the launch makes it zero.
    """
    interpret = _interpret() if interpret is None else interpret
    b, h, width = q.shape
    _, page_size, lanes = pages.shape
    assert 0 < value_width < width <= lanes, (q.shape, pages.shape)
    q = pad_lanes(q, lanes)
    max_pages = block_tables.shape[1]
    g = walk_geometry(page_size, max_pages, pages_per_step)[0]
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    live, rows, walk = decode_grid(lengths, page_size, max_pages, g)

    def q_map(i_, j_, lengths_s, tables_s, live_s):
        return (live_s[i_], 0, 0)

    def page_map(i):
        def index(i_, j_, lengths_s, tables_s, live_s):
            b_ = live_s[i_]
            last = jnp.clip(lengths_s[b_] - 1, 0,
                            max_pages * page_size - 1) // page_size
            return (tables_s[b_, jnp.minimum(j_ * g + i, last)], 0, 0)
        return index

    kernel = functools.partial(
        _decode_kernel, scale=float(scale), page_size=page_size,
        pages_per_step=g, value_width=int(value_width))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, walk),
        in_specs=[pl.BlockSpec((1, h, lanes), q_map)] + [
            pl.BlockSpec((1, page_size, lanes), page_map(i))
            for i in range(g)],
        out_specs=pl.BlockSpec((1, h, value_width), q_map),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, value_width), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="latent_paged_decode",
    )
    out = fn(lengths, block_tables, live, q.astype(pages.dtype),
             *([pages] * g))
    return jnp.where((lengths > 0)[:, None, None], out, 0)


# ------------------------------------------------------------------- chunk

def latent_chunk_attention(q, pages, block_tables, context_lens, query_lens,
                           scale, value_width):
    """Chunk rows of a step's flat token axis: row ``b``'s
    ``query_lens[b]`` queries lie end to end after row ``b - 1``'s
    (``ragged_paged_attention.ragged_rows``), sit at absolute positions
    ``context_lens[b] + i`` and attend over the row's whole table window
    under an absolute-position causal mask, in the absorbed form.  One
    row at a time, and only the rows with ``query_lens > 1``: a step
    with no chunk row runs nothing here, and a row's ``[T, H, lanes]``
    queries are gathered inside its own iteration.

    q [T, H, width] → [T, H, value_width] in q's dtype; slots of rows
    with ``query_lens <= 1`` and the padded tail hold zeros."""
    t, h, _ = q.shape
    b = block_tables.shape[0]
    page, lanes = pages.shape[1:]
    window = block_tables.shape[1] * page
    slots = jnp.arange(window, dtype=jnp.int32)
    i = jnp.arange(t, dtype=jnp.int32)
    query_lens = query_lens.astype(jnp.int32)
    starts = jnp.cumsum(query_lens) - query_lens
    is_chunk = query_lens > 1
    chunk_rows = jnp.nonzero(is_chunk, size=b, fill_value=0)[0]

    def attend(n, out):
        r = chunk_rows[n]
        idx = starts[r] + i
        qr = pad_lanes(q[jnp.minimum(idx, t - 1)], lanes)  # [T, H, lanes]
        kw = pages[block_tables[r]].reshape(window, lanes)
        s = jnp.einsum("chw,kw->hck", qr.astype(kw.dtype), kw,
                       preferred_element_type=jnp.float32) * scale
        pos = context_lens[r] + i
        s = jnp.where(slots[None, None, :] <= pos[None, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # one plain [heads x chunk, window] @ [window, value] product
        o = jnp.matmul(p.astype(kw.dtype).reshape(h * t, window),
                       kw[:, :value_width],
                       preferred_element_type=jnp.float32)
        o = o.reshape(h, t, value_width).transpose(1, 0, 2).astype(q.dtype)
        # the row's own slots; what lies past its length goes nowhere
        return out.at[jnp.where(i < query_lens[r], idx, t)].set(
            o, mode="drop")

    return jax.lax.fori_loop(
        0, jnp.sum(is_chunk.astype(jnp.int32)), attend,
        jnp.zeros((t, h, value_width), q.dtype))


def latent_ragged_attention(q, pages, block_tables, context_lens,
                            query_lens, scale, value_width):
    """The mixed step's attention over latent pages the step has just
    written, on the step's flat token axis: decode rows
    (``query_lens == 1``) through the kernel, which reads the one query
    at each row's first slot; chunk rows through the per-row
    composition; inactive rows nowhere.

    q [T, H, width] → [T, H, value_width]; the padded tail holds zeros."""
    t = q.shape[0]
    is_decode = query_lens == 1
    starts = jnp.cumsum(query_lens) - query_lens
    # the Pallas call stays outside the scope: the TPU compiler names a
    # Mosaic custom call after its innermost scope, and readers key on
    # the kernel's own name
    dec = latent_paged_decode(
        q[jnp.minimum(starts, t - 1)], pages, block_tables,
        jnp.where(is_decode, context_lens + 1, 0), scale, value_width)
    with jax.named_scope("latent_attention"):
        out = latent_chunk_attention(q, pages, block_tables, context_lens,
                                     query_lens, scale, value_width)
        return out.at[jnp.where(is_decode, starts, t)].set(
            dec.astype(out.dtype), mode="drop")
