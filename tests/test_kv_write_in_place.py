"""The token writers store K/V page by page (``_write_token_spans``: gather
the touched pages, replace their written slots, put them back along the
pool's leading dimension) instead of the per-token scatter
``pages.at[page_idx, :, slot].set(kv)``, which the TPU compiler serves by
relayouting the whole pool twice.  The per-token scatter stays here as the
oracle: every page but the scratch page must hold the same bits, on fp and
int8 pools (payload and scales), for every way a span can lie in its pages.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_infer_tpu.ops.pallas import paged_attention as PA
from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

PAGE, H, D, MAX_PAGES = 4, 2, 8, 4
WINDOW = PAGE * MAX_PAGES


# ------------------------------------------------------------------ oracle
# the writers as they were before the in-place write, kept verbatim

def _oracle_quantized_scatter(pages, page_idx, slot, kv):
    payload, scales = pages
    kvf = kv.astype(jnp.float32)
    tok = jnp.maximum(jnp.max(jnp.abs(kvf), axis=-1) / PA._QMAX,
                      PA.KV_SCALE_EPS)
    cand = jnp.where((slot == 0)[..., None], tok, -1.0)
    fresh = jnp.full(scales.shape, -1.0, jnp.float32) \
        .at[page_idx].max(cand)
    scales = jnp.where(fresh > 0, fresh, scales)
    sc = scales[page_idx]
    q = jnp.clip(jnp.round(kvf / sc[..., None]), -PA._QMAX, PA._QMAX) \
        .astype(jnp.int8)
    return payload.at[page_idx, :, slot].set(q), scales


def _oracle_scatter(pages, page_idx, slot, kv):
    if PA.is_quantized(pages):
        return _oracle_quantized_scatter(pages, page_idx, slot, kv)
    return pages.at[page_idx, :, slot].set(kv.astype(pages.dtype))


def _oracle_ragged(pages, block_tables, kv, context_lens, query_lens,
                   scratch_page):
    c = kv.shape[1]
    max_pages = block_tables.shape[1]
    i = jnp.arange(c, dtype=jnp.int32)[None]
    pos = context_lens[:, None] + i
    valid = i < query_lens[:, None]
    safe_pos = jnp.where(valid, pos, 0)
    page_idx = jnp.take_along_axis(
        block_tables, jnp.clip(safe_pos // PAGE, 0, max_pages - 1), axis=1)
    page_idx = jnp.where(valid, page_idx,
                         jnp.asarray(scratch_page, jnp.int32))
    slot = jnp.where(valid, safe_pos % PAGE, i % PAGE)
    return _oracle_scatter(pages, page_idx, slot, kv)


def _oracle_chunk(pages, block_tables, kv, offsets):
    s = kv.shape[1]
    pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    page_idx = jnp.take_along_axis(block_tables, pos // PAGE, axis=1)
    return _oracle_scatter(pages, page_idx, pos % PAGE, kv)


def _oracle_token(pages, block_tables, kv, positions):
    page_idx = jnp.take_along_axis(
        block_tables, (positions // PAGE)[:, None], axis=1)[:, 0]
    return _oracle_scatter(pages, page_idx, positions % PAGE, kv)


# ------------------------------------------------------------------- cases

def _pool(rng, rows, quantized):
    """A pool already full of other bits (an untouched slot that changed
    would show), one table of its own pages per row in shuffled order,
    and the scratch page no table maps."""
    num_pages = rows * MAX_PAGES + 2
    scratch = num_pages - 1
    order = rng.permutation(num_pages - 1)[:rows * MAX_PAGES]
    tables = jnp.asarray(order.reshape(rows, MAX_PAGES), jnp.int32)
    shape = (num_pages, H, PAGE, D)
    if quantized:
        pool = (jnp.asarray(rng.randint(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(0.01, 0.1, shape[:2]), jnp.float32))
    else:
        pool = jnp.asarray(rng.randn(*shape), jnp.float32)
    return pool, tables, scratch


def _assert_same_pool(got, want, scratch):
    if PA.is_quantized(want):
        # the scale protocol is untouched: scales agree on every page,
        # the scratch page's included
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        got, want = got[0], want[0]
    keep = np.arange(want.shape[0]) != scratch
    np.testing.assert_array_equal(np.asarray(got)[keep],
                                  np.asarray(want)[keep])


# (context_lens, query_lens) of a ragged step with chunk capacity 6
RAGGED = {
    "decode_rows_slot0_mid_last": ([4, 6, 7, 0], [1, 1, 1, 1]),
    "chunk_from_slot0": ([0, 4, 8], [6, 5, 3]),
    "chunk_from_mid_page": ([1, 6, 9], [6, 4, 2]),
    "chunk_from_last_slot": ([3, 7, 11], [6, 2, 5]),
    "chunk_ends_on_page_edge": ([2, 4, 5], [6, 4, 3]),
    "row_in_last_table_page": ([12, 10, 15], [4, 6, 1]),
    "inactive_rows": ([0, 5, 9], [0, 0, 0]),
    "mixed_decode_chunk_inactive": ([7, 3, 0, 13], [1, 5, 0, 3]),
}
# (offsets, chunk length) of the legacy prefix-prefill writer
CHUNK = {
    "from_slot0": ([0, 4, 8], 6),
    "from_mid_page": ([1, 6, 9], 6),
    "from_last_slot": ([3, 7, 11], 5),
    "ends_on_page_edge": ([2, 6, 10], 6),
    "ends_in_last_table_page": ([8, 9, 10], 6),
    "page_aligned_whole_pages": ([0, 4, 8], 8),
    "one_token": ([3, 4, 15], 1),
}
# positions of the decode writer
TOKEN = {
    "slot0": [0, 4, 12],
    "mid_page": [1, 6, 9],
    "last_slot": [3, 7, 15],
    "mixed": [0, 5, 15, 8],
}


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("case", sorted(RAGGED))
def test_write_ragged_pages_bits_equal_the_per_token_scatter(case,
                                                             quantized):
    ctx, qlens = (jnp.asarray(a, jnp.int32) for a in RAGGED[case])
    rng = np.random.RandomState(len(case))
    pool, tables, scratch = _pool(rng, len(ctx), quantized)
    kv = jnp.asarray(rng.randn(len(ctx), 6, H, D), jnp.float32)
    got = RPA.write_ragged_pages(pool, tables, kv, ctx, qlens, scratch)
    want = _oracle_ragged(pool, tables, kv, ctx, qlens, scratch)
    _assert_same_pool(got, want, scratch)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_speculative_rewrite_of_the_same_positions(quantized):
    """A verify row writes ``ctx .. ctx+k``; after a rejection the next
    step writes the same positions again (here from a lower context, over
    a page edge and across slot 0, so the int8 scale is re-seeded)."""
    rng = np.random.RandomState(7)
    pool, tables, scratch = _pool(rng, 3, quantized)
    got = want = pool
    for ctx, qlens in (([2, 7, 9], [5, 4, 3]), ([3, 7, 10], [4, 4, 1]),
                       ([3, 8, 10], [1, 2, 5])):
        ctx, qlens = jnp.asarray(ctx, jnp.int32), jnp.asarray(qlens,
                                                              jnp.int32)
        kv = jnp.asarray(rng.randn(3, 5, H, D), jnp.float32)
        got = RPA.write_ragged_pages(got, tables, kv, ctx, qlens, scratch)
        want = _oracle_ragged(want, tables, kv, ctx, qlens, scratch)
        _assert_same_pool(got, want, scratch)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("case", sorted(CHUNK))
def test_write_chunk_pages_bits_equal_the_per_token_scatter(case,
                                                            quantized):
    offsets, s = CHUNK[case]
    offsets = jnp.asarray(offsets, jnp.int32)
    rng = np.random.RandomState(len(case))
    pool, tables, _ = _pool(rng, len(offsets), quantized)
    kv = jnp.asarray(rng.randn(len(offsets), s, H, D), jnp.float32)
    got = PA.write_chunk_pages(pool, tables, kv, offsets)
    want = _oracle_chunk(pool, tables, kv, offsets)
    # no pads here: the scratch page is untouched too
    _assert_same_pool(got, want, scratch=-1)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("case", sorted(TOKEN))
def test_write_token_page_bits_equal_the_per_token_scatter(case, quantized):
    positions = jnp.asarray(TOKEN[case], jnp.int32)
    rng = np.random.RandomState(len(case))
    pool, tables, _ = _pool(rng, len(positions), quantized)
    kv = jnp.asarray(rng.randn(len(positions), H, D), jnp.float32)
    got = PA.write_token_page(pool, tables, kv, positions)
    want = _oracle_token(pool, tables, kv, positions)
    _assert_same_pool(got, want, scratch=-1)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_quantized_prompt_write_matches_chunk_write_from_zero(quantized):
    """``write_prompt_pages`` shares the int8 payload write: an aligned
    prompt equals the chunk writer at offset 0 (and, on an fp pool, its
    own whole-page scatter)."""
    rng = np.random.RandomState(3)
    pool, tables, _ = _pool(rng, 2, quantized)
    kv = jnp.asarray(rng.randn(2, 2 * PAGE, H, D), jnp.float32)
    got = PA.write_prompt_pages(pool, tables, kv)
    want = _oracle_chunk(pool, tables, kv, jnp.zeros((2,), jnp.int32))
    _assert_same_pool(got, want, scratch=-1)


def test_a_span_past_the_table_is_dropped_not_wrapped():
    """A row whose chunk would run past its last table page writes what
    fits and nothing else: no page of another row, no unmapped page."""
    rng = np.random.RandomState(11)
    pool, tables, scratch = _pool(rng, 2, quantized=False)
    kv = jnp.asarray(rng.randn(2, 6, H, D), jnp.float32)
    ctx = jnp.asarray([WINDOW - 2, 0], jnp.int32)
    qlens = jnp.asarray([6, 0], jnp.int32)
    out = np.asarray(RPA.write_ragged_pages(pool, tables, kv, ctx, qlens,
                                            scratch))
    want = np.asarray(pool).copy()
    last = int(tables[0, -1])
    want[last, :, 2] = np.asarray(kv[0, 0])
    want[last, :, 3] = np.asarray(kv[0, 1])
    np.testing.assert_array_equal(out, want)
