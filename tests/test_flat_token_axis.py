"""The mixed step's flat token axis (serving/engine_core.py's packer,
serving/programs.build_mixed_step, ops/pallas/ragged_paged_attention's
``ragged_rows`` / ``rows_from_flat``).

The step program takes ``ids[token_budget]``: the rows' tokens laid end
to end in slot order, the tail padded; ``qlens``, ``ctx``, the tables and
every sampling array stay per row.  Three layers of coverage:

* the index arithmetic — for named compositions (decode only, one chunk,
  decode rows and a chunk that exhaust the budget, a starved chunk row, a
  speculating row with drafts, an inactive gap between live slots) every
  real slot maps to the row and offset ``qlens`` say, nothing past
  ``sum(qlens)`` is valid, and the per-row view round-trips;
* the packer — every step ``EngineCore`` hands the program over fuzzed
  traffic (plain and speculating cores) holds, at each row's span, the
  tokens that sit at absolute positions ``ctx .. ctx + qlens - 1`` of that
  request's final sequence, zeros past ``sum(qlens)``, at most
  ``token_budget`` real tokens, and the StepLog's ``token_slots``;
* the counter — ``token_slots`` on every serving record, 0 on records
  that launch no step (tests/benchmarks/test_bench_token_slot_fill.py
  has the reader).

Token identity of the streams (greedy and seeded against ``generate()``,
chunk boundaries, prefix hits, replay, speculation, grammar and LoRA rows
beside plain ones, the ``--mp`` rehearsal) is held by the modules that
already compared them — tests/test_ragged_serving.py,
test_speculative_serving.py, test_structured.py, test_adapters.py,
test_sharded_serving.py, test_latent_moe.py — through the one step
program there is.
"""
import itertools
import random

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.ops.pallas.ragged_paged_attention import (
    ragged_rows, rows_from_flat)
from paddle_infer_tpu.serving import EngineCore, RequestState
from paddle_infer_tpu.serving import request as request_mod

T = 16          # the token budget of every core and composition below

# qlens per slot; each sums to at most T
COMPOSITIONS = {
    "decode_only": [1, 1, 1, 1],
    "one_chunk": [0, 16, 0, 0],
    "decode_and_chunk_exhaust_the_budget": [1, 1, 14, 0],
    "starved_chunk_row": [1, 14, 0, 1],        # slot 2 waits: budget spent
    "speculating_rows_with_drafts": [4, 1, 3, 0],
    "inactive_gap_between_live_slots": [1, 0, 0, 5],
    "nothing_alive": [0, 0, 0, 0],
}


@pytest.mark.parametrize("name", sorted(COMPOSITIONS))
def test_ragged_rows_place_every_slot_where_qlens_say(name):
    qlens = np.asarray(COMPOSITIONS[name], np.int32)
    ctx = np.asarray([7, 0, 21, 3], np.int32)
    starts, row, offset, valid = map(np.asarray,
                                     ragged_rows(jnp.asarray(qlens), T))
    n = int(qlens.sum())
    want_row = np.repeat(np.arange(4), qlens)
    want_off = np.concatenate([np.arange(q) for q in qlens] + [[]])
    np.testing.assert_array_equal(starts, np.cumsum(qlens) - qlens)
    np.testing.assert_array_equal(valid, np.arange(T) < n)
    np.testing.assert_array_equal(row[:n], want_row)
    np.testing.assert_array_equal(offset[:n], want_off)
    # pad slots index in range and sit at position 0
    assert row.min() >= 0 and row.max() <= 3
    np.testing.assert_array_equal(offset[n:], 0)
    pos = np.where(valid, ctx[row] + offset, 0)
    np.testing.assert_array_equal(
        pos[:n], np.concatenate([c + np.arange(q)
                                 for c, q in zip(ctx, qlens)] + [[]]))
    np.testing.assert_array_equal(pos[n:], 0)

    # the per-row view holds each row's tokens at [b, :qlens[b]] and
    # gathers back to the flat axis unchanged on every real slot
    flat = jnp.arange(100, 100 + T, dtype=jnp.int32)[:, None] \
        * jnp.ones((1, 3), jnp.int32)
    per_row = np.asarray(rows_from_flat(flat, jnp.asarray(starts), T))
    assert per_row.shape == (4, T, 3)
    for b, q in enumerate(qlens):
        np.testing.assert_array_equal(
            per_row[b, :q, 0], 100 + starts[b] + np.arange(q))
    np.testing.assert_array_equal(per_row[row, offset][:n],
                                  np.asarray(flat)[:n])


# ---------------------------------------------------------------- packer

@pytest.fixture(scope="module", autouse=True)
def _meshless():
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
    from paddle_infer_tpu.observability import get_compile_log
    get_compile_log().reset()
    yield
    get_compile_log().reset()


@pytest.fixture(scope="module")
def engine():
    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    m.eval()
    return PagedGenerationEngine(m, page_size=8)


CORE_SHAPE = dict(max_batch=4, max_model_len=48, token_budget=T,
                  prefill_chunk=T, enable_prefix_cache=True,
                  prefix_cache_headroom_pages=12)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _watch_steps(core, engine, monkeypatch):
    """Record, for every launch of the step program, the packed arrays
    and which request sat in which slot."""
    seen = []
    real = engine.run_paged_program

    def spy(key, builder, *args):
        if key[0] == "serve-step":
            # one packed buffer, reused by the packer: copy, then view
            f = core._step_in.views(np.array(args[0]))
            ids, qlens, ctx = f["ids"], f["qlens"], f["ctx"]
            spec = (f["spec"] != 0 if core._spec_window > 1
                    else np.zeros_like(qlens, bool))
            seen.append(dict(
                ids=ids, qlens=qlens, ctx=ctx, spec=spec,
                reqs=[None if s is None else s["req"]
                      for s in core._slots]))
        return real(key, builder, *args)

    monkeypatch.setattr(engine, "run_paged_program", spy)
    return seen


@pytest.mark.parametrize("speculate", [False, True],
                         ids=["plain", "speculating"])
def test_packer_lays_rows_end_to_end_over_fuzzed_traffic(engine, monkeypatch,
                                                         speculate):
    request_mod._rid_counter = itertools.count(9100)
    kw = dict(speculate=True, num_draft_tokens=3) if speculate else {}
    core = EngineCore(engine, **CORE_SHAPE, **kw)
    seen = _watch_steps(core, engine, monkeypatch)
    try:
        rng = random.Random(5)
        # repeated prompts, so that the radix tree proposes drafts and
        # warm admissions chunk only a suffix, among fresh long ones that
        # take whole budgets and starve the chunk row behind them
        pool = [_prompt(200 + i, n)
                for i, n in enumerate([3, 5, 11, 17, 26, 38])]
        live, arrivals, steps = [], 0, 0
        while arrivals < 28 or any(not r.done for r, _ in live):
            if (arrivals < 28 and core.queue_depth < 3
                    and rng.random() < 0.5):
                ids = (rng.choice(pool) if rng.random() < 0.5 else
                       _prompt(300 + arrivals, rng.choice([17, 26, 38])))
                (r,) = core.submit(ids, GenerationConfig(
                    max_new_tokens=rng.randint(2, 9)))
                live.append((r, ids))
                arrivals += 1
            core.run_once()
            steps += 1
            assert steps < 3000, "traffic never drained"
        assert all(r.state is RequestState.DONE for r, _ in live)
        final = {id(r): np.concatenate([ids, np.asarray(r.result())])
                 for r, ids in live}

        kinds = set()
        for step in seen:
            ids, qlens, ctx = step["ids"], step["qlens"], step["ctx"]
            assert ids.shape == (T,) and qlens.shape == (4,)
            n = int(qlens.sum())
            assert n <= T
            np.testing.assert_array_equal(ids[n:], 0)
            starts = np.cumsum(qlens) - qlens
            for i, req in enumerate(step["reqs"]):
                q = int(qlens[i])
                if req is None:
                    assert q == 0
                    continue
                got = ids[starts[i]:starts[i] + q]
                seq = final[id(req)]
                if step["spec"][i]:
                    # [last_tok, d_1..d_k]: the fed token is the
                    # sequence's; a draft is only a proposal
                    assert q > 1
                    assert got[0] == seq[ctx[i]]
                    kinds.add("spec")
                else:
                    np.testing.assert_array_equal(
                        got, seq[ctx[i]:ctx[i] + q])
                    kinds.add("decode" if q == 1 else
                              "chunk" if q > 1 else "waiting")
            live_q = qlens[qlens > 0]
            if (live_q == 1).any() and (live_q > 1).any() and n == T:
                kinds.add("budget_exhausted")
            gaps = np.flatnonzero(qlens > 0)
            if gaps.size and (qlens[gaps[0]:gaps[-1] + 1] == 0).any():
                kinds.add("gap")
        # the run held every composition the layout has to place
        want = {"decode", "chunk", "budget_exhausted", "gap", "waiting"}
        if speculate:
            want.add("spec")
        assert want <= kinds, want - kinds

        records = [r for r in core.steplog.records()
                   if r["kind"] in ("mixed", "prefill", "decode")]
        assert len(records) == len(seen)
        for rec, step in zip(records, seen):
            assert rec["token_slots"] == T
            assert (rec["decode_rows"] + rec["prefill_chunk_tokens"]
                    + rec["draft_tokens"]) == int(step["qlens"].sum())
    finally:
        core.close()


# --------------------------------------------------------------- counter

def test_token_slots_is_zero_on_records_that_launch_no_step(engine):
    core = EngineCore(engine, **CORE_SHAPE)
    try:
        (r,) = core.submit(_prompt(1, 9), GenerationConfig(max_new_tokens=3))
        for _ in range(50):
            if r.done:
                break
            core.run_once()
        core.steplog.record("evict")
        by_kind = {}
        for rec in core.steplog.records():
            by_kind.setdefault(rec["kind"], set()).add(rec["token_slots"])
        assert by_kind.pop("evict") == {0}
        assert by_kind and all(v == {T} for v in by_kind.values()), by_kind
    finally:
        core.close()
