"""``chunk_step_gap_share``: the reader, on made-up step records, and the
entries with the cells that read them (``.axk1`` is held as PR 27 entered
it: see ``contract_rules.HELD_SUFFIX``)."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.evidence import Evidence
from benchmarks.readers import (chunk_step_gap_share, padded_slot_share,
                                token_slot_fill)

from conftest import ROOT

NAME = "chunk_step_gap_share"
# the cell, and the entry it reads the measurement under
CELLS = {"mistral-d12.chat": NAME, "axk1-ep16.ragchat": NAME + ".axk1",
         "xing4-d7.reasoning": NAME}


def _evidence(steps):
    return Evidence(config={}, traffic={}, cell={},
                    device_kind="TPU v5 lite", chips=1, setup_s=1.0,
                    w0=0.0, w1=10.0, steps=steps)


def _step(**kw):
    return dict(dict(kind="mixed", failed=False, decode_rows=0,
                     prefill_chunk_tokens=0), **kw)


def test_gaps_of_steps_that_hold_a_chunk_over_all_gaps():
    steps = [_step(kind="decode", decode_rows=3),
             _step(decode_rows=2, prefill_chunk_tokens=62),
             _step(decode_rows=5, prefill_chunk_tokens=1),
             _step(kind="decode", decode_rows=10),
             # none counts: a chunk beside no decode row hands out no gap,
             # a step that failed, a record of no launch
             _step(kind="prefill", prefill_chunk_tokens=64),
             _step(decode_rows=9, prefill_chunk_tokens=9, failed=True),
             _step(kind="evict", decode_rows=4, prefill_chunk_tokens=4)]
    assert chunk_step_gap_share.read(_evidence(steps)) == pytest.approx(
        100.0 * (2 + 5) / (3 + 2 + 5 + 10))


@pytest.mark.parametrize("steps,want", [
    ([_step(kind="decode", decode_rows=4)], 0.0),
    ([_step(decode_rows=1, prefill_chunk_tokens=63)], 100.0)],
    ids=["decode_only", "every_step_chunked"])
def test_the_ends_of_the_range(steps, want):
    assert chunk_step_gap_share.read(_evidence(steps)) == want


@pytest.mark.parametrize("steps", [
    [], [_step(kind="evict")], [_step(kind="prefill",
                                      prefill_chunk_tokens=64)]],
    ids=["no_steps", "no_serving_step", "no_decode_row"])
def test_nothing_to_read_is_none(steps):
    assert chunk_step_gap_share.read(_evidence(steps)) is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_entry_names_the_cell(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = CELLS[cell]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert cell in entry.pop("workloads")
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "itl_p95_ms"}
    assert run.load_json("layer_metrics", name + ".json") == {
        "reader": "chunk_step_gap_share", "args": {}}


def test_the_stale_metric_is_retired_or_mended():
    """``padded_slot_share.axk1`` is gone.  ``.chat`` stays, because
    ``tests/test_latent_moe.py`` pins its place among the entries, and
    reads the real axis now: 100 less ``token_slot_fill_share``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"]
            if m["name"].startswith("padded_slot_share")] == [
                "padded_slot_share.chat"]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "padded_slot_share.axk1.json"))
    steps = [_step(kind="decode", decode_rows=10, token_slots=64),
             _step(decode_rows=4, prefill_chunk_tokens=50, token_slots=64)]
    ev = _evidence(steps)
    ev.max_batch, ev.token_budget = 16, 64
    assert padded_slot_share.read(ev) == pytest.approx(50.0)
    assert padded_slot_share.read(ev) == pytest.approx(
        100.0 - token_slot_fill.read(ev))
    assert padded_slot_share.read(_evidence([_step(decode_rows=1)])) is None
