"""``chunk_step_gap_share``: the reader, on made-up step records, and the
entry with the cells that read it."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.evidence import Evidence
from benchmarks.readers import (chunk_step_gap_share, padded_slot_share,
                                token_slot_fill)

from conftest import ROOT

NAME = "chunk_step_gap_share"
CELLS = ("mistral-d12.chat", "axk1-ep16.ragchat", "xing4-d7.reasoning",
         "glm5-ep16.longdoc")


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
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert cell in entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "itl_p95_ms"}
    assert run.load_json("layer_metrics", NAME + ".json") == {
        "reader": "chunk_step_gap_share", "args": {}}


def test_the_stale_metric_is_retired_or_mended():
    """``padded_slot_share`` stays for the chat cell alone, because
    ``tests/test_latent_moe.py`` asserts that chat reads it, and reads the
    real axis: 100 less ``token_slot_fill_share``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"].startswith("padded_slot_share")]
    assert entry["name"] == "padded_slot_share"
    assert "mistral-d12.chat" in entry["workloads"]
    steps = [_step(kind="decode", decode_rows=10, token_slots=64),
             _step(decode_rows=4, prefill_chunk_tokens=50, token_slots=64)]
    ev = _evidence(steps)
    ev.max_batch, ev.token_budget = 16, 64
    assert padded_slot_share.read(ev) == pytest.approx(50.0)
    assert padded_slot_share.read(ev) == pytest.approx(
        100.0 - token_slot_fill.read(ev))
    assert padded_slot_share.read(_evidence([_step(decode_rows=1)])) is None
