"""``token_slot_fill_share``: the reader, on made-up step records, and
the entry with the cells that read it."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.evidence import Evidence
from benchmarks.readers import token_slot_fill

from conftest import ROOT, load_data

NAME = "token_slot_fill_share"
CELLS = ("mistral-d12.chat", "axk1-ep16.ragchat", "xing4-d7.reasoning")


def _evidence(steps):
    return Evidence(config={}, traffic={}, cell={},
                    device_kind="TPU v5 lite", chips=1, setup_s=1.0,
                    w0=0.0, w1=10.0, steps=steps)


def _step(**kw):
    return dict(dict(kind="decode", failed=False, decode_rows=0,
                     prefill_chunk_tokens=0, draft_tokens=0,
                     token_slots=64), **kw)


def test_real_tokens_over_the_flat_axis_s_slots():
    steps = [_step(decode_rows=3),
             _step(kind="mixed", decode_rows=2, prefill_chunk_tokens=62),
             _step(kind="prefill", prefill_chunk_tokens=40),
             _step(decode_rows=2, draft_tokens=5),
             # neither counts: a step that failed, a record of no launch
             _step(decode_rows=9, failed=True),
             _step(kind="evict", token_slots=0)]
    assert token_slot_fill.read(_evidence(steps)) == pytest.approx(
        100.0 * (3 + 64 + 40 + 7) / (4 * 64))


@pytest.mark.parametrize("steps", [
    [], [_step(kind="evict", token_slots=0)],
    # the parent's records: a [max_batch, token_budget] slot array and no
    # such field, or the schema's default
    [{k: v for k, v in _step(decode_rows=3).items() if k != "token_slots"}],
    [_step(decode_rows=3, token_slots=0)]],
    ids=["no_steps", "no_serving_step", "no_field", "field_zero"])
def test_nothing_to_read_is_none(steps):
    assert token_slot_fill.read(_evidence(steps)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_entry_names_the_cell(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert cell in entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "step program",
                     "moves": "itl_p95_ms"}
    assert run.load_json("layer_metrics", NAME + ".json") == {
        "reader": "token_slot_fill", "args": {}}


def test_the_served_cell_reads_it_from_the_program_s_records(
        benchmark_json, tmp_path):
    """Through the runner on the CPU: the tiny chat cell's step records
    carry ``token_slots`` and the metric comes out between 0 and 100."""
    import time

    import jax

    ctx = run.Context(load_data("tiny-llama.json"),
                      load_data("tiny-chat.json"), {"rate_rps": 4.0}, 1,
                      2 ** 31 + 91, 1.0, 0, jax.devices()[:1],
                      time.monotonic(), trace_dir=str(tmp_path / "trace"))
    ev = run.run_cell(ctx)["evidence"]
    layer = run.read_metrics(benchmark_json["per_layer"], "layer_metrics",
                             ev, "mistral-d12.chat")
    fill = layer[NAME]["value"]
    assert 0 < fill <= 100
    steps = [s for s in ev.steps if s["kind"] in ("mixed", "decode",
                                                  "prefill")]
    assert steps and all(s["token_slots"] == ev.token_budget for s in steps)
