"""``decode_grid_steps_mean``: the reader (``steplog_mean_where``) on
made-up step records, the entry with the latent cells that read it, and a
rehearsal of each on the CPU."""
import time

import jax
import pytest

from benchmarks import run
from benchmarks.evidence import Evidence
from benchmarks.readers import steplog_mean_where

from conftest import load_data

NAME = "decode_grid_steps_mean"
SPEC = {"reader": "steplog_mean_where",
        "args": {"field": "decode_grid_steps", "where": "decode_rows"}}
# the cells whose model runs the latent decode kernel, a tiny one of each
CELLS = {"axk1-ep16.ragchat": "tiny-axk1.json",
         "xing4-d7.reasoning": "tiny-xing4.json"}


def _ev(steps):
    return Evidence(config={}, traffic={}, cell={}, device_kind="cpu",
                    chips=1, setup_s=0.0, w0=0.0, w1=10.0, steps=steps)


def _step(kind="decode", failed=False, **fields):
    return dict(kind=kind, failed=failed, **fields)


def test_the_mean_is_over_the_steps_with_a_decode_row():
    steps = [_step(decode_rows=3, decode_grid_steps=24),
             _step("mixed", decode_rows=2, decode_grid_steps=40),
             # none counts: a chunk alone launches one grid step over a
             # dead row and books 0; a failed step; a record of no launch
             _step("prefill", decode_rows=0, decode_grid_steps=0),
             _step(failed=True, decode_rows=9, decode_grid_steps=900),
             _step("evict", decode_rows=1, decode_grid_steps=7)]
    read = steplog_mean_where.read
    assert read(_ev(steps), **SPEC["args"]) == pytest.approx(32.0)
    # a mean over every serving step would read (24 + 40 + 0) / 3
    assert read(_ev(steps), "decode_grid_steps", "decode_grid_steps") \
        == pytest.approx(32.0)


@pytest.mark.parametrize("steps", [
    [], [_step("evict", decode_rows=1, decode_grid_steps=7)],
    [_step("prefill", decode_rows=0, decode_grid_steps=0)],
    # a model with no latent pages books 0 on every record
    [_step(decode_rows=4, decode_grid_steps=0)],
    # a program older than the field
    [_step(decode_rows=4)], [_step(decode_grid_steps=8)]],
    ids=["no_steps", "no_serving_step", "no_decode_row", "every_one_zero",
         "no_field", "no_where_field"])
def test_nothing_to_read_is_none(steps):
    assert steplog_mean_where.read(_ev(steps), **SPEC["args"]) is None


def test_the_entry_names_the_latent_cells(benchmark_json):
    (entry,) = [dict(m) for m in benchmark_json["per_layer"]
                if m["name"] == NAME]
    cells = entry.pop("workloads")
    assert set(CELLS) <= set(cells) and "mistral-d12.chat" not in cells
    assert entry == {"name": NAME, "unit": "steps", "better": "lower",
                     "source": "program_counter", "layer": "latent attention",
                     "moves": "itl_p95_ms"}
    assert run.load_json("layer_metrics", NAME + ".json") == SPEC


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_rehearsal_of_the_cell_reads_it(cell, benchmark_json, tmp_path):
    from benchmarks.readers.steplog_stat import serving_steps

    ctx = run.Context(load_data(CELLS[cell]), load_data("tiny-chat.json"),
                      {"rate_rps": 4.0}, 1, 2 ** 31 + 42, 2.0, 0,
                      jax.devices()[:1], time.monotonic(),
                      say=lambda s: None, trace_dir=str(tmp_path / "trace"))
    ev = run.run_cell(ctx)["evidence"]
    got = run.read_metrics(benchmark_json["per_layer"], "layer_metrics", ev,
                           cell)[NAME]
    assert got["unit"] == "steps"
    with_rows = [s for s in serving_steps(ev) if s["decode_rows"] > 0]
    # rows x the longest one's walk: at least a grid step a decode row
    assert all(s["decode_grid_steps"] >= s["decode_rows"] for s in with_rows)
    assert got["value"] == pytest.approx(
        sum(s["decode_grid_steps"] for s in with_rows) / len(with_rows))
    # the chat cell's model has no latent pages, and does not list it
    assert NAME not in run.read_metrics(
        benchmark_json["per_layer"], "layer_metrics", ev, "mistral-d12.chat")
