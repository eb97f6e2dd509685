"""The per-layer metrics of a request's finish, of the emit phase's row
loop, of the longest read-back's two halves, of the interpreter's
collections and of the engine thread's time off the CPU: four reducers on
made-up records, the nine metric files, and the nine entries of
``BENCHMARK.json`` that name them, each read by the serving cells its
``workloads`` lists (PR 41 brought files, readers and tests; the entries
waited for PR 43)."""
import time

import jax
import pytest

from benchmarks import run
from benchmarks.evidence import Evidence
from benchmarks.readers import (record_quantile, step_gap_share,
                                steplog_ratio, steplog_window_share)

from conftest import load_data

CHAT, RAGCHAT, REASONING = ("mistral-d12.chat", "axk1-ep16.ragchat",
                            "xing4-d7.reasoning")
CELLS = (("chat", CHAT, "tiny-llama.json"), ("axk1", RAGCHAT, "tiny-axk1.json"),
         ("xing4", REASONING, "tiny-xing4.json"))
KV, SCHED, STEP = "KV lifecycle", "scheduler", "step program"
SPAN, COUNTER = "program_span", "program_counter"
# name, layer, source, unit: each for every cell, all `better: lower`
METRICS = (
    ("finish_stall_ms_p50", KV, SPAN, "ms"),
    ("finish_stall_wall_share", KV, SPAN, "%"),
    ("finish_step_gap_share", KV, COUNTER, "%"),
    ("evict_scanned_nodes_per_block", KV, COUNTER, "nodes"),
    ("emit_rows_ms_per_step", SCHED, SPAN, "ms"),
    ("readback_wait_ms_max", STEP, SPAN, "ms"),
    ("readback_ready_ms_max", STEP, SPAN, "ms"),
    ("gc_pause_ms_max", SCHED, SPAN, "ms"),
    ("host_off_cpu_ms_max", SCHED, COUNTER, "ms"),
)
NINE = tuple(name for name, *_ in METRICS)
# no window of ``xing4-d7.reasoning`` evicts (its pool outlasts 51 s), so
# that cell does not list the ratio: a listed cell has to report
NEVER_EVICTS = {REASONING: {"evict_scanned_nodes_per_block"}}


def _entries(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    return [by_name[name] for name in NINE]


# what a program older than the spans writes (the parent commit, which
# the driver runs under these files): these two and no more
ON_THE_PARENT = ("finish_stall_ms_p50", "readback_wait_ms_max")


def _ev(steps, w0=0.0, w1=10.0):
    return Evidence(config={}, traffic={}, cell={}, device_kind="cpu",
                    chips=1, setup_s=0.0, w0=w0, w1=w1, steps=steps)


def _step(kind="decode", failed=False, **fields):
    return dict(kind=kind, failed=failed, **fields)


def test_record_quantile_reads_one_kind_of_record():
    steps = [_step("evict", wall_s=w) for w in (0.004, 0.002, 0.010, 0.006)]
    steps += [_step("decode", wall_s=9.0), _step("page_copy", wall_s=7.0)]
    read = record_quantile.read
    assert read(_ev(steps), "evict", "wall_s", 0.5, 1000.0) \
        == pytest.approx(5.0)           # between 0.004 and 0.006
    assert read(_ev(steps), "evict", "wall_s", 1.0) == pytest.approx(0.010)
    assert read(_ev(steps), "page_copy", "wall_s", 0.5) == 7.0
    assert read(_ev(steps), "park", "wall_s", 0.5) is None
    assert read(_ev(steps), "evict", "insert_s", 0.5) is None
    assert read(_ev([]), "evict", "wall_s", 0.5) is None


def test_steplog_window_share_divides_by_the_window():
    steps = [_step(release_s=0.25), _step("mixed", release_s=0.0),
             _step(release_s=0.15), _step("evict", release_s=5.0),
             _step(failed=True, release_s=5.0)]
    read = steplog_window_share.read
    assert read(_ev(steps, 2.0, 10.0), "release_s") == pytest.approx(5.0)
    assert read(_ev(steps, 2.0, 10.0), "release_s", scale=1.0) \
        == pytest.approx(0.05)
    assert read(_ev(steps, 3.0, 3.0), "release_s") is None
    assert read(_ev(steps + [_step()]), "release_s") is None   # the parent's
    assert read(_ev([]), "release_s") is None


def test_step_gap_share_weights_steps_by_their_decode_rows():
    steps = [_step(decode_rows=8, finished_rows=0, prefill_chunk_tokens=3),
             _step(decode_rows=6, finished_rows=2, prefill_chunk_tokens=0),
             _step("prefill", decode_rows=0, finished_rows=1,
                   prefill_chunk_tokens=9),
             _step("mixed", decode_rows=2, finished_rows=1,
                   prefill_chunk_tokens=4),
             _step(failed=True, decode_rows=50, finished_rows=1,
                   prefill_chunk_tokens=1),
             _step("evict", decode_rows=0, finished_rows=0,
                   prefill_chunk_tokens=0)]
    read = step_gap_share.read
    assert read(_ev(steps), "finished_rows") == pytest.approx(50.0)
    # over prefill_chunk_tokens it is the reader the chunk steps have
    from benchmarks.readers import chunk_step_gap_share
    assert read(_ev(steps), "prefill_chunk_tokens") \
        == chunk_step_gap_share.read(_ev(steps)) == pytest.approx(62.5)
    assert read(_ev(steps), "gc_gen2") is None
    assert read(_ev([_step(decode_rows=0, finished_rows=1)]),
                "finished_rows") is None
    assert read(_ev([]), "finished_rows") is None


def test_steplog_ratio_is_a_sum_over_a_sum():
    steps = [_step(evict_scanned_nodes=3000, evicted_blocks=3),
             _step(evict_scanned_nodes=0, evicted_blocks=0),
             _step("mixed", evict_scanned_nodes=1100, evicted_blocks=1),
             _step("evict", evict_scanned_nodes=9, evicted_blocks=9)]
    read = steplog_ratio.read
    assert read(_ev(steps), "evict_scanned_nodes", "evicted_blocks") \
        == pytest.approx(1025.0)
    assert read(_ev(steps[1:2]), "evict_scanned_nodes",
                "evicted_blocks") is None          # nothing was evicted
    assert read(_ev(steps), "evict_scanned_nodes", "parked") is None
    assert read(_ev(steps), "scanned", "evicted_blocks") is None
    assert read(_ev([]), "evict_scanned_nodes", "evicted_blocks") is None


def test_the_nine_are_entries_by_name(benchmark_json):
    bench = benchmark_json
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    for m, (name, layer, source, unit) in zip(_entries(bench), METRICS):
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "itl_p95_ms"}
        for _, cell, _ in CELLS:
            assert cell in itl["workloads"]
            assert (cell in m["workloads"]) == (
                name not in NEVER_EVICTS.get(cell, ()))
    # the layers the benchmark named keep their names; the new one is one
    assert {m["layer"] for m in bench["per_layer"]} >= {SCHED, STEP, KV}


def _rehearse(config, seed, tmp_path):
    ctx = run.Context(load_data(config), load_data("tiny-chat.json"),
                      {"rate_rps": 4.0}, 1, seed, 2.0, 0, jax.devices()[:1],
                      time.monotonic(), say=lambda s: None,
                      trace_dir=str(tmp_path / "trace"))
    return run.run_cell(ctx)["evidence"]


@pytest.mark.parametrize("suffix,cell,config", CELLS,
                         ids=[c[0] for c in CELLS])
def test_a_rehearsal_of_the_cell_reads_its_nine(suffix, cell, config,
                                                tmp_path, benchmark_json):
    ev = _rehearse(config, 2 ** 31 + 41, tmp_path)
    entries = _entries(benchmark_json)
    got = run.read_metrics(entries, "layer_metrics", ev, cell)
    listed = {m["name"] for m in entries if cell in m["workloads"]}
    assert listed == set(NINE) - NEVER_EVICTS.get(cell, set())
    # requests finished inside the window; whether the cache had to evict
    # is the pool's to say, and the ratio is left out where it did not
    assert listed - {"evict_scanned_nodes_per_block"} <= set(got) <= listed
    value = lambda name: got[name]["value"]
    assert 0 < value("finish_stall_ms_p50") < 1000
    assert 0 < value("finish_stall_wall_share") < 100
    assert 0 < value("finish_step_gap_share") <= 100
    assert 0 < value("emit_rows_ms_per_step") < 1000
    assert 0 < value("readback_ready_ms_max") <= \
        value("readback_wait_ms_max")
    assert value("gc_pause_ms_max") >= 0
    assert value("host_off_cpu_ms_max") >= 0
    finishes = [s for s in ev.steps if s["kind"] == "evict"]
    steps = [s for s in ev.steps if s["kind"] != "evict"]
    assert sum(s["finished_rows"] for s in steps) == len(finishes) > 0
    # the same records as a program older than the spans wrote them
    new = {"ready_s", "emit_rows_s", "release_s", "finished_rows",
           "insert_s", "evict_s", "evicted_blocks", "evict_scanned_nodes",
           "retained_blocks", "gc_s", "gc_gen2", "cpu_s", "off_cpu_s"}
    ev.steps = [{k: v for k, v in s.items() if k not in new}
                for s in ev.steps]
    old = run.read_metrics(entries, "layer_metrics", ev, cell)
    assert set(old) == set(ON_THE_PARENT)
    assert old == {name: got[name] for name in old}
