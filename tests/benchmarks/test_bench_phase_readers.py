"""The readers of the program's step phases and counts
(readers/steplog_phase, step_roofline_counted, steplog_hbm_share) through
the metric files BENCHMARK.json names: the expected number on a recorded
sample, and nothing (never an error) on the records of a program that
has no such fields."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.evidence import Evidence

from conftest import ROOT, load_data

SAMPLE = load_data("steplog_phase_sample.json")
MISTRAL = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "mistral-7b-v0.1-d12.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _roofline_share():
    """The sample's two traced steps by hand: 12 layers of 4096-wide GQA
    (32 query heads, 8 KV heads of 128) and a 14336 SwiGLU, bf16; both
    steps are bound by memory on a v5e (197 TFLOP/s, 819 GB/s)."""
    per_layer = 4096 * (32 + 16) * 128 + 32 * 128 * 4096 + 3 * 4096 * 14336
    weights = 2 * (12 * (per_layer + 2 * 4096) + 4096 * 32000)
    kv = 2 * 8 * 128 * 2
    total = 0.0
    for new, sampled, keys, resident in ((10, 10, 5000, 5010),
                                         (64, 11, 9000, 5074)):
        flops = 2 * new * 12 * per_layer + 4 * 32 * 128 * keys * 12 \
            + 2 * sampled * 4096 * 32000
        nbytes = weights + 12 * kv * (resident + new)
        assert flops / 197e12 < nbytes / 819e9
        total += nbytes / 819e9
    return 100.0 * total / 0.4


# three serving steps; the failed one and the evict are not steps
EXPECTED = {
    "loop_gap_ms_per_step": 1.0,
    "admit_ms_per_step": 2.0,
    "pack_ms_per_step": 2.0,
    "launch_ms_per_step": 5.0,
    "readback_wait_ms_p50": 152.0,
    "host_serial_ms_per_step": (8.0 + 14.0 + 14.0) / 3,
    "h2d_kb_per_step": (14.0 + 14.0 + 16.0) / 3,
    "step_roofline_share_counted.chat": _roofline_share(),
    "step_temp_share": 100.0 * 858993459 / (16 * 1024 ** 3),
}
NEW = [m for m in BENCH["per_layer"] if m["name"] in EXPECTED]


def _evidence(steps, traced=True):
    return Evidence(config=MISTRAL, traffic={}, cell={},
                    device_kind=SAMPLE["device_kind"], chips=1, setup_s=1.0,
                    w0=10.0, w1=61.0, steps=steps,
                    trace=dict(SAMPLE["trace"]) if traced else None)


# the reader each of the nine is read through
READERS = dict({name: "steplog_phase" for name in EXPECTED},
               **{"step_roofline_share_counted.chat": "step_roofline_counted",
                  "step_temp_share": "steplog_hbm_share"})


def test_the_issue_s_nine_metrics_are_declared():
    """By name: each is an entry, the chat cell reads it, it moves what
    that cell is judged on, its file names the reader expected here.
    Where it stands in ``per_layer`` and which other cells read it is not
    this test's."""
    assert {m["name"] for m in NEW} == set(EXPECTED)
    for m in NEW:
        assert "mistral-d12.chat" in m["workloads"]
        assert m["moves"] == "itl_p95_ms"
        assert run.load_json("layer_metrics", m["name"] + ".json")[
            "reader"] == READERS[m["name"]]


@pytest.mark.parametrize("entry", NEW, ids=[m["name"] for m in NEW])
def test_reader_gives_the_expected_number_on_the_sample(entry):
    got = run.read_metrics([entry], "layer_metrics",
                           _evidence(SAMPLE["steps"]), "mistral-d12.chat")
    assert got[entry["name"]]["unit"] == entry["unit"]
    assert got[entry["name"]]["value"] == pytest.approx(
        EXPECTED[entry["name"]], rel=1e-9)


@pytest.mark.parametrize("entry", NEW, ids=[m["name"] for m in NEW])
def test_reader_gives_nothing_on_records_without_the_fields(entry):
    """The parent commit's records: every field the phases added gone."""
    old = ("kind", "failed", "t", "wall_s", "host_s", "decode_rows",
           "prefill_chunk_tokens", "emitted_tokens")
    steps = [{k: s[k] for k in old if k in s} for s in SAMPLE["steps"]]
    assert run.read_metrics([entry], "layer_metrics", _evidence(steps),
                            "mistral-d12.chat") == {}
    assert run.read_metrics([entry], "layer_metrics", _evidence([]),
                            "mistral-d12.chat") == {}


def test_roofline_and_temp_share_need_their_sources():
    by = {m["name"]: m for m in NEW}
    untraced = _evidence(SAMPLE["steps"], traced=False)
    assert run.read_metrics([by["step_roofline_share_counted.chat"]],
                            "layer_metrics", untraced,
                            "mistral-d12.chat") == {}
    # a backend with no memory analysis records 0: nothing to report
    zero = [dict(s, program_temp_bytes=0) for s in SAMPLE["steps"]]
    assert run.read_metrics([by["step_temp_share"]], "layer_metrics",
                            _evidence(zero), "mistral-d12.chat") == {}
    # a device without published peaks has no share either
    ev = _evidence(SAMPLE["steps"])
    ev.device_kind = "cpu"
    assert run.read_metrics([by["step_temp_share"]], "layer_metrics",
                            ev, "mistral-d12.chat") == {}
