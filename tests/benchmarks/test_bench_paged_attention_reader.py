"""``paged_attention_roofline_share.chat``: its cost function, its reader
and its entry, on the CPU from made-up evidence."""
import json
import os

import pytest

from benchmarks import costs, costs_paged_attention, run
from benchmarks.evidence import Evidence
from benchmarks.readers import paged_attention_roofline

from conftest import ROOT

NAME = "paged_attention_roofline_share.chat"
KERNEL = "ragged_paged_attention"


def _mistral():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-7b-v0.1-d12.json")) as f:
        return json.load(f)


def _evidence(steps, op_seconds):
    return Evidence(
        config=_mistral(), traffic={}, cell={}, device_kind="TPU v5 lite",
        chips=1, setup_s=1.0, w0=0.0, w1=10.0, steps=steps,
        trace={"busy_s": 5.0, "window_s": 6.0, "t0": 0.0, "t1": 6.0,
               "op_seconds": op_seconds})


def test_the_cost_is_the_attention_part_of_the_step_s_cost():
    """What the step's cost counts for attention and nothing else: the
    whole step less the same step with no key attended, no cached token
    read and K/V of no width."""
    cfg, kv = _mistral(), 2 * 8 * 128 * 2
    whole = costs.llama_step_cost(cfg, 70, 9, 4000, kv, 3500)
    bare = costs.llama_step_cost(cfg, 70, 9, 0, 0, 0)
    got = costs_paged_attention.paged_attention_cost(cfg, 70, 4000, kv, 3500)
    assert got["flops"] == whole["flops"] - bare["flops"]
    assert got["bytes"] == whole["bytes"] - bare["bytes"]
    assert got["flops"] == 12 * 4 * 32 * 128 * 4000
    assert got["bytes"] == 12 * kv * (3500 + 70)


def test_share_from_counters_and_the_kernel_s_seconds():
    step = dict(t=1.0, kind="mixed", failed=False, decode_rows=6,
                prefill_chunk_tokens=64, emitted_tokens=6,
                attended_keys=30000, resident_tokens=3000)
    ops = {"custom-call ragged_paged_attention bf16[16,32,64,128]": 0.003,
           "fusion fusion bf16[16,64,14336]": 0.03}
    ev = _evidence([step, dict(step, t=2.0), dict(step, t=7.0)], ops)
    # memory-bound: K and V of 3070 tokens at 8 heads of 128 in 12 layers
    least = 2 * 12 * 4096 * 3070 / 819e9
    assert 12 * 4 * 32 * 128 * 30000 / 197e12 < least / 2
    got = paged_attention_roofline.read(ev, KERNEL)
    assert got == pytest.approx(100 * least / 0.003)
    # a program that serves its attention some other way (the parent of
    # PR 28), a program without the counters, a run that was not traced
    assert paged_attention_roofline.read(
        _evidence([step], {"custom-call run_plain bf16[16,32,128]": 0.2}),
        KERNEL) is None
    bare = {k: v for k, v in step.items() if k != "attended_keys"}
    assert paged_attention_roofline.read(_evidence([bare], ops),
                                         KERNEL) is None
    ev.trace = None
    assert paged_attention_roofline.read(ev, KERNEL) is None


def test_the_entry_names_the_chat_cell():
    """Found by name, not by place: the next PR appends too."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert "mistral-d12.chat" in entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "itl_p95_ms"}
    spec = run.load_json("layer_metrics", NAME + ".json")
    assert spec == {"reader": "paged_attention_roofline",
                    "args": {"kernel": KERNEL}}
