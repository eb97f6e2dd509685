"""The harness from just after its look for a chip to its result, on the
CPU at a tiny size: sound runs come out correct, and a timed path broken
underneath comes out not correct."""
import io
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, xplane
from benchmarks.systems import ernie_train, llama_serving

from conftest import ROOT, load_data

PRETRAIN = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                       "pretrain.json")))


def _ctx(config, traffic, cell, seed, seconds, tmp_path, trace=0, say=print):
    return run.Context(config, traffic, cell, 1, seed, seconds, trace,
                       jax.devices()[:1], time.monotonic(), say=say,
                       trace_dir=str(tmp_path / "trace"))


@pytest.fixture(scope="module")
def chat_result(tmp_path_factory):
    ctx = _ctx(load_data("tiny-llama.json"), load_data("tiny-chat.json"),
               {"rate_rps": 4.0}, 2 ** 31 + 77, 2.0,
               tmp_path_factory.mktemp("chat"))
    return run.run_cell(ctx)


def test_open_loop_cell_runs_and_is_correct(chat_result):
    res = chat_result
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 8            # floor(4.0 x 2.0): the whole deck
    ev = res["evidence"]
    assert ev.compiles_in_window == 0
    assert ev.setup_s > 0 and ev.steps and ev.queue_waits


def test_open_loop_metrics_read_from_data_files(chat_result, benchmark_json):
    ev = chat_result["evidence"]
    e2e = run.read_metrics(benchmark_json["end_to_end"], "e2e_metrics", ev,
                           "mistral-d12.chat")
    assert set(e2e) == {"itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    layer = run.read_metrics(benchmark_json["per_layer"], "layer_metrics",
                             ev, "mistral-d12.chat")
    # not traced: the readers of the device trace found nothing to read
    assert "device_idle_share" not in layer
    assert "step_roofline_share_counted.chat" not in layer
    assert layer["compiles_in_window"]["value"] == 0
    assert 0 < layer["token_slot_fill_share"]["value"] < 100
    assert layer["padded_slot_share"]["value"] == pytest.approx(
        100 - layer["token_slot_fill_share"]["value"])
    assert 0 <= layer["chunk_step_gap_share"]["value"] <= 100
    # a loaded test machine runs late; the chip run reads 1.6 ms
    assert 0 <= layer["gen_lateness_p99_ms"]["value"] < 2000
    assert {"ttft_p50_ms", "ttft_mean_ms", "ttft_p90_ms", "itl_mean_ms",
            "itl_p99_ms"} <= set(layer)


def test_result_line_has_the_contract_keys(chat_result, benchmark_json):
    line = run.result_line(dict(chat_result), benchmark_json,
                           "mistral-d12.chat", 0, "cpu", 1)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    # last: each number compared beside its limit
    assert line["check"] == {"widest_logit_gap": [
        pytest.approx(0.0, abs=0.007), 0.007]}
    said = io.StringIO()
    run.say_check(line, file=said)
    assert said.getvalue() == "check: widest_logit_gap %s (limit 0.007)\n" \
        % line["check"]["widest_logit_gap"][0]
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes",
            "allocator_peak_bytes", "program_temp_bytes"} == set(
                line["device"])
    # the compiled step's temporaries as the StepLog recorded them: the
    # largest over the window's steps (null where the program had no
    # memory analysis to offer: its program_cost finds none once an earlier
    # test of the process left a multi-device fleet mesh behind)
    ev = chat_result["evidence"]
    largest = max(s["program_temp_bytes"] for s in ev.steps)
    assert line["device"]["program_temp_bytes"] == (largest or None)
    assert ev.allocator_peak_bytes is None      # a CPU reports none
    json.dumps(line)


def _said_what_the_trace_cost(said, ev):
    """One line once the trace is reduced: events, spans, seconds to load,
    seconds to reduce."""
    assert [s for s in said if s.startswith("trace: ")] \
        == [xplane.cost_line(ev.trace)]
    cost = ev.trace["cost"]
    assert cost["device_events"] > 0 and cost["host_spans"] > 0
    assert 0 < cost["load_s"] < 60 and 0 <= cost["reduce_s"] < 60
    assert 0 < cost["stop_s"] < 60
    assert 0 < ev.trace["busy_s"] <= ev.trace["window_s"]
    assert ev.trace["device_ops"] and ev.trace["idle_gaps"]


def test_a_traced_serving_run_says_what_its_trace_cost(tmp_path,
                                                       cpu_trace_loader):
    said = []
    ctx = _ctx(load_data("tiny-llama.json"), load_data("tiny-chat.json"),
               {"rate_rps": 4.0}, 2 ** 31 + 31, 1.0, tmp_path, trace=1,
               say=said.append)
    res = run.run_cell(ctx)
    assert res["correct"] is True and res["failed"] == 0
    ev = res["evidence"]
    if not ev.trace["devices"]:
        pytest.skip("the CPU profiler wrote no operations to read")
    _said_what_the_trace_cost(said, ev)
    gaps = dict(ev.trace["idle_gaps"])
    assert gaps.get("engine.launch", 0) + gaps.get("engine.wait", 0) > 0


def test_a_traced_run_whose_trace_is_empty_still_prints_its_line(
        tmp_path, monkeypatch, benchmark_json, capsys):
    """No request was alive while the profiler ran, so no operation is in
    the trace: the run still gives its line, the device all idle, the
    breakdown empty, the metrics that divide by a kernel's seconds left
    out, and the command's exit code is 0."""
    def load(trace_dir):
        return {}, [("engine.step", 10.0, 0.25), ("engine.admit", 10.0, 0.05)]

    monkeypatch.setattr(xplane, "load", load)
    said = []
    ctx = _ctx(load_data("tiny-llama.json"), load_data("tiny-chat.json"),
               {"rate_rps": 4.0}, 2 ** 31 + 33, 1.0, tmp_path, trace=1,
               say=said.append)
    res = run.run_cell(ctx)
    assert res["correct"] is True and res["failed"] == 0
    tr = res["evidence"].trace
    assert tr["devices"] == 0 and tr["busy_s"] == 0.0
    # traced: the window's last second (here all of it), stopped at its end
    assert res["evidence"].w1 - 1.0 <= tr["t0"] < tr["t1"]
    assert tr["t1"] == pytest.approx(res["evidence"].w1, abs=0.25)
    line = run.result_line(res, benchmark_json, "mistral-d12.chat", 1,
                           "cpu", 1)
    assert line["metrics"]["device_idle_share"] == {"value": 100.0,
                                                    "unit": "%"}
    assert "step_roofline_share_counted.chat" not in line["metrics"]
    assert "paged_attention_roofline_share.chat" not in line["metrics"]
    assert line["device"]["busy_s"] == 0.0
    assert line["device"]["window_s"] == pytest.approx(1.0, abs=0.25)
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert line["breakdown"]["device_ops"] == []
    assert gaps["engine.admit"] == pytest.approx(0.05)
    assert gaps["engine.step"] == pytest.approx(0.20)
    assert sum(gaps.values()) == pytest.approx(line["device"]["window_s"])
    assert [s for s in said if s.startswith("trace: 0 device events, 2 ")]
    json.dumps(line)
    # the line prints the ten largest of each; the reduction keeps every
    # label, so that its gaps sum to the idle seconds
    from benchmarks import spans
    labels = spans.GAP_SPANS + (spans.OUTSIDE,)
    every = [[name, 1.0 / (i + 1)] for i, name in enumerate(labels)]
    tr["idle_gaps"], kept = every, tr["idle_gaps"]
    cut = run.result_line(res, benchmark_json, "mistral-d12.chat", 1, "cpu",
                          1)["breakdown"]["idle_gaps"]
    assert len(labels) > 10 and cut == every[:10]
    tr["idle_gaps"] = kept

    # and through the command itself, the look for a chip stepped over
    monkeypatch.setattr(run, "run_cell", lambda ctx: res)
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    tpu = type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    assert run.main(["--workload", "mistral-d12.chat", "--seed", "1",
                     "--seconds", "1", "--trace", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["breakdown"] == {"device_ops": [],
                                "idle_gaps": line["breakdown"]["idle_gaps"]}
    assert out["metrics"]["device_idle_share"]["value"] == 100.0


class _AlteredStream:
    """A request whose tokens are altered where they are produced."""

    def __init__(self, req, vocab):
        self._req, self._vocab = req, vocab

    def stream(self, timeout=None):
        for chunk in self._req.stream(timeout=timeout):
            yield (np.asarray(chunk) + 1) % self._vocab

    def result(self, timeout=None):
        return self._req.result(timeout)


class _BrokenServing(llama_serving.System):
    def submit(self, ids, max_new):
        return _AlteredStream(super().submit(ids, max_new),
                              int(self.config["vocab_size"]))


def test_altered_tokens_come_out_not_correct(tmp_path):
    ctx = _ctx(load_data("tiny-llama.json"), load_data("tiny-chat.json"),
               {"rate_rps": 4.0}, 9, 1.5, tmp_path)
    broken = type("M", (), {"System": _BrokenServing})
    res = run.run_cell(ctx, system_mod=broken)
    assert res["failed"] == 0 and res["correct"] is False


def test_training_cell_runs_and_is_correct(tmp_path, benchmark_json,
                                           cpu_trace_loader):
    said = []
    ctx = _ctx(load_data("tiny-ernie.json"), PRETRAIN, {}, 2 ** 31 + 3, 1.0,
               tmp_path, trace=1, say=said.append)
    res = run.run_cell(ctx)
    assert res["correct"] is True and res["attempted"] >= 3
    ev = res["evidence"]
    assert ev.compiles_in_window == 0
    e2e = run.read_metrics(benchmark_json["end_to_end"], "e2e_metrics", ev,
                           "ernie-base.pretrain")
    assert set(e2e) == {"train_tokens_per_s", "setup_s"}
    line = run.result_line(res, benchmark_json, "ernie-base.pretrain", 0,
                           "cpu", 1)
    # two sources, two fields: the step's temporaries are the program's own
    # memory analysis, the allocator's peak is the backend's (none on a CPU)
    assert line["device"]["program_temp_bytes"] > 0
    assert line["program"]["flags"] == {"use_autotune": True,
                                        "autotune_cache_file": ""}
    assert line["program"]["autotune_winners"] == {}
    json.dumps(line)
    if ev.trace["devices"]:     # traced: the CPU profiler wrote operations
        _said_what_the_trace_cost(said, ev)


class _FrozenTraining(ernie_train.System):
    """A step that returns its state unchanged."""

    def call(self, batch):
        keep = jax.tree_util.tree_map(jnp.copy, (self.step.params,
                                                 self.step.opt_state))
        loss = super().call(batch)
        self.step.params, _ = keep
        return loss


def test_a_step_that_keeps_its_state_comes_out_not_correct(tmp_path):
    ctx = _ctx(load_data("tiny-ernie.json"), PRETRAIN, {}, 4, 0.5, tmp_path)
    frozen = type("M", (), {"System": _FrozenTraining,
                            "make_batches": staticmethod(
                                ernie_train.make_batches)})
    res = run.run_cell(ctx, system_mod=frozen)
    assert res["correct"] is False


def test_four_chip_cell_places_on_four_of_the_eight_devices():
    """A ``chips: 4`` cell is data: ``deployment.mp`` 4 shards the seeded
    weights as they are made (never whole on device 0) and serves."""
    config = load_data("tiny-llama.json")
    config["deployment"] = dict(config["deployment"], mp=4, chips=4)
    devices = jax.devices()
    assert len(devices) >= 8
    system = llama_serving.System(config, devices[:4], 3, False)
    system.build()
    try:
        used = set()
        for name, p in system.engine._model.named_parameters():
            arr = p._data
            used |= {d.id for d in arr.sharding.device_set}
            if name.endswith("qkv_proj.weight"):
                assert len(arr.sharding.device_set) == 4
                assert arr.addressable_shards[0].data.shape[1] \
                    == arr.shape[1] // 4
        assert used == {d.id for d in devices[:4]}
        toks = system.submit(np.arange(3, 40, dtype=np.int32), 5).result(
            timeout=300)
        assert len(toks) == 5
    finally:
        system.free()
