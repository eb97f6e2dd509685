import json
import os

import pytest

from benchmarks import spans, xplane

from conftest import DATA


def test_interval_algebra():
    u = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert xplane.total(u) == 5
    assert xplane.complement(u, -1, 10) == [(-1, 0), (3, 5), (7, 10)]
    assert xplane.intersect(u, [(2, 5.5), (6.5, 8)]) == [
        (2, 3), (5, 5.5), (6.5, 7)]
    assert xplane._subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]


def test_op_key_drops_the_instance_number():
    assert xplane.op_key("fusion.123") == "fusion"
    assert xplane.op_key("copy.4") == xplane.op_key("copy.9")
    assert xplane.op_key("all-reduce") == "all-reduce"


def test_reduction_busy_idle_and_gap_attribution():
    # one device: busy 0-1 and 3-4 of the 4 s its operations span (the
    # host's own 5 s is overruled by the trace's clock), a gap of 2 s of which
    # the host spent 0.5 s in the step's launch phase, 0.75 s in its wait,
    # 0.25 s in the step but in no phase, and 0.5 s outside any step
    ops = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("copy.2", 3.0, 0.5),
                             ("all-reduce.3", 3.5, 0.5)]}
    host = [("engine.step", 0.5, 2.0), ("engine.launch", 1.0, 0.5),
            ("engine.wait", 1.5, 0.75)]
    r = xplane.reduce_events(ops, host, 5.0)
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["window_s"] == pytest.approx(4.0)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["collective_s"] == pytest.approx(0.5)
    gaps = dict(r["idle_gaps"])
    assert gaps == {"engine.launch": pytest.approx(0.5),
                    "engine.wait": pytest.approx(0.75),
                    "engine.step": pytest.approx(0.25),
                    spans.OUTSIDE: pytest.approx(0.5)}
    assert r["device_ops"][0] == ["fusion", pytest.approx(1.0)]
    assert r["op_seconds"] == {"fusion": pytest.approx(1.0),
                               "copy": pytest.approx(0.5),
                               "all-reduce": pytest.approx(0.5)}


def test_a_train_step_s_dispatch_is_a_gap_label():
    ops = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("fusion.1", 2.0, 1.0)]}
    r = xplane.reduce_events(ops, [("fleet.train_step", 1.25, 0.5)], 3.0)
    assert dict(r["idle_gaps"]) == {"fleet.train_step": pytest.approx(0.5),
                                    spans.OUTSIDE: pytest.approx(0.5)}


def test_reduction_averages_over_devices():
    ops = {"/device:TPU:0": [("a.1", 0.0, 1.0)],
           "/device:TPU:1": [("a.1", 0.0, 3.0)]}
    r = xplane.reduce_events(ops, [], 4.0)
    assert r["busy_s"] == pytest.approx(2.0) and r["devices"] == 2


def test_no_device_events_reads_as_nothing_busy():
    assert xplane.reduce_events({}, [], 2.0)["busy_s"] == 0.0


def test_recorded_trace_sample():
    """A slice of a real trace of the chat cell on the chip (TPU v5 lite),
    cut to a few steps: the reduction's numbers on it are fixed."""
    path = os.path.join(DATA, "trace_sample.json")
    with open(path) as f:
        sample = json.load(f)
    ops = {k: [tuple(e) for e in v] for k, v in sample["device_ops"].items()}
    host = [tuple(e) for e in sample["host_spans"]]
    r = xplane.reduce_events(ops, host, sample["window_s"])
    want = sample["expect"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert [k for k, _ in r["device_ops"][:3]] == want["top3"]
    assert 0.0 < r["idle_share"] < 1.0
    # to a few nanoseconds: an event of no duration can end the window
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-8)
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= set(spans.GAP_SPANS) | {spans.OUTSIDE}
    assert gaps["engine.launch"] > 0 and gaps["engine.wait"] > 0
    for label, seconds in want["idle_gaps"].items():
        assert gaps[label] == pytest.approx(seconds, rel=1e-9)


def test_every_operation_s_seconds_on_the_recorded_sample():
    """``op_seconds`` holds every operation key of the trace, a named
    kernel's whatever its rank; its ten largest are what the result line
    prints; containers hold other events and are left out."""
    with open(os.path.join(DATA, "trace_sample.json")) as f:
        sample = json.load(f)
    ops = {k: [tuple(e) for e in v] for k, v in sample["device_ops"].items()}
    r = xplane.reduce_events(ops, [], sample["window_s"])
    events = [e for evs in ops.values() for e in evs]
    plain = [e for e in events
             if xplane.op_key(e[0]).split(" ", 1)[0] not in xplane.CONTAINERS]
    assert set(r["op_seconds"]) == {xplane.op_key(e[0]) for e in plain}
    assert len(r["op_seconds"]) > 10
    assert sum(r["op_seconds"].values()) == pytest.approx(
        sum(e[2] for e in plain), rel=1e-9)
    largest = sorted(r["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    assert r["device_ops"] == [list(kv) for kv in largest]
    # a kernel that is not among the ten still has its seconds
    eleventh = list(r["op_seconds"])[10]
    assert eleventh not in dict(r["device_ops"])
    assert 0 < r["op_seconds"][eleventh] <= largest[-1][1]
    assert r["op_seconds"]["custom-call run_plain bf16[16,32,128]"] > 0


def test_loader_reads_a_trace_the_profiler_just_wrote(tmp_path):
    import jax
    import jax.numpy as jnp

    win = xplane.TraceWindow(str(tmp_path / "tr"), 0.2)
    win.start()
    # as the program's StepClock writes them: a step span that carries its
    # number, a phase span inside it
    with jax.profiler.StepTraceAnnotation("engine.step", step_num=7):
        x = jnp.ones((256, 256))
        with jax.profiler.TraceAnnotation("engine.launch"):
            (x @ x).block_until_ready()
    win._timer.join(60)
    # on the CPU the operations sit on the host plane's XLA threads
    ops, host = xplane.load(str(tmp_path / "tr"), device_prefix="/host:CPU",
                            op_line="tf_XLAPjRtCpuClient")
    assert any("dot" in n for evs in ops.values() for n, _, _ in evs)
    assert {n for n, _, _ in host} == {"engine.step", "engine.launch"}
    inner = next(h for h in host if h[0] == "engine.launch")
    outer = next(h for h in host if h[0] == "engine.step")
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2] + 1e-6
