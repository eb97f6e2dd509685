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
    # the host spent 0.5 s inside a dispatch, 1 s elsewhere in run_once,
    # and 0.5 s outside any span
    ops = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("copy.2", 3.0, 0.5),
                             ("all-reduce.3", 3.5, 0.5)]}
    host = [("bench.run_once", 0.5, 2.0), ("bench.dispatch", 1.0, 0.5)]
    r = xplane.reduce_events(ops, host, 5.0)
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["window_s"] == pytest.approx(4.0)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["collective_s"] == pytest.approx(0.5)
    gaps = dict(r["idle_gaps"])
    assert gaps["in_dispatch"] == pytest.approx(0.5)
    assert gaps["scheduler_host"] == pytest.approx(1.0)
    assert gaps[spans.OUTSIDE] == pytest.approx(0.5)
    assert r["device_ops"][0] == ["fusion", pytest.approx(1.0)]


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
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-9)
    assert dict(r["idle_gaps"])["in_dispatch"] > 0


def test_loader_reads_a_trace_the_profiler_just_wrote(tmp_path):
    import jax
    import jax.numpy as jnp

    win = xplane.TraceWindow(str(tmp_path / "tr"), 0.2)
    win.start()
    with jax.profiler.TraceAnnotation("bench.run_once"):
        x = jnp.ones((256, 256))
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            (x @ x).block_until_ready()
    win._timer.join(60)
    # on the CPU the operations sit on the host plane's XLA threads
    ops, host = xplane.load(str(tmp_path / "tr"), device_prefix="/host:CPU",
                            op_line="tf_XLAPjRtCpuClient")
    assert any("dot" in n for evs in ops.values() for n, _, _ in evs)
    assert {n for n, _, _ in host} == {"bench.run_once", "bench.dispatch"}
    inner = next(h for h in host if h[0] == "bench.dispatch")
    outer = next(h for h in host if h[0] == "bench.run_once")
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2] + 1e-6
