import json
import os
import random
import threading
import time

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
    # one interval of ys over several of xs, and over an edge of each
    assert xplane._subtract([(0, 2), (3, 5), (6, 8), (9, 9)],
                            [(-1, 1), (1.5, 6.5), (7, 7.5)]) == [
        (1, 1.5), (6.5, 7), (7.5, 8)]
    assert xplane._subtract([(0, 1)], []) == [(0, 1)]
    assert xplane._subtract([], [(0, 1)]) == []


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


def test_a_gap_goes_to_the_innermost_span_that_covers_it():
    """The finish, the row loop, the read-back's first half and a
    collection are labels of their own, ahead of the phases they lie in:
    every idle second gets one label, the innermost span's."""
    ops = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("fusion.1", 5.0, 1.0)]}
    host = [("engine.step", 0.5, 5.0),
            ("engine.wait", 1.0, 1.0), ("engine.ready", 1.0, 0.8),
            ("engine.emit", 2.0, 2.5), ("engine.emit_rows", 2.2, 2.0),
            ("engine.release", 2.5, 1.0), ("prefix.evict", 2.6, 0.8),
            ("host.gc", 3.0, 0.2),
            ("prefix.insert", 2.5, 0.1)]                    # no label
    r = xplane.reduce_events(ops, host, 6.0)
    gaps = dict(r["idle_gaps"])
    assert gaps == {"host.gc": pytest.approx(0.2),
                    "prefix.evict": pytest.approx(0.6),
                    "engine.release": pytest.approx(0.2),
                    "engine.emit_rows": pytest.approx(1.0),
                    "engine.ready": pytest.approx(0.8),
                    "engine.wait": pytest.approx(0.2),
                    "engine.emit": pytest.approx(0.5),
                    "engine.step": pytest.approx(0.5)}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert [k for k, _ in r["idle_gaps"]][:2] == ["engine.emit_rows",
                                                  "engine.ready"]
    # the same spans over a trace in which nothing ran
    empty = dict(xplane.reduce_events({}, host, 6.0)["idle_gaps"])
    assert empty == dict(gaps, **{
        "engine.step": pytest.approx(5.0 - 3.5),
        spans.OUTSIDE: pytest.approx(1.0)})
    # what the phases of a program older than the spans are given
    old = [h for h in host if h[0] in ("engine.step", "engine.wait",
                                       "engine.emit")]
    assert dict(xplane.reduce_events(ops, old, 6.0)["idle_gaps"]) == {
        "engine.wait": pytest.approx(1.0), "engine.emit": pytest.approx(2.5),
        "engine.step": pytest.approx(0.5)}


def test_reduction_averages_over_devices():
    ops = {"/device:TPU:0": [("a.1", 0.0, 1.0)],
           "/device:TPU:1": [("a.1", 0.0, 3.0)]}
    r = xplane.reduce_events(ops, [], 4.0)
    assert r["busy_s"] == pytest.approx(2.0) and r["devices"] == 2


_SOME_TRACE = ({"/device:TPU:0": [("fusion.1", 0.0, 1.0)]},
               [("engine.step", 0.0, 2.0)], 2.0)


@pytest.mark.parametrize("ops", [{}, {"/device:TPU:0": []},
                                 {"/device:TPU:0": [], "/device:TPU:1": []}],
                         ids=["no_plane", "one_empty_plane", "two"])
def test_an_empty_trace_gives_every_key_a_trace_with_operations_does(ops):
    """The engine had no request alive while it was traced: all of the
    window is idle, under the host span it falls in, innermost first,
    else ``between_steps``; nothing else differs from any other trace."""
    host = [("engine.step", 1.0, 2.0), ("engine.step", 4.0, 0.5),
            ("engine.admit", 1.0, 0.25), ("engine.wait", 1.5, 1.0),
            ("engine.admit", 2.0, 0.25),       # inside the wait: innermost
            ("engine.retire", 0.0, 5.0)]       # no label
    r = xplane.reduce_events(ops, host, 6.0)
    assert list(r) == list(xplane.reduce_events(*_SOME_TRACE))
    assert r["window_s"] == 6.0 and r["busy_s"] == 0.0
    assert r["devices"] == len(ops)
    assert r["idle_share"] == 1.0 and r["collective_s"] == 0.0
    assert r["op_seconds"] == {} and r["device_ops"] == []
    assert r["idle_gaps"] == [[spans.OUTSIDE, pytest.approx(3.5)],
                              ["engine.step", pytest.approx(1.25)],
                              ["engine.wait", pytest.approx(0.75)],
                              ["engine.admit", pytest.approx(0.5)]]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"])


def test_an_empty_trace_with_no_span_is_all_between_steps():
    r = xplane.reduce_events({}, [], 2.0)
    assert r["busy_s"] == 0.0 and r["idle_share"] == 1.0
    assert r["idle_gaps"] == [[spans.OUTSIDE, 2.0]]
    # spans past the host's own window cannot make the rest negative
    r = xplane.reduce_events({}, [("engine.step", 0.0, 3.0)], 2.0)
    assert r["idle_gaps"] == [["engine.step", 3.0]]


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

    # both shapes are compiled before the trace opens: compiled inside it
    # on a loaded machine they outlast a short trace, which then closes
    # without its ``dot`` (one run in five under the gate's six workers)
    x = jnp.ones((256, 256))
    (x @ x).block_until_ready()
    now = time.monotonic()
    win = xplane.TraceWindow(str(tmp_path / "tr"), 1.0, now, now + 1.0)
    win.start()
    # as the program's StepClock writes them: a step span that carries its
    # number, a phase span inside it, a child span inside the phase
    with jax.profiler.StepTraceAnnotation("engine.step", step_num=7):
        x = jnp.ones((256, 256))
        with jax.profiler.TraceAnnotation("engine.launch"):
            (x @ x).block_until_ready()
        with jax.profiler.TraceAnnotation("engine.wait"):
            with jax.profiler.TraceAnnotation("engine.ready"):
                (x @ x).block_until_ready()
        with jax.profiler.TraceAnnotation("engine.retire"):     # no label
            pass
    assert win._stopped.wait(60)
    # on the CPU the operations sit on the host plane's XLA threads
    ops, host = xplane.load(str(tmp_path / "tr"), device_prefix="/host:CPU",
                            op_line="tf_XLAPjRtCpuClient")
    assert any("dot" in n for evs in ops.values() for n, _, _ in evs)
    assert {n for n, _, _ in host} == {"engine.step", "engine.launch",
                                       "engine.wait", "engine.ready"}
    span = {n: (s, s + d) for n, s, d in host}
    for inner, outer in (("engine.launch", "engine.step"),
                         ("engine.wait", "engine.step"),
                         ("engine.ready", "engine.wait")):
        assert span[outer][0] <= span[inner][0]
        assert span[inner][1] <= span[outer][1] + 1e-6


# ------------------------------------------------- the quadratic oracle
# The attribution as it stood before PR 31: ``_subtract`` scans every
# interval of ``ys`` for every interval of ``xs``, ``op_key`` and the
# search for a collective run on every event, the window takes a second
# pass.  Kept here, where it costs nothing, as what ``reduce_events`` has
# to return on any trace.

def _oracle_subtract(xs, ys):
    out = []
    for a, b in xs:
        out.extend(xplane.complement(
            [y for y in ys if y[1] > a and y[0] < b], a, b))
    return out


def _oracle_reduce_events(device_ops, host_spans, window_s):
    if not any(device_ops.values()):
        # nothing ran: the whole window idle, by label, innermost first
        gaps_by, seen = {}, []
        for label in spans.GAP_SPANS:
            iv = _oracle_subtract(xplane.union(
                (s, s + d) for n, s, d in host_spans if n == label), seen)
            if iv:
                gaps_by[label] = xplane.total(iv)
                seen = xplane.union(seen + iv)
        if window_s > xplane.total(seen):
            gaps_by[spans.OUTSIDE] = window_s - xplane.total(seen)
        return {"window_s": window_s, "busy_s": 0.0,
                "devices": len(device_ops), "idle_share": 1.0,
                "collective_s": 0.0, "op_seconds": {}, "device_ops": [],
                "idle_gaps": [[k, v] for k, v in sorted(
                    gaps_by.items(), key=lambda kv: -kv[1])]}
    busy, ops, coll = [], {}, 0.0
    gaps_by = {}
    labelled = {label: xplane.union((s, s + d) for n, s, d in host_spans
                                    if n == label)
                for label in spans.GAP_SPANS}
    for dev, events in device_ops.items():
        iv = xplane.union((s, s + d) for _, s, d in events)
        busy.append(xplane.total(iv))
        for n, s, d in events:
            key = xplane.op_key(n)
            if key.split(" ", 1)[0] not in xplane.CONTAINERS:
                ops[key] = ops.get(key, 0.0) + d
            if any(c in n for c in xplane.COLLECTIVES):
                coll += d
        if not iv:
            continue
        rest = xplane.complement(iv, iv[0][0], iv[-1][1])
        for label in spans.GAP_SPANS:
            inside = xplane.intersect(rest, labelled[label])
            if inside:
                gaps_by[label] = gaps_by.get(label, 0.0) \
                    + xplane.total(inside)
                rest = _oracle_subtract(rest, inside)
        if rest:
            gaps_by[spans.OUTSIDE] = gaps_by.get(spans.OUTSIDE, 0.0) \
                + xplane.total(rest)
    n = len(device_ops)
    marks = [(s, s + d) for ev in device_ops.values() for _, s, d in ev]
    if marks:
        window_s = max(b for _, b in marks) - min(a for a, _ in marks)
    busy_s = sum(busy) / n
    op_seconds = {k: v / n for k, v in
                  sorted(ops.items(), key=lambda kv: -kv[1])}
    return {
        "window_s": window_s, "busy_s": busy_s, "devices": n,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "collective_s": coll / n,
        "op_seconds": op_seconds,
        "device_ops": [[k, v] for k, v in list(op_seconds.items())[:10]],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gaps_by.items(), key=lambda kv: -kv[1])]}


def _same(got, want):
    """Key for key, in the same order, every number to 1e-9."""
    assert list(got) == list(want)
    for k in ("window_s", "busy_s", "idle_share", "collective_s"):
        assert got[k] == pytest.approx(want[k], rel=1e-9, abs=1e-9), k
    assert got["devices"] == want["devices"]
    for k in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[k]] == [n for n, _ in want[k]], k
        assert [v for _, v in got[k]] == pytest.approx(
            [v for _, v in want[k]], rel=1e-9, abs=1e-9), k
    assert list(got["op_seconds"]) == list(want["op_seconds"])
    assert list(got["op_seconds"].values()) == pytest.approx(
        list(want["op_seconds"].values()), rel=1e-9, abs=1e-9)


_NAMES = (
    ["%%fusion.%d = bf16[16,64,%d]{2,1,0} fusion(bf16[16,64,4096]{2,1,0} "
     "%%p.%d), kind=kLoop" % (i, 128 * (i % 5 + 1), i) for i in range(12)]
    + ["%all-reduce.3 = f32[4096]{0} all-reduce(f32[4096]{0} %x)",
       "%all-gather.7 = bf16[8,128]{1,0} all-gather(bf16[2,128]{1,0} %p)",
       "%while.5 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
       "%conditional.2 = f32[8]{0} conditional(pred[] %c, f32[8]{0} %a)",
       "%ragged_paged_attention.9 = bf16[16,32,64,128]{3,2,1,0} "
       "custom-call(bf16[16,64,32,128]{3,2,1,0} %q)",
       "copy.4", "copy.11", "fusion.123", "collective-permute-start.1"])
_PHASES = [g for g in spans.GAP_SPANS if g not in ("engine.step",
                                                   "fleet.train_step")]


def _random_trace(seed, with_spans=True):
    """Two devices whose operations overlap, abut, have no length or leave
    a gap; steps whose phases nest inside them, abut, overlap one another,
    hang over the step's end or are missing; spans that begin or end on an
    operation's own edge, so gaps straddle them; a span no label names."""
    r = random.Random(seed)
    ops, edges = {}, []
    for dev in range(2):
        t, events = r.uniform(0.0, 0.01), []
        for _ in range(r.randint(15, 30)):
            for _ in range(r.randint(3, 12)):
                d = 0.0 if r.random() < 0.1 else r.uniform(1e-5, 2e-3)
                events.append((r.choice(_NAMES), t, d))
                edges += [t, t + d]
                t += r.choice([d * r.random(), d, d + r.uniform(0, 3e-3)])
            t += r.uniform(0, 5e-3)
        # several lines of one plane come one after the other, each in its
        # own order: nothing may lean on the events being sorted
        cut = r.randrange(len(events))
        ops["/device:TPU:%d" % dev] = events[cut:] + events[:cut]
    host = []
    if with_spans:
        end, t = max(edges), 0.0
        while t < end:
            length = r.uniform(2e-3, 2e-2)
            if r.random() < 0.5:
                length = r.choice(edges) - t if r.choice(edges) > t \
                    else length
            if r.random() < 0.7:
                host.append(("engine.step", t, length))
            at = t + r.choice([0.0, r.uniform(0, 1e-3)])
            for phase in _PHASES:
                if r.random() < 0.2:
                    continue
                d = r.choice([0.0, r.uniform(0, length / 2),
                              max(0.0, r.choice(edges) - at)])
                host.append((phase, at, min(d, 2 * length)))
                at += r.choice([d, d + r.uniform(0, 5e-4), d / 2])
            if r.random() < 0.3:
                host.append(("fleet.train_step", t + r.uniform(-1e-3, length),
                             r.uniform(0, 2e-2)))
            host.append(("engine.retire", t, length))       # no label
            t += length + r.choice([0.0, r.uniform(0, 3e-3),
                                    r.uniform(5e-3, 2e-2)])
        r.shuffle(host)
    return ops, host, r.uniform(0.1, 1.0)


@pytest.mark.parametrize("seed", range(10))
def test_reduction_equals_the_quadratic_oracle(seed):
    ops, host, window = _random_trace(seed)
    got = xplane.reduce_events(ops, host, window)
    _same(got, _oracle_reduce_events(ops, host, window))
    labels = {k for k, _ in got["idle_gaps"]}
    assert labels <= set(spans.GAP_SPANS) | {spans.OUTSIDE}
    assert len(labels) >= 5
    assert got["collective_s"] > 0
    assert not any(k.startswith(xplane.CONTAINERS) for k in got["op_seconds"])


@pytest.mark.parametrize("seed", (100, 101))
def test_reduction_equals_the_oracle_with_no_spans_at_all(seed):
    ops, host, window = _random_trace(seed, with_spans=False)
    assert host == []
    got = xplane.reduce_events(ops, host, window)
    _same(got, _oracle_reduce_events(ops, host, window))
    assert [k for k, _ in got["idle_gaps"]] == [spans.OUTSIDE]


def test_reduction_of_devices_without_events_equals_the_oracle():
    for ops in ({}, {"/device:TPU:0": []},
                {"/device:TPU:0": [], "/device:TPU:1": [("a.1", 1.0, 0.0)]},
                {"/device:TPU:0": [("a.1", 1.0, 0.5)], "/device:TPU:1": []}):
        _same(xplane.reduce_events(ops, [("engine.step", 0.0, 2.0)], 3.0),
              _oracle_reduce_events(ops, [("engine.step", 0.0, 2.0)], 3.0))


@pytest.mark.parametrize("seed", range(4))
def test_subtract_equals_the_quadratic_one(seed):
    """Any two sorted disjoint lists, not only gaps and their parts."""
    r = random.Random(seed)

    def intervals(n):
        cuts = sorted(r.choice([r.uniform(0, 10), float(r.randint(0, 10))])
                      for _ in range(2 * n))
        return xplane.union(zip(cuts[::2], cuts[1::2]))

    for _ in range(50):
        xs, ys = intervals(r.randint(0, 12)), intervals(r.randint(0, 12))
        assert xplane._subtract(xs, ys) == _oracle_subtract(xs, ys)


def test_recorded_trace_sample_equals_the_oracle():
    with open(os.path.join(DATA, "trace_sample.json")) as f:
        sample = json.load(f)
    ops = {k: [tuple(e) for e in v] for k, v in sample["device_ops"].items()}
    host = [tuple(e) for e in sample["host_spans"]]
    _same(xplane.reduce_events(ops, host, sample["window_s"]),
          _oracle_reduce_events(ops, host, sample["window_s"]))


def _long_trace(steps, ops_a_step, seed=31):
    """``steps`` serving steps of ``ops_a_step`` operations under a few
    hundred instruction names, each step with a span of every label."""
    r = random.Random(seed)
    names = ["%%fusion.%d = bf16[16,64,%d]{2,1,0} fusion(bf16[16,64,4096]"
             "{2,1,0} %%p.%d), kind=kLoop" % (i, 128 * (i % 50 + 1), i)
             for i in range(320)]
    ops, host, t = [], [], 0.0
    for _ in range(steps):
        t0 = t
        host.append(("engine.admit", t, 1e-4))
        host.append(("engine.pack", t + 1e-4, 2e-4))
        host.append(("engine.launch", t + 3e-4, 3e-3))
        d = t + 1.3e-3
        for i in range(ops_a_step):
            dur = r.uniform(2e-5, 6e-5)
            ops.append((names[i % 320], d, dur))
            d += dur + r.uniform(0, 4e-6)
        host.append(("engine.wait", t + 3.3e-3, d - t - 3.2e-3))
        host.append(("engine.ready", t + 3.3e-3, d - t - 3.3e-3))
        host.append(("engine.emit", d + 1e-4, 3e-4))
        host.append(("engine.emit_rows", d + 1.2e-4, 2.6e-4))
        host.append(("engine.release", d + 1.5e-4, 2e-4))
        host.append(("prefix.evict", d + 1.6e-4, 1.5e-4))
        host.append(("host.gc", d + 2e-4, 5e-5))
        t = d + 4e-4
        host.append(("engine.step", t0, t - t0))
        host.append(("fleet.train_step", t, 1e-4))
        t += 2e-4
    return {"/device:TPU:0": ops}, host, t


def test_reduction_time_grows_with_the_events_not_their_square():
    """300,000 events and 6,000 spans of each label: the quadratic
    attribution took 12.3 s at 22,400 events and would take over half an
    hour here."""
    ops, host, window = _long_trace(6000, 50)
    assert len(ops["/device:TPU:0"]) == 300_000
    assert all(sum(n == g for n, _, _ in host) == 6000
               for g in spans.GAP_SPANS)
    began = time.perf_counter()
    r = xplane.reduce_events(ops, host, window)
    assert time.perf_counter() - began < 20.0
    # every label, and together every idle second (the result line prints
    # the ten largest: ``run.result_line``)
    assert set(dict(r["idle_gaps"])) == set(spans.GAP_SPANS) | {
        spans.OUTSIDE}
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    # and a slice of it still reads as the oracle does
    ops, host, window = _long_trace(12, 50)
    _same(xplane.reduce_events(ops, host, window),
          _oracle_reduce_events(ops, host, window))


def test_a_finished_window_says_what_its_trace_cost(tmp_path,
                                                    cpu_trace_loader):
    """The four numbers a runner prints after ``TraceWindow.finish``, on a
    trace the CPU's profiler just wrote."""
    import jax
    import jax.numpy as jnp

    now = time.monotonic()
    win = xplane.TraceWindow(str(tmp_path / "tr"), 0.2, now, now + 0.2)
    win.start()
    with jax.profiler.StepTraceAnnotation("engine.step", step_num=3):
        x = jnp.ones((256, 256))
        with jax.profiler.TraceAnnotation("engine.launch"):
            (x @ x).block_until_ready()
    out = win.finish()
    cost = out["cost"]
    assert set(cost) == {"device_events", "host_spans", "stop_s", "load_s",
                         "reduce_s"}
    assert cost["host_spans"] == 2
    assert cost["stop_s"] > 0 and cost["load_s"] > 0
    assert cost["reduce_s"] >= 0
    if not cost["device_events"]:
        pytest.skip("the CPU profiler wrote no operations to read")
    assert out["busy_s"] > 0 and out["t1"] > out["t0"]
    assert xplane.cost_line(out) == (
        "trace: %d device events, 2 host spans, stopped in %.2f s, "
        "loaded in %.2f s, reduced in %.2f s" % (
            cost["device_events"], cost["stop_s"], cost["load_s"],
            cost["reduce_s"]))
    assert not os.path.exists(str(tmp_path / "tr"))


# ------------------------------------------- the window's last seconds

class _FakeHost:
    """What ``xplane`` reaches the host through, as one fake: a clock the
    test moves (``time.monotonic``), timers it fires (``threading.Timer``)
    and a profiler that only writes down when it was started and stopped
    (``jax.profiler``)."""

    def __init__(self, monkeypatch, now):
        import jax

        self.now, self.timers, self.calls = now, [], []
        monkeypatch.setattr(xplane, "time", self)
        monkeypatch.setattr(xplane, "threading", self)
        monkeypatch.setattr(jax.profiler, "start_trace", self.start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", self.stop_trace)

    Event = threading.Event

    def monotonic(self):
        return self.now

    def Timer(self, seconds, fn):
        fake = self

        class T:
            daemon = False

            def start(self):
                fake.timers.append((fake.now + seconds, fn))
        return T()

    def fire_next(self):
        at, fn = self.timers.pop(0)
        self.now = max(self.now, at)
        fn()

    def start_trace(self, trace_dir, **kw):
        self.calls.append(("start", self.now))
        self.now += 0.3                     # a start takes its time

    def stop_trace(self):
        self.calls.append(("stop", self.now))
        self.now += 20.0                    # and a stop far more


@pytest.mark.parametrize("span_s,begins", [(2.0, 149.0), (6.0, 145.0),
                                           (80.0, 100.0)],
                         ids=["2s", "6s", "longer_than_the_window"])
def test_the_last_seconds_of_a_window_are_what_is_traced(
        tmp_path, monkeypatch, span_s, begins):
    """Asked at the window's opening for its last ``s`` seconds, the trace
    starts at ``w1 - s`` and its stop is called at ``w1``: the stop's own
    seconds fall after the close."""
    w0, w1 = 100.0, 151.0
    fake = _FakeHost(monkeypatch, now=w0)
    win = xplane.TraceWindow(str(tmp_path / "tr"), span_s, w0, w1)
    assert win.span_s == min(span_s, w1 - w0) and win.begin_at == begins
    win.start()
    if begins > w0:
        assert fake.calls == [] and [t for t, _ in fake.timers] == [begins]
        fake.fire_next()
    assert fake.calls == [("start", begins)]
    assert not win._stopped.is_set()
    assert [t for t, _ in fake.timers] == [pytest.approx(w1)]
    fake.fire_next()
    assert fake.calls == [("start", begins), ("stop", pytest.approx(w1))]
    assert win._stopped.is_set()
    assert win._t0 == pytest.approx(begins + 0.3) and win._t1 \
        == pytest.approx(w1)
    assert win._stop_s == pytest.approx(20.0)


def test_there_is_one_way_to_place_a_trace():
    """Both runners hand the window's ends to the same constructor; none
    can ask for a trace from its opening."""
    import inspect

    assert list(inspect.signature(xplane.TraceWindow).parameters) == [
        "trace_dir", "span_s", "w0", "w1"]
    assert not hasattr(xplane.TraceWindow, "last_of")


def test_a_trace_that_cannot_begin_is_reported_at_the_finish(tmp_path,
                                                             monkeypatch):
    fake = _FakeHost(monkeypatch, now=0.0)

    def refuses(trace_dir, **kw):
        raise RuntimeError("another trace is running")

    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", refuses)
    win = xplane.TraceWindow(str(tmp_path / "tr"), 2.0, 0.0, 10.0)
    win.start()
    fake.fire_next()
    assert win._stopped.is_set() and fake.timers == []
    with pytest.raises(RuntimeError, match="another trace"):
        win.finish()
