"""From the profiler's trace to numbers: which intervals the device was
busy, which operations took the time, and what the host was doing in the
gaps.  The pure functions work on (name, start_s, duration_s) tuples, so
they are checked on a small recorded trace without a profiler."""
from __future__ import annotations

import glob
import os
import re
import shutil
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import spans

Interval = Tuple[float, float]
# operations that only hold others: their bodies are events of their own
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


# ------------------------------------------------------------ pure parts

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(xs: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in xs:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


_NAME = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = (.*)$", re.S)
_SHAPE = re.compile(r"\w+\[[\d,]*\]")
_OPCODE = re.compile(r"(?<![\w\-])([a-z][a-z\-]*)\(")


def op_key(name: str) -> str:
    """Instances of one operation under one name.  The TPU's trace names
    an event by its whole HLO instruction; kept are the opcode, the
    instruction's name without its number, and the first output shape:
    ``fusion fusion bf16[16,64,14336]``.  A bare ``fusion.123`` loses its
    number."""
    m = _NAME.match(name)
    if not m:
        return re.sub(r"[.:]\d+$", "", name)[:96]
    base, rest = m.groups()
    opcode, shape = _OPCODE.search(rest), _SHAPE.search(rest)
    parts = (opcode.group(1) if opcode else None, base,
             shape.group(0) if shape else None)
    return " ".join(x for x in parts if x)[:96]


def reduce_events(device_ops: Dict[str, List[tuple]],
                  host_spans: List[tuple], window_s: float) -> dict:
    """``device_ops``: per device, (name, start_s, duration_s) of each
    operation; ``host_spans``: the program's spans that
    ``spans.GAP_SPANS`` names, same clock.
    Returns busy seconds (mean over devices), the idle share of the
    window, every operation key's seconds (``op_seconds``; the ten largest
    again as ``device_ops``), collective seconds, and the idle gaps by
    what the host was doing.

    The cost is linear in events and spans but for the sorts: one pass
    over the events, and for each label one merge-walk over the gaps that
    are left.  A faster program puts more steps, so more events, into the
    same traced seconds; nothing here may grow faster than they do.

    A trace in which nothing ran on a device gives the same keys
    (``_nothing_ran``): a run's line is printed whatever its trace
    holds."""
    if not any(device_ops.values()):
        return _nothing_ran(len(device_ops), host_spans, window_s)
    busy, ops, coll = [], {}, 0.0
    gaps_by: Dict[str, float] = {}
    by_label: Dict[str, List[Interval]] = {
        label: [] for label in spans.GAP_SPANS}
    for n, s, d in host_spans:
        if n in by_label:
            by_label[n].append((s, s + d))
    labelled = {label: union(iv) for label, iv in by_label.items()}
    # a step repeats the same few hundred instruction names: what follows
    # from the name alone (its key, None for a container; whether it is a
    # collective) is worked out once for each
    named: Dict[str, Tuple[Optional[str], bool]] = {}
    first, last = float("inf"), float("-inf")
    for dev, events in device_ops.items():
        marks = []
        for n, s, d in events:
            e = s + d
            marks.append((s, e))
            if s < first:
                first = s
            if e > last:
                last = e
            about = named.get(n)
            if about is None:
                key = op_key(n)
                about = named[n] = (
                    None if key.split(" ", 1)[0] in CONTAINERS else key,
                    any(c in n for c in COLLECTIVES))
            if about[0] is not None:
                ops[about[0]] = ops.get(about[0], 0.0) + d
            if about[1]:
                coll += d
        iv = union(marks)
        busy.append(total(iv))
        if not iv:
            continue
        rest = complement(iv, iv[0][0], iv[-1][1])
        for label in spans.GAP_SPANS:         # innermost first
            inside = intersect(rest, labelled[label])
            if inside:
                gaps_by[label] = gaps_by.get(label, 0.0) + total(inside)
                rest = _subtract(rest, inside)
        if rest:
            gaps_by[spans.OUTSIDE] = gaps_by.get(spans.OUTSIDE, 0.0) \
                + total(rest)
    n = len(device_ops)
    # the window on the trace's own clock, first start to last end of the
    # device's operations: the host's stop call returns a little early, and
    # its clock is not the trace's
    if first <= last:
        window_s = last - first
    busy_s = sum(busy) / n
    # every operation key's seconds, largest first: a reader takes a named
    # kernel's whatever its rank; the result line prints the first ten
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


def _nothing_ran(devices: int, host_spans: List[tuple],
                 window_s: float) -> dict:
    """The reduction of a trace in which no operation ran on any device
    (the engine had no request alive while it was traced): every key a
    trace with operations has.  The whole window is idle; its seconds go
    to the host span they fall in, innermost first as ever, and what no
    span covers to ``spans.OUTSIDE``.  With no operation to take the
    window's ends from, its length stays the host's."""
    gaps_by: Dict[str, float] = {}
    covered: List[Interval] = []
    for label in spans.GAP_SPANS:
        inside = _subtract(union((s, s + d) for n, s, d in host_spans
                                 if n == label), covered)
        if inside:
            gaps_by[label] = total(inside)
            covered = union(covered + inside)
    outside = window_s - total(covered)
    if outside > 0:
        gaps_by[spans.OUTSIDE] = outside
    return {
        "window_s": window_s, "busy_s": 0.0, "devices": devices,
        "idle_share": 1.0, "collective_s": 0.0, "op_seconds": {},
        "device_ops": [],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps_by.items(), key=lambda kv: -kv[1])]}


def _subtract(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """``xs`` less ``ys``, both sorted and disjoint: one walk, an index
    into ``ys`` that only moves forward."""
    out, j, n = [], 0, len(ys)
    for a, b in xs:
        while j < n and ys[j][1] <= a:
            j += 1
        at, k = a, j
        while k < n and ys[k][0] < b:
            if ys[k][0] > at:
                out.append((at, ys[k][0]))
            at = max(at, ys[k][1])
            k += 1
        if at < b:
            out.append((at, b))
    return out


# --------------------------------------------------------- the profiler

def load(trace_dir: str, device_prefix: str = "/device:TPU",
         op_line: str = "XLA Ops"):
    """(device_ops, host_spans) from the newest ``.xplane.pb`` under
    ``trace_dir``; times in seconds on the trace's own clock."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    device_ops, host_spans = {}, []
    labels = frozenset(spans.GAP_SPANS)
    for plane in data.planes:
        is_device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            if is_device and line.name.startswith(op_line):
                device_ops.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name not in labels)
            if plane.name.startswith("/host:"):
                host_spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name in labels)
    return device_ops, host_spans


class TraceWindow:
    """Trace the last ``span_s`` seconds of the measured window [w0, w1)
    (all of a shorter window): ``start()``, called at the window's
    opening, sets the profiler to begin at ``w1 - span_s`` and to stop at
    ``w1``.  The profiler's stop takes seconds to tens of seconds and
    slows the steps it overlaps, so it falls after the close, where no
    metric reads.  ``finish()`` waits for the stop and returns the
    reduction, with what it cost under ``cost``: the device events and
    host spans it loaded, and the seconds the profiler's stop, the
    loading and the reducing took."""

    STOP_WAIT_S = 300.0

    def __init__(self, trace_dir: str, span_s: float, w0: float, w1: float):
        self.dir = trace_dir
        self.span_s = min(float(span_s), w1 - w0)
        self.begin_at = w1 - self.span_s
        self._t0 = self._t1 = self._stop_s = None
        self._err = None
        self._stopped = threading.Event()

    def start(self):
        self._after(self.begin_at - time.monotonic(), self._begin)

    @staticmethod
    def _after(seconds, fn):
        if seconds <= 0:
            return fn()
        t = threading.Timer(seconds, fn)
        t.daemon = True
        t.start()

    def _begin(self):
        import jax

        try:
            shutil.rmtree(self.dir, ignore_errors=True)
            kw = {}
            try:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0       # our spans only
                kw["profiler_options"] = opts
            except AttributeError:
                pass
            jax.profiler.start_trace(self.dir, **kw)
        except Exception as e:          # reported by finish()
            self._err = e
            self._stopped.set()
            return
        self._t0 = time.monotonic()
        # the stop is due span_s after the trace was due to begin: a
        # start that took its time shortens the trace and moves no end
        self._after(self.begin_at + self.span_s - self._t0, self._stop)

    def _stop(self):
        import jax

        self._t1 = time.monotonic()
        try:
            jax.profiler.stop_trace()
        except Exception as e:          # reported by finish()
            self._err = e
        self._stop_s = time.monotonic() - self._t1
        self._stopped.set()

    def finish(self) -> dict:
        if not self._stopped.wait(self.STOP_WAIT_S):
            raise RuntimeError("the trace was never stopped")
        if self._err is not None:
            raise self._err
        began = time.monotonic()
        device_ops, host_spans = load(self.dir)
        loaded = time.monotonic()
        out = reduce_events(device_ops, host_spans, self._t1 - self._t0)
        out["cost"] = {
            "device_events": sum(len(v) for v in device_ops.values()),
            "host_spans": len(host_spans), "stop_s": self._stop_s,
            "load_s": loaded - began,
            "reduce_s": time.monotonic() - loaded}
        out["t0"], out["t1"] = self._t0, self._t1      # monotonic clock
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def cost_line(trace: dict) -> str:
    """What a runner says once ``TraceWindow.finish`` has returned: a
    traced run that is slow to close shows here where the time went."""
    c = trace["cost"]
    return ("trace: %d device events, %d host spans, stopped in %.2f s, "
            "loaded in %.2f s, reduced in %.2f s" % (
                c["device_events"], c["host_spans"], c["stop_s"],
                c["load_s"], c["reduce_s"]))
