"""One run of a serving cell: build, warm, ramp, window, check."""
from __future__ import annotations

import importlib
import time

from . import check_served, xplane
from .accounting import gaps_in_window, quantile
from .evidence import CompileCounter, Evidence
from .readers.steplog_phase import per_step


def run(ctx, system_mod=None) -> dict:
    config, traffic, cell = ctx.config, ctx.traffic, ctx.cell
    if system_mod is None:
        system_mod = importlib.import_module(
            f"benchmarks.systems.{config['system']}")
    gen = importlib.import_module(
        f"benchmarks.generators.{traffic['generator']}")
    counter = CompileCounter()
    system = system_mod.System(config, ctx.devices, ctx.seed, ctx.trace)
    system.build()
    system.warm(traffic)
    state = {}

    def on_open(w0, w1):
        counter.mark()
        state["clock_offset"] = time.time() - time.monotonic()
        ctx.say(f"window open after {w0 - ctx.process_start:.1f}s of set-up")
        if ctx.trace:
            # the window's LAST trace_s seconds: the profiler's stop then
            # falls after the close and slows no step a metric reads
            state["trace"] = xplane.TraceWindow(
                ctx.trace_dir, float(traffic.get("trace_s", 6.0)), w0, w1)
            state["trace"].start()

    try:
        records, w0, w1 = gen.run(system, traffic, cell, ctx.seed,
                                  ctx.seconds, int(config["vocab_size"]),
                                  on_window_open=on_open)
        ctx.say("window closed")
        compiles = counter.since_mark()
        trace = None
        if ctx.trace:
            trace = state["trace"].finish()
            ctx.say(xplane.cost_line(trace))
        off = state["clock_offset"]
        steps = [dict(r, t=r["ts"] - off) for r in system.steplog.records()]
        ev = Evidence(
            config=config, traffic=traffic, cell=cell,
            device_kind=ctx.devices[0].device_kind, chips=ctx.chips,
            setup_s=w0 - ctx.process_start, w0=w0, w1=w1, records=records,
            steps=[s for s in steps if w0 <= s["t"] < w1],
            queue_waits=system.queue_wait_spans(),
            compiles_in_window=int(compiles),
            allocator_peak_bytes=ctx.allocator_peak(),
            token_budget=system.token_budget, max_batch=system.max_batch,
            trace=trace)
        # the compiled step's temporaries, which the allocator's peak
        # leaves out: the largest the program recorded over the window's
        # steps (what readers/steplog_hbm_share.py reads)
        ev.program_temp_bytes = int(max(
            per_step(ev, ["program_temp_bytes"]) or [0])) or None
    finally:
        system.free()
    measured = [r for r in records
                if r.phase != "ramp" and r.sent is not None and r.sent < w1]
    failed = [r for r in measured if not r.token_times]
    for r in failed[:5]:
        ctx.say(f"failed: request {r.index} ({r.phase}) error={r.error}")
    ttft = sorted(r.token_times[0] - (r.due if r.due is not None else r.sent)
                  for r in measured if r.token_times)
    if ttft:
        mid = ttft[len(ttft) // 4:len(ttft) - len(ttft) // 4]
        ctx.say("ttft_ms of %d: mean %.1f midmean %.1f p25 %.1f p50 %.1f "
                "p75 %.1f p90 %.1f max %.1f" % (
                    len(ttft), 1e3 * sum(ttft) / len(ttft),
                    1e3 * sum(mid) / len(mid),
                    *(1e3 * quantile(ttft, q) for q in (.25, .5, .75, .9)),
                    1e3 * ttft[-1]))
    gaps = sorted(gaps_in_window((r.token_times for r in records), w0, w1))
    if gaps:
        ctx.say("itl_ms of %d: mean %.2f p50 %.2f p90 %.2f p95 %.2f p99 %.2f "
                "max %.1f" % (len(gaps), 1e3 * sum(gaps) / len(gaps),
                              *(1e3 * quantile(gaps, q)
                                for q in (.5, .9, .95, .99)),
                              1e3 * gaps[-1]))
    correct, compared = check_served.check(config, ctx.seed, records,
                                           ctx.say)
    return {"correct": correct, "attempted": len(measured),
            "failed": len(failed), "evidence": ev, "check": compared}
