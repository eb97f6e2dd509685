"""One run of a training cell: build, first steps, window, check."""
from __future__ import annotations

import importlib
import time

import jax

from . import check_train, xplane
from .evidence import CompileCounter, Evidence


def run(ctx, system_mod=None) -> dict:
    config, traffic, cell = ctx.config, ctx.traffic, ctx.cell
    if system_mod is None:
        system_mod = importlib.import_module(
            f"benchmarks.systems.{config['system']}")
    counter = CompileCounter()
    system = system_mod.System(config, ctx.devices, ctx.seed, ctx.trace)
    batches = system_mod.make_batches(config, traffic, ctx.seed)
    system.build()
    try:
        got = system.first_steps(batches)        # compiles, warms, checks
        depth = int(traffic["steps_in_flight"])
        counter.mark()
        w0 = time.monotonic()
        w1 = w0 + float(ctx.seconds)
        trace = None
        if ctx.trace:
            # the window's last trace_s seconds, as a serving run's
            trace = xplane.TraceWindow(
                ctx.trace_dir, float(traffic.get("trace_s", 6.0)), w0, w1)
            trace.start()
        pending, done = [], []
        i = 3
        while time.monotonic() < w1:
            pending.append(system.call(batches[i % len(batches)]))
            i += 1
            if len(pending) > depth:
                loss = pending.pop(0)
                jax.block_until_ready(loss)
                done.append(time.monotonic())
        for loss in pending:
            jax.block_until_ready(loss)
            done.append(time.monotonic())
        last_loss = float(loss)             # the window's one fetch
        compiles = counter.since_mark()
        temp = system.program_temp_bytes(batches[0])
        program = system.program_report()
        if trace is not None:
            trace = trace.finish()
            ctx.say(xplane.cost_line(trace))
        ev = Evidence(
            config=config, traffic=traffic, cell=cell,
            device_kind=ctx.devices[0].device_kind, chips=ctx.chips,
            setup_s=w0 - ctx.process_start, w0=w0, w1=done[-1],
            train_done_times=done,
            positions_per_step=int(config["batch_size"])
            * int(config["seq_len"]),
            compiles_in_window=int(compiles),
            allocator_peak_bytes=ctx.allocator_peak(),
            program_temp_bytes=temp, program=program,
            trace=trace)
    finally:
        system.free()
    ctx.say(f"window: {len(done)} steps, last loss {last_loss:.4f}")
    finite = last_loss == last_loss and abs(last_loss) != float("inf")
    correct, compared = check_train.check(config, ctx.seed, batches, got,
                                          ctx.say)
    return {"correct": bool(correct and finite), "attempted": len(done),
            "failed": 0 if finite else len(done), "evidence": ev,
            "check": compared}
