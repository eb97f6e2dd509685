"""The one sweep that finds the knee of an open-loop cell, on the chip.

    python benchmarks/sweep.py --workload <name> --rates 0.4,0.6,0.8 --seconds 40

One process, one build; for each offered rate a ramp and a window of the
cell's own traffic, then a drain.  Printed per rate: requests due, the
median and 90th percentile of TTFT from due time, the mean TTFT of the
window's first and second half (a growing backlog shows as the second
half above the first) and requests still without a first token when the
window closed.  The knee is the highest rate whose backlog does not grow;
the cell's ``rate_rps`` is a share of it, written into ``cells/`` with
every line of the sweep.

Beside them, for choosing the share: the pooled gaps between tokens
(their number, median and 95th percentile) and, from the StepLog, the
rows alive a step over the window and over its first 2 s (a ramp too
short for the batch to fill reads lower there), the median step, and the
share of the gaps that a step holding a prompt chunk made
(``readers/chunk_step_gap_share.py``: a tail that sits between the two
kinds of gap reads either by the seed).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import accounting                            # noqa: E402
from benchmarks.evidence import Evidence                     # noqa: E402
from benchmarks.readers import (chunk_step_gap_share,        # noqa: E402
                                steplog_stat)


def offer(system, gen, config, traffic, cell, rate, seed, seconds) -> dict:
    """One rate: ramp, window and drain of the cell's own traffic on the
    system as it stands; the row that is printed."""
    state = {}

    def on_open(w0, w1):
        state["off"] = time.time() - time.monotonic()

    records, w0, w1 = gen.run(system, traffic, dict(cell, rate_rps=rate),
                              seed, seconds, int(config["vocab_size"]),
                              on_window_open=on_open)
    win = [r for r in records if r.phase == "window"]
    got = [r for r in win if r.token_times]
    ttft = [r.token_times[0] - r.due for r in got]
    mid = 0.5 * (w0 + w1)

    def half_mean_ms(lo, hi):
        xs = [r.token_times[0] - r.due for r in got if lo <= r.due < hi]
        return sum(xs) / len(xs) * 1e3 if xs else None

    late = [r for r in win if not r.token_times or r.token_times[0] > w1]
    steps = [dict(r, t=r["ts"] - state["off"])
             for r in system.steplog.records()]

    def evidence(t0, t1):
        return Evidence(config=config, traffic=traffic, cell=cell,
                        device_kind="", chips=1, setup_s=0.0, w0=t0, w1=t1,
                        steps=[s for s in steps if t0 <= s["t"] < t1])

    ev = evidence(w0, w1)
    gaps = accounting.gaps_in_window((r.token_times for r in records),
                                     w0, w1)
    return {"rate_rps": rate, "seconds": seconds, "due": len(win),
            "ttft_p50_ms": accounting.quantile(ttft, 0.5) * 1e3,
            "ttft_p90_ms": accounting.quantile(ttft, 0.9) * 1e3,
            "ttft_mean_first_half_ms": half_mean_ms(w0, mid),
            "ttft_mean_second_half_ms": half_mean_ms(mid, w1),
            "no_first_token_at_close": len(late),
            "gaps": len(gaps),
            "itl_p50_ms": accounting.quantile(gaps, 0.5) * 1e3,
            "itl_p95_ms": accounting.quantile(gaps, 0.95) * 1e3,
            "batch_rows_mean": steplog_stat.read(ev, "active_rows", "mean"),
            "batch_rows_mean_first_2s": steplog_stat.read(
                evidence(w0, w0 + 2.0), "active_rows", "mean"),
            "step_ms_p50": steplog_stat.read(ev, "wall_s", "p50", 1e3),
            "chunk_step_gap_share": chunk_step_gap_share.read(ev)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run

    entry, config, traffic, cell, _ = run.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print("sweep.py: needs the cell's TPU chips", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.configure_cache()
    mod = importlib.import_module(f"benchmarks.systems.{config['system']}")
    gen = importlib.import_module(
        f"benchmarks.generators.{traffic['generator']}")
    system = mod.System(config, devices, args.seed, False)
    system.build()
    system.warm(traffic)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            row = offer(system, gen, config, traffic, cell, rate,
                        args.seed + k, args.seconds)
            print(json.dumps(row), flush=True)
            os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
            with open(os.path.join(ROOT, "chiprun_out",
                                   f"sweep_{args.workload}.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
            # let the batch empty before the next rate
            t_end = time.monotonic() + 90.0
            while system.core.active_count and time.monotonic() < t_end:
                time.sleep(0.2)
    finally:
        system.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
