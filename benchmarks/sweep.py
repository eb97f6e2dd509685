"""The one sweep that finds the knee of an open-loop cell, on the chip.

    python benchmarks/sweep.py --workload <name> --rates 0.4,0.6,0.8 --seconds 40

One process, one build; for each offered rate a ramp and a window of the
cell's own traffic, then a drain.  Printed per rate: requests due, the
median and 90th percentile of TTFT from due time, the mean TTFT of the
window's first and second half (a growing backlog shows as the second
half above the first) and requests still without a first token when the
window closed.  The knee is the highest rate whose backlog does not grow;
the cell's ``rate_rps`` is a share of it, written into ``cells/``.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from benchmarks import accounting, run

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
    rows = []
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            records, w0, w1 = gen.run(system, traffic,
                                      dict(cell, rate_rps=rate),
                                      args.seed + k, args.seconds,
                                      int(config["vocab_size"]))
            win = [r for r in records if r.phase == "window"]
            got = [r for r in win if r.token_times]
            ttft = [r.token_times[0] - r.due for r in got]
            mid = 0.5 * (w0 + w1)
            half = lambda lo, hi: [r.token_times[0] - r.due for r in got
                                   if lo <= r.due < hi]
            mean = lambda xs: sum(xs) / len(xs) if xs else None
            late = [r for r in win if not r.token_times
                    or r.token_times[0] > w1]
            row = {"rate_rps": rate, "due": len(win),
                   "ttft_p50_ms": accounting.quantile(ttft, 0.5) * 1e3,
                   "ttft_p90_ms": accounting.quantile(ttft, 0.9) * 1e3,
                   "ttft_mean_first_half_ms": mean(half(w0, mid)) * 1e3,
                   "ttft_mean_second_half_ms": mean(half(mid, w1)) * 1e3,
                   "no_first_token_at_close": len(late)}
            rows.append(row)
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
