"""Readings that the limits of ``correct`` are set from, taken on the chip.

    python benchmarks/control.py --workload <name> --seeds 1,2,3 [--seconds S]

For each seed, in one process: what sound runs of the program read, and
what the control reads — the plain reference computed in the nearest
precision below the one the configuration states (fp8 for bfloat16), put
in the program's place.  A limit goes above the sound runs' largest and
below the control's smallest (PERF.md section 2 has the readings).  The
benchmark's own runs never run this; ``tests/benchmarks`` keeps it at a
size a test can hold.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def serving_readings(ctx, precision):
    from benchmarks import check_served, run

    res = run.run_cell(ctx)
    ev = res["evidence"]
    spec = ctx.config["check"]
    cases = check_served.sample(ev.records, ctx.seed,
                                int(spec["sample_requests"]),
                                int(spec["max_tokens_per_request"]))
    q = check_served.gap_quantile

    def summary(g):
        return {"widest": float(g.max()), "p99": q(g, 0.99),
                "p90": q(g, 0.9), "nonzero": int((g > 0).sum())}

    sound = check_served.gaps(ctx.config, ctx.seed, cases)
    out = {"tokens": int(sound.size), "failed": res["failed"],
           "sound": summary(sound)}
    for p in precision.split("+"):
        out["control_" + p] = summary(
            check_served.gaps(ctx.config, ctx.seed, cases, p))
    return out


def training_readings(ctx, precision, steps=3):
    """Besides the lower precisions, two readings of the reference against
    itself: ``remask`` (another draw of its dropout masks: the floor of
    every number) and ``half_batch`` (the fault the gradient norm is there
    to catch).  Per-leaf norms are kept, so that another statistic can be
    read from the same runs later."""
    import importlib

    from benchmarks import check_train

    mod = importlib.import_module(
        f"benchmarks.systems.{ctx.config['system']}")
    batches = mod.make_batches(ctx.config, ctx.traffic, ctx.seed)
    system = mod.System(ctx.config, ctx.devices, ctx.seed, False)
    system.build()
    try:
        got = system.first_steps(batches, steps)
    finally:
        system.free()
    read = lambda b=batches, **kw: check_train.reference_readings(
        ctx.config, ctx.seed, b, steps, **kw)
    ref = read()
    strip = lambda d: {k: v for k, v in d.items() if not k.startswith("_")}
    out = {"steps": steps,
           "sound": strip(check_train.compare(ctx.config, got, ref)),
           "losses": {"program": got["losses"], "reference": ref["losses"]},
           "leaves": {"program": got, "reference": ref}}
    for p in precision.split("+"):
        if p == "remask":
            ctrl = read(mask_stream=10)
        elif p == "half_batch":
            half = [tuple(a[:len(a) // 2] for a in b) for b in batches]
            ctrl = read(half, mask_stream=10)
        else:
            ctrl = read(precision=p)
        out["control_" + p] = strip(check_train.compare(ctx.config, ctrl,
                                                        ref))
        out["losses"][p] = ctrl["losses"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--precision", default="fp8",
                    help="fp8, fp8_scaled, bfloat16 (training also: remask, "
                    "half_batch); several joined by +")
    ap.add_argument("--steps", type=int, default=3,
                    help="training: steps followed (1 reads the first "
                    "step's numbers only)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run

    entry, config, traffic, cell, _ = run.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print(f"control.py: needs {entry['chips']} TPU chip(s)",
              file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.configure_cache()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"control_{args.workload}.jsonl")
    if config["kind"] == "serving":
        read = serving_readings
    else:
        read = lambda c, p: training_readings(c, p, args.steps)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(config, traffic, cell, entry["chips"], seed,
                          args.seconds, 0, devices, time.monotonic())
        row = {"workload": args.workload, "seed": seed,
               "precision": args.precision, **read(ctx, args.precision)}
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        row.pop("leaves", None)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
