"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses to run without the cell's chips, builds the
system at the configuration's sizes with weights from ``--seed``, warms
the cell's own shapes, measures for ``--seconds`` and prints one JSON
object as the last line of its standard output.  Everything about a cell
is data: ``BENCHMARK.json`` names its configuration, traffic mix and
metrics; ``configs/``, ``traffic/``, ``cells/``, ``e2e_metrics/`` and
``layer_metrics/`` hold one file each; ``readers/`` holds the reducers
the metric files name.  See README.md.
"""
from __future__ import annotations

import time

_PROCESS_START = time.monotonic()        # first statement: set-up starts here

import argparse                                              # noqa: E402
import importlib                                             # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_CHIP, EXIT_NO_PROGRAM, EXIT_BAD_CELL = 3, 4, 5


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str):
    """(cell entry, config, traffic, cell parameters, benchmark)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", entry["traffic"] + ".json")
    cell_path = os.path.join(HERE, "cells", workload + ".json")
    cell = {}
    if os.path.exists(cell_path):
        with open(cell_path) as f:
            cell = json.load(f)
    return entry, config, traffic, cell, bench


class Context:
    """What a run is given.  ``devices`` is the look for a chip's result;
    tests hand in CPU devices and skip that look."""

    def __init__(self, config, traffic, cell, chips, seed, seconds, trace,
                 devices, process_start, say=None, trace_dir=None):
        self.config, self.traffic, self.cell = config, traffic, cell
        self.chips, self.seed = int(chips), int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.devices = list(devices)
        self.process_start = process_start
        self.say = say or (lambda s: print(s, flush=True))
        self.trace_dir = trace_dir or os.path.join(ROOT, ".bench_trace")

    def allocator_peak(self):
        """The allocator's peak bytes in use on the fullest chip, where the
        backend reports it.  On the TPU it counts live buffers (weights,
        state, cache) and leaves out the temporaries of a running program."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices[:self.chips]]
        peaks = [p for p in peaks if p is not None]
        return int(max(peaks)) if peaks else None


def runner_for(kind: str):
    """``<kind>_run.py`` beside this file: one ``run(ctx, system_mod)``
    for every configuration of that kind."""
    return importlib.import_module(f"benchmarks.{kind}_run")


def run_cell(ctx: Context, system_mod=None) -> dict:
    """Everything after the look for a chip: returns the run's result
    with its evidence."""
    from benchmarks import reference

    runner = runner_for(ctx.config["kind"])
    reference.find(ctx.config)      # a cell that cannot be checked: now
    return runner.run(ctx, system_mod)


def read_metrics(entries, folder, ev, workload):
    """Each metric of ``entries`` that this cell reports: its file under
    ``folder`` names a reader module and its arguments; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = load_json(folder, m["name"] + ".json")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        value = reader.read(ev, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(result, bench, workload, trace, platform, chips) -> dict:
    """The run's one JSON object: the cell's end-to-end metrics, or with
    ``trace`` its per-layer metrics and the breakdown."""
    ev = result["evidence"]
    if trace:
        metrics = read_metrics(bench["per_layer"], "layer_metrics", ev,
                               workload)
    else:
        metrics = read_metrics(bench["end_to_end"], "e2e_metrics", ev,
                               workload)
    device = {"platform": platform, "kind": ev.device_kind, "count": chips,
              "memory_peak_bytes": ev.memory_peak_bytes,
              "allocator_peak_bytes": ev.allocator_peak_bytes,
              "program_temp_bytes": ev.program_temp_bytes}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if ev.program is not None:
        line["program"] = ev.program
    if trace and ev.trace is not None:
        device["busy_s"] = ev.trace["busy_s"]
        device["window_s"] = ev.trace["window_s"]
        # the ten largest of each: the gaps have a label a span of
        # ``spans.GAP_SPANS`` and one more, and sum to the idle seconds
        # only in ``ev.trace``
        line["breakdown"] = {"device_ops": ev.trace["device_ops"][:10],
                             "idle_gaps": ev.trace["idle_gaps"][:10]}
    # last: each number compared beside its limit
    line["check"] = result["check"]
    return line


def say_check(line: dict, file=None):
    """The numbers compared, each beside its limit: a run's last lines on
    standard error."""
    for name, (value, limit) in line["check"].items():
        print(f"check: {name} {value} (limit {limit})",
              file=file or sys.stderr, flush=True)


def configure_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    cached however quick its compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_infer_tpu")):
        print("benchmarks/run.py: no program here — this directory holds "
              "the benchmark only", file=sys.stderr)
        return EXIT_NO_PROGRAM
    try:
        entry, config, traffic, cell, bench = load_cell(args.workload)
    except (KeyError, StopIteration, OSError, ValueError) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return EXIT_BAD_CELL

    import jax

    devices = jax.devices()
    chips = int(entry["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmarks/run.py: {args.workload} needs {chips} TPU "
              f"chip(s); JAX reports {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        return EXIT_NO_CHIP
    configure_cache()
    from benchmarks.peaks import peaks_for

    peaks_for(devices[0].device_kind)       # unknown chip: an error, now

    ctx = Context(config, traffic, cell, chips, args.seed, args.seconds,
                  args.trace, devices, _PROCESS_START)
    line = result_line(run_cell(ctx), bench, args.workload, args.trace,
                       devices[0].platform, chips)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    say_check(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
