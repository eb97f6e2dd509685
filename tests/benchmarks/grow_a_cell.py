"""What a PR that adds a cell appends (``benchmarks/README.md``, "What a PR
that adds a cell appends"), as a function of a benchmark and as a command
on a copy of the tree:

    python tests/benchmarks/grow_a_cell.py <root of a copy of the tree>

appends a REAL fifth cell there (``axk1-ep16b.chat``), by new files and
appends alone: a second cut of A.X-K1 under the ``chat`` traffic mix, its
name on the list of every end-to-end metric and every shared per-layer
metric its kin is on, two entries of its own at the end with their files.
The whole of
``tests/benchmarks/`` then has to pass in the copy (PERF.md section 6 has
the command and its count); ``test_bench_fifth_cell.py`` runs the cheap
part of that in every run of the suite, and ``grown`` in memory with a
configuration a test can rehearse."""
import copy
import json
import os
import shutil
import sys


def shared_with(bench, kin):
    """The per-layer entries a cell of ``kin``'s family joins: those that
    list ``kin`` under no suffix (a suffix says that the reader or its
    cost file is one configuration's own)."""
    return [m for m in bench["per_layer"]
            if kin in m["workloads"] and "." not in m["name"]]


def grown(bench, config, cell, kin, own):
    """``bench`` as the PR that adds ``cell`` would leave it: the five
    appends, nothing that was there edited otherwise.  ``own`` maps the
    new entries' names to their fields but for ``moves`` and
    ``workloads``."""
    bench = copy.deepcopy(bench)
    bench["configs"].append(config)
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:
        if kin in m.get("workloads", ()):
            m["workloads"].append(cell["name"])
    for m in shared_with(bench, kin):
        m["workloads"].append(cell["name"])
    moves = [m["name"] for m in bench["end_to_end"]
             if cell["name"] in m.get("workloads", ())]
    bench["per_layer"] += [
        dict(fields, name=name, moves=moves[0], workloads=[cell["name"]])
        for name, fields in own.items()]
    return bench


KIN, CELL, CONFIG = "xing4-d7.reasoning", "axk1-ep16b.chat", "a.x-k1-ep16-d7b"
OWN = {
    "attended_keys_mean.axk1b": (
        {"unit": "keys", "better": "lower", "source": "program_counter",
         "layer": "latent attention"},
        {"reader": "steplog_stat",
         "args": {"field": "attended_keys", "stat": "mean"}}),
    "step_ms_p99.axk1b": (
        {"unit": "ms", "better": "lower", "source": "program_counter",
         "layer": "step program"},
        {"reader": "steplog_quantile",
         "args": {"field": "wall_s", "q": 0.99, "scale": 1000.0}}),
}


def main(root):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    assert CELL not in [w["name"] for w in bench["workloads"]], \
        "this tree has the cell already"
    under = lambda *parts: os.path.join(root, "benchmarks", *parts)
    shutil.copy(under("configs", "a.x-k1-ep16-d7.json"),
                under("configs", CONFIG + ".json"))
    shutil.copy(under("cells", "axk1-ep16.ragchat.json"),
                under("cells", CELL + ".json"))
    like = next(c for c in bench["configs"] if c["name"] == "a.x-k1-ep16-d7")
    bench = grown(
        bench, dict(like, name=CONFIG,
                    file="benchmarks/configs/%s.json" % CONFIG),
        {"name": CELL, "config": CONFIG, "traffic": "chat", "chips": 1,
         "why": "a fifth cell on a copy of the tree: the latent family "
                "under short chat prompts"},
        KIN, {name: fields for name, (fields, _) in OWN.items()})
    for name, (_, spec) in OWN.items():
        with open(under("layer_metrics", name + ".json"), "w") as f:
            json.dump(spec, f, indent=1)
    with open(path, "w") as f:
        f.write(json.dumps(bench, indent=1) + "\n")
    joined = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    print("%s joined %d lists; %d entries; %d bytes" % (
        CELL, len(joined), len(bench["per_layer"]), os.path.getsize(path)))


if __name__ == "__main__":
    main(sys.argv[1])
