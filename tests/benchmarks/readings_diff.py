"""What each cell's line reports under two tables of ``per_layer``, after
the map from PR 45's names to today's:

    python tests/benchmarks/readings_diff.py <old BENCHMARK.json> [<new>]

prints for every cell of the old table how many measurements it read there
and reads now, what it lost and what it gained.  ``new`` is the tree's
``BENCHMARK.json`` unless given.  ``tests/benchmarks/data/per_layer_pr45.json``
keeps the table as PR 45 left it ({name: workloads}), for the test that
holds no cell to have lost a reading by PR 46's fold."""
import json
import os
import sys

# the entries PR 46 left under a cell's suffix: the reader's arguments or
# its cost file are that configuration's own
KEPT = ("step_roofline_share_counted.chat", "step_roofline_share_counted.axk1",
        "paged_attention_roofline_share.chat")


def new_name(old):
    """The name a measurement of the ledger's lines up to PR 45 reads
    under since PR 46: ``<name>.chat`` and ``<name>.axk1`` are ``<name>``,
    but for the three of ``KEPT``; every other name is unchanged."""
    base, _, suffix = old.rpartition(".")
    return base if suffix in ("chat", "axk1") and old not in KEPT else old


def readings(per_layer, rename=lambda name: name):
    """{cell: the set of measurements its line reports}, from a table
    {name: workloads}."""
    out = {}
    for name, cells in per_layer.items():
        for cell in cells:
            out.setdefault(cell, set()).add(rename(name))
    return out


def table(bench):
    return {m["name"]: m["workloads"] for m in bench["per_layer"]}


def main(old_path, new_path=None):
    here = os.path.dirname(os.path.abspath(__file__))
    new_path = new_path or os.path.join(here, "..", "..", "BENCHMARK.json")
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = readings(table(json.load(f)))
    old = readings(table(old) if "per_layer" in old else old, new_name)
    for cell in sorted(old):
        now = new.get(cell, set())
        print("%s: %d -> %d" % (cell, len(old[cell]), len(now)))
        print("  lost:  ", ", ".join(sorted(old[cell] - now)) or "nothing")
        print("  gained:", ", ".join(sorted(now - old[cell])) or "nothing")


if __name__ == "__main__":
    main(*sys.argv[1:3])
