"""The rules BENCHMARK.json is held to, stated once for every cell and
every metric, as functions of a benchmark given to them: the committed
file (``test_bench_contract.py``) and a copy with a cell no PR has added
yet (``test_bench_fifth_cell.py``).

No rule looks at a place in a list, at a list's length or end, or at the
whole of one: a PR that adds a cell appends to ``configs``, ``workloads``,
the end-to-end metrics' lists, the shared per-layer metrics' lists and
``per_layer`` itself, and every rule here holds before and after."""
import importlib
import inspect
import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
FOLDER = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}
MAX_PER_LAYER, MAX_BYTES = 128, 64 * 1024
# a width may never be cut
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head_dim)$")


def cells_of(bench, metric):
    """The cells that report ``metric``, in the benchmark's order: those
    its ``workloads`` lists, every cell where it has none."""
    return [w["name"] for w in bench["workloads"]
            if "workloads" not in metric or w["name"] in metric["workloads"]]


def pairs(bench, kind):
    """(metric entry, cell) for each cell's reading of each metric of
    ``bench[kind]``: what a run of that cell puts on its line."""
    return [(m, cell) for m in bench[kind] for cell in cells_of(bench, m)]


def pair_ids(bench, kind):
    return [f"{m['name']}@{cell}" for m, cell in pairs(bench, kind)]


def end_to_end_of(bench, cell):
    return [m["name"] for m in bench["end_to_end"]
            if cell in cells_of(bench, m)]


def metric_spec(folder, name, load=None):
    """The metric's file (through ``load``, ``run.load_json`` unless a
    test brings files of its own) and the reader it names, its arguments
    bound to the reader's signature."""
    from benchmarks import run

    spec = (load or run.load_json)(folder, name + ".json")
    assert set(spec) <= {"reader", "args"}, name
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    assert callable(reader.read)
    inspect.signature(reader.read).bind(None, **spec.get("args", {}))
    return spec, reader


def check_top_level(bench, size):
    assert set(bench) == KEYS
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= len(bench["command"]) <= 32
    n = len(bench["workloads"])
    assert 1 <= n <= 24 and 1 <= len(bench["configs"]) <= 24
    # at most a quarter of the cells, and one always, on four chips
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, n // 4)
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= MAX_PER_LAYER
    assert size < MAX_BYTES


def check_names_are_unique(bench):
    for names in ([m["name"] for m in bench["end_to_end"] + bench["per_layer"]],
                  [w["name"] for w in bench["workloads"]],
                  [c["name"] for c in bench["configs"]],
                  [c["file"] for c in bench["configs"]],
                  [(w["config"], w["traffic"]) for w in bench["workloads"]]):
        assert len(names) == len(set(names)), sorted(
            n for n in set(names) if names.count(n) > 1)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def check_metric(bench, kind, m):
    """One entry of ``end_to_end`` or ``per_layer``: its own fields."""
    cells = [w["name"] for w in bench["workloads"]]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m["name"]
    assert m["better"] in ("lower", "higher")
    if "workloads" in m:
        # known cells, each once, in the benchmark's order
        assert m["workloads"], m["name"]
        assert m["workloads"] == [c for c in cells if c in m["workloads"]], \
            m["name"]
    if kind == "end_to_end":
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m["name"]
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and not re.search(r"[\t\n]",
                                                             m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def check_pair_moves(bench, m, cell):
    """A per-layer metric moves an end-to-end metric that this cell of
    its list reports."""
    assert m["moves"] in end_to_end_of(bench, cell), (m["name"], cell)
    assert m["moves"] != "setup_s"


def check_cell(bench, entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key]), entry[key]
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    e2e = end_to_end_of(bench, entry["name"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert [m for m in bench["per_layer"]
            if entry["name"] in cells_of(bench, m)]


def check_config(bench, entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert len(entry["reduced"]) <= 16
    assert not [k for k in entry["reduced"] if WIDTH.search(k)]
    assert cfg["source"].startswith("http") and entry["source"]


def check_no_two_entries_measure_the_same(bench, load=None):
    """One measurement is one entry, and the cells that report it are its
    ``workloads``: no two per-layer entries have the same metric file and
    the same fields but for ``name`` and ``workloads``, whatever their
    names."""
    seen = {}
    for m in bench["per_layer"]:
        spec, _ = metric_spec("layer_metrics", m["name"], load)
        key = (json.dumps(spec, sort_keys=True),) + tuple(
            m[k] for k in ("unit", "better", "source", "layer", "moves"))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def check_all(bench, size, load=None):
    """Every rule, on a benchmark that is not the committed one."""
    check_top_level(bench, size)
    check_names_are_unique(bench)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            check_metric(bench, kind, m)
            metric_spec(FOLDER[kind], m["name"], load)
    for m, cell in pairs(bench, "per_layer"):
        check_pair_moves(bench, m, cell)
    for w in bench["workloads"]:
        check_cell(bench, w)
    for c in bench["configs"]:
        check_config(bench, c)
    check_no_two_entries_measure_the_same(bench, load)
