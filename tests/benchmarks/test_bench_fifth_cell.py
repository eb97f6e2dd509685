"""A cell that is not there: what a ``model_config`` PR would commit, held
to every rule of the contract and rehearsed on the CPU.

Such a PR may add files and append entries; it may edit no file under the
benchmark's paths.  So it must be able to append its configuration, its
cell, its name to the lists of the end-to-end metric and of every shared
per-layer metric its family reads, and its own entries at the end of
``per_layer``, and find every rule and every test still holding.
``grow_a_cell.grown`` does exactly that, here in memory with a tiny
configuration of the latent attention + expert family; its ``main`` does
it to a copy of the tree with a configuration of the real size, and the
tests that hold ``BENCHMARK.json`` by name run there."""
import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

import contract_rules as rules
import grow_a_cell
from benchmarks import run
from conftest import ROOT, load_data

CELL, CONFIG, KIN = "tiny-axk1.tinychat", "tiny-axk1", "xing4-d7.reasoning"
# the new cell's own entries and, in memory, the files they would bring
OWN = {
    "decode_keys_mean.tiny": (
        {"unit": "keys", "better": "lower", "source": "program_counter",
         "layer": "latent attention"},
        {"reader": "steplog_mean_where",
         "args": {"field": "decode_keys", "where": "decode_rows"}}),
    "step_ms_p90.tiny": (
        {"unit": "ms", "better": "lower", "source": "program_counter",
         "layer": "step program"},
        {"reader": "steplog_quantile",
         "args": {"field": "wall_s", "q": 0.9, "scale": 1000.0}}),
}


def grown(bench):
    return grow_a_cell.grown(
        bench,
        {"name": CONFIG,
         "source": "https://huggingface.co/skt/A.X-K1/blob/main/config.json",
         "file": "tests/benchmarks/data/tiny-axk1.json", "reduced": [],
         "why": "the latent attention + expert family at a size a test "
                "holds"},
        {"name": CELL, "config": CONFIG, "traffic": "tiny-chat", "chips": 1,
         "why": "prompts 8-100, outputs 4-24, open loop 4 req/s: a fifth "
                "cell as the next model_config PR would append it"},
        KIN, {name: fields for name, (fields, _) in OWN.items()})


_load_json = run.load_json


def load_with_the_new_files(*parts):
    """``run.load_json`` over the committed files and the two the PR
    would add."""
    name = parts[-1][:-len(".json")]
    if parts[0] == "layer_metrics" and name in OWN:
        return OWN[name][1]
    return _load_json(*parts)


@pytest.fixture(scope="module")
def fifth(benchmark_json):
    return grown(benchmark_json)


def test_the_copy_appends_and_edits_nothing_else(benchmark_json, fifth):
    was, now = benchmark_json, fifth
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == was[key]
    for key in ("configs", "workloads", "per_layer", "end_to_end"):
        assert len(now[key]) >= len(was[key])
        for a, b in zip(was[key], now[key]):
            rest = {k: v for k, v in b.items() if k != "workloads"}
            assert rest == {k: v for k, v in a.items() if k != "workloads"}
            if "workloads" in a and key != "workloads":
                assert b["workloads"] in (a["workloads"],
                                          a["workloads"] + [CELL])
    # it joined the family's shared metrics: most of what its kin reads
    joined = [m["name"] for m in now["per_layer"] if CELL in m["workloads"]]
    assert len(joined) >= 25 + len(OWN) and joined[-len(OWN):] == list(OWN)
    # and no cell that was there reads another set of metrics for it
    for w in was["workloads"]:
        reads = lambda b: [m["name"] for kind in ("end_to_end", "per_layer")
                           for m in b[kind]
                           if w["name"] in rules.cells_of(b, m)]
        assert reads(now) == reads(was)


def test_every_rule_of_the_contract_holds_on_the_copy(fifth):
    size = len(json.dumps(fifth, indent=1)) + 1
    rules.check_all(fifth, size, load=load_with_the_new_files)
    # what is free for the PRs after it, as benchmarks/README.md states it
    assert rules.MAX_PER_LAYER - len(fifth["per_layer"]) >= 30


BREAKS = [
    (lambda b: b["per_layer"][-1].pop("workloads"), "no list"),
    (lambda b: b["per_layer"][-1]["workloads"].append("no-such.cell"),
     "unknown cell"),
    (lambda b: b["per_layer"][0]["workloads"].reverse(), "order"),
    (lambda b: b["per_layer"][-1].update(moves="train_tokens_per_s"),
     "moves what the cell does not report"),
    (lambda b: b["per_layer"].append(dict(
        b["per_layer"][0], name="gen_lateness_p99_ms.tiny",
        workloads=[CELL])), "a second entry over the same file and fields"),
    (lambda b: b["per_layer"].append(dict(
        next(m for m in b["per_layer"] if m["name"] == "step_ms_p50"),
        name="step_ms_p50.chat", workloads=["mistral-d12.chat"])),
     "a shared measurement entered again under a cell's suffix"),
    (lambda b: b["workloads"].append(dict(b["workloads"][-1])),
     "a name twice"),
    (lambda b: b["workloads"][-1].update(chips=4) or b["workloads"][0].update(
        chips=4), "two of five cells on four chips"),
    (lambda b: b["per_layer"].extend(
        dict(b["per_layer"][-1], name="x%d" % i) for i in range(80)),
     "more than 128 entries"),
]


@pytest.mark.parametrize("break_it", [b for b, _ in BREAKS],
                         ids=[re.sub(r"\W+", "_", why) for _, why in BREAKS])
def test_the_rules_refuse_a_copy_that_breaks_one(fifth, break_it):
    broken = copy.deepcopy(fifth)
    break_it(broken)
    load = lambda *parts: (
        {"reader": "client_quantile",
         "args": {"series_name": "lateness", "q": 0.99, "scale": 1000.0}}
        if parts[-1] == "gen_lateness_p99_ms.tiny.json"
        else _load_json("layer_metrics", "step_ms_p50.json")
        if parts[-1] == "step_ms_p50.chat.json"
        else OWN["step_ms_p90.tiny"][1] if re.match(r"x\d+\.json", parts[-1])
        else load_with_the_new_files(*parts))
    with pytest.raises((AssertionError, KeyError)):
        rules.check_all(broken, len(json.dumps(broken, indent=1)), load=load)


def test_a_rehearsal_of_the_new_cell_reads_every_metric_that_lists_it(
        fifth, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "load_json", load_with_the_new_files)
    ctx = run.Context(load_data("tiny-axk1.json"),
                      load_data("tiny-chat.json"), {"rate_rps": 4.0}, 1,
                      2 ** 31 + 43, 2.0, 0, jax.devices()[:1],
                      time.monotonic(), say=lambda s: None,
                      trace_dir=str(tmp_path / "trace"))
    res = run.run_cell(ctx)
    assert res["correct"] is True and res["failed"] == 0
    ev = res["evidence"]
    e2e = run.read_metrics(fifth["end_to_end"], "e2e_metrics", ev, CELL)
    assert set(e2e) == {"itl_p95_ms", "setup_s"}
    got = run.read_metrics(fifth["per_layer"], "layer_metrics", ev, CELL)
    listed = [m for m in fifth["per_layer"] if CELL in m["workloads"]]
    # on the CPU and untraced nothing reads the device's trace, and the
    # CPU has no published capacity to take a share of
    cannot = {m["name"] for m in listed
              if m["source"] == "device_trace" or m["layer"] == "device"}
    assert cannot == {"device_idle_share", "hbm_peak_share",
                      "step_temp_share", "latent_decode_roofline_share",
                      "moe_grouped_matmul_roofline_share"}
    assert set(got) == {m["name"] for m in listed} - cannot
    assert set(OWN) <= set(got)
    assert all(got[m["name"]]["unit"] == m["unit"] for m in listed
               if m["name"] in got)
    # the line a run of the new cell would print
    line = run.result_line(res, fifth, CELL, 0, "cpu", 1)
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    # and a cell that was there reads on the same evidence what it read:
    # the shared metrics give both cells one number
    theirs = run.read_metrics(fifth["per_layer"], "layer_metrics", ev, KIN)
    assert set(theirs) & set(got) == set(got) - set(OWN)
    assert all(theirs[name] == got[name] for name in set(got) - set(OWN))


def test_the_tests_hold_on_a_copy_of_the_tree_with_a_real_fifth_cell(
        tmp_path, benchmark_json):
    """``grow_a_cell.py`` as a command, on a copy of the benchmark's files
    and tests: a fifth cell of the real size, appended by new files and
    appends alone.  The tests that hold ``BENCHMARK.json`` by name and
    rehearse nothing run there, in a process of their own; the whole of
    ``tests/benchmarks/`` on such a copy is run by hand (PERF.md section
    6).  A test that pins a list again fails here."""
    cell = grow_a_cell.CELL
    if cell in [w["name"] for w in benchmark_json["workloads"]]:
        pytest.skip("this tree is such a copy")
    root = str(tmp_path / "tree")
    junk = shutil.ignore_patterns("__pycache__", ".*")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"), ignore=junk)
    shutil.copytree(os.path.join(ROOT, "tests", "benchmarks"),
                    os.path.join(root, "tests", "benchmarks"), ignore=junk)
    for name in ("BENCHMARK.json", os.path.join("tests", "conftest.py")):
        shutil.copy(os.path.join(ROOT, name), os.path.join(root, name))
    # the program is not the benchmark's to copy
    for name in ("paddle_infer_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    grow_a_cell.main(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert cell in [w["name"] for w in bench["workloads"]]
    files = ["test_bench_contract.py", "test_bench_phase_readers.py",
             "test_bench_chunk_step_gap_share.py",
             "test_bench_paged_attention_reader.py"]
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist"]
        + [os.path.join("tests", "benchmarks", f) for f in files],
        cwd=root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    tail = done.stdout[-3000:] + done.stderr[-1000:]
    assert done.returncode == 0, tail
    passed = int(re.search(r"(\d+) passed", tail).group(1))
    # each reading of the new cell is a case of its own there
    assert "step_ms_p50@%s" % cell in done.stdout
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert passed > 2 * len(rules.pairs(json.load(f), "per_layer"))
