"""BENCHMARK.json against the harness: every name resolves to a file,
every file to a reader, as data alone."""
import importlib
import json
import os
import re

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _metric_files(folder, entries):
    return [(folder, m["name"]) for m in entries]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
    n = len(BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, n // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    | {w["name"] for w in BENCH["workloads"]}
    | {c["name"] for c in BENCH["configs"]}
    | {w["traffic"] for w in BENCH["workloads"]}))
def test_names_are_names(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("folder,name", _metric_files(
    "e2e_metrics", BENCH["end_to_end"]) + _metric_files(
    "layer_metrics", BENCH["per_layer"]))
def test_every_metric_has_a_file_and_a_reader(folder, name):
    with open(os.path.join(ROOT, "benchmarks", folder, name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    assert callable(reader.read)
    assert set(spec) <= {"reader", "args"}


@pytest.mark.parametrize("entry", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_and_reports(entry):
    from benchmarks import run

    e, config, traffic, cell, _ = run.load_cell(entry["name"])
    assert callable(run.runner_for(config["kind"]).run)
    importlib.import_module("benchmarks.systems." + config["system"])
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    reports = lambda m: "workloads" not in m or entry["name"] in m["workloads"]
    e2e = [m["name"] for m in BENCH["end_to_end"] if reports(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if reports(m)]
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_every_configuration_names_a_reference_with_its_contract(entry):
    from benchmarks import reference

    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    mod = reference.find(config)
    assert mod.__name__ == "benchmarks.reference." + config["reference"]
    for fn in reference.CONTRACT[config["kind"]]:
        assert callable(getattr(mod, fn))


@pytest.mark.parametrize("name", [
    "check_served.py", "check_train.py", "serving_run.py",
    "training_run.py", "control.py", "run.py"])
def test_the_harness_names_no_architecture(name):
    """What checks and runs every family names none of them: no model, no
    weight, no reference module."""
    with open(os.path.join(ROOT, "benchmarks", name)) as f:
        text = f.read().lower()
    for word in ("mistral", "llama", "ernie", "wqkv", "word_emb"):
        assert word not in text, (name, word)


def test_per_layer_entries_are_well_formed():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m["workloads"]) <= cells and m["workloads"]
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_configs_state_their_cuts():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert c["file"].startswith("benchmarks/configs/")
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head_dim)$", key)
        assert cfg["source"].startswith("http")
    mistral = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/mistral-7b-v0.1-d12.json")))
    published = dict(hidden_size=4096, intermediate_size=14336,
                     num_attention_heads=32, num_key_value_heads=8,
                     vocab_size=32000, rms_norm_eps=1e-5, rope_theta=10000.0,
                     sliding_window=4096, max_position_embeddings=32768)
    for k, v in published.items():
        assert mistral[k] == v, k
    assert mistral["deployment"]["max_model_len"] <= mistral["sliding_window"]


def test_run_refuses_where_there_is_no_program(tmp_path, monkeypatch):
    from benchmarks import run

    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    assert run.main(["--workload", "ernie-base.pretrain", "--seed", "1",
                     "--seconds", "1"]) == run.EXIT_NO_PROGRAM


def test_run_refuses_without_the_chip(capsys):
    from benchmarks import run

    # the tests' JAX is held to the CPU: no result line, a non-zero exit
    code = run.main(["--workload", "ernie-base.pretrain", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code == run.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""
