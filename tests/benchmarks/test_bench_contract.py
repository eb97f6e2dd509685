"""BENCHMARK.json against the harness: every name resolves to a file,
every file to a reader, as data alone.  The rules themselves are in
``contract_rules.py``, stated once for every cell; here each cell's
reading of each metric is a case of its own, found by name."""
import importlib
import json
import os

import pytest

import contract_rules as rules
import readings_diff
from conftest import ROOT, load_data

PATH = os.path.join(ROOT, "BENCHMARK.json")
BENCH = json.load(open(PATH))
E2E, LAYER = "end_to_end", "per_layer"
PAIRS = [(kind, m, cell) for kind in (E2E, LAYER)
         for m, cell in rules.pairs(BENCH, kind)]
PAIR_IDS = rules.pair_ids(BENCH, E2E) + rules.pair_ids(BENCH, LAYER)


def test_top_level_keys_and_limits():
    rules.check_top_level(BENCH, os.path.getsize(PATH))
    rules.check_names_are_unique(BENCH)


@pytest.mark.parametrize("kind,metric,cell", PAIRS, ids=PAIR_IDS)
def test_names_are_names(kind, metric, cell):
    rules.check_metric(BENCH, kind, metric)
    assert rules.NAME.match(cell)


# a traced window in which no operation ran: what ``xplane._nothing_ran``
# gives a reader
NOTHING_RAN = {"busy_s": 0.0, "window_s": 2.0, "idle_share": 1.0,
               "collective_s": 0.0, "op_seconds": {}, "device_ops": [],
               "idle_gaps": [], "t0": 0.0, "t1": 2.0}


@pytest.mark.parametrize("kind,metric,cell", PAIRS, ids=PAIR_IDS)
def test_every_metric_has_a_file_and_a_reader(kind, metric, cell):
    """What ``run.read_metrics`` does for this cell's line: the file names
    a reader, its arguments are the reader's, and on the evidence of a
    window of this cell in which nothing happened, traced or not, the
    reader finds nothing to read and says so: it never raises, and only
    the set-up time and an idle device are numbers then."""
    from benchmarks import run
    from benchmarks.evidence import Evidence

    rules.metric_spec(rules.FOLDER[kind], metric["name"])
    _, config, traffic, params, _ = run.load_cell(cell)
    for trace in (None, NOTHING_RAN):
        ev = Evidence(config=config, traffic=traffic, cell=params,
                      device_kind="TPU v5 lite", chips=1, setup_s=1.0,
                      w0=0.0, w1=2.0, trace=trace)
        got = run.read_metrics([metric], rules.FOLDER[kind], ev, cell)
        assert not [name for name in got if name != "setup_s"
                    and not name.startswith("device_idle_share")], got


@pytest.mark.parametrize("metric,cell", rules.pairs(BENCH, LAYER),
                         ids=rules.pair_ids(BENCH, LAYER))
def test_every_per_layer_metric_moves_what_its_cell_reports(metric, cell):
    rules.check_pair_moves(BENCH, metric, cell)


def test_no_two_entries_measure_the_same():
    rules.check_no_two_entries_measure_the_same(BENCH)


@pytest.mark.parametrize("name", [
    "step_ms_p50.chat", "step_ms_p50.axk1", "step_p50"])
def test_two_equal_entries_are_refused_whatever_their_names(name):
    """Until PR 46 an entry under ``.chat`` or ``.axk1`` could stand beside
    the plain entry it duplicated.  No name may now: the same file and
    fields a second time are refused, under a cell's suffix too."""
    from benchmarks import run

    plain = next(m for m in BENCH["per_layer"] if m["name"] == "step_ms_p50")
    twice = dict(BENCH, per_layer=BENCH["per_layer"] + [
        dict(plain, name=name, workloads=plain["workloads"][:1])])
    load = lambda *parts: run.load_json(*parts[:-1], (
        "step_ms_p50.json" if parts[-1] == name + ".json" else parts[-1]))
    with pytest.raises(AssertionError, match="step_ms_p50"):
        rules.check_no_two_entries_measure_the_same(twice, load)
    # the same entry over another file is another measurement
    other = lambda *parts: (
        {"reader": "steplog_quantile",
         "args": {"field": "wall_s", "q": 0.9, "scale": 1000.0}}
        if parts[-1] == name + ".json" else run.load_json(*parts))
    rules.check_no_two_entries_measure_the_same(twice, other)


PR45 = load_data("per_layer_pr45.json")


@pytest.mark.parametrize("cell", sorted(readings_diff.readings(PR45)))
def test_no_cell_lost_a_reading_by_the_fold(cell):
    """PR 46 folded the entries held under ``.chat`` and ``.axk1`` into
    their plain ones: each measurement a cell's line held at PR 45 it holds
    now, under the name ``readings_diff.new_name`` maps it to."""
    was = readings_diff.readings(PR45, readings_diff.new_name)[cell]
    now = readings_diff.readings(readings_diff.table(BENCH))[cell]
    assert was <= now, sorted(was - now)


def test_every_metric_file_has_an_entry_and_every_reader_a_file():
    """Nothing lies about under the benchmark's folders that no entry
    names: a file left behind by a merged or retired entry shows here."""
    for kind, folder in rules.FOLDER.items():
        files = {f[:-len(".json")] for f in os.listdir(
            os.path.join(ROOT, "benchmarks", folder))}
        assert files == {m["name"] for m in BENCH[kind]}
    named = {rules.metric_spec(rules.FOLDER[kind], m["name"])[0]["reader"]
             for kind in (E2E, LAYER) for m in BENCH[kind]}
    readers = {f[:-len(".py")] for f in os.listdir(
        os.path.join(ROOT, "benchmarks", "readers"))
        if f.endswith(".py") and f != "__init__.py"}
    assert readers == named


@pytest.mark.parametrize("entry", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_and_reports(entry):
    from benchmarks import run

    e, config, traffic, cell, _ = run.load_cell(entry["name"])
    assert callable(run.runner_for(config["kind"]).run)
    importlib.import_module("benchmarks.systems." + config["system"])
    rules.check_cell(BENCH, entry)


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


def test_configs_state_their_cuts():
    for c in BENCH["configs"]:
        rules.check_config(BENCH, c)
        assert c["file"].startswith("benchmarks/configs/")
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
