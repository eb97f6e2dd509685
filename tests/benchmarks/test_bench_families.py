"""A second family is files only.  A toy served family and a toy trained
family that exist in this file alone (a reference, a system adapter and
their seeded weights, put where ``benchmarks.reference.<name>`` and
``benchmarks.systems.<name>`` are looked for) go through ``run.run_cell``
with no file of ``benchmarks/`` changed: sound runs come out correct,
broken ones do not, and a configuration that names no reference, or a
module without the contract, is refused with a message that says so.
And through the same dispatch the two families the benchmark has read
what the direct composition read before it: bit for bit."""
import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check_served, check_train, reference, run, weights
from benchmarks.reference import ernie, lowp, mistral
from benchmarks.systems import ernie_train

from conftest import ROOT, load_data

PRETRAIN = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                       "pretrain.json")))


def _ctx(config, traffic, cell, seed, seconds):
    return run.Context(config, traffic, cell, 1, seed, seconds, 0,
                       jax.devices()[:1], time.monotonic(),
                       say=lambda s: print(s))


def _module(name, **members):
    mod = types.ModuleType(name)
    mod.__dict__.update(members)
    return mod


# ------------------------------------------------- a toy served family
# a Markov model: the next token's logits are tanh(embed[token] @ w) @ head

MARKOV = {
    "kind": "serving", "system": "toy_markov", "reference": "toy_markov",
    "vocab_size": 96, "hidden_size": 24, "torch_dtype": "bfloat16",
    "check": {"sample_requests": 3, "max_tokens_per_request": 24,
              "limit_logit_gap": 1e-4}}


def _markov_weights(cfg, seed):
    """The family's seeded weights, in the served type."""
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    keys = jax.random.split(weights.seed_key(seed, 41), 3)
    make = lambda k, shape: jax.random.normal(k, shape, jnp.float32) \
        .astype(cfg["torch_dtype"])
    return {"embed": make(keys[0], (v, h)), "w": make(keys[1], (h, h)),
            "head": make(keys[2], (h, v))}


def _markov_served_logits(cfg, seed, tokens, rows, precision="float32"):
    w = {k: a.astype(jnp.float32) for k, a in
         _markov_weights(cfg, seed).items()}
    r = lowp.rounder(precision)
    x = w["embed"][jnp.asarray(np.asarray(tokens)[np.asarray(rows)])]
    return lowp.matmul(jnp.tanh(lowp.matmul(x, w["w"], r)), w["head"], r)


class _Answer:
    def __init__(self, tokens):
        self._tokens = tokens

    def stream(self, timeout=None):
        for t in self._tokens:
            yield [t]

    def result(self, timeout=None):
        return self._tokens


class _Steps:
    """What the harness reads of a StepLog: one record a launch."""

    def __init__(self):
        self._records = []

    def add(self, temp_bytes):
        self._records.append({"ts": time.time(), "kind": "decode",
                              "failed": False,
                              "program_temp_bytes": temp_bytes})

    def records(self):
        return list(self._records)


class _MarkovSystem:
    kind = "serving"
    token_budget, max_batch = 64, 4

    def __init__(self, config, devices, seed, traced):
        self.config, self.seed = config, seed
        self.steplog = _Steps()

    def build(self):
        w = _markov_weights(self.config, self.seed)
        self._next = jax.jit(lambda t: jnp.argmax(jnp.matmul(
            jnp.tanh(jnp.matmul(w["embed"][t].astype(jnp.float32),
                                w["w"].astype(jnp.float32),
                                precision="highest")),
            w["head"].astype(jnp.float32), precision="highest")))

    def warm(self, traffic):
        self.submit(np.asarray([1, 2], np.int32), 2)

    def submit(self, ids, max_new):
        out, last = [], int(ids[-1])
        for _ in range(int(max_new)):
            last = int(self._next(last))
            out.append(last)
        self.steplog.add(4096 * (1 + len(self.steplog.records()) % 2))
        return _Answer(out)

    def queue_wait_spans(self):
        return []

    def free(self):
        self._next = None


class _AlteredMarkov(_MarkovSystem):
    """Tokens altered where they are produced."""

    def submit(self, ids, max_new):
        toks = super().submit(ids, max_new).result()
        return _Answer([(t + 1) % self.config["vocab_size"] for t in toks])


@pytest.fixture
def toy_markov(monkeypatch):
    monkeypatch.setitem(sys.modules, "benchmarks.reference.toy_markov",
                        _module("benchmarks.reference.toy_markov",
                                served_logits=_markov_served_logits))
    monkeypatch.setitem(sys.modules, "benchmarks.systems.toy_markov",
                        _module("benchmarks.systems.toy_markov",
                                System=_MarkovSystem))


@pytest.mark.parametrize("system,correct", [(_MarkovSystem, True),
                                            (_AlteredMarkov, False)])
def test_a_served_family_of_this_file_alone_is_checked(
        toy_markov, monkeypatch, system, correct):
    monkeypatch.setattr(sys.modules["benchmarks.systems.toy_markov"],
                        "System", system)
    res = run.run_cell(_ctx(MARKOV, load_data("tiny-chat.json"),
                            {"rate_rps": 4.0}, 2 ** 31 + 5, 1.0))
    assert res["failed"] == 0 and res["attempted"] == 4
    assert res["correct"] is correct
    (value, limit), = res["check"].values()
    assert limit == MARKOV["check"]["limit_logit_gap"]
    assert (value <= limit) is correct
    # a serving line's peak: the allocator's (a CPU reports none) plus the
    # largest temporaries the window's steps recorded
    ev = res["evidence"]
    assert ev.program_temp_bytes == max(
        s["program_temp_bytes"] for s in ev.steps) == 8192
    assert ev.memory_peak_bytes is None
    ev.allocator_peak_bytes = 1000
    assert ev.memory_peak_bytes == 1000 + 8192


def test_the_toy_served_control_fails_its_limit(toy_markov):
    rng = np.random.default_rng(3)
    cases = [(rng.integers(0, MARKOV["vocab_size"], 24).astype(np.int32),
              np.arange(24), None) for _ in range(8)]
    control = check_served.gaps(MARKOV, 7, cases, precision="fp8")
    assert control.size == 192
    assert control.max() > 100 * MARKOV["check"]["limit_logit_gap"]


# ------------------------------------------------ a toy trained family
# y = tanh(x @ w1) @ w2, mean squared error, plain gradient descent

REGRESS = {
    "kind": "training", "system": "toy_regress", "reference": "toy_regress",
    "batch_size": 16, "seq_len": 1, "width": 12, "learning_rate": 0.05,
    "check": {"head_leaves": ["w2"],
              "limits": {"loss_gap_step1": 1e-4, "grad_norm_gap_head": 1e-3,
                         "delta_norm_gap_matrices": 1e-3}}}


def _regress_weights(cfg, seed):
    k1, k2 = jax.random.split(weights.seed_key(seed, 43))
    d = cfg["width"]
    return {"w1": jax.random.normal(k1, (d, d), jnp.float32) * 0.3,
            "w2": jax.random.normal(k2, (d, 1), jnp.float32) * 0.3}


def _regress_batches(config, traffic, seed):
    out = []
    for j in range(int(traffic["distinct_batches"])):
        rng = np.random.default_rng([int(seed), 13, j])
        x = rng.normal(size=(config["batch_size"], config["width"]))
        out.append((x.astype(np.float32),
                    np.sin(x.sum(-1, keepdims=True)).astype(np.float32)))
    return out


def _regress_loss(p, x, t, r=lambda a: a):
    y = lowp.matmul(jnp.tanh(lowp.matmul(x, p["w1"], r)), p["w2"], r)
    return jnp.mean((y - t) ** 2)


def _norms(tree):
    return {k: float(jnp.linalg.norm(v.ravel())) for k, v in tree.items()}


def _regress_first_steps(cfg, seed, batches, n=3, precision="float32",
                         mask_stream=9):
    r = lowp.rounder(precision)
    p = start = _regress_weights(cfg, seed)
    losses, grad_norms = [], None
    for t in range(n):
        loss, g = jax.value_and_grad(_regress_loss)(
            p, *batches[t % len(batches)], r)
        losses.append(float(loss))
        grad_norms = grad_norms or _norms(g)
        p = {k: p[k] - cfg["learning_rate"] * g[k] for k in p}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": _norms({k: p[k] - start[k] for k in p})}


class _RegressSystem:
    kind = "training"

    def __init__(self, config, devices, seed, traced):
        self.config, self.seed = config, seed

    def build(self):
        lr = self.config["learning_rate"]
        self.params = _regress_weights(self.config, self.seed)

        @jax.jit
        def step(p, x, t):
            loss, g = jax.value_and_grad(_regress_loss)(p, x, t)
            return {k: p[k] - lr * g[k] for k in p}, loss, g

        self._step = step

    def call(self, batch):
        self.params, loss, self._grads = self._step(self.params, *batch)
        return loss

    def first_steps(self, batches, n=3):
        start, losses, grad_norms = self.params, [], None
        for t in range(n):
            losses.append(float(self.call(batches[t % len(batches)])))
            grad_norms = grad_norms or _norms(self._grads)
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": _norms({k: self.params[k] - start[k]
                                       for k in start})}

    def program_temp_bytes(self, batch):
        return None

    def program_report(self):
        return None

    def free(self):
        self.params = self._step = None


class _FrozenRegress(_RegressSystem):
    """A step that returns its state unchanged."""

    def call(self, batch):
        keep = self.params
        loss = super().call(batch)
        self.params = keep
        return loss


@pytest.fixture
def toy_regress(monkeypatch):
    monkeypatch.setitem(sys.modules, "benchmarks.reference.toy_regress",
                        _module("benchmarks.reference.toy_regress",
                                first_steps=_regress_first_steps,
                                matrix_leaves=lambda cfg: ["w1", "w2"]))
    monkeypatch.setitem(sys.modules, "benchmarks.systems.toy_regress",
                        _module("benchmarks.systems.toy_regress",
                                System=_RegressSystem,
                                make_batches=_regress_batches))


@pytest.mark.parametrize("system,correct", [(_RegressSystem, True),
                                            (_FrozenRegress, False)])
def test_a_trained_family_of_this_file_alone_is_checked(
        toy_regress, monkeypatch, system, correct):
    monkeypatch.setattr(sys.modules["benchmarks.systems.toy_regress"],
                        "System", system)
    res = run.run_cell(_ctx(REGRESS, PRETRAIN, {}, 2 ** 31 + 9, 0.3))
    assert res["attempted"] >= 3 and res["correct"] is correct
    assert set(res["check"]) == set(REGRESS["check"]["limits"])
    over = [k for k, (v, lim) in res["check"].items() if not v <= lim]
    assert over == ([] if correct else ["delta_norm_gap_matrices"])
    assert res["evidence"].positions_per_step == 16


def test_the_toy_trained_control_fails_a_limit(toy_regress):
    batches = _regress_batches(REGRESS, PRETRAIN, 5)
    ref = check_train.reference_readings(REGRESS, 5, batches)
    ctrl = check_train.reference_readings(REGRESS, 5, batches,
                                          precision="fp8")
    numbers = check_train.compare(REGRESS, ctrl, ref)
    assert any(numbers[k] > v
               for k, v in REGRESS["check"]["limits"].items()), numbers


# ------------------------------------- what a configuration must name

@pytest.mark.parametrize("config,fixture", [(MARKOV, "toy_markov"),
                                            (REGRESS, "toy_regress")],
                         ids=["serving", "training"])
def test_a_configuration_that_names_no_reference_is_refused(
        config, fixture, request):
    request.getfixturevalue(fixture)
    nameless = {k: v for k, v in config.items() if k != "reference"}
    with pytest.raises(KeyError, match="names no plain reference"):
        run.run_cell(_ctx(nameless, PRETRAIN, {}, 1, 0.2))
    with pytest.raises(KeyError, match="no benchmarks/reference/absent.py"):
        run.run_cell(_ctx(dict(config, reference="absent"), PRETRAIN, {},
                          1, 0.2))


@pytest.mark.parametrize("config,lacks", [
    (MARKOV, "served_logits"), (REGRESS, "first_steps, matrix_leaves")],
    ids=["serving", "training"])
def test_a_reference_without_its_contract_is_refused(monkeypatch, config,
                                                     lacks):
    monkeypatch.setitem(sys.modules, "benchmarks.reference.hollow",
                        _module("benchmarks.reference.hollow",
                                logits_at=lambda *a: None))
    with pytest.raises(TypeError, match=f"hollow.py lacks {lacks}"):
        run.run_cell(_ctx(dict(config, reference="hollow"), PRETRAIN, {},
                          1, 0.2))


def test_a_kind_without_a_runner_is_refused():
    with pytest.raises(ModuleNotFoundError, match="benchmarks.folding_run"):
        run.runner_for("folding")


# ------------------------- the two families the benchmark has, bit for bit

def _llama_cases(config):
    rng = np.random.default_rng(11)
    cases = []
    for prompt, new in ((37, 20), (9, 64)):
        seq = rng.integers(0, config["vocab_size"], prompt + new - 1)
        served = rng.integers(0, config["vocab_size"], new)
        cases.append((seq.astype(np.int32),
                      np.arange(prompt - 1, len(seq)),
                      served.astype(np.int32)))
    return cases


@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_served_gaps_through_the_dispatch_are_the_direct_composition_s(
        precision):
    config, seed = load_data("tiny-llama.json"), 2 ** 31 + 4
    cases = _llama_cases(config)
    dtype = config["torch_dtype"]
    outer = weights.llama_outer_weights(config, seed, dtype)
    layer = lambda i: weights.llama_layer_weights(config, seed, i, dtype)
    width = config["check"]["max_tokens_per_request"]
    direct = []
    for seq, rows, served in cases:
        n = len(rows)
        padded = np.concatenate([rows, np.full((width - n,), rows[-1])])
        ref = np.asarray(mistral.logits_at(config, layer, outer, seq,
                                           padded))[:n]
        if precision != "float32":
            served = np.asarray(mistral.logits_at(
                config, layer, outer, seq, padded, precision))[:n].argmax(-1)
        direct.append(ref.max(-1) - ref[np.arange(n), served])
    got = check_served.gaps(config, seed, cases, precision)
    assert got.dtype == np.float32 and got.shape == (84,)
    assert np.array_equal(got, np.concatenate(direct))
    assert got.max() > 0


def test_training_readings_through_the_dispatch_are_the_direct_call_s():
    config, seed = load_data("tiny-ernie.json"), 2 ** 31 + 6
    batches = ernie_train.make_batches(config, PRETRAIN, seed)
    direct = ernie.follow_steps(
        config, config["optimizer"],
        weights.ernie_weights(config, config["seq_len"], seed),
        [batches[t % len(batches)] for t in range(3)],
        weights.seed_key(seed, 9), "float32", config["check"]["row_block"])
    assert check_train.reference_readings(config, seed, batches) == direct
    mats = reference.find(config).matrix_leaves(config)
    assert "word_emb" in mats and "l1.wqkv" in mats and "dec_b" not in mats
