"""The control of ``correct``, at a size a test can hold: the plain
reference computed in the precision below the configuration's (fp8 for
bfloat16), put in the program's place, comes out NOT correct, while the
program itself comes out correct.  The readings at the cells' own sizes,
on the chip, are in PERF.md section 2."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import check_served, check_train, run
from benchmarks.reference import lowp
from benchmarks.systems import ernie_train

from conftest import ROOT, load_data


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 2])
def test_served_control_fails_where_the_program_passes(seed):
    config = load_data("tiny-llama.json")
    # every served request in the sample, and longer answers: some hundred
    # tokens, so that the control's few flipped tokens are surely among them
    config["check"] = dict(config["check"], sample_requests=64)
    traffic = dict(load_data("tiny-chat.json"),
                   output_len={"kind": "uniform", "lo": 16, "hi": 32})
    ctx = run.Context(config, traffic,
                      {"rate_rps": 4.0}, 1, seed, 3.0, 0, jax.devices()[:1],
                      time.monotonic(), say=lambda s: None)
    res = run.run_cell(ctx)
    assert res["correct"] is True
    spec = config["check"]
    cases = check_served.sample(res["evidence"].records, seed,
                                spec["sample_requests"],
                                spec["max_tokens_per_request"])
    assert sum(len(c[2]) for c in cases) >= 150
    limit = spec["limit_logit_gap"]
    sound = check_served.gaps(config, seed, cases)
    control = check_served.gaps(config, seed, cases, precision="fp8")
    assert sound.max() <= limit < control.max()
    # the longest served request is always in the sample
    longest = max(len(r.prompt) + len(r.tokens)
                  for r in res["evidence"].records if r.tokens)
    assert max(len(c[0]) + 1 for c in cases) >= min(
        longest, spec["max_tokens_per_request"])


# ------------------------------------ a second number beside the widest

def _gaps(widest, p99_level, n=600):
    """n gaps: nought but for the top hundredth at ``p99_level`` and one
    at ``widest``."""
    g = np.zeros(n)
    g[:n // 100 + 1] = p99_level
    g[0] = widest
    return g


@pytest.mark.parametrize("g,spec,want,names", [
    # one near-tie flips a token far down: the maximum's business alone
    (_gaps(1.34, 0.05), {"limit_logit_gap": 2.7,
                         "limit_logit_gap_p99": 0.55}, True,
     ["widest_logit_gap", "logit_gap_p99"]),
    (_gaps(1.34, 0.05), {"limit_logit_gap": 1.15,
                         "limit_logit_gap_p99": 0.55}, False,
     ["widest_logit_gap", "logit_gap_p99"]),
    # a lower precision moves the hundredth and stays under the maximum
    (_gaps(1.7, 1.2), {"limit_logit_gap": 2.7,
                       "limit_logit_gap_p99": 0.55}, False,
     ["widest_logit_gap", "logit_gap_p99"]),
    # one altered token among six hundred: the widest's to catch
    (_gaps(6.8, 0.05), {"limit_logit_gap": 2.7,
                        "limit_logit_gap_p99": 0.55}, False,
     ["widest_logit_gap", "logit_gap_p99"]),
    # a configuration that states no second limit compares one number
    (_gaps(1.7, 1.2), {"limit_logit_gap": 2.7}, True,
     ["widest_logit_gap"]),
    (np.zeros((0,)), {"limit_logit_gap": 2.7,
                      "limit_logit_gap_p99": 0.55}, False,
     ["widest_logit_gap", "logit_gap_p99"]),
], ids=["one_near_tie", "one_near_tie_old_limit", "lower_precision",
        "one_altered_token", "one_limit_stated", "nothing_served"])
def test_the_served_check_compares_the_limits_its_configuration_states(
        monkeypatch, g, spec, want, names):
    monkeypatch.setattr(check_served, "sample", lambda *a: ["case"])
    monkeypatch.setattr(check_served, "gaps", lambda *a: g)
    said = []
    correct, compared = check_served.check(
        {"check": dict(spec, sample_requests=4, max_tokens_per_request=256)},
        7, [], said.append)
    assert correct is want and list(compared) == names
    for name, (value, limit) in compared.items():
        assert limit == spec["limit_" + name.replace(
            "widest_logit_gap", "logit_gap")]
        if g.size:
            assert f"{name} {value:.6f} (limit {limit})" in said[0]
    if g.size:
        assert compared["widest_logit_gap"][0] == g.max()
        if "logit_gap_p99" in compared:
            assert compared["logit_gap_p99"][0] == check_served.gap_quantile(
                g, 0.99) == np.sort(g)[int(0.99 * (g.size - 1))]


def test_the_committed_latent_cell_states_both_limits_and_chat_one(
        benchmark_json):
    checks = {}
    for c in benchmark_json["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            checks[c["name"]] = json.load(f)["check"]
    latent = checks["a.x-k1-ep16-d7"]
    # PERF.md section 2: between the sound runs' largest and the
    # control's smallest, at the cell's own load
    assert 0.208 < latent["limit_logit_gap_p99"] < 1.15
    assert 1.3427 < latent["limit_logit_gap"] < 3.44
    assert "limit_logit_gap_p99" not in checks["mistral-7b-v0.1-d12"]
    assert checks["mistral-7b-v0.1-d12"]["limit_logit_gap"] == 0.7


def test_fp8_roundings():
    x = np.linspace(-1e-6, 1e-6, 257).astype(np.float32)
    # the plain cast flushes what is small to zero, gradients above all
    plain = lowp.rounder("fp8")
    assert np.abs(np.asarray(plain(jax.numpy.asarray(x)))).max() == 0
    big = np.linspace(0.5, 2.0, 97).astype(np.float32)
    rel = np.abs(np.asarray(plain(jax.numpy.asarray(big))) - big) / big
    assert 0.005 < rel.max() < 0.07             # three bits of mantissa
    # under a per-tensor scale nothing flushes, forward or backward
    r = lowp.rounder("fp8_scaled")
    y = np.asarray(r(jax.numpy.asarray(x)))
    keep = np.abs(x) > 1e-7
    assert 0.005 < (np.abs(y - x)[keep] / np.abs(x)[keep]).max() < 0.07
    g = np.asarray(jax.grad(lambda v: (r(v) * 1e-9).sum())(
        jax.numpy.asarray(x)))
    assert np.isfinite(g).all() and np.abs(g).max() > 0
    assert (np.asarray(lowp.rounder("float32")(x)) == x).all()
    with pytest.raises(ValueError):
        lowp.rounder("int3")


def _first_steps(system_cls, program_config, config, traffic, seed):
    batches = ernie_train.make_batches(config, traffic, seed)
    system = system_cls(program_config, jax.devices()[:1], seed, False)
    system.build()
    try:
        got = system.first_steps(batches)
    finally:
        system.free()
    ref = check_train.reference_readings(config, seed, batches)
    return check_train.compare(config, got, ref)


class _HalfBatch(ernie_train.System):
    """A step that leaves out half of the rows it is fed."""

    def call(self, batch):
        return super().call(tuple(a[:len(a) // 2] for a in batch))


class _SkippedUpdate(ernie_train.System):
    """A step that computes its loss and keeps its parameters."""

    def call(self, batch):
        keep = jax.tree_util.tree_map(jax.numpy.copy, self.step.params)
        loss = super().call(batch)
        self.step.params = keep
        return loss


@pytest.mark.parametrize("fault,caught_by", [
    ("dropped_nsp_term", "loss_gap_step1"),
    ("half_batch", "grad_norm_gap_head"),
    ("doubled_learning_rate", "delta_norm_gap_matrices"),
    ("skipped_update", "delta_norm_gap_matrices")])
def test_each_planted_fault_fails_the_limit_that_is_there_for_it(
        fault, caught_by, monkeypatch):
    """The program itself with one fault planted in it, at a tiny size:
    the number that is judged for that fault comes out over its limit,
    and the sound program stays under every limit."""
    config = dict(load_data("tiny-ernie.json"), batch_size=32)
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "pretrain.json")))
    seed = 2 ** 31 + 21
    limits = config["check"]["limits"]
    cls, program_config = ernie_train.System, config
    if fault == "dropped_nsp_term":
        import paddle_infer_tpu.models as models
        from paddle_infer_tpu.models.losses import masked_lm_loss

        monkeypatch.setattr(
            models, "ernie_pretrain_loss",
            lambda mlm, nsp, labels, nsp_labels: masked_lm_loss(
                mlm, labels, ignore_index=-100))
    elif fault == "half_batch":
        cls = _HalfBatch
    elif fault == "doubled_learning_rate":
        program_config = dict(config, optimizer=dict(
            config["optimizer"],
            learning_rate=2 * config["optimizer"]["learning_rate"]))
    else:
        cls = _SkippedUpdate
    broken = _first_steps(cls, program_config, config, traffic, seed)
    assert broken[caught_by] > 1.5 * limits[caught_by], broken
    if fault == "skipped_update":       # once is enough: the sound program
        monkeypatch.undo()
        sound = _first_steps(ernie_train.System, config, config, traffic,
                             seed)
        assert all(sound[k] <= v / 1.5 for k, v in limits.items()), sound


def test_training_control_fails_where_the_program_passes():
    """ERNIE at a tiny size: the program's first steps agree with the
    float32 reference inside the limits, the reference in plain fp8 put in
    its place does not (its gradients flush to zero)."""
    config = load_data("tiny-ernie.json")
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "pretrain.json")))
    seed = 2 ** 31 + 21
    batches = ernie_train.make_batches(config, traffic, seed)
    system = ernie_train.System(config, jax.devices()[:1], seed, False)
    system.build()
    try:
        got = system.first_steps(batches)
    finally:
        system.free()
    ref = check_train.reference_readings(config, seed, batches)
    ctrl = check_train.reference_readings(config, seed, batches,
                                          precision="fp8")
    sound = check_train.compare(config, got, ref)
    control = check_train.compare(config, ctrl, ref)
    limits = config["check"]["limits"]
    assert all(sound[k] <= v for k, v in limits.items()), sound
    assert any(control[k] > v for k, v in limits.items()), control
    assert control["grad_norm_gap_matrices"] > 3 * sound[
        "grad_norm_gap_matrices"]
