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
