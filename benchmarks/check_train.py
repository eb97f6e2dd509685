"""``correct`` for a training step: the program's first three steps
against the plain reference's, by the first step's loss, the norm of the
first gradient as the optimizer got it, and the norm of the parameters'
change after the three — norms compared leaf by leaf as the gap between
the two norms (never the norm of a difference: the two sides draw their
own dropout masks) against the reference's norm of that leaf or of the
median leaf, whichever is larger.

Each judged number is there for a fault (tests/benchmarks plants each):
``loss_gap_step1`` a term of the loss dropped or scaled (later steps'
losses are chaotic after Adam's first sign-like update: recorded only);
``grad_norm_gap_head`` a part of the batch left out, or gradients flushed
by a lower precision: the worst of the leaves the configuration names as
``check.head_leaves``, those whose gradient is a sum over every token
(the masked-LM head and the tied embedding).  At seeded weights a gradient
is noise, so its norm grows by 1.41 when half of the rows are dropped —
but the leaves under the pooler take most of theirs from the 64 rows of
the next-sentence loss, and their norms move as much under another draw
of the dropout masks as under half a batch (PERF.md section 2);
``delta_norm_gap_matrices`` a step that keeps its state, or another
learning rate.  The rest is recorded beside them, not judged.

The reference is the module the configuration's file names under
``reference`` (``reference/__init__.py`` has what is asked of it)."""
from __future__ import annotations

import statistics

from . import reference


def reference_readings(config: dict, seed: int, batches, n: int = 3,
                       precision: str = "float32",
                       mask_stream: int = 9) -> dict:
    return reference.find(config).first_steps(config, seed, batches, n,
                                              precision, mask_stream)


def worst_leaf(got: dict, ref: dict, leaves=None):
    """(widest relative gap, its leaf) over ``leaves`` (all by default)."""
    floor = statistics.median(ref.values())
    worst, at = 0.0, None
    for name in (leaves or ref):
        gap = abs(got[name] - ref[name]) / max(ref[name], floor)
        if gap > worst:
            worst, at = gap, name
    return worst, at


def compare(config: dict, got: dict, ref: dict) -> dict:
    mats = reference.find(config).matrix_leaves(config)
    g_all, g_at = worst_leaf(got["grad_norms"], ref["grad_norms"])
    g_mat, gm_at = worst_leaf(got["grad_norms"], ref["grad_norms"], mats)
    d_mat, dm_at = worst_leaf(got["delta_norms"], ref["delta_norms"], mats)
    g_head, gh_at = worst_leaf(got["grad_norms"], ref["grad_norms"],
                               config["check"]["head_leaves"])
    total = lambda d: sum(v * v for v in d.values()) ** 0.5
    ratio = statistics.median(
        got["grad_norms"][n] / ref["grad_norms"][n] for n in mats)
    return {
        "loss_gap_step1": abs(got["losses"][0] - ref["losses"][0]),
        "loss_gap": max(abs(a - b) for a, b in
                        zip(got["losses"], ref["losses"])),
        "grad_norm_gap_head": g_head,
        "grad_norm_gap_median": abs(ratio - 1.0),
        "grad_norm_gap_matrices": g_mat,
        "grad_norm_gap_all_leaves": g_all,
        "grad_norm_gap_global": abs(total(got["grad_norms"])
                                    - total(ref["grad_norms"]))
        / total(ref["grad_norms"]),
        "delta_norm_gap_matrices": d_mat,
        "_at": {"grad_head": gh_at, "grad_all": g_at, "grad_matrices": gm_at,
                "delta_matrices": dm_at}}


def check(config: dict, seed: int, batches, got: dict, say=print):
    """(correct, {number compared: [value, limit]})."""
    ref = reference_readings(config, seed, batches)
    numbers = compare(config, got, ref)
    say(f"check: losses program {got['losses']} reference {ref['losses']}")
    ok, compared = True, {}
    for name, limit in config["check"]["limits"].items():
        value = numbers[name]
        say(f"check: {name} {value:.6f} (limit {limit})")
        ok = ok and value == value and value <= float(limit)
        compared[name] = [value, float(limit)]
    say(f"check: recorded, not judged: " + ", ".join(
        f"{k} {v:.6f}" for k, v in numbers.items()
        if not k.startswith("_") and k not in config["check"]["limits"]))
    say(f"check: worst leaves {numbers['_at']}")
    return ok, compared
