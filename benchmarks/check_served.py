"""``correct`` for a served model: the gaps by which the served (greedy)
tokens' logits lie below the plain reference's best, over a seeded sample
of the requests the run served, the longest among them.  Compared: the
widest of them and, where the configuration's ``check`` states a
``limit_logit_gap_p99``, their 99th percentile too (a maximum over some
hundred tokens swings with one near-tie; the percentile is steady from
seed to seed and is what a lower precision moves).

The reference is the module the configuration's file names under
``reference`` (``reference/__init__.py`` has what is asked of it); it
runs once over each sampled prompt with its served tokens (teacher-forced:
under greedy decoding the served stream is its own input).  The control
reads, at the same positions, the gap of the token a lower precision puts
first.
"""
from __future__ import annotations

import numpy as np

from . import reference
from .rng import SplitMix


def sample(records, seed: int, n: int, max_tokens: int = 256):
    """The longest served request and n - 1 others drawn from the seed;
    each as (sequence fed, rows whose next token was served, served)."""
    served = [r for r in records if r.tokens]
    if not served:
        return []
    served.sort(key=lambda r: (r.prompt_len + len(r.tokens), r.index))
    picks = [served.pop()]
    order = SplitMix(seed, 77).permutation(len(served))
    picks += [served[i] for i in order[:max(0, n - 1)]]
    out = []
    for r in picks:
        toks = r.tokens[:max_tokens]
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(toks[:-1], np.int32)])
        rows = np.arange(r.prompt_len - 1, len(seq))
        out.append((seq, rows, np.asarray(toks, np.int32)))
    return out


def gaps(config: dict, seed: int, cases, precision: str = "float32"):
    """Per served token: reference's best logit minus the served token's.
    With a lower ``precision`` the "served" token is replaced by the one
    that precision puts first (the control)."""
    logits = reference.find(config).served_logits
    out = []
    width = int(config["check"]["max_tokens_per_request"])
    for seq, rows, served in cases:
        # one shape of head for every request: rows padded by repetition
        n = len(rows)
        padded = np.concatenate([rows, np.full((width - n,), rows[-1])])
        ref = np.asarray(logits(config, seed, seq, padded))[:n]
        if precision != "float32":
            low = np.asarray(logits(config, seed, seq, padded,
                                    precision))[:n]
            served = low.argmax(-1)
        out.append(ref.max(-1) - ref[np.arange(n), served])
    return np.concatenate(out) if out else np.zeros((0,))


def gap_quantile(g, q: float) -> float:
    """The order statistic at ``int(q * (n - 1))``: what ``control.py``
    read the limits' readings with."""
    return float(np.sort(np.asarray(g))[int(q * (len(g) - 1))])


def check(config: dict, seed: int, records, say=print):
    """(correct, {number compared: [value, limit]})."""
    spec = config["check"]
    cases = sample(records, seed, int(spec["sample_requests"]),
                   int(spec["max_tokens_per_request"]))
    g = gaps(config, seed, cases)
    limits = {"widest_logit_gap": float(spec["limit_logit_gap"])}
    if "limit_logit_gap_p99" in spec:
        limits["logit_gap_p99"] = float(spec["limit_logit_gap_p99"])
    if g.size == 0:
        say("check: no served token to compare -> not correct")
        return False, {name: [None, lim] for name, lim in limits.items()}
    read = {"widest_logit_gap": float(g.max()),
            "logit_gap_p99": gap_quantile(g, 0.99)}
    compared = {name: [read[name], lim] for name, lim in limits.items()}
    say(f"check: served tokens compared {g.size} over {len(cases)} "
        f"requests; exact argmax {int((g == 0).sum())}; " + "; ".join(
            f"{name} {value:.6f} (limit {lim})"
            for name, (value, lim) in compared.items()))
    return (bool(np.isfinite(g).all() and all(
        value <= lim for value, lim in compared.values())), compared)
