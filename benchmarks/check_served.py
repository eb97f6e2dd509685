"""``correct`` for a served model: the widest gap by which a served
(greedy) token's logit lies below the plain reference's best, over a
seeded sample of the requests the run served, the longest among them.

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


def check(config: dict, seed: int, records, say=print):
    """(correct, {number compared: [value, limit]})."""
    spec = config["check"]
    cases = sample(records, seed, int(spec["sample_requests"]),
                   int(spec["max_tokens_per_request"]))
    g = gaps(config, seed, cases)
    limit = float(spec["limit_logit_gap"])
    if g.size == 0:
        say("check: no served token to compare -> not correct")
        return False, {"widest_logit_gap": [None, limit]}
    widest = float(g.max())
    say(f"check: served tokens compared {g.size} over {len(cases)} "
        f"requests; exact argmax {int((g == 0).sum())}; "
        f"widest_logit_gap {widest:.6f} (limit {limit})")
    return (bool(np.isfinite(g).all() and widest <= limit),
            {"widest_logit_gap": [widest, limit]})
