"""Request decks and arrival schedules, from a traffic file's parameters.

A traffic file states length distributions, not draws.  The deck of a
window is the N quantile mid-points of each distribution (N = the number
of requests the window offers), prompt and output lengths paired by a
permutation that the file fixes (``pairing_seed``).  So the multiset of
(prompt length, output length) measured in a window is identical in every
run of a cell; ``--seed`` only permutes the order in which the deck is
dealt, jitters the arrivals inside their slots and draws the token ids.
The ramp before the window deals from a separate copy of the deck.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from .rng import SplitMix

# streams of one --seed (see rng.SplitMix)
STREAM_ORDER, STREAM_JITTER, STREAM_RAMP_ORDER, STREAM_TOKENS = 1, 2, 3, 4


def quantile_midpoints(dist: Dict, n: int) -> List[int]:
    """The n mid-points (k + 1/2) / n of ``dist``'s quantile function,
    rounded to whole tokens.  Kinds: ``loguniform`` and ``uniform`` over
    [lo, hi], ``constant`` (value)."""
    kind = dist["kind"]
    if n < 1:
        return []
    if kind == "constant":
        return [int(dist["value"])] * n
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if not 0 < lo <= hi:
        raise ValueError(f"bad range in {dist}")
    out = []
    for k in range(n):
        u = (k + 0.5) / n
        if kind == "loguniform":
            x = lo * math.exp(u * math.log(hi / lo))
        elif kind == "uniform":
            x = lo + u * (hi - lo)
        else:
            raise ValueError(f"unknown distribution kind {kind!r}")
        out.append(int(round(x)))
    return out


def build_deck(traffic: Dict, n: int) -> List[Tuple[int, int]]:
    """The n (prompt_len, output_len) pairs of one deck, in the file's own
    order: prompts ascending, outputs paired by the file's permutation."""
    prompts = quantile_midpoints(traffic["prompt_len"], n)
    outputs = quantile_midpoints(traffic["output_len"], n)
    pairing = SplitMix(int(traffic["pairing_seed"])).permutation(n)
    return [(prompts[i], outputs[pairing[i]]) for i in range(n)]


def deal(deck: List[Tuple[int, int]], seed: int, stream: int) -> List[Tuple[int, int]]:
    """The deck in the order this seed deals it."""
    order = SplitMix(seed, stream).permutation(len(deck))
    return [deck[i] for i in order]


def arrivals(rate_rps: float, n: int, seed: int,
             stream: int = STREAM_JITTER) -> List[float]:
    """n due times in seconds from 0, at ``rate_rps``: one arrival per slot
    of 1/rate, placed uniformly inside its slot — the steady flow of many
    independent users."""
    rng = SplitMix(seed, stream)
    slot = 1.0 / float(rate_rps)
    return [(k + rng.uniform()) * slot for k in range(n)]


def token_ids(seed: int, index: int, length: int, vocab: int):
    """The prompt of request ``index``: distinct per request, so no two
    share a prefix unless the traffic file asks for one."""
    import numpy as np

    rng = np.random.default_rng([int(seed), STREAM_TOKENS, int(index)])
    # ids 0..2 are left to pad/bos/eos by convention
    return rng.integers(3, vocab, size=int(length), dtype=np.int64).astype(
        np.int32)
