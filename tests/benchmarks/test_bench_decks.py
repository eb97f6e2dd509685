import collections
import json
import math
import os

import numpy as np
import pytest

from benchmarks import decks
from benchmarks.generators import open_deck
from benchmarks.rng import SplitMix

from conftest import ROOT

CHAT = json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "chat.json")))
# each open-loop cell: its traffic file, the two distributions and the
# pairing its deck has had since the cell was added
CELLS = {"mistral-d12.chat": ("chat", (32, 512), (32, 256), 20260927),
         "axk1-ep16.ragchat": ("ragchat", (512, 3072), (64, 256), 20260928)}
UNIFORM = dict(CHAT, prompt_len={"kind": "uniform", "lo": 512, "hi": 1536},
               output_len={"kind": "uniform", "lo": 32, "hi": 128})


def test_splitmix_is_fixed_bits():
    # the decks of every machine and version rest on these
    r = SplitMix(1)
    assert [r.next_u64() for _ in range(2)] == [13757245211066428519, 17911839290282890590]
    assert SplitMix(7).permutation(6) == [1, 5, 4, 2, 0, 3]
    assert SplitMix(7).permutation(6) == SplitMix(7).permutation(6)
    assert sorted(SplitMix(7).permutation(50)) == list(range(50))
    assert SplitMix(7).permutation(50) != SplitMix(8).permutation(50)
    assert SplitMix(7, 1).permutation(50) != SplitMix(7, 2).permutation(50)
    us = [SplitMix(3).uniform() for _ in range(3)]
    assert us[0] == us[1] == us[2] and 0.0 <= us[0] < 1.0


@pytest.mark.parametrize("n", [1, 7, 31, 64])
def test_quantile_midpoints_cover_the_range(n):
    xs = decks.quantile_midpoints({"kind": "loguniform", "lo": 32,
                                   "hi": 512}, n)
    assert len(xs) == n and xs == sorted(xs)
    assert 32 <= xs[0] and xs[-1] <= 512
    if n >= 31:
        # median of a log-uniform is the geometric mean of its ends
        assert abs(xs[n // 2] - math.sqrt(32 * 512)) < 8
    ys = decks.quantile_midpoints({"kind": "uniform", "lo": 512,
                                   "hi": 1536}, n)
    assert abs(sum(ys) / n - 1024) <= 1
    assert decks.quantile_midpoints({"kind": "constant", "value": 9},
                                    n) == [9] * n


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        decks.quantile_midpoints({"kind": "zipf", "lo": 1, "hi": 2}, 3)


@pytest.mark.parametrize("traffic", [CHAT, UNIFORM], ids=["chat", "uniform"])
def test_deck_is_the_same_multiset_for_every_seed(traffic):
    deck = decks.build_deck(traffic, 31)
    dealt = [decks.deal(deck, seed, decks.STREAM_ORDER)
             for seed in (1, 2, 2 ** 31 + 17)]
    want = collections.Counter(deck)
    for d in dealt:
        assert collections.Counter(d) == want
    assert dealt[0] != dealt[1] and dealt[1] != dealt[2]
    # the pairing is the file's, not the seed's
    assert decks.build_deck(traffic, 31) == deck
    other = dict(traffic, pairing_seed=traffic["pairing_seed"] + 1)
    assert decks.build_deck(other, 31) != deck
    assert sorted(p for p, _ in decks.build_deck(other, 31)) \
        == sorted(p for p, _ in deck)


def test_slotted_arrivals_one_per_slot():
    for seed in (1, 99, 2 ** 31 + 5):
        due = decks.arrivals(0.6, 30, seed)
        for k, t in enumerate(due):
            assert k / 0.6 <= t < (k + 1) / 0.6
    assert decks.arrivals(0.6, 30, 1) != decks.arrivals(0.6, 30, 2)
    assert decks.arrivals(0.6, 30, 1) == decks.arrivals(0.6, 30, 1)


def test_plan_measures_the_whole_deck_once_and_ramps_from_a_copy():
    plans = {seed: open_deck.plan(CHAT, {"rate_rps": 0.6}, seed, 51.0, 32000)
             for seed in (3, 4)}
    decks_seen = []
    for seed, recs in plans.items():
        win = [r for r in recs if r.phase == "window"]
        ramp = [r for r in recs if r.phase == "ramp"]
        assert len(win) == 30 and len(ramp) == 12
        assert all(0.0 <= r.due < 51.0 + 1e-9 for r in win)
        assert all(-20.0 <= r.due < 0.0 for r in ramp)
        assert [r.due for r in recs] == sorted(r.due for r in recs)
        decks_seen.append(collections.Counter(
            (r.prompt_len, r.max_new) for r in win))
        # no request was shortened, and none shares a prefix
        assert all(32 <= r.prompt_len <= 512 and 32 <= r.max_new <= 256
                   for r in win + ramp)
        firsts = {tuple(r.prompt[:4]) for r in recs}
        assert len(firsts) == len(recs)
    assert decks_seen[0] == decks_seen[1]


def test_token_ids_are_seeded_and_in_range():
    a = decks.token_ids(2 ** 31 + 9, 3, 100, 32000)
    assert a.dtype == np.int32 and a.min() >= 3 and a.max() < 32000
    assert (a == decks.token_ids(2 ** 31 + 9, 3, 100, 32000)).all()
    assert (a != decks.token_ids(2 ** 31 + 9, 4, 100, 32000)).any()


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_cell_s_deck_at_its_rate_is_the_same_two_distributions(workload):
    """A cell's ``rate_rps`` may be swept again; its deck stays the
    quantile mid-points of the same two log-uniform distributions, paired
    under the same ``pairing_seed``, only more of them."""
    name, prompts, outputs, pairing = CELLS[workload]
    here = os.path.join(ROOT, "benchmarks")
    traffic = json.load(open(os.path.join(here, "traffic", name + ".json")))
    cell = json.load(open(os.path.join(here, "cells", workload + ".json")))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert traffic["prompt_len"] == {"kind": "loguniform", "lo": prompts[0],
                                     "hi": prompts[1]}
    assert traffic["output_len"] == {"kind": "loguniform", "lo": outputs[0],
                                     "hi": outputs[1]}
    assert traffic["pairing_seed"] == pairing
    assert traffic["drain_s"] == 30.0
    n = int(cell["rate_rps"] * bench["run_seconds"] + 1e-9)
    assert n >= 50             # four times the requests of the old rates
    want_p = decks.quantile_midpoints(traffic["prompt_len"], n)
    want_o = decks.quantile_midpoints(traffic["output_len"], n)
    perm = SplitMix(pairing).permutation(n)
    for seed in (5, 2 ** 31 + 11):
        win = [r for r in open_deck.plan(traffic, cell, seed,
                                         float(bench["run_seconds"]), 20480)
               if r.phase == "window"]
        assert len(win) == n
        assert sorted(r.prompt_len for r in win) == want_p
        assert sorted(r.max_new for r in win) == want_o
        assert collections.Counter((r.prompt_len, r.max_new) for r in win) \
            == collections.Counter((want_p[i], want_o[perm[i]])
                                   for i in range(n))
    # the geometric mean of the ends is the median of either
    assert abs(want_p[n // 2] - math.sqrt(prompts[0] * prompts[1])) \
        < 0.02 * prompts[1]
    assert abs(want_o[n // 2] - math.sqrt(outputs[0] * outputs[1])) \
        < 0.02 * outputs[1]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_cell_s_rate_is_a_stated_share_of_a_knee_it_shows_the_sweep_of(
        workload):
    cell = json.load(open(os.path.join(ROOT, "benchmarks", "cells",
                                       workload + ".json")))
    assert 0.7 <= cell["share_of_knee"] <= 0.85
    assert cell["rate_rps"] == pytest.approx(
        cell["share_of_knee"] * cell["knee_rps"], rel=0.02)
    rows = cell["sweep"]
    assert len(rows) >= 6 and all(r["seconds"] == 40 for r in rows)
    rates = sorted({r["rate_rps"] for r in rows})
    assert cell["knee_rps"] in rates and max(rates) > cell["knee_rps"]

    def sustained(rate):
        """The rule the cells have stated since PR 23, over every window
        the sweep made at ``rate``: the mean TTFT of the second halves not
        above the first halves', and at most one request a window without
        its first token at the close (the one that fell due in the
        window's last moments)."""
        at = [r for r in rows if r["rate_rps"] == rate]
        first = sum(r["ttft_mean_first_half_ms"] for r in at)
        second = sum(r["ttft_mean_second_half_ms"] or float("inf")
                     for r in at)
        return second <= first and sum(
            r["no_first_token_at_close"] for r in at) <= len(at)

    # the knee is the highest rate sustained
    assert sustained(cell["knee_rps"])
    assert not any(sustained(r) for r in rates if r > cell["knee_rps"])
