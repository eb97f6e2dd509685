"""The serving step's sampling tail (serving/programs.py ``sampling_rows``,
``_process_rows``, ``_pick_rows``) does what its rows ask for: an argmax
for a step of greedy rows, one sort for a step in which a row draws
through a filter, a draw only where a row draws.

* against the chain as it stood before (tests/sampler_oracle.py: two
  sorts and a draw for every row of every step) the tokens are the same
  on every row of every batch, and a filtering row's processed logits the
  same bit for bit, ties at the k-th entry and at the nucleus's edge
  included; plain and under the ``vmap`` of the ``W > 1`` program;
* what a row gets follows from its own fields: a greedy or idle row is
  never filtered, and ``top_p`` 1.0 is off (the old chain cut a tail
  there, where its cumulative sum had rounded to 1.0);
* the traced tail holds one sort and its random bits inside
  conditionals, which stay conditionals under the ``vmap``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sampler_oracle
from paddle_infer_tpu.inference import sampling
from paddle_infer_tpu.serving import programs

V = 512
EOS = 3

#        name              draw  temp  k   p    min  step  now
ROWS = [("greedy",          0,   1.0,  0, 1.0,   0,   0,   1),
        ("greedy_cold_ban", 0,   0.3,  0, 1.0,   4,   1,   1),
        ("sampled_plain",   1,   1.0,  0, 1.0,   0,   5,   1),
        ("sampled_hot",     1,   1.7,  0, 1.0,   0,   2,   1),
        ("top_k",           1,   1.0,  5, 1.0,   0,   9,   1),
        ("top_p",           1,   1.0,  0, 0.9,   0,   4,   1),
        ("both_cold",       1,   0.3, 12, 0.8,   0,   7,   1),
        ("ban_top_p",       1,   1.0,  0, 0.95,  6,   2,   1),
        ("idle_stale",      1,   1.0,  7, 0.5,   0,   0,   0),
        ("masked_top_k",    1,   0.3, 50, 1.0,   0,   3,   1)]
NAMES = [r[0] for r in ROWS]
B = len(ROWS)


def _samp(batch):
    """The rows' sampling fields for one kind of batch, with
    ``sample_now`` and the step indices."""
    col = lambda i, dt: jnp.asarray([r[i] for r in ROWS], dt)
    samp = {"temperature": col(2, jnp.float32), "top_k": col(3, jnp.int32),
            "top_p": col(4, jnp.float32), "min_len": col(5, jnp.int32),
            "eos": jnp.full((B,), EOS, jnp.int32),
            "do_sample": col(1, jnp.bool_),
            "pad": jnp.full((B,), V - 1, jnp.int32)}
    if batch == "all_greedy":
        samp["do_sample"] = jnp.zeros((B,), jnp.bool_)
    elif batch in ("all_sampled", "plain_draws"):
        samp["do_sample"] = jnp.ones((B,), jnp.bool_)
    if batch == "plain_draws":      # rows that draw, none through a filter
        samp["top_k"] = jnp.zeros((B,), jnp.int32)
        samp["top_p"] = jnp.ones((B,), jnp.float32)
    return samp, col(7, jnp.bool_), col(6, jnp.int32)


def _logits(seed, lanes):
    """``[B, lanes, V]`` logits on a grid of eighths, so equal values are
    everywhere; a tie of three planted across the ``top_k`` row's k-th
    entry, the eos column the largest of the rows that ban it, and the
    last row as a grammar mask leaves it: three tokens allowed, the rest
    ``NEG_INF`` lower (under its temperature, below ``NEG_INF``)."""
    rng = np.random.default_rng(seed)
    x = np.round(rng.normal(0.0, 2.0, (B, lanes, V)) * 8) / 8
    for lane in range(lanes):
        row = x[NAMES.index("top_k"), lane]
        order = np.argsort(-row, kind="stable")
        row[order[4:7]] = row[order[4]]
        for name in ("greedy_cold_ban", "ban_top_p"):
            x[NAMES.index(name), lane, EOS] = 9.0
        x[NAMES.index("masked_top_k"), lane, 3:] += sampling.NEG_INF
    return jnp.asarray(x, jnp.float32)


def _keys(seed):
    return jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 i))(jnp.arange(B))


def _tail(logits, samp, sample_now, steps, keys):
    draws, filters = programs.sampling_rows(samp, sample_now)
    proc = programs._process_rows(logits, samp, steps, filters)
    tok = programs._pick_rows(proc, samp, steps, keys, draws)
    return proc, jnp.where(sample_now, tok, samp["pad"])


def _old_tail(logits, samp, sample_now, steps, keys):
    proc = sampler_oracle.process_rows(logits, samp, steps)
    tok = sampler_oracle.pick_rows(proc, samp, steps, keys)
    return proc, jnp.where(sample_now, tok, samp["pad"])


def _window(tail):
    """``tail`` over the lanes of a ``[B, W, V]`` window, as the
    speculating program runs it: the rows' fields shared, a step index a
    lane."""
    def run(logits_w, samp, sample_now, steps0, keys):
        steps_w = steps0[:, None] + jnp.arange(logits_w.shape[1])[None]
        return jax.vmap(lambda lg, st: tail(lg, samp, sample_now, st, keys),
                        in_axes=(1, 1), out_axes=1)(logits_w, steps_w)
    return run


def _base(logits, samp, steps):
    """The part of the chain every row gets: the ban and the temperature."""
    ban = (steps < samp["min_len"])[:, None] & (jnp.arange(V) == EOS)[None]
    return (jnp.where(ban, sampling.NEG_INF, logits)
            / samp["temperature"][:, None])


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.mark.parametrize("lanes", [1, 3], ids=["plain", "window"])
@pytest.mark.parametrize("batch", ["all_greedy", "all_sampled", "mixed",
                                   "plain_draws"])
def test_tail_equals_the_two_sort_chain(batch, lanes):
    samp, sample_now, steps0 = _samp(batch)
    logits, keys = _logits(39 + lanes, lanes), _keys(7)
    if lanes == 1:
        args = (logits[:, 0], samp, sample_now, steps0, keys)
        new, old = jax.jit(_tail)(*args), jax.jit(_old_tail)(*args)
        steps_w = steps0[:, None]
    else:
        args = (logits, samp, sample_now, steps0, keys)
        new = jax.jit(_window(_tail))(*args)
        old = jax.jit(_window(_old_tail))(*args)
        steps_w = steps0[:, None] + jnp.arange(lanes)[None]
    new_proc, old_proc = (np.asarray(p[0]).reshape(B, lanes, V)
                          for p in (new, old))
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(old[1]))
    # the eos ban held on the rows it applies to
    toks = np.asarray(new[1]).reshape(B, lanes)
    for name in ("greedy_cold_ban", "ban_top_p"):
        i = NAMES.index(name)
        banned = np.asarray(steps_w[i] < samp["min_len"][i])
        assert banned.any() and (toks[i][banned] != EOS).all(), name

    draws, filters = (np.asarray(m)
                      for m in programs.sampling_rows(samp, sample_now))
    assert draws.sum() == {"all_greedy": 0, "mixed": 7}.get(batch, B - 1)
    assert filters.sum() == {"all_greedy": 0,
                             "plain_draws": 0}.get(batch, 5)
    for i, name in enumerate(NAMES):
        for lane in range(lanes):
            got, want = new_proc[i, lane], old_proc[i, lane]
            base = np.asarray(
                _base(logits[:, lane], samp, steps_w[:, lane]))[i]
            if not filters[i]:
                # asked for no filter, or draws nothing: never filtered
                np.testing.assert_array_equal(_bits(got), _bits(base), name)
            if float(samp["top_p"][i]) < 1.0 and filters[i]:
                np.testing.assert_array_equal(_bits(got), _bits(want), name)
            elif draws[i]:
                # ``top_p`` 1.0: the old chain's nucleus may have cut a
                # rounding tail more; nothing else may differ
                more = _bits(got) != _bits(want)
                assert (want[more] == np.float32(sampling.NEG_INF)).all()
                assert np.asarray(jax.nn.softmax(got))[more].sum() < 1e-6


def test_the_deck_plants_the_ties_it_says():
    """The ``top_k`` row's chain keeps seven entries for k = 5 (three tied
    at the k-th), the nucleus rows' cuts fall on values held more than
    once, and the masked row's allowed tokens number fewer than its k."""
    samp, sample_now, steps0 = _samp("mixed")
    logits = _logits(40, 1)[:, 0]
    _, filters = programs.sampling_rows(samp, sample_now)
    proc = np.asarray(programs._process_rows(logits, samp, steps0, filters))
    kept = proc > sampling.NEG_INF / 2
    assert kept[NAMES.index("top_k")].sum() == 7
    assert kept[NAMES.index("masked_top_k")].sum() == 3
    base = np.asarray(_base(logits, samp, steps0))
    for name in ("top_p", "ban_top_p"):
        i = NAMES.index(name)
        edge = proc[i][kept[i]].min()
        assert 1 < kept[i].sum() < V and (base[i] == edge).sum() > 1, name


def test_top_p_one_is_off_where_the_old_chain_cut_a_rounding_tail():
    """At a real vocabulary the cumulative sum reaches 1.0 before a row's
    end, so the old chain's nucleus at ``top_p`` 1.0 set a tail of some
    1e-7 of the mass to ``NEG_INF``.  A row that asks for no nucleus now
    gets none, beside filtering neighbours or not: what it is handed
    follows from its own fields, as ``sampling.process_logits`` has it
    offline."""
    vocab, b = 131072, 4
    logits = jnp.asarray(np.random.default_rng(0).normal(0, 1.2, (b, vocab)),
                         jnp.float32)
    samp = {"temperature": jnp.full((b,), 0.3, jnp.float32),
            "top_k": jnp.zeros((b,), jnp.int32),
            "top_p": jnp.asarray([1.0, 1.0, 1.0, 0.9], jnp.float32),
            "min_len": jnp.zeros((b,), jnp.int32),
            "eos": jnp.full((b,), -1, jnp.int32),
            "do_sample": jnp.ones((b,), jnp.bool_),
            "pad": jnp.zeros((b,), jnp.int32)}
    steps = jnp.zeros((b,), jnp.int32)
    base = logits / samp["temperature"][:, None]
    old = np.asarray(jax.jit(sampler_oracle.process_rows)(logits, samp, steps))
    cut = (old != np.asarray(base))[:3]
    assert cut.any(), "the old chain passed top_p 1.0 through whole here"
    assert (np.asarray(jax.nn.softmax(base))[:3] * cut).sum(-1).max() < 1e-6
    for neighbour_filters in (True, False):
        now = jnp.asarray([True, True, True, neighbour_filters])
        _, filters = programs.sampling_rows(samp, now)
        assert bool(filters[3]) == neighbour_filters and not filters[:3].any()
        new = np.asarray(jax.jit(programs._process_rows)(logits, samp, steps,
                                                         filters))
        np.testing.assert_array_equal(_bits(new[:3]), _bits(base[:3]))
        if neighbour_filters:
            np.testing.assert_array_equal(_bits(new[3]), _bits(old[3]))


def _primitives(jaxpr, under_cond=False, out=None):
    """``{(primitive name, inside a conditional's branch)}`` over a jaxpr
    and everything it calls."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add((eqn.primitive.name, under_cond))
        inner = under_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, inner, out)
    return out


@pytest.mark.parametrize("lanes", [1, 3], ids=["plain", "window"])
def test_the_sort_and_the_draw_sit_inside_conditionals(lanes):
    """Traced, the tail holds its sort, its cumulative sum and its random
    bits in conditionals' branches and nowhere else; under the ``W > 1``
    program's ``vmap`` they are still conditionals (a batched predicate
    would have made them selects over both sides)."""
    samp, sample_now, steps0 = _samp("mixed")
    logits, keys = _logits(41, lanes), _keys(7)
    if lanes == 1:
        jaxpr = jax.make_jaxpr(_tail)(logits[:, 0], samp, sample_now, steps0,
                                      keys)
    else:
        jaxpr = jax.make_jaxpr(_window(_tail))(logits, samp, sample_now,
                                               steps0, keys)
    prims = _primitives(jaxpr.jaxpr)
    assert ("cond", False) in prims
    for name in ("sort", "cumsum", "random_bits"):
        assert (name, True) in prims and (name, False) not in prims, name
    text = str(jaxpr)
    assert text.count(" sort[") == 1 and text.count("cond[") == 2


def test_the_packer_counts_by_the_traced_rule():
    """``sampling_rows`` on the host's views (bools as 0 / 1 words) gives
    what it gives the traced program."""
    samp, sample_now, _ = _samp("mixed")
    lay = programs.step_input_layout(B, 16, 2)
    fields = lay.views(np.zeros((lay.size,), np.int32))
    for name, _ in programs.SAMP_FIELDS:
        fields[name][:] = np.asarray(samp[name])
    fields["sample_now"][:] = np.asarray(sample_now)
    host = programs.sampling_rows(fields, fields["sample_now"])
    traced = programs.sampling_rows(samp, sample_now)
    for h, t in zip(host, traced):
        np.testing.assert_array_equal(h.astype(bool), np.asarray(t))
    assert [int(np.count_nonzero(h)) for h in host] == [7, 5]
