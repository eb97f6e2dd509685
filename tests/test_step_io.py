"""The mixed step's host interface (serving/programs.StepLayout,
``step_input_layout``, ``step_output_layout``): one packed ``int32``
buffer to the device and one back, a serving step.

* the layout tables tile their buffers and carry every field bit for bit,
  host views to traced unpack and traced pack to host views, for the four
  program variants at two deployment shapes;
* streams served through ``EngineCore`` over the packed interface equal
  the offline oracle token for token: greedy against ``generate()``,
  sampled against the whole sampling chain on the eager model's logits
  under the request's fixed key, plain and speculating;
* a serving step's StepLog record counts ONE array each way (two in with a
  grammar mask), ``h2d_bytes`` is what was put, and no step after the
  first compiles.
"""
import itertools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_infer_tpu as pit
import sampler_oracle
from paddle_infer_tpu.core.tensor import Tensor
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import (GPTConfig, GPTForCausalLM,
                                     GPTMoEForCausalLM, MoEConfig)
from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                      FaultPlane, FaultSpec, RequestState)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.programs import (CAPACITY_COUNTERS,
                                               DROPLESS_COUNTERS,
                                               SAMP_FIELDS, StepLayout,
                                               step_input_layout,
                                               step_output_layout)
from paddle_infer_tpu.serving.structured import default_vocab


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """Streams are compared across differently shaped executables, which
    is bitwise only when all run unsharded."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


# ------------------------------------------------------------------ layout

SHAPES = [(4, 16, 6), (16, 64, 128)]        # max_batch, token_budget, pages
VARIANTS = {                                # spec_window, the output's moe
    "plain": (1, None), "spec": (4, None), "grammar": (1, None),
    "moe_dropless": (1, "dropless"), "moe_capacity": (1, 6),
    "spec_moe_capacity": (3, 6)}


def _random_words(rng, shape, dtype):
    """Random bit patterns of a field's dtype: every float32 and uint32
    word a random 32 bits (NaN payloads and denormals among them), int32
    across its whole range, bool 0 / 1."""
    if np.dtype(dtype) == np.bool_:
        return rng.integers(0, 2, shape).astype(np.bool_)
    words = rng.integers(0, 2 ** 32, shape, dtype=np.uint64).astype(np.uint32)
    return words.view(np.dtype(dtype)).reshape(shape)


def _bits(x):
    x = np.asarray(x)
    return x.astype(np.int32) if x.dtype == np.bool_ else x.view(np.uint32)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_layout_carries_every_field_bit_for_bit(variant, shape):
    b, T, pages = shape
    W, moe = VARIANTS[variant]
    rng = np.random.default_rng(zlib.crc32(repr((variant, shape)).encode()))

    # in: the packer's views on the host, the program's unpack under jit
    lay = step_input_layout(b, T, pages, W)
    names = [r[0] for r in lay.rows]
    assert ("spec" in names) == (W > 1)
    assert "gmask" not in names             # the mask stays its own array
    assert {n for n, _ in SAMP_FIELDS} <= set(names)
    buf = np.zeros((lay.size,), np.int32)
    views = lay.views(buf)
    want = {}
    for name, shp, dtype, _, _ in lay.rows:
        want[name] = _random_words(rng, shp, dtype)
        views[name][...] = want[name]
    got = jax.jit(lay.unpack)(buf)
    for name, shp, dtype, _, _ in lay.rows:
        assert got[name].shape == shp and got[name].dtype == dtype, name
        np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]),
                                      err_msg=name)

    # out: the program's pack under jit, the host's views of the read-back
    out = step_output_layout(b, W, moe)
    onames = [r[0] for r in out.rows]
    assert onames[:2] == ["tok", "fin"] and ("n_emit" in onames) == (W > 1)
    assert out.rows[0][1] == ((b,) if W == 1 else (b, W))
    counters = (DROPLESS_COUNTERS if moe == "dropless"
                else CAPACITY_COUNTERS if moe else ())
    assert tuple(onames[len(onames) - len(counters):]) == counters
    fields = {name: _random_words(rng, shp, dtype)
              for name, shp, dtype, _, _ in out.rows}
    packed = np.asarray(jax.jit(out.pack)(fields))
    assert packed.dtype == np.int32 and packed.shape == (out.size,)
    back = out.views(packed)
    for name in fields:
        np.testing.assert_array_equal(_bits(back[name]), _bits(fields[name]),
                                      err_msg=name)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_layout_tiles_its_buffer_and_follows_the_four_constants(shape):
    b, T, pages = shape
    for lay in (step_input_layout(b, T, pages),
                step_input_layout(b, T, pages, 4),
                step_output_layout(b, 1, "dropless"),
                step_output_layout(b, 4, 6)):
        at = 0
        for _, shp, _, off, n in lay.rows:
            assert off == at and n == int(np.prod(shp, dtype=np.int64))
            at += n
        assert at == lay.size
    plain = step_input_layout(b, T, pages)
    # ids, five per-row words, the table, seven sampling words, the key
    # pair, the scratch page: 4 bytes an element
    assert plain.size == T + 5 * b + b * pages + 7 * b + 2 * b + 1
    assert step_input_layout(b, T, pages, 4).size == plain.size + b
    assert step_input_layout(b, T + 1, pages).size == plain.size + 1


def test_pack_refuses_a_field_that_left_its_row():
    lay = StepLayout([("tok", (4,), "int32"), ("fin", (4,), "bool")])
    ok = dict(tok=jnp.zeros((4,), jnp.int32), fin=jnp.zeros((4,), bool))
    assert lay.pack(ok).shape == (8,)
    with pytest.raises(ValueError, match="tok"):
        lay.pack(dict(ok, tok=jnp.zeros((4, 1), jnp.int32)))
    with pytest.raises(ValueError, match="fin"):
        lay.pack(dict(ok, fin=jnp.zeros((4,), jnp.int32)))


# ------------------------------------------------------------------ engine

DIMS = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0)
CORE_SHAPE = dict(max_batch=3, max_model_len=48, token_budget=16,
                  prefill_chunk=16, enable_prefix_cache=True,
                  prefix_cache_headroom_pages=12)
SPEC = dict(speculate=True, num_draft_tokens=3)


@pytest.fixture(scope="module")
def model():
    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(**DIMS))
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    return PagedGenerationEngine(model, page_size=8)


@pytest.fixture(scope="module")
def ref(model):
    """A separate engine for ``generate()``: a direct call on the
    core-owned one would corrupt its slot reservations."""
    return PagedGenerationEngine(model, page_size=8)


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _drive(core, reqs, max_iters=400):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _serve(engine, prompts, cfgs, rid_base, **kw):
    """Serve the prompts together through a fresh core; the finished
    requests and the core's serving-step records."""
    request_mod._rid_counter = itertools.count(rid_base)
    core = EngineCore(engine, **CORE_SHAPE, **kw)
    try:
        reqs = [core.submit(p, g)[0] for p, g in zip(prompts, cfgs)]
        _drive(core, reqs)
        assert all(r.state is RequestState.DONE for r in reqs)
        steps = [r for r in core.steplog.records()
                 if r["kind"] in ("prefill", "decode", "mixed")]
        return reqs, steps
    finally:
        core.close()


@pytest.mark.parametrize("kw", [{}, SPEC], ids=["plain", "speculating"])
def test_greedy_streams_equal_generate(engine, ref, kw):
    prompts = [_prompt(71, 9), _prompt(72, 21), _prompt(73, 5)]
    cfgs = [GenerationConfig(max_new_tokens=9),
            GenerationConfig(max_new_tokens=6, min_length=3, eos_token_id=5,
                             pad_token_id=0),
            GenerationConfig(max_new_tokens=11)]
    for rid_base in (8100, 8200):
        reqs, steps = _serve(engine, prompts, cfgs, rid_base, **kw)
        for ids, g, req in zip(prompts, cfgs, reqs):
            np.testing.assert_array_equal(req.padded_result(),
                                          ref.generate(ids[None], g)[0])
        assert all(r["h2d_arrays"] == r["d2h_arrays"] == 1 for r in steps)
        # the comparison is of the W > 1 program's verify rows too
        assert (sum(r["draft_tokens"] for r in steps) > 0) == bool(kw)


def _sampled_oracle(model, ids, g, rid):
    """The stream the whole sampling chain, run for the one row alone as
    every step ran it before PR 39 (tests/sampler_oracle.py), draws from
    the EAGER model's logits under the request's key:
    ``fold_in(PRNGKey(seed), rid)``, then the generation step folded in,
    a token at a time."""
    key = jax.random.fold_in(jax.random.PRNGKey(g.seed), rid)[None]
    samp = {"temperature": jnp.asarray([g.temperature], jnp.float32),
            "top_k": jnp.asarray([g.top_k or 0], jnp.int32),
            "top_p": jnp.asarray([g.top_p], jnp.float32),
            "min_len": jnp.asarray([g.min_length], jnp.int32),
            "eos": jnp.asarray([-1 if g.eos_token_id is None
                                else g.eos_token_id], jnp.int32),
            "do_sample": jnp.asarray([True]),
            "pad": jnp.asarray([g.pad_token_id], jnp.int32)}
    seq, out = list(ids), []
    for step in range(g.max_new_tokens):
        logits = model(Tensor(jnp.asarray(seq, jnp.int32)[None]))._data[:, -1]
        steps = jnp.asarray([step], jnp.int32)
        proc = sampler_oracle.process_rows(logits, samp, steps)
        tok = int(sampler_oracle.pick_rows(proc, samp, steps, key)[0])
        out.append(tok)
        seq.append(tok)
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("kw", [{}, SPEC], ids=["plain", "speculating"])
def test_sampled_streams_equal_the_oracle_under_a_fixed_key(engine, model,
                                                            kw):
    """Temperature and ``top_p`` as ``float32`` bit patterns, ``top_k``,
    the ``uint32`` key pair and the step index all reach the sampler as
    the packer wrote them.  A speculating core that drafts from a cold
    radix tree alone proposes nothing, so its rows are the plain rows of
    the ``W > 1`` program (a verified sampled row is distributed as the
    plain one, not bitwise it)."""
    prompts = [_prompt(81, 7), _prompt(82, 13)]
    cfgs = [GenerationConfig(max_new_tokens=8, do_sample=True,
                             temperature=0.7, top_k=12, top_p=0.9, seed=5),
            GenerationConfig(max_new_tokens=6, do_sample=True,
                             temperature=1.3, seed=11)]
    if kw:
        kw = dict(kw, draft_source="prefix_cache")
    reqs, steps = _serve(engine, prompts, cfgs, 8300, **kw)
    assert sum(r["draft_tokens"] for r in steps) == 0
    for ids, g, req in zip(prompts, cfgs, reqs):
        np.testing.assert_array_equal(
            req.result(), _sampled_oracle(model, ids, g, req.rid))


def test_steplog_counts_the_rows_that_draw_and_filter(engine):
    """Two greedy requests and one sampled through ``top_p`` 0.9 whose
    prompt takes four steps: ``draw_rows`` / ``filter_rows`` read 0 / 0
    until the step that samples its first token, 1 / 1 on that step and
    its decode steps, 0 / 0 again when only the greedy rows are left."""
    prompts = [_prompt(91, 6), _prompt(92, 9), _prompt(93, 30)]
    cfgs = [GenerationConfig(max_new_tokens=12),
            GenerationConfig(max_new_tokens=12),
            GenerationConfig(max_new_tokens=5, do_sample=True, top_p=0.9,
                             seed=3)]
    reqs, steps = _serve(engine, prompts, cfgs, 8500)
    counts = [(r["draw_rows"], r["filter_rows"]) for r in steps]
    first = counts.index((1, 1))
    n = len(reqs[2].result())
    assert first >= 3 and n == 5
    assert counts == ([(0, 0)] * first + [(1, 1)] * n
                      + [(0, 0)] * (len(counts) - first - n))
    assert len(counts) > first + n


def test_a_failed_step_records_what_its_tail_was_asked_for(engine):
    """The record of a step that raised in its launch carries the two
    counts too: here a sampled row drawing through ``top_k`` beside a
    sampled row with no filter."""
    request_mod._rid_counter = itertools.count(8600)
    plane = FaultPlane([FaultSpec("decode.step", at=3)])
    core = EngineCore(engine, fault_plane=plane, **CORE_SHAPE)
    sup = EngineSupervisor(core)
    try:
        reqs = [core.submit(_prompt(94, 5), GenerationConfig(
                    max_new_tokens=6, do_sample=True, top_k=8, seed=1))[0],
                core.submit(_prompt(95, 7), GenerationConfig(
                    max_new_tokens=6, do_sample=True, temperature=1.2,
                    seed=2))[0]]
        for _ in range(400):
            if all(r.done for r in reqs):
                break
            sup.run_once()
        assert all(r.state is RequestState.DONE for r in reqs)
        (failed,) = [r for r in core.steplog.records() if r["failed"]]
        assert (failed["draw_rows"], failed["filter_rows"]) == (2, 1)
    finally:
        sup.close()


def _watch_puts(engine, monkeypatch):
    """The bytes of the host arrays each step launch was handed."""
    seen = []
    real = engine.run_paged_program

    def spy(key, builder, *args):
        if key[0] == "serve-step":
            assert all(isinstance(a, np.ndarray) for a in args)
            seen.append([a.nbytes for a in args])
        return real(key, builder, *args)

    monkeypatch.setattr(engine, "run_paged_program", spy)
    return seen


@pytest.mark.parametrize("variant", ["plain", "speculating", "grammar",
                                     "moe_capacity"])
def test_a_step_is_one_array_each_way_and_compiles_once(model, monkeypatch,
                                                        variant):
    from paddle_infer_tpu.observability import get_compile_log

    kw, n_in = {}, 1
    if variant == "moe_capacity":
        pit.seed(0)
        model = GPTMoEForCausalLM(MoEConfig(num_experts=4, **DIMS))
        model.eval()
    elif variant == "grammar":
        kw, n_in = dict(grammar_vocab=default_vocab(96)), 2
    elif variant == "speculating":
        kw = dict(SPEC)
    engine = PagedGenerationEngine(model, page_size=8)
    puts = _watch_puts(engine, monkeypatch)
    core = EngineCore(engine, **CORE_SHAPE, **kw)
    try:
        clog = get_compile_log()
        (first,) = core.submit(_prompt(91, 19),
                               GenerationConfig(max_new_tokens=4))
        core.run_once()                 # the warm-up step: it may compile
        compiles0 = clog.count("serving-decode")
        grammar = ({"type": "regex", "pattern": "[ab]{2,6}"}
                   if variant == "grammar" else None)
        more = [core.submit(_prompt(92, 6), GenerationConfig(
                    max_new_tokens=7, do_sample=True, temperature=0.8,
                    seed=3))[0],
                core.submit(_prompt(91, 19), GenerationConfig(
                    max_new_tokens=5), grammar=grammar)[0]]
        _drive(core, [first] + more)
        steps = [r for r in core.steplog.records()
                 if r["kind"] in ("prefill", "decode", "mixed")]
        assert len(steps) == len(puts) >= 6
        size = 4 * core._step_in.size
        for r, put in zip(steps, puts):
            assert (r["h2d_arrays"], r["d2h_arrays"]) == (n_in, 1)
            assert len(put) == n_in and put[0] == size
            assert r["h2d_bytes"] == sum(put)
        assert all(r["compile_events"] == 0 for r in steps[1:])
        assert clog.count("serving-decode") == compiles0
        if variant == "moe_capacity":
            # the counters rode out in the same array
            assert all(r["moe_tokens_routed"] > 0 for r in steps)
            assert "moe_routed" in [row[0] for row in core._step_out.rows]
        # records that launch no step carry neither count
        others = [r for r in core.steplog.records()
                  if r["kind"] not in ("prefill", "decode", "mixed")]
        assert others and all(r["h2d_arrays"] == r["d2h_arrays"] == 0
                              for r in others)
    finally:
        core.close()


def test_d2h_arrays_counts_the_arrays_read_back(engine, monkeypatch):
    """``d2h_arrays`` is a count of what the host read, not a constant: a
    program that hands a second host-bound array back reads 2."""
    real = engine.run_paged_program

    def two_outputs(key, builder, *args):
        outs = list(real(key, builder, *args))
        return outs + [outs[0][:1]] if key[0] == "serve-step" else outs

    monkeypatch.setattr(engine, "run_paged_program", two_outputs)
    g = GenerationConfig(max_new_tokens=4)
    reqs, steps = _serve(engine, [_prompt(95, 7)], [g], 8500)
    assert steps and all(r["d2h_arrays"] == 2 for r in steps)
    assert all(r["h2d_arrays"] == 1 for r in steps)
