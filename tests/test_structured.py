"""Constrained decoding (serving/structured/): grammar/JSON-schema
guided generation as a data-only logit mask.

The acceptance surface, per docs/SERVING.md "Constrained decoding":

  * the host-side compiler lowers regex / JSON-schema / JSON-mode
    specs to token-level FSMs over the deployment vocabulary, rejects
    malformed and unsatisfiable grammars at admission, and caches one
    CompiledGrammar per digest;
  * every emitted token of a constrained row is grammar-legal
    (``violations == 0``), the finished text conforms to its spec, and
    EOS is only reachable in accepting states — a row that exhausts
    ``max_new_tokens`` mid-grammar FAILS with GrammarIncompleteError;
  * the mask is per-row DATA through the ONE mixed-step executable:
    constrained greedy under speculation (each lane masked by its own
    advanced FSM state) is BITWISE the non-speculative stream, FSM
    state rides fleet handoff and park/resume packets verbatim, and 32
    distinct grammars churn through a warm core with zero post-warmup
    decode compiles.

Request ids feed the per-row sampling RNG (``fold_in(key, rid)``), so
parity runs pin the process-wide rid counter — the same idiom as
tests/test_kv_tier.py and tests/test_fleet.py.  Sampled speculative
runs are compared against the same-config uninterrupted run (the
repo-wide convention, see test_kv_tier's speculative park parity):
plain-vs-spec is bitwise for greedy rows by the accept rule; sampled
rows get the distributional guarantee plus the never-violates
invariant checked here.
"""
import itertools
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.observability.compilelog import get_compile_log
from paddle_infer_tpu.serving import (EngineCore, GrammarCache,
                                      GrammarError,
                                      GrammarIncompleteError,
                                      ReplicaHandle, ReplicaRole,
                                      RequestState,
                                      conforms, decode_text,
                                      default_vocab, grammar_digest)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.fleet import migrate, ready_for_handoff
from paddle_infer_tpu.serving.structured import runtime as grammar_rt
from paddle_infer_tpu.serving.structured.fsm import compile_grammar
from paddle_infer_tpu.serving.structured.grammar import (MAX_SCHEMA_BYTES,
                                                         validate_spec)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = default_vocab(96)

SCHEMA = {"type": "json_schema",
          "schema": {"type": "object",
                     "properties": {"tool": {"enum": ["calc", "go"]},
                                    "n": {"type": "integer"}}}}
REGEX = {"type": "regex", "pattern": "(yes|no|maybe)!"}
JSONG = {"type": "json", "max_depth": 1}


def _tid(c):
    """default_vocab maps token id i -> chr(32 + i)."""
    return ord(c) - 32


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """Parity compares tokens across executables — bitwise only when
    every run is unsharded."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
    get_compile_log().reset()
    yield
    get_compile_log().reset()


@pytest.fixture(scope="module")
def model():
    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    return PagedGenerationEngine(model, page_size=8)


# replicas never share an engine (pools are per-engine), so the fleet
# tests draw from a module-scoped pool — executables compile once
@pytest.fixture(scope="module")
def engines(model):
    return [PagedGenerationEngine(model, page_size=8) for _ in range(3)]


CORE_KW = dict(max_batch=2, max_model_len=64)
# handoff needs chunked prefill so a 24-token prompt crosses a
# boundary while still streaming — same shape as tests/test_fleet.py
FLEET_KW = dict(max_batch=2, max_model_len=64,
                token_budget=16, prefill_chunk=16)


def _drive(core, reqs, max_iters=600):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _run_jobs(engine_obj, jobs, rid_base, core_kw=None, park_at=()):
    """Drive ``jobs`` (``(prompt, gen, grammar)``) on a fresh
    grammar-enabled core; returns (requests, snapshot)."""
    request_mod._rid_counter = itertools.count(rid_base)
    kw = dict(CORE_KW, grammar_vocab=VOCAB)
    kw.update(core_kw or {})
    core = EngineCore(engine_obj, **kw)
    parked = []
    try:
        reqs = [core.submit(p, g, grammar=spec)[0]
                for p, g, spec in jobs]
        for step in range(1, 600 + 1):
            if all(r.done for r in reqs):
                break
            core.run_once()
            if step in park_at:
                parked.append(core.park_for_pressure())
        assert all(r.done for r in reqs), "requests did not finish"
        snap = core.metrics_snapshot()["structured"]
        return reqs, snap
    finally:
        core.close()


# ----------------------------------------------------------- FSM units


class TestFSM:
    def test_regex_walk_accept_complete(self):
        g = compile_grammar({"type": "regex", "pattern": "(yes|no)!"},
                            VOCAB)
        s = g.start
        for c in "yes!":
            s, ok = g.advance(s, _tid(c))
            assert ok
        assert g.accepting(s) and g.complete(s)
        # a complete state allows nothing more: advance clamps
        s2, ok = g.advance(s, _tid("x"))
        assert not ok and s2 == s

    def test_bounded_repetition(self):
        g = compile_grammar({"type": "regex", "pattern": "a{2,4}"},
                            VOCAB)
        s, seen = g.start, []
        for _ in range(4):
            s, ok = g.advance(s, _tid("a"))
            assert ok
            seen.append(g.accepting(s))
        assert seen == [False, True, True, True]
        _, ok = g.advance(s, _tid("a"))      # fifth 'a' is illegal
        assert not ok

    def test_classes_escapes_and_bare_brace(self):
        g = compile_grammar({"type": "regex", "pattern": r"[A-C]\d"},
                            VOCAB)
        s, ok = g.advance(g.start, _tid("B"))
        assert ok
        s, ok = g.advance(s, _tid("7"))
        assert ok and g.accepting(s)
        _, ok = g.advance(g.start, _tid("D"))
        assert not ok
        # '{' with no parsable bounds is a literal, like re
        g2 = compile_grammar({"type": "regex", "pattern": "a{b"}, VOCAB)
        s = g2.start
        for c in "a{b":
            s, ok = g2.advance(s, _tid(c))
            assert ok
        assert g2.accepting(s)

    def test_parser_rejects_malformed(self):
        for bad in ["(", "a{5,2}", "a{100}", "[z-a]"]:
            with pytest.raises(GrammarError):
                compile_grammar({"type": "regex", "pattern": bad}, VOCAB)

    def test_unsatisfiable_and_empty_only_rejected(self):
        # '\t' is outside the printable serving alphabet: no token can
        # ever advance the FSM, so admission must refuse it
        with pytest.raises(GrammarError, match="unsatisfiable"):
            compile_grammar({"type": "regex", "pattern": "\t"}, VOCAB)
        # a grammar matching ONLY the empty string would ban every
        # token at step one
        with pytest.raises(GrammarError, match="empty string"):
            compile_grammar({"type": "regex", "pattern": "z{0,0}"},
                            VOCAB)

    def test_multichar_tokens_lifted(self):
        """Token-level lifting folds multi-char tokens through the char
        DFA — and permanently bans empty-string tokens."""
        mv = ["", "a", "b", "ab", "!", "zz"]
        g = compile_grammar({"type": "regex", "pattern": "(ab)+!"}, mv)
        m0 = np.asarray(grammar_rt.mask_row(g, g.start))
        assert [mv[i] for i in np.flatnonzero(m0 == 0.0)] == ["a", "ab"]
        s, ok = g.advance(g.start, 3)        # consume "ab" in one token
        assert ok
        m1 = np.asarray(grammar_rt.mask_row(g, s))
        assert [mv[i] for i in np.flatnonzero(m1 == 0.0)] == [
            "a", "ab", "!"]

    def test_mask_row_eos_gating(self):
        """EOS is legal exactly in accepting states."""
        g = compile_grammar({"type": "regex", "pattern": "ab"}, VOCAB)
        eos = 5
        assert np.asarray(grammar_rt.mask_row(g, g.start, eos))[eos] != 0
        s = g.start
        for c in "ab":
            s, _ = g.advance(s, _tid(c))
        m = np.asarray(grammar_rt.mask_row(g, s, eos))
        assert m[eos] == 0.0
        # the complete state allows ONLY eos
        assert grammar_rt.masked_count(g, s, eos) == len(VOCAB) - 1

    def test_advance_many_counts_violations(self):
        g = compile_grammar({"type": "regex", "pattern": "abc!"}, VOCAB)
        _, viol = grammar_rt.advance_many(
            g, g.start, [_tid("a"), _tid("b"), _tid("c"), _tid("!")])
        assert viol == 0
        _, viol = grammar_rt.advance_many(
            g, g.start, [_tid("a"), _tid("z"), _tid("b")])
        assert viol >= 1

    def test_filter_drafts_truncates_at_first_illegal(self):
        g = compile_grammar({"type": "regex", "pattern": "abc!"}, VOCAB)
        drafts = [_tid("a"), _tid("b"), _tid("z")]
        assert list(grammar_rt.filter_drafts(g, g.start, drafts)) == [
            _tid("a"), _tid("b")]

    def test_lane_states_and_masks(self):
        """Speculative lane j is masked by the state reached through
        drafts 0..j-1 — the per-lane walk the engine ships as data."""
        g = compile_grammar({"type": "regex", "pattern": "abc!"}, VOCAB)
        drafts = [_tid("a"), _tid("b")]
        lanes = list(grammar_rt.lane_states(g, g.start, drafts, 3))
        want, s = [g.start], g.start
        for d in drafts:
            s, ok = g.advance(s, d)
            assert ok
            want.append(s)
        assert lanes == want
        masks = np.asarray(grammar_rt.lane_masks(g, g.start, drafts, 3))
        assert masks.shape == (3, len(VOCAB))
        for j, st in enumerate(want):
            np.testing.assert_array_equal(
                masks[j], np.asarray(grammar_rt.mask_row(g, st)))


# ----------------------------------------------------- spec validation


class TestSpecValidation:
    @pytest.mark.parametrize("bad", [
        "not-a-dict",
        {"type": "ebnf", "pattern": "a"},
        {"type": "regex"},
        {"type": "regex", "pattern": ""},
        {"type": "json_schema"},
        {"type": "json_schema", "schema": []},
        {"type": "json", "max_depth": 99},
    ])
    def test_malformed_specs_rejected(self, bad):
        with pytest.raises(GrammarError):
            validate_spec(bad)

    def test_oversized_spec_rejected(self):
        with pytest.raises(GrammarError, match="canonical bytes"):
            validate_spec({"type": "regex",
                           "pattern": "a" * (MAX_SCHEMA_BYTES + 1)})

    @pytest.mark.parametrize("schema", [
        {"type": "object",
         "properties": {f"k{i}": {"type": "integer"}
                        for i in range(17)}},          # > MAX_OBJECT_PROPS
        {"type": "string", "maxLength": 65},           # > MAX_STRING_LEN
        {"enum": [f"v{i}" for i in range(33)]},        # > MAX_ENUM_VALS
    ])
    def test_schema_bounds_enforced(self, schema):
        with pytest.raises(GrammarError):
            validate_spec({"type": "json_schema", "schema": schema})

    def test_digest_canonical_under_key_order(self):
        a = validate_spec(SCHEMA)
        b = validate_spec({"schema": SCHEMA["schema"],
                           "type": "json_schema"})
        assert grammar_digest(a) == grammar_digest(b)


# -------------------------------------------------------- compile cache


class TestGrammarCache:
    def test_hit_shares_one_fsm_object(self):
        c = GrammarCache(VOCAB)
        a = c.get_or_compile(REGEX)
        b = c.get_or_compile(dict(REGEX))    # equal spec, new dict
        assert a is b
        s = c.summary()
        assert s["misses"] == 1 and s["hits"] == 1 and s["entries"] == 1
        assert s["vocab_size"] == len(VOCAB)
        assert s["compile_seconds"] > 0.0

    def test_lru_eviction_bounded(self):
        c = GrammarCache(VOCAB, max_entries=4)
        for i in range(6):
            c.get_or_compile({"type": "regex", "pattern": f"q{i}"})
        assert c.summary()["entries"] == 4
        # the two oldest were evicted: touching them compiles again
        c.get_or_compile({"type": "regex", "pattern": "q0"})
        assert c.summary()["misses"] == 7

    def test_malformed_spec_never_cached(self):
        c = GrammarCache(VOCAB)
        with pytest.raises(GrammarError):
            c.get_or_compile({"type": "regex", "pattern": "("})
        assert c.summary()["entries"] == 0


# ---------------------------------------------------- admission gating


class TestAdmission:
    def test_grammar_without_grammar_vocab_rejected(self, engine):
        core = EngineCore(engine, **CORE_KW)
        try:
            with pytest.raises(GrammarError, match="serves no grammars"):
                core.submit(_prompt(1), GenerationConfig(max_new_tokens=4),
                            grammar=REGEX)
            assert core.metrics_snapshot().get("structured") is None
            assert core.active_count == 0 and core.queue_depth == 0
        finally:
            core.close()

    def test_grammar_vocab_size_must_match_model(self, engine):
        with pytest.raises(ValueError, match="vocab"):
            EngineCore(engine, grammar_vocab=default_vocab(97),
                       **CORE_KW)

    def test_bad_grammars_rejected_before_any_reservation(self, engine):
        core = EngineCore(engine, grammar_vocab=VOCAB, **CORE_KW)
        try:
            for bad in ({"type": "ebnf", "g": "x"},
                        {"type": "regex", "pattern": "\t"},
                        {"type": "regex", "pattern": "("}):
                with pytest.raises(GrammarError):
                    core.submit(_prompt(1),
                                GenerationConfig(max_new_tokens=4),
                                grammar=bad)
            snap = core.metrics_snapshot()["structured"]
            assert snap["rejected"] == 3
            assert core.active_count == 0 and core.queue_depth == 0
            assert snap["entries"] == 0
        finally:
            core.close()

    def test_min_length_conflicts_with_grammar(self, engine):
        core = EngineCore(engine, grammar_vocab=VOCAB, **CORE_KW)
        try:
            with pytest.raises(GrammarError, match="min_length"):
                core.submit(_prompt(1),
                            GenerationConfig(max_new_tokens=8,
                                             min_length=4),
                            grammar=REGEX)
        finally:
            core.close()


# -------------------------------------------------------- conformance


class TestConformance:
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("spec", [REGEX, SCHEMA, JSONG],
                             ids=["regex", "json_schema", "json"])
    def test_output_conforms(self, engine, spec, sampled):
        g = (GenerationConfig(max_new_tokens=40, do_sample=True,
                              temperature=0.9, top_k=20, seed=7)
             if sampled else GenerationConfig(max_new_tokens=40))
        (req,), snap = _run_jobs(engine, [(_prompt(3), g, spec)],
                                 rid_base=7000)
        assert req.state is RequestState.DONE
        text = decode_text(VOCAB, req.result(timeout=60))
        assert conforms(spec, text), text
        assert snap["violations"] == 0 and snap["incomplete"] == 0
        assert snap["entries"] >= 1 and snap["active_rows"] == 0

    def test_grammar_row_leaves_plain_row_bitwise(self, engine):
        """All-zero mask rows ARE the unconstrained semantics: batching
        a constrained request next to a plain one must not move the
        plain stream by a bit."""
        gen = GenerationConfig(max_new_tokens=10, do_sample=True,
                               temperature=0.8, top_p=0.9, seed=11)
        (solo,), _ = _run_jobs(engine, [(_prompt(5), gen, None)],
                               rid_base=7100)
        (plain, constrained), snap = _run_jobs(
            engine, [(_prompt(5), gen, None),
                     (_prompt(6), GenerationConfig(max_new_tokens=24),
                      REGEX)],
            rid_base=7100)
        np.testing.assert_array_equal(
            np.asarray(plain.result(timeout=60)),
            np.asarray(solo.result(timeout=60)))
        assert conforms(REGEX,
                        decode_text(VOCAB,
                                    constrained.result(timeout=60)))
        assert snap["violations"] == 0

    def test_incomplete_grammar_fails_request(self, engine):
        """A row that exhausts its budget mid-grammar must FAIL loudly
        — truncated non-conforming output is never DONE."""
        (req,), snap = _run_jobs(
            engine,
            [(_prompt(4), GenerationConfig(max_new_tokens=3), SCHEMA)],
            rid_base=7200)
        assert req.state is RequestState.FAILED
        with pytest.raises(GrammarIncompleteError):
            req.result(timeout=60)
        assert snap["incomplete"] == 1


# ------------------------------------------------------ parity matrix


class TestParity:
    @pytest.mark.parametrize("window", [2, 4], ids=["spec2", "spec4"])
    def test_greedy_speculative_bitwise(self, engine, window):
        """Constrained greedy under speculation is BITWISE the plain
        constrained stream: each lane is masked by its own advanced FSM
        state, so accept/verify sees exactly the sequential logits."""
        gen = GenerationConfig(max_new_tokens=30)
        (want,), _ = _run_jobs(engine, [(_prompt(1), gen, SCHEMA)],
                               rid_base=7300)
        (got,), snap = _run_jobs(
            engine, [(_prompt(1), gen, SCHEMA)], rid_base=7300,
            core_kw=dict(speculate=True, num_draft_tokens=window))
        np.testing.assert_array_equal(
            np.asarray(got.result(timeout=60)),
            np.asarray(want.result(timeout=60)))
        assert snap["violations"] == 0

    @pytest.mark.parametrize("window", [2, 4], ids=["spec2", "spec4"])
    def test_sampled_speculative_never_violates(self, engine, window):
        """Sampled speculation keeps the distributional guarantee, not
        bitwise plain-parity (true of the unconstrained engine too) —
        what the grammar adds is that NO lane, draft accept, bonus or
        resample can ever emit an illegal token."""
        gen = GenerationConfig(max_new_tokens=40, do_sample=True,
                               temperature=0.9, top_k=20, seed=7)
        (req,), snap = _run_jobs(
            engine, [(_prompt(2), gen, SCHEMA)], rid_base=7400,
            core_kw=dict(speculate=True, num_draft_tokens=window))
        assert req.state is RequestState.DONE
        assert conforms(SCHEMA, decode_text(VOCAB,
                                            req.result(timeout=60)))
        assert snap["violations"] == 0

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_park_resume_parity(self, engine, sampled):
        """FSM state rides the park packet as plain data: a constrained
        row preempted to the host tier and resumed emits exactly the
        uninterrupted stream."""
        gen = (GenerationConfig(max_new_tokens=30, do_sample=True,
                                temperature=0.9, top_k=20, seed=9)
               if sampled else GenerationConfig(max_new_tokens=30))
        kw = dict(kv_host_pages=64)
        (want,), _ = _run_jobs(engine, [(_prompt(8), gen, SCHEMA)],
                               rid_base=7500, core_kw=kw)
        (got,), snap = _run_jobs(engine, [(_prompt(8), gen, SCHEMA)],
                                 rid_base=7500, core_kw=kw,
                                 park_at=(3,))
        np.testing.assert_array_equal(
            np.asarray(got.result(timeout=60)),
            np.asarray(want.result(timeout=60)))
        assert snap["violations"] == 0

    def test_park_resume_parity_speculative_sampled(self, engine):
        """Park/resume under constrained speculation: both runs use the
        same speculative config (the repo-wide sampled-spec parity
        convention), the parked one is preempted mid-decode."""
        gen = GenerationConfig(max_new_tokens=30, do_sample=True,
                               temperature=0.9, top_k=20, seed=13)
        kw = dict(kv_host_pages=64, speculate=True, num_draft_tokens=4)
        (want,), _ = _run_jobs(engine, [(_prompt(9), gen, SCHEMA)],
                               rid_base=7600, core_kw=kw)
        (got,), snap = _run_jobs(engine, [(_prompt(9), gen, SCHEMA)],
                                 rid_base=7600, core_kw=kw,
                                 park_at=(3,))
        np.testing.assert_array_equal(
            np.asarray(got.result(timeout=60)),
            np.asarray(want.result(timeout=60)))
        assert snap["violations"] == 0
        assert conforms(SCHEMA, decode_text(VOCAB,
                                            got.result(timeout=60)))


# ------------------------------------------------------- fleet handoff


class TestHandoff:
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_handoff_parity(self, engines, sampled):
        """The handoff packet ships the grammar SPEC (data, never FSM
        objects): the target re-compiles or cache-hits on its own
        GrammarCache and the stream stays bitwise."""
        gen = (GenerationConfig(max_new_tokens=28, do_sample=True,
                                temperature=0.9, top_p=0.9, seed=3)
               if sampled else GenerationConfig(max_new_tokens=28))
        prompt = _prompt(41, n=24)           # 2 prefill chunks

        request_mod._rid_counter = itertools.count(7700)
        ref = EngineCore(engines[0], grammar_vocab=VOCAB, **FLEET_KW)
        cores = [ref]
        try:
            want_req = ref.submit(prompt, gen, grammar=SCHEMA)[0]
            _drive(ref, [want_req])
            want = np.asarray(want_req.result(timeout=60))

            request_mod._rid_counter = itertools.count(7700)
            src_core = EngineCore(engines[1], grammar_vocab=VOCAB,
                                  **FLEET_KW)
            dst_core = EngineCore(engines[2], grammar_vocab=VOCAB,
                                  **FLEET_KW)
            cores += [src_core, dst_core]
            src = ReplicaHandle("p0", src_core, ReplicaRole.PREFILL)
            dst = ReplicaHandle("d0", dst_core, ReplicaRole.DECODE)
            req = src.core.submit(prompt, gen, grammar=SCHEMA)[0]
            for _ in range(400):
                if ready_for_handoff(src.core, req):
                    break
                src.core.run_once()
            else:
                raise AssertionError("never handoff-ready")
            assert migrate(req, src, dst)
            _drive(dst.core, [req])
            np.testing.assert_array_equal(
                np.asarray(req.result(timeout=60)), want)
            dsnap = dst_core.metrics_snapshot()["structured"]
            assert dsnap["entries"] >= 1      # compiled on the target
            assert dsnap["violations"] == 0
            assert conforms(SCHEMA, decode_text(VOCAB, want))
        finally:
            for c in cores:
                c.close()

    def test_handoff_to_grammarless_target_recovers(self, engines):
        """A target with no grammar plane must refuse the import — and
        the refusal recovers: the row re-imports into the source and
        still finishes there, bitwise."""
        gen = GenerationConfig(max_new_tokens=12)
        prompt = _prompt(43, n=24)

        request_mod._rid_counter = itertools.count(7800)
        ref = EngineCore(engines[0], grammar_vocab=VOCAB, **FLEET_KW)
        cores = [ref]
        try:
            want_req = ref.submit(prompt, gen, grammar=REGEX)[0]
            _drive(ref, [want_req])
            want = np.asarray(want_req.result(timeout=60))

            request_mod._rid_counter = itertools.count(7800)
            src_core = EngineCore(engines[1], grammar_vocab=VOCAB,
                                  **FLEET_KW)
            dst_core = EngineCore(engines[2], **FLEET_KW)  # no grammars
            cores += [src_core, dst_core]
            src = ReplicaHandle("p0", src_core, ReplicaRole.PREFILL)
            dst = ReplicaHandle("d0", dst_core, ReplicaRole.DECODE)
            req = src.core.submit(prompt, gen, grammar=REGEX)[0]
            for _ in range(400):
                if ready_for_handoff(src.core, req):
                    break
                src.core.run_once()
            else:
                raise AssertionError("never handoff-ready")
            assert not migrate(req, src, dst)
            assert dst.handoffs_in == 0
            assert dst.core.active_count == 0
            _drive(src.core, [req])
            np.testing.assert_array_equal(
                np.asarray(req.result(timeout=60)), want)
        finally:
            for c in cores:
                c.close()


# ----------------------------------------------------- recompile churn


class TestChurn:
    def test_32_grammar_churn_zero_post_warmup_compiles(self, engine):
        """The executable key carries only the static 'grammar' marker:
        32 DISTINCT grammars churning through one warm core must not
        trigger a single post-warmup decode compile — the FSM is data.

        This is the instrumented twin of the static gate in
        analysis/rules/recompile_hazard.py (grammar-shape-keyed serving
        builders are lint errors)."""
        request_mod._rid_counter = itertools.count(7900)
        core = EngineCore(engine, grammar_vocab=VOCAB, **CORE_KW)
        try:
            warm = core.submit(_prompt(10),
                               GenerationConfig(max_new_tokens=6),
                               grammar={"type": "regex",
                                        "pattern": "w+"})[0]
            _drive(core, [warm])
            log = get_compile_log()
            before = log.summary()["post_warmup_decode_compiles"]
            reqs = []
            for i in range(32):
                spec = {"type": "regex", "pattern": f"g{i}(a|b)"}
                reqs.append(core.submit(
                    _prompt(11 + i),
                    GenerationConfig(max_new_tokens=8),
                    grammar=spec)[0])
            _drive(core, reqs, max_iters=2000)
            after = log.summary()["post_warmup_decode_compiles"]
            assert after - before == 0
            snap = core.metrics_snapshot()["structured"]
            assert snap["entries"] == 33     # warmup + 32 distinct
            assert snap["violations"] == 0
            for i, r in enumerate(reqs):
                text = decode_text(VOCAB, r.result(timeout=60))
                assert conforms({"type": "regex",
                                 "pattern": f"g{i}(a|b)"}, text), text
        finally:
            core.close()


# ---------------------------------------------------- loadgen roundtrip


class TestLoadgen:
    def test_structured_trace_roundtrip_and_replay(self, engine,
                                                   tmp_path):
        """The structured tenant class survives the JSONL round trip
        (grammar specs are plain JSON) and a replayed event decodes
        into a conforming stream."""
        from tools import loadgen

        events = loadgen.generate_trace(
            5, 4.0, 10.0, tenants=loadgen.structured_tenants())
        with_grammar = [e for e in events if e.get("grammar")]
        assert with_grammar, "structured tenant emitted no events"
        assert all(e["grammar"] == loadgen.TOOL_CALL_GRAMMAR
                   for e in with_grammar)

        path = str(tmp_path / "trace.jsonl")
        loadgen.write_trace(path, events)
        back = loadgen.read_trace(path)
        assert back == events                # lossless, grammar included

        ev = dict(with_grammar[0])
        ev["timeout_s"] = None               # replay off the wall clock
        # fit the tiny 64-position test model: the worst-case tool-call
        # emission is ~50 chars, so trim the prompt and budget the rest
        ev["prompt"] = ev["prompt"][:4]
        ev["max_new"] = 58
        req = loadgen.request_from_event(ev)
        assert req.grammar == loadgen.TOOL_CALL_GRAMMAR
        core = EngineCore(engine, grammar_vocab=VOCAB,
                          **dict(CORE_KW, max_model_len=64))
        try:
            core.enqueue(req)
            _drive(core, [req])
            assert req.state is RequestState.DONE
            text = decode_text(VOCAB, req.result(timeout=60))
            assert conforms(loadgen.TOOL_CALL_GRAMMAR, text), text
        finally:
            core.close()


# -------------------------------------------------------- HTTP surface


def _post(url, path, body):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=300)


@pytest.fixture(scope="module")
def structured_server(tmp_path_factory):
    from tests.test_serve import _spawn_server, _tiny_model

    d = str(tmp_path_factory.mktemp("model") / "gpt")
    _tiny_model(d)
    url, proc = _spawn_server(d, "--structured", "--max_model_len",
                              "64")
    yield url
    proc.terminate()
    proc.wait(timeout=30)


class TestServeStructured:
    def test_constrained_generate_conforms(self, structured_server):
        ids = _prompt(21).reshape(1, -1)
        with _post(structured_server, "/generate",
                   {"ids": ids.tolist(), "max_new_tokens": 16,
                    "grammar": REGEX}) as r:
            row = json.load(r)["tokens"][0]
        # the serving vocab maps specials/pads to chr(32+i); strip the
        # pad tail before checking full-match conformance
        text = decode_text(VOCAB, row).strip(" ")
        assert conforms(REGEX, text), text

    @pytest.mark.parametrize("grammar", [
        {"type": "ebnf", "rules": "S ::= 'a'"},       # unknown type
        {"type": "regex", "pattern": "("},            # malformed
        {"type": "regex", "pattern": "\t"},           # unsatisfiable
        {"type": "regex", "pattern": "a" * 70000},    # oversized
    ], ids=["unknown-type", "malformed", "unsatisfiable", "oversized"])
    def test_bad_grammar_is_400_with_structured_body(
            self, structured_server, grammar):
        ids = _prompt(22).reshape(1, -1)
        try:
            _post(structured_server, "/generate",
                  {"ids": ids.tolist(), "max_new_tokens": 4,
                   "grammar": grammar})
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            body = json.loads(e.read())
            assert body["error_type"] == "GrammarError"
            assert body["error"]
