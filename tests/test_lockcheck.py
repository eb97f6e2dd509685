"""Whole-program concurrency analysis: the static ``lock-order`` rule
(analysis/interproc.py) and its dynamic counterpart, the instrumented
lock checker (analysis/lockcheck.py).

Static side: synthetic multi-file fixtures prove the interprocedural
walk resolves locks across files/receivers — ABBA cycles fire with
call-path witnesses, bounded (timeout) acquires never participate,
blocking calls under a lock fire, reentrant RLock use stays silent
while re-acquiring a plain Lock is a finding.

Dynamic side: ``instrument_locks()`` wraps serving-plane lock
construction and must observe acquisition-order inversions (two-stack
witnesses), same-thread Lock re-acquisition (raised instead of
deadlocking the suite), host syncs under non-dispatch locks, and hold
stats — and export a graph whose every edge appears in the committed
static graph (``gap_report`` empty: dynamic ⊆ static).

The end-to-end gate: an instrumented ``EngineCore`` serving real
requests reports ZERO violations and an empty gap report against
``tools/lock_graph_baseline.json``.  (The full fleet/resilience suites
run instrumented behind the ``lockcheck`` marker — see
tests/test_ci_tools.py.)
"""
import json
import os
import textwrap
import threading
import time

import numpy as np
import pytest

from paddle_infer_tpu.analysis import Analyzer, all_rules
from paddle_infer_tpu.analysis.lockcheck import (LockChecker,
                                                 instrument_locks)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE = os.path.join(ROOT, "tools", "lock_graph_baseline.json")


# ------------------------------------------------------------ static
def run_lock_order(tmp_path, sources, config=None):
    """sources: {relpath: code}.  Returns (findings, rule) — the rule
    keeps the built LockGraph for structural assertions."""
    paths = []
    for rel, src in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        paths.append(str(p))
    rules = all_rules(["lock-order"])
    analyzer = Analyzer(rules, root=str(tmp_path), config=config)
    findings, _ = analyzer.run(sorted(paths))
    return findings, rules[0]


ABBA_A = """
    import threading

    class A:
        def __init__(self, peer: "B"):
            self._lock = threading.Lock()
            self.peer = peer

        def work(self):
            with self._lock:
                self.peer.poke()
"""

ABBA_B = """
    import threading

    class B:
        def __init__(self):
            self._lock = threading.Lock()

        def attach(self, owner: "A"):
            self.owner = owner

        def poke(self):
            with self._lock:
                pass

        def back(self):
            with self._lock:
                self.owner.work()
"""


def test_static_abba_cycle_across_files(tmp_path):
    fs, rule = run_lock_order(tmp_path, {"serving/a.py": ABBA_A,
                                         "serving/b.py": ABBA_B})
    cycles = rule.graph.cycles()
    assert len(cycles) == 1
    assert sorted(cycles[0]["nodes"]) == ["A._lock", "B._lock"]
    assert len(fs) == 1
    f = fs[0]
    assert f.rule == "lock-order" and "lock-order cycle" in f.message
    # the witness explains HOW the analyzer got the first lock held
    assert "held since" in f.message and " -> " in f.message


def test_static_bounded_acquire_breaks_cycle(tmp_path):
    bounded_b = ABBA_B.replace(
        """def back(self):
            with self._lock:
                self.owner.work()""",
        """def back(self):
            with self._lock:
                if not self.owner._lock.acquire(timeout=0.1):
                    return
                try:
                    pass
                finally:
                    self.owner._lock.release()""")
    fs, rule = run_lock_order(tmp_path, {"serving/a.py": ABBA_A,
                                         "serving/b.py": bounded_b})
    assert rule.graph.cycles() == []
    assert fs == []
    # the ordering is still IN the graph, downgraded to bounded-only
    edges = {(e["src"], e["dst"]): e["bounded"]
             for e in rule.graph.to_stable_dict()["edges"]}
    assert edges[("B._lock", "A._lock")] is True
    assert edges[("A._lock", "B._lock")] is False


def test_static_cross_instance_self_cycle(tmp_path):
    # the real fleet-handoff bug shape: a DIFFERENT instance of the
    # lock you already hold (replica A hands off to replica B while B
    # hands off to A)
    src = """
        import threading

        class Core:
            def __init__(self):
                self._lock = threading.Lock()

            def handoff(self, other: "Core"):
                with self._lock:
                    with other._lock:
                        pass
    """
    fs, rule = run_lock_order(tmp_path, {"serving/core.py": src})
    cycles = rule.graph.cycles()
    assert len(cycles) == 1 and cycles[0]["nodes"] == ["Core._lock"]
    assert len(fs) == 1
    assert "Core._lock" in fs[0].message


def test_static_blocking_under_lock(tmp_path):
    src = """
        import threading
        import time

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._done = threading.Event()

            def run(self):
                with self._lock:
                    time.sleep(0.5)
    """
    fs, rule = run_lock_order(tmp_path, {"serving/w.py": src})
    assert len(fs) == 1
    assert "blocking call" in fs[0].message
    assert "W._lock" in fs[0].message


def test_static_reacquire_plain_lock_fires_rlock_silent(tmp_path):
    src = """
        import threading

        class R:
            def __init__(self):
                self._lock = threading.{kind}()

            def outer(self):
                with self._lock:
                    self._inner()

            def _inner(self):
                with self._lock:
                    pass
    """
    fs, _ = run_lock_order(
        tmp_path, {"serving/r.py": src.format(kind="Lock")})
    assert len(fs) == 1
    assert "re-acquiring non-reentrant Lock" in fs[0].message

    fs, _ = run_lock_order(
        tmp_path, {"serving/r.py": src.format(kind="RLock")})
    assert fs == []


def test_static_findings_scoped_to_serving(tmp_path):
    # the graph spans the project but findings only anchor on serving/
    fs, rule = run_lock_order(tmp_path, {"ops/a.py": ABBA_A,
                                         "ops/b.py": ABBA_B})
    assert rule.graph.cycles()          # the cycle IS in the graph
    assert fs == []                     # ...but out of finding scope


def test_static_graph_export_is_stable_and_json_native(tmp_path):
    _, rule = run_lock_order(tmp_path, {"serving/a.py": ABBA_A,
                                        "serving/b.py": ABBA_B})
    d = rule.graph.to_stable_dict()
    # round-trips and carries no line numbers (edits must not churn it)
    assert json.loads(json.dumps(d, sort_keys=True)) == d
    assert "line" not in json.dumps(d)
    dot = rule.graph.to_dot()
    assert dot.startswith("digraph") and "A._lock" in dot


# ----------------------------------------------------------- dynamic
def test_dynamic_inversion_two_stack_witness():
    with instrument_locks(paths=[HERE]) as chk:
        lock_a = threading.Lock()
        lock_b = threading.Lock()
        with lock_a:
            with lock_b:
                pass
        with lock_b:
            with lock_a:
                pass
    assert [v["kind"] for v in chk.violations] == ["inversion"]
    v = chk.violations[0]
    assert set(v["locks"]) == {"test_lockcheck.lock_a",
                               "test_lockcheck.lock_b"}
    # the classic two-witness shape: one stack per direction
    assert v["witness_forward"] and v["witness_backward"]
    fwd_held, fwd_acq = v["witness_forward"]
    assert any("test_lockcheck" in fr for fr in fwd_held + fwd_acq)


def test_dynamic_bounded_backoff_is_not_inversion():
    with instrument_locks(paths=[HERE]) as chk:
        lock_a = threading.Lock()
        lock_b = threading.Lock()
        with lock_a:
            with lock_b:
                pass
        with lock_b:
            # the fixed handoff pattern: bounded acquire backs off
            if lock_a.acquire(timeout=0.1):
                lock_a.release()
    assert chk.violations == []
    edges = {(e["src"], e["dst"]): e["bounded"]
             for e in chk.graph()["edges"]}
    assert edges[("test_lockcheck.lock_a", "test_lockcheck.lock_b")] \
        is False
    assert edges[("test_lockcheck.lock_b", "test_lockcheck.lock_a")] \
        is True


def test_dynamic_threaded_inversion_detected():
    # same inversion, actually cross-thread (sequenced so it cannot
    # deadlock the suite)
    with instrument_locks(paths=[HERE]) as chk:
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def forward():
            with lock_a:
                with lock_b:
                    pass

        t = threading.Thread(target=forward)
        t.start()
        t.join()
        with lock_b:
            with lock_a:
                pass
    kinds = [v["kind"] for v in chk.violations]
    assert kinds == ["inversion"]


def test_dynamic_plain_lock_reacquire_raises_not_deadlocks():
    with instrument_locks(paths=[HERE]) as chk:
        lock = threading.Lock()
        with lock:
            with pytest.raises(RuntimeError, match="re-acquired"):
                lock.acquire()
    assert [v["kind"] for v in chk.violations] == ["self-deadlock"]
    assert v_locks(chk) == ["test_lockcheck.lock"]


def v_locks(chk):
    return sorted({n for v in chk.violations for n in v["locks"]})


def test_dynamic_rlock_reentrancy_clean():
    with instrument_locks(paths=[HERE]) as chk:
        rl = threading.RLock()
        with rl:
            with rl:
                pass
    assert chk.violations == []
    st = chk.hold_stats["test_lockcheck.rl"]
    assert st["count"] == 1             # one ownership span, not two


def test_dynamic_hold_stats():
    with instrument_locks(paths=[HERE]) as chk:
        lk = threading.Lock()
        with lk:
            time.sleep(0.02)
        with lk:
            pass
    st = chk.hold_stats["test_lockcheck.lk"]
    assert st["count"] == 2
    assert st["max_s"] >= 0.015
    assert st["total_s"] >= st["max_s"]


def test_dynamic_host_sync_under_lock():
    import jax

    with instrument_locks(paths=[HERE]) as chk:
        lk = threading.Lock()
        with lk:
            jax.block_until_ready(np.zeros(2))
    assert [v["kind"] for v in chk.violations] == \
        ["host-sync-under-lock"]
    assert chk.violations[0]["locks"] == ["test_lockcheck.lk"]

    # ...and the allow list (the step lock serializes device work BY
    # DESIGN) keeps it quiet
    with instrument_locks(
            paths=[HERE],
            allow_host_sync_under=("test_lockcheck.lk",)) as chk:
        lk = threading.Lock()
        with lk:
            jax.block_until_ready(np.zeros(2))
    assert chk.violations == []


def test_dynamic_condition_integration():
    # a Condition constructed bare gets a named wrapped RLock; wait()
    # releases and restores it without corrupting held-state
    with instrument_locks(paths=[HERE]) as chk:
        cond = threading.Condition()
        with cond:
            cond.wait(timeout=0.01)
            lk = threading.Lock()
            with lk:
                pass
    assert chk.violations == []
    edges = {(e["src"], e["dst"]) for e in chk.graph()["edges"]}
    assert ("test_lockcheck.cond", "test_lockcheck.lk") in edges


def test_dynamic_outside_paths_untouched():
    # stdlib-owned locks must come back raw: instrumentation is scoped
    # to the serving plane, not the interpreter
    with instrument_locks(paths=[os.path.join(HERE, "no_such_dir")]):
        lk = threading.Lock()
    assert type(lk) is not LockChecker
    assert not hasattr(lk, "_checker")


def test_gap_report_direction_aware():
    with instrument_locks(paths=[HERE]) as chk:
        lock_a = threading.Lock()
        lock_b = threading.Lock()
        with lock_a:
            with lock_b:
                pass
    edge = ("test_lockcheck.lock_a", "test_lockcheck.lock_b")
    covered = {"edges": [{"src": edge[0], "dst": edge[1],
                          "bounded": True}]}    # bounded still covers
    assert chk.gap_report(covered) == []
    reversed_only = {"edges": [{"src": edge[1], "dst": edge[0],
                                "bounded": False}]}
    assert chk.gap_report(reversed_only) == [edge]
    assert chk.gap_report({"edges": []}) == [edge]


# -------------------------------------------------------------- e2e
def test_engine_core_instrumented_end_to_end():
    """The acceptance gate in miniature: a real EngineCore serving a
    real request under full instrumentation reports zero violations,
    and every observed edge is in the committed static graph."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference.generation import (
        GenerationConfig, PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import EngineCore

    pit.seed(0)
    with instrument_locks() as chk:
        model = GPTForCausalLM(GPTConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        model.eval()
        engine = PagedGenerationEngine(model, page_size=8)
        core = EngineCore(engine, max_batch=2, max_model_len=48,
                          token_budget=16, prefill_chunk=16)
        prompt = np.random.RandomState(7).randint(
            0, 96, (8,)).astype(np.int32)
        (req,) = core.submit(prompt, GenerationConfig(max_new_tokens=6))
        for _ in range(200):
            if req.done:
                break
            core.run_once()
        core.close()
    assert req.done
    assert chk.violations == [], chk.violations
    g = chk.graph()
    assert "EngineCore._step_lock" in g["nodes"]   # really observed
    with open(BASELINE) as f:
        static = json.load(f)
    gaps = chk.gap_report(static)
    assert gaps == [], \
        f"dynamic lock edges missing from the static graph: {gaps}"


def test_structured_instrumented_end_to_end():
    """The constrained-decoding plane under full instrumentation: a
    grammar-compiling admission, masked decode steps and the
    structured metrics snapshot (engine counters under the step lock,
    cache counters on the GrammarCache leaf strictly after it) report
    zero violations, and every observed edge — including any touching
    ``GrammarCache._lock`` — is in the committed static graph."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference.generation import (
        GenerationConfig, PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import EngineCore, default_vocab

    pit.seed(0)
    with instrument_locks() as chk:
        model = GPTForCausalLM(GPTConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        model.eval()
        engine = PagedGenerationEngine(model, page_size=8)
        core = EngineCore(engine, max_batch=2, max_model_len=48,
                          token_budget=16, prefill_chunk=16,
                          grammar_vocab=default_vocab(96))
        prompt = np.random.RandomState(7).randint(
            0, 96, (8,)).astype(np.int32)
        (req,) = core.submit(
            prompt, GenerationConfig(max_new_tokens=12),
            grammar={"type": "regex", "pattern": "(yes|no|maybe)!"})
        for _ in range(200):
            if req.done:
                break
            core.run_once()
        snap = core.metrics_snapshot()
        core.close()
    assert req.done
    assert snap["structured"]["entries"] >= 1
    assert chk.violations == [], chk.violations
    g = chk.graph()
    assert "GrammarCache._lock" in g["nodes"]      # really observed
    with open(BASELINE) as f:
        static = json.load(f)
    gaps = chk.gap_report(static)
    assert gaps == [], \
        f"dynamic lock edges missing from the static graph: {gaps}"
