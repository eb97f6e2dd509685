"""Disaggregated serving fleet (paddle_infer_tpu/serving/fleet/):
prefill/decode replica roles, the prefix-affinity router, and
cross-replica KV page handoff.

The load-bearing invariant is HANDOFF EXACTNESS: a request that
prefills on one replica and decodes on another must emit the same
tokens, bit for bit, as the same request served end-to-end by a single
core — for greedy AND seeded-sampled configs (per-request sampling keys
are ``fold_in(PRNGKey(seed), rid)``, so the compared runs pin the rid
counter).  On top of that: the read-only ``PrefixCache.peek`` probe the
router spams per dispatch must be side-effect-free, routing must honor
health and roles, and the elastic policy must flip with hysteresis and
never strand the fleet without a prefill- or decode-capable replica.
"""
import itertools
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu import native
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.serving import (ElasticRolePolicy, EngineCore,
                                      FleetRouter, RejectedError,
                                      ReplicaHandle, ReplicaRole,
                                      parse_fleet_roles)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.fleet import migrate, ready_for_handoff
from paddle_infer_tpu.serving.prefix_cache import PrefixCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """Handoff parity compares tokens across replicas and against a
    single core — bitwise only when everything runs unsharded."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
    from paddle_infer_tpu.observability import get_compile_log
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


# four engines, module-scoped so the serving executables compile once:
# replicas NEVER share an engine (pools and compile caches are strictly
# per-engine), but they do share the model
@pytest.fixture(scope="module")
def engines(model):
    return [PagedGenerationEngine(model, page_size=8) for _ in range(4)]


CORE_SHAPE = dict(max_batch=3, max_model_len=48, token_budget=16,
                  prefill_chunk=16)


@pytest.fixture
def make_core(engines):
    cores = []
    pool = list(engines)

    def make(**kw):
        for k, v in CORE_SHAPE.items():
            kw.setdefault(k, v)
        core = EngineCore(pool.pop(0), **kw)
        cores.append(core)
        return core

    yield make
    for c in cores:
        c.close()


def _drive(core, reqs, max_iters=400):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _drive_router(router, reqs, max_iters=600):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        router.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


# ------------------------------------------------------------- handoff

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_handoff_stream_bitwise_equal(make_core, sampled):
    """Prefill on one replica, decode on another: the stream must be
    bitwise identical to a single-replica run of the same request —
    including the sampled config, whose per-row keys fold in the rid
    and the absolute step index (both carried by the packet)."""
    g = (GenerationConfig(max_new_tokens=10, do_sample=True,
                          temperature=0.9, top_p=0.9, seed=3)
         if sampled else GenerationConfig(max_new_tokens=10))
    prompt = _prompt(41, n=24)              # 2 prefill chunks

    request_mod._rid_counter = itertools.count(5100)
    ref = make_core()
    req_ref = ref.submit(prompt, g)[0]
    _drive(ref, [req_ref])
    want = np.asarray(req_ref.result(timeout=60))

    request_mod._rid_counter = itertools.count(5100)   # same rid
    src = ReplicaHandle("p0", make_core(), ReplicaRole.PREFILL)
    dst = ReplicaHandle("d0", make_core(), ReplicaRole.DECODE)
    req = src.core.submit(prompt, g)[0]
    for _ in range(400):
        if ready_for_handoff(src.core, req):
            break
        src.core.run_once()
    else:
        raise AssertionError("request never became handoff-ready")
    emitted_before = req.emitted
    assert emitted_before >= 1 and not req.done

    assert migrate(req, src, dst)
    assert src.handoffs_out == 1 and dst.handoffs_in == 1
    # export released the source slot AND its pages (no prefix cache on
    # these cores, so nothing is retained; only the one-page ragged
    # scratch reservation stays resident)
    assert src.core.active_count == 0
    assert src.core._used_pages() == 1

    _drive(dst.core, [req])
    got = np.asarray(req.result(timeout=60))
    np.testing.assert_array_equal(got, want)
    # continuation happened on the target, not a replay from scratch
    assert req.emitted > emitted_before
    # the finished slot frees every page on the target too (scratch
    # reservation aside)
    for _ in range(3):
        dst.core.run_once()
    assert dst.core.active_count == 0
    assert dst.core._used_pages() == 1


def test_migrate_refuses_cleanly_when_not_slotted(make_core):
    """A request that already finished has no slot: migrate must return
    False without touching either replica."""
    src = ReplicaHandle("p0", make_core(), ReplicaRole.PREFILL)
    dst = ReplicaHandle("d0", make_core(), ReplicaRole.DECODE)
    req = src.core.submit(_prompt(7), GenerationConfig(max_new_tokens=4))[0]
    _drive(src.core, [req])
    assert not migrate(req, src, dst)
    assert src.handoffs_out == 0 and dst.handoffs_in == 0
    assert dst.core.active_count == 0


def test_migrate_replay_fallback_bypasses_drain_gate(make_core):
    """Worst-case recovery: BOTH imports refused (the source started
    draining between export and re-import).  The replay fallback must
    not go through ``enqueue`` — its drain gate raises LoadShedError in
    exactly this state, which would escape migrate and strand the
    request with its exported slot already freed.  It must land at the
    source queue's head, replay there (a draining core keeps stepping),
    and still finish bitwise-identical to a single-core run."""
    g = GenerationConfig(max_new_tokens=10)
    prompt = _prompt(43, n=24)
    ref = make_core()
    want_req = ref.submit(prompt, g)[0]
    _drive(ref, [want_req])
    want = np.asarray(want_req.result(timeout=60))

    src = ReplicaHandle("p0", make_core(), ReplicaRole.PREFILL)
    dst = ReplicaHandle("d0", make_core(), ReplicaRole.DECODE)
    req = src.core.submit(prompt, g)[0]
    for _ in range(400):
        if ready_for_handoff(src.core, req):
            break
        src.core.run_once()
    else:
        raise AssertionError("request never became handoff-ready")
    dst.core.set_draining(True)             # import refused
    src.core.set_draining(True)             # re-import refused too
    assert not migrate(req, src, dst)       # must NOT raise
    assert src.core.queue_depth == 1        # requeued at the source
    assert not req.done
    _drive(src.core, [req])
    np.testing.assert_array_equal(np.asarray(req.result(timeout=60)),
                                  want)


# ---------------------------------------------------------------- peek

def test_peek_is_read_only_after_1000_probes():
    """1000 ``peek`` probes must not move a single pin, refcount, LRU
    clock, or hit/query counter — the router calls peek against every
    replica per dispatch, and a probe that pinned or touched LRU state
    would corrupt eviction under routing load."""
    pool = native.KVBlockPool(16, 4)
    cache = PrefixCache(pool, page_size=4, watermark=1.0)
    pool.reserve(0, 10)                     # 2 full pages + 2-token tail
    table = [int(x) for x in pool.block_table(0)]
    cache.insert(list(range(10)), table)
    pool.free(0)                            # tree holds the only refs
    toks = list(range(10)) + [77]

    def state():
        nodes, partials = [], []
        stack = [(salt, n) for salt, n in cache._roots.items()]
        while stack:
            salt, n = stack.pop()
            stack.extend((salt, c) for c in n.children.values())
            nodes.append((salt, id(n), n.pins, n.last_used))
            for ptoks, entry in n.partials.items():
                partials.append((ptoks, entry[0], entry[1], entry[2]))
        return (sorted(nodes), sorted(partials),
                {b: pool.block_refcount(b) for b in table},
                cache.queries, cache.hits, cache._clock,
                pool.free_blocks)

    before = state()
    for _ in range(1000):
        got = cache.peek(toks)
    assert got == 10                        # 8 full-page + 2 partial
    assert state() == before
    assert cache.peek(toks, salt="other-tenant") == 0
    snap = cache.stats_snapshot()
    assert snap["peeks"] == 1001
    assert snap["queries"] == 0 and snap["hits"] == 0
    # peek's answer agrees with the authoritative (pinning) matcher
    m = cache.match(toks)
    assert m.cached_tokens == 10
    cache.release(m)


# -------------------------------------------------------------- routing

def test_router_prefix_affinity_routes_to_warm_replica(make_core):
    """A resubmitted prompt must land on the replica whose radix tree
    holds its prefix — confirmed via peek, counted as an affinity hit —
    not on the emptier replica the load fallback would pick."""
    a = ReplicaHandle("a", make_core(enable_prefix_cache=True))
    b = ReplicaHandle("b", make_core(enable_prefix_cache=True))
    router = FleetRouter([a, b], prefix_affinity=True)
    prompt = _prompt(11, n=20)
    g = GenerationConfig(max_new_tokens=4)

    r1 = router.submit(prompt, g)
    _drive_router(router, [r1])             # finish -> insert into tree
    warm = a if a.dispatched else b
    assert warm.dispatched == 1

    r2 = router.submit(prompt, g)
    assert warm.dispatched == 2             # routed back to the warm tree
    assert warm.affinity_hits == 1
    assert warm.core.prefix_cache.peeks >= 1
    # the cold replica's shadow predicts no match, so it must never be
    # probed — peek() takes its tree lock, and probing every candidate
    # per dispatch is the serialization the shadow exists to avoid
    cold = b if warm is a else a
    assert cold.core.prefix_cache.peeks == 0
    _drive_router(router, [r2])
    np.testing.assert_array_equal(np.asarray(r2.result(timeout=60)),
                                  np.asarray(r1.result(timeout=60)))
    snap = router.snapshot()
    assert snap["affinity_hits"] == 1
    assert snap["shadow"]["nodes"] >= 1


def test_threaded_handoff_fires_at_chunk_boundary(make_core, model):
    """With replicas running their OWN scheduler threads (the serve.py
    deployment shape), every long prompt must still hand off.  The
    stepping thread holds the step lock nearly back-to-back, so a
    router-side poll alone can lose the lock race and miss the whole
    decode phase — the ``on_prefill_complete`` boundary hook is what
    makes this deterministic; this test fails without it."""
    p = ReplicaHandle("prefill0", make_core().start(), ReplicaRole.PREFILL)
    d = ReplicaHandle("decode0", make_core().start(), ReplicaRole.DECODE)
    ref = make_core()
    router = FleetRouter([p, d], prefix_affinity=True)
    router.start(start_cores=False)
    try:
        g = GenerationConfig(max_new_tokens=12)
        for i in range(3):
            prompt = _prompt(70 + i, n=24)      # >= prefill_threshold
            want = ref.submit(prompt, g)[0]
            _drive(ref, [want])
            got = router.submit(prompt, g)
            got.result(timeout=120)
            # greedy streams are rid-independent, so the single-core
            # run is the bitwise reference without pinning rids
            np.testing.assert_array_equal(np.asarray(got.tokens),
                                          np.asarray(want.tokens))
            assert p.handoffs_out == i + 1, \
                "long prompt finished on the prefill replica instead " \
                "of handing off at its chunk boundary"
            assert d.handoffs_in == i + 1
        assert router.snapshot()["handoffs"] == 3
        assert router.requeued == 0
    finally:
        router.stop()


def test_router_role_gate_and_health_gate(make_core):
    """Long prompts go to the prefill replica, short ones to the decode
    replica; a DRAINING replica gets nothing new and its queued (never
    slotted) admissions are reclaimed and rerouted."""
    p = ReplicaHandle("p0", make_core(), ReplicaRole.PREFILL)
    d = ReplicaHandle("d0", make_core(), ReplicaRole.DECODE)
    router = FleetRouter([p, d])
    g = GenerationConfig(max_new_tokens=4)

    long_req = router.submit(_prompt(1, n=24), g)     # >= chunk+1 = 17
    short_req = router.submit(_prompt(2, n=8), g)
    assert p.dispatched == 1 and d.dispatched == 1
    # the long prompt on a dedicated prefill replica is handoff-bound
    assert router.snapshot()["pending_handoffs"] == 1
    _drive_router(router, [long_req, short_req])
    assert router.handoffs == 1
    assert p.handoffs_out == 1 and d.handoffs_in == 1

    # strand a queued admission on the (now draining) decode replica:
    # overfill it so the last request cannot be slotted
    reqs = [d.core.submit(_prompt(3 + i, n=8),
                          GenerationConfig(max_new_tokens=8))[0]
            for i in range(CORE_SHAPE["max_batch"] + 1)]
    d.health.to_draining("test drain")
    assert not d.is_serving()
    _drive_router(router, reqs)
    assert router.requeued >= 1             # reclaimed from d0's queue
    # nothing NEW routes to the draining replica (short prompts fall
    # back to the prefill replica: roles are policy, not capability)
    before = p.dispatched
    r = router.submit(_prompt(90, n=8), g)
    assert p.dispatched == before + 1
    assert d.dispatched == 1                # unchanged since the drain
    _drive_router(router, [r])


def test_reroute_survives_target_refusal(make_core):
    """The target replica can fill (or start draining) between the
    reroute's ``_serving()`` check and the enqueue.  The refusal must
    not abort the reroute loop or drop requests: everything the drained
    source queue held goes back to its head and retries next tick."""
    a = ReplicaHandle("a0", make_core())
    b = ReplicaHandle("b0", make_core())
    router = FleetRouter([a, b])
    g = GenerationConfig(max_new_tokens=4)
    n = CORE_SHAPE["max_batch"] + 2
    reqs = [a.core.submit(_prompt(60 + i, n=8), g)[0] for i in range(n)]
    router.run_once()                       # a slots max_batch, 2 queue
    stranded = a.core.queue_depth
    assert stranded == 2
    a.health.to_draining("test drain")
    depth, b.core._queue.max_depth = b.core._queue.max_depth, 0
    router.run_once()                       # b refuses every enqueue
    assert router.requeued == 0
    assert a.core.queue_depth == stranded   # nothing lost
    b.core._queue.max_depth = depth
    router.run_once()
    assert router.requeued == stranded      # retried and rerouted
    _drive_router(router, reqs)
    for r in reqs:
        assert len(r.result(timeout=60)) > 0


def test_shadow_forgets_replica_that_stops_serving(make_core):
    """A replica that drains (or goes DOWN) must be dropped from the
    shadow index: a restarted core comes back with an EMPTY tree, so
    stale entries would keep attracting affinity probes."""
    a = ReplicaHandle("a0", make_core(enable_prefix_cache=True))
    b = ReplicaHandle("b0", make_core(enable_prefix_cache=True))
    router = FleetRouter([a, b], prefix_affinity=True)
    r1 = router.submit(_prompt(31, n=20), GenerationConfig(max_new_tokens=4))
    _drive_router(router, [r1])
    warm = a if a.dispatched else b
    assert router.snapshot()["shadow"]["nodes"] >= 1
    warm.health.to_draining("maintenance")
    router.run_once()
    snap = router.snapshot()["shadow"]
    assert snap["nodes"] == 0 and snap["replicas"] == 0


def test_router_rejects_when_no_replica_serving(make_core):
    h = ReplicaHandle("only", make_core())
    router = FleetRouter([h])
    h.health.to_draining("maintenance")
    with pytest.raises(RejectedError):
        router.submit(_prompt(5), GenerationConfig(max_new_tokens=2))
    assert router.no_replica_rejects == 1
    assert h.dispatched == 0


# -------------------------------------------------------------- elastic

def test_elastic_policy_hysteresis_and_dwell():
    pol = ElasticRolePolicy(high=0.65, low=0.25, window=4,
                            min_dwell_s=10.0, min_tokens=10)
    assert pol.decide(ReplicaRole.MIXED, now=100.0) is None  # no signal
    pol.observe(100, 0)
    assert pol.prefill_fraction == 1.0
    assert pol.decide(ReplicaRole.MIXED, now=100.0) is ReplicaRole.PREFILL
    # decide() is a pure query: until the router COMMITS the flip, the
    # dwell clock must not start — a coverage-guard rejection would
    # otherwise suppress every later flip for min_dwell_s
    assert pol.decide(ReplicaRole.MIXED, now=101.0) is ReplicaRole.PREFILL
    pol.committed(101.0)
    # dwell guard: no second flip inside min_dwell_s of the commit
    for _ in range(4):
        pol.observe(0, 100)
    assert pol.decide(ReplicaRole.PREFILL, now=105.0) is None
    assert pol.decide(ReplicaRole.PREFILL, now=120.0) is ReplicaRole.DECODE
    pol.committed(120.0)
    # mid-band pulls back to MIXED (the rest state)
    for _ in range(4):
        pol.observe(50, 50)
    assert pol.decide(ReplicaRole.DECODE, now=140.0) is ReplicaRole.MIXED
    # under min_tokens the mix is noise -> no decision
    quiet = ElasticRolePolicy(min_tokens=64)
    quiet.observe(4, 2)
    assert quiet.prefill_fraction is None
    assert quiet.decide(ReplicaRole.MIXED, now=1e4) is None
    with pytest.raises(ValueError):
        ElasticRolePolicy(high=0.2, low=0.5)


def test_router_elastic_flips_only_when_fleet_stays_covered(make_core):
    """Prefill-heavy traffic flips a mixed-configured replica toward
    PREFILL — but only while another serving replica still accepts
    decode; with a prefill-only peer the same pressure must not strip
    the fleet of its last decode-capable replica."""
    policy = ElasticRolePolicy(high=0.6, low=0.2, window=8,
                               min_dwell_s=0.0, min_tokens=8)
    m = ReplicaHandle("m0", make_core())            # configured mixed
    d = ReplicaHandle("d0", make_core(), ReplicaRole.DECODE)
    router = FleetRouter([m, d], elastic=policy)
    req = router.submit(_prompt(21, n=24), GenerationConfig(max_new_tokens=4))
    router.run_once()     # 24 prefill tokens observed, ~0 decode tokens
    assert m.role is ReplicaRole.PREFILL and m.role_flips == 1
    assert m.configured_role is ReplicaRole.MIXED
    _drive_router(router, [req])

    policy2 = ElasticRolePolicy(high=0.6, low=0.2, window=8,
                                min_dwell_s=0.0, min_tokens=8)
    m2 = ReplicaHandle("m1", make_core())
    p2 = ReplicaHandle("p1", make_core(), ReplicaRole.PREFILL)
    router2 = FleetRouter([m2, p2], elastic=policy2)
    req2 = router2.submit(_prompt(22, n=24),
                          GenerationConfig(max_new_tokens=4))
    router2.run_once()
    # same pressure, but m1 is the only decode-capable replica: blocked
    assert m2.role is ReplicaRole.MIXED and m2.role_flips == 0
    _drive_router(router2, [req2])


# ------------------------------------------------------------ plumbing

def test_parse_fleet_roles():
    assert parse_fleet_roles("prefill, decode,MIXED") == [
        ReplicaRole.PREFILL, ReplicaRole.DECODE, ReplicaRole.MIXED]
    with pytest.raises(ValueError):
        parse_fleet_roles("prefill,bogus")
    with pytest.raises(ValueError):
        parse_fleet_roles(" , ")


def test_router_snapshot_shape(make_core):
    """The snapshot is the contract the router_* Prometheus families
    render from (observability/prometheus.py + check_metrics.py)."""
    h = ReplicaHandle("solo", make_core())
    router = FleetRouter([h])
    req = router.submit(_prompt(31, n=8), GenerationConfig(max_new_tokens=2))
    _drive_router(router, [req])
    snap = router.snapshot()
    assert {"replicas", "dispatched", "affinity_hits",
            "affinity_hit_rate", "handoffs", "requeued",
            "no_replica_rejects", "pending_handoffs", "inflight",
            "prefill_threshold", "shadow"} <= set(snap)
    (rep,) = snap["replicas"]
    assert rep["name"] == "solo" and rep["role"] == "mixed"
    assert rep["health"]["code"] == 0 and rep["health"]["serving"]
    assert snap["dispatched"] == 1 and snap["inflight"] == 0


# ------------------------------------------------- serve.py fleet mode

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(url, path, body):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=300)


def test_fleet_server_routes_and_drains(tmp_path, model):
    """tools/serve.py --fleet_roles prefill,decode: /generate parity
    with the plain engine, router_* families on /metrics, and
    /admin/drain draining EVERY replica while reporting the fleet-wide
    in-flight and queued counts."""
    d = str(tmp_path / "gpt")
    model.save_pretrained(d)
    port = _free_port()
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
         "--model_dir", d, "--port", str(port), "--page_size", "8",
         "--fleet_roles", "prefill,decode"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    url = f"http://127.0.0.1:{port}"
    try:
        for _ in range(120):
            try:
                with urllib.request.urlopen(url + "/health",
                                            timeout=2) as r:
                    if json.load(r)["status"] == "ok":
                        break
            except Exception:
                if proc.poll() is not None:
                    raise RuntimeError(proc.stderr.read()[-1500:])
                time.sleep(1)
        else:
            raise RuntimeError("fleet server never became healthy")

        ids = np.random.RandomState(0).randint(0, 96, (2, 8)) \
            .astype(np.int32)
        g = GenerationConfig(max_new_tokens=6)
        want = PagedGenerationEngine(model, page_size=8).generate(ids, g)
        with _post(url, "/generate", {"ids": ids.tolist(),
                                      "max_new_tokens": 6}) as r:
            got = np.asarray(json.load(r)["tokens"])
        np.testing.assert_array_equal(got, want)

        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            snap = json.load(r)
        assert snap["router"]["dispatched"] >= 2
        names = {rep["name"] for rep in snap["router"]["replicas"]}
        assert names == {"prefill0", "decode1"}
        req = urllib.request.Request(url + "/metrics",
                                     headers={"Accept": "text/plain"})
        with urllib.request.urlopen(req, timeout=30) as r:
            text = r.read().decode()
        assert "# TYPE router_replica_info gauge" in text
        assert 'router_dispatched_total{replica="decode1"}' in text

        with _post(url, "/admin/drain", {}) as r:
            body = json.load(r)
        assert body["status"] == "draining"
        assert isinstance(body["in_flight"], int) and body["in_flight"] >= 0
        assert isinstance(body["queued"], int) and body["queued"] >= 0
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# -------------------------------------------------- weight-only serving

def test_weight_only_dist_attr_placement():
    """Quantizing a TP layer must carry the fp weight's dist_attr onto
    the int8 payload: qweight follows the weight spec, scales shard
    only on the out-dim (the group axis is a reduction), bias keeps its
    own spec.  Unstamped buffers would silently replicate the payload
    per replica in fleet mode and forfeit the fp plan's mp sharding."""
    from paddle_infer_tpu.parallel.mp_layers import (ColumnParallelLinear,
                                                     RowParallelLinear)
    from paddle_infer_tpu.quantization.weight_only import WeightOnlyLinear

    col = ColumnParallelLinear(16, 32, gather_output=False)
    q = WeightOnlyLinear.from_linear(col)
    assert q.qweight.dist_attr == (None, "mp")
    assert q.scale.dist_attr == (None, "mp")
    assert q.bias.dist_attr == ("mp",)
    assert q._out_spec == "mp"       # gather_output=False constraint

    row = RowParallelLinear(32, 16)
    q = WeightOnlyLinear.from_linear(row)
    assert q.qweight.dist_attr == ("mp", None)
    assert q.scale.dist_attr == (None, None)   # never on the group axis
    assert q._out_spec is None

    from paddle_infer_tpu.nn import Linear
    plain = Linear(8, 8)
    q = WeightOnlyLinear.from_linear(plain)
    assert getattr(q.qweight, "dist_attr", None) is None


def test_weight_only_fleet_handoff_parity(model):
    """Regression for serving a weight-only checkpoint across the
    fleet: prefill on one replica, decode on another, stream bitwise
    equal to a single-replica run of the same quantized model."""
    from paddle_infer_tpu.quantization.weight_only import quantize_model

    pit.seed(0)
    qm = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    qm.eval()
    quantize_model(qm, algo="weight_only_int8")

    # two engines only: the decode replica doubles as the single-core
    # reference (its pool drains fully before the handoff run), saving
    # a third executable compile for the quantized model
    cores = [EngineCore(PagedGenerationEngine(qm, page_size=8),
                        **CORE_SHAPE) for _ in range(2)]
    try:
        g = GenerationConfig(max_new_tokens=8, do_sample=True,
                             temperature=0.9, top_p=0.9, seed=3)
        prompt = _prompt(43, n=24)          # 2 prefill chunks

        request_mod._rid_counter = itertools.count(5400)
        req_ref = cores[1].submit(prompt, g)[0]
        _drive(cores[1], [req_ref])
        want = np.asarray(req_ref.result(timeout=60))

        request_mod._rid_counter = itertools.count(5400)   # same rid
        src = ReplicaHandle("p0", cores[0], ReplicaRole.PREFILL)
        dst = ReplicaHandle("d0", cores[1], ReplicaRole.DECODE)
        req = src.core.submit(prompt, g)[0]
        for _ in range(400):
            if ready_for_handoff(src.core, req):
                break
            src.core.run_once()
        else:
            raise AssertionError("request never became handoff-ready")
        assert migrate(req, src, dst)
        _drive(dst.core, [req])
        np.testing.assert_array_equal(
            np.asarray(req.result(timeout=60)), want)
        # the quantized sections survive into each replica's snapshot
        for c in cores:
            wo = c.metrics_snapshot()["weight_only"]
            assert wo["algos"] == ["weight_only_int8"]
            assert wo["layers"] >= 1
    finally:
        for c in cores:
            c.close()
