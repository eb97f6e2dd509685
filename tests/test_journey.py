"""Fleet-wide request journeys (paddle_infer_tpu/observability/
journey.py): cross-replica trace stitching, latency attribution and
per-tenant SLO accounting.

The load-bearing properties:

* a request that prefills on one replica, hands off to another and is
  parked/resumed mid-decode there is ONE journey — both replica lanes
  stitched, hop edges recorded, and the e2e wall decomposed into
  non-overlapping attribution buckets that sum back to the wall within
  3% with coverage >= 0.97;
* the journey plane is host-side data-only: the streamed tokens stay
  bitwise identical to a single-core run of the same rid, and the
  measured run compiles nothing after warmup;
* ``tenant=`` is an accounting label, never a scheduling input: each
  tenant's Prometheus series carry exactly its own label and the
  exposition validates (including the journey_id exemplars).
"""
import itertools
import json

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.observability.compilelog import get_compile_log
from paddle_infer_tpu.observability.journey import (BUCKETS,
                                                    JourneyStore,
                                                    attribute)
from paddle_infer_tpu.observability.prometheus import (
    render_prometheus, validate_exposition)
from paddle_infer_tpu.serving import (EngineCore, ReplicaHandle,
                                      ReplicaRole)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.fleet import migrate, ready_for_handoff


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """Journey parity compares tokens across replicas and against a
    single core — bitwise only when everything runs unsharded."""
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


# replicas never share an engine (pools and compile caches are strictly
# per-engine), but they do share the model; module-scoped so the
# serving executables compile once across the parametrized runs
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


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


# ------------------------------------------------------- attribute unit

def test_attribute_partitions_exactly():
    """The sweep partitions [begin, finish] exactly: overlaps resolve
    by priority, holes land in ``other``, and the bucket seconds sum to
    the wall with no tolerance at all."""
    intervals = [
        (0.0, 2.0, "queue_wait", 4),
        (1.5, 4.0, "prefill_compute", 3),    # loses the 1.5..2.0 overlap
        (4.5, 6.0, "decode_compute", 3),
        (5.0, 5.5, "parked", 5),             # wins over decode
    ]
    out = attribute(intervals, 0.0, 7.0)
    assert set(out) == set(BUCKETS)
    assert abs(sum(out.values()) - 7.0) < 1e-12
    assert out["queue_wait"] == pytest.approx(2.0)
    assert out["prefill_compute"] == pytest.approx(2.0)
    assert out["decode_compute"] == pytest.approx(1.0)
    assert out["parked"] == pytest.approx(0.5)
    assert out["other"] == pytest.approx(1.5)   # 4.0..4.5 + 6.0..7.0


def test_attribute_clips_to_window():
    out = attribute([(-5.0, 20.0, "decode_compute", 3)], 1.0, 3.0)
    assert out["decode_compute"] == pytest.approx(2.0)
    assert sum(out.values()) == pytest.approx(2.0)


# ------------------------------------- stitching across handoff + park

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_journey_one_across_handoff_park_resume(make_core, sampled):
    """Prefill on p0, migrate to d0, park into the host tier mid-decode
    on d0, resume, finish: ONE journey spanning both replicas, with the
    handoff and parked intervals attributed, the bucket decomposition
    summing to the e2e wall, coverage >= 0.97 — and the stream bitwise
    identical to a single-core run of the same rid, with zero
    post-warmup decode compiles."""
    g = (GenerationConfig(max_new_tokens=20, do_sample=True,
                          temperature=0.9, top_p=0.9, seed=3)
         if sampled else GenerationConfig(max_new_tokens=20))
    prompt = _prompt(41, n=24)              # 2 prefill chunks

    # reference: the same rid end-to-end on a single core
    request_mod._rid_counter = itertools.count(7100)
    ref = make_core()
    req_ref = ref.submit(prompt, g)[0]
    _drive(ref, [req_ref])
    want = np.asarray(req_ref.result(timeout=60))

    # fleet: per-replica tracers (EngineCore default), ONE shared store
    store = JourneyStore()
    src = ReplicaHandle("p0", make_core(journeys=store,
                                        replica_name="p0"),
                        ReplicaRole.PREFILL)
    dst = ReplicaHandle("d0", make_core(journeys=store,
                                        replica_name="d0",
                                        kv_host_pages=64),
                        ReplicaRole.DECODE)

    # warm both cores' executables so the measured run compiles nothing
    warm = [src.core.submit(_prompt(7, n=24),
                            GenerationConfig(max_new_tokens=4))[0],
            dst.core.submit(_prompt(8, n=24),
                            GenerationConfig(max_new_tokens=4))[0]]
    for _ in range(200):
        if all(r.done for r in warm):
            break
        src.core.run_once()
        dst.core.run_once()
    assert all(r.done for r in warm)
    warm_compiles = get_compile_log().summary()[
        "post_warmup_decode_compiles"]

    request_mod._rid_counter = itertools.count(7100)   # same rid as ref
    req = src.core.submit(prompt, g, tenant="gold")[0]
    rid = req.rid
    for _ in range(400):
        if ready_for_handoff(src.core, req):
            break
        src.core.run_once()
    else:
        raise AssertionError("request never became handoff-ready")
    assert migrate(req, src, dst)

    dst.core.run_once()                      # decode a little on d0...
    assert not req.done
    assert dst.core.park_for_pressure()      # ...then preempt to host
    _drive(dst.core, [req])                  # auto-resume + finish

    got = np.asarray(req.result(timeout=60))
    np.testing.assert_array_equal(got, want)
    assert get_compile_log().summary()[
        "post_warmup_decode_compiles"] == warm_compiles

    # ONE journey (plus the two warmups), spanning both replicas
    s = store.summary()
    assert s["count"] == 3 and s["live"] == 0
    assert s["hops_total"] >= 1
    j = store.get(f"j{rid}")
    assert j is not None and j["request_id"] == rid
    assert j["tenant"] == "gold"
    assert j["origin"] == "p0"
    assert set(j["replicas"]) == {"p0", "d0"}
    assert j["hops"] >= 1
    assert j["hop_events"]
    assert all(h["kind"] == "handoff" for h in j["hop_events"])
    assert {(h["src"], h["dst"]) for h in j["hop_events"]} == {
        ("p0", "d0")}

    # attribution: buckets partition the e2e wall
    e2e = j["e2e_s"]
    assert e2e > 0
    total = sum(j["buckets"].values())
    assert abs(total - e2e) <= 0.03 * e2e
    assert j["coverage"] >= 0.97
    assert j["buckets"]["handoff"] > 0.0
    assert j["buckets"]["parked"] > 0.0
    assert j["buckets"]["prefill_compute"] > 0.0
    assert j["buckets"]["decode_compute"] > 0.0

    # chrome export: one pid lane per replica plus the journey lane
    ch = store.to_chrome(f"j{rid}")
    assert ch is not None
    lanes = [e["args"]["name"] for e in ch["traceEvents"]
             if e.get("ph") == "M"]
    assert "replica p0" in lanes and "replica d0" in lanes
    assert "journey" in lanes
    assert any(e.get("ph") == "X"
               and str(e.get("name", "")).startswith("hop p0->d0")
               for e in ch["traceEvents"])
    for e in ch["traceEvents"]:
        if e.get("ph") == "X":
            assert e["dur"] >= 0.0
    json.dumps(ch)                           # must be serializable

    # per-tenant SLO accounting landed on the finishing core
    snap = dst.core.metrics_snapshot()
    tn = snap.get("tenants") or {}
    assert "gold" in tn
    assert tn["gold"]["requests"] == 1
    assert tn["gold"]["parked_seconds"] > 0.0
    assert tn["gold"]["attainment"] == 1.0   # no deadline -> attained
    text = render_prometheus(snap)
    assert validate_exposition(text) == []
    assert 'tenant_requests_total{tenant="gold"} 1' in text
    assert 'tenant_parked_seconds_total{tenant="gold"}' in text


# ---------------------------------------------------- tenant isolation

def test_tenant_label_isolation(make_core):
    """Tenants are accounting labels: each tenant's series carry
    exactly its own label, untenanted traffic lands under ``default``,
    and every exemplar journey_id maps back to a journey of that
    tenant."""
    store = JourneyStore()
    core = make_core(journeys=store, replica_name="c0")
    g = GenerationConfig(max_new_tokens=6)
    reqs = [core.submit(_prompt(11, n=8), g, tenant="gold")[0],
            core.submit(_prompt(12, n=8), g, tenant="free")[0],
            core.submit(_prompt(13, n=8), g)[0]]
    _drive(core, reqs)

    snap = core.metrics_snapshot()
    tn = snap.get("tenants") or {}
    assert set(tn) == {"gold", "free", "default"}
    for name in tn:
        assert tn[name]["requests"] == 1
        assert tn[name]["tokens"] > 0
        assert tn[name]["parked_seconds"] == 0.0

    text = render_prometheus(snap)
    assert validate_exposition(text) == []
    for name in ("gold", "free", "default"):
        assert f'tenant_requests_total{{tenant="{name}"}} 1' in text

    # exemplars are per-tenant, never crossed
    for name, t in tn.items():
        assert t["exemplars"], f"tenant {name} has no exemplar"
        for ex in t["exemplars"].values():
            j = store.get(ex["journey_id"])
            assert j is not None
            assert (j["tenant"] or "default") == name

    # journey summary: three single-replica journeys, no hops
    s = store.summary()
    assert s["count"] == 3 and s["hops_total"] == 0
    assert s["attribution_coverage"] >= 0.97
