"""Sharded serving plane (paddle_infer_tpu/serving/sharded): the
mesh-parallel EngineCore and the quantized collective wire format.

Three layers of coverage:

* config — ``ServingMesh`` validation rejects every combination that
  would serve incorrectly (quantized+speculate, quantized+prefix-cache,
  indivisible heads/batch, missing devices) at construction time, and
  ``EngineCore`` re-runs that validation against its own feature flags;
* parity — the acceptance bar: EngineCore token streams under mp=2 and
  mp=2×dp=2 meshes are BITWISE identical to single-device across
  greedy, seeded-sampled, chunked-long-prompt, warm-prefix,
  speculative, and supervisor-replay schedules, with zero new XLA
  compiles once the executables are warm (sharding is placement, not
  shape).  Sampled comparisons pin the request-id counter — per-request
  keys are ``fold_in(PRNGKey(seed), rid)``;
* quantized collectives — blockwise-int8 ``quantized_psum`` error stays
  inside its analytic bound on both the two-stage and the exact-shape
  fallback path, wire-byte accounting matches the ring model, and a
  quantized serving run reports bytes saved through the ledger, the
  steplog, and the Prometheus exposition.
"""
import itertools

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine,
                                                   serving_param_spec)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.parallel import collective, topology
from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                      FaultPlane, FaultSpec, RequestState,
                                      ServingMesh, ShardedConfigError,
                                      build_sharded_engine,
                                      validate_serving_config)
from paddle_infer_tpu.serving import request as request_mod


@pytest.fixture(scope="module", autouse=True)
def _clean_topology():
    """Mesh AND quantized-allreduce mode are trace-time globals; leak
    either and every later module's executables change."""
    prev_mesh = topology.get_current_mesh()
    prev_q = topology.get_quantized_allreduce()
    topology.set_current_mesh(None)
    topology.set_quantized_allreduce(None)
    yield
    topology.set_current_mesh(prev_mesh)
    topology.set_quantized_allreduce(prev_q)


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


@pytest.fixture(scope="module")
def engine_single(model):
    return build_sharded_engine(model, ServingMesh(), page_size=8)


@pytest.fixture(scope="module")
def engine_mp2(model):
    return build_sharded_engine(model, ServingMesh(mp=2), page_size=8)


@pytest.fixture(scope="module")
def engine_mp2_dp2(model):
    return build_sharded_engine(model, ServingMesh(mp=2, dp_replicas=2),
                                page_size=8)


@pytest.fixture(scope="module")
def engine_quant(model):
    return build_sharded_engine(
        model, ServingMesh(mp=2, quantized_allreduce="int8"), page_size=8)


# One (max_batch, max_model_len, token_budget) for every core so the
# serving executables compile once per engine; max_batch=4 divides the
# dp=2 replica groups.
CORE_SHAPE = dict(max_batch=4, max_model_len=48, token_budget=16,
                  prefill_chunk=16)

MESH_CFGS = {"single": ServingMesh(), "mp2": ServingMesh(mp=2),
             "mp2dp2": ServingMesh(mp=2, dp_replicas=2)}


def _drive(core, reqs, max_iters=400):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(
        0, 96, (n,)).astype(np.int32)


def _serve(engine, cfg, prompts, gens, rid_base, **kw):
    """One batch through a fresh core with the rid counter pinned (so
    sampled rows fold_in identical rids across runs)."""
    for k, v in CORE_SHAPE.items():
        kw.setdefault(k, v)
    request_mod._rid_counter = itertools.count(rid_base)
    core = EngineCore(engine, serving_mesh=(
        cfg if cfg is not None and cfg.n_devices > 1 else None), **kw)
    try:
        reqs = [core.submit(p, g)[0] for p, g in zip(prompts, gens)]
        _drive(core, reqs)
        assert all(r.state is RequestState.DONE for r in reqs)
        return [np.asarray(r.padded_result()) for r in reqs]
    finally:
        core.close()


# ------------------------------------------------------------ config


class TestServingMeshConfig:
    def test_describe_and_device_count(self):
        cfg = ServingMesh(mp=2, dp_replicas=2,
                          quantized_allreduce="int8")
        assert cfg.n_devices == 4
        assert "mp=2" in cfg.describe() and "dp=2" in cfg.describe()

    @pytest.mark.parametrize("kw,flags", [
        (dict(mp=0), {}),
        (dict(mp=2, quantized_allreduce="fp8"), {}),
        (dict(mp=1, quantized_allreduce="int8"), {}),
        (dict(mp=2, quantized_allreduce="int8"), dict(speculate=True)),
        (dict(mp=2, quantized_allreduce="int8"),
         dict(enable_prefix_cache=True)),
        (dict(mp=2), dict(num_heads=3)),
        (dict(dp_replicas=2), dict(max_batch=3)),
        (dict(mp=4, dp_replicas=4), dict(available_devices=8)),
    ])
    def test_invalid_combos_rejected(self, kw, flags):
        with pytest.raises(ShardedConfigError):
            validate_serving_config(ServingMesh(**kw), **flags)

    def test_valid_config_is_silent(self):
        validate_serving_config(
            ServingMesh(mp=2, dp_replicas=2), max_batch=4, num_heads=4,
            available_devices=8)

    def test_single_device_build_has_no_mesh(self, engine_single):
        assert engine_single._mesh is None
        assert engine_single.shard_report() is None

    def test_core_rejects_mesh_config_on_meshless_engine(
            self, engine_single):
        with pytest.raises(ShardedConfigError):
            EngineCore(engine_single, serving_mesh=ServingMesh(mp=2),
                       **CORE_SHAPE)

    def test_core_rejects_quantized_mismatch(self, engine_mp2):
        with pytest.raises(ShardedConfigError):
            EngineCore(engine_mp2,
                       serving_mesh=ServingMesh(
                           mp=2, quantized_allreduce="int8"),
                       **CORE_SHAPE)

    def test_core_rejects_quant_engine_with_speculation(
            self, engine_quant):
        with pytest.raises(ShardedConfigError):
            EngineCore(engine_quant, speculate=True, **CORE_SHAPE)
        with pytest.raises(ShardedConfigError):
            EngineCore(engine_quant, enable_prefix_cache=True,
                       **CORE_SHAPE)


# ------------------------------------------------------------ parity


class TestMeshParity:
    @pytest.mark.parametrize("deg", ["mp2", "mp2dp2"])
    def test_greedy_streams_bitwise_equal(self, request, engine_single,
                                          deg):
        eng = request.getfixturevalue(
            "engine_mp2" if deg == "mp2" else "engine_mp2_dp2")
        prompts = [_prompt(1, 11), _prompt(2, 21), _prompt(3, 5)]
        gens = [GenerationConfig(max_new_tokens=8),
                GenerationConfig(max_new_tokens=6),
                GenerationConfig(max_new_tokens=7)]
        want = _serve(engine_single, None, prompts, gens, rid_base=7000)
        got = _serve(eng, MESH_CFGS[deg], prompts, gens, rid_base=7000)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)

    def test_kv_pool_head_sharded(self, engine_mp2):
        # the pool exists after the parity drives above
        assert engine_mp2._k_pages is not None
        assert engine_mp2._k_pages[0].sharding.spec[1] == "mp"

    @pytest.mark.parametrize("deg", ["mp2", "mp2dp2"])
    def test_sampled_streams_bitwise_equal(self, request, engine_single,
                                           deg):
        eng = request.getfixturevalue(
            "engine_mp2" if deg == "mp2" else "engine_mp2_dp2")
        prompts = [_prompt(4, 11), _prompt(5, 21), _prompt(6, 5)]
        gens = [GenerationConfig(max_new_tokens=8, do_sample=True,
                                 temperature=0.8, top_k=12, top_p=0.9,
                                 seed=7),
                GenerationConfig(max_new_tokens=6, do_sample=True,
                                 temperature=1.2, seed=11),
                GenerationConfig(max_new_tokens=7, do_sample=True,
                                 top_k=5, seed=3)]
        want = _serve(engine_single, None, prompts, gens, rid_base=7100)
        got = _serve(eng, MESH_CFGS[deg], prompts, gens, rid_base=7100)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)

    def test_chunked_long_prompt_parity_mp2(self, engine_single,
                                            engine_mp2):
        # longer than prefill_chunk=16: crosses several mixed steps
        ids = _prompt(7, 40)
        g = GenerationConfig(max_new_tokens=8)
        (want,) = _serve(engine_single, None, [ids], [g], rid_base=7200)
        (got,) = _serve(engine_mp2, MESH_CFGS["mp2"], [ids], [g],
                        rid_base=7200)
        np.testing.assert_array_equal(got, want)

    def test_warm_prefix_hits_parity_mp2(self, engine_single,
                                         engine_mp2):
        base = _prompt(8, 24)
        tail = np.concatenate([base[:16], _prompt(9, 6)])
        g = GenerationConfig(max_new_tokens=6)

        def run(engine, cfg):
            request_mod._rid_counter = itertools.count(7300)
            core = EngineCore(
                engine, enable_prefix_cache=True,
                serving_mesh=(cfg if cfg is not None
                              and cfg.n_devices > 1 else None),
                **CORE_SHAPE)
            try:
                outs = []
                for ids in (base, base, tail):  # cold, full, partial
                    (r,) = core.submit(ids, g)
                    _drive(core, [r])
                    outs.append(np.asarray(r.padded_result()))
                stats = core.prefix_cache.stats_snapshot()
                assert stats["hits"] >= 2, "warm admissions never hit"
                return outs
            finally:
                core.close()

        want = run(engine_single, None)
        got = run(engine_mp2, MESH_CFGS["mp2"])
        for w, g_ in zip(want, got):
            np.testing.assert_array_equal(g_, w)

    def test_speculative_parity_mp2(self, engine_single, engine_mp2):
        """Speculation on the sharded engine: verify rows ride the same
        sharded mixed step, and greedy streams stay bitwise equal to
        the PLAIN single-device run — speculation and sharding are both
        throughput knobs, never correctness knobs."""
        prompts = [_prompt(10, 12), _prompt(11, 9)]
        gens = [GenerationConfig(max_new_tokens=10),
                GenerationConfig(max_new_tokens=8)]
        want = _serve(engine_single, None, prompts, gens, rid_base=7400)
        got = _serve(engine_mp2, MESH_CFGS["mp2"], prompts, gens,
                     rid_base=7400, speculate=True, num_draft_tokens=3)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)

    def test_supervisor_replay_parity_mp2(self, engine_single,
                                          engine_mp2):
        """A mid-decode crash that loses the (head-sharded) KV pools:
        the supervisor replays the in-flight row and the recovered
        stream equals the uninterrupted single-device one."""
        ids = _prompt(12, 10)
        g = GenerationConfig(max_new_tokens=12)
        (want,) = _serve(engine_single, None, [ids], [g], rid_base=7500)

        request_mod._rid_counter = itertools.count(7500)
        plane = FaultPlane([FaultSpec("decode.step", at=4, lose_kv=True)])
        core = EngineCore(engine_mp2, fault_plane=plane,
                          serving_mesh=MESH_CFGS["mp2"], **CORE_SHAPE)
        sup = EngineSupervisor(core)
        try:
            (req,) = core.submit(ids, g)
            for _ in range(400):
                if req.done:
                    break
                sup.run_once()
            assert req.state is RequestState.DONE
            assert req.retries == 1
            np.testing.assert_array_equal(req.padded_result(), want)
        finally:
            sup.close()

    def test_zero_compiles_once_warm_mp2(self, engine_mp2):
        """Batch composition is data on the sharded executable too: a
        second, differently-composed batch over warm shapes must not
        compile anything."""
        from paddle_infer_tpu.observability import get_compile_log

        gens = [GenerationConfig(max_new_tokens=6),
                GenerationConfig(max_new_tokens=7)]
        _serve(engine_mp2, MESH_CFGS["mp2"],
               [_prompt(13, 8), _prompt(14, 8)], gens, rid_base=7600)
        before = get_compile_log().count()
        _serve(engine_mp2, MESH_CFGS["mp2"],
               [_prompt(15, 8), _prompt(16, 8)], gens, rid_base=7700)
        assert get_compile_log().count() == before


# ------------------------------------------------- quantized collectives


def _psum_via_shard_map(parts, block=256):
    """Run quantized_psum over an mp=2 mesh; parts is [2, n] with one
    addend per rank."""
    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_infer_tpu.parallel.topology import shard_map_norep

    mesh = ServingMesh(mp=2).build(jax.devices()[:2])
    return np.asarray(shard_map_norep(
        lambda x: collective.quantized_psum(x[0], "mp", 2, block), mesh,
        in_specs=(P("mp"),), out_specs=P())(parts))


class TestQuantizedCollectives:
    @pytest.mark.parametrize("n", [2048,   # nb=8 % 2 == 0: two-stage
                                   700])   # nb=3: exact-shape fallback
    def test_psum_error_within_analytic_bound(self, n):
        parts = np.random.RandomState(n).randn(2, n).astype(np.float32)
        got = _psum_via_shard_map(parts)
        err = float(np.max(np.abs(got - parts.sum(axis=0))))
        bound = collective.quantization_error_bound(list(parts))
        assert err <= bound
        # and the bound is meaningful, not vacuous
        assert bound < 0.15

    def test_wire_bytes_ring_model(self):
        # 2048 f32 over 2 ranks: nb=8 blocks; ring factor 2(r-1)/r = 1
        q, fp = collective.quantized_wire_bytes(2048, 2)
        assert fp == pytest.approx(2048 * 4)
        assert q == pytest.approx(8 * 256 + 8 * 4)
        assert q < fp / 3

    def test_quantized_serving_reports_bytes_saved(self, engine_quant):
        collective.LEDGER.reset()
        gens = [GenerationConfig(max_new_tokens=6),
                GenerationConfig(max_new_tokens=6)]
        cfg = ServingMesh(mp=2, quantized_allreduce="int8")
        request_mod._rid_counter = itertools.count(7800)
        core = EngineCore(engine_quant, serving_mesh=cfg, **CORE_SHAPE)
        try:
            reqs = [core.submit(_prompt(s, 8), g)[0]
                    for s, g in zip((17, 18), gens)]
            _drive(core, reqs)
            steps = core.steplog.summary()
            snap = core.metrics_snapshot()
        finally:
            core.close()
        assert steps["ici_bytes_saved_total"] > 0
        assert steps["ici_bytes_est_total"] > 0
        led = collective.LEDGER.snapshot()
        assert led["bytes_saved_total"] > 0
        assert led["by_op_dtype"]["mp_allreduce"]["int8"] > 0
        sh = snap["sharding"]
        assert sh["quantized_allreduce"] == "int8"
        assert sh["mesh_axes"] == {"mp": 2}
        assert sh["collectives"]["bytes_saved_total"] > 0

    def test_exact_serving_reports_no_savings(self, engine_mp2):
        collective.LEDGER.reset()
        (_,) = _serve(engine_mp2, MESH_CFGS["mp2"], [_prompt(19, 8)],
                      [GenerationConfig(max_new_tokens=5)],
                      rid_base=7900)
        led = collective.LEDGER.snapshot()
        assert led["bytes_saved_total"] == 0
        assert led["bytes_total"] > 0


# --------------------------------------------- shard report + exposition


class TestShardReportAndMetrics:
    def test_shard_report_contents(self, engine_mp2):
        rep = engine_mp2.shard_report()
        assert rep["mesh_axes"] == {"mp": 2}
        assert rep["devices"] == 2
        assert rep["sharded_params"] > 0
        assert rep["params_total"] >= rep["sharded_params"]
        assert rep["quantized_allreduce"] == ""

    def test_param_fallback_logged_once_and_listed(self, caplog):
        mesh = ServingMesh(mp=2).build()
        arr = np.zeros((7, 6), np.float32)   # mp=2 doesn't divide 7
        fallback = []
        with caplog.at_level(
                "WARNING", logger="paddle_infer_tpu.inference.generation"):
            serving_param_spec(arr, ("mp", None), mesh,
                               name="odd.weight", fallback=fallback)
            serving_param_spec(arr, ("mp", None), mesh,
                               name="odd.weight", fallback=fallback)
        assert len(fallback) == 2            # every fallback is counted
        warnings = [r for r in caplog.records
                    if "odd.weight" in r.getMessage()]
        assert len(warnings) == 1            # ...but logged once

    def test_prometheus_renders_collective_families(self, engine_quant):
        from paddle_infer_tpu.observability import get_compile_log
        from paddle_infer_tpu.observability.prometheus import (
            render_prometheus, validate_exposition)

        cfg = ServingMesh(mp=2, quantized_allreduce="int8")
        request_mod._rid_counter = itertools.count(8000)
        core = EngineCore(engine_quant, serving_mesh=cfg, **CORE_SHAPE)
        try:
            (r,) = core.submit(_prompt(20, 8),
                               GenerationConfig(max_new_tokens=4))
            _drive(core, [r])
            text = render_prometheus(core.metrics_snapshot(),
                                     get_compile_log().summary())
        finally:
            core.close()
        assert validate_exposition(text) == []
        assert ('serving_mesh_info{devices="2",dp="1",ep="1",mp="2",'
                'quantized_allreduce="int8"}') in text
        assert "serving_shard_sharded_params" in text
        assert 'collective_bytes_total{dtype="int8",op="mp_allreduce"}' \
            in text
        assert "collective_bytes_saved_total" in text


def test_adopt_placement_frees_the_single_device_copies():
    """After ``adopt_placement`` the model's own parameters ARE the placed
    shards (nothing keeps the default device's whole copy alive), the
    placement cache still hits, and the streams do not change."""
    import jax

    # a model of its own: adopting rebinds its parameters, and the
    # module's shared model feeds single-device engines too
    pit.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    g = GenerationConfig(max_new_tokens=5)
    ids = _prompt(3, 9)[None]
    eng = build_sharded_engine(model, ServingMesh(mp=2), page_size=8)
    before = np.asarray(eng.generate(ids, g))
    assert all(len(p._data.sharding.device_set) == 1
               for p in model.parameters())
    eng.adopt_placement()
    placed = eng._params
    assert eng.refresh_params() is not None
    assert all(eng._params[n] is placed[n] for n in placed)   # cache hit
    sharded = [n for n, p in model.named_parameters()
               if len(p._data.sharding.device_set) == 2]
    assert len(sharded) == len(list(model.named_parameters()))
    assert eng.shard_report()["param_devices"] == sorted(
        d.id for d in jax.devices()[:2])
    np.testing.assert_array_equal(np.asarray(eng.generate(ids, g)), before)
