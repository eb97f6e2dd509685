"""MoE expert-parallel serving plane (paddle_infer_tpu/serving/moe).

Coverage mirrors the sharded-serving suite's three layers, plus the
routing-determinism bar MoE adds:

* gate determinism — dispatch masks are a pure function of the logits:
  identical across reruns and eager vs jit (argmax ties routed on raw
  logits, integer cumsum positions);
* ops — the static-capacity serving ops are bitwise the training fused
  path at the default capacity, surface dropped tokens deterministically
  when capacity pinches, and the global_scatter/global_gather all-to-all
  formulation round-trips bitwise against the einsum dispatch over a
  2-device ep mesh;
* config — every unservable combination (ep over a dense model, ep not
  dividing the expert count, int8-activation experts under speculation
  without an accept margin, mixed expert counts/algos) is rejected at
  construction;
* parity — the acceptance bar: EngineCore token streams over a MoE
  model are BITWISE identical to the unconverted engine, to ep=1 vs
  ep=2, and across supervisor replay, with zero post-warmup compiles
  through a long mixed decode/prefill/speculative fuzz — routing
  changes data, never shapes.
"""
import itertools

import jax
import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.core.dispatch import dispatch as D
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTMoEForCausalLM, MoEConfig
from paddle_infer_tpu.parallel import topology
from paddle_infer_tpu.parallel.moe import MoELayer, _capacity, gshard_gate
from paddle_infer_tpu.quantization.moe import (Int8MoELayer,
                                               WeightOnlyMoELayer)
from paddle_infer_tpu.quantization.slim import _swap
from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                      FaultPlane, FaultSpec, RequestState,
                                      ServingMesh, ShardedConfigError,
                                      build_sharded_engine,
                                      moe_serving_info,
                                      prepare_moe_serving,
                                      serving_capacity,
                                      validate_moe_quant_combo,
                                      validate_serving_config)
from paddle_infer_tpu.serving import request as request_mod
from paddle_infer_tpu.serving.moe.layer import ServingMoELayer


@pytest.fixture(scope="module", autouse=True)
def _clean_topology():
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


MOE_DIMS = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=64,
                max_position_embeddings=64, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)


def _fresh_model():
    pit.seed(0)
    m = GPTMoEForCausalLM(MoEConfig(num_experts=4, **MOE_DIMS))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _fresh_model()


@pytest.fixture(scope="module")
def engine_single(model):
    return build_sharded_engine(model, ServingMesh(), page_size=8)


@pytest.fixture(scope="module")
def engine_ep2(model):
    return build_sharded_engine(model, ServingMesh(ep=2), page_size=8)


CORE_SHAPE = dict(max_batch=4, max_model_len=48, token_budget=16,
                  prefill_chunk=16)


def _drive(core, reqs, max_iters=600):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(
        0, 96, (n,)).astype(np.int32)


def _serve(engine, cfg, prompts, gens, rid_base, **kw):
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


# -------------------------------------------------- gate determinism


class TestGateDeterminism:
    def _tie_logits(self):
        """Logits engineered to stress tie handling: duplicated rows,
        exactly-equal top pairs, and tails that underflow softmax."""
        rng = np.random.RandomState(3)
        lg = rng.randn(24, 4).astype(np.float32)
        lg[3] = lg[7]                       # duplicated preference rows
        lg[5, 0] = lg[5, 1]                 # exact top-2 tie
        lg[9] = np.array([60.0, -60.0, -60.0, -60.0], np.float32)
        return jax.numpy.asarray(lg)

    def test_dispatch_mask_identical_across_reruns_and_jit(self):
        lg = self._tie_logits()
        runs = [gshard_gate(lg, 8) for _ in range(3)]
        jit_run = jax.jit(lambda a: gshard_gate(a, 8))(lg)
        c0, d0, a0 = runs[0]
        for c, d, a in runs[1:] + [jit_run]:
            np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
            np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
            assert float(a) == float(a0)

    def test_serving_op_dispatch_deterministic(self):
        """The full serving op (gate + dispatch + FFN + combine) is a
        pure function of its operands — identical outputs AND stats
        across reruns (the replay-safety bar for dropped tokens)."""
        pit.seed(0)
        lay = MoELayer(16, 32, 4)
        rng = np.random.RandomState(0)
        x = jax.numpy.asarray(rng.randn(1, 12, 16).astype(np.float32))
        v = jax.numpy.ones((12,), bool)
        outs = [D("serving_moe", x, lay.gate_weight, lay.w1, lay.b1,
                  lay.w2, lay.b2, v, gate="gshard", top_k=2, capacity=4)
                for _ in range(3)]
        o0, r0, dr0, a0 = (np.asarray(t) for t in outs[0])
        for out in outs[1:]:
            o, r, dr, a = (np.asarray(t) for t in out)
            np.testing.assert_array_equal(o, o0)
            np.testing.assert_array_equal(r, r0)
            assert int(dr) == int(dr0)


# ------------------------------------------------------- serving ops


class TestServingOps:
    def _layer_and_x(self, n=16, d=16, f=32, e=4, seed=0):
        pit.seed(0)
        lay = MoELayer(d, f, e)
        rng = np.random.RandomState(seed)
        return lay, jax.numpy.asarray(
            rng.randn(1, n, d).astype(np.float32))

    def test_default_capacity_matches_training_fused_bitwise(self):
        lay, x = self._layer_and_x()
        n = x.shape[0] * x.shape[1]
        cap = _capacity(n, lay.num_experts, lay.capacity_factor,
                        lay.top_k)
        want, want_aux = D("fused_moe", x, lay.gate_weight, lay.w1,
                           lay.b1, lay.w2, lay.b2, gate="gshard",
                           top_k=2, capacity_factor=2.0)
        got, routed, dropped, aux = D(
            "serving_moe", x, lay.gate_weight, lay.w1, lay.b1, lay.w2,
            lay.b2, jax.numpy.ones((n,), bool), gate="gshard", top_k=2,
            capacity=cap)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.numpy()))
        assert float(aux) == float(want_aux.numpy())
        assert int(np.asarray(routed).sum()) + int(dropped) == 2 * n

    def test_dropped_tokens_surfaced_not_silent(self):
        lay, x = self._layer_and_x()
        n = x.shape[0] * x.shape[1]
        # capacity 4 over 16 tokens × top-2: at most 4*4=16 of 32
        # assignments fit — overflow must land in `dropped`
        _, routed, dropped, _ = D(
            "serving_moe", x, lay.gate_weight, lay.w1, lay.b1, lay.w2,
            lay.b2, jax.numpy.ones((n,), bool), gate="gshard", top_k=2,
            capacity=4)
        routed = np.asarray(routed)
        assert int(dropped) > 0
        assert routed.max() <= 4
        assert int(routed.sum()) + int(dropped) == 2 * n

    def test_stats_masked_to_valid_slots(self):
        """Pad slots compete for capacity exactly as in the unconverted
        model but never count: the output is unchanged, the stats only
        see valid rows."""
        lay, x = self._layer_and_x()
        n = x.shape[0] * x.shape[1]
        v_all = jax.numpy.ones((n,), bool)
        v_half = jax.numpy.asarray(np.arange(n) < n // 2)
        out_a, routed_a, dropped_a, _ = D(
            "serving_moe", x, lay.gate_weight, lay.w1, lay.b1, lay.w2,
            lay.b2, v_all, gate="gshard", top_k=2, capacity=32)
        out_h, routed_h, dropped_h, _ = D(
            "serving_moe", x, lay.gate_weight, lay.w1, lay.b1, lay.w2,
            lay.b2, v_half, gate="gshard", top_k=2, capacity=32)
        np.testing.assert_array_equal(np.asarray(out_h),
                                      np.asarray(out_a))
        assert int(np.asarray(routed_h).sum()) \
            + int(dropped_h) == 2 * (n // 2)
        assert int(np.asarray(routed_h).sum()) \
            < int(np.asarray(routed_a).sum())

    def test_converted_layer_matches_bare_layer(self):
        pit.seed(0)
        lay = MoELayer(16, 32, 4)
        serving = ServingMoELayer(lay, capacity=32)
        from paddle_infer_tpu.core.tensor import Tensor
        x = Tensor(np.random.RandomState(1).randn(
            2, 8, 16).astype(np.float32))
        want = lay(x).numpy()
        got = serving(x).numpy()
        np.testing.assert_array_equal(got, want)


# ------------------------------------- all-to-all vs einsum dispatch


class TestGlobalScatterGatherParity:
    def test_round_trip_bitwise_on_ep2_mesh(self):
        """The explicit all-to-all formulation (global_scatter/
        global_gather, and the raw shard_map lax.all_to_all it stands
        for) moves the dispatch buffer WITHOUT changing it: bitwise
        equal to the einsum dispatch/combine path over a real 2-device
        ep mesh."""
        from jax.sharding import PartitionSpec as P

        from paddle_infer_tpu.core.tensor import Tensor
        from paddle_infer_tpu.parallel.topology import shard_map_norep
        from paddle_infer_tpu.serving.moe.ops import _serving_dispatch

        pit.seed(0)
        lay = MoELayer(16, 32, 4)
        rng = np.random.RandomState(2)
        x = jax.numpy.asarray(rng.randn(1, 16, 16).astype(np.float32))
        combine, expert_in, _, _, _ = _serving_dispatch(
            x, jax.numpy.asarray(lay.gate_weight._data),
            jax.numpy.ones((16,), bool), "gshard", 2, 8)

        mesh = topology.create_hybrid_mesh(ep=2,
                                           devices=jax.devices()[:2])
        prev = topology.get_current_mesh()
        topology.set_current_mesh(mesh)
        try:
            scattered = D("global_scatter", Tensor(np.asarray(expert_in)))
            gathered = D("global_gather", scattered)
            np.testing.assert_array_equal(gathered.numpy(),
                                          np.asarray(expert_in))
        finally:
            topology.set_current_mesh(prev)

        # raw shard_map leg: token-sharded in, expert-sharded out via
        # one lax.all_to_all — still the identity on the full buffer
        a2a = shard_map_norep(
            lambda b: jax.lax.all_to_all(b, "ep", split_axis=0,
                                         concat_axis=1, tiled=True),
            mesh, in_specs=(P(None, "ep", None),),
            out_specs=P("ep", None, None))
        np.testing.assert_array_equal(np.asarray(a2a(expert_in)),
                                      np.asarray(expert_in))

        # and the einsum combine over the round-tripped buffer is the
        # einsum combine over the original — dispatch/combine and the
        # all-to-all formulation are the same function
        from paddle_infer_tpu.parallel.moe import _combine_out
        want = _combine_out(x, combine, expert_in)
        got = _combine_out(x, combine,
                           jax.numpy.asarray(gathered.numpy()))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ config


class TestMoEServingConfig:
    def test_mesh_describe_and_device_count(self):
        cfg = ServingMesh(mp=2, ep=2)
        assert cfg.n_devices == 4
        assert "ep=2" in cfg.describe()
        assert "ep" not in ServingMesh(mp=2).describe()

    @pytest.mark.parametrize("kw,flags", [
        (dict(ep=0), {}),
        (dict(ep=2), {}),                        # dense model
        (dict(ep=2), dict(num_experts=3)),       # ep does not divide E
        (dict(ep=4), dict(num_experts=4, available_devices=2)),
        (dict(ep=2), dict(num_experts=4, moe_quant="int8_act",
                          speculate=True)),
        (dict(), dict(num_experts=4, moe_quant="fp4")),
    ])
    def test_invalid_combos_rejected(self, kw, flags):
        with pytest.raises(ShardedConfigError):
            validate_serving_config(ServingMesh(**kw), **flags)

    def test_valid_combos_silent(self):
        validate_serving_config(ServingMesh(ep=2), num_experts=4,
                                available_devices=8)
        validate_serving_config(
            ServingMesh(ep=2), num_experts=4, available_devices=8,
            moe_quant="int8_act", speculate=True,
            spec_accept_threshold=0.1)
        validate_moe_quant_combo("weight_only_int4", speculate=True)

    def test_int8_act_speculation_needs_margin(self):
        with pytest.raises(ShardedConfigError):
            validate_moe_quant_combo("int8_act", speculate=True)
        validate_moe_quant_combo("int8_act", speculate=True,
                                 spec_accept_threshold=0.05)

    def test_mixed_expert_algos_rejected(self):
        m = _fresh_model()
        m.gpt.layers[0].mlp = WeightOnlyMoELayer.from_moe(
            m.gpt.layers[0].mlp)
        with pytest.raises(ShardedConfigError):
            moe_serving_info(m)

    def test_serving_info_and_capacity(self, model):
        info = moe_serving_info(model)
        assert info["num_experts"] == 4 and info["layers"] == 2
        assert info["algo"] == "fp" and info["gate"] == "gshard"
        assert info["expert_hbm_bytes"] > 0
        # sized on the step's flat token axis, not max_batch rows of it
        cap = serving_capacity(CORE_SHAPE["token_budget"], info)
        assert cap == _capacity(16, 4, info["capacity_factor"], 2)

    def test_prepare_idempotent(self):
        m = _fresh_model()
        assert prepare_moe_serving(m, 8) == 2
        assert isinstance(m.gpt.layers[0].mlp, ServingMoELayer)
        assert prepare_moe_serving(m, 16) == 2     # rebind, no re-wrap
        assert not isinstance(m.gpt.layers[0].mlp.inner,
                              ServingMoELayer)
        assert m.gpt.layers[0].mlp.capacity == 16


# ------------------------------------------------------------ parity


class TestMoEServingParity:
    def test_stream_matches_unconverted_engine(self):
        """The conversion acceptance bar: EngineCore serving (converted
        layers, static capacity, stats plumbing) produces bitwise the
        stream of a plain unconverted PagedGenerationEngine.generate."""
        ref_model = _fresh_model()
        ref_eng = PagedGenerationEngine(ref_model, page_size=8)
        ids = _prompt(30, 9)
        want = np.asarray(ref_eng.generate(
            ids[None], GenerationConfig(max_new_tokens=6)))[0]

        served_model = _fresh_model()
        eng = build_sharded_engine(served_model, ServingMesh(),
                                   page_size=8)
        (got,) = _serve(eng, None, [ids],
                        [GenerationConfig(max_new_tokens=6)],
                        rid_base=9000)
        np.testing.assert_array_equal(got, want)

    def test_greedy_and_sampled_streams_ep2_bitwise(self, engine_single,
                                                    engine_ep2):
        prompts = [_prompt(31, 11), _prompt(32, 21), _prompt(33, 5)]
        gens = [GenerationConfig(max_new_tokens=8),
                GenerationConfig(max_new_tokens=6, do_sample=True,
                                 temperature=0.8, top_k=12, seed=7),
                GenerationConfig(max_new_tokens=7)]
        want = _serve(engine_single, None, prompts, gens, rid_base=9100)
        got = _serve(engine_ep2, ServingMesh(ep=2), prompts, gens,
                     rid_base=9100)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)

    def test_expert_params_ep_sharded(self, engine_ep2):
        # pools/params exist after the parity drives above
        snap = engine_ep2._snapshot_params()
        specs = {n: a.sharding.spec for n, a in snap.items()
                 if ".mlp." in n and n.endswith("w1")}
        assert specs, "no stacked expert params in the snapshot"
        assert all(s[0] == "ep" for s in specs.values())

    def test_supervisor_replay_parity_ep2(self, engine_single,
                                          engine_ep2):
        """A mid-decode crash that loses the KV pools: the replayed
        stream (re-routing every step's tokens through the gate again)
        equals the uninterrupted ep=1 stream — dropped-token handling
        is deterministic under replay."""
        ids = _prompt(34, 10)
        g = GenerationConfig(max_new_tokens=12)
        (want,) = _serve(engine_single, None, [ids], [g], rid_base=9200)

        request_mod._rid_counter = itertools.count(9200)
        plane = FaultPlane([FaultSpec("decode.step", at=4, lose_kv=True)])
        core = EngineCore(engine_ep2, fault_plane=plane,
                          serving_mesh=ServingMesh(ep=2), **CORE_SHAPE)
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

    def test_speculative_parity_moe(self, engine_single):
        """Verify rows ride the same MoE mixed step (W-keyed variant of
        the one executable): greedy streams equal the plain run."""
        prompts = [_prompt(35, 12), _prompt(36, 9)]
        gens = [GenerationConfig(max_new_tokens=10),
                GenerationConfig(max_new_tokens=8)]
        want = _serve(engine_single, None, prompts, gens, rid_base=9300)
        got = _serve(engine_single, None, prompts, gens, rid_base=9300,
                     speculate=True, num_draft_tokens=3)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)


# --------------------------------------------------- quantized experts


class TestQuantizedExpertServing:
    def _quantized_model(self, kind):
        m = _fresh_model()
        if kind == "int8_act":
            _swap(m, (MoELayer,),
                  lambda sub: Int8MoELayer.from_moe(sub), None)
        else:
            _swap(m, (MoELayer,),
                  lambda sub: WeightOnlyMoELayer.from_moe(sub, algo=kind),
                  None)
        return m

    @pytest.mark.parametrize("algo", ["weight_only_int8",
                                      "weight_only_int4"])
    def test_weight_only_experts_serve(self, algo):
        m = self._quantized_model(algo)
        assert moe_serving_info(m)["algo"] == algo
        eng = build_sharded_engine(m, ServingMesh(), page_size=8)
        streams = _serve(eng, None, [_prompt(40, 8)],
                         [GenerationConfig(max_new_tokens=5)],
                         rid_base=9400)
        assert streams[0].shape == (5,)

    def test_int8_act_experts_serve_and_gate_speculation(self):
        m = self._quantized_model("int8_act")
        eng = build_sharded_engine(m, ServingMesh(), page_size=8)
        with pytest.raises(ShardedConfigError):
            _serve(eng, None, [], [], rid_base=9450, speculate=True)
        streams = _serve(eng, None, [_prompt(41, 8)],
                         [GenerationConfig(max_new_tokens=5)],
                         rid_base=9460, speculate=True,
                         spec_accept_threshold=0.1)
        assert streams[0].shape == (5,)

    def test_weight_only_stream_tracks_fp_closely(self):
        """Weight-only error is deterministic and small at these dims —
        the greedy stream usually matches fp exactly; require at least
        the first tokens to agree so a quantization regression (wrong
        scales, transposed payload) cannot hide."""
        ids = _prompt(42, 10)
        g = [GenerationConfig(max_new_tokens=6)]
        fp_eng = build_sharded_engine(_fresh_model(), ServingMesh(),
                                      page_size=8)
        (want,) = _serve(fp_eng, None, [ids], g, rid_base=9500)
        wo_eng = build_sharded_engine(
            self._quantized_model("weight_only_int8"), ServingMesh(),
            page_size=8)
        (got,) = _serve(wo_eng, None, [ids], g, rid_base=9500)
        assert got.shape == want.shape
        np.testing.assert_array_equal(got[:2], want[:2])


# ----------------------------------------------- observability + fuzz


class TestMoEObservability:
    def test_snapshot_and_prometheus(self, engine_ep2):
        from paddle_infer_tpu.observability import get_compile_log
        from paddle_infer_tpu.observability.prometheus import (
            render_prometheus, validate_exposition)

        request_mod._rid_counter = itertools.count(9600)
        core = EngineCore(engine_ep2, serving_mesh=ServingMesh(ep=2),
                          **CORE_SHAPE)
        try:
            reqs = [core.submit(_prompt(50, 8),
                                GenerationConfig(max_new_tokens=6))[0]]
            _drive(core, reqs)
            snap = core.metrics_snapshot()
            text = render_prometheus(snap, get_compile_log().summary())
        finally:
            core.close()
        moe = snap["moe"]
        assert moe["num_experts"] == 4 and moe["ep"] == 2
        assert moe["algo"] == "fp"
        assert len(moe["expert_tokens"]) == 4
        assert moe["tokens_routed"] == sum(moe["expert_tokens"]) > 0
        assert 1.0 <= moe["utilization_skew"] <= 4.0
        assert 0.0 <= moe["dropped_ratio"] <= 1.0
        steps = core.steplog.summary()
        assert steps["moe_tokens_routed_total"] == moe["tokens_routed"]
        assert steps["moe_tokens_dropped_total"] \
            == moe["tokens_dropped"]

        assert validate_exposition(text) == []
        assert 'serving_mesh_info{devices="2",dp="1",ep="2",mp="1"' \
            in text
        assert 'moe_info{' in text and 'ep="2"' in text
        assert 'moe_expert_tokens_total{expert="0"}' in text
        assert "moe_utilization_skew" in text
        assert "steplog_moe_tokens_routed_total" in text
        assert 'collective_bytes_total{dtype="float32",' \
            'op="ep_alltoall"}' in text

    def test_mixed_fuzz_zero_post_warmup_compiles(self, engine_ep2):
        """The acceptance fuzz: ≥200 mixed decode/prefill/speculative
        steps over the 2-device ep mesh — staggered arrivals, chunked
        long prompts, greedy (speculated) and sampled rows, routing
        shifting every step — with ZERO post-warmup compiles.  Routing
        is data; the executable never follows it."""
        from paddle_infer_tpu.observability import get_compile_log

        request_mod._rid_counter = itertools.count(9700)
        core = EngineCore(engine_ep2, serving_mesh=ServingMesh(ep=2),
                          speculate=True, num_draft_tokens=3,
                          **CORE_SHAPE)
        rng = np.random.RandomState(0)
        try:
            # warm both executables (W=1 spec-off composition never
            # occurs under speculate=True; greedy+sampled covers both
            # row kinds)
            warm = [core.submit(_prompt(60, 8),
                                GenerationConfig(max_new_tokens=4))[0],
                    core.submit(_prompt(61, 30),
                                GenerationConfig(max_new_tokens=4,
                                                 do_sample=True,
                                                 seed=1))[0]]
            _drive(core, warm)
            log = get_compile_log()
            before = log.summary()["post_warmup_decode_compiles"]
            steps0 = core.steplog.summary()["records"]

            live, i = [], 0
            for _ in range(4000):
                done_steps = core.steplog.summary()["records"] - steps0
                if done_steps >= 200 and not live:
                    break
                if done_steps < 200 and len(live) < 4:
                    i += 1
                    n = int(rng.randint(3, 36))
                    if rng.rand() < 0.5:
                        g = GenerationConfig(
                            max_new_tokens=int(rng.randint(2, 8)))
                    else:
                        g = GenerationConfig(
                            max_new_tokens=int(rng.randint(2, 8)),
                            do_sample=True, temperature=0.9, seed=i)
                    live.append(core.submit(_prompt(100 + i, n), g)[0])
                core.run_once()
                live = [r for r in live if not r.done]
            total = core.steplog.summary()["records"] - steps0
            assert total >= 200, f"fuzz only drove {total} steps"
            after = log.summary()["post_warmup_decode_compiles"]
            assert after - before == 0
        finally:
            core.close()
