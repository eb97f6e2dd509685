"""The main path's Pallas kernels, compiled by the TPU's compiler for a
described (not attached) v5e at the widths ``chip_smoke.py`` runs them:
``llama-7b`` serving (32 heads of 128, page 16) and ``ernie-3.0-base``
training (12 heads of 64, batch 32, seq 512).  What Mosaic refuses here it
refuses on the chip — a block that is not tile-aligned, a vector load from
SMEM, more scoped VMEM than a kernel may have — and interpret mode shows
none of it.  A compile that passes is not a chip run; ``chip_smoke.py``
is.

Everything that touches the topology lives in fixtures and tests of this
one file: the TPU library loads in the xdist worker that runs it and in
no other process, and nothing is decided at import time.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these tests silent and
    the cache clean."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *specs):
    """Lower + compile for the shardings' device; returns the module text."""
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# llama-7b serving widths, as tools/serve.py's defaults lay them out
H, D, PAGE = 32, 128, 16
B, MAX_PAGES, POOL = 8, 128, 1024
WINDOW, CHUNK = 4, 64


def _pool(spec, quantized):
    pages = spec((POOL, H, PAGE, D), jnp.int8 if quantized else jnp.bfloat16)
    return (pages, spec((POOL, H), jnp.float32)) if quantized else pages


@pytest.fixture
def spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_attention_decode_compiles(spec, quantized):
    from paddle_infer_tpu.ops.pallas import paged_attention as PA

    pool = _pool(spec, quantized)
    _compile(functools.partial(PA._decode_local, interpret=False),
             spec((B, H, D), jnp.bfloat16), pool, pool,
             spec((B, MAX_PAGES), jnp.int32), spec((B,), jnp.int32))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_attention_verify_compiles(spec, quantized):
    from paddle_infer_tpu.ops.pallas import paged_attention as PA

    pool = _pool(spec, quantized)
    _compile(functools.partial(PA._verify_local, interpret=False),
             spec((B, WINDOW, H, D), jnp.bfloat16), pool, pool,
             spec((B, MAX_PAGES), jnp.int32),
             spec((B, WINDOW), jnp.int32))


@pytest.mark.parametrize("ambient", [None, "highest"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_ragged_kernel_compiles(spec, quantized, ambient):
    """Also under a caller's ``default_matmul_precision("highest")``
    (``chip_smoke.py`` compares under one): the kernel states its own
    contraction precisions, and Mosaic refuses a float32 contraction of
    bf16 operands."""
    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    pool = _pool(spec, quantized)
    with jax.default_matmul_precision(ambient or "default"):
        _compile(functools.partial(RPA._ragged_local, interpret=False),
                 spec((B, CHUNK, H, D), jnp.bfloat16), pool, pool,
                 spec((B, MAX_PAGES), jnp.int32), spec((B,), jnp.int32),
                 spec((B,), jnp.int32))


# (batch, seq, heads, head_dim, causal): ernie-3.0-base's training step,
# and llama-7b heads at the length where the pure-Pallas kernel takes over
ATTN_SHAPES = {"ernie-3.0-base": (32, 512, 12, 64, False),
               "llama-7b-s4096": (1, 4096, 32, 128, True)}


@pytest.mark.parametrize("kernel", ["flash_attention", "hybrid_attention"])
@pytest.mark.parametrize("widths", sorted(ATTN_SHAPES))
def test_flash_kernels_compile_fwd_bwd(spec, kernel, widths):
    """Forward and backward with segment ids and dropout, at the default
    512x512 blocks (the autotuner's incumbent, which must compile)."""
    from paddle_infer_tpu.ops.pallas import flash_attention as FA

    b, s, h, d, causal = ATTN_SHAPES[widths]
    fn = getattr(FA, kernel)

    def step(q, k, v, seg, seed):
        def loss(q_, k_, v_):
            o = fn(q_, k_, v_, q_segment_ids=seg, kv_segment_ids=seg,
                   dropout_p=0.1, dropout_seed=seed, is_causal=causal,
                   block_q=512, block_k=512, interpret=False)
            return jnp.sum(o.astype(jnp.float32))

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = spec((b, s, h, d), jnp.bfloat16)
    text = _compile(step, qkv, qkv, qkv, spec((b, s), jnp.int32),
                    spec((), jnp.uint32))
    # dK/dV and dQ are Mosaic kernels in both; flash adds the forward
    assert text.count("tpu_custom_call") >= (3 if kernel ==
                                             "flash_attention" else 2)


# ------------------------------------------------- the K/V write, in place
# mistral-d12.chat's widths (benchmarks/configs/mistral-7b-v0.1-d12.json):
# the pool of 16 rows x 2048 tokens + the scratch page, batch 16, chunk 64
CELL_POOL, CELL_B, CELL_CHUNK = (2049, H, PAGE, D), 16, 64

_POOL_SHAPED = r"= \w+\[2049,32,16,128\]\S* (copy|transpose)\("


def _pool_relayouts(text):
    """Instructions that copy or transpose something of the pool's shape:
    what a scatter with a sliced dimension between its index dimensions
    costs on the TPU (two per call), and what a CPU run cannot see."""
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(_POOL_SHAPED, line)]


@pytest.mark.parametrize("chunk", [CELL_CHUNK, 1], ids=["chunk64", "decode"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_kv_writer_updates_the_pool_in_place(spec, quantized, chunk):
    """``write_ragged_pages`` with the pool donated: temporaries under a
    tenth of the pool, and no pool-shaped copy in the module."""
    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    dtype = jnp.int8 if quantized else jnp.bfloat16
    pool = spec(CELL_POOL, dtype)
    pool_bytes = math.prod(CELL_POOL) * jnp.dtype(dtype).itemsize
    if quantized:
        pool = (pool, spec(CELL_POOL[:2], jnp.float32))
    compiled = jax.jit(RPA.write_ragged_pages, donate_argnums=0).lower(
        pool, spec((CELL_B, MAX_PAGES), jnp.int32),
        spec((CELL_B, chunk, H, D), jnp.bfloat16),
        spec((CELL_B,), jnp.int32), spec((CELL_B,), jnp.int32),
        spec((), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 10
    assert not _pool_relayouts(compiled.as_text())


def _window_relayouts(text):
    """Instructions shaped like every row's whole table window: the
    ``k_pages[block_tables]`` gather (``[16 x 128 pages, 32, 16, 128]``)
    and its transpose (``[16, 128, 16, 32, 128]``), which a step pays
    when its attention is the dense composition and not the kernel."""
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(r"= \w+\[(2048,32,16,128|16,128,16,32,128)\]", line)]


@pytest.fixture(scope="module")
def not_interpreted():
    """The served step asks the backend whether to interpret its Pallas
    calls, and the backend here is the CPU: for the compiles of whole
    steps below, every kernel module answers no."""
    from paddle_infer_tpu.ops.pallas import grouped_matmul as GM
    from paddle_infer_tpu.ops.pallas import latent_attention as LA
    from paddle_infer_tpu.ops.pallas import mhc_maps as MM
    from paddle_infer_tpu.ops.pallas import paged_attention as PA
    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA
    from paddle_infer_tpu.ops.pallas import sparse_latent_attention as SA

    mods = (PA, RPA, LA, GM, MM, SA)
    prev = [m._interpret for m in mods]
    for m in mods:
        m._interpret = lambda: False
    yield
    for m, f in zip(mods, prev):
        m._interpret = f


def _step_args(spec, params, b, tokens, max_pages, k_pools, v_pools,
               spec_window=1):
    """The mixed step's arguments as ``EngineCore`` hands them over: the
    parameters, the ONE packed ``int32`` buffer of ``step_input_layout``,
    the pools."""
    from paddle_infer_tpu.serving.programs import step_input_layout

    size = step_input_layout(b, tokens, max_pages, spec_window).size
    return (params, spec((size,), jnp.int32), k_pools, v_pools)


def _entry_io(compiled):
    """``(inputs, outputs)`` of the compiled program's entry computation,
    counted in its text: ``parameter(i)`` instructions, and the elements
    of the root tuple."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    body = entry[:entry.index("\n}")]
    n_in = len(set(re.findall(r" parameter\((\d+)\)", body)))
    root = next(line for line in body.splitlines()
                if line.lstrip().startswith("ROOT "))
    n_out = (len(re.findall(r"%[\w.-]+(?=[,)])", root.split(" tuple(", 1)[1]))
             if " tuple(" in root else 1)
    return n_in, n_out


def _llama_step(one_chip, grammar=False, spec_window=1, **widths):
    """One layer of the served mixed step of a LLaMA-block model, as
    ``EngineCore`` builds it (pools donated; ``spec_window`` > 1: the
    speculating program), compiled for the chip; with it, how many
    parameters the step was handed (it has two pools)."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_infer_tpu.nn.initializer import abstract_parameters
    from paddle_infer_tpu.serving.programs import build_mixed_step

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    cfg = LlamaConfig(vocab_size=32000, num_hidden_layers=1,
                      max_position_embeddings=32768, rms_norm_eps=1e-5,
                      rope_theta=10000.0, **widths)
    with abstract_parameters():
        model = LlamaForCausalLM(cfg)
    engine = PagedGenerationEngine(model, page_size=PAGE,
                                   cache_dtype=jnp.bfloat16)
    run = build_mixed_step(engine, CELL_B, CELL_CHUNK, MAX_PAGES,
                           spec_window=spec_window, grammar=grammar)
    params = {n: spec(a.shape, jnp.bfloat16)
              for n, a in engine._params.items()}
    pools = [spec((CELL_POOL[0], cfg.num_attention_heads) + CELL_POOL[2:],
                  jnp.bfloat16)]
    _, packed, k_pools, v_pools = _step_args(
        spec, params, CELL_B, CELL_CHUNK, MAX_PAGES, pools, pools,
        spec_window)
    # a grammar deployment's mask rides behind the packed buffer
    mask = (spec((CELL_B, 32000), jnp.float32),) if grammar else ()
    return run.lower(params, packed, *mask, k_pools,
                     v_pools).compile(), len(params)


@pytest.fixture(scope="module")
def chat_step(one_chip, not_interpreted):
    """The step at the chat cell's widths."""
    return _llama_step(one_chip, hidden_size=4096, num_attention_heads=H,
                       num_key_value_heads=8, intermediate_size=14336)


@pytest.fixture(scope="module")
def chat_step_text(chat_step):
    return chat_step[0].as_text()


def test_chat_step_takes_one_host_array_and_returns_one(chat_step):
    """Beside its parameters and its two pools the compiled step has ONE
    input, the packed buffer, and beside the pools ONE output."""
    compiled, n_params = chat_step
    assert _entry_io(compiled) == (n_params + 1 + 2, 1 + 2)


def test_grammar_step_takes_the_mask_as_its_second_host_array(
        one_chip, not_interpreted):
    """A grammar deployment's step has TWO such inputs: the packed buffer
    and the ``[max_batch, vocab]`` mask, which is not copied into it."""
    compiled, n_params = _llama_step(
        one_chip, grammar=True, hidden_size=1024, num_attention_heads=8,
        num_key_value_heads=8, intermediate_size=2048)
    assert _entry_io(compiled) == (n_params + 2 + 2, 1 + 2)


def test_mixed_step_layer_has_no_pool_or_window_sized_copy(chat_step_text):
    """The K and V writes reach the compiled step as in-place scatters,
    with no copy or transpose of the pool around them, and its attention
    is the ragged kernel's one launch: nothing gathers, transposes or
    scores a row's whole table window."""
    text = chat_step_text
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    assert "ragged_paged_attention" in text
    assert not _pool_relayouts(text)
    assert not _window_relayouts(text)


def _shaped(text, dims):
    """Instructions of the compiled program whose output has ``dims``."""
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(r"= \w+\[%s\]\S* \w[\w-]*\(" % dims, line)]


@pytest.mark.parametrize("dims, what", [
    ("16,64,14336", "the FFN over every row's slots"),
    ("16,64,6144", "QKV over every row's slots"),
    ("16,64,32000", "the head over every row's slots"),
    ("1024,\\d+", "anything over max_batch x token_budget flat slots")])
def test_chat_step_runs_its_token_wise_layers_over_the_flat_axis(
        chat_step_text, dims, what):
    """The step's token-wise layers run over ``token_budget`` slots: no
    instruction's output is shaped like the per-row slot array."""
    assert not _shaped(chat_step_text, dims), what
    # the shapes looked for are the ones such a program would hold
    assert _shaped(chat_step_text, "64,14336")


def test_chat_step_head_reads_max_batch_rows(chat_step_text):
    """The hidden state is gathered at each row's sampled slot before the
    final norm and the head: the logits are ``[max_batch, vocab]``."""
    assert _shaped(chat_step_text, "16,32000")
    assert not _shaped(chat_step_text, "64,32000")
    assert not _shaped(chat_step_text, "1,64,32000")


def _copies_of(text, dims):
    """Copies or transposes whose output has ``dims`` (either order of a
    matrix's two sides)."""
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(r"= \w+\[(%s)\]\S* (copy|transpose)\(" % dims, line)]


def test_chat_step_does_not_copy_the_qkv_weight(chat_step_text):
    """Over 64 flat tokens the TPU compiler would rather compute QKV
    head-major and transpose the whole ``[4096, 6144]`` weight for it on
    every step (50 MB moved a layer); the barrier behind the projection
    (models/transformer_block.py) keeps the head split out of it."""
    assert not _copies_of(chat_step_text, "6144,4096|4096,6144")


def test_the_window_shapes_are_what_the_dense_composition_compiles_to(spec):
    """The refusal above looks for the right thing: the plain composition
    the kernel is tested against does gather and transpose every row's
    window at these widths."""
    from paddle_infer_tpu.ops.pallas import paged_attention as PA

    pool = spec(CELL_POOL, jnp.bfloat16)
    text = jax.jit(PA.prefix_prefill_attention).lower(
        spec((CELL_B, CELL_CHUNK, H, D), jnp.bfloat16), pool, pool,
        spec((CELL_B, MAX_PAGES), jnp.int32), spec((CELL_B,), jnp.int32)
    ).compile().as_text()
    assert _window_relayouts(text)


# ------------------------------------------ latent pages and grouped experts
# axk1-ep16.ragchat's widths (benchmarks/configs/a.x-k1-ep16-d7.json): 64
# heads against one 512 + 64 row a token (640 lanes in the pool), the pool of
# 16 rows x 4096 tokens + the scratch page; 12 experts of 7168 x 2048 over
# 64 x 8 assignment rows
LAT_POOL, LAT_PAGES, LAT_B = (4097, PAGE, 640), 256, 16


def _latent_pool_copies(text):
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(r"= \w+\[4097,(1,)?16,640\]\S* (copy|transpose)\(",
                         line)]


def test_a_latent_pool_at_its_cached_width_would_not_be_row_major(spec):
    """Why the pool states its lanes: for a last dimension that is no
    multiple of 128 the TPU's own layout makes the page index the fastest
    dimension, and a donated write then transposes the pool in and out."""
    from paddle_infer_tpu.ops.pallas import latent_attention as LA

    def layouts(width):
        text = jax.jit(LA.write_latent_pages, donate_argnums=0).lower(
            spec((4097, PAGE, width), jnp.bfloat16),
            spec((LAT_B, LAT_PAGES), jnp.int32),
            spec((LAT_B, CELL_CHUNK, 576), jnp.bfloat16),
            spec((LAT_B,), jnp.int32), spec((LAT_B,), jnp.int32)
        ).compile().as_text()
        root = [ln for ln in text.splitlines() if "ROOT" in ln][-1]
        return re.search(r"bf16\[4097,16,\d+\]\{([\d,]+)", root).group(1)

    assert layouts(576) == "0,2,1"
    assert layouts(640) == "2,1,0"


def test_latent_decode_and_grouped_matmul_compile(spec, monkeypatch):
    """The decode kernel with its lengths as the served step makes them
    (traced: dead rows where a row carries no decode token), so BOTH
    bounds of its grid are traced values Mosaic has to take, the outer
    ``parallel`` one too; and with a step's mix as constants, dead rows
    between live ones and a longest row of three grid steps of 32."""
    import numpy as np
    from paddle_infer_tpu.ops.pallas import grouped_matmul as GM
    from paddle_infer_tpu.ops.pallas import latent_attention as LA

    monkeypatch.setattr(LA, "_interpret", lambda: False)
    monkeypatch.setattr(GM, "_interpret", lambda: False)
    i32 = jnp.int32
    served = lambda q, pool, t, ctx, qlens: LA.latent_paged_decode(
        q, pool, t, jnp.where(qlens == 1, ctx + 1, 0), 0.13, 512)
    args = (spec((LAT_B, 64, 576), jnp.bfloat16),
            spec(LAT_POOL, jnp.bfloat16), spec((LAT_B, LAT_PAGES), i32),
            spec((LAT_B,), i32), spec((LAT_B,), i32))
    calls = [e for e in jax.make_jaxpr(served)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert [e.params["grid_mapping"].num_dynamic_grid_bounds
            for e in calls] == [2]
    assert "latent_paged_decode" in _compile(served, *args)
    mixed = np.zeros((LAT_B,), np.int32)
    mixed[[0, 2, 3, 7, LAT_B - 1]] = 128, 1, 129, 300, 17
    _compile(lambda q, pool, t: LA.latent_paged_decode(
        q, pool, t, jnp.asarray(mixed), 0.13, 512), *args[:3])
    for k, n in ((7168, 2048), (2048, 7168)):
        _compile(GM.grouped_matmul, spec((512, k), jnp.bfloat16),
                 spec((12, k, n), jnp.bfloat16), spec((12,), i32))


@pytest.mark.parametrize("tokens", [64, 1024])
def test_mhc_maps_compiles_as_one_call(spec, tokens):
    """The three maps of a hyper-connected sub-layer, Sinkhorn's 20 rounds
    included, at the reasoning cell's width: ONE Mosaic call under its own
    name over the step's flat token axis (and over a longer one)."""
    from paddle_infer_tpu.ops.pallas.mhc_maps import mhc_maps

    text = _compile(
        lambda z, s, b: mhc_maps(z, s, b, 4, 20, 1e-6, -30.0, 30.0,
                                 interpret=False),
        spec((tokens, 24), jnp.float32), spec((24,), jnp.float32),
        spec((24,), jnp.float32))
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%mhc_maps" in calls[0]


@pytest.fixture(scope="module")
def streams_step(one_chip, not_interpreted):
    """A dense and an expert layer of the served mixed step at the
    reasoning cell's widths (four residual streams, all 64 experts, the
    whole vocabulary), pools donated, compiled for the chip."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                    LatentMoEForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters
    from paddle_infer_tpu.serving.programs import build_mixed_step

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    cfg = LatentMoEConfig(
        vocab_size=131072, hidden_size=3584, num_hidden_layers=2,
        num_attention_heads=32, q_lora_rank=768, intermediate_size=9216,
        moe_intermediate_size=1024, n_routed_experts=64,
        num_experts_per_tok=4, routed_scaling_factor=2,
        topk_method="noaux_tc", n_group=1, topk_group=1, hc_mult=4,
        rope_scaling=dict(
            beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=4096, type="yarn"))
    with abstract_parameters():
        model = LatentMoEForCausalLM(cfg)
    engine = PagedGenerationEngine(model, page_size=PAGE,
                                   cache_dtype=jnp.bfloat16)
    batch = 32
    run = build_mixed_step(engine, batch, CELL_CHUNK, LAT_PAGES,
                           moe_stats=True, residual_stats=True)
    small = ("alpha", "bias", "e_score_correction_bias")
    params = {n: spec(a.shape, jnp.float32 if n.rsplit(".", 1)[-1] in small
                      else jnp.bfloat16)
              for n, a in engine._params.items()}
    pools = [spec((batch * LAT_PAGES + 1,) + LAT_POOL[1:], jnp.bfloat16)] * 2
    return run.lower(*_step_args(spec, params, batch, CELL_CHUNK, LAT_PAGES,
                                 pools, [None, None])).compile()


def test_streams_step_holds_one_map_call_a_sublayer(streams_step):
    """Four sub-layers: four ``mhc_maps`` calls beside the latent decode
    kernel and the grouped matmul, the streams on the flat token axis in
    bfloat16, and no temporary near a layer's weights."""
    text = streams_step.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert sum("%mhc_maps" in ln for ln in calls) == 4
    assert "latent_paged_decode" in text and "moe_grouped_matmul" in text
    assert f"bf16[{CELL_CHUNK},4,3584]" in text
    assert streams_step.memory_analysis().temp_size_in_bytes < 0.5e9
    assert _entry_io(streams_step)[1] == 3      # packed output + 2 pools


@pytest.fixture(scope="module")
def latent_step(one_chip, not_interpreted):
    """A dense and an expert layer of the served mixed step at the ragchat
    cell's widths, pools donated, compiled for the chip."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                    LatentMoEForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters
    from paddle_infer_tpu.serving.programs import build_mixed_step

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    cfg = LatentMoEConfig(
        vocab_size=20480, num_hidden_layers=2, n_routed_experts=12,
        n_routed_experts_published=192, rope_scaling=dict(
            beta_fast=32, beta_slow=1, factor=32, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=4096, type="yarn"))
    with abstract_parameters():
        model = LatentMoEForCausalLM(cfg)
    engine = PagedGenerationEngine(model, page_size=PAGE,
                                   cache_dtype=jnp.bfloat16)
    run = build_mixed_step(engine, LAT_B, CELL_CHUNK, LAT_PAGES,
                           moe_stats=True)
    params = {n: spec(a.shape, jnp.bfloat16)
              for n, a in engine._params.items()}
    pools = [spec(LAT_POOL, jnp.bfloat16)] * 2
    return run.lower(*_step_args(spec, params, LAT_B, CELL_CHUNK, LAT_PAGES,
                                 pools, [None, None])).compile()


def test_latent_mixed_step_keeps_its_pool_in_place(latent_step):
    """Nothing copies or transposes anything of the pool's shape, and both
    kernels are in the step under their own names."""
    text = latent_step.as_text()
    assert "latent_paged_decode" in text and "moe_grouped_matmul" in text
    assert not _latent_pool_copies(text)
    # the widest temporary is one row's [heads, chunk, window] scores, not
    # every row's window of expanded keys and values (2 GB a layer)
    assert latent_step.memory_analysis().temp_size_in_bytes < 1.0e9


def test_latent_step_takes_one_host_array_and_returns_one(latent_step):
    """The dropless layers' four counters ride out in the one packed
    output: beside parameters and pools (one a latent layer), one input
    and one output."""
    n_params = len(latent_step.args_info[0][0])
    assert _entry_io(latent_step) == (n_params + 1 + 2, 1 + 2)


def test_latent_step_does_not_copy_the_query_up_projection(latent_step):
    """The same barrier behind ``q_b_proj`` (models/latent_moe.py): the
    ``[1536, 12288]`` weight is read where it lies."""
    assert not _copies_of(latent_step.as_text(), "1536,12288|12288,1536")


@pytest.mark.parametrize("dims, what", [
    ("16,64,18432", "the dense FFN over every row's slots"),
    ("16,64,2048", "the shared expert over every row's slots"),
    ("16,64,20480", "the head over every row's slots"),
    ("1024,7168", "the experts' combine over max_batch x token_budget"),
    ("16,64,64,5\\d\\d", "a per-row view of every head's query or output"),
    ("8192", "a sort over max_batch x token_budget x top-k keys")])
def test_latent_step_runs_its_token_wise_layers_over_the_flat_axis(
        latent_step, dims, what):
    """Token-wise layers, the expert layer's sort and combine and the
    queries' relayouts all follow the flat ``token_budget`` axis."""
    text = latent_step.as_text()
    assert not _shaped(text, dims), what
    assert _shaped(text, "64,18432") and _shaped(text, "64,7168")
    # the head's product has max_batch rows
    assert _shaped(text, "16,20480") and not _shaped(text, "64,20480")


# ------------------------------- a latent layer with an indexer's selection

DSA_PAGES, DSA_CHUNK = 1024, 256       # max_model_len 16384, token_budget
DSA_POOL = (16 * DSA_PAGES + 1, PAGE)


def test_index_scores_and_sparse_decode_compile(spec, not_interpreted):
    """The two kernels of a decode row's selection at the long-document
    cell's widths: 32 index heads of 128 over 32 pages a grid step, then
    64 heads over 2,048 gathered rows of 640 lanes, both grids traced."""
    from paddle_infer_tpu.ops.pallas import sparse_latent_attention as SA

    i32, bf16 = jnp.int32, jnp.bfloat16
    text = _compile(
        lambda q, w, pool, t, n: SA.dsa_index_scores(q, w, pool, t, n),
        spec((LAT_B, 32, 128), bf16), spec((LAT_B, 32), jnp.float32),
        spec(DSA_POOL + (128,), bf16), spec((LAT_B, DSA_PAGES), i32),
        spec((LAT_B,), i32))
    assert "dsa_index_scores" in text
    text = _compile(
        lambda q, rows, n: SA.dsa_sparse_decode(q, rows, n, 0.0625, 512),
        spec((LAT_B, 64, 576), bf16), spec((LAT_B, 2048, 640), bf16),
        spec((LAT_B,), i32))
    assert "dsa_sparse_decode" in text


@pytest.fixture(scope="module")
def selecting_step(one_chip, not_interpreted):
    """A dense and an expert layer of the served mixed step at the
    long-document cell's widths and deployment (16 rows of 16384, 256
    token slots), both pools of each layer donated, compiled for the
    chip."""
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                    LatentMoEForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters
    from paddle_infer_tpu.serving.programs import build_mixed_step

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    cfg = LatentMoEConfig(
        vocab_size=19456, hidden_size=6144, num_hidden_layers=2,
        q_lora_rank=2048, qk_nope_head_dim=192, v_head_dim=256,
        intermediate_size=12288, n_routed_experts=16,
        n_routed_experts_published=256, topk_method="noaux_tc", n_group=1,
        topk_group=1, rms_norm_eps=1e-5, max_position_embeddings=202752,
        rope_parameters=dict(rope_theta=1000000, rope_type="default"),
        index_topk=2048, index_n_heads=32, index_head_dim=128,
        model_type="glm_moe_dsa")
    with abstract_parameters():
        model = LatentMoEForCausalLM(cfg)
    engine = PagedGenerationEngine(model, page_size=PAGE,
                                   cache_dtype=jnp.bfloat16)
    run = build_mixed_step(engine, LAT_B, DSA_CHUNK, DSA_PAGES,
                           moe_stats=True)
    params = {n: spec(a.shape, a.dtype if a.dtype == jnp.float32
                      else jnp.bfloat16) for n, a in engine._params.items()}
    return run.lower(*_step_args(
        spec, params, LAT_B, DSA_CHUNK, DSA_PAGES,
        [spec(DSA_POOL + (640,), jnp.bfloat16)] * 2,
        [spec(DSA_POOL + (128,), jnp.bfloat16)] * 2)).compile()


def test_selecting_step_holds_its_kernels_and_fits(selecting_step):
    """Both selecting kernels under their own names and none of the dense
    decode kernel; neither pool copied or transposed; the widest
    temporary a tile of one chunk row's scores, not [heads, chunk,
    window] (1 GB at 256 x 16384) nor a row's gathered window."""
    text = selecting_step.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    # two layers: each kernel once a layer
    for kernel, n in (("%dsa_index_scores", 2), ("%dsa_sparse_decode", 2),
                      ("%latent_paged_decode", 0)):
        assert sum(kernel in ln for ln in calls) == n, kernel
    assert "moe_grouped_matmul" in text
    pool = r"bf16\[%d,16,(640|128)\]" % DSA_POOL[0]
    assert not [ln for ln in text.splitlines()
                if re.search(r"= %s\S* (copy|transpose)\(" % pool, ln)]
    assert selecting_step.memory_analysis().temp_size_in_bytes < 0.6e9
    n_params = len(selecting_step.args_info[0][0])
    # parameters, the packed input, two pools a layer; the packed output
    # and the pools back
    assert _entry_io(selecting_step) == (n_params + 1 + 4, 1 + 4)


def test_selecting_step_gathers_inside_the_live_rows_loop(selecting_step):
    """The selection's plumbing follows the live decode rows: one sort a
    layer (the top-k's, scores and positions: a third operand costs it
    half as much again on the chip), the chosen positions looked up and
    their rows gathered a live row at a time inside the loop, and nothing
    gathered for the whole batch (32,768 table entries and 32,768 latent
    rows a layer before PR 45)."""
    text = selecting_step.as_text()
    lines = [ln for ln in text.splitlines() if re.search(
        r'op_name="[^"]*/dsa_select/[^"]*"', ln)]
    sorts = [ln for ln in lines if " sort(" in ln]
    assert len(sorts) == 2, sorts
    for ln in sorts:
        assert re.search(r"= \(f32\[16,16384\]\S*, s32\[16,16384\]\S*\) sort\(",
                         ln), ln[:200]
    gathers = [ln for ln in lines if re.search(r'/gather"', ln)
               and re.search(r"= \S+ (gather|fusion)\(", ln)]
    assert gathers
    for ln in gathers:
        assert "/while/body/" in ln, ln[:200]
        assert not re.search(
            r"= (s32\[32768\]|bf16\[32768,640\]|bf16\[16,2048,640\])", ln)
    assert sum(bool(re.search(r"= bf16\[2048,640\]\S* fusion\(", ln))
               for ln in gathers) == 2
    assert not re.search(r"= s32\[32768\]", text)


# ---------------------------------------------------------- the sampling tail

@pytest.fixture(scope="module")
def spec_step_text(one_chip, not_interpreted):
    """The speculating (``W`` = 4) program of a small LLaMA-block model
    over the chat cell's vocabulary."""
    return _llama_step(one_chip, spec_window=WINDOW, hidden_size=1024,
                       num_attention_heads=8, num_key_value_heads=8,
                       intermediate_size=2048)[0].as_text()


def _sides_of_the_conditionals(text):
    """The compiled module's instruction lines in two lists: those the
    entry computation reaches without entering a conditional's branch
    (what every step runs), and those it reaches only through one."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.-]+) \(.*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    always, branched = set(), set()

    def walk(comp, seen):
        if comp in seen:
            return
        seen.add(comp)
        for line in comps[comp]:
            for ref in re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.-]+)", line):
                walk(ref, seen)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                for ref in re.findall(r"%([\w.-]+)", group):
                    walk(ref, branched)

    walk(entry, always)
    lines = lambda names: [ln for c in sorted(names) for ln in comps[c]]
    return lines(always), lines(branched - always)


@pytest.mark.parametrize("step, rows", [
    ("chat_step_text", "16,32000"), ("latent_step", "16,20480"),
    ("streams_step", "32,131072"),
    ("spec_step_text", "16,4,32000|4,16,32000|64,32000")])
def test_step_sorts_the_vocabulary_once_and_inside_a_conditional(
        request, step, rows):
    """The sampling tail of the compiled step (``_process_rows``,
    ``_pick_rows``): ONE sort over ``[max_batch, vocab]`` where there
    were two, and it, the nucleus's cumulative sum and the draw's random
    bits lie in branches of conditionals, so a step whose rows are all
    greedy runs none of them.  The speculating program's tail runs under
    a ``vmap`` over its window: its conditionals are conditionals too,
    not selects over both sides."""
    text = request.getfixturevalue(step)
    text = text if isinstance(text, str) else text.as_text()
    always, branched = _sides_of_the_conditionals(text)
    wide_sort = r"= \(?f32\[(%s)\][^=]* sort\(" % rows
    assert len([ln for ln in branched if re.search(wide_sort, ln)]) == 1
    # (the experts' router sorts its [tokens, experts] scores on every step)
    assert not [ln for ln in always if re.search(wide_sort, ln)]
    assert len(re.findall(r" conditional\(", text)) >= 2
    for what, mark in (("the nucleus's cumulative sum",
                        r"= f32\[\S* reduce-window\("),
                       ("the draw's random bits", "threefry")):
        assert [ln for ln in branched if re.search(mark, ln)], what
        # the speculating program's accept tail draws on every step (W6)
        if step != "spec_step_text":
            assert not [ln for ln in always if "lm_head_sample" in ln
                        and re.search(mark, ln)], what


# one layer of the tool-chat cell's step: two latent attentions, two dense
# blocks and the shortcut expert block at its widths and deployment (64
# rows of 4096, 256 token slots)
SHORTCUT_B, SHORTCUT_CHUNK, SHORTCUT_PAGES = 64, 256, 256


@pytest.fixture(scope="module")
def shortcut_step(one_chip, not_interpreted):
    from paddle_infer_tpu.inference.generation import PagedGenerationEngine
    from paddle_infer_tpu.models.longcat_flash import (
        LongcatFlashConfig, LongcatFlashForCausalLM)
    from paddle_infer_tpu.nn.initializer import abstract_parameters
    from paddle_infer_tpu.serving.programs import build_mixed_step

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    cfg = LongcatFlashConfig(vocab_size=16384, num_layers=1,
                             n_routed_experts=16,
                             n_routed_experts_published=512)
    with abstract_parameters():
        model = LongcatFlashForCausalLM(cfg)
    engine = PagedGenerationEngine(model, page_size=PAGE,
                                   cache_dtype=jnp.bfloat16)
    run = build_mixed_step(engine, SHORTCUT_B, SHORTCUT_CHUNK,
                           SHORTCUT_PAGES, moe_stats=True)
    params = {n: spec(a.shape, jnp.float32
                      if n.endswith("e_score_correction_bias")
                      else jnp.bfloat16) for n, a in engine._params.items()}
    pools = [spec((SHORTCUT_B * SHORTCUT_PAGES + 1, PAGE, 640),
                  jnp.bfloat16)] * 2
    return run.lower(*_step_args(
        spec, params, SHORTCUT_B, SHORTCUT_CHUNK, SHORTCUT_PAGES, pools,
        [None, None])).compile()


def test_shortcut_step_holds_two_decode_kernels_a_layer_and_fits(
        shortcut_step):
    """One layer: the latent decode kernel once an attention sub-layer,
    the grouped matmul for the one expert block, neither pool copied or
    transposed, the packed output and both pools back."""
    text = shortcut_step.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    entry = text.split("\nENTRY ", 1)[1]
    assert len(set(re.findall(r"%(latent_paged_decode[\w.]*) = ", entry))) \
        == 2
    assert sum("%moe_grouped_matmul" in ln for ln in calls) >= 3
    pool = r"bf16\[%d,16,640\]" % (SHORTCUT_B * SHORTCUT_PAGES + 1)
    assert not [ln for ln in text.splitlines()
                if re.search(r"= %s\S* (copy|transpose)\(" % pool, ln)]
    assert shortcut_step.memory_analysis().temp_size_in_bytes < 0.8e9
    n_params = len(shortcut_step.args_info[0][0])
    assert _entry_io(shortcut_step) == (n_params + 1 + 2, 1 + 2)


def test_shortcut_step_identity_term_is_two_small_operations_a_block(
        shortcut_step):
    """The identity experts' term compiles to one masked sum a token
    (``select_reduce_fusion f32[256]``) and one multiply into the combine's
    base (``convert_multiply_fusion f32[256,6144]``): the two operation
    keys ``moe_identity_ms_per_step.longcat`` sums, each carrying the
    scope ``moe_identity``, and nothing under the scope or under
    ``moe_experts`` is as wide as tokens x router outputs but the scores
    themselves; the rows buffer is tokens x 12 whatever is chosen."""
    import json
    import os

    text = shortcut_step.as_text()
    entry = text.split("\nENTRY ", 1)[1]
    entry = entry[:entry.index("\n}")]
    scoped = [ln for ln in entry.splitlines() if re.search(
        r'op_name="[^"]*/moe_identity/[^"]*"', ln) and " fusion(" in ln]
    names = sorted(re.match(r"\s*%([a-z_]+)[\w.]* = \(?(\w+\[[\d,]*\])",
                            ln).groups() for ln in scoped)
    assert names == [("convert_multiply_fusion", "f32[256,6144]"),
                     ("select_reduce_fusion", "f32[256]")]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "layer_metrics",
                           "moe_identity_ms_per_step.longcat.json")) as f:
        keys = json.load(f)["args"]["kernels"]
    assert sorted(keys) == sorted("fusion %s %s" % n for n in names)
    # no other operation of the step goes by either key
    for name, shape in names:
        same = [ln for ln in entry.splitlines() if re.match(
            r"\s*%%%s[\w.]* = \(?%s" % (name, re.escape(shape)), ln)]
        assert len(same) == 1, (name, len(same))
    experts = [ln for ln in text.splitlines() if re.search(
        r'op_name="[^"]*/(moe_experts|moe_identity)/[^"]*"', ln)]
    assert experts and not [ln for ln in experts
                            if re.search(r"\[256,768\]|\[768,256\]", ln)]
    assert "bf16[3072,2048]" in text and "bf16[3072,6144]" in text
