"""Headline benchmark: ERNIE-3.0-base training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The reference publishes no numbers (BASELINE.md); the recorded target is the
north star "≥35% MFU training ERNIE-3.0-base", so ``vs_baseline`` reports
achieved-MFU / 0.35 (≥1.0 beats the bar).  Peak bf16 FLOPs per chip is taken
from the detected TPU generation.

This measures the REAL pretraining config — dropout 0.1 (hidden + attention
probs) and a 10%-padded batch with the padding mask riding as segment ids —
i.e. the conditions that engage the masked/dropout-capable flash kernels,
not a benchmark-clean special case (round-2 verdict, "what's weak" #1).

MFU is reported two ways: the standard 6·N·T analytic estimate *plus the
attention term* (12·L·s·hidden per token), and an XLA-compiler-derived
number from the compiled step's cost_analysis() — the profiler-grade backing
for the analytic claim.  ``vs_baseline`` keeps the (conservative) analytic
definition for round-over-round comparability.

One process, and it needs the chip: with no TPU attached the script
fails before it measures anything, an unknown ``device_kind`` has no peak
to divide by and raises, and a section that fails is recorded in the
result and makes the exit code non-zero.  There is no CPU fallback — a
number from a CPU run must never appear under a device metric's name.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np


PEAK_BF16_FLOPS = {
    # per-chip dense bf16 peak
    "v4": 275e12,
    "v5lite": 197e12,   # v5e
    "v5": 459e12,       # v5p
    "v6lite": 918e12,   # v6e (trillium)
}

DECODE_P50_TARGET_MS = 1.70          # BASELINE.md round-4 addendum
DECODE_MARGINAL_TARGET_MS = 1.0

class _SectionTimeout(Exception):
    pass


import contextlib  # noqa: E402
import signal  # noqa: E402


@contextlib.contextmanager
def _section_alarm(seconds: int):
    """Best-effort per-section time limit (SIGALRM).  A hang inside a
    GIL-releasing device wait can outlive the alarm (the handler needs
    Python to resume)."""

    def handler(signum, frame):
        raise _SectionTimeout(f"section exceeded {seconds}s")

    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _require_tpu():
    """The device this process measures on, or an error.  Nothing below
    runs on another backend: no retry, no probe child, no CPU stand-in."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; JAX found {dev.platform} "
            f"({getattr(dev, 'device_kind', '?')})")
    return dev


def _peak_flops() -> float:
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind.lower().replace(" ", "")
    for key, val in PEAK_BF16_FLOPS.items():
        if key in kind:
            return val
    raise KeyError(
        f"no bf16 peak recorded for device_kind {dev.device_kind!r}; "
        f"add it to PEAK_BF16_FLOPS with its source (known: "
        f"{sorted(PEAK_BF16_FLOPS)})")


def _resnet50_throughput(on_tpu: bool):
    """ResNet-50 training throughput (BASELINE.md milestone #3, unbenched
    until round 4).  bf16 AMP, SGD momentum, synthetic ImageNet batch."""
    import jax

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, fleet)
    from paddle_infer_tpu.vision.models import resnet50

    batch = 64 if on_tpu else 2
    size = 224 if on_tpu else 32
    model = resnet50()
    model.train()
    opt = pit.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=model.parameters())
    strategy = DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs = {"level": "O2", "dtype": "bfloat16"}

    def loss_fn(m, x, y):
        return pit.nn.functional.cross_entropy(m(x), y)

    step = FleetTrainStep(model, loss_fn, opt, strategy=strategy)
    rng = np.random.RandomState(0)
    # Device-put the batch ONCE: re-feeding numpy would copy ~38 MB
    # host-to-device per step and time the transfer with the step.
    x = pit.to_tensor(rng.rand(batch, 3, size, size).astype(np.float32))
    y = pit.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int32))
    step(x, y)
    step(x, y).numpy()                     # compile + settle
    iters = 20 if on_tpu else 2
    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(x, y)
        loss.numpy()
        dt = min(dt, time.perf_counter() - t0)
    return batch * iters / dt


def main():
    t_start = time.monotonic()

    import jax

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.models import (ErnieConfig, ErnieForPretraining,
                                         ernie_pretrain_loss)
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, fleet)

    from paddle_infer_tpu.utils.compile_cache import \
        configure_compile_cache

    configure_compile_cache()
    device = _require_tpu()
    # the sections still take the flag and carry off-chip sizes; both go
    # with the benchmark rewrite (ROADMAP S0), not with this file's
    # device handling
    on_tpu = True
    batch, seq = (32, 512) if on_tpu else (4, 128)

    # real pretraining config: dropout 0.1, padded batches (not the clean
    # dropout-0/no-mask special case)
    cfg = ErnieConfig.from_preset(
        "ernie-3.0-base", vocab_size=40000, max_position_embeddings=seq,
        hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1) \
        if on_tpu else ErnieConfig(
            vocab_size=1024, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=512,
            max_position_embeddings=seq, hidden_dropout_prob=0.1,
            attention_probs_dropout_prob=0.1)

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1}
    strategy.amp = True
    strategy.amp_configs = {"level": "O2", "dtype": "bfloat16"}
    fleet.init(is_collective=True, strategy=strategy,
               devices=jax.devices()[:1])

    model = ErnieForPretraining(cfg)
    model.train()
    opt = pit.optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())

    def loss_fn(m, ids, mask, labels, nsp_labels):
        mlm, nsp = m(ids, attention_mask=mask)
        return ernie_pretrain_loss(mlm, nsp, labels, nsp_labels)

    step = FleetTrainStep(model, loss_fn, opt, strategy=strategy)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    # ~10% trailing padding per row (padding mask -> segment ids inside the
    # model, so the flash kernels stay engaged)
    pad = max(1, seq // 10)
    mask = np.ones((batch, seq), np.int32)
    mask[:, seq - pad:] = 0
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels[:, seq - pad:] = -100           # pads excluded from the loss
    nsp = rng.randint(0, 2, (batch,)).astype(np.int32)

    # warmup (compile)
    step(ids, mask, labels, nsp)
    step(ids, mask, labels, nsp).numpy()

    # both estimators (ADVICE r3): blocks[0] is the single-block estimate
    # comparable with r01/r02; min(blocks) is best-of-3 — the dev chip is
    # shared and another tenant's burst only ever slows a block
    iters = 30 if on_tpu else 5
    blocks = []
    for _ in range(3 if on_tpu else 1):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(ids, mask, labels, nsp)
        loss.numpy()   # sync
        blocks.append(time.perf_counter() - t0)
    dt = min(blocks)

    tokens_per_sec = batch * seq * iters / dt
    tokens_per_sec_single = batch * seq * iters / blocks[0]
    n_params = sum(int(p.size) for p in model.parameters())
    # 6ND fwd+bwd + the attention term (2 matmuls of 2·s·hidden each, x3
    # for fwd+bwd: 12·L·s·hidden per token; ERNIE attends bidirectionally
    # so no causal /2)
    model_flops_per_tok = (6 * n_params
                           + 12 * cfg.num_hidden_layers * seq
                           * cfg.hidden_size)
    peak = _peak_flops()
    mfu = tokens_per_sec * model_flops_per_tok / peak

    # compiler-derived backing number: XLA's own FLOP count for the
    # compiled step executable (includes attention, dropout, optimizer)
    mfu_xla = None
    cost = step.cost_analysis(ids, mask, labels, nsp)
    xla_flops = float(cost.get("flops", 0.0))
    if xla_flops > 0:
        mfu_xla = xla_flops * iters / dt / peak

    # one xplane capture of the measured region (round-2 verdict item 9),
    # under the run's TMPDIR; a profiler that cannot trace is an error
    xplane_dir = tempfile.mkdtemp(prefix="pit_bench_xplane_")
    jax.profiler.start_trace(xplane_dir)
    try:
        step(ids, mask, labels, nsp).numpy()
    finally:
        jax.profiler.stop_trace()

    # per-kernel table over that capture (the reference profiler's Kernel
    # Summary): top ops by device-time share, so a perf regression names
    # its kernel in the bench JSON instead of hiding in the headline
    from paddle_infer_tpu.profiler.statistic import device_op_stats

    top_ops = None
    stats = device_op_stats(xplane_dir)
    if stats:
        total = sum(s.total_ns for s in stats.values()) or 1.0
        top_ops = [{"name": s.name[:96],
                    "ratio": round(s.total_ns / total, 4),
                    "avg_ms": round(s.avg_ns / 1e6, 4),
                    "calls": s.call}
                   for s in sorted(stats.values(),
                                   key=lambda s: -s.total_ns)[:5]]

    # headline is in hand: print a PRELIMINARY JSON line now, so a run
    # cut during an aux section still leaves the headline in its output
    headline = {
        "metric": "ernie3.0-base train tokens/sec/chip "
                  "(bf16, bs%d seq%d, dropout 0.1, 10%% padded)"
                  % (batch, seq),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 3),
        "mfu_6nt_plus_attn": round(mfu, 4),
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
    }
    print(json.dumps({**headline, "preliminary": "aux sections pending"}),
          flush=True)

    failed_sections = []

    def run_section(name, cap_s, fn):
        """An aux section that fails does not stop the ones after it, but
        it is recorded, and a run with a failed section exits non-zero."""
        try:
            with _section_alarm(int(cap_s)):
                return fn()
        except Exception as e:
            print(f"{name} FAILED: {e!r}", file=sys.stderr)
            failed_sections.append(f"{name}: {repr(e)[:200]}")
            return None

    # ResNet-50 milestone (#3) throughput
    resnet_ips = run_section("resnet50", 600,
                             lambda: _resnet50_throughput(on_tpu))

    # the latency bench needs the native runtime (paged-KV pool); never let
    # it take down the training metric
    lat = run_section("decode_latency", 700,
                      lambda: _decode_latency_bs1(on_tpu))
    if lat is not None:
        p50_ms, marginal_ms, marginal_int8_ms = lat
        p50_ms = round(p50_ms, 3)
    else:
        p50_ms = marginal_ms = marginal_int8_ms = None

    # LLaMA-architecture paged decode (BASELINE milestone #5, scaled-down)
    llama_marginal = run_section("llama_decode", 420,
                                 _llama_decode_marginal)

    # MoE decode marginal, fp vs weight-only int8 experts (the fork's
    # fused_multi_transformer_moe(_weight_only) serving pair)
    moe_marginal = run_section("moe_decode", 420, _moe_decode_marginal)

    # speculative decoding: acceptance + marginal-latency delta
    spec_stats = run_section("spec_decode", 600, _spec_decode_stats)

    # continuous-batching serving engine vs sequential generate()
    serving = run_section("serving", 600,
                          lambda: _serving_bench(on_tpu))

    # in-engine speculative decoding vs plain ragged serving on warm
    # repeat traffic (greedy streams must stay bitwise identical)
    speculative = run_section("speculative", 600,
                              lambda: _speculative_bench(on_tpu))

    # prefix KV-cache: warm (shared system prompt) vs cold TTFT
    prefix_cache = run_section("prefix_cache", 420,
                               lambda: _prefix_cache_bench(on_tpu))

    # int8 paged KV vs fp: resident concurrency at equal pool bytes,
    # decode throughput, measured quantization error vs analytic bound
    quantized_kv = run_section("quantized_kv", 500,
                               lambda: _quantized_kv_bench(on_tpu))

    # fault tolerance: goodput + token integrity under a seeded fault
    # schedule (engine crashes, KV loss, injected OOM)
    resilience = run_section("resilience", 420,
                             lambda: _resilience_bench(on_tpu))

    # SLO-aware scheduler: fifo vs slack admission replaying one
    # recorded multi-tenant bursty trace (byte-identical offered load),
    # with the zero-recompile and bitwise-stream gates
    multi_tenant = run_section("multi_tenant", 560,
                               lambda: _multi_tenant_bench(on_tpu))

    # multi-LoRA tenancy: one Zipf popularity draw served at 1 / 32 /
    # 256 addressable adapters over 8 device slots — tok/s + ITL p99
    # scaling, and the zero-recompile-under-churn gate
    adapter_tenancy = run_section("adapter_tenancy", 500,
                                  lambda: _adapter_tenancy_bench(on_tpu))

    # host-RAM KV tier: oversubscription replay without/with the tier —
    # sheds become parks, deadline-less goodput holds at 1.0, streams
    # stay bitwise identical, zero post-warmup compiles
    kv_tier = run_section("kv_tier", 560,
                          lambda: _kv_tier_bench(on_tpu))

    # constrained decoding: one sampled offered batch unconstrained vs
    # under per-request grammars — conformance 1.0, zero violations,
    # zero post-warmup compiles, ITL overhead of the data-only mask
    structured_output = run_section("structured_output", 500,
                                    lambda: _structured_bench(on_tpu))

    result = {
        **headline,
        "tokens_per_sec_single_block": round(tokens_per_sec_single, 1),
    }
    if mfu_xla is not None:
        result["mfu_xla_cost_analysis"] = round(mfu_xla, 4)
    result["xplane_dir"] = xplane_dir
    if top_ops is not None:
        result["top_ops"] = top_ops
    if resnet_ips is not None:
        result["resnet50_train_img_per_sec"] = round(resnet_ips, 1)
    if p50_ms is not None:
        result["decode_p50_ms_per_token_bs1"] = p50_ms
        result["decode_p50_target_ms"] = DECODE_P50_TARGET_MS
        result["decode_within_target"] = bool(
            p50_ms <= DECODE_P50_TARGET_MS)
    if marginal_ms is not None:
        result["decode_marginal_ms_per_token_bs1"] = round(marginal_ms, 3)
        result["decode_marginal_target_ms"] = DECODE_MARGINAL_TARGET_MS
    if marginal_int8_ms is not None:
        result["decode_marginal_ms_per_token_bs1_int8"] = round(
            marginal_int8_ms, 3)
    if llama_marginal is not None:
        result["llama_decode_marginal_ms_per_token_bs1"] = round(
            llama_marginal, 3)
    if moe_marginal is not None:
        result["moe_decode_marginal_ms_per_token_bs1"] = round(
            moe_marginal[0], 3)
        result["moe_decode_marginal_ms_per_token_bs1_int8"] = round(
            moe_marginal[1], 3)
    if spec_stats is not None:
        result["spec_decode_acceptance"] = round(spec_stats[0] or 0.0, 3)
        result["spec_decode_marginal_ms_per_token"] = round(
            spec_stats[1], 3)
        result["spec_decode_plain_marginal_ms_per_token"] = round(
            spec_stats[2], 3)
    if serving is not None:
        result["serving"] = serving
    if speculative is not None:
        result["speculative"] = speculative
    if prefix_cache is not None:
        result["prefix_cache"] = prefix_cache
    if quantized_kv is not None:
        result["quantized_kv"] = quantized_kv
    if resilience is not None:
        result["resilience"] = resilience
    if multi_tenant is not None:
        result["multi_tenant"] = multi_tenant
    if adapter_tenancy is not None:
        result["adapter_tenancy"] = adapter_tenancy
    if kv_tier is not None:
        result["kv_tier"] = kv_tier
    if structured_output is not None:
        result["structured_output"] = structured_output
    if failed_sections:
        result["failed_sections"] = failed_sections
    result["bench_wall_s"] = round(time.monotonic() - t_start, 1)
    print(json.dumps(result))
    return 1 if failed_sections else 0


def _decode_latency_bs1(on_tpu: bool):
    """p50 per-token decode latency, bs=1, paged-KV serving path (the
    'Paddle Inference p50 latency @bs1' metric from BASELINE.md) on a
    GPT sized like ERNIE-base.  Also measures the weight-only-int8
    marginal decode (the fork's fused_multi_transformer_weight_only
    serving mode): bs=1 decode is weight-bandwidth-bound, so halving the
    weight bytes should show up directly."""
    import jax

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM

    pit.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=40000, hidden_size=768,
                        num_hidden_layers=12, num_attention_heads=12,
                        intermediate_size=3072,
                        max_position_embeddings=1024,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        prompt, max_new, reps = 128, 64, 20
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=64,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=128, max_position_embeddings=256,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        prompt, max_new, reps = 32, 8, 3
    model = GPTForCausalLM(cfg)
    model.eval()
    if on_tpu:   # serve in bf16 like the trained AMP O2 model
        import jax.numpy as jnp

        for p in model.parameters():
            p._data = p._data.astype(jnp.bfloat16)
    eng = PagedGenerationEngine(model, page_size=16, prompt_bucket=prompt)
    g = GenerationConfig(max_new_tokens=max_new)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (1, prompt)).astype(np.int32)
    eng.generate(ids, g)                      # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.generate(ids, g)
        times.append((time.perf_counter() - t0) / max_new * 1e3)
    p50_whole = float(np.percentile(times, 50))

    # marginal per-token decode: see _marginal_decode_ms (isolates the
    # steady-state decode step from prefill + per-call host cost)
    def _marginal(engine):
        return _marginal_decode_ms(engine, ids, max_new, reps)

    marginal = marginal_int8 = None
    if on_tpu:
        marginal = _marginal(eng)
        from paddle_infer_tpu.quantization.weight_only import \
            quantize_model

        mq = quantize_model(model, algo="weight_only_int8")
        engq = PagedGenerationEngine(mq, page_size=16,
                                     prompt_bucket=prompt)
        marginal_int8 = _marginal(engq)
    return p50_whole, marginal, marginal_int8


def _marginal_decode_ms(engine, ids, max_new, reps):
    """Marginal per-token decode via difference of two generation
    lengths (cancels prefill and whatever each call costs on the host
    whatever its length).  Shared by the dense/LLaMA/MoE/quantized decode benches
    so the methodology can only change in one place."""
    from paddle_infer_tpu.inference import GenerationConfig

    g_long = GenerationConfig(max_new_tokens=max_new)
    g_short = GenerationConfig(max_new_tokens=max_new // 2)
    engine.generate(ids, g_long)       # compile both programs
    engine.generate(ids, g_short)
    t_long, t_short = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.generate(ids, g_long)
        t_long.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        engine.generate(ids, g_short)
        t_short.append(time.perf_counter() - t0)
    m = ((np.percentile(t_long, 50) - np.percentile(t_short, 50))
         / (max_new - max_new // 2) * 1e3)
    return float(max(m, 0.0))


def _llama_decode_marginal():
    """Marginal per-token paged decode for a scaled-down LLaMA
    architecture (RoPE + RMSNorm + SwiGLU; BASELINE.md milestone #5 bench
    entry — 7B itself exceeds one dev chip's useful bench window)."""
    import jax.numpy as jnp

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import PagedGenerationEngine
    from paddle_infer_tpu.models import LlamaConfig, LlamaForCausalLM

    pit.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      num_hidden_layers=8, num_attention_heads=8,
                      intermediate_size=2816,
                      max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    model.eval()
    for p in model.parameters():
        p._data = p._data.astype(jnp.bfloat16)
    prompt = 128
    eng = PagedGenerationEngine(model, page_size=16, prompt_bucket=prompt)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, prompt)).astype(np.int32)
    return _marginal_decode_ms(eng, ids, max_new=64, reps=10)


def _moe_decode_marginal():
    """Marginal per-token paged MoE decode, float experts vs weight-only
    int8 experts (reference fused_multi_transformer_moe_op.cu vs
    fused_multi_transformer_moe_weight_only_op.cu — the quantized-MoE
    serving delta, round-4 verdict missing #1).  Returns (fp_ms,
    int8_ms)."""
    import jax.numpy as jnp

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import PagedGenerationEngine
    from paddle_infer_tpu.models import GPTMoEForCausalLM, MoEConfig
    from paddle_infer_tpu.quantization import quantize_model

    def build():
        pit.seed(0)
        cfg = MoEConfig(num_experts=8, moe_top_k=2, vocab_size=32000,
                        hidden_size=768, num_hidden_layers=8,
                        num_attention_heads=12, intermediate_size=1536,
                        max_position_embeddings=512,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        m = GPTMoEForCausalLM(cfg)
        m.eval()
        for p in m.parameters():
            p._data = p._data.astype(jnp.bfloat16)
        return m

    prompt = 64
    ids = np.random.RandomState(0).randint(
        0, 32000, (1, prompt)).astype(np.int32)

    def marginal(model):
        eng = PagedGenerationEngine(model, page_size=16,
                                    prompt_bucket=prompt)
        return _marginal_decode_ms(eng, ids, max_new=32, reps=10)

    from paddle_infer_tpu.parallel.moe import MoELayer

    fp = marginal(build())
    # quantize ONLY the MoE experts so the delta isolates the
    # moe-op-vs-moe-weight-only-op difference (dense linears stay float)
    q = marginal(quantize_model(
        build(), algo="weight_only_int8",
        skip=lambda name, lay: not isinstance(lay, MoELayer)))
    return fp, q


def _spec_decode_stats():
    """Speculative-decoding evidence (round-4 verdict, next-round #10:
    'a latency feature with no latency number').  Random-init draft/
    target would show ~0 acceptance, so both models first learn a
    deterministic token pattern (~1 min of tiny-model training); the
    draft then genuinely predicts the target and the measured numbers —
    acceptance rate, spec marginal vs plain marginal — reflect the
    mechanism, not luck.  Returns (accept_rate, spec_ms, plain_ms)."""
    import jax.numpy as jnp

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import GenerationConfig
    from paddle_infer_tpu.inference.generation import GenerationEngine
    from paddle_infer_tpu.inference.speculative import SpeculativeEngine
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM

    vocab, seq = 128, 64

    def make(h, layers, heads, inter):
        return GPTForCausalLM(GPTConfig(
            vocab_size=vocab, hidden_size=h, num_hidden_layers=layers,
            num_attention_heads=heads, intermediate_size=inter,
            max_position_embeddings=512, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))

    def batch(rng, bs):
        # cyclic successor pattern with random phase — learnable by a
        # 2-layer draft, so draft tracks target
        start = rng.randint(0, vocab, (bs, 1))
        return ((start + np.arange(seq + 1)[None, :]) % vocab) \
            .astype(np.int32)

    def train(model, steps, lr=3e-3):
        model.train()
        opt = pit.optimizer.AdamW(learning_rate=lr,
                                  parameters=model.parameters())
        rng = np.random.RandomState(0)
        for _ in range(steps):
            data = batch(rng, 32)
            x, y = data[:, :-1], data[:, 1:]
            logits = model(pit.to_tensor(x))
            loss = pit.nn.functional.cross_entropy(
                logits.reshape([-1, vocab]),
                pit.to_tensor(y.reshape(-1)))
            loss.backward()
            opt.step()
            opt.clear_grad()
        model.eval()
        return model

    pit.seed(0)
    target = train(make(512, 8, 8, 1024), 80)
    pit.seed(1)
    draft = train(make(128, 2, 4, 256), 80)
    for m in (target, draft):
        for p in m.parameters():
            p._data = p._data.astype(jnp.bfloat16)

    prompt, max_new, reps = 64, 32, 8
    ids = batch(np.random.RandomState(7), 1)[:, :prompt]
    se = SpeculativeEngine(target, draft, num_draft_tokens=4,
                           cache_bucket=128, prompt_bucket=prompt)
    spec_ms = _marginal_decode_ms(se, ids, max_new, reps)
    accept = se.last_acceptance
    plain = GenerationEngine(target, cache_bucket=128,
                             prompt_bucket=prompt)
    plain_ms = _marginal_decode_ms(plain, ids, max_new, reps)
    return accept, spec_ms, plain_ms


def _serving_bench(on_tpu: bool):
    """Continuous-batching serving throughput vs the sequential
    baseline: 8 synthetic clients with mixed prompt lengths, all
    decoding greedily for the same budget.  Sequential = 8 back-to-back
    bs-1 ``generate()`` calls (one client at a time, the pre-serving
    deployment story); continuous = the same 8 requests submitted
    concurrently to ``serving.EngineCore``, sharing fused decode steps.
    Both sides are compile-warmed first so the ratio measures the
    scheduler, not XLA.  TTFT/ITL percentiles come from the core's own
    ServingMetrics — the same numbers ``GET /metrics`` serves."""
    import threading

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import EngineCore

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_clients, max_new = 8, 48
    lens = [16, 32] * (n_clients // 2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    g = GenerationConfig(max_new_tokens=max_new)

    # sequential baseline: each client waits for the previous one
    seq_eng = PagedGenerationEngine(model, page_size=16, prompt_bucket=16)
    for p in prompts[:2]:
        seq_eng.generate(p[None], g)          # compile (one per plen)
    t0 = time.perf_counter()
    for p in prompts:
        seq_eng.generate(p[None], g)
    seq_tps = n_clients * max_new / (time.perf_counter() - t0)

    # max_model_len bounds the per-slot page-table width AND the pool —
    # leaving it at max_position_embeddings makes every decode step drag
    # a 4x-oversized pool through the scan carry (XLA copies it on
    # platforms where the scatter isn't done in place)
    core = EngineCore(
        PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
        max_batch=n_clients,
        max_model_len=max(lens) + max_new).start()
    try:
        for p in prompts[:2]:                 # compile-warm both plens
            core.submit(p, g)[0].result(timeout=600)
        core.metrics.reset()
        core.steplog.clear()                  # drop compile-inflated steps
        reqs = [None] * n_clients

        def client(i):
            reqs[i] = core.submit(prompts[i], g)[0]

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in reqs:
            r.result(timeout=600)
        cont_s = time.perf_counter() - t0
        cont_tps = sum(r.emitted for r in reqs) / cont_s
        snap = core.metrics_snapshot()
        steps = core.steplog.summary()
    finally:
        core.close()

    # native-histogram tails next to the reservoir percentiles, plus the
    # steplog's analytic-vs-measured step-cost fit (ROADMAP: cost-model
    # scheduling feeds off this error signal)
    from paddle_infer_tpu.observability import histogram as _hist

    def _hq(key, q):
        s = (snap.get("histograms") or {}).get(key)
        v = _hist.quantile(s, q) if s else None
        return round(v, 5) if v is not None else None

    model = steps.get("decode_model") or {}
    out = {
        "clients": n_clients,
        "max_new_tokens": max_new,
        "sequential_tokens_per_s": round(seq_tps, 1),
        "continuous_tokens_per_s": round(cont_tps, 1),
        "speedup": round(cont_tps / seq_tps, 2),
        "ttft_p50_s": round(snap["ttft_s"]["p50_recent"], 4),
        "ttft_p99_s": round(snap["ttft_s"]["p99_recent"], 4),
        "itl_p50_s": round(snap["inter_token_latency_s"]["p50_recent"], 5),
        "mean_batch_occupancy": round(snap["occupancy"]["mean"], 3),
        "ttft_p99_hist_s": _hq("ttft", 0.99),
        "step_wall_p99_hist_s": _hq("step_wall", 0.99),
        "queue_wait_p50_hist_s": _hq("queue_wait", 0.50),
        "steplog_records": steps.get("records", 0),
        "step_model_n": model.get("n", 0),
    }
    if model.get("mean_abs_rel_err") is not None:
        out["step_model_mean_abs_rel_err"] = round(
            model["mean_abs_rel_err"], 4)
    if model.get("pearson_r") is not None:
        out["step_model_pearson_r"] = round(model["pearson_r"], 4)
    return out


def _speculative_bench(on_tpu: bool):
    """In-engine speculative decoding vs plain ragged serving: the same
    8 greedy clients, warm repeat traffic (prefix cache retained their
    first pass), with and without ``speculate=True``.  Repeat traffic
    is the speculation sweet spot the radix-tree draft source exists
    for: lookahead proposes the retained continuation, the verify row
    accepts nearly everything, and a decode step emits up to
    ``num_draft_tokens + 1`` tokens for one launch.  Greedy streams
    must stay BITWISE IDENTICAL between the two cores — speculation is
    a throughput knob, never a correctness knob."""
    import threading

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import EngineCore

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_clients, max_new = 8, 48
    lens = [16, 32] * (n_clients // 2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    g = GenerationConfig(max_new_tokens=max_new)

    def run(speculate):
        # retention headroom is load-bearing: the measured pass needs
        # the warm pass's retained radix tree (the draft source) to
        # survive NEXT TO all 8 live reservations — without it a full
        # batch evicts the retained continuations on admission and
        # lookahead goes blind.  Headroom widens only the pool, not the
        # per-slot page tables, so the step stays cheap.
        core = EngineCore(
            PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
            max_batch=n_clients,
            max_model_len=max(lens) + max_new,
            enable_prefix_cache=True,
            prefix_cache_headroom_pages=48,
            speculate=speculate, num_draft_tokens=4).start()
        try:
            # first pass: compile-warm AND retain every stream into the
            # radix tree (the measured pass is repeat traffic)
            warm = [core.submit(p, g)[0] for p in prompts]
            for r in warm:
                r.result(timeout=600)
            core.metrics.reset()
            core.steplog.clear()
            reqs = [None] * n_clients

            def client(i):
                reqs[i] = core.submit(prompts[i], g)[0]

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for r in reqs:
                r.result(timeout=600)
            wall = time.perf_counter() - t0
            tps = sum(r.emitted for r in reqs) / wall
            streams = [np.asarray(r.padded_result()) for r in reqs]
            return tps, streams, core.metrics_snapshot()
        finally:
            core.close()

    base_tps, base_streams, _ = run(False)
    spec_tps, spec_streams, snap = run(True)
    identical = all(np.array_equal(a, b) for a, b
                    in zip(base_streams, spec_streams))
    spec = snap.get("speculation") or {}
    out = {
        "clients": n_clients,
        "max_new_tokens": max_new,
        "base_decode_tok_per_s": round(base_tps, 1),
        "spec_decode_tok_per_s": round(spec_tps, 1),
        "spec_decode_speedup": round(spec_tps / base_tps, 2),
        "speedup_target": 1.5,
        "meets_target": bool(spec_tps / base_tps >= 1.5),
        "identical_streams": identical,
        "acceptance_rate": round(spec.get("acceptance_rate", 0.0), 3),
        "wasted_ratio": round(spec.get("wasted_ratio", 0.0), 3),
        "spec_rows": spec.get("rows", 0),
        "drafts_proposed": spec.get("drafts_proposed", 0),
        "drafts_accepted": spec.get("drafts_accepted", 0),
    }
    return out


def _multi_tenant_bench(on_tpu: bool):
    """SLO-aware scheduler A/B: replay ONE recorded multi-tenant bursty
    trace (tools/loadgen.py JSONL — byte-identical offered load) against
    ``fifo`` and ``slack`` admission.  Under a burst the EDF policy
    moves tight-deadline chat traffic ahead of deadline-less batch
    prompts and predictively sheds requests already doomed to miss, so
    it should win on SLO attainment — while the per-request token
    streams stay BITWISE IDENTICAL (rid-pinned fold_in sampling keys
    make streams schedule-independent) and the decode executable never
    recompiles (planner decisions are data-only)."""
    import itertools

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability.compilelog import get_compile_log
    from paddle_infer_tpu.serving import EngineCore, RequestState
    from paddle_infer_tpu.serving import request as request_mod
    from tools import loadgen

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()

    # record the trace, then REPLAY THE FILE — the recorded JSONL is the
    # workload both policies see.  The mix deliberately OVERLOADS the
    # engine in bursts: deadline-less long batch prompts congest the
    # queue so FIFO makes tight-deadline chat traffic wait out its SLO
    tenants = (
        {"name": "chat", "weight": 4.0, "prompt_len": (4, 12),
         "max_new": (8, 16), "timeout_s": (0.5, 1.0),
         "shared_prefix_len": 0, "cache_salt": None},
        {"name": "rag", "weight": 2.0, "prompt_len": (12, 24),
         "max_new": (8, 16), "timeout_s": (1.0, 2.0),
         "shared_prefix_len": 8, "cache_salt": "tenant-rag"},
        {"name": "batch", "weight": 2.0, "prompt_len": (32, 48),
         "max_new": (24, 48), "timeout_s": None,
         "shared_prefix_len": 0, "cache_salt": None},
    )
    trace_path = "/tmp/pit_bench_trace.jsonl"
    loadgen.write_trace(trace_path, loadgen.generate_trace(
        0, duration_s=2.5, rate_per_s=48.0, tenants=tenants,
        vocab_size=cfg.vocab_size, burstiness=8.0, do_sample=True))
    events = loadgen.read_trace(trace_path)
    max_plen = max(len(e["prompt"]) for e in events)
    max_new = max(int(e["max_new"]) for e in events)
    n_deadline = sum(e["timeout_s"] is not None for e in events)

    def run(policy):
        # pin the rid counter so both runs hand out IDENTICAL rids in
        # trace order — per-request keys are fold_in(PRNGKey(seed), rid)
        request_mod._rid_counter = itertools.count(50_000)
        core = EngineCore(
            PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
            max_batch=8,
            max_model_len=max_plen + max_new,
            enable_prefix_cache=True,
            sched_policy=policy, slo_ttft_s=0.5, slo_itl_s=0.25)
        # never .start()ed: loadgen.replay owns the stepping
        try:
            g = GenerationConfig(max_new_tokens=16)
            rngw = np.random.RandomState(123)
            warm = [core.submit(rngw.randint(
                0, cfg.vocab_size, (n,)).astype(np.int32), g)[0]
                for n in (12, 28, 44)]
            while not all(r.done for r in warm):
                core.run_once(wait_s=0.0)
            # keep the steplog: its rolling fit IS the planner/admission
            # calibration the measured pass runs on
            core.metrics.reset()
            compiles0 = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            t0 = time.perf_counter()
            handles = loadgen.replay(core, events, timeout_s=240.0)
            wall = time.perf_counter() - t0
            compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - compiles0
            snap = core.metrics_snapshot()
            steps = core.steplog.summary()
        finally:
            core.close()
        done = {i: r for i, r in handles.items()
                if r.state == RequestState.DONE}
        attained = sum(1 for e in events if e["timeout_s"] is not None
                       and e["i"] in done)
        sched = snap.get("sched") or {}
        return {
            "attainment": attained / max(n_deadline, 1),
            "tenant_attainment": loadgen.tenant_attainment(events,
                                                           handles),
            "tenants": snap.get("tenants") or {},
            "goodput_tok_per_s":
                sum(r.emitted for r in done.values()) / wall,
            "completed": len(done),
            "predictive_sheds": int(sched.get("predictive_sheds", 0)),
            "deadline_misses": int(
                snap["counters"]["cancelled_deadline"]),
            "compiles": int(compiles),
            "streams": {i: np.asarray(r.tokens, np.int32)
                        for i, r in handles.items()},
            "planner": steps.get("planner_model") or {},
            "chunk_limited": int((sched.get("planner") or {})
                                 .get("chunk_limited_steps", 0)),
        }

    fifo = run("fifo")
    slack = run("slack")

    # bitwise stream check: any tokens both runs delivered for the same
    # trace event must agree on the common prefix, and requests DONE in
    # both runs must match exactly
    identical = True
    for i in fifo["streams"]:
        a, b = fifo["streams"][i], slack["streams"][i]
        n = min(a.size, b.size)
        if not np.array_equal(a[:n], b[:n]):
            identical = False
            break

    planner = slack["planner"]
    out = {
        "trace_events": len(events),
        "trace_deadline_events": n_deadline,
        "trace_path": trace_path,
        "slo_attainment_fifo": round(fifo["attainment"], 3),
        "slo_attainment_slack": round(slack["attainment"], 3),
        "slack_beats_fifo": bool(
            slack["attainment"] >= fifo["attainment"]),
        "goodput_tok_per_s_fifo": round(fifo["goodput_tok_per_s"], 1),
        "goodput_tok_per_s_slack": round(slack["goodput_tok_per_s"], 1),
        "shed_rate_slack": round(
            slack["predictive_sheds"] / len(events), 3),
        "deadline_misses_fifo": fifo["deadline_misses"],
        "deadline_misses_slack": slack["deadline_misses"],
        "identical_streams": identical,
        "post_warmup_decode_compiles": fifo["compiles"]
        + slack["compiles"],
        "planner_chunk_limited": slack["chunk_limited"],
        "planner_pred_n": planner.get("n", 0),
    }
    # per-tenant SLO accounting (journey plane): attainment per tenant
    # class under the slack policy, plus — for the tenant with the
    # worst e2e p99 — where its wall time actually went (top-3
    # latency-attribution buckets), so a fairness regression names its
    # victim AND its cause in one bench line
    for name, t in sorted(slack["tenant_attainment"].items()):
        if t["attainment"] is not None:
            out[f"tenant_{name}_attainment"] = round(t["attainment"], 3)
    from paddle_infer_tpu.observability.histogram import quantile
    worst, worst_p99 = None, -1.0
    for name, t in slack["tenants"].items():
        p99 = quantile(t.get("e2e"), 0.99)
        if p99 is not None and p99 > worst_p99:
            worst, worst_p99 = name, p99
    if worst is not None:
        buckets = slack["tenants"][worst].get("buckets") or {}
        top3 = sorted(buckets.items(), key=lambda kv: -kv[1])[:3]
        out["worst_p99_tenant"] = worst
        out["worst_p99_tenant_e2e_p99_s"] = round(worst_p99, 4)
        out["worst_p99_tenant_top_buckets"] = {
            b: round(v, 4) for b, v in top3}
    if planner.get("mean_abs_rel_err") is not None:
        out["planner_pred_wall_mean_abs_rel_err"] = round(
            planner["mean_abs_rel_err"], 4)
        out["planner_pred_wall_max_abs_rel_err"] = round(
            planner["max_abs_rel_err"], 4)
    return out


def _kv_tier_bench(on_tpu: bool):
    """Host-RAM KV tier A/B: replay ONE recorded oversubscription trace
    (tight-deadline chat bursts over sustained deadline-less batch work
    at 2-4x the slot capacity) under ``slack`` admission, without and
    with a host tier.  Without the tier the EDF policy predictively
    SHEDS doomed requests; with it every shed decision becomes a PARK
    of the deadline-richest victim — the doomed request admits into the
    freed slot and the victim resumes bitwise later, so deadline-less
    goodput holds at 1.0 with zero sheds while the token streams stay
    bitwise identical and the decode executable never recompiles (park
    and resume move page contents, never shapes)."""
    import itertools

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability.compilelog import get_compile_log
    from paddle_infer_tpu.serving import EngineCore, RequestState
    from paddle_infer_tpu.serving import request as request_mod
    from tools import loadgen

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()

    # offered load: the deadline-less oversubscription mix plus one
    # tight-deadline interactive class whose bursts force the slack
    # policy into shed-or-park decisions
    tenants = loadgen.oversubscription_tenants(1.0) + (
        {"name": "chat", "weight": 4.0, "prompt_len": (4, 12),
         "max_new": (8, 16), "timeout_s": (0.5, 1.0),
         "shared_prefix_len": 0, "cache_salt": None},
    )
    trace_path = "/tmp/pit_bench_kv_tier_trace.jsonl"
    loadgen.write_trace(trace_path, loadgen.generate_trace(
        1, duration_s=2.5, rate_per_s=40.0, tenants=tenants,
        vocab_size=cfg.vocab_size, burstiness=8.0, do_sample=True))
    events = loadgen.read_trace(trace_path)
    max_plen = max(len(e["prompt"]) for e in events)
    max_new = max(int(e["max_new"]) for e in events)
    batch_events = [e for e in events if e["timeout_s"] is None]

    def run(host_pages):
        request_mod._rid_counter = itertools.count(60_000)
        core = EngineCore(
            PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
            max_batch=4,
            max_model_len=max_plen + max_new,
            enable_prefix_cache=True,
            sched_policy="slack", slo_ttft_s=0.5, slo_itl_s=0.25,
            kv_host_pages=host_pages)
        try:
            g = GenerationConfig(max_new_tokens=16)
            rngw = np.random.RandomState(123)
            warm = [core.submit(rngw.randint(
                0, cfg.vocab_size, (n,)).astype(np.int32), g)[0]
                for n in (8, 16, 28)]
            while not all(r.done for r in warm):
                core.run_once(wait_s=0.0)
            core.metrics.reset()
            compiles0 = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            t0 = time.perf_counter()
            handles = loadgen.replay(core, events, timeout_s=240.0)
            wall = time.perf_counter() - t0
            compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - compiles0
            snap = core.metrics_snapshot()
        finally:
            core.close()
        done = {i: r for i, r in handles.items()
                if r.state == RequestState.DONE}
        tier = snap.get("kv_tier") or {}
        sched = snap.get("sched") or {}
        return {
            "goodput_batch": (sum(1 for e in batch_events
                                  if e["i"] in done)
                              / max(len(batch_events), 1)),
            "goodput_tok_per_s":
                sum(r.emitted for r in done.values()) / wall,
            "completed": len(done),
            "sheds": int(snap["resilience"]["requests_shed"])
            + int(sched.get("predictive_sheds", 0)),
            "deadline_misses": int(
                snap["counters"]["cancelled_deadline"]),
            "parks": int(tier.get("parks_total", 0)),
            "resumes": int(tier.get("resumes_total", 0)),
            "swap_fails": int(tier.get("swap_fails_total", 0)),
            "host_pages_peak": int(tier.get("host_pages_peak", 0)),
            "compiles": int(compiles),
            "streams": {i: np.asarray(r.tokens, np.int32)
                        for i, r in handles.items()},
        }

    base = run(0)
    tier = run(256)

    # bitwise gate: whatever both runs delivered for the same trace
    # event must agree on the common prefix — parked-and-resumed
    # streams equal the never-parked ones
    identical = True
    for i in base["streams"]:
        a, b = base["streams"][i], tier["streams"][i]
        n = min(a.size, b.size)
        if not np.array_equal(a[:n], b[:n]):
            identical = False
            break

    return {
        "trace_events": len(events),
        "trace_batch_events": len(batch_events),
        "trace_path": trace_path,
        "goodput_batch_base": round(base["goodput_batch"], 3),
        "goodput_batch_tier": round(tier["goodput_batch"], 3),
        "goodput_tok_per_s_base": round(base["goodput_tok_per_s"], 1),
        "goodput_tok_per_s_tier": round(tier["goodput_tok_per_s"], 1),
        "sheds_base": base["sheds"],
        "sheds_tier": tier["sheds"],
        "deadline_misses_base": base["deadline_misses"],
        "deadline_misses_tier": tier["deadline_misses"],
        "parks": tier["parks"],
        "resumes": tier["resumes"],
        "swap_fails": tier["swap_fails"],
        "host_pages_peak": tier["host_pages_peak"],
        "park_dont_drop": bool(
            tier["sheds"] == 0
            and tier["goodput_batch"] >= base["goodput_batch"]),
        "identical_streams": identical,
        "post_warmup_decode_compiles": base["compiles"]
        + tier["compiles"],
    }


def _structured_bench(on_tpu: bool):
    """Constrained decoding A/B: the SAME sampled offered batch served
    unconstrained and under per-request grammars (a tool-call JSON
    schema alternating with a short regex — distinct FSMs churning
    through one core).  Gates: every constrained stream fullmatches its
    grammar (conformance 1.0) with zero violating tokens, the grammar
    mask — per-row DATA through the one mixed-step executable — adds no
    post-warmup decode compiles, and the constrained ITL p50 overhead
    stays in the same ballpark as the unconstrained run (host-side
    state advance + mask gather per constrained row)."""
    import itertools

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability.compilelog import get_compile_log
    from paddle_infer_tpu.serving import (EngineCore, RequestState,
                                          conforms, decode_text,
                                          default_vocab)
    from paddle_infer_tpu.serving import request as request_mod

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    vocab = default_vocab(cfg.vocab_size)

    schema = {"type": "json_schema",
              "schema": {"type": "object",
                         "properties": {"tool": {"enum": ["search",
                                                          "calc"]},
                                        "n": {"type": "integer"}}}}
    regex = {"type": "regex", "pattern": "(yes|no|maybe)!"}
    n_requests = 16
    rngp = np.random.RandomState(7)
    prompts = [rngp.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(n_requests)]
    # per-request grammars for the constrained run: the worst-case
    # tool-call emission is 27 chars, so max_new=40 always completes
    specs = [schema if i % 2 == 0 else regex
             for i in range(n_requests)]

    def run(constrained):
        request_mod._rid_counter = itertools.count(70_000)
        core = EngineCore(
            PagedGenerationEngine(model, page_size=16,
                                  prompt_bucket=16),
            max_batch=4, max_model_len=56,
            grammar_vocab=vocab)
        try:
            g = GenerationConfig(max_new_tokens=40)
            warm = [core.submit(prompts[0], g)[0],
                    core.submit(prompts[1], g, grammar=regex)[0]]
            while not all(r.done for r in warm):
                core.run_once(wait_s=0.0)
            core.metrics.reset()
            compiles0 = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            t0 = time.perf_counter()
            reqs = [core.submit(
                p, GenerationConfig(max_new_tokens=40, do_sample=True,
                                    temperature=0.9, top_k=40, seed=i),
                grammar=(specs[i] if constrained else None))[0]
                for i, p in enumerate(prompts)]
            while not all(r.done for r in reqs):
                core.run_once(wait_s=0.0)
            wall = time.perf_counter() - t0
            compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - compiles0
            snap = core.metrics_snapshot()
        finally:
            core.close()
        done = [r for r in reqs if r.state == RequestState.DONE]
        conforming = sum(
            1 for i, r in enumerate(reqs)
            if r.state == RequestState.DONE
            and conforms(specs[i], decode_text(vocab, r.tokens)))
        structured = snap.get("structured") or {}
        return {
            "wall_s": wall,
            "completed": len(done),
            "tokens": sum(r.emitted for r in reqs),
            "itl_p50_s": snap["inter_token_latency_s"]["p50_recent"],
            "conforming": conforming,
            "violations": int(structured.get("violations", 0)),
            "incomplete": int(structured.get("incomplete", 0)),
            "cache_entries": int(structured.get("entries", 0)),
            "compile_seconds": float(
                structured.get("compile_seconds", 0.0)),
            "compiles": int(compiles),
        }

    plain = run(False)
    constrained = run(True)
    itl_p = plain["itl_p50_s"] or 0.0
    itl_c = constrained["itl_p50_s"] or 0.0
    return {
        "requests": n_requests,
        "conformance": round(
            constrained["conforming"] / float(n_requests), 3),
        "violations": constrained["violations"],
        "grammar_incomplete": constrained["incomplete"],
        "tok_per_s_plain": round(plain["tokens"] / plain["wall_s"], 1),
        "tok_per_s_constrained": round(
            constrained["tokens"] / constrained["wall_s"], 1),
        "itl_p50_ms_plain": round(itl_p * 1000.0, 3),
        "itl_p50_ms_constrained": round(itl_c * 1000.0, 3),
        "itl_p50_overhead_pct": (
            round((itl_c - itl_p) / itl_p * 100.0, 1) if itl_p else None),
        "grammar_cache_entries": constrained["cache_entries"],
        "grammar_compile_seconds": round(
            constrained["compile_seconds"], 4),
        "post_warmup_decode_compiles": plain["compiles"]
        + constrained["compiles"],
    }


def _adapter_tenancy_bench(on_tpu: bool):
    """Multi-LoRA tenancy scaling: the SAME offered load (48 requests
    whose adapter ids follow one recorded Zipf popularity draw) served
    with 1, 32 and 256 of the registered adapters addressable, over a
    fixed S=8 device-slot pool.  Residency churn (hundreds of tenants
    over 7 usable slots) must stay DATA — uploads are ``.at[slot].set``
    payload rebinds into fixed-shape pools, so the decode executable
    compiles once in warmup and every config must report ZERO
    post-warmup compiles; the cost of tenancy shows up as upload
    traffic and cache hit rate, never as recompiles."""
    import itertools

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability.compilelog import get_compile_log
    from paddle_infer_tpu.serving import EngineCore
    from paddle_infer_tpu.serving import request as request_mod
    from paddle_infer_tpu.serving.adapters import (AdapterStore,
                                                   adapter_layer_spec)

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=128,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    spec = adapter_layer_spec(model)
    rank, slots, n_req, max_new = 8, 8, 48, 8

    # one arena with all 256 tenants registered up front: the 1- and
    # 32-adapter configs address a prefix of the SAME store, so host
    # registration cost is identical and only residency churn varies
    frng = np.random.RandomState(7)
    store = AdapterStore(spec, rank=rank)
    for j in range(256):
        store.add(f"bench-{j}", {
            p: (frng.randn(d_in, rank).astype(np.float32) * 0.05,
                frng.randn(rank, d_out).astype(np.float32) * 0.05)
            for p, (d_in, d_out) in spec.items()})

    g = GenerationConfig(max_new_tokens=max_new)
    prng = np.random.RandomState(11)
    prompts = [prng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prng.randint(6, 14, size=n_req)]
    # one popularity draw shared by every config: folding it modulo the
    # addressable-adapter count keeps the request sequence identical
    # while widening the tenant tail from 1 to 256 distinct ids
    draws = [int(z) - 1 for z in
             np.random.RandomState(23).zipf(1.5, size=n_req)]

    def run(n_adapters):
        request_mod._rid_counter = itertools.count(70_000)
        core = EngineCore(
            PagedGenerationEngine(model, page_size=16),
            max_batch=8, max_model_len=32, token_budget=32,
            prefill_chunk=16,
            adapter_store=store, adapter_slots=slots)
        try:
            warm = [core.submit(prompts[0], g)[0],
                    core.submit(prompts[1], g, adapter_id="bench-0")[0]]
            while not all(r.done for r in warm):
                core.run_once()
            core.metrics.reset()
            compiles0 = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            c0 = core._adapters.summary()
            t0 = time.perf_counter()
            reqs = [core.submit(
                p, g, adapter_id=f"bench-{draws[k] % n_adapters}")[0]
                for k, p in enumerate(prompts)]
            while not all(r.done for r in reqs):
                core.run_once()
            wall = time.perf_counter() - t0
            toks = sum(r.emitted for r in reqs)
            compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - compiles0
            snap = core.metrics_snapshot()
            c1 = core._adapters.summary()
        finally:
            core.close()
        hits = c1["hits"] - c0["hits"]
        lookups = hits + c1["misses"] - c0["misses"]
        itl_p99 = snap["inter_token_latency_s"]["p99_recent"]
        return {
            "tok_per_s": round(toks / wall, 1),
            "itl_p99_s": round(itl_p99, 5) if itl_p99 else None,
            "hit_rate": round(hits / max(lookups, 1), 3),
            "uploads": c1["uploads"] - c0["uploads"],
            "evictions": c1["evictions"] - c0["evictions"],
            "post_warmup_decode_compiles": int(compiles),
        }

    out = {"device_slots": slots, "rank": rank, "requests": n_req,
           "registered_adapters": 256}
    total_compiles = 0
    for n in (1, 32, 256):
        r = run(n)
        total_compiles += r["post_warmup_decode_compiles"]
        out[f"adapters_{n}"] = r
    out["churn_zero_recompiles"] = bool(total_compiles == 0)
    return out


def _prefix_cache_bench(on_tpu: bool):
    """Prefix-cache TTFT evidence: N clients sharing one long system
    prompt (distinct short tails), admitted one at a time so TTFT is
    pure admission + prefill.  The cold pass gives every client its own
    ``cache_salt`` (no sharing possible); the warm pass runs them in one
    salt domain after a seed request populated the radix tree, so each
    admission maps the shared pages and prefills only the tail bucket.
    Every plen bucket, the page-copy program and the decode chunk are
    compile-warmed first, so the delta measures prefill work saved, not
    XLA."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import EngineCore

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_clients, sys_len, tail_len, max_new = 8, 96, 8, 16
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab_size, (sys_len,)).astype(np.int32)

    def prompt():
        return np.concatenate([
            system,
            rng.randint(0, cfg.vocab_size, (tail_len,)).astype(np.int32)])

    g = GenerationConfig(max_new_tokens=max_new)
    core = EngineCore(
        PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
        max_batch=4,
        max_model_len=sys_len + tail_len + max_new,
        enable_prefix_cache=True).start()
    try:
        # compile warmup: cold full-prompt plen, warm suffix plen, the
        # CoW page-copy program and the fused decode chunk
        w = prompt()
        core.submit(w, g, cache_salt="warmup")[0].result(timeout=600)
        core.submit(prompt(), g, cache_salt="warmup")[0].result(
            timeout=600)
        core.submit(w, g, cache_salt="warmup")[0].result(timeout=600)

        def ttft_p50(reqs):
            ts = sorted(r.first_token_at - r.arrival for r in reqs)
            return ts[len(ts) // 2]

        # cold pass: per-client salts — no request can reuse another's
        cold_reqs = []
        for i in range(n_clients):
            (r,) = core.submit(prompt(), g, cache_salt=f"cold-{i}")
            r.result(timeout=600)
            cold_reqs.append(r)

        # warm pass: one salt domain, tree seeded by the first request
        core.submit(prompt(), g, cache_salt="shared")[0].result(
            timeout=600)
        before = core.prefix_cache.stats_snapshot()
        warm_reqs = []
        for i in range(n_clients):
            (r,) = core.submit(prompt(), g, cache_salt="shared")
            r.result(timeout=600)
            warm_reqs.append(r)
        after = core.prefix_cache.stats_snapshot()
    finally:
        core.close()
    cold_p50 = ttft_p50(cold_reqs)
    warm_p50 = ttft_p50(warm_reqs)
    warm_q = after["queries"] - before["queries"]
    warm_hits = after["hits"] - before["hits"]
    return {
        "clients": n_clients,
        "system_prompt_tokens": sys_len,
        "tail_tokens": tail_len,
        "ttft_p50_cold_s": round(cold_p50, 4),
        "ttft_p50_warm_s": round(warm_p50, 4),
        "ttft_speedup": round(cold_p50 / warm_p50, 2),
        "warm_hit_rate": round(warm_hits / warm_q, 3) if warm_q else 0.0,
        "cached_token_ratio": round(after["token_ratio"], 3),
        "cow_copies": after["cow_copies"],
        "evicted_blocks": after["evicted_blocks"],
        "cached_blocks": after["cached_blocks"],
    }


def _kv_logit_amplification(model, cfg) -> float:
    """Loose first-order operator-norm amplification of a KV-domain
    perturbation into the logit domain.  Sound ingredients only —
    LayerNorm output is elementwise bounded by ``sqrt(d)*max|γ| +
    max|β|``, its Lipschitz constant by ``2*max|γ|/sqrt(eps)`` (the eps
    floor bounds 1/σ), softmax weights move at most ``2*max|Δlogit|``
    in total variation, attention output is a convex combination of V
    rows, GELU is 1.13-Lipschitz — so the product DOMINATES the true
    sensitivity but is loose by orders of magnitude (the 1/sqrt(eps)
    factor per LN).  The tight per-element bound lives in the KV domain
    (``kv_dequant_error_bound``); this factor only translates it to a
    formally-sound logit-domain ceiling for the bench gate."""
    params = {n: np.asarray(p._data, np.float64)
              for n, p in model.named_parameters()}
    d = cfg.hidden_size
    dh = d // cfg.num_attention_heads

    def opn(w):
        # ∞-operator norm of x -> x @ w for [in, out] weights
        return float(np.max(np.sum(np.abs(w), axis=0)))

    layers = []
    for l in range(cfg.num_hidden_layers):
        p = f"gpt.layers.{l}."
        eps1 = float(model.gpt.layers[l].norm1.epsilon)
        eps2 = float(model.gpt.layers[l].norm2.epsilon)
        g1 = float(np.max(np.abs(params[p + "norm1.weight"])))
        g2 = float(np.max(np.abs(params[p + "norm2.weight"])))
        b1 = float(np.max(np.abs(params[p + "norm1.bias"])))
        B1 = np.sqrt(d) * g1 + b1
        wq, _, wv = np.split(params[p + "self_attn.qkv_proj.weight"],
                             3, axis=1)
        bq, _, bv = np.split(params[p + "self_attn.qkv_proj.bias"], 3)
        qmax = B1 * opn(wq) + float(np.max(np.abs(bq)))
        vmax = B1 * opn(wv) + float(np.max(np.abs(bv)))
        no = opn(params[p + "self_attn.out_proj.weight"])
        # eps_kv lands twice: V rows (convex combination, factor 1) and
        # K rows (softmax total-variation, first order 2*sqrt(dh)*qmax,
        # weighted by the V magnitude)
        inject = no * (1.0 + 2.0 * np.sqrt(dh) * qmax * vmax)
        lln1 = 2.0 * g1 / np.sqrt(eps1)
        lln2 = 2.0 * g2 / np.sqrt(eps2)
        attn_lip = lln1 * no * (opn(wq) * 2.0 * np.sqrt(dh) * vmax
                                + opn(wv))
        mlp_lip = lln2 * 1.13 * opn(params[p + "mlp.fc1.weight"]) \
            * opn(params[p + "mlp.fc2.weight"])
        layers.append((inject, (1.0 + attn_lip) * (1.0 + mlp_lip)))
    gf = float(np.max(np.abs(params["gpt.final_norm.weight"])))
    llnf = 2.0 * gf / np.sqrt(float(model.gpt.final_norm.epsilon))
    nlm = opn(params["gpt.word_embeddings.weight"].T)
    total = 0.0
    for l, (inject, _) in enumerate(layers):
        down = 1.0
        for m in range(l + 1, len(layers)):
            down *= layers[m][1]
        total += inject * down
    return total * llnf * nlm


def _quantized_kv_bench(on_tpu: bool):
    """Quantized paged-KV evidence (docs/SERVING.md 'Quantized KV cache
    & weight-only serving'): the same model and workload served from
    the fp pool and from int8 pages with per-(page, head) scales.
    (a) resident concurrency at EQUAL pool bytes, from the allocated
        pools' actual per-page bytes (payload + scales);
    (b) bs=1 decode throughput fp vs int8 through engine.generate;
    (c) measured KV dequant error vs the analytic slot-0-protocol
        bound, and measured prefill logit max-abs error vs that bound
        amplified by the loose operator-norm factor;
    (d) zero post-warmup decode compiles while serving int8."""
    import jax
    import jax.numpy as jnp

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability.compilelog import get_compile_log
    from paddle_infer_tpu.ops.pallas.paged_attention import (
        dequantize_pages, kv_dequant_error_bound)

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    plen, max_new, page = 48, 32, 16
    prompt = rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32)
    g = GenerationConfig(max_new_tokens=max_new)

    fp_eng = PagedGenerationEngine(model, page_size=page,
                                   prompt_bucket=64)
    q_eng = PagedGenerationEngine(model, page_size=page, prompt_bucket=64,
                                  kv_dtype="int8")

    # ---- (b) decode throughput, compile-warmed, plus (d) compile gate
    def toks_per_s(eng, reps=3):
        eng.generate(prompt[None], g)                  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            eng.generate(prompt[None], g)
            best = min(best, time.perf_counter() - t0)
        return max_new / best

    fp_tps = toks_per_s(fp_eng)
    compiles0 = get_compile_log().summary()["post_warmup_decode_compiles"]
    q_tps = toks_per_s(q_eng)
    post_warmup = get_compile_log().summary()[
        "post_warmup_decode_compiles"] - compiles0

    # ---- (a) per-page pool bytes, measured from the live arrays
    kf, vf = fp_eng._ensure_pages()
    kq, vq = q_eng._ensure_pages()
    n_fp = kf[0].shape[0]
    n_q = kq[0][0].shape[0]
    fp_page_bytes = sum(x.nbytes for x in kf + vf) / n_fp
    q_page_bytes = sum(p.nbytes + s.nbytes for p, s in kq + vq) / n_q
    resident_ratio = fp_page_bytes / q_page_bytes

    # ---- (c) error accounting on an identical context: one windowed
    # prefill over serving pools, same block table on both engines
    plen_pad = 64
    max_pages = plen_pad // page

    def prefill_logits(eng):
        L = eng._num_layers
        pool = eng.serving_pool(max_pages + 1)
        pool.reserve(0, plen_pad)
        table = np.full((1, max_pages), max_pages, np.int32)
        t = pool.block_table(0)
        table[0, :len(t)] = np.asarray(t, np.int32)
        ids = np.zeros((1, plen_pad), np.int32)
        ids[0, :plen] = prompt

        def build():
            def run(params, ids, offsets, tables, k_pages, v_pages):
                marker = jnp.zeros((1,), jnp.int32)
                caches = [(k_pages[i], v_pages[i], tables, offsets,
                           marker) for i in range(L)]
                pos2d = offsets[:, None] + jnp.broadcast_to(
                    jnp.arange(plen_pad, dtype=jnp.int32)[None],
                    (1, plen_pad))
                logits, caches = eng._model_step(params, ids, pos2d,
                                                 None, caches)
                return (logits, [c[0] for c in caches],
                        [c[1] for c in caches])
            return jax.jit(run, donate_argnums=(4, 5))

        (lg,) = eng.run_paged_program(("qkv-bench-prefill", plen_pad),
                                      build, ids,
                                      np.zeros((1,), np.int32), table)
        return np.asarray(lg)[0, :plen], table[0]

    fp_logits, blocks = prefill_logits(fp_eng)
    q_logits, _ = prefill_logits(q_eng)
    logit_err = float(np.max(np.abs(q_logits - fp_logits)))

    kv_err = 0.0
    kv_bound = 0.0
    for fp_pool, q_pool in zip(fp_eng._k_pages + fp_eng._v_pages,
                               q_eng._k_pages + q_eng._v_pages):
        ref = np.asarray(fp_pool)[blocks]
        deq = np.asarray(dequantize_pages(q_pool))[blocks]
        kv_err = max(kv_err, float(np.max(np.abs(deq - ref))))
        kv_bound = max(kv_bound, kv_dequant_error_bound(
            ref, np.asarray(q_pool[1])[blocks]))
    logit_bound = kv_bound * _kv_logit_amplification(model, cfg)

    out = {
        "kv_dtype": "int8",
        "fp_page_bytes": int(fp_page_bytes),
        "int8_page_bytes": int(q_page_bytes),
        "resident_pages_ratio_equal_bytes": round(resident_ratio, 2),
        "decode_tok_s_fp": round(fp_tps, 1),
        "decode_tok_s_int8": round(q_tps, 1),
        "decode_tok_s_ratio": round(q_tps / fp_tps, 3),
        "kv_dequant_err_max": round(kv_err, 6),
        "kv_dequant_err_bound": round(kv_bound, 6),
        "logit_err_max": round(logit_err, 6),
        "logit_err_bound_first_order": float(f"{logit_bound:.3g}"),
        "post_warmup_decode_compiles": int(post_warmup),
    }
    out["kv_err_within_bound"] = bool(kv_err <= kv_bound)
    out["logit_err_within_bound"] = bool(logit_err <= logit_bound)
    out["resident_ratio_target_met"] = bool(resident_ratio >= 1.9)
    out["decode_within_10pct"] = bool(q_tps >= 0.9 * fp_tps)
    return out


def _resilience_bench(on_tpu: bool):
    """Goodput and token integrity under a seeded fault schedule: the
    same greedy workload runs twice — fault-free for the expected token
    streams and baseline wall time, then under a scripted ``FaultPlane``
    (a mid-decode engine crash that loses the KV pools, an injected
    allocator OOM, a second crash) with an ``EngineSupervisor``
    replaying the interrupted requests.  Token loss must be zero: every
    non-quarantined request finishes with exactly the stream the
    fault-free run produced."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                          FaultPlane, FaultSpec)

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_clients, max_new = 8, 24
    lens = [16, 32] * (n_clients // 2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    g = GenerationConfig(max_new_tokens=max_new)

    def run(plane):
        from paddle_infer_tpu.observability.compilelog import \
            get_compile_log
        core = EngineCore(
            PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
            max_batch=4,
            max_model_len=max(lens) + max_new,
            enable_prefix_cache=True, fault_plane=plane)
        sup = EngineSupervisor(core, watchdog_s=60.0,
                               max_retries=2).start()
        try:
            for p in prompts[:2]:             # compile-warm both plens
                core.submit(p, g)[0].result(timeout=600)
            core.metrics.reset()
            compiles0 = get_compile_log().summary()[
                "post_warmup_decode_compiles"]
            t0 = time.perf_counter()
            reqs = [core.submit(p, g)[0] for p in prompts]
            outs = []
            for r in reqs:
                try:
                    outs.append(r.result(timeout=600).tolist())
                except Exception:
                    outs.append(None)
            wall = time.perf_counter() - t0
            snap = core.metrics_snapshot()
            compiles = get_compile_log().summary()[
                "post_warmup_decode_compiles"] - compiles0
        finally:
            sup.close()
        return outs, wall, snap, compiles

    expected, base_wall, _, _ = run(None)

    # Scripted schedule.  Fire indices are absolute per-site counts and
    # the warmup pass burns some: 2 requests x 6 decode chunks = 12
    # decode.step fires, 2 kv.alloc fires.  The measured pass then sees
    # a crash inside the donated decode call (full KV loss -> restart +
    # replay of every in-flight row), an allocator OOM at admission
    # (degradation ladder + requeue), and a plain decode crash (KV
    # intact -> per-row replay).
    plane = FaultPlane([
        FaultSpec("decode.step", at=15, lose_kv=True),
        FaultSpec("kv.alloc", at=5, exc="MemoryError"),
        FaultSpec("decode.step", at=24),
    ], seed=0)
    got, fault_wall, snap, replay_compiles = run(plane)

    res = snap["resilience"]
    completed = sum(1 for o in got if o is not None)
    mismatched = sum(1 for e, o in zip(expected, got)
                     if o is not None and o != e)
    lost_tokens = sum(len(e) - len(o) for e, o in zip(expected, got)
                      if o is not None)
    return {
        "clients": n_clients,
        "max_new_tokens": max_new,
        "faults_injected": res["faults_injected"],
        "engine_restarts": res["engine_restarts"],
        "request_retries": res["request_retries"],
        "requests_quarantined": res["requests_quarantined"],
        "goodput": round(completed / n_clients, 3),
        "mismatched_streams": mismatched,
        "lost_tokens": lost_tokens,
        "replay_decode_compiles": replay_compiles,
        "wall_s_fault_free": round(base_wall, 3),
        "wall_s_faulted": round(fault_wall, 3),
        "recovery_overhead": round(fault_wall / base_wall, 2),
        "health_state_final": res["health_state"],
    }


def _kernel_summary() -> str:
    """Program/kernel inventory for the evidence bundle: every XLA
    compilation this process performed (site, cache key, wall time)
    plus the eager-op registry size."""
    from paddle_infer_tpu.core.dispatch import _REGISTRY
    from paddle_infer_tpu.observability import get_compile_log

    log = get_compile_log()
    lines = [f"registered eager ops: {len(_REGISTRY)}",
             f"xla compilations this process: {log.count()}", ""]
    for ev in log.events():
        lines.append(f"{ev.wall_s * 1e3:9.1f} ms  {ev.site:18s} "
                     f"{ev.key!r}")
    return "\n".join(lines) + "\n"


def _evidence_main(out_dir: str) -> int:
    """``--evidence-dir DIR``: one-shot evidence bundle.  Serves a few
    requests through a real EngineCore so the compile log, tracer ring,
    and metrics hold live data, then captures device probe + compile
    log + kernel summary + trace sample + metrics (JSON and Prometheus)
    into ONE directory with a manifest.  Needs the chip like the rest of
    this file: evidence gathered on another backend would describe
    another system."""
    import jax

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability import capture_bundle
    from paddle_infer_tpu.serving import EngineCore

    device = _require_tpu()
    pit.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=128,
                    max_position_embeddings=128, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    g = GenerationConfig(max_new_tokens=12)
    rng = np.random.RandomState(0)
    core = EngineCore(
        PagedGenerationEngine(model, page_size=16, prompt_bucket=16),
        max_batch=4, max_model_len=64).start()
    try:
        reqs = []
        for plen in (16, 16, 32):
            prompt = rng.randint(0, cfg.vocab_size, (plen,)) \
                .astype(np.int32)
            reqs += core.submit(prompt, g)
        for r in reqs:
            r.result(timeout=600)
        manifest = capture_bundle(
            out_dir, core=core, kernel_summary=_kernel_summary(),
            extra={"platform": device.platform,
                   "device_kind": device.device_kind,
                   "requests_served": len(reqs),
                   "coverage": [round(core.tracer.get(r.rid).coverage(), 4)
                                for r in reqs if core.tracer.get(r.rid)]})
    finally:
        core.close()
    print(json.dumps({"evidence_dir": os.path.abspath(out_dir),
                      "files": sorted(manifest["files"]),
                      "missing": manifest["missing"]}))
    return 0


if __name__ == "__main__":
    if "--evidence-dir" in sys.argv:
        sys.exit(_evidence_main(
            sys.argv[sys.argv.index("--evidence-dir") + 1]))
    sys.exit(main())
