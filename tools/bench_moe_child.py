#!/usr/bin/env python
"""MoE-serving bench child: ep=2 over virtual CPU devices.

Run by hand with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2``
(the ``bench_sharded_child`` pattern) — never from a process that holds
the chip: ``bench.py`` starts no children.  Prints ONE JSON line:

  - decode tokens/s dense vs MoE (same hidden dims) and MoE ep=1 vs
    ep=2 with bitwise stream parity;
  - expert utilization skew and dropped-token ratio from the serving
    metrics snapshot;
  - per-step dispatch (all-to-all) bytes with fp vs int8-activation
    experts, and the bytes saved;
  - weight-only expert dequant error vs the per-channel analytic bound
    and the end-to-end logit error vs a loose first-order operator-norm
    ceiling (the quantized-KV bench pattern);
  - zero post-warmup compiles while serving MoE.

Numbers here are CPU-relative (scheduling + bytes + numerics evidence,
not chip throughput); bench_diff still gates them round-over-round.

Usage (standalone):
  env PYTHONPATH=. JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      python tools/bench_moe_child.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _opn(w):
    # ∞-operator norm of x -> x @ w, per expert for stacked [E, in, out]
    w = np.asarray(w, np.float64)
    return float(np.max(np.sum(np.abs(w), axis=-2)))


def _moe_logit_amplification(model, cfg, s1_max_opn, s2_max_opn):
    """Loose first-order ceiling on the logit error caused by the
    expert-weight dequant perturbation.  Same sound-but-loose
    ingredients as bench._kv_logit_amplification (LayerNorm Lipschitz
    2*max|γ|/sqrt(eps), GELU 1.13-Lipschitz, ∞-operator norms), with
    two MoE-specific facts: the combine is a sub-convex combination of
    expert outputs (gate probabilities sum to at most 1, so the worst
    expert bounds the mixture), and the per-layer injected error is
    first-order in the weight perturbation — routing flips are a
    second-order effect this ceiling deliberately ignores, which the
    orders-of-magnitude 1/sqrt(eps) slack dwarfs in practice."""
    d = cfg.hidden_size
    dh = d // cfg.num_attention_heads
    params = {n: np.asarray(p._data, np.float64)
              for n, p in model.named_parameters()}
    layers = []
    total_inject = []
    for l in range(cfg.num_hidden_layers):
        p = f"gpt.layers.{l}."
        blk = model.gpt.layers[l]
        g1 = float(np.max(np.abs(params[p + "norm1.weight"])))
        g2 = float(np.max(np.abs(params[p + "norm2.weight"])))
        b1n = float(np.max(np.abs(params[p + "norm1.bias"])))
        b2n = float(np.max(np.abs(params[p + "norm2.bias"])))
        lln1 = 2.0 * g1 / np.sqrt(float(blk.norm1.epsilon))
        lln2 = 2.0 * g2 / np.sqrt(float(blk.norm2.epsilon))
        B2 = np.sqrt(d) * g2 + b2n
        wq, _, wv = np.split(params[p + "self_attn.qkv_proj.weight"],
                             3, axis=1)
        bq, _, bv = np.split(params[p + "self_attn.qkv_proj.bias"], 3)
        B1 = np.sqrt(d) * g1 + b1n
        qmax = B1 * _opn(wq) + float(np.max(np.abs(bq)))
        vmax = B1 * _opn(wv) + float(np.max(np.abs(bv)))
        no = _opn(params[p + "self_attn.out_proj.weight"])
        attn_lip = lln1 * no * (_opn(wq) * 2.0 * np.sqrt(dh) * vmax
                                + _opn(wv))
        w1 = params[p + "mlp.w1"]
        w2 = params[p + "mlp.w2"]
        opn_w1, opn_w2 = _opn(w1), _opn(w2)
        mlp_lip = lln2 * 1.13 * opn_w1 * opn_w2
        layers.append((1.0 + attn_lip) * (1.0 + mlp_lip))
        # injected FFN-output error: Δ(act(hW1+b1)W2) to first order,
        # |h|∞ ≤ B2, |act(x)| ≤ |x|, combine sub-convex
        b_hid = B2 * opn_w1 + float(np.max(np.abs(params[p + "mlp.b1"])))
        total_inject.append(1.13 * B2 * s1_max_opn * opn_w2
                            + b_hid * s2_max_opn)
    gf = float(np.max(np.abs(params["gpt.final_norm.weight"])))
    llnf = 2.0 * gf / np.sqrt(float(model.gpt.final_norm.epsilon))
    nlm = _opn(params["gpt.word_embeddings.weight"].T)
    total = 0.0
    for l, inject in enumerate(total_inject):
        down = 1.0
        for m in range(l + 1, len(layers)):
            down *= layers[m]
        total += inject * down
    return total * llnf * nlm


def _serve(core, prompts, g):
    """Warm, then one measured pass; returns (streams, tok/s,
    post_warmup_compiles, (ici_per_step, ici_saved_per_step), moe
    snapshot section)."""
    from paddle_infer_tpu.observability.compilelog import get_compile_log

    for p in prompts[:2]:
        core.submit(p, g)[0].result(timeout=600)
    core.metrics.reset()
    core.steplog.clear()
    compiles0 = get_compile_log().summary()["post_warmup_decode_compiles"]
    t0 = time.perf_counter()
    reqs = [core.submit(p, g)[0] for p in prompts]
    for r in reqs:
        r.result(timeout=600)
    wall = time.perf_counter() - t0
    tps = sum(r.emitted for r in reqs) / wall
    steps = core.steplog.summary()
    n = max(1, steps.get("records", 1))
    ici = steps.get("ici_bytes_est_total", 0.0) / n
    ici_saved = steps.get("ici_bytes_saved_total", 0.0) / n
    compiles = get_compile_log().summary()[
        "post_warmup_decode_compiles"] - compiles0
    streams = [np.asarray(r.padded_result()) for r in reqs]
    moe = core.metrics_snapshot().get("moe")
    return streams, tps, compiles, (ici, ici_saved), moe


def main() -> int:
    import jax

    if len(jax.devices()) < 2:
        print(json.dumps({"error": "needs >=2 devices (set XLA_FLAGS="
                                   "--xla_force_host_platform_device_"
                                   "count=2)"}))
        return 1

    import jax.numpy as jnp

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import GenerationConfig
    from paddle_infer_tpu.models import (GPTConfig, GPTForCausalLM,
                                         GPTMoEForCausalLM, MoEConfig)
    from paddle_infer_tpu.parallel import collective
    from paddle_infer_tpu.quantization.moe import (Int8MoELayer,
                                                   _moe_weight_dequantize)
    from paddle_infer_tpu.quantization.weight_only import quantize_model
    from paddle_infer_tpu.serving import (EngineCore, ServingMesh,
                                          build_sharded_engine)

    dims = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=128,
                max_position_embeddings=128, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
    moe_cfg = MoEConfig(num_experts=4, moe_top_k=2,
                        moe_capacity_factor=2.0, **dims)

    def fresh(kind):
        # identical weights per kind across variants: rebuild from a
        # fixed seed instead of deep-copying converted layers
        pit.seed(0)
        m = (GPTForCausalLM(GPTConfig(**dims)) if kind == "dense"
             else GPTMoEForCausalLM(moe_cfg))
        m.eval()
        return m

    n_clients, max_new = 4, 16
    lens = [12, 20] * (n_clients // 2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, dims["vocab_size"], (n,)).astype(np.int32)
               for n in lens]
    g = GenerationConfig(max_new_tokens=max_new)

    def run(model, mesh_cfg):
        collective.LEDGER.reset()
        engine = build_sharded_engine(model, mesh_cfg, page_size=16)
        core = EngineCore(
            engine, max_batch=n_clients, max_model_len=max(lens) + max_new,
            serving_mesh=(mesh_cfg if mesh_cfg.n_devices > 1
                          or mesh_cfg.quantized_allreduce else None),
        ).start()
        try:
            return _serve(core, prompts, g)
        finally:
            core.close()

    _, dense_tps, _, _, _ = run(fresh("dense"), ServingMesh())
    (moe_streams, moe_tps, moe_compiles, _, moe_snap) = run(
        fresh("moe"), ServingMesh())
    (ep_streams, ep_tps, ep_compiles, (ep_ici, _), _) = run(
        fresh("moe"), ServingMesh(ep=2))

    identical = all(np.array_equal(a, b)
                    for a, b in zip(moe_streams, ep_streams))

    # ---- int8-activation experts shrink the ep dispatch leg to 1 B/elem
    m_act = fresh("moe")
    from paddle_infer_tpu.parallel.moe import MoELayer
    from paddle_infer_tpu.quantization.slim import _swap
    _swap(m_act, (MoELayer,), lambda sub: Int8MoELayer.from_moe(sub),
          None)
    (q_streams, q_tps, q_compiles, (q_ici, q_saved), _) = run(
        m_act, ServingMesh(ep=2))

    # ---- weight-only experts: dequant error vs the per-channel
    # analytic bound (round-to-nearest under absmax scaling errs at
    # most scale/2 per element), then the end-to-end logit error vs
    # the loose first-order operator-norm ceiling
    m_ref = fresh("moe")
    m_wo = fresh("moe")
    quantize_model(m_wo, algo="weight_only_int8",
                   skip=lambda name, lay: not isinstance(lay, MoELayer))
    wo_err = 0.0
    wo_within = True
    s1_opn = s2_opn = 0.0
    for ref_blk, wo_blk in zip(m_ref.gpt.layers, m_wo.gpt.layers):
        for wn, qn, sn in (("w1", "qw1", "s1"), ("w2", "qw2", "s2")):
            ref_w = np.asarray(getattr(ref_blk.mlp, wn)._data, np.float32)
            q = getattr(wo_blk.mlp, qn)._data
            s = np.asarray(getattr(wo_blk.mlp, sn)._data, np.float32)
            deq = np.asarray(_moe_weight_dequantize(
                jnp.asarray(q), jnp.asarray(s), "weight_only_int8",
                jnp.float32))
            err = np.abs(deq - ref_w)                       # [E, in, out]
            wo_err = max(wo_err, float(err.max()))
            # per-(expert, out-channel) containment, not just the max
            wo_within = wo_within and bool(
                np.all(err.max(axis=1) <= s / 2.0 + 1e-7))
            opn_bound = float(np.max(ref_w.shape[1] * s / 2.0))
            if wn == "w1":
                s1_opn = max(s1_opn, opn_bound)
            else:
                s2_opn = max(s2_opn, opn_bound)
    wo_bound = max(s1_opn / moe_cfg.hidden_size,
                   s2_opn / moe_cfg.intermediate_size)

    ids = pit.to_tensor(prompts[1][None])
    ref_logits = np.asarray(m_ref(ids).numpy(), np.float32)
    wo_logits = np.asarray(m_wo(ids).numpy(), np.float32)
    logit_err = float(np.max(np.abs(ref_logits - wo_logits)))
    logit_bound = _moe_logit_amplification(m_ref, moe_cfg, s1_opn, s2_opn)

    print(json.dumps({
        "clients": n_clients,
        "max_new_tokens": max_new,
        "num_experts": moe_cfg.num_experts,
        "dense_tokens_per_s": round(dense_tps, 1),
        "moe_tokens_per_s": round(moe_tps, 1),
        "moe_ep2_tokens_per_s": round(ep_tps, 1),
        "moe_ep2_int8_act_tokens_per_s": round(q_tps, 1),
        "identical_streams_ep2": identical,
        "post_warmup_compiles_moe": moe_compiles,
        "post_warmup_compiles_ep2": ep_compiles,
        "post_warmup_compiles_int8_act": q_compiles,
        "expert_utilization_skew": round(
            moe_snap["utilization_skew"], 3),
        "dropped_token_ratio": round(moe_snap["dropped_ratio"], 4),
        "dispatch_bytes_step_exact": round(ep_ici, 1),
        "dispatch_bytes_step_quant": round(q_ici, 1),
        "dispatch_bytes_saved_step": round(q_saved, 1),
        "wo_expert_dequant_err_max": round(wo_err, 6),
        "wo_expert_dequant_err_bound": float(f"{wo_bound:.3g}"),
        "wo_err_within_bound": wo_within,
        "wo_logit_err_max": round(logit_err, 6),
        "wo_logit_err_bound_first_order": float(f"{logit_bound:.3g}"),
        "wo_logit_within_bound": bool(logit_err <= logit_bound),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
