#!/usr/bin/env python
"""Sharded-serving bench child: mp=2 over virtual CPU devices.

Run by hand with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2``
(the same pattern ``__graft_entry__.dryrun_multichip`` uses) — never
from a process that holds the chip: ``bench.py`` starts no children.
Prints ONE JSON line:

  - single-device vs mp=2 tokens/s and bitwise stream parity;
  - interconnect bytes per step with exact vs int8-quantized mp
    all-reduces, and the bytes saved;
  - the quantized wire format's measured error next to its analytic
    bound (microbench) plus the end-to-end max-abs logit error of a
    quantized forward vs the exact mp=2 forward.

Numbers here are CPU-relative (scheduling + bytes + numerics evidence,
not chip throughput); bench_diff still gates them round-over-round.

Usage (standalone):
  env PYTHONPATH=. JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      python tools/bench_sharded_child.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _serve(core, prompts, g):
    """Warm both plens, then time one measured pass; returns
    (streams, tokens_per_s, post_warmup_compiles, ici_per_step)."""
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
    return streams, tps, compiles, (ici, ici_saved)


def main() -> int:
    import jax

    if len(jax.devices()) < 2:
        print(json.dumps({"error": "needs >=2 devices (set XLA_FLAGS="
                                   "--xla_force_host_platform_device_"
                                   "count=2)"}))
        return 1

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import GenerationConfig
    from paddle_infer_tpu.parallel import collective
    from paddle_infer_tpu.parallel.topology import shard_map_norep
    from paddle_infer_tpu.serving import (EngineCore, ServingMesh,
                                          build_sharded_engine)

    pit.seed(0)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=128,
                    max_position_embeddings=128, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_clients, max_new = 4, 16
    lens = [12, 20] * (n_clients // 2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    g = GenerationConfig(max_new_tokens=max_new)

    def run(mesh_cfg):
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

    single_streams, single_tps, _, _ = run(ServingMesh())
    mp_streams, mp_tps, mp_compiles, (mp_ici, _) = run(ServingMesh(mp=2))
    q_cfg = ServingMesh(mp=2, quantized_allreduce="int8")
    _, q_tps, q_compiles, (q_ici, q_saved) = run(q_cfg)
    ledger = collective.LEDGER.snapshot()

    identical = all(np.array_equal(a, b)
                    for a, b in zip(single_streams, mp_streams))

    # ---- quantized wire format: measured error vs analytic bound.
    # 700 floats -> 3 blocks, indivisible by 2 ranks, so this also
    # exercises the exact-shape fallback path.
    from jax.sharding import PartitionSpec as P

    mesh = ServingMesh(mp=2).build(jax.devices()[:2])
    parts = np.random.RandomState(1).randn(2, 700).astype(np.float32)
    want = parts.sum(axis=0)
    got = shard_map_norep(
        lambda x: collective.quantized_psum(x[0], "mp", 2), mesh,
        in_specs=(P("mp"),), out_specs=P())(parts)
    q8_err = float(np.max(np.abs(np.asarray(got) - want)))
    q8_bound = float(collective.quantization_error_bound(list(parts)))

    # ---- end-to-end logit error of the quantized wire format: one
    # forward under the mp=2 mesh, exact vs int8 all-reduces
    from paddle_infer_tpu.inference.generation import _MeshContext

    ids = pit.to_tensor(prompts[1][None])
    with _MeshContext(mesh):
        exact_logits = np.asarray(model(ids).numpy(), np.float32)
    with _MeshContext(mesh, "int8"):
        quant_logits = np.asarray(model(ids).numpy(), np.float32)
    logit_err = float(np.max(np.abs(exact_logits - quant_logits)))

    print(json.dumps({
        "clients": n_clients,
        "max_new_tokens": max_new,
        "single_tokens_per_s": round(single_tps, 1),
        "mp2_tokens_per_s": round(mp_tps, 1),
        "mp2_quant_tokens_per_s": round(q_tps, 1),
        "identical_streams_mp2": identical,
        "post_warmup_compiles_mp2": mp_compiles,
        "post_warmup_compiles_quant": q_compiles,
        "ici_bytes_step_exact": round(mp_ici, 1),
        "ici_bytes_step_quant": round(q_ici, 1),
        "ici_bytes_saved_step": round(q_saved, 1),
        "ledger_bytes_saved_total": round(
            ledger["bytes_saved_total"], 1),
        "q8_allreduce_err": round(q8_err, 6),
        "q8_allreduce_err_bound": round(q8_bound, 6),
        "q8_within_bound": bool(q8_err <= q8_bound),
        "logit_max_abs_err_quant": round(logit_err, 6),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
