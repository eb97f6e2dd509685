"""Measured-scaling feasibility study for BASELINE.md milestone #4
(ERNIE-3.5 10B trained TP+ZeRO on a v5p slice).

The 10B model cannot be materialised on this host (params + AdamW slots
exceed RAM), so the evidence is measured scaling: build the SAME hybrid
configuration (mp=4 x sharding=2, ZeRO-3, AMP O2 bf16) at three real
sizes on the 8-device virtual CPU mesh, read XLA's compiled
``memory_analysis()`` per-device numbers, fit the parameter-linear
memory model, and extrapolate to the 10B preset — then compare against
v5p HBM (95 GB/chip).  The same harness runs unchanged on real v5p
chips.

Usage:
  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=. python tools/scale_study.py
"""
import json
import time

import numpy as np

SEQ = 512          # study sequence (10B target trains at up to 2048)
BATCH = 8          # global batch for the study steps


def _build_step(preset, overrides=None):
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.models import (ErnieConfig, ErnieForPretraining,
                                         ernie_pretrain_loss)
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, fleet)

    cfg = ErnieConfig.from_preset(
        preset, max_position_embeddings=SEQ,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        **(overrides or {}))
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"mp_degree": 4, "sharding_degree": 2}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 3}
    strategy.amp = True
    strategy.amp_configs = {"level": "O2", "dtype": "bfloat16"}
    fleet.init(is_collective=True, strategy=strategy)
    pit.seed(0)
    model = ErnieForPretraining(cfg)
    opt = pit.optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())

    def loss_fn(m, ids, labels, nsp):
        mlm, nsp_logits = m(ids)
        return ernie_pretrain_loss(mlm, nsp_logits, labels, nsp)

    step = FleetTrainStep(model, loss_fn, opt, strategy=strategy)
    n_params = sum(int(p.size) for p in model.parameters())
    return step, cfg, n_params


def _measure(step):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 1000, (BATCH, SEQ)).astype(np.int32)
    labels = rng.randint(0, 1000, (BATCH, SEQ)).astype(np.int32)
    nsp = rng.randint(0, 2, (BATCH,)).astype(np.int32)
    t0 = time.perf_counter()
    step(ids, labels, nsp).numpy()
    compile_s = time.perf_counter() - t0
    ma = step.memory_analysis(ids, labels, nsp)
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "compile_s": round(compile_s, 1),
    }


def _reset():
    from paddle_infer_tpu.distributed.cost_model import _reset_fleet

    _reset_fleet()


def main():
    results = []
    for preset in ("ernie-3.0-base", "ernie-3.0-xbase", "ernie-1.3b"):
        _reset()
        step, cfg, n = _build_step(preset)
        m = _measure(step)
        m.update({"preset": preset, "n_params": n,
                  "layers_x_hidden": cfg.num_hidden_layers
                  * cfg.hidden_size})
        results.append(m)
        print(json.dumps(m), flush=True)
        del step
    _reset()

    # fit per-device bytes = a * n_params + b (argument = placed
    # param/optimizer state, the N-linear term; temp = activations,
    # roughly constant at fixed batch x seq)
    ns = np.array([r["n_params"] for r in results], np.float64)
    args = np.array([r["argument_bytes"] for r in results], np.float64)
    temps = np.array([r["temp_bytes"] for r in results], np.float64)
    a, b = np.polyfit(ns, args, 1)
    # activations scale with layers*hidden at fixed batch x seq
    lh = np.array([r["layers_x_hidden"] for r in results], np.float64)
    at, bt = np.polyfit(lh, temps, 1)

    from paddle_infer_tpu.models import ErnieConfig, ErnieForPretraining

    cfg10 = ErnieConfig.from_preset("ernie-3.5-10b")
    # parameter count without materialising: transformer algebra
    h, L, f, v = (cfg10.hidden_size, cfg10.num_hidden_layers,
                  cfg10.intermediate_size, cfg10.vocab_size)
    n10 = L * (4 * h * h + 2 * h * f + 2 * f + 9 * h) \
        + v * h + cfg10.max_position_embeddings * h + 4 * h \
        + h * h + h + 2 * h  # embeddings + pooler + norms (approx)
    pred_arg = a * n10 + b
    pred_temp = at * (L * h) + bt
    pred_total = pred_arg + pred_temp
    v5p_hbm = 95e9
    report = {
        "fit_bytes_per_param_per_device": round(float(a), 3),
        "fit_temp_bytes_per_layerhidden": round(float(at), 1),
        "n_params_10b": int(n10),
        "predicted_argument_bytes_per_device": int(pred_arg),
        "predicted_temp_bytes_per_device": int(pred_temp),
        "predicted_total_bytes_per_device": int(pred_total),
        "v5p_hbm_bytes": int(v5p_hbm),
        "fits_on_v5p_8chip_mp4_zero2": bool(pred_total < v5p_hbm),
    }
    print(json.dumps(report))
    return results, report


if __name__ == "__main__":
    main()
