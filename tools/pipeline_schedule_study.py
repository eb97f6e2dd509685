"""Measure the AD-transposed GPipe pipeline's memory/time vs micro-batch
count (the round-2 verdict's requested 'measured argument' in lieu of a
hand-coded 1F1B scheduler; see docs/PIPELINE.md for the written analysis).

Runs on the 8-device virtual CPU mesh: pp=2 x mp=2 x dp=2 over a
transformer PipelineStack; reports XLA's compiled memory breakdown
(temp = activations + collectives workspace) and wall-clock step time
for micro_batches in {1, 2, 4, 8}, with and without per-layer remat.

Usage:
  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=. python tools/pipeline_schedule_study.py
"""
import time

import numpy as np


def study(num_layers=8, hidden=64, heads=4, ffn=256, seq=32, batch=16,
          vocab=128):
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.models.transformer_block import (
        ParallelTransformerLayer)
    from paddle_infer_tpu.nn import functional as F
    from paddle_infer_tpu.nn.layer import Layer
    from paddle_infer_tpu.nn.layers_common import Embedding, Linear
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, LayerDesc,
                                           PipelineStack, fleet)

    rows = []
    for recompute in (False, True):
        for m in (1, 2, 4, 8):
            st = DistributedStrategy()
            st.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                 "pp_degree": 2}
            fleet.init(is_collective=True, strategy=st)

            class Model(Layer):
                def __init__(self):
                    super().__init__()
                    self.embed = Embedding(vocab, hidden)
                    self.stack = PipelineStack(
                        LayerDesc(ParallelTransformerLayer, hidden, heads,
                                  ffn, dropout=0.0, causal=True,
                                  normalize_before=True),
                        num_layers=num_layers, micro_batches=m,
                        recompute=recompute)
                    self.head = Linear(hidden, vocab)

                def forward(self, ids):
                    return self.head(self.stack(self.embed(ids)))

            pit.seed(0)
            model = Model()
            opt = pit.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=model.parameters())

            def loss_fn(mod, ids, labels):
                logits = mod(ids)
                return F.cross_entropy(logits.reshape((-1, vocab)),
                                       labels.reshape((-1,)),
                                       reduction="mean")

            step = FleetTrainStep(model, loss_fn, opt)
            rng = np.random.RandomState(0)
            ids = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
            labels = np.roll(ids, -1, 1).astype(np.int32)
            step(ids, labels).numpy()          # compile + run
            t0 = time.perf_counter()
            for _ in range(3):
                loss = step(ids, labels)
            loss.numpy()
            dt = (time.perf_counter() - t0) / 3
            ma = step.memory_analysis(ids, labels)
            rows.append((recompute, m,
                         ma.temp_size_in_bytes / 1e6,
                         ma.argument_size_in_bytes / 1e6,
                         dt * 1e3))
            print(f"recompute={recompute!s:5}  M={m}  "
                  f"temp={rows[-1][2]:8.2f} MB  "
                  f"args={rows[-1][3]:7.2f} MB  step={rows[-1][4]:7.1f} ms",
                  flush=True)
    return rows


def study_interleave(num_layers=8, hidden=64, heads=4, ffn=256, seq=32,
                     batch=16, vocab=128):
    """pp=4 bubble study (round-3 verdict #7): GPipe (v=1) vs virtual
    stages (v=2) at small M where the fill/drain bubble dominates —
    bubble fraction (pp-1)/(v*M + pp - 1)."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.models.transformer_block import (
        ParallelTransformerLayer)
    from paddle_infer_tpu.nn import functional as F
    from paddle_infer_tpu.nn.layer import Layer
    from paddle_infer_tpu.nn.layers_common import Embedding, Linear
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, LayerDesc,
                                           PipelineStack, fleet)

    rows = []
    for v in (1, 2):
        for m in (4, 8):
            st = DistributedStrategy()
            st.hybrid_configs = {"dp_degree": 2, "pp_degree": 4}
            fleet.init(is_collective=True, strategy=st)

            class Model(Layer):
                def __init__(self):
                    super().__init__()
                    self.embed = Embedding(vocab, hidden)
                    self.stack = PipelineStack(
                        LayerDesc(ParallelTransformerLayer, hidden, heads,
                                  ffn, dropout=0.0, causal=True,
                                  normalize_before=True),
                        num_layers=num_layers, micro_batches=m,
                        recompute=True, interleave=v)
                    self.head = Linear(hidden, vocab)

                def forward(self, ids):
                    return self.head(self.stack(self.embed(ids)))

            pit.seed(0)
            model = Model()
            opt = pit.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=model.parameters())

            def loss_fn(mod, ids, labels):
                logits = mod(ids)
                return F.cross_entropy(logits.reshape((-1, vocab)),
                                       labels.reshape((-1,)),
                                       reduction="mean")

            step = FleetTrainStep(model, loss_fn, opt)
            rng = np.random.RandomState(0)
            ids = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
            labels = np.roll(ids, -1, 1).astype(np.int32)
            step(ids, labels).numpy()
            t0 = time.perf_counter()
            for _ in range(5):
                loss = step(ids, labels)
            loss.numpy()
            dt = (time.perf_counter() - t0) / 5
            ma = step.memory_analysis(ids, labels)
            rows.append((v, m, ma.temp_size_in_bytes / 1e6, dt * 1e3))
            print(f"interleave={v}  M={m}  temp={rows[-1][2]:8.2f} MB  "
                  f"step={rows[-1][3]:7.1f} ms", flush=True)
    return rows


if __name__ == "__main__":
    import sys

    if "--interleave" in sys.argv:
        study_interleave()
    else:
        study()
