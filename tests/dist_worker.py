"""Spawned worker for the multi-process distributed harness test
(tests/test_multiprocess.py) — kept jax-import-free at module level so
the child process can pin its platform/device-count env before any
backend initializes (the reference keeps the same split:
test_dist_base.py's _run_cluster workers are standalone scripts)."""
import json
import os


def _model_and_data():
    import numpy as np

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.nn.layer import Layer
    from paddle_infer_tpu.nn.layers_common import Linear

    class MLP(Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = Linear(16, 32)
            self.fc2 = Linear(32, 8)

        def forward(self, x):
            from paddle_infer_tpu.nn import functional as F

            return self.fc2(F.gelu(self.fc1(x)))

    pit.seed(42)
    model = MLP()
    rng = np.random.RandomState(7)
    batches = [(rng.randn(8, 16).astype(np.float32),
                rng.randn(8, 8).astype(np.float32)) for _ in range(3)]
    return model, batches


def _train(model, batches, local_slice=None):
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, fleet)
    import jax

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8}
    fleet.init(is_collective=True, strategy=strategy,
               devices=jax.devices()[:8])
    opt = pit.optimizer.AdamW(learning_rate=1e-2,
                              parameters=model.parameters())

    def loss_fn(m, x, y):
        out = m(x)
        return ((out - y) * (out - y)).mean()

    step = FleetTrainStep(model, loss_fn, opt, strategy=strategy)
    losses = []
    for x, y in batches:
        if local_slice is not None:
            x, y = x[local_slice], y[local_slice]
        losses.append(float(step(x, y).numpy()))
    return losses


def dp_train_worker(out_dir):
    """2 processes x 4 CPU devices: DP train over the 8-device global
    mesh, each process feeding its half of every batch."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from paddle_infer_tpu.distributed import env as denv

    denv.init_parallel_env()
    import jax

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())
    idx = jax.process_index()
    model, batches = _model_and_data()
    local = slice(idx * 4, (idx + 1) * 4)
    losses = _train(model, batches, local_slice=local)
    with open(os.path.join(out_dir, f"proc{idx}.json"), "w") as f:
        json.dump({"losses": losses,
                   "local_devices": len(jax.local_devices())}, f)


def single_process_reference(out_dir):
    """Same job in one process over 8 devices (the parity oracle)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    model, batches = _model_and_data()
    losses = _train(model, batches)
    with open(os.path.join(out_dir, "single.json"), "w") as f:
        json.dump({"losses": losses}, f)
