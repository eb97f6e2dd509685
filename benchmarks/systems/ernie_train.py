"""System under test: ERNIE pretraining through ``fleet.init`` +
``FleetTrainStep`` (AMP O2 bf16), built as ``chip_smoke.py`` and
``bench.py``'s headline build it.  Glue only: sizes from the
configuration file, the benchmark's seeded float32 weights bound to the
program's parameters, the program's own compiled step with its state.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np


def program_names(layers: int) -> dict:
    """benchmark's weight name -> the program's parameter name."""
    e = "ernie.embeddings."
    out = {"word_emb": e + "word_embeddings.weight",
           "pos_emb": e + "position_embeddings.weight",
           "type_emb": e + "token_type_embeddings.weight",
           "emb_ln_w": e + "layer_norm.weight",
           "emb_ln_b": e + "layer_norm.bias",
           "pool_w": "ernie.pooler.dense.weight",
           "pool_b": "ernie.pooler.dense.bias",
           "mlm_w": "cls.transform.weight", "mlm_b": "cls.transform.bias",
           "mlm_ln_w": "cls.layer_norm.weight",
           "mlm_ln_b": "cls.layer_norm.bias", "dec_b": "cls.decoder_bias",
           "nsp_w": "nsp.weight", "nsp_b": "nsp.bias"}
    for i in range(layers):
        a, p = f"l{i}.", f"ernie.layers.{i}."
        out.update({
            a + "wqkv": p + "self_attn.qkv_proj.weight",
            a + "bqkv": p + "self_attn.qkv_proj.bias",
            a + "wo": p + "self_attn.out_proj.weight",
            a + "bo": p + "self_attn.out_proj.bias",
            a + "w1": p + "mlp.fc1.weight", a + "b1": p + "mlp.fc1.bias",
            a + "w2": p + "mlp.fc2.weight", a + "b2": p + "mlp.fc2.bias",
            a + "ln1_w": p + "norm1.weight", a + "ln1_b": p + "norm1.bias",
            a + "ln2_w": p + "norm2.weight", a + "ln2_b": p + "norm2.bias"})
    return out


def make_batches(config: dict, traffic: dict, seed: int):
    """``distinct_batches`` batches of rows that all differ: ids, padding
    mask (trailing ``pad_share`` of every row, carried as segment ids),
    MLM labels (-100 on padding) and NSP labels."""
    b, s = int(config["batch_size"]), int(config["seq_len"])
    v = int(config["vocab_size"])
    pad = max(1, int(round(s * float(traffic["pad_share"]))))
    out = []
    for j in range(int(traffic["distinct_batches"])):
        rng = np.random.default_rng([int(seed), 11, j])
        ids = rng.integers(0, v, (b, s), dtype=np.int64).astype(np.int32)
        mask = np.ones((b, s), np.int32)
        mask[:, s - pad:] = 0
        labels = rng.integers(0, v, (b, s), dtype=np.int64).astype(np.int32)
        labels[:, s - pad:] = -100
        nsp = rng.integers(0, 2, (b,), dtype=np.int64).astype(np.int32)
        out.append((ids, mask, labels, nsp))
    return out


@jax.jit
def _norms(tree):
    return {n: jnp.linalg.norm(a.astype(jnp.float32).ravel())
            for n, a in tree.items()}


@jax.jit
def _delta_norms(now, start):
    return {n: jnp.linalg.norm((now[n].astype(jnp.float32)
                                - start[n].astype(jnp.float32)).ravel())
            for n in now}


class System:
    kind = "training"

    def __init__(self, config: dict, devices, seed: int, traced: bool):
        self.config, self.devices = config, list(devices)
        self.seed, self.traced = int(seed), bool(traced)
        self.step = None

    def build(self):
        import paddle_infer_tpu as pit
        from paddle_infer_tpu.models import (ErnieConfig,
                                             ErnieForPretraining,
                                             ernie_pretrain_loss)
        from paddle_infer_tpu.nn.initializer import abstract_parameters
        from paddle_infer_tpu.parallel import (DistributedStrategy,
                                               FleetTrainStep, fleet)

        from .. import weights

        cfg = self.config
        seq = int(cfg["seq_len"])
        ecfg = ErnieConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            hidden_act=cfg["hidden_act"],
            hidden_dropout_prob=cfg["hidden_dropout_prob"],
            attention_probs_dropout_prob=cfg[
                "attention_probs_dropout_prob"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            layer_norm_eps=cfg["layer_norm_eps"])
        # deployment facts the configuration states as the program's flags
        self.flags = dict(cfg.get("program_flags", {}))
        pit.set_flags(self.flags)
        pit.seed(self.seed & 0x7FFFFFFF)     # the program's dropout stream
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1}
        strategy.amp = True
        strategy.amp_configs = dict(cfg["amp"])
        fleet.init(is_collective=True, strategy=strategy,
                   devices=self.devices[:1])
        with abstract_parameters():
            model = ErnieForPretraining(ecfg)
        made = weights.ernie_weights(cfg, seq, self.seed)
        names = program_names(int(cfg["num_hidden_layers"]))
        self.to_bench = {v: k for k, v in names.items()}
        params = dict(model.named_parameters())
        if set(params) != set(names.values()):
            raise KeyError(
                "the program's parameters and the benchmark's differ: "
                f"{sorted(set(params) ^ set(names.values()))[:6]}")
        for k, name in names.items():
            if tuple(made[k].shape) != tuple(params[name]._data.shape):
                raise ValueError(f"{name}: made {made[k].shape}, program "
                                 f"builds {tuple(params[name]._data.shape)}")
            params[name]._data = made[k]
        del made
        model.train()
        o = cfg["optimizer"]
        opt = pit.optimizer.AdamW(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"], parameters=model.parameters())

        def loss_fn(m, ids, mask, labels, nsp_labels):
            mlm, nsp = m(ids, attention_mask=mask)
            return ernie_pretrain_loss(mlm, nsp, labels, nsp_labels)

        self.step = FleetTrainStep(model, loss_fn, opt, strategy=strategy)
        self.model = model

    def call(self, batch):
        """The window's own call: one step, the loss left on the device."""
        return self.step(*batch)._data

    def first_steps(self, batches, n: int = 3) -> dict:
        """Drive the compiled step through its first ``n`` steps with the
        window's own call and feed; read what the check compares."""
        b1 = float(self.config["optimizer"]["beta1"])
        start = {k: jnp.copy(v) for k, v in self.step.params.items()}
        losses, grad_norms = [], None
        for t in range(n):
            losses.append(float(self.call(batches[t % len(batches)])))
            if grad_norms is None:
                m = {k: s["m"] for k, s in self.step.opt_state.items()}
                grad_norms = {k: float(v) / (1.0 - b1)
                              for k, v in _norms(m).items()}
        delta = {k: float(v) for k, v in
                 _delta_norms(self.step.params, start).items()}
        del start
        rename = lambda d: {self.to_bench[k]: v for k, v in d.items()}
        return {"losses": losses, "grad_norms": rename(grad_norms),
                "delta_norms": rename(delta)}

    def program_temp_bytes(self, batch):
        """Temporaries of the compiled step, from the program's own
        ``memory_analysis`` (the allocator's peak leaves them out)."""
        ma = self.step.memory_analysis(*batch)
        return int(getattr(ma, "temp_size_in_bytes", 0) or 0)

    def program_report(self) -> dict:
        """What decides which program was compiled, for the result line:
        the flags as the program reads them and the kernel autotuner's
        winners on file (none where ``use_autotune`` is off)."""
        import json
        import os

        import paddle_infer_tpu as pit
        from paddle_infer_tpu.ops.pallas import autotune  # noqa: F401 (its flags)
        from paddle_infer_tpu.utils.compile_cache import CHECKOUT

        flags = pit.get_flags(["use_autotune", "autotune_cache_file"])
        path = flags.get("autotune_cache_file") or os.path.join(
            CHECKOUT, ".autotune_cache.json")
        winners = {}
        if flags.get("use_autotune") and os.path.exists(path):
            with open(path) as f:
                winners = json.load(f)
        return {"flags": flags, "autotune_winners": winners}

    def free(self):
        self.step = self.model = None
        gc.collect()
