"""System under test: the decoder whose layer holds two latent attentions,
two dense SwiGLU blocks and one shortcut expert block with identity
experts (``model_type: longcat_flash``;
``paddle_infer_tpu/models/longcat_flash.py``) served through
``EngineCore`` — the same scheduler, page pool, prefix cache and
mixed-step program as every other served model.  Glue only: the model is
built from the configuration file's own keys (the source's
``config.json`` names), the benchmark's seeded arrays are bound to it,
and the engine is started the way ``tools/serve.py`` does, with the
deployment's ``token_budget``.  Driving, warming and evidence are
``llama_serving.System``'s.

The adapter imports what only a program with this model has, and every
seeded array must find a parameter of its own name and shape and every
parameter a seeded array (norm weights are one): a program that cannot be
this model is refused at once, before anything is made on the device.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import weights_longcat
from . import llama_serving
from .latent_moe_serving import NOT_MODEL_KEYS

ATTN = {"w_qa": "q_a_proj", "w_qb": "q_b_proj",
        "w_kva": "kv_a_proj_with_mqa", "w_kvb": "kv_b_proj",
        "w_o": "o_proj"}
DENSE = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
EXPERT = {"router": "mlp.gate_weight",
          "e_bias": "mlp.e_score_correction_bias", "e_gate": "mlp.w_gate",
          "e_up": "mlp.w_up", "e_down": "mlp.w_down"}


def program_names(i: int) -> dict:
    names = dict(EXPERT)
    for j in range(weights_longcat.SUB_LAYERS):
        names.update({f"a{j}_{k}": f"self_attn.{j}.{v}.weight"
                      for k, v in ATTN.items()})
        names.update({f"m{j}_{k}": f"mlps.{j}.{v}.weight"
                      for k, v in DENSE.items()})
    return {k: f"model.layers.{i}.{v}" for k, v in names.items()}


class System(llama_serving.System):

    def build(self):
        # what only a program with this model has
        from paddle_infer_tpu.models.longcat_flash import (
            LongcatFlashConfig, LongcatFlashForCausalLM)
        from paddle_infer_tpu.serving.programs import IDENTITY_COUNTERS  # noqa: F401

        from paddle_infer_tpu.nn.initializer import abstract_parameters
        from paddle_infer_tpu.observability.steplog import StepLog
        from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                              ServingMesh,
                                              build_sharded_engine)

        cfg, dep = self.config, self.config["deployment"]
        mcfg = LongcatFlashConfig(**{k: v for k, v in cfg.items()
                                     if k not in NOT_MODEL_KEYS})
        with abstract_parameters():
            model = LongcatFlashForCausalLM(mcfg)
        model.eval()
        params = dict(model.named_parameters())
        # before a single array is made: is the program this model?
        wanted = {"model.embed_tokens.weight", "lm_head.weight"}
        for i in range(int(cfg["num_layers"])):
            wanted.update(program_names(i).values())
        absent = sorted(wanted - set(params))
        unseeded = sorted(n for n in set(params) - wanted if "norm" not in n)
        if absent or unseeded:
            raise KeyError(
                f"{cfg['model_type']}: the program builds no parameter for "
                f"{len(absent)} seeded arrays (first: {(absent or [None])[0]})"
                f" and {len(unseeded)} parameters no array is seeded for "
                f"(first: {(unseeded or [None])[0]}): it cannot be this "
                "model")
        dtype = jnp.dtype(cfg["torch_dtype"])
        w = weights_longcat.all_weights(cfg, self.seed, dtype)
        bound = {"model.embed_tokens.weight": w["embed"],
                 "lm_head.weight": w["lm_head"]}
        for i, lw in enumerate(w["layers"]):
            for k, name in program_names(i).items():
                bound[name] = lw[k]
        for name, p in params.items():
            value = bound[name] if name in bound \
                else jnp.ones(p._data.shape, dtype)
            if tuple(value.shape) != tuple(p._data.shape):
                raise ValueError(f"{name}: made {value.shape}, program "
                                 f"builds {tuple(p._data.shape)}")
            p._data = value
        del w, bound
        smesh = ServingMesh(mp=int(dep.get("mp", 1)))
        engine = build_sharded_engine(
            model, smesh, page_size=int(dep["page_size"]),
            kv_dtype=dep.get("kv_dtype"), devices=self.devices[:1])
        self.engine = engine
        self.steplog = StepLog(capacity=65536)
        self.core = EngineCore(
            engine, max_batch=int(dep["max_batch"]),
            max_queue=int(dep.get("max_queue", 256)),
            max_model_len=int(dep["max_model_len"]),
            token_budget=dep.get("token_budget"),
            enable_prefix_cache=bool(dep["enable_prefix_cache"]),
            steplog=self.steplog)
        self.sup = EngineSupervisor(self.core).start()
        self.token_budget = int(self.core._token_budget)
        self.max_batch = int(dep["max_batch"])
