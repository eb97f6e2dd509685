"""System under test: the latent-attention + shared-expert MoE decoder
with hyper-connected residual streams and bias-corrected routing
(``model_type: xing4_0``; ``paddle_infer_tpu/models/latent_moe.py`` with
``hc_mult`` > 1 and ``topk_method: "noaux_tc"``) served through
``EngineCore`` — the same scheduler, page pool, prefix cache and
mixed-step program as every other served model.  Glue only: the model is
built from the configuration file's own keys (the source's
``config.json`` names), the benchmark's seeded arrays are bound to it,
and the engine is started the way ``tools/serve.py`` does.  Driving,
warming and evidence are ``llama_serving.System``'s.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import weights_xing4
from . import llama_serving
from .latent_moe_serving import ATTN, DENSE, EXPERT, NOT_MODEL_KEYS

MAPS = {f"{sub}_{leaf}": f"{sub}.{leaf}"
        for sub in ("hc_attn", "hc_ffn") for leaf in ("phi", "alpha", "bias")}
ROUTER_BIAS = {"e_bias": "mlp.experts.e_score_correction_bias"}


def program_names(cfg: dict, i: int) -> dict:
    names = dict(ATTN, **MAPS)
    names.update(DENSE if weights_xing4.is_dense(cfg, i)
                 else dict(EXPERT, **ROUTER_BIAS))
    return {k: f"model.layers.{i}.{v}" for k, v in names.items()}


class System(llama_serving.System):

    def build(self):
        from paddle_infer_tpu.models.latent_moe import (LatentMoEConfig,
                                                        LatentMoEForCausalLM)
        from paddle_infer_tpu.nn.initializer import abstract_parameters
        from paddle_infer_tpu.observability.steplog import StepLog
        from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                              ServingMesh,
                                              build_sharded_engine)

        cfg, dep = self.config, self.config["deployment"]
        mcfg = LatentMoEConfig(**{k: v for k, v in cfg.items()
                                  if k not in NOT_MODEL_KEYS})
        with abstract_parameters():
            model = LatentMoEForCausalLM(mcfg)
        model.eval()
        params = dict(model.named_parameters())
        dtype = jnp.dtype(cfg["torch_dtype"])
        w = weights_xing4.all_weights(cfg, self.seed, dtype)
        bound = {"model.embed_tokens.weight": w["embed"],
                 "lm_head.weight": w["lm_head"]}
        for i, lw in enumerate(w["layers"]):
            for k, name in program_names(cfg, i).items():
                bound[name] = lw[k]
        for name, p in params.items():
            if name in bound:
                value = bound[name]
            elif name.endswith("norm.weight"):
                value = jnp.ones(p._data.shape, dtype)
            else:
                raise KeyError(f"no seeded value for parameter {name}")
            if tuple(value.shape) != tuple(p._data.shape):
                raise ValueError(f"{name}: made {value.shape}, program "
                                 f"builds {tuple(p._data.shape)}")
            p._data = value
        unused = set(bound) - set(params)
        if unused:
            raise KeyError(f"seeded arrays the program has no parameter "
                           f"for: {sorted(unused)}")
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
            enable_prefix_cache=bool(dep["enable_prefix_cache"]),
            steplog=self.steplog)
        self.sup = EngineSupervisor(self.core).start()
        self.token_budget = int(self.core._token_budget)
        self.max_batch = int(dep["max_batch"])
