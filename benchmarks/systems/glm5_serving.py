"""System under test: the latent-attention + shared-expert MoE decoder
whose attention reads a learned indexer's selection (``model_type:
glm_moe_dsa``; ``paddle_infer_tpu/models/latent_moe.py`` with
``index_topk``) served through ``EngineCore`` — the same scheduler, page
pool, prefix cache and mixed-step program as every other served model.
Glue only: the model is built from the configuration file's own keys (the
source's ``config.json`` names), the benchmark's seeded arrays are bound
to it, and the engine is started the way ``tools/serve.py`` does, with
the deployment's ``token_budget`` where the file states one.  Driving,
warming and evidence are ``llama_serving.System``'s.

Every seeded array must find a parameter of its own name and shape, and
every parameter a seeded array (norm weights are one): a program that
does not build the indexer swallows the ``index_*`` keys and would serve
dense attention under this model's name, so the adapter refuses it at
once, before anything is made on the device.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import weights_glm5
from . import llama_serving
from .latent_moe_serving import ATTN, DENSE, EXPERT, NOT_MODEL_KEYS

INDEXER = {"idx_wq": "self_attn.indexer.wq_b.weight",
           "idx_wk": "self_attn.indexer.wk.weight",
           "idx_ww": "self_attn.indexer.weights_proj.weight",
           "idx_norm_w": "self_attn.indexer.k_norm.weight",
           "idx_norm_b": "self_attn.indexer.k_norm.bias"}
ROUTER_BIAS = {"e_bias": "mlp.experts.e_score_correction_bias"}


def program_names(cfg: dict, i: int) -> dict:
    names = dict(ATTN, **INDEXER)
    names.update(DENSE if weights_glm5.is_dense(cfg, i)
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
        # before a single array is made: is the program this model?
        wanted = {"model.embed_tokens.weight", "lm_head.weight"}
        for i in range(int(cfg["num_hidden_layers"])):
            wanted.update(program_names(cfg, i).values())
        absent = sorted(wanted - set(params))
        if absent:
            raise KeyError(
                f"the program builds no parameter for {len(absent)} seeded "
                f"arrays of {cfg['model_type']} (first: {absent[0]}): it "
                "cannot be this model")
        dtype = jnp.dtype(cfg["torch_dtype"])
        w = weights_glm5.all_weights(cfg, self.seed, dtype)
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
