"""System under test: the LLaMA-family decoder served through
``EngineCore`` — the scheduler, page pool and step program that
``tools/serve.py`` puts behind HTTP.  This file is the glue only: it
builds the model at the configuration's sizes, binds the benchmark's
seeded weights to it, starts the engine the way ``tools/serve.py`` does
(``build_sharded_engine`` -> ``adopt_placement`` -> ``EngineCore`` ->
``EngineSupervisor.start``) and hands out ``submit``.

Deployment facts come from the configuration file's ``deployment`` group;
every other serving option keeps the program's default.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import weights

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "max_position_embeddings",
              "rms_norm_eps", "rope_theta")


def _program_names(i: int) -> dict:
    p = f"llama.layers.{i}."
    return {"wqkv": p + "self_attn.qkv_proj.weight",
            "wo": p + "self_attn.out_proj.weight",
            "w_gate": p + "mlp.gate_proj.weight",
            "w_up": p + "mlp.up_proj.weight",
            "w_down": p + "mlp.down_proj.weight"}


class System:
    kind = "serving"

    def __init__(self, config: dict, devices, seed: int, traced: bool):
        self.config = config
        self.devices = list(devices)
        self.seed = int(seed)
        self.traced = bool(traced)
        self.core = None
        self.sup = None
        self.steplog = None

    # ------------------------------------------------------------ build
    def build(self):
        from paddle_infer_tpu.inference.generation import serving_param_spec
        from paddle_infer_tpu.models.llama import (LlamaConfig,
                                                   LlamaForCausalLM)
        from paddle_infer_tpu.nn.initializer import abstract_parameters
        from paddle_infer_tpu.observability.steplog import StepLog
        from paddle_infer_tpu.serving import (EngineCore, EngineSupervisor,
                                              ServingMesh,
                                              build_sharded_engine)

        cfg, dep = self.config, self.config["deployment"]
        lcfg = LlamaConfig(**{k: cfg[k] for k in MODEL_KEYS})
        with abstract_parameters():
            model = LlamaForCausalLM(lcfg)
        model.eval()
        params = dict(model.named_parameters())
        mp = int(dep.get("mp", 1))
        smesh = ServingMesh(mp=mp)
        shardings = None
        if mp > 1:
            from jax.sharding import NamedSharding

            mesh = smesh.build(self.devices[:mp])

            def sh(name):
                p = params[name]
                return NamedSharding(mesh, serving_param_spec(
                    p._data, getattr(p, "dist_attr", None), mesh, name=name,
                    fallback=[]))

            shardings = {k: sh(n) for k, n in _program_names(0).items()}
            shardings["embed"] = sh("llama.embed_tokens.weight")
            shardings["lm_head"] = sh("lm_head.weight")
        dtype = jnp.dtype(cfg["torch_dtype"])
        w = weights.llama_all_weights(cfg, self.seed, dtype, shardings)
        bound = {"llama.embed_tokens.weight": w["embed"],
                 "lm_head.weight": w["lm_head"]}
        for i, lw in enumerate(w["layers"]):
            for k, name in _program_names(i).items():
                bound[name] = lw[k]
        for name, p in params.items():
            if name in bound:
                value = bound[name]
            elif name.endswith("norm.weight") or name.endswith(
                    "layernorm.weight"):
                value = jnp.ones(p._data.shape, dtype)
            elif name.endswith(".bias"):
                # the published model has no biases; the program's block does
                value = jnp.zeros(p._data.shape, dtype)
            else:
                raise KeyError(f"no seeded value for parameter {name}")
            if tuple(value.shape) != tuple(p._data.shape):
                raise ValueError(f"{name}: made {value.shape}, program "
                                 f"builds {tuple(p._data.shape)}")
            p._data = value
        del w, bound
        engine = build_sharded_engine(
            model, smesh, page_size=int(dep["page_size"]),
            kv_dtype=dep.get("kv_dtype"),
            devices=self.devices[:max(mp, 1)])
        engine.adopt_placement()
        self.engine = engine
        self.steplog = StepLog(capacity=65536)
        self.core = EngineCore(
            engine, max_batch=int(dep["max_batch"]),
            max_queue=int(dep.get("max_queue", 256)),
            max_model_len=int(dep["max_model_len"]),
            enable_prefix_cache=bool(dep["enable_prefix_cache"]),
            steplog=self.steplog,
            serving_mesh=smesh if smesh.n_devices > 1 else None)
        self.sup = EngineSupervisor(self.core).start()
        self.token_budget = int(self.core._token_budget)
        self.max_batch = int(dep["max_batch"])

    # ------------------------------------------------------------- drive
    def submit(self, ids, max_new: int):
        from paddle_infer_tpu.inference.generation import GenerationConfig

        g = GenerationConfig(max_new_tokens=int(max_new), do_sample=False,
                             eos_token_id=None)
        return self.core.submit(np.asarray(ids, np.int32), g)[0]

    def warm(self, traffic: dict):
        """Every shape the window will use: the mixed step is ONE program
        for every composition, so a prompt of several chunks, a short one
        beside it and their decode steps compile and run all of it."""
        budget = self.token_budget
        lens = [2 * budget + 7, 5]
        reqs = [self.submit(np.full((n,), 7 + i, np.int32), 4)
                for i, n in enumerate(lens)]
        for r in reqs:
            r.result(timeout=1500)
        # two random prompts can share their first tokens: the prefix
        # cache then copies a partly matched page, a program of its own
        # (a full-page match and a part of the next)
        page = int(self.config["deployment"]["page_size"])
        again = np.full((page + 5 + 9,), 7, np.int32)
        again[page + 5:] = 11
        self.submit(again, 2).result(timeout=1500)

    def stop(self):
        if self.sup is not None:
            self.sup.close()
            self.sup = None

    def free(self):
        """Drop the program's device state before the reference runs."""
        self.stop()
        self.core = self.engine = None
        import gc

        gc.collect()

    # ---------------------------------------------------------- evidence
    def queue_wait_spans(self):
        out = []
        for tr in self.core.tracer.completed():
            for sp in tr.ordered():
                if sp.name == "queue_wait" and sp.end is not None:
                    out.append((sp.start, sp.end))
        return out
