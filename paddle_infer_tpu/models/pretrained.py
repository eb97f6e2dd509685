"""save_pretrained / from_pretrained for the model zoo.

Reference: PaddleNLP's PretrainedModel surface (the fork's model families
are consumed through ``AutoModel.from_pretrained`` — config.json + a
weights payload per directory).

TPU-first: weights go through the native mmap TensorStore
(native/tensor_store.cc — zero-copy reads at serving start, the
``.pdiparams`` analog), falling back to pickle when the native library
is unavailable; the config is plain JSON of the Config object.
"""
from __future__ import annotations

import json
import os

import numpy as np

_WEIGHTS_PITS = "model.pits"
_WEIGHTS_PKL = "model.pdparams"
_CONFIG = "config.json"


class AutoModel:
    """Architecture-dispatching loader (PaddleNLP AutoModel surface):
    reads ``architecture`` from config.json and loads through the right
    class."""

    _REGISTRY = {
        "GPTForCausalLM": ("gpt", "GPTForCausalLM"),
        "GPTMoEForCausalLM": ("gpt_moe", "GPTMoEForCausalLM"),
        "LlamaForCausalLM": ("llama", "LlamaForCausalLM"),
        "ErnieForMaskedLM": ("ernie", "ErnieForMaskedLM"),
        "ErnieForPretraining": ("ernie", "ErnieForPretraining"),
        "ErnieForSequenceClassification": (
            "ernie", "ErnieForSequenceClassification"),
        "LatentMoEForCausalLM": ("latent_moe", "LatentMoEForCausalLM"),
        "LongcatFlashForCausalLM": ("longcat_flash",
                                    "LongcatFlashForCausalLM"),
    }

    # a directory holding a model's own published config.json carries no
    # "architecture"; its "model_type" names the family
    _BY_MODEL_TYPE = {
        "axk1": ("latent_moe", "LatentMoEForCausalLM"),
        "xing4_0": ("latent_moe", "LatentMoEForCausalLM"),
        "glm_moe_dsa": ("latent_moe", "LatentMoEForCausalLM"),
        "longcat_flash": ("longcat_flash", "LongcatFlashForCausalLM"),
    }

    @classmethod
    def _resolve(cls, save_dir: str):
        """-> (model class, config dict without 'architecture')."""
        import importlib

        with open(os.path.join(save_dir, _CONFIG)) as f:
            cfg = json.load(f)
        arch = cfg.pop("architecture", None)
        entry = cls._REGISTRY.get(arch) if arch is not None \
            else cls._BY_MODEL_TYPE.get(cfg.get("model_type"))
        if entry is None:
            raise ValueError(
                f"unknown architecture {arch!r} / model_type "
                f"{cfg.get('model_type')!r} in {save_dir} "
                f"(known: {sorted(cls._REGISTRY)}; model types "
                f"{sorted(cls._BY_MODEL_TYPE)})")
        mod = importlib.import_module(f".{entry[0]}", __package__)
        return getattr(mod, entry[1]), cfg

    @classmethod
    def from_pretrained(cls, save_dir: str):
        model_cls, _ = cls._resolve(save_dir)
        return model_cls.from_pretrained(save_dir)


class AutoConfig:
    """Config-only loader companion to AutoModel."""

    @classmethod
    def from_pretrained(cls, save_dir: str):
        model_cls, cfg = AutoModel._resolve(save_dir)
        return model_cls.config_class(**cfg)


class PretrainedMixin:
    """Mixed into the *ForCausalLM / *For* heads; subclasses define
    ``config_class``."""

    def save_pretrained(self, save_dir: str) -> None:
        from .. import save as pit_save
        from .. import native

        os.makedirs(save_dir, exist_ok=True)
        cfg = {k: v for k, v in vars(self.config).items()
               if isinstance(v, (int, float, str, bool, list, tuple,
                                 type(None)))}
        cfg["architecture"] = type(self).__name__
        with open(os.path.join(save_dir, _CONFIG), "w") as f:
            json.dump(cfg, f, indent=1, sort_keys=True)
        tensors = {n: np.asarray(p._data)
                   for n, p in self.named_parameters()}
        if native.available():
            native.save_tensors(os.path.join(save_dir, _WEIGHTS_PITS),
                                tensors)
        else:
            pit_save(tensors, os.path.join(save_dir, _WEIGHTS_PKL))

    @classmethod
    def from_pretrained(cls, save_dir: str):
        import jax.numpy as jnp

        from .. import load as pit_load
        from .. import native
        from ..nn.initializer import abstract_parameters

        with open(os.path.join(save_dir, _CONFIG)) as f:
            cfg = json.load(f)
        arch = cfg.pop("architecture", cls.__name__)
        if arch != cls.__name__:
            raise ValueError(
                f"{save_dir} holds a {arch}, not a {cls.__name__} — "
                f"load it with {arch}.from_pretrained")
        config = cls.config_class(**cfg)
        # parameters stay abstract until the checkpoint binds them: the
        # model is served in the checkpoint's dtype, and the device never
        # holds a default-initialised fp32 copy beside the loaded one
        with abstract_parameters():
            model = cls(config)
        pits = os.path.join(save_dir, _WEIGHTS_PITS)
        if os.path.exists(pits):
            tensors = native.load_tensors(pits)
        else:
            tensors = pit_load(os.path.join(save_dir, _WEIGHTS_PKL))
        missing = []
        for name, p in model.named_parameters():
            if name not in tensors:
                missing.append(name)
                continue
            value = np.asarray(tensors[name])
            if tuple(value.shape) != tuple(p._data.shape):
                raise ValueError(
                    f"{save_dir}: {name} has shape {value.shape}, the "
                    f"config builds {tuple(p._data.shape)}")
            p._data = jnp.asarray(value)
        if missing:
            raise ValueError(
                f"{save_dir} lacks {len(missing)} parameter(s) the "
                f"config builds: {missing[:8]}")
        model.eval()
        return model
