"""The plain references, one module to a family, found by the name the
configuration's file gives under ``reference``.

What a check asks of the module it is handed (nothing else of it is
named anywhere in the harness):

- a served model: ``served_logits(config, seed, tokens, rows,
  precision="float32") -> [len(rows), vocab]`` float32 logits at the
  positions ``rows`` of one sequence;
- a training step: ``first_steps(config, seed, batches, n,
  precision="float32", mask_stream=9) -> {"losses", "grad_norms",
  "delta_norms"}`` and ``matrix_leaves(config) -> [leaf names]``.

Each makes its own weights from ``seed`` through its family's weights
module, and takes nothing that the program has made.
"""
from __future__ import annotations

import importlib

CONTRACT = {"serving": ("served_logits",),
            "training": ("first_steps", "matrix_leaves")}


def find(config: dict):
    """The module ``config["reference"]`` names, holding what the check
    of ``config["kind"]`` asks for.  No default: a configuration that
    names no reference is an error."""
    name = config.get("reference")
    if not name:
        raise KeyError(
            "the configuration's file names no plain reference: add "
            '"reference": "<module under benchmarks/reference/>"')
    try:
        mod = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise KeyError(f"the configuration names the reference {name!r}, "
                       f"and there is no benchmarks/reference/{name}.py") \
            from None
    lacking = [f for f in CONTRACT[config["kind"]]
               if not callable(getattr(mod, f, None))]
    if lacking:
        raise TypeError(
            f"benchmarks/reference/{name}.py lacks {', '.join(lacking)}: "
            f"the contract of a {config['kind']} reference "
            "(benchmarks/reference/__init__.py)")
    return mod
