"""Public-API signature guard.

Reference: paddle/fluid/API.spec + tools/check_api_compatible.py — CI
fails when a public signature changes without the spec being updated,
so API breaks are always deliberate.

Here: walk the package's public surface (modules in
paddle_infer_tpu.__init__ + the documented namespaces), record every
public callable's signature into tools/API.spec, and ``--check``
diffs the live surface against it.

Usage:
  python tools/api_spec.py --update      # rewrite the spec
  python tools/api_spec.py --check       # exit 1 on any drift
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC_PATH = os.path.join(HERE, "API.spec")

NAMESPACES = [
    "paddle_infer_tpu",
    "paddle_infer_tpu.nn",
    "paddle_infer_tpu.nn.functional",
    "paddle_infer_tpu.optimizer",
    "paddle_infer_tpu.optimizer.lr",
    "paddle_infer_tpu.amp",
    "paddle_infer_tpu.io",
    "paddle_infer_tpu.jit",
    "paddle_infer_tpu.inference",
    "paddle_infer_tpu.distributed",
    "paddle_infer_tpu.distributed.checkpoint",
    "paddle_infer_tpu.parallel",
    "paddle_infer_tpu.models",
    "paddle_infer_tpu.metric",
    "paddle_infer_tpu.hapi",
    "paddle_infer_tpu.vision.ops",
    "paddle_infer_tpu.sequence",
    "paddle_infer_tpu.sparse",
    "paddle_infer_tpu.linalg",
    "paddle_infer_tpu.quantization",
]


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _own(obj) -> bool:
    """Defined by this package?  A namespace that imports ``NamedSharding``
    or ``typing.Any`` for its own use re-exports it by accident; recording
    a third-party symbol's signature makes the guard fail whenever THAT
    library changes, which is not an API break of this one."""
    mod = getattr(obj, "__module__", None) or ""
    return mod.split(".")[0] == "paddle_infer_tpu"


def collect() -> dict:
    import importlib

    spec = {}
    for ns in NAMESPACES:
        try:
            mod = importlib.import_module(ns)
        except Exception as e:
            spec[ns] = f"IMPORT ERROR {e!r}"
            continue
        names = getattr(mod, "__all__", None)
        if names is None:
            names = [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(names):
            try:
                obj = getattr(mod, name)
            except AttributeError:
                spec[f"{ns}.{name}"] = "MISSING (__all__ lists it)"
                continue
            if not _own(obj):
                continue
            if inspect.isclass(obj):
                spec[f"{ns}.{name}"] = "class" + _signature(obj)
                for mname, m in sorted(vars(obj).items()):
                    if mname.startswith("_") or not callable(m):
                        continue
                    spec[f"{ns}.{name}.{mname}"] = _signature(m)
            elif callable(obj):
                spec[f"{ns}.{name}"] = _signature(obj)
    return spec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    spec = collect()
    lines = [f"{k} {v}" for k, v in sorted(spec.items())]
    if args.update:
        with open(SPEC_PATH, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"{len(lines)} public symbols -> {SPEC_PATH}")
        return 0
    if args.check:
        if not os.path.exists(SPEC_PATH):
            print("no API.spec recorded — run --update first",
                  file=sys.stderr)
            return 1
        with open(SPEC_PATH) as f:
            old = dict(line.split(" ", 1)
                       for line in f.read().splitlines() if line)
        new = {k: v for k, v in spec.items()}
        removed = sorted(set(old) - set(new))
        added = sorted(set(new) - set(old))
        changed = sorted(k for k in set(old) & set(new)
                         if old[k].strip() != new[k].strip())
        for k in removed:
            print(f"REMOVED {k}", file=sys.stderr)
        for k in changed:
            print(f"CHANGED {k}: {old[k].strip()} -> {new[k].strip()}",
                  file=sys.stderr)
        for k in added:
            print(f"ADDED {k}")
        if removed or changed:
            print(f"{len(removed)} removed, {len(changed)} changed — "
                  "update tools/API.spec if deliberate", file=sys.stderr)
            return 1
        print(f"API surface stable ({len(new)} symbols, "
              f"{len(added)} new)")
        return 0
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
