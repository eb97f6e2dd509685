"""host-sync: device→host synchronization reachable from the serving
hot path.

The continuous-batching step loop's latency budget assumes exactly one
host sync per fused decode chunk (reading the chunk's tokens back).
Any extra ``block_until_ready`` / ``device_get`` / ``np.asarray`` /
``.item()`` on a device array inside the step loop serializes the TPU
pipeline against Python and shows up directly as inter-token latency.

Detection is call-graph based, not textual: within every class that
owns a scheduler entry point (``run_once`` / ``step`` /
``decode_step``), the rule BFS-walks ``self.<method>`` calls (and
property reads) to the full set of hot methods, then flags sync
constructs inside them.  Intentional chunk-boundary syncs stay, with a
``# tpulint: disable=host-sync -- <why>`` comment — the reason is
mandatory, and the suppression is the documentation.

Eager collectives count too: a ``parallel.collective.all_reduce`` (or
any sibling from that module) issued from host serving code dispatches
a standalone collective program and blocks every mesh participant at a
rendezvous — a cross-device sync strictly worse than a local readback.
Collectives belong *inside* traced step programs (GSPMD inserts them)
or behind the quantized shard_map ops, never in the scheduler loop.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set

from ..core import FileContext, Rule, dotted

HOT_ROOTS = {"run_once", "_run_once_locked", "step", "decode_step"}

_SYNC_DOTTED = {"jax.device_get", "jax.block_until_ready"}
# Eager collective entry points (parallel/collective.py): each call from
# host code is a standalone dispatched program plus a cross-device
# rendezvous — every mesh participant stalls, not just this host thread.
_COLLECTIVE_FNS = {"all_reduce", "all_gather", "reduce_scatter",
                   "broadcast", "alltoall", "ppermute", "p2p_transfer",
                   "barrier", "reduce"}
_NP_CONVERT = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "np.copy", "numpy.copy"}
_LITERALS = (ast.Constant, ast.List, ast.Tuple, ast.Dict, ast.Set,
             ast.ListComp, ast.DictComp, ast.GeneratorExp)


def _methods(cls: ast.ClassDef) -> Dict[str, ast.FunctionDef]:
    return {n.name: n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def _self_refs(fn: ast.FunctionDef) -> Set[str]:
    """Names accessed as ``self.<name>`` anywhere in the method (calls
    and property loads both count as edges)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            out.add(node.attr)
    return out


class HostSyncRule(Rule):
    id = "host-sync"
    name = "host sync in hot path"
    rationale = ("device→host readbacks inside the serving step loop "
                 "serialize the accelerator pipeline and inflate "
                 "inter-token latency")
    path_scope = ("serving",)

    def check_file(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(ctx, node)

    def _check_class(self, ctx: FileContext, cls: ast.ClassDef):
        methods = _methods(cls)
        roots = sorted(HOT_ROOTS & set(methods))
        if not roots:
            return
        hot_via: Dict[str, str] = {r: r for r in roots}
        frontier: List[str] = list(roots)
        while frontier:
            m = frontier.pop()
            for ref in sorted(_self_refs(methods[m])):
                if ref in methods and ref not in hot_via:
                    hot_via[ref] = hot_via[m]
                    frontier.append(ref)
        for m, root in sorted(hot_via.items()):
            yield from self._check_method(ctx, methods[m], root)

    def _check_method(self, ctx: FileContext, fn: ast.FunctionDef,
                      root: str):
        qn = ctx.qualname(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            label = self._sync_label(node)
            if label:
                yield ctx.finding(
                    self.id, node,
                    f"{label} forces a device->host sync inside hot "
                    f"path '{qn}' (reachable from {root}())")

    @staticmethod
    def _sync_label(call: ast.Call) -> str:
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr == "block_until_ready":
                return ".block_until_ready()"
            if func.attr == "item" and not call.args:
                return ".item()"
        d = dotted(func)
        if d in _SYNC_DOTTED:
            return f"{d}()"
        if "." in d:
            prefix, _, last = d.rpartition(".")
            if last in _COLLECTIVE_FNS and "collective" in prefix:
                return (f"eager collective {d}() (cross-device "
                        "rendezvous; belongs inside the traced step "
                        "program)")
        if d in _NP_CONVERT and call.args \
                and not isinstance(call.args[0], _LITERALS):
            return f"{d}() on a possibly-device value"
        if isinstance(func, ast.Name) and func.id in ("float", "int",
                                                      "bool") \
                and len(call.args) == 1 \
                and isinstance(call.args[0], ast.Call):
            inner = dotted(call.args[0].func)
            if inner.startswith(("jnp.", "jax.")):
                return f"{func.id}() over a {inner}() result"
        return ""
