"""recompile-hazard: unbounded Python values flowing into program-cache
keys.

Every distinct executable-cache key compiles (and retains) one XLA
program.  A key built from an unbucketed value — a raw ``len()``, an
f-string over arbitrary data, a ``str()``/``repr()`` of an array —
makes the cache's cardinality proportional to traffic diversity instead
of to the bucketed shape family, which is exactly the recompile storm
``CompileLog`` exists to catch at runtime.  This rule catches it at
review time.

What counts as a cache key, statically:

  * a tuple assigned to a name ending in ``key`` (the repo convention:
    ``pkey`` / ``dkey`` / ``ckey``);
  * a tuple passed directly to ``run_paged_program(...)``;
  * a subscript write into a name containing ``cache`` / ``compiled``.

Flagged elements: f-strings, ``len(...)``, ``str(...)`` / ``repr(...)``.
Bare names are deliberately NOT flagged (where a name's value comes
from is the ``key-provenance`` rule's job), so the rule stays quiet on
disciplined keys and loud on raw ones.

Program BUILDERS are also checked: a ``def build_*`` whose signature
takes a shape-valued parameter (``plen`` / ``batch`` / ``chunk``)
closes one executable over every distinct value — the per-shape program
family the mixed step replaced.  A bounded family that is kept on
purpose carries a reasoned
``# tpulint: disable-next-line=recompile-hazard -- <why>``
suppression.
"""
from __future__ import annotations

import ast

from ..core import FileContext, Rule, dotted

# parameter names that key an executable to traffic shape (exact match:
# config-sized names like max_batch / token_budget are bounded by
# construction and deliberately not flagged)
_SHAPE_VALUED = frozenset({"plen", "batch", "chunk"})

# serving-path builders additionally must not key on MoE routing sizes:
# expert count and per-expert capacity are DEPLOYMENT config there (one
# (E, C) per config, baked into the converted layers), so a build_*
# signature taking them re-opens a per-routing-shape program family —
# precisely what the static-capacity serving plane exists to prevent.
# Scoped to serving/ because training-side builders legitimately
# parameterize over experts.
_MOE_SHAPE_VALUED = frozenset({"num_experts", "n_experts", "experts",
                               "capacity", "expert_capacity",
                               "moe_capacity"})

# likewise for the multi-LoRA plane: the stacked pool shapes
# [slots, d, r] are DEPLOYMENT config (one (slots, rank) per config,
# baked into the converted LoRAServingLinear layers), so a serving
# build_* signature taking rank or slot count re-opens a
# per-adapter-shape program family — residency churn would then
# compile instead of riding as per-row slot data.
_ADAPTER_SHAPE_VALUED = frozenset({"rank", "lora_rank", "adapter_rank",
                                   "adapter_slots", "num_adapters",
                                   "n_adapters", "slot_count"})

# and for the constrained-decoding plane: the grammar mask is per-row
# DATA (a [b, V] f32 gathered host-side from the compiled FSM), so a
# serving build_* signature taking a grammar or vocab shape re-opens a
# per-grammar program family — 32 distinct schemas would compile 32
# executables instead of riding the one grammar-marked mixed step.
_GRAMMAR_SHAPE_VALUED = frozenset({"vocab_size", "n_vocab", "vocab",
                                   "num_states", "n_states",
                                   "grammar_states", "fsm_states",
                                   "num_grammars", "n_grammars"})


def _element_label(el: ast.AST) -> str:
    if isinstance(el, ast.JoinedStr):
        return "f-string"
    if isinstance(el, ast.Call):
        d = dotted(el.func)
        if d == "len":
            return "raw len() (bucket it first)"
        if d in ("str", "repr"):
            return f"{d}() of a runtime value"
    return ""


class RecompileHazardRule(Rule):
    id = "recompile-hazard"
    name = "unbounded value in program-cache key"
    rationale = ("cache keys built from unbucketed runtime values give "
                 "the executable cache unbounded cardinality — every "
                 "novel value pays XLA compile latency")

    def check_file(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                yield from self._check_assign(ctx, node)
            elif isinstance(node, ast.Call):
                yield from self._check_call(ctx, node)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                yield from self._check_builder(ctx, node)

    def _check_builder(self, ctx: FileContext, node: ast.AST):
        if not node.name.startswith("build_"):
            return
        args = node.args
        names = [a.arg for a in (args.posonlyargs + args.args
                                 + args.kwonlyargs)]
        hazards = [n for n in names if n in _SHAPE_VALUED]
        if hazards:
            yield ctx.finding(
                self.id, node,
                f"shape-keyed program builder {node.name}"
                f"({', '.join(hazards)}) compiles one executable per "
                "distinct value — fold the shape into a "
                "composition-keyed executable (ragged mixed step) or "
                "suppress with the reason the per-shape family must "
                "stay")
        if "serving" in ctx.relpath.replace("\\", "/").split("/"):
            moe_hazards = [n for n in names if n in _MOE_SHAPE_VALUED]
            if moe_hazards:
                yield ctx.finding(
                    self.id, node,
                    f"MoE-shape-keyed serving builder {node.name}"
                    f"({', '.join(moe_hazards)}) re-opens a per-"
                    "routing-shape program family — expert count and "
                    "capacity are deployment config: bake them into "
                    "the converted layers (prepare_moe_serving) and "
                    "key the ONE executable on the config tuple")
            lora_hazards = [n for n in names
                            if n in _ADAPTER_SHAPE_VALUED]
            if lora_hazards:
                yield ctx.finding(
                    self.id, node,
                    f"adapter-shape-keyed serving builder {node.name}"
                    f"({', '.join(lora_hazards)}) re-opens a per-"
                    "adapter-shape program family — rank and slot "
                    "count are deployment config: bake them into the "
                    "converted layers (prepare_lora_serving) and pass "
                    "which adapter each row runs as per-row slot DATA")
            grammar_hazards = [n for n in names
                               if n in _GRAMMAR_SHAPE_VALUED]
            if grammar_hazards:
                yield ctx.finding(
                    self.id, node,
                    f"grammar-shape-keyed serving builder {node.name}"
                    f"({', '.join(grammar_hazards)}) re-opens a per-"
                    "grammar program family — vocab and FSM sizes are "
                    "host-side compile products: gather the per-state "
                    "allow-mask on the host and pass it as per-row "
                    "[b, V] mask DATA into the one grammar-marked "
                    "executable")

    def _check_assign(self, ctx: FileContext, node: ast.Assign):
        key_target = any(isinstance(t, ast.Name)
                         and t.id.lower().endswith("key")
                         for t in node.targets)
        if key_target and isinstance(node.value, ast.Tuple):
            yield from self._check_tuple(ctx, node.value, "cache key")
        for t in node.targets:
            if isinstance(t, ast.Subscript):
                base = dotted(t.value).lower()
                if ("cache" in base or "compiled" in base) \
                        and isinstance(t.slice, ast.JoinedStr):
                    yield ctx.finding(
                        self.id, t.slice,
                        f"f-string key into '{dotted(t.value)}' — "
                        "unbounded cache cardinality")

    def _check_call(self, ctx: FileContext, node: ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute) \
                and func.attr == "run_paged_program" and node.args \
                and isinstance(node.args[0], ast.Tuple):
            yield from self._check_tuple(ctx, node.args[0],
                                         "run_paged_program key")

    def _check_tuple(self, ctx: FileContext, tup: ast.Tuple, what: str):
        for el in tup.elts:
            label = _element_label(el)
            if label:
                yield ctx.finding(
                    self.id, el,
                    f"{label} inside a {what} tuple — every distinct "
                    "value compiles and retains a fresh executable")
