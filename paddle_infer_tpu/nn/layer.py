"""Layer base class (reference: python/paddle/fluid/dygraph/layers.py:98).

Same user contract as paddle.nn.Layer — named parameter/sublayer trees,
state_dict round-trip, train/eval flags, hooks — plus a TPU-first extra:
``functional_state`` / ``functional_call`` which lift a layer into a pure
function over a params pytree so the jit/pjit compile path (and jax.grad)
can consume it.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Iterator, Optional, Tuple

import numpy as np

from ..core.tensor import Parameter, Tensor

class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_forward_pre_hooks", OrderedDict())
        object.__setattr__(self, "_forward_post_hooks", OrderedDict())
        self._dtype = dtype
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ------------------------------------------------------------ attribute
    def __setattr__(self, name, value):
        if isinstance(value, Parameter):
            if value.name is None:
                scope = getattr(self, "_name_scope",
                                type(self).__name__.lower())
                value.name = f"{scope}.{name}"
            self._parameters[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            self._sub_layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if name in self._parameters:
                del self._parameters[name]
            if name in self._sub_layers:
                del self._sub_layers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        params = self.__dict__.get("_parameters")
        if params is not None and name in params:
            return params[name]
        subs = self.__dict__.get("_sub_layers")
        if subs is not None and name in subs:
            return subs[name]
        bufs = self.__dict__.get("_buffers")
        if bufs is not None and name in bufs:
            return bufs[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        if name in self._parameters:
            del self._parameters[name]
        elif name in self._sub_layers:
            del self._sub_layers[name]
        elif name in self._buffers:
            del self._buffers[name]
        else:
            object.__delattr__(self, name)

    # ------------------------------------------------------------- registry
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, dtype=None, default_initializer=None,
                         attr=None, is_bias=False):
        from . import initializer as I

        dtype = dtype or self._dtype
        if attr is not None and getattr(attr, "initializer", None) is not None:
            default_initializer = attr.initializer
        if default_initializer is None:
            default_initializer = I._default_initializer(is_bias)
        data = default_initializer(shape, dtype)
        name = None
        if attr is not None and getattr(attr, "name", None):
            name = attr.name
        p = Parameter(data, name=name)
        if attr is not None:
            if getattr(attr, "learning_rate", None) is not None:
                p.optimize_attr["learning_rate"] = attr.learning_rate
            if getattr(attr, "trainable", True) is False:
                p.stop_gradient = True
                p.trainable = False
            if getattr(attr, "regularizer", None) is not None:
                p.regularizer = attr.regularizer
        return p

    # ------------------------------------------------------------ traversal
    def parameters(self, include_sublayers: bool = True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix: str = "", include_sublayers: bool = True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(prefix=sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(prefix=sub_prefix)

    def sublayers(self, include_self: bool = False):
        out = [self] if include_self else []
        for layer in self._sub_layers.values():
            if layer is not None:
                out.extend(layer.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix: str = "", include_self: bool = False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True)

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items()
                    if l is not None)

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # ----------------------------------------------------------------- mode
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    # ----------------------------------------------------------- state dict
    def state_dict(self, destination=None, include_sublayers=True, prefix=""):
        if destination is None:
            destination = OrderedDict()
        for name, p in self._parameters.items():
            if p is not None:
                destination[f"{prefix}{name}"] = p
        for name, b in self._buffers.items():
            if b is not None and name not in self._non_persistable_buffer_names:
                destination[f"{prefix}{name}"] = b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is not None:
                    layer.state_dict(destination=destination,
                                     prefix=f"{prefix}{lname}.")
        return destination

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing = []
        for key, target in own.items():
            if key in state_dict:
                value = state_dict[key]
                if isinstance(value, Tensor):
                    value = value._data
                target.set_value(value)
            else:
                missing.append(key)
        unexpected = [k for k in state_dict if k not in own]
        return missing, unexpected

    load_dict = set_state_dict

    def to(self, dtype=None):
        if dtype is not None:
            from ..core import dtype as dtypes

            d = dtypes.convert_dtype(dtype)
            for p in self.parameters():
                p._data = p._data.astype(d)
            for b in self.buffers():
                if b is not None and np.issubdtype(np.dtype(b.dtype), np.floating):
                    b._data = b._data.astype(d)
        return self

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ---------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    # ----------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    # ---------------------------------------------------- functional bridge
    def functional_state(self):
        """Return ``{name: jax.Array}`` of all trainable params (pytree leaf
        dict) — what the compile path feeds to jax.grad / pjit."""
        return {n: p._data for n, p in self.named_parameters()
                if not p.stop_gradient}

    def functional_buffers(self):
        return {n: b._data for n, b in self.named_buffers() if b is not None}

    def functional_call(self, params, *inputs, buffers=None, **kwargs):
        """Run forward with parameter payloads temporarily swapped to
        ``params`` (jax arrays keyed by named_parameters names).  This is how
        a stateful Layer becomes a pure function for jit/grad."""
        named = dict(self.named_parameters())
        named_buf = dict(self.named_buffers()) if buffers else {}
        old = {}
        try:
            for n, arr in params.items():
                old[n] = named[n]._data
                named[n]._data = arr
            if buffers:
                for n, arr in buffers.items():
                    if n in named_buf:
                        old[("buf", n)] = named_buf[n]._data
                        named_buf[n]._data = arr
            wrapped = [Tensor(x) if not isinstance(x, Tensor) else x
                       for x in inputs]
            return self(*wrapped, **kwargs)
        finally:
            for n, arr in old.items():
                if isinstance(n, tuple):
                    named_buf[n[1]]._data = arr
                else:
                    named[n]._data = arr

    def functional_caller(self, params, buffers=None):
        """A callable standing in for this layer with ``params`` payloads —
        what fleet's compiled train step passes to user loss functions.
        Sublayer access returns a caller scoped to that sublayer (params
        filtered by prefix), so loss functions may call ``m.decoder(x)``
        etc. without bypassing the traced parameters."""
        return _FunctionalCaller(self, dict(params),
                                 dict(buffers) if buffers else None)

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        lines = [f"{self.__class__.__name__}({self.extra_repr()}"]
        for name, layer in self._sub_layers.items():
            sub = repr(layer).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else "".join(lines)


class _FunctionalCaller:
    """Proxy over a Layer bound to a params pytree (see functional_caller)."""

    def __init__(self, layer, params, buffers):
        object.__setattr__(self, "_layer", layer)
        object.__setattr__(self, "_params", params)
        object.__setattr__(self, "_buffers", buffers)

    def __call__(self, *inputs, **kwargs):
        return self._layer.functional_call(self._params, *inputs,
                                           buffers=self._buffers, **kwargs)

    def __getattr__(self, name):
        layer = self._layer
        sub = layer.__dict__.get("_sub_layers", {})
        if name in sub and sub[name] is not None:
            pfx = name + "."
            sub_params = {k[len(pfx):]: v for k, v in self._params.items()
                          if k.startswith(pfx)}
            sub_buffers = None
            if self._buffers:
                sub_buffers = {k[len(pfx):]: v
                               for k, v in self._buffers.items()
                               if k.startswith(pfx)}
            return _FunctionalCaller(sub[name], sub_params, sub_buffers)
        own = layer.__dict__.get("_parameters", {})
        if name in own and own[name] is not None:
            if name in self._params:
                from ..core.tensor import Tensor

                return Tensor(self._params[name], stop_gradient=False)
        return getattr(layer, name)


class _HookHandle:
    _next_id = 0

    def __init__(self, hooks_dict):
        self.id = _HookHandle._next_id
        _HookHandle._next_id += 1
        self._hooks = hooks_dict

    def remove(self):
        self._hooks.pop(self.id, None)
