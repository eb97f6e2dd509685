"""Eager Tensor for paddle_infer_tpu.

Wraps a ``jax.Array`` and carries autograd metadata, mirroring the role of the
reference's ``paddle::experimental::Tensor`` + ``egr::AutogradMeta``
(reference: paddle/phi/api/include/tensor.h:83, paddle/fluid/eager/autograd_meta.h).
The numerical payload always lives on device as an XLA buffer; all compute is
dispatched through the op registry (core/dispatch.py) so every eager op is a
jitted XLA computation.

Paddle semantics preserved:
  * ``stop_gradient`` defaults to True for raw tensors, False for Parameters.
  * ``tensor.backward()`` runs the GradNode tape (core/autograd.py).
  * ``tensor.grad`` is itself a Tensor (or None).
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes


class Tensor:
    __slots__ = (
        "_data",
        "stop_gradient",
        "grad",
        "_grad_node",
        "_out_slot",
        "_retain_grads",
        "_hooks",
        "name",
        "persistable",
        "dist_attr",   # optional mesh partition spec (set on params AND
                       # non-trainable payloads, e.g. quantized weights)
        "__weakref__",
    )

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None):
        if isinstance(data, Tensor):
            data = data._data
        # a ShapeDtypeStruct is an abstract payload (shape and dtype, no
        # buffer): nn.layer.abstract_parameters builds layers from them
        # so a loader can bind checkpoint arrays without an init copy
        if not isinstance(data, (jax.Array, jax.ShapeDtypeStruct)):
            data = jnp.asarray(data)
        self._data = data
        self.stop_gradient = stop_gradient
        self.grad: Optional[Tensor] = None
        self._grad_node = None   # (GradNode, slot) producer, set by dispatch
        self._out_slot = 0
        self._retain_grads = False
        self._hooks = None
        self.name = name
        self.persistable = False
        self.dist_attr = None

    # ------------------------------------------------------------------ meta
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    def numel(self):
        return self.size

    @property
    def T(self):
        return self.transpose(list(range(self.ndim))[::-1])

    @property
    def place(self):
        devs = getattr(self._data, "devices", None)
        if devs is None:
            return "unknown"
        return str(next(iter(self._data.devices())))

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_flag = "" if self.stop_gradient else ", stop_gradient=False"
        return (
            f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}"
            f"{grad_flag},\n       {np.asarray(self._data)})"
        )

    # ------------------------------------------------------------- conversion
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self):
        return self._data.item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __array__(self, dtype=None):
        arr = np.asarray(self._data)
        return arr.astype(dtype) if dtype is not None else arr

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.item())

    # -------------------------------------------------------------- autograd
    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def requires_grad_(self, value: bool = True) -> "Tensor":
        self.stop_gradient = not value
        return self

    def retain_grads(self):
        self._retain_grads = True
        return self

    def register_hook(self, hook):
        """Register grad hook: fn(grad_tensor) -> new grad or None."""
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)

        idx = len(self._hooks) - 1
        hooks = self._hooks

        class _Removable:
            def remove(self_inner):
                hooks[idx] = None

        return _Removable()

    def backward(self, grad_tensor: Optional["Tensor"] = None, retain_graph: bool = False):
        from .autograd import run_backward

        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):
        self.grad = None

    def detach(self) -> "Tensor":
        return Tensor(self._data, stop_gradient=True, name=self.name)

    # ------------------------------------------------------------- mutation
    def _rebind(self, out: "Tensor") -> "Tensor":
        """Adopt another tensor's payload AND autograd producer — the one
        implementation behind every public in-place (`op_`) variant (the
        reference mutates buffers; XLA ops are functional, so in-place =
        compute + rebind this Python handle)."""
        self._data = out._data
        self._grad_node = out._grad_node
        return self

    def set_value(self, value):
        """In-place replace the payload (used by optimizers / load)."""
        if isinstance(value, Tensor):
            value = value._data
        value = jnp.asarray(value)
        if tuple(value.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {value.shape} vs {self._data.shape}")
        self._data = value.astype(self._data.dtype)
        return self

    def _replace_data(self, data):
        self._data = data
        return self

    def copy_(self, other):
        return self.set_value(other)

    # indexing -------------------------------------------------------------
    def __getitem__(self, idx):
        from . import dispatch

        return dispatch.dispatch("getitem", self, idx=_freeze_index(idx))

    def __setitem__(self, idx, value):
        # Functional scatter; only supported on tensors outside the tape.
        if isinstance(value, Tensor):
            value = value._data
        self._data = self._data.at[idx].set(value)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)


def _freeze_index(idx):
    """Make an index expression hashable so it can key the jit cache."""
    if isinstance(idx, tuple):
        return tuple(_freeze_index(i) for i in idx)
    if isinstance(idx, slice):
        return ("__slice__", idx.start, idx.stop, idx.step)
    if isinstance(idx, list):
        return ("__list__", tuple(idx))
    if isinstance(idx, np.ndarray):
        return ("__array__", idx.shape, idx.dtype.str, tuple(idx.ravel().tolist()))
    if isinstance(idx, Tensor):
        return ("__array__", tuple(idx.shape), np.dtype(idx.dtype).str,
                tuple(idx.numpy().ravel().tolist()))
    return idx


def _thaw_index(idx):
    if isinstance(idx, tuple):
        if len(idx) and idx[0] == "__slice__":
            return slice(idx[1], idx[2], idx[3])
        if len(idx) and idx[0] == "__list__":
            return list(idx[1])
        if len(idx) and idx[0] == "__array__":
            return np.array(idx[3], dtype=np.dtype(idx[2])).reshape(idx[1])
        return tuple(_thaw_index(i) for i in idx)
    return idx


class Parameter(Tensor):
    """Trainable tensor: ``stop_gradient=False`` by default, persistable."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip")

    def __init__(self, data, name=None, trainable=True):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        # Per-dim mesh-axis names (PartitionSpec entries) or None; consumed by
        # the fleet train-step builder to shard this parameter over the mesh
        # (the analog of the reference's per-layer is_mp_parameter split
        # attrs, fleet/layers/mpu/mp_layers.py).
        self.dist_attr = None
