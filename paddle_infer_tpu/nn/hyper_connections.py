"""Manifold-constrained hyper-connections (mHC, arXiv 2512.24880, on
Hyper-Connections, arXiv 2409.19606): the residual path of a block that
carries ``n`` streams ``x [.., n, C]`` instead of one.

One :class:`HyperConnection` belongs to one sub-layer ``F`` (an
attention or a feed-forward with its own pre-norm).  Per token, all in
float32 (``ops/pallas/mhc_maps.py`` has the maps):

    f  = vec(x) in R^{nC};  f^ = f / sqrt(mean(f^2) + norm_eps)
    z  = f^ Phi,  Phi in R^{nC x (n^2 + 2n)}  ->  z_pre (n), z_post (n),
                                                   z_res (n^2)
    H_pre  = sigmoid(alpha_pre z_pre + b_pre)
    H_post = 2 sigmoid(alpha_post z_post + b_post)
    H_res  = Sinkhorn-Knopp(exp(clamp(alpha_res mat(z_res) + b_res)))
    u      = sum_j H_pre[j] x[j]                     (``read``)
    x'[i]  = sum_j H_res[i, j] x[j] + H_post[i] F(u) (``write``)

``H_res`` is doubly stochastic up to what ``iters`` rounds leave: rows
sum to one (the row step is the last), columns nearly.  The largest
``|colsum - 1|`` over a serving step's valid slots rides out through
:func:`collect_stats` — a thread-local side channel like
``serving/moe/stats.py``: the mixed step opens it around the model's
forward and drains it into its packed output; outside it nothing is
computed for it.

On the device the projection is ONE matmul over the streams as they are
stored (the norm is applied to its float32 result as a per-token scale,
so ``x`` is read once), the three maps are one Pallas call, and the two
mixes are contractions under the scopes ``mhc_pre_mix`` / ``mhc_post_mix``
(the Pallas call stays outside every scope: docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..ops.pallas.mhc_maps import mhc_maps
from . import initializer as I
from .layer import Layer

_TLS = threading.local()


class ResidualStatsCollector:
    """One step's sink: ``valid`` is the traced [N] bool mask of real
    token slots; every hyper-connection notes the largest column-sum gap
    of its ``H_res`` over them, and the streams' shape as stored."""

    def __init__(self, valid):
        self.valid = valid
        self.gaps = []
        self.streams = 0
        self.stream_bytes = 0

    def note(self, h_res, x):
        """h_res [N, n, n] float32; x [N, n, C] as stored."""
        gap = jnp.abs(jnp.sum(h_res, axis=1) - 1.0)            # [N, n]
        self.gaps.append(jnp.max(jnp.where(self.valid[:, None], gap, 0.0)))
        self.streams = int(x.shape[-2])
        self.stream_bytes = int(x.shape[-2] * x.shape[-1]
                                * x.dtype.itemsize)

    def totals(self):
        """(largest gap f32, streams i32, bytes a token i32): the last two
        read from the streams' array at trace time."""
        if not self.gaps:
            raise RuntimeError(
                "residual stats were collected but no hyper-connection "
                "noted any: the model has no HyperConnection layer")
        return (jnp.max(jnp.stack(self.gaps)).astype(jnp.float32),
                jnp.asarray(self.streams, jnp.int32),
                jnp.asarray(self.stream_bytes, jnp.int32))


class collect_stats:
    """Context manager installing a :class:`ResidualStatsCollector` for
    the current thread; nests (the previous one is restored)."""

    def __init__(self, valid):
        self._valid = valid
        self._prev = None

    def __enter__(self) -> ResidualStatsCollector:
        self._prev = getattr(_TLS, "active", None)
        _TLS.active = ResidualStatsCollector(self._valid)
        return _TLS.active

    def __exit__(self, *exc):
        _TLS.active = self._prev
        return False


def expand_streams(x, n: int):
    """x [.., C] -> [.., n, C]: every stream starts as the embedding."""
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def merge_streams(x):
    """x [.., n, C] -> [.., C]: the read-out is the streams' sum."""
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


class HyperConnection(Layer):
    """The maps of one sub-layer over ``n`` streams of width ``hidden``."""

    def __init__(self, hidden: int, n: int, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, norm_eps: float = 1e-6,
                 clamp_min: float = -30.0, clamp_max: float = 30.0,
                 init_std: float = 0.02):
        super().__init__()
        self.n, self.hidden = int(n), int(hidden)
        self.iters, self.eps = int(sinkhorn_iters), float(eps)
        self.norm_eps = float(norm_eps)
        self.clamp = (float(clamp_min), float(clamp_max))
        w = self.n * self.n + 2 * self.n
        self.phi = self.create_parameter(
            (self.n * hidden, w), default_initializer=I.Normal(0.0, init_std))
        # [alpha_pre, alpha_post, alpha_res] and [b_pre (n), b_post (n),
        # b_res (n^2, row-major)]: float32 whatever the model is served in
        self.alpha = self.create_parameter(
            (3,), dtype="float32", default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter(
            (w,), dtype="float32", default_initializer=I.Constant(0.0))

    def maps(self, x):
        """x [N, n, C] -> (H_pre [N, n], H_post [N, n], H_res [N, n, n]),
        float32."""
        n = self.n
        with jax.named_scope("mhc_proj"):
            f = x.reshape(x.shape[0], n * self.hidden)
            z = jnp.matmul(f, self.phi._data.astype(f.dtype),
                           preferred_element_type=jnp.float32)
            ms = jnp.mean(jnp.square(f.astype(jnp.float32)), axis=-1,
                          keepdims=True)
            z = z * jax.lax.rsqrt(ms + self.norm_eps)
            # alpha_pre over n columns, alpha_post over n, alpha_res over n^2
            scale = jnp.repeat(self.alpha._data.astype(jnp.float32),
                               np.array([n, n, n * n]),
                               total_repeat_length=n * n + 2 * n)
        h = mhc_maps(z, scale, self.bias._data.astype(jnp.float32), n,
                     self.iters, self.eps, *self.clamp)
        return h[:, :n], h[:, n:2 * n], h[:, 2 * n:].reshape(-1, n, n)

    def read(self, x):
        """x: Tensor [b, s, n, C] -> (u Tensor [b, s, C], carry): the
        sub-layer's input and what :meth:`write` needs."""
        b, s = x.shape[0], x.shape[1]
        xs = x._data.reshape(b * s, self.n, self.hidden)
        h_pre, h_post, h_res = self.maps(xs)
        col = getattr(_TLS, "active", None)
        if col is not None:
            with jax.named_scope("mhc_proj"):
                col.note(h_res, xs)
        with jax.named_scope("mhc_pre_mix"):
            xf = xs.astype(jnp.float32)
            u = sum(h_pre[:, j, None] * xf[:, j] for j in range(self.n))
            u = u.astype(xs.dtype)
        return Tensor(u.reshape(b, s, self.hidden)), (xs, h_post, h_res)

    def write(self, carry, y):
        """The streams after the sub-layer: ``H_res x + H_post y``.
        y: Tensor [b, s, C] -> Tensor [b, s, n, C]."""
        xs, h_post, h_res = carry
        b, s = y.shape[0], y.shape[1]
        with jax.named_scope("mhc_post_mix"):
            yf = y._data.reshape(b * s, self.hidden).astype(jnp.float32)
            xf = xs.astype(jnp.float32)
            out = h_post[:, :, None] * yf[:, None, :]
            for j in range(self.n):
                out = out + h_res[:, :, j, None] * xf[:, None, j, :]
            out = out.astype(xs.dtype)
        return Tensor(out.reshape(b, s, self.n, self.hidden))

    def extra_repr(self):
        return (f"streams={self.n}, hidden={self.hidden}, "
                f"sinkhorn_iters={self.iters}")


def hyper_connection_info(model):
    """``{streams, hidden, sublayers}`` of a model's hyper-connections, or
    None when it has none (the plain residual)."""
    layers = [sub for _, sub in model.named_sublayers()
              if isinstance(sub, HyperConnection)]
    if not layers:
        return None
    return {"streams": layers[0].n, "hidden": layers[0].hidden,
            "sublayers": len(layers)}
