"""The three maps of a manifold-constrained hyper-connection (mHC, arXiv
2512.24880) for every token of a step, Sinkhorn-Knopp's rounds included,
as ONE device operation.

``mhc_maps(z [T, n^2 + 2n], scale, bias, ...) -> [T, n^2 + 2n]`` float32,
per token, with ``a = z * scale + bias`` (``scale`` holds ``alpha_pre``
over the first ``n`` columns, ``alpha_post`` over the next ``n`` and
``alpha_res`` over the last ``n^2``):

    H_pre  = sigmoid(a[:n])
    H_post = 2 sigmoid(a[n:2n])
    M      = exp(clamp(a[2n:], clamp_min, clamp_max))  as an n x n matrix,
    ``iters`` times:  M <- M / (colsum(M) + eps);  M <- M / (rowsum(M) + eps)
    H_res  = M      (row-major in the output's last n^2 columns)

Kernel design: tokens ride the LANE axis (``z`` is handed over as
``[n^2 + 2n, T]``, ``T`` padded to whole 128-lane tiles), so the ``n x
n`` matrix of a token is ``n^2`` lane vectors and its row and column
sums are plain adds of those vectors: no cross-lane reduction anywhere,
and the ``2 x iters`` normalisations, which XLA would issue as a chain
of as many small fusions a sub-layer, run out of registers inside one
launch.  The work is a few thousand vector operations on a few KB: the
kernel is bound by its launch, not by a roofline (its share of one is
reported all the same, benchmarks/costs_xing4.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

LANES = 128
LANE_BLOCK = 512


def _kernel(z_ref, scale_ref, bias_ref, o_ref, *, n, iters, eps, clamp_min,
            clamp_max):
    a = z_ref[:] * scale_ref[:] + bias_ref[:]            # [n^2 + 2n, tl]
    o_ref[0:n, :] = jax.nn.sigmoid(a[0:n])
    o_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(a[n:2 * n])
    m = [[jnp.exp(jnp.clip(a[2 * n + i * n + j:2 * n + i * n + j + 1],
                           clamp_min, clamp_max))
          for j in range(n)] for i in range(n)]           # m[i][j]: [1, tl]

    def one_round(_, flat):
        m = [list(flat[i * n:(i + 1) * n]) for i in range(n)]
        for j in range(n):
            s = m[0][j]
            for i in range(1, n):
                s = s + m[i][j]
            s = s + eps
            for i in range(n):
                m[i][j] = m[i][j] / s
        for i in range(n):
            s = m[i][0]
            for j in range(1, n):
                s = s + m[i][j]
            s = s + eps
            for j in range(n):
                m[i][j] = m[i][j] / s
        return tuple(v for row in m for v in row)

    flat = jax.lax.fori_loop(0, iters, one_round,
                             tuple(v for row in m for v in row))
    for k, v in enumerate(flat):
        o_ref[2 * n + k:2 * n + k + 1, :] = v


def mhc_maps(z, scale, bias, n: int, iters: int, eps: float,
             clamp_min: float, clamp_max: float, interpret=None):
    """See the module docstring.  ``z`` [T, n^2 + 2n] float32; ``scale``
    and ``bias`` [n^2 + 2n] float32."""
    interpret = _interpret() if interpret is None else interpret
    t, w = z.shape
    assert w == n * n + 2 * n and scale.shape == bias.shape == (w,), (
        z.shape, scale.shape, bias.shape, n)
    tp = -(-t // LANES) * LANES
    tl = LANE_BLOCK if tp % LANE_BLOCK == 0 else LANES
    zt = jnp.pad(z.astype(jnp.float32).T, ((0, 0), (0, tp - t)))
    col = lambda v: v.astype(jnp.float32).reshape(w, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, iters=int(iters), eps=float(eps),
                          clamp_min=float(clamp_min),
                          clamp_max=float(clamp_max)),
        grid=(tp // tl,),
        in_specs=[pl.BlockSpec((w, tl), lambda i: (0, i)),
                  pl.BlockSpec((w, 1), lambda i: (0, 0)),
                  pl.BlockSpec((w, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((w, tl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((w, tp), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="mhc_maps",
    )(zt, col(scale), col(bias))
    return out[:, :t].T
