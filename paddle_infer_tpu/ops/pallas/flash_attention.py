"""Pallas TPU flash attention (forward + backward) with segment-id masks,
in-kernel dropout, and an unpadded varlen entry point.

The role of the reference's FlashAttention CUDA kernels
(phi/kernels/gpu/flash_attn_kernel.cu, flash_attn_grad_kernel.cu; yaml
phi/api/yaml/ops.yaml:239 flash_attn — dropout is a first-class arg there —
and ops.yaml:252 flash_attn_unpadded / the CUTLASS
variable_length_memory_efficient_attention.cu varlen kernels) — but designed
for the TPU memory hierarchy: blocks of Q stay resident in VMEM while K/V
blocks stream in, both matmuls of each tile land on the MXU, and the
online-softmax state (m, l, acc) lives in VMEM scratch that persists across
the innermost grid dimension.

Layout: (batch, seq, heads, head_dim) — same as the reference flash_attn op —
folded to (batch*heads, seq, head_dim) for the kernel.

Backward is FlashAttention-2 style: save only the LSE from forward, then two
kernels — dKdV (grid over k-blocks, streaming q) and dQ (grid over q-blocks,
streaming k) — recompute P = exp(S - lse) per tile.  No O(s^2) tensor is ever
materialised.

Masking is segment-ids (the TPU-idiomatic form of padding + packed-sequence
varlen masks): q/kv positions attend iff their int32 segment ids are equal.
Padding = give pad tokens a distinct id; packing = one id per sequence.

Dropout is a counter-based hash RNG (splitmix32 finalizer over the absolute
(head, row, col) coordinates), NOT the stateful TPU PRNG: the same integer
function evaluates identically inside the Pallas tiles, in the hybrid XLA
forward, and in interpret mode on CPU — so forward and backward agree
bit-exactly about which probabilities were dropped without ever storing the
O(s^2) mask.

The per-row statistics (lse, delta) and q-side segment ids are stored
lane-broadcast as (bh, seq, STAT_LANES) so both grids read them in
(rows=q, lanes) orientation without sublane/lane transposes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

LANES = 128
# HBM-stored per-row stats (lse, delta, q-segment ids) only need a narrow
# lane tile; 128 lanes would write/read 16x the bytes for the same info
STAT_LANES = 8
# kv-side segment ids are stored (b, SEG_SUBLANES, sk): TPU block shapes
# need the second-minor dim divisible by 8 (or full), so the ids are
# sublane-broadcast the same way the q-side stats are lane-broadcast
SEG_SUBLANES = 8
NEG_INF = -1e30


def _fit_block(requested: int, seq: int) -> int:
    """Largest tile-aligned block <= requested that divides seq (so e.g.
    seq 4224 = 33*128 gets block 128 instead of a ValueError + silent XLA
    fallback). Steps by 128 down to 128, then by 8 (sublane tile)."""
    b = min(requested, seq)
    while b > 8 and seq % b:
        b -= 128 if b > 128 else 8
    return max(b, 1)


# ------------------------------------------------------------- hash dropout

_U = jnp.uint32


def _mix32(x):
    # splitmix32 finalizer: full avalanche over 32 bits in two
    # multiply-xorshift rounds — plenty for dropout-quality uniformity
    x = x ^ (x >> 16)
    x = x * _U(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * _U(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def dropout_keep(seed, bh, rows, cols, dropout_p):
    """Deterministic keep-mask for attention-probability dropout.

    ``seed`` uint32 scalar (traced ok); ``bh``/``rows``/``cols`` int arrays
    broadcastable together — the *absolute* folded-head index and q/k
    coordinates, so every caller (Pallas tile, XLA forward, interpret mode)
    regenerates the identical mask.  P(keep) = 1 - dropout_p.
    """
    thresh = _U(min(int(round(float(dropout_p) * 4294967296.0)), 4294967295))
    x = (jnp.asarray(rows).astype(_U) * _U(0x9E3779B1)
         + jnp.asarray(cols).astype(_U) * _U(0x85EBCA77)
         + jnp.asarray(bh).astype(_U) * _U(0xC2B2AE3D))
    x = _mix32(x ^ jnp.asarray(seed).astype(_U))
    return x >= thresh


# ---------------------------------------------------------------- forward

def _fwd_kernel(*refs, scale, causal, offset, block_q, block_k, num_k,
                segmented, dropout_p):
    i = 0
    if dropout_p:
        seed_ref = refs[i]; i += 1
    q_ref, k_ref, v_ref = refs[i:i + 3]; i += 3
    if segmented:
        qseg_ref, kseg_ref = refs[i:i + 2]; i += 2
    o_ref, lse_ref = refs[i:i + 2]; i += 2
    acc_ref, m_ref, l_ref = refs[i:i + 3]

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    needed = True
    if causal:
        # block (qi, ki) contributes iff some k index <= some q index
        needed = ki * block_k <= qi * block_q + block_q - 1 + offset

    @pl.when(needed)
    def _():
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]                                      # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        if segmented:
            seg_ok = qseg_ref[0][:, :1] == kseg_ref[0][:1]  # (bq,1)==(1,bk)
            s = jnp.where(seg_ok, s, NEG_INF)
        m_prev = m_ref[:, :1]                             # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        # the softmax denominator uses the raw p; dropout only affects what
        # reaches the value accumulation
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = dropout_keep(seed_ref[0], bh, rows, cols,
                                dropout_p)
            p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
        else:
            p_acc = p
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k - 1)
    def _():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[:] / safe_l
        if segmented:
            # fully-masked rows (e.g. pad queries with no same-segment key
            # when pads are unique) produce garbage accumulations behind a
            # still-NEG_INF running max — define their output as zero
            o = jnp.where(m_ref[:, :1] <= NEG_INF * 0.5, 0.0, o)
        o_ref[0] = o.astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(safe_l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _seg_specs(h, block_q, block_k, qmap, kmap):
    """BlockSpecs for (q_segment_ids (b, sq, STAT_LANES),
    kv_segment_ids (b, SEG_SUBLANES, sk)) — the grid's dim 0 is the folded
    batch*heads, so the index maps divide it back down to the batch
    coordinate.  Both sides carry a broadcast minor/major tile dim because
    TPU blocks need (8, 128)-aligned (or full) trailing dims."""
    qspec = pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, i, j: (b // h, qmap(i, j), 0))
    kspec = pl.BlockSpec((1, SEG_SUBLANES, block_k),
                         lambda b, i, j: (b // h, 0, kmap(i, j)))
    return qspec, kspec


def _fwd(q, k, v, qseg, kseg, seed, causal, scale, dropout_p, block_q,
         block_k, interpret, h):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    segmented = qseg is not None
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             offset=sk - sq, block_q=block_q,
                             block_k=block_k, num_k=nk, segmented=segmented,
                             dropout_p=dropout_p)
    in_specs, args = [], []
    if dropout_p:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed.reshape(1))
    in_specs += [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    args += [q, k, v]
    if segmented:
        qs, ks = _seg_specs(h, block_q, block_k,
                            lambda i, j: i, lambda i, j: j)
        in_specs += [qs, ks]
        args += [qseg, kseg]
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return o, lse


# --------------------------------------------------------------- backward

def _masked_p(s, lse, qi, ki, causal, segmented, offset, block_q, block_k,
              qseg_ref, kseg_ref):
    """Recompute P = exp(S - lse) for one tile, applying causal + segment
    masks.  Masked entries go through s = NEG_INF so they vanish for live
    rows; fully-masked (dead) rows have lse ~ NEG_INF which would make them
    exp(0) = 1, so segment masking is re-applied to p explicitly."""
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows + offset >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)
    if causal:
        p = jnp.where(rows + offset >= cols, p, 0.0)
    if segmented:
        seg_ok = qseg_ref[0][:, :1] == kseg_ref[0][:1]
        p = jnp.where(seg_ok, p, 0.0)
    return p


def _dkdv_kernel(*refs, scale, causal, offset, block_q, block_k, num_q,
                 segmented, dropout_p):
    i = 0
    if dropout_p:
        seed_ref = refs[i]; i += 1
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[i:i + 6]; i += 6
    qseg_ref = kseg_ref = None
    if segmented:
        qseg_ref, kseg_ref = refs[i:i + 2]; i += 2
    dk_ref, dv_ref = refs[i:i + 2]; i += 2
    dk_acc, dv_acc = refs[i:i + 2]

    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = True
    if causal:
        needed = ki * block_k <= qi * block_q + block_q - 1 + offset

    @pl.when(needed)
    def _():
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]
        do = do_ref[0]                                    # (bq, d)
        lse = lse_ref[0][:, :1]                           # (bq, 1)
        delta = delta_ref[0][:, :1]                       # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        p = _masked_p(s, lse, qi, ki, causal, segmented, offset,
                      block_q, block_k, qseg_ref, kseg_ref)
        # dp = do @ v^T
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        if dropout_p:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = dropout_keep(seed_ref[0], bh, rows, cols,
                                dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            pd = jnp.where(keep, p, 0.0) * inv            # what fwd used
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            pd = p
        # dv += pd^T @ do   (contract over q rows)
        dv_acc[:] += jax.lax.dot_general(
            pd, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dk += ds^T @ q
        dk_acc[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(*refs, scale, causal, offset, block_q, block_k, num_k,
               segmented, dropout_p):
    i = 0
    if dropout_p:
        seed_ref = refs[i]; i += 1
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[i:i + 6]; i += 6
    qseg_ref = kseg_ref = None
    if segmented:
        qseg_ref, kseg_ref = refs[i:i + 2]; i += 2
    dq_ref = refs[i]; i += 1
    dq_acc = refs[i]

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = True
    if causal:
        needed = ki * block_k <= qi * block_q + block_q - 1 + offset

    @pl.when(needed)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = _masked_p(s, lse, qi, ki, causal, segmented, offset,
                      block_q, block_k, qseg_ref, kseg_ref)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = dropout_keep(seed_ref[0], bh, rows, cols,
                                dropout_p)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout_p))
        ds = p * (dp - delta) * scale                     # (bq, bk)
        dq_acc[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, qseg, kseg, seed, causal, scale,
              dropout_p, block_q, block_k, interpret, h):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    segmented = qseg is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)               # (bh, sq, 1)
    delta = jnp.broadcast_to(delta, (bh, sq, STAT_LANES))

    q_spec_q = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
    stat_spec_q = pl.BlockSpec((1, block_q, STAT_LANES),
                               lambda b, i, j: (b, j, 0))
    kv_spec_k = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))

    seed_args, seed_specs = [], []
    if dropout_p:
        seed_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        seed_args = [seed.reshape(1)]

    seg_args = [qseg, kseg] if segmented else []
    # dkdv grid: i = k-block, j = q-block
    seg_specs_kq = (list(_seg_specs(h, block_q, block_k,
                                    lambda i, j: j, lambda i, j: i))
                    if segmented else [])
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                          offset=sk - sq, block_q=block_q,
                          block_k=block_k, num_q=nq, segmented=segmented,
                          dropout_p=dropout_p),
        grid=(bh, nk, nq),
        in_specs=seed_specs + [q_spec_q, kv_spec_k, kv_spec_k, q_spec_q,
                               stat_spec_q, stat_spec_q] + seg_specs_kq,
        out_specs=[kv_spec_k, kv_spec_k],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(*seed_args, q, k, v, do, lse, delta, *seg_args)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    stat_spec = pl.BlockSpec((1, block_q, STAT_LANES),
                             lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    # dq grid: i = q-block, j = k-block
    seg_specs_qk = (list(_seg_specs(h, block_q, block_k,
                                    lambda i, j: i, lambda i, j: j))
                    if segmented else [])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          offset=sk - sq, block_q=block_q,
                          block_k=block_k, num_k=nk, segmented=segmented,
                          dropout_p=dropout_p),
        grid=(bh, nq, nk),
        in_specs=seed_specs + [q_spec, kv_spec, kv_spec, q_spec, stat_spec,
                               stat_spec] + seg_specs_qk,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*seed_args, q, k, v, do, lse, delta, *seg_args)
    return dq, dk, dv


# ---------------------------------------------------- custom-vjp assembly
#
# seed is passed as (uint32 scalar array, static dropout_p) so a zero
# dropout config never pays for RNG codegen; qseg/kseg/seed may be None
# (empty pytrees through custom_vjp, None cotangents on the way back).

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash3(q, k, v, qseg, kseg, seed, causal, scale, dropout_p, block_q,
            block_k, interpret, h):
    o, _ = _fwd(q, k, v, qseg, kseg, seed, causal, scale, dropout_p,
                block_q, block_k, interpret, h)
    return o


def _flash3_fwd(q, k, v, qseg, kseg, seed, causal, scale, dropout_p,
                block_q, block_k, interpret, h):
    o, lse = _fwd(q, k, v, qseg, kseg, seed, causal, scale, dropout_p,
                  block_q, block_k, interpret, h)
    return o, (q, k, v, o, lse, qseg, kseg, seed)


def _flash3_bwd(causal, scale, dropout_p, block_q, block_k, interpret, h,
                res, do):
    q, k, v, o, lse, qseg, kseg, seed = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, qseg, kseg, seed, causal,
                           scale, dropout_p, block_q, block_k, interpret, h)
    return dq, dk, dv, None, None, None


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def _prep_segments(q_segment_ids, kv_segment_ids, b, sq, sk):
    if q_segment_ids is None and kv_segment_ids is None:
        return None, None
    if q_segment_ids is None or kv_segment_ids is None:
        raise ValueError("segment ids must be given for both q and kv")
    qseg = jnp.asarray(q_segment_ids, jnp.int32)
    kseg = jnp.asarray(kv_segment_ids, jnp.int32)
    if qseg.shape != (b, sq) or kseg.shape != (b, sk):
        raise ValueError(
            f"segment ids must be (batch, seq): got {qseg.shape} for q "
            f"(want {(b, sq)}), {kseg.shape} for kv (want {(b, sk)})")
    # q-side ids ride the same lane-broadcast layout as the row stats;
    # kv-side ids are sublane-broadcast for TPU block alignment
    qseg = jnp.broadcast_to(qseg[..., None], (b, sq, STAT_LANES))
    kseg = jnp.broadcast_to(kseg[:, None, :], (b, SEG_SUBLANES, sk))
    return qseg, kseg


def _prep_seed(dropout_p, dropout_seed):
    if not dropout_p:
        return None
    if dropout_seed is None:
        raise ValueError("dropout_p > 0 requires a dropout_seed")
    return jnp.asarray(dropout_seed).astype(jnp.uint32).reshape(())


_BLOCK_CANDIDATES = ((512, 512), (256, 512), (512, 256), (256, 256),
                     (1024, 512), (128, 128))


def _tuned_blocks(kind, b, h, sq, sk, d, dtype, causal, segmented,
                  dropout_p, interpret, runner):
    """Measured block-size selection (ops/pallas/autotune.py; reference
    phi/kernels/autotune AutoTuneBase::Run) — benchmarks fwd+bwd on dummy
    operands at trace time, keyed by the full shape signature."""
    from . import autotune as at

    default = (_fit_block(512, sq), _fit_block(512, sk))
    if interpret or not at.enabled():
        return default
    key = (f"{kind}:b{b}h{h}q{sq}k{sk}d{d}:{dtype}:c{int(causal)}"
           f":s{int(segmented)}:p{dropout_p:g}")

    def measure(blocks):
        bq = _fit_block(blocks[0], sq)
        bk = _fit_block(blocks[1], sk)
        if (bq, bk) != tuple(blocks):
            raise ValueError("blocks don't fit seq")
        return at.time_fn(lambda: runner(bq, bk))

    cands = [c for c in _BLOCK_CANDIDATES
             if c[0] <= sq and c[1] <= sk]
    try:
        return at.autotune(key, default, cands, measure)
    finally:
        _TUNE_OPERANDS.clear()     # winners are cached; free the HBM


def flash_attention(q, k, v, mask=None, q_segment_ids=None,
                    kv_segment_ids=None, dropout_p=0.0, dropout_seed=None,
                    is_causal=False, scale=None,
                    block_q=None, block_k=None, interpret=None):
    """Flash attention in (batch, seq, heads, head_dim) layout.

    Masking is via int32 ``{q,kv}_segment_ids`` (attend iff equal) plus
    ``is_causal``; arbitrary dense ``mask`` tensors are not supported by the
    kernel (the XLA sdpa path in ops/attention.py handles those).  Dropout
    drops attention probabilities with the deterministic ``dropout_keep``
    hash so backward regenerates the identical mask (reference flash_attn
    dropout arg, ops.yaml:239).  Seq lengths must divide the block sizes
    (block sizes are clamped to the seq lengths first).
    """
    if mask is not None:
        raise NotImplementedError("pallas flash kernel: dense mask "
                                  "unsupported — use segment ids")
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if interpret is None:
        interpret = _interpret()
    if block_q is None or block_k is None:
        def runner(bq, bk):
            return _tune_run(_flash3, b, h, sq, sk, d, q.dtype,
                             bool(is_causal), q_segment_ids is not None,
                             float(dropout_p), bq, bk)

        block_q, block_k = _tuned_blocks(
            "flash", b, h, sq, sk, d, str(q.dtype), bool(is_causal),
            q_segment_ids is not None, float(dropout_p), interpret,
            runner)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq ({sq},{sk}) must divide blocks "
                         f"({block_q},{block_k})")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qseg, kseg = _prep_segments(q_segment_ids, kv_segment_ids, b, sq, sk)
    seed = _prep_seed(dropout_p, dropout_seed)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    o = _flash3(fold(q), fold(k), fold(v), qseg, kseg, seed,
                bool(is_causal), float(scale), float(dropout_p),
                int(block_q), int(block_k), bool(interpret), h)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def _tune_run(kernel, b, h, sq, sk, d, dtype, causal, segmented,
              dropout_p, bq, bk):
    """One fwd+bwd execution of ``kernel`` on cached dummy operands —
    what the autotuner times per block candidate."""
    import numpy as _np

    # bounded key: str(dtype) ranges over jnp's closed dtype set, and
    # this caches autotune dummy operands, not compiled executables
    # tpulint: disable-next-line=recompile-hazard -- bounded key over jnp's closed dtype set; caches autotune operands, not executables
    key = (b, h, sq, sk, d, str(dtype), segmented)
    ops = _TUNE_OPERANDS.get(key)
    if ops is None:
        rng = _np.random.RandomState(0)
        mk = lambda s_: jnp.asarray(
            rng.randn(b * h, s_, d).astype(_np.float32) * 0.1, dtype)
        qf, kf, vf = mk(sq), mk(sk), mk(sk)
        if segmented:
            qseg = jnp.broadcast_to(
                jnp.ones((b, sq, 1), jnp.int32), (b, sq, STAT_LANES))
            kseg = jnp.broadcast_to(
                jnp.ones((b, 1, sk), jnp.int32), (b, SEG_SUBLANES, sk))
        else:
            qseg = kseg = None
        ops = (qf, kf, vf, qseg, kseg)
        _TUNE_OPERANDS[key] = ops
    qf, kf, vf, qseg, kseg = ops
    seed = jnp.uint32(0) if dropout_p else None
    scale = 1.0 / math.sqrt(d)

    @jax.jit
    def step(qf, kf, vf):
        def loss(qf, kf, vf):
            o = kernel(qf, kf, vf, qseg, kseg, seed, causal, scale,
                       dropout_p, bq, bk, False, h)
            return jnp.sum(o.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(qf, kf, vf)

    return step(qf, kf, vf)


_TUNE_OPERANDS = {}


# --------------------------------------------- hybrid: XLA fwd + Pallas bwd
#
# Measured on v5e at ERNIE-base shapes (b=32, h=12, d=64, s=512, bf16): the
# fused XLA forward (one HBM round-trip of the [s, s] logits) beats the
# Pallas kernel's forward (1.71ms vs 2.19ms), while the Pallas backward
# beats XLA's transpose (which materialises several [s, s] tensors).  So the
# fastest full training step pairs them: XLA forward that also emits the
# LSE, Pallas dKdV/dQ backward that recomputes P per tile from that LSE.
# Because dropout is the deterministic coordinate hash, the XLA forward and
# the Pallas backward agree on the dropped entries with no stored mask —
# which is what keeps this path available under real training configs
# (dropout 0.1 + padded batches), not just the benchmark-clean ones.

def _xla_fwd_with_lse(q, k, v, qseg, kseg, seed, causal, scale,
                      dropout_p, h):
    """Fused XLA attention forward returning (o, lse) in folded
    (bh, s, d) / (bh, sq) layout; lse is broadcast to STAT_LANES like
    _fwd's.  qseg here is the lane-broadcast (b, sq, STAT_LANES) form."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    b = bh // h
    logits = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale        # (bh, sq, sk)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        logits = jnp.where(rows + (sk - sq) >= cols, logits, NEG_INF)
    if qseg is not None:
        seg_ok = qseg[:, :, :1] == kseg[:, :1, :]          # (b, sq, sk)
        seg_ok = jnp.broadcast_to(seg_ok[:, None], (b, h, sq, sk))
        seg_ok = seg_ok.reshape(bh, sq, sk)
        logits = jnp.where(seg_ok, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if dropout_p:
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)[None]
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)[None]
        keep = dropout_keep(seed, jnp.arange(bh)[:, None, None],
                            rows, cols, dropout_p)
        p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
    o = jax.lax.dot_general(
        (p / l).astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    if qseg is not None:
        o = jnp.where(m <= NEG_INF * 0.5, 0.0, o)          # dead rows -> 0
    o = o.astype(q.dtype)
    lse = (m + jnp.log(jnp.where(l == 0.0, 1.0, l)))[..., 0]   # (bh, sq)
    return o, jnp.broadcast_to(lse[..., None], lse.shape + (STAT_LANES,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _hybrid(q, k, v, qseg, kseg, seed, causal, scale, dropout_p, block_q,
            block_k, interpret, h):
    o, _ = _xla_fwd_with_lse(q, k, v, qseg, kseg, seed, causal, scale,
                             dropout_p, h)
    return o


def _hybrid_fwd(q, k, v, qseg, kseg, seed, causal, scale, dropout_p,
                block_q, block_k, interpret, h):
    o, lse = _xla_fwd_with_lse(q, k, v, qseg, kseg, seed, causal, scale,
                               dropout_p, h)
    return o, (q, k, v, o, lse, qseg, kseg, seed)


def _hybrid_bwd(causal, scale, dropout_p, block_q, block_k, interpret, h,
                res, do):
    q, k, v, o, lse, qseg, kseg, seed = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, qseg, kseg, seed, causal,
                           scale, dropout_p, block_q, block_k, interpret, h)
    return dq, dk, dv, None, None, None


_hybrid.defvjp(_hybrid_fwd, _hybrid_bwd)


def hybrid_attention(q, k, v, q_segment_ids=None, kv_segment_ids=None,
                     dropout_p=0.0, dropout_seed=None, is_causal=False,
                     scale=None, block_q=None, block_k=None,
                     interpret=None):
    """XLA-forward / Pallas-backward attention, (b, s, h, d) layout.

    The training-path default on TPU for moderate sequence lengths (the
    pure-Pallas ``flash_attention`` takes over where the O(s^2) logits of
    the forward would blow HBM).  Supports segment-id masks and hash
    dropout like ``flash_attention``.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if interpret is None:
        interpret = _interpret()
    if block_q is None or block_k is None:
        def runner(bq, bk):
            return _tune_run(_hybrid, b, h, sq, sk, d, q.dtype,
                             bool(is_causal), q_segment_ids is not None,
                             float(dropout_p), bq, bk)

        block_q, block_k = _tuned_blocks(
            "hybrid", b, h, sq, sk, d, str(q.dtype), bool(is_causal),
            q_segment_ids is not None, float(dropout_p), interpret,
            runner)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq ({sq},{sk}) must divide blocks "
                         f"({block_q},{block_k})")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qseg, kseg = _prep_segments(q_segment_ids, kv_segment_ids, b, sq, sk)
    seed = _prep_seed(dropout_p, dropout_seed)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    o = _hybrid(fold(q), fold(k), fold(v), qseg, kseg, seed,
                bool(is_causal), float(scale), float(dropout_p),
                int(block_q), int(block_k), bool(interpret), h)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


# ------------------------------------------------------ varlen (unpadded)

def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k=None,
                      dropout_p=0.0, dropout_seed=None, is_causal=False,
                      scale=None, block_q=512, block_k=512, interpret=None):
    """Unpadded variable-length attention over packed sequences.

    The reference's flash_attn_unpadded (phi/api/yaml/ops.yaml:252) /
    variable_length_memory_efficient_attention.cu: ``q``/``k``/``v`` are
    (total_tokens, heads, head_dim) with the batch's sequences concatenated,
    and ``cu_seqlens_*`` are (n_seqs + 1,) int32 prefix sums of the sequence
    lengths.  TPU redesign: no ragged CUDA kernel — the packing IS the
    layout, and per-sequence isolation is segment-id masking inside the
    flash kernel, so one dense MXU-friendly kernel serves every batch shape.

    ``is_causal`` requires q and k packed with the same cu_seqlens (the
    self-attention case): causality is then per-sequence automatically
    because global order equals within-sequence order.
    """
    if cu_seqlens_k is None:
        cu_seqlens_k = cu_seqlens_q
    if is_causal and (cu_seqlens_k.shape != cu_seqlens_q.shape):
        raise NotImplementedError(
            "varlen causal requires identically packed q and k")
    total_q, heads, d = q.shape
    total_k = k.shape[0]

    def seg_ids(total, cu):
        # token t belongs to sequence j iff cu[j] <= t < cu[j+1]; tokens at
        # or past cu[-1] (alignment padding) land in segment n_seqs, which
        # never equals a real id on the other side *if* the other side has
        # no padding — and only pads-with-pads otherwise (masked downstream)
        pos = jnp.arange(total, dtype=jnp.int32)
        return jnp.searchsorted(cu[1:].astype(jnp.int32), pos,
                                side="right").astype(jnp.int32)

    qseg = seg_ids(total_q, cu_seqlens_q)[None]           # (1, total_q)
    kseg = seg_ids(total_k, cu_seqlens_k)[None]

    pad_q = (-total_q) % LANES
    pad_k = (-total_k) % LANES
    if pad_q or pad_k:
        n_seqs = cu_seqlens_q.shape[0] - 1
        pad3 = lambda x, p: jnp.pad(x, ((0, p), (0, 0), (0, 0)))
        q = pad3(q, pad_q)
        k = pad3(k, pad_k)
        v = pad3(v, pad_k)
        # alignment pads get a segment id past every real sequence
        qseg = jnp.pad(qseg, ((0, 0), (0, pad_q)), constant_values=n_seqs)
        kseg = jnp.pad(kseg, ((0, 0), (0, pad_k)),
                       constant_values=n_seqs + 1)
    out = flash_attention(
        q[None], k[None], v[None], q_segment_ids=qseg, kv_segment_ids=kseg,
        dropout_p=dropout_p, dropout_seed=dropout_seed, is_causal=is_causal,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return out[0, :total_q]
