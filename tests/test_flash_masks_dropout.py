"""Segment-id masks, hash dropout, and the varlen entry of the flash
attention kernels — the reference's flash_attn dropout arg (ops.yaml:239)
and flash_attn_unpadded / variable-length CUTLASS kernels (ops.yaml:252).

Pattern follows the reference's OpTest: kernel vs numpy/XLA reference,
values and grads, in Pallas interpret mode on the CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_infer_tpu.ops.attention import _xla_sdpa
from paddle_infer_tpu.ops.pallas.flash_attention import (
    dropout_keep, flash_attention, flash_attn_varlen, hybrid_attention)


def _make(b, s, h, d, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32) * 0.3,
                             dtype)
    return mk(), mk(), mk()


def _pad_segments(b, s, n_pad, rng):
    """Key-padding style segment ids: 1 for real tokens, 0 for trailing
    pads (per-row random pad counts up to n_pad)."""
    seg = np.ones((b, s), np.int32)
    for i in range(b):
        p = rng.randint(1, n_pad + 1)
        seg[i, s - p:] = 0
    return jnp.asarray(seg)


@pytest.mark.parametrize("impl", [flash_attention, hybrid_attention])
@pytest.mark.parametrize("causal", [False, True])
def test_segment_mask_matches_xla(impl, causal):
    b, s, h, d = 2, 256, 2, 64
    q, k, v = _make(b, s, h, d)
    seg = _pad_segments(b, s, 96, np.random.RandomState(3))
    out = impl(q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
               is_causal=causal, interpret=True)
    ref = _xla_sdpa(q, k, v, None, None, 0.0, causal, None,
                    q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", [flash_attention, hybrid_attention])
@pytest.mark.parametrize("causal", [False, True])
def test_segment_mask_grads_match_xla(impl, causal):
    b, s, h, d = 1, 128, 2, 64
    q, k, v = _make(b, s, h, d, seed=1)
    seg = _pad_segments(b, s, 40, np.random.RandomState(5))
    co = jnp.asarray(np.random.RandomState(2).randn(b, s, h, d)
                     .astype(np.float32))

    def loss_k(q, k, v):
        return jnp.sum(impl(q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
                            is_causal=causal, interpret=True) * co)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_sdpa(q, k, v, None, None, 0.0, causal, None,
                                 q_segment_ids=seg, kv_segment_ids=seg)
                       * co)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_packed_segments_isolate_sequences():
    """Two sequences packed into one row must attend only within
    themselves — same result as attending to each separately."""
    h, d = 2, 64
    s1, s2 = 128, 128
    q, k, v = _make(1, s1 + s2, h, d, seed=7)
    seg = jnp.asarray(np.concatenate(
        [np.zeros(s1, np.int32), np.ones(s2, np.int32)])[None])
    out = flash_attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
                          interpret=True)
    ref1 = _xla_sdpa(q[:, :s1], k[:, :s1], v[:, :s1], None, None, 0.0,
                     False, None)
    ref2 = _xla_sdpa(q[:, s1:], k[:, s1:], v[:, s1:], None, None, 0.0,
                     False, None)
    np.testing.assert_allclose(np.asarray(out[:, :s1]), np.asarray(ref1),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out[:, s1:]), np.asarray(ref2),
                               atol=2e-5, rtol=2e-5)


def test_fully_masked_rows_zero_output_zero_grads():
    """Queries with a unique segment id (no matching key) get zero output
    and contribute zero grads instead of NaN."""
    b, s, h, d = 1, 128, 2, 64
    q, k, v = _make(b, s, h, d, seed=9)
    qseg = np.ones((b, s), np.int32)
    qseg[0, -16:] = 7                      # no key carries id 7
    kseg = jnp.asarray(np.ones((b, s), np.int32))
    qseg = jnp.asarray(qseg)

    def loss(q, k, v):
        o = flash_attention(q, k, v, q_segment_ids=qseg,
                            kv_segment_ids=kseg, interpret=True)
        return jnp.sum(o), o

    (val, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    assert np.isfinite(np.asarray(val))
    np.testing.assert_array_equal(np.asarray(o[0, -16:]), 0.0)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
    # dead queries generate no dq
    np.testing.assert_array_equal(np.asarray(grads[0][0, -16:]), 0.0)


# ------------------------------------------------------------- dropout

@pytest.mark.parametrize("impl", [flash_attention, hybrid_attention])
def test_dropout_matches_xla_reference(impl):
    """The hash RNG makes every impl produce the identical dropout pattern,
    so kernel-vs-XLA comparison is exact-mask (values allclose)."""
    b, s, h, d = 2, 256, 2, 64
    q, k, v = _make(b, s, h, d, seed=11)
    seed = jnp.uint32(1234)
    out = impl(q, k, v, dropout_p=0.1, dropout_seed=seed, interpret=True)
    ref = _xla_sdpa(q, k, v, None, seed, 0.1, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", [flash_attention, hybrid_attention])
@pytest.mark.parametrize("causal", [False, True])
def test_dropout_grads_match_xla(impl, causal):
    b, s, h, d = 1, 128, 2, 64
    q, k, v = _make(b, s, h, d, seed=13)
    seed = jnp.uint32(99)
    co = jnp.asarray(np.random.RandomState(4).randn(b, s, h, d)
                     .astype(np.float32))

    def loss_k(q, k, v):
        return jnp.sum(impl(q, k, v, dropout_p=0.2, dropout_seed=seed,
                            is_causal=causal, interpret=True) * co)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_sdpa(q, k, v, None, seed, 0.2, causal, None)
                       * co)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_dropout_numeric_gradient():
    """With a fixed seed the dropped function is deterministic, so the
    analytic kernel backward must match finite differences (the OpTest
    numeric-grad check, op_test.py:1899)."""
    b, s, h, d = 1, 128, 1, 64
    q, k, v = _make(b, s, h, d, seed=17)
    seed = jnp.uint32(7)
    co = jnp.asarray(np.random.RandomState(6).randn(b, s, h, d)
                     .astype(np.float32))

    def loss(q):
        return jnp.sum(flash_attention(
            q, k, v, dropout_p=0.3, dropout_seed=seed, interpret=True) * co)

    g = np.asarray(jax.grad(loss)(q))
    rng = np.random.RandomState(8)
    qn = np.asarray(q)
    for _ in range(5):
        i = tuple(rng.randint(0, n) for n in qn.shape)
        eps = 1e-3
        qp, qm = qn.copy(), qn.copy()
        qp[i] += eps
        qm[i] -= eps
        num = (float(loss(jnp.asarray(qp))) - float(loss(jnp.asarray(qm)))) \
            / (2 * eps)
        np.testing.assert_allclose(g[i], num, atol=1e-3, rtol=1e-2)


def test_dropout_keep_rate_and_determinism():
    rows = jax.lax.broadcasted_iota(jnp.int32, (256, 256), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (256, 256), 1)
    keep = dropout_keep(jnp.uint32(42), 3, rows, cols, 0.25)
    rate = float(jnp.mean(keep.astype(jnp.float32)))
    assert abs(rate - 0.75) < 0.01, rate
    keep2 = dropout_keep(jnp.uint32(42), 3, rows, cols, 0.25)
    assert bool(jnp.all(keep == keep2))
    # different seed, head, or offset -> different mask
    assert not bool(jnp.all(
        keep == dropout_keep(jnp.uint32(43), 3, rows, cols, 0.25)))
    assert not bool(jnp.all(
        keep == dropout_keep(jnp.uint32(42), 4, rows, cols, 0.25)))


def test_dropout_zero_equals_no_dropout():
    q, k, v = _make(1, 128, 2, 64, seed=19)
    a = flash_attention(q, k, v, interpret=True)
    b_ = flash_attention(q, k, v, dropout_p=0.0, dropout_seed=jnp.uint32(5),
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_dropout_with_segments_and_causal():
    """All three features composed, kernel vs XLA reference."""
    b, s, h, d = 2, 256, 2, 64
    q, k, v = _make(b, s, h, d, seed=23)
    seg = _pad_segments(b, s, 64, np.random.RandomState(29))
    seed = jnp.uint32(31)
    out = flash_attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
                          dropout_p=0.15, dropout_seed=seed, is_causal=True,
                          interpret=True)
    ref = _xla_sdpa(q, k, v, None, seed, 0.15, True, None,
                    q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------- varlen

@pytest.mark.parametrize("causal", [False, True])
def test_varlen_matches_per_sequence_dense(causal):
    """Packed varlen attention == per-sequence dense attention (the
    reference flash_attn_unpadded contract)."""
    h, d = 2, 64
    lens = [100, 28, 130]                  # total 258 -> padded to 384
    total = sum(lens)
    rng = np.random.RandomState(37)
    mk = lambda: jnp.asarray(rng.randn(total, h, d).astype(np.float32) * 0.3)
    q, k, v = mk(), mk(), mk()
    cu = jnp.asarray(np.cumsum([0] + lens).astype(np.int32))
    out = flash_attn_varlen(q, k, v, cu, is_causal=causal, interpret=True)
    assert out.shape == (total, h, d)
    off = 0
    for n in lens:
        sl = slice(off, off + n)
        ref = _xla_sdpa(q[None, sl], k[None, sl], v[None, sl], None, None,
                        0.0, causal, None)[0]
        np.testing.assert_allclose(np.asarray(out[sl]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"seq at offset {off}")
        off += n


def test_varlen_grads_flow():
    h, d = 1, 64
    lens = [64, 64]
    total = sum(lens)
    rng = np.random.RandomState(41)
    mk = lambda: jnp.asarray(rng.randn(total, h, d).astype(np.float32) * 0.3)
    q, k, v = mk(), mk(), mk()
    cu = jnp.asarray(np.array([0, 64, 128], np.int32))

    def loss(q, k, v):
        return jnp.sum(flash_attn_varlen(q, k, v, cu, is_causal=True,
                                         interpret=True) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert g.shape == (total, h, d)
        assert np.all(np.isfinite(np.asarray(g)))
        assert float(jnp.sum(jnp.abs(g))) > 0


def test_varlen_functional_api():
    """nn.functional.flash_attn_unpadded end-to-end through the op
    registry (Tensor in / Tensor out, grads recorded)."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.nn import functional as F

    rng = np.random.RandomState(43)
    q = pit.Tensor(rng.randn(128, 2, 64).astype(np.float32))
    k = pit.Tensor(rng.randn(128, 2, 64).astype(np.float32))
    v = pit.Tensor(rng.randn(128, 2, 64).astype(np.float32))
    q.stop_gradient = False
    cu = pit.Tensor(np.array([0, 50, 128], np.int32))
    out = F.flash_attn_unpadded(q, k, v, cu, causal=True)
    assert tuple(out.shape) == (128, 2, 64)
    out.sum().backward()
    assert q.grad is not None
    assert np.all(np.isfinite(q.grad.numpy()))


# ------------------------------------------------------- fallback warnings

def test_dense_mask_warns_once_on_tpu(monkeypatch):
    import warnings as W

    from paddle_infer_tpu.ops import attention as A

    monkeypatch.setattr(A.pallas, "interpret", lambda: False)
    A._FALLBACK_WARNED.clear()
    q = jnp.zeros((1, 512, 2, 64))
    mask = jnp.zeros((1, 1, 512, 512))
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        assert A._attn_impl_choice(q, q, mask) == "xla"
        assert A._attn_impl_choice(q, q, mask) == "xla"
    msgs = [str(r.message) for r in rec if r.category is RuntimeWarning]
    assert len(msgs) == 1 and "segment_ids" in msgs[0]


def test_alignment_cliff_warns_once(monkeypatch):
    import warnings as W

    from paddle_infer_tpu.ops import attention as A

    monkeypatch.setattr(A.pallas, "interpret", lambda: False)
    A._FALLBACK_WARNED.clear()
    q = jnp.zeros((1, 520, 2, 64))         # 520 % 128 != 0
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        assert A._attn_impl_choice(q, q, None) == "xla"
        assert A._attn_impl_choice(q, q, None) == "xla"
    msgs = [str(r.message) for r in rec if r.category is RuntimeWarning]
    assert len(msgs) == 1 and "128" in msgs[0]


def test_internal_masks_do_not_warn(monkeypatch):
    """Engine-internal dense masks (kv_cache_mask decode) must not spam
    the user-facing fallback warning."""
    from paddle_infer_tpu.ops import attention as A

    monkeypatch.setattr(A.pallas, "interpret", lambda: False)
    A._FALLBACK_WARNED.clear()
    q = jnp.zeros((1, 512, 2, 64))
    mask = jnp.zeros((1, 1, 512, 512))
    assert A._attn_impl_choice(q, q, mask, quiet=True) == "xla"
    assert not A._FALLBACK_WARNED
    # short shapes never warn either (XLA is the intended path there)
    assert A._attn_impl_choice(jnp.zeros((1, 128, 2, 64)),
                               jnp.zeros((1, 128, 2, 64)), mask) == "xla"
    assert not A._FALLBACK_WARNED


def test_segments_do_not_force_xla(monkeypatch):
    """Segment ids and dropout keep the kernel engaged (VERDICT r2 #1)."""
    from paddle_infer_tpu.ops import attention as A

    monkeypatch.setattr(A.pallas, "interpret", lambda: False)
    q = jnp.zeros((1, 512, 2, 64))
    assert A._attn_impl_choice(q, q, None) == "hybrid"
    q = jnp.zeros((1, 4096, 2, 64))
    assert A._attn_impl_choice(q, q, None) == "flash"


# --------------------------------------------------- model-level plumbing

def test_ernie_padded_batch_trains_with_dropout():
    """ERNIE forward/backward with a padded batch + dropout 0.1 — the
    round-2 'real training config' — runs finite end to end with the
    2D mask riding as segment ids."""
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_infer_tpu.models.ernie import ernie_pretrain_loss

    cfg = ErnieConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=2, intermediate_size=128,
                      max_position_embeddings=64,
                      hidden_dropout_prob=0.1,
                      attention_probs_dropout_prob=0.1)
    model = ErnieForPretraining(cfg)
    model.train()
    rng = np.random.RandomState(0)
    b, s = 2, 64
    ids = pit.Tensor(rng.randint(0, 128, (b, s)).astype(np.int32))
    mask_np = np.ones((b, s), np.float32)
    mask_np[:, -6:] = 0.0                  # ~10% padding
    mask = pit.Tensor(mask_np)
    labels = pit.Tensor(rng.randint(0, 128, (b, s)).astype(np.int32))
    nsp = pit.Tensor(rng.randint(0, 2, (b,)).astype(np.int32))
    mlm, pooled = model(ids, attention_mask=mask)
    loss = ernie_pretrain_loss(mlm, pooled, labels, nsp)
    assert np.isfinite(loss.numpy())
    loss.backward()
    for p in model.parameters():
        if p.grad is not None:
            assert np.all(np.isfinite(p.grad.numpy()))
