"""Tensor-parallel transformer building blocks shared by the model zoo.

Reference: the fused-multi-transformer decoder layer
(paddle/fluid/operators/fused/fused_multi_transformer_op.cc — attention +
FFN + layernorms in one op, cache-KV aware) and the Megatron TP layers
(fleet/layers/mpu/mp_layers.py).

TPU-first: blocks are built from Column/RowParallelLinear so the mp sharding
is carried by parameter partition specs; the attention core is the fused
``sdpa`` op (MXU-friendly single XLA computation / Pallas flash kernel).
Everything traces into one program under fleet/jit — the XLA analog of the
reference's fused op.
"""
from __future__ import annotations

import jax

from ..core.dispatch import dispatch as D
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers_common import Dropout, LayerNorm
from ..parallel.mp_layers import ColumnParallelLinear, RowParallelLinear


def _sep_active() -> bool:
    from ..parallel import topology

    mesh = topology.get_current_mesh()
    return mesh is not None and dict(mesh.shape).get("sep", 1) > 1


def take_head_rows(x, head_rows):
    """The hidden states the head will read.  ``x`` is a served step's
    flat token axis ``[1, T, hidden]`` and ``head_rows`` the in-range
    flat slots whose next-token logits the step samples from (``[b]``, or
    ``[b, W]`` under speculation): the result is ``[b(, W), hidden]``, so
    the final norm and the head run over those rows alone.  ``None``
    (every other caller) leaves ``x`` as it is."""
    if head_rows is None:
        return x
    from ..core.tensor import Tensor

    return Tensor(x._data[0][head_rows._data])


class ParallelSelfAttention(Layer):
    """Self-attention with heads sharded over "mp"; optional KV cache for
    decode (cache layout [b, s, h, d] — the reference CacheKV is
    [2, b, h, max_seq, d], fused_multi_transformer_op.cc:103)."""

    def __init__(self, hidden, num_heads, dropout=0.0, causal=False,
                 seq_parallel=None, rope_theta=None, num_kv_heads=None):
        """``rope_theta``: enable rotary position embedding (LLaMA-class
        decoders; reference fused_rope) with the given base.
        ``num_kv_heads``: grouped-query attention — fewer K/V heads,
        expanded to the query heads after RoPE (reference
        fused_multi_transformer GQA serving variants)."""
        super().__init__()
        assert hidden % num_heads == 0
        assert seq_parallel in (None, "ring", "ulysses")
        self.hidden = hidden
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        assert num_heads % self.num_kv_heads == 0
        self.head_dim = hidden // num_heads
        self.dropout = dropout
        self.causal = causal
        self.seq_parallel = seq_parallel
        self.rope_theta = rope_theta
        qkv_out = (num_heads + 2 * self.num_kv_heads) * self.head_dim
        self.qkv_proj = ColumnParallelLinear(hidden, qkv_out,
                                             gather_output=False)
        self.out_proj = RowParallelLinear(hidden, hidden,
                                          input_is_parallel=True)

    def _split_qkv(self, qkv, b, s):
        """[b, s, (hq+2*hkv)*d] -> q [b,s,hq,d], k/v [b,s,hkv,d]."""
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        if hkv == hq:
            qkv = D("reshape", qkv, shape=(b, s, 3, hq, d))
            return D("unstack", qkv, axis=2)
        qkv = D("reshape", qkv, shape=(b, s, hq + 2 * hkv, d))
        return D("split", qkv, num_or_sections=(hq, hkv, hkv), axis=2)

    def _rope_positions(self, cache, s):
        """Absolute positions for the current chunk, from the cache kind:
        paged → per-row page cursor, static → traced write index,
        growing → cached prefix length, none → 0..s-1."""
        import jax.numpy as jnp

        from ..core.tensor import Tensor

        ar = Tensor(jnp.arange(s, dtype=jnp.int32))
        if cache is not None and len(cache) >= 4:
            return D("unsqueeze", cache[3], axis=1) + ar     # [b, s]
        if cache is not None and len(cache) == 3:
            return ar + cache[2]
        if cache is not None:
            past = cache[0].shape[1]
            return Tensor(jnp.arange(past, past + s, dtype=jnp.int32))
        return ar

    def forward(self, x, attn_mask=None, cache=None, segment_ids=None,
                position_ids=None):
        b, s = x.shape[0], x.shape[1]
        # jax.named_scope: metadata on the operations (a trace names the
        # part after a refactor), no operation changes
        with jax.named_scope("qkv_proj"):
            qkv = self.qkv_proj(x)
            if cache is not None and len(cache) >= 6:
                # the mixed step's few flat tokens: keep the head split
                # out of the projection, or the TPU compiler computes the
                # product head-major and transposes the whole weight for
                # it on every step (tests/test_chip_compile.py)
                from ..core.tensor import Tensor

                qkv = Tensor(jax.lax.optimization_barrier(qkv._data))
            q, k, v = self._split_qkv(qkv, b, s)
            if self.rope_theta:
                if position_ids is None:
                    position_ids = self._rope_positions(cache, s)
                q = D("rope", q, position_ids, theta=self.rope_theta)
                k = D("rope", k, position_ids, theta=self.rope_theta)
            if self.num_kv_heads != self.num_heads:
                # GQA: expand K/V to the query heads post-RoPE so every
                # downstream path (caches incl. paged pools, sdpa,
                # kernels) sees plain MHA.  Cache-side narrow-kv storage
                # is a possible follow-up optimisation.
                rep = self.num_heads // self.num_kv_heads
                k = D("repeat_interleave", k, repeats=rep, axis=2)
                v = D("repeat_interleave", v, repeats=rep, axis=2)
        if cache is not None and len(cache) >= 4:
            return self._forward_paged(x, q, k, v, cache, attn_mask)
        static_cache = cache is not None and len(cache) == 3
        if static_cache:
            # decode path: fixed-length buffers [b, max_len, h, d] + traced
            # write index — one static shape for the whole generation loop
            # (reference CacheKV append, fused_multi_transformer_op.cu; here
            # dynamic_update_slice so XLA keeps a single executable).
            k_buf, v_buf, index = cache
            k = D("dynamic_update_slice", k_buf, k, index, axis=1)
            v = D("dynamic_update_slice", v_buf, v, index, axis=1)
        elif cache is not None:
            k = D("concat", cache[0], k, axis=1)
            v = D("concat", cache[1], v, axis=1)
        # pin head (and, under sequence parallelism, seq) sharding so GSPMD
        # keeps attention local per mp shard / per sep seq-shard
        hspec = (("data", "sep", "mp", None) if self.seq_parallel
                 else ("data", None, "mp", None))
        q = D("sharding_constraint", q, spec=hspec)
        k = D("sharding_constraint", k, spec=hspec)
        v = D("sharding_constraint", v, spec=hspec)
        if self.seq_parallel and _sep_active():
            assert cache is None, \
                "seq_parallel is a training feature (no KV cache)"
            op = ("ring_attention" if self.seq_parallel == "ring"
                  else "ulysses_attention")
            out = D(op, q, k, v, is_causal=self.causal)
        elif static_cache:
            # only slots < index + s hold real keys; the mask also carries
            # causality within the current chunk, so is_causal is off.
            mask = D("kv_cache_mask", index, q_len=s, kv_len=k.shape[1])
            if attn_mask is not None:
                mask = attn_mask + mask
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=0.0, is_causal=False,
                internal_mask=True)
        else:
            # causal stays on with a cache: the sdpa mask is offset by
            # (len_k - len_q), so cached prefill/decode attends to the full
            # past but never to future tokens of the current chunk.
            # Padding masks ride as segment ids (self-attention: same ids on
            # both sides) so the Pallas kernels stay engaged under real
            # padded-batch training configs.
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                dropout_p=self.dropout if self.training else 0.0,
                is_causal=self.causal,
                q_segment_ids=segment_ids, kv_segment_ids=segment_ids)
        with jax.named_scope("attn_out"):
            out = D("reshape", out, shape=(b, s, self.hidden))
            out = self.out_proj(out)
        if static_cache:
            return out, (k, v, index + s)
        if cache is not None:
            return out, (k, v)
        return out

    def _forward_paged(self, x, q, k, v, cache, attn_mask):
        """Paged-KV serving path (reference CacheKV semantics re-designed
        as a shared page pool, fused_multi_transformer_op.cc:103-119 +
        native/kv_allocator.cc): ``cache`` is
        ``(k_pages [P,h,page,d], v_pages, block_tables [b,max_pages],
        positions [b])`` where ``positions`` counts tokens already cached
        per row.  Prompt chunks (s > 1) scatter into pages and attend
        causally over themselves (right-padded batches: real tokens never
        see pads under causality); decode steps (s == 1) append one token
        at its per-row position and walk the page table with the Pallas
        decode kernel.

        A SIX-element cache ``(k_pages, v_pages, tables, positions,
        query_lens, scratch_page)`` selects the ragged mixed-batch
        variant (serving/programs.build_mixed_step): ``x`` is the
        step's flat token axis ``[1, T, hidden]`` with the rows laid end
        to end (``ragged_paged_attention.ragged_rows``), while tables,
        positions and ``query_lens`` stay per row.  Every row carries
        its own ``(query_len, context_len)``, decode rows have
        ``query_len == 1`` and chunk rows a prompt slice, all in one
        launch.  Only the writers and the kernel see the per-row
        ``[B, T, h, d]`` view, gathered from the flat axis here and
        gathered back after the launch — positions past a row's
        ``query_len`` are written nowhere and never attended.

        A SEVEN-element cache appends ``verify [b, W] bool`` (per-row
        speculative-verify flag broadcast over the draft window — the
        STATIC window size W rides in the array's shape, because every
        cache element is Tensor-wrapped on the way through
        ``_model_step``): flagged rows route their first W query
        positions through per-position decode-kernel math so draft
        verification stays bitwise-identical to sequential decode
        (serving/programs.build_mixed_step with ``spec_window > 1``)."""
        from ..core.tensor import Tensor
        from ..ops.pallas import paged_attention as PA

        # quantized pools ride as (payload, scales) Tensor pairs — unwrap
        # and rewrap per element so the cache pytree shape round-trips
        # through _model_step unchanged
        def raw(c):
            return tuple(t._data for t in c) if isinstance(c, tuple) \
                else c._data

        def wrap(a):
            return tuple(Tensor(x) for x in a) if isinstance(a, tuple) \
                else Tensor(a)

        b, s = x.shape[0], x.shape[1]
        k_pages, v_pages, tables, positions = (raw(c) for c in cache[:4])
        if len(cache) >= 6:
            from ..ops.pallas import ragged_paged_attention as RPA

            qlens = cache[4]._data
            scratch = cache[5]._data
            verify = cache[6]._data if len(cache) == 7 else None
            starts, row, offset, _ = RPA.ragged_rows(qlens, s)
            per_row = lambda t: RPA.rows_from_flat(t._data[0], starts, s)
            with jax.named_scope("kv_write"):
                k_pages = RPA.write_ragged_pages(k_pages, tables, per_row(k),
                                                 positions, qlens, scratch)
                v_pages = RPA.write_ragged_pages(v_pages, tables, per_row(v),
                                                 positions, qlens, scratch)
            with jax.named_scope("paged_attention"):
                q_rows = per_row(q)
            # scoped "paged_attention" inside (it keeps its Pallas calls'
            # instruction names out of the scope, see there)
            out = RPA.ragged_paged_attention(
                q_rows, k_pages, v_pages, tables, positions, qlens,
                verify_rows=None if verify is None else verify[:, 0],
                verify_window=None if verify is None
                else verify.shape[1])
            with jax.named_scope("paged_attention"):
                out = Tensor(out[row, offset][None])
            with jax.named_scope("attn_out"):
                out = D("reshape", out, shape=(b, s, self.hidden))
                out = self.out_proj(out)
            new = (wrap(k_pages), wrap(v_pages), Tensor(tables),
                   Tensor(positions + qlens), cache[4], cache[5])
            return out, (new + (cache[6],) if len(cache) == 7 else new)
        if s > 1:
            # prefill: pages for slots 0..s-1 (s % page_size == 0, padded
            # by the engine); garbage in pad slots is masked by `lengths`
            # at every later read
            k_pages = PA.write_prompt_pages(k_pages, tables, k._data)
            v_pages = PA.write_prompt_pages(v_pages, tables, v._data)
            if PA.is_quantized(k_pages):
                # quantized-domain prefill: attend over the bytes just
                # written, not the in-flight fp K/V — every other page
                # consumer dequantizes on read, and a near-tie argmax
                # would otherwise diverge between generate() and the
                # serving plane's chunked/ragged prefill
                k = Tensor(PA.gather_prompt_pages(k_pages, tables, s))
                v = Tensor(PA.gather_prompt_pages(v_pages, tables, s))
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, dropout_p=0.0, is_causal=True)
            new_pos = positions + s
        else:
            k_pages = PA.write_token_page(k_pages, tables, k._data[:, 0],
                                          positions)
            v_pages = PA.write_token_page(v_pages, tables, v._data[:, 0],
                                          positions)
            o = PA.paged_attention_decode(q._data[:, 0], k_pages, v_pages,
                                          tables, positions + 1)
            out = Tensor(o[:, None])         # [b, 1, h, d]
            new_pos = positions + 1
        with jax.named_scope("attn_out"):
            out = D("reshape", out, shape=(b, s, self.hidden))
            out = self.out_proj(out)
        return out, (wrap(k_pages), wrap(v_pages), Tensor(tables),
                     Tensor(new_pos))


class ParallelMLP(Layer):
    """Column→activation→Row FFN (Megatron split: no comm inside)."""

    def __init__(self, hidden, ffn_hidden, activation="gelu", dropout=0.0):
        super().__init__()
        self.fc1 = ColumnParallelLinear(hidden, ffn_hidden,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(ffn_hidden, hidden,
                                     input_is_parallel=True)
        self.activation = getattr(F, activation)
        self.dropout = Dropout(dropout)

    def forward(self, x):
        # act-dropout sits between the two matmuls (reference
        # TransformerEncoderLayer: linear2(dropout(act(linear1(x)))))
        return self.fc2(self.dropout(self.activation(self.fc1(x))))


class ParallelTransformerLayer(Layer):
    """One encoder/decoder block (post-LN default, matching ERNIE/BERT;
    pre-LN via normalize_before for GPT)."""

    def __init__(self, hidden, num_heads, ffn_hidden, dropout=0.1,
                 attn_dropout=None, activation="gelu",
                 normalize_before=False, causal=False,
                 layer_norm_eps=1e-12, seq_parallel=None,
                 num_experts=1, moe_gate="gshard", moe_top_k=2,
                 moe_capacity_factor=2.0):
        super().__init__()
        self.normalize_before = normalize_before
        self.self_attn = ParallelSelfAttention(
            hidden, num_heads,
            dropout=attn_dropout if attn_dropout is not None else dropout,
            causal=causal, seq_parallel=seq_parallel)
        if num_experts > 1:
            # MoE FFN (reference fused_multi_transformer_moe_op: per-layer
            # expert FFNs behind a gate; here parallel/moe.py fused path)
            from ..parallel.moe import MoELayer

            self.mlp = MoELayer(hidden, ffn_hidden, num_experts,
                                gate=moe_gate, top_k=moe_top_k,
                                capacity_factor=moe_capacity_factor,
                                activation=activation)
        else:
            self.mlp = ParallelMLP(hidden, ffn_hidden, activation, dropout)
        self.norm1 = LayerNorm(hidden, epsilon=layer_norm_eps)
        self.norm2 = LayerNorm(hidden, epsilon=layer_norm_eps)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)

    def forward(self, x, attn_mask=None, cache=None, segment_ids=None):
        residual = x
        if self.normalize_before:
            x = self.norm1(x)
        if cache is not None:
            attn_out, new_cache = self.self_attn(x, attn_mask, cache,
                                                 segment_ids=segment_ids)
        else:
            attn_out = self.self_attn(x, attn_mask,
                                      segment_ids=segment_ids)
            new_cache = None
        x = residual + self.dropout1(attn_out)
        if not self.normalize_before:
            x = self.norm1(x)
        residual = x
        if self.normalize_before:
            x = self.norm2(x)
        with jax.named_scope("ffn"):
            x = residual + self.dropout2(self.mlp(x))
        if not self.normalize_before:
            x = self.norm2(x)
        if cache is not None:
            return x, new_cache
        return x
