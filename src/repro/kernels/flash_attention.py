"""Pallas TPU flash-attention (prefill/training hot spot).

TPU-native adaptation of the FlashAttention blocking scheme:

* grid = (batch, q_heads, q_blocks, kv_blocks) — the kv dimension is the
  *last* (sequential) grid axis, so VMEM scratch (accumulator, running
  max/denominator) persists across kv iterations of one q block;
* BlockSpecs stage 128-aligned q/k/v tiles HBM->VMEM; the [block_q,
  block_kv] score tile and the [block_q, D] accumulator live in VMEM and
  feed the MXU directly;
* online softmax in f32 VREGs; output written once on the final kv step;
* GQA is handled in the index map (kv block index = q_head // group) —
  no repeated-KV materialisation in HBM;
* the wrapper lays q/k/v out head-major (``[B, H, L, D]``), so every
  block is a whole ``[block, D]`` tile: the TPU lowering refuses a
  size-1 head axis in a block's last two dims;
* causal + sliding-window masking skips kv blocks that are entirely
  masked (``pl.when`` around the whole body), so the causal case does
  ~L^2/2 work and the windowed case O(L * window).

Oracle: ``ref.mha_naive`` / ``ref.flash_attention_chunked`` (identical
math, same masking semantics).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30  # avoids -inf NaN propagation inside exp on fully-masked rows


def _flash_kernel(
    q_ref, k_ref, v_ref,            # VMEM tiles
    o_ref,                          # output tile
    acc_ref, m_ref, l_ref,          # VMEM scratch
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    logit_softcap: Optional[float],
    prefix_len: int,
    q_offset: int,
    lk_valid: int,
    block_q: int,
    block_kv: int,
    n_kv_blocks: int,
):
    i = pl.program_id(2)            # q block
    j = pl.program_id(3)            # kv block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this tile
    q_start = i * block_q + q_offset
    k_start = j * block_kv

    # block-level skip: entirely-future (causal) or entirely-expired (window)
    run = k_start < lk_valid
    if causal:
        vis = k_start <= q_start + block_q - 1
        if prefix_len:
            vis = jnp.logical_or(vis, k_start < prefix_len)
        run = jnp.logical_and(run, vis)
    if window is not None:
        # newest query in tile: q_start + block_q - 1; oldest visible key:
        # q_pos - window + 1. Tile's newest key is k_start + block_kv - 1.
        vis = k_start + block_kv - 1 >= q_start - window + 1
        if prefix_len:
            vis = jnp.logical_or(vis, k_start < prefix_len)
        run = jnp.logical_and(run, vis)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                    # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [bq, bkv]
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        inner = jnp.ones((block_q, block_kv), dtype=jnp.bool_)
        if causal:
            inner = jnp.logical_and(inner, q_pos >= k_pos)
        if window is not None:
            inner = jnp.logical_and(inner, q_pos - k_pos < window)
        if prefix_len:
            inner = jnp.logical_or(inner, k_pos < prefix_len)
        mask = jnp.logical_and(k_pos < lk_valid, inner)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                   # [bq]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)                        # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # [B, Lq, H, D]
    k: jax.Array,            # [B, Lk, Hk, D]
    v: jax.Array,            # [B, Lk, Hk, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    prefix_len: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    """GQA flash attention. Queries are aligned to the *end* of the key
    sequence when Lq < Lk (decode-style)."""
    B, Lq, H, D = q.shape
    _, Lk, Hk, _ = k.shape
    assert H % Hk == 0, (H, Hk)
    group = H // Hk

    block_q = min(block_q, max(Lq, 8))
    block_kv = min(block_kv, max(Lk, 8))
    nq = -(-Lq // block_q)
    nk = -(-Lk // block_kv)
    pad_q = nq * block_q - Lq
    pad_k = nk * block_kv - Lk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    kernel = functools.partial(
        _flash_kernel,
        scale=D ** -0.5,
        causal=causal,
        window=window,
        logit_softcap=logit_softcap,
        prefix_len=prefix_len,
        q_offset=Lk - Lq,
        lk_valid=Lk,
        block_q=block_q,
        block_kv=block_kv,
        n_kv_blocks=nk,
    )

    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))   # head-major
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
        scratch_shapes=[
            # f32 VMEM scratch persisted across the sequential kv axis
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Lq].transpose(0, 2, 1, 3)
