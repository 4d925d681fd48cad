"""Pallas TPU paged-attention decode kernel.

TPU-native adaptation of vLLM's PagedAttention (the paper's serving
runtime): the GPU kernel's warp-level gather over 16-token pages becomes
explicit page-granular DMA — the page table is a *scalar-prefetch*
operand, so Pallas issues the HBM->VMEM copy for page
``page_table[b, p]`` ahead of the grid step that consumes it
(double-buffered by the pipeline), which is the TPU idiom for
data-dependent addressing.

* grid = (batch, kv_heads, pages_per_seq); the page axis is last
  (sequential), so the online-softmax scratch persists per (b, kv_head);
* the GQA query-head group for one kv head — a [group, D] tile — is the
  MXU operand, so all of a kv head's q-heads amortise one page fetch
  (GQA folding, DESIGN.md §5);
* pages past ``ceil(seq_len / page_size)`` are skipped via ``pl.when``
  (their DMA still lands in VMEM but no FLOPs are spent; index_map clamps
  to a valid page id);
* one new token per sequence (decode); memory-bound by design.

Pool layout is head-major, ``[n_pages, Hk, page_size, D]``: one grid
step's K/V block is a whole ``[page_size, D]`` tile of one kv head, so
the block's last two dims equal the array's — the TPU lowering refuses
a size-1 head axis in the second-to-last position.

Oracle: ``ref.paged_decode_attention_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def safe_page_index(page_table, seq_lens, b, p, page_size: int):
    """Physical page for grid step ``p`` of sequence ``b``, clamped to the
    sequence's last valid page. Steps past ``ceil(seq_len / page_size)``
    spend no FLOPs (the kernel body is skipped) but their block DMA still
    executes, so the index map must never read a stale/poisoned tail entry
    of the page table — those slots are allocator garbage."""
    n_valid = jnp.maximum(pl.cdiv(seq_lens[b], page_size), 1)
    return page_table[b, jnp.minimum(p, n_valid - 1)]


def _paged_kernel(
    # scalar-prefetch operands
    page_table_ref,                 # [B, pages_per_seq] int32 (SMEM)
    seq_lens_ref,                   # [B] int32 (SMEM)
    # array operands
    q_ref,                          # [1, 1, group, D]
    k_ref,                          # [1, 1, page_size, D]
    v_ref,                          # [1, 1, page_size, D]
    o_ref,                          # [1, 1, group, D]
    acc_ref, m_ref, l_ref,          # VMEM scratch
    *,
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    pages_per_seq: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens_ref[b]
    valid = seq_len - p * page_size          # tokens of this page in use

    @pl.when(valid > 0)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [group, D]
        k = k_ref[0, 0].astype(jnp.float32)                   # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [group, page]
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < valid
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        pexp = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + pexp.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,            # [B, H, D]
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    seq_lens: jax.Array,     # [B] int32
    *,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    n_pages, Hk, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    assert H % Hk == 0
    group = H // Hk
    q_r = q.reshape(B, Hk, group, D)

    def k_index(b, h, p, page_table, seq_lens):
        # clamp to a valid page id when past the sequence end; the body
        # is skipped there, the DMA just needs a legal source.
        page = safe_page_index(page_table, seq_lens, b, p, page_size)
        return (page, h, 0, 0)

    def q_index(b, h, p, page_table, seq_lens):
        return (b, h, 0, 0)

    kernel = functools.partial(
        _paged_kernel,
        scale=D ** -0.5,
        logit_softcap=logit_softcap,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hk, pages_per_seq),
            in_specs=[
                pl.BlockSpec((1, 1, group, D), q_index),
                pl.BlockSpec((1, 1, page_size, D), k_index),
                pl.BlockSpec((1, 1, page_size, D), k_index),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((group, D), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, group, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, seq_lens, q_r, k_pages, v_pages)
    return out.reshape(B, H, D)


def _batched_kernel(
    # scalar-prefetch operands
    page_table_ref,                 # [B, pages_per_seq] int32 (SMEM)
    seq_lens_ref,                   # [B] int32 (SMEM)
    # array operands
    q_ref,                          # [1, 1, group, D]
    k_ref,                          # [1, 1, page_size, D]
    v_ref,                          # [1, 1, page_size, D]
    k_new_ref,                      # [1, 1, 1, D]
    v_new_ref,                      # [1, 1, 1, D]
    o_ref,                          # [1, 1, group, D]
    acc_ref, m_ref, l_ref,          # VMEM scratch
    *,
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    n_page_steps: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens_ref[b]
    valid = seq_len - p * page_size          # tokens of this page in use
    q = q_ref[0, 0].astype(jnp.float32) * scale               # [group, D]

    def _softcap(s):
        if logit_softcap is not None:
            return logit_softcap * jnp.tanh(s / logit_softcap)
        return s

    def _accumulate(s, mask, values):
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        pexp = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + pexp.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            pexp, values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when((p < n_page_steps) & (valid > 0))
    def _page_body():
        k = k_ref[0, 0].astype(jnp.float32)                   # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = _softcap(jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))                                                     # [group, page]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accumulate(s, pos < valid, v)

    @pl.when(p == n_page_steps)
    def _new_token_body():
        # the current iteration's own K/V — not yet resident in the pool,
        # fused here so the kernel never reads a page it aliases with a
        # same-step scatter (position seq_len always attends to itself)
        k1 = k_new_ref[0, 0].astype(jnp.float32)               # [1, D]
        v1 = v_new_ref[0, 0].astype(jnp.float32)
        s = _softcap(jax.lax.dot_general(
            q, k1, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))                                                     # [group, 1]
        _accumulate(s, jnp.ones_like(s, dtype=jnp.bool_), v1)
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def batched_paged_decode_attention(
    q: jax.Array,            # [B, H, D]
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    seq_lens: jax.Array,     # [B] int32 tokens resident BEFORE this step
    k_new: jax.Array,        # [B, Hk, D] this iteration's key (not in pool)
    v_new: jax.Array,        # [B, Hk, D]
    *,
    max_pages: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """One engine iteration's whole decode set in a single ``pallas_call``.

    Extends :func:`paged_decode_attention` two ways, matching the engine's
    continuous-batching loop:

    * the current token's K/V ride along as operands and are folded in as
      a virtual trailing grid step, so attention covers ``seq_lens + 1``
      tokens without first scattering into the pool (the scatter still
      happens for the pool carry, but the kernel no longer reads pages it
      aliases — XLA needn't sequence a full-pool copy before the call);
    * ``max_pages`` statically trims the page grid to the deepest live
      sequence (the engine rounds to a power of two to bound recompiles),
      so a mostly-shallow batch doesn't stream ``pages_per_seq`` pages.

    Numerics match scatter-then-``paged_decode_attention(seq_lens + 1)``
    when ``k_new``/``v_new`` are pre-cast to the pool dtype.
    Oracle: ``ref.batched_paged_decode_attention_ref``.
    """
    B, H, D = q.shape
    n_pages, Hk, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    n_page_steps = pages_per_seq if max_pages is None else max_pages
    assert 1 <= n_page_steps <= pages_per_seq, (n_page_steps, pages_per_seq)
    assert H % Hk == 0
    group = H // Hk
    q_r = q.reshape(B, Hk, group, D)
    # a unit row axis makes the new token's [1, D] block span the
    # array's last two dims
    k_new = k_new.reshape(B, Hk, 1, D)
    v_new = v_new.reshape(B, Hk, 1, D)

    def k_index(b, h, p, page_table, seq_lens):
        page = safe_page_index(page_table, seq_lens, b, p, page_size)
        return (page, h, 0, 0)

    def q_index(b, h, p, page_table, seq_lens):
        return (b, h, 0, 0)

    kernel = functools.partial(
        _batched_kernel,
        scale=D ** -0.5,
        logit_softcap=logit_softcap,
        page_size=page_size,
        n_page_steps=n_page_steps,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # one extra (virtual) grid step folds in the new token
            grid=(B, Hk, n_page_steps + 1),
            in_specs=[
                pl.BlockSpec((1, 1, group, D), q_index),
                pl.BlockSpec((1, 1, page_size, D), k_index),
                pl.BlockSpec((1, 1, page_size, D), k_index),
                pl.BlockSpec((1, 1, 1, D), q_index),
                pl.BlockSpec((1, 1, 1, D), q_index),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((group, D), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, group, D), q.dtype),
        interpret=interpret,
        name="batched_paged_decode_attention",
    )(page_table, seq_lens, q_r, k_pages, v_pages, k_new, v_new)
    return out.reshape(B, H, D)
