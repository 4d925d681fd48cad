"""Pallas TPU fused chunked-prefill attention over the paged KV pool.

The engine prefills a request in ``chunk_prefill_tokens`` slabs that share
each iteration's token budget with decode (Sarathi-style piggybacking, the
serving contract DESIGN.md §4 models). Each slab's K/V is scattered into
the sequence's pages *first* (the caller owns the scatter, exactly like
the decode path); this kernel then attends the query slab against every
resident page — the chunks written by slabs ``0..N-1`` *and* the prefix
pages matched in the radix tree — with query-offset causal masking:

* query row ``i`` of the slab sits at absolute position
  ``q_offset + i // group`` (rows are the flattened ``[chunk, group]``
  GQA tile, so one page fetch feeds all of a kv head's q-heads);
* key column ``j`` of page ``p`` sits at ``p * page_size + j``;
* a score survives iff ``k_pos <= q_pos`` and both fall inside
  ``kv_len`` — so resuming from a cached prefix is just ``q_offset > 0``
  with the prefix pages resident in the table.

The pool is head-major, ``[n_pages, Hk, page_size, D]`` (see
``paged_attention``). Grid = (batch, kv_heads, pages_per_seq); the page
axis is last
(sequential), so the online-softmax scratch — one ``[chunk * group, D]``
accumulator per (b, kv_head) — persists across pages. Pages wholly above
the slab's causal frontier or past ``kv_len`` are skipped via ``pl.when``
(index map clamped by ``safe_page_index``, as in the decode kernel).

Oracle: ``ref.chunked_prefill_attention_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, safe_page_index


def _chunked_prefill_kernel(
    # scalar-prefetch operands
    page_table_ref,                 # [B, pages_per_seq] int32 (SMEM)
    q_offsets_ref,                  # [B] int32 (SMEM)
    kv_lens_ref,                    # [B] int32 (SMEM)
    # array operands
    q_ref,                          # [1, 1, chunk * group, D]
    k_ref,                          # [1, 1, page_size, D]
    v_ref,                          # [1, 1, page_size, D]
    o_ref,                          # [1, 1, chunk * group, D]
    acc_ref, m_ref, l_ref,          # VMEM scratch
    *,
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    pages_per_seq: int,
    chunk: int,
    group: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_off = q_offsets_ref[b]
    kv_len = kv_lens_ref[b]
    page_start = p * page_size
    valid = kv_len - page_start              # tokens of this page in use

    # skip pages past the sequence end AND pages wholly above the slab's
    # causal frontier (a resumed chunk never looks past q_off + chunk - 1)
    @pl.when((valid > 0) & (page_start < q_off + chunk))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # [chunk * group, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # [chunk * group, page]
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_off + rows // group                 # absolute query pos
        k_pos = page_start + cols                     # absolute key pos
        mask = (k_pos <= q_pos) & (k_pos < kv_len) & (q_pos < kv_len)
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
        l = jnp.where(l == 0.0, 1.0, l)   # rows past kv_len -> zeros
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def chunked_prefill_attention(
    q: jax.Array,            # [B, chunk, H, D] query slab
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    q_offsets: jax.Array,    # [B] int32 absolute position of q[:, 0]
    kv_lens: jax.Array,      # [B] int32 resident tokens incl. this slab
    *,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Attend a prefill slab against paged KV it was just scattered into.

    The caller must have written the slab's K/V to the pages covering
    positions ``[q_offsets, q_offsets + chunk)`` before the call;
    ``kv_lens`` counts everything resident (cached prefix + prior chunks
    + this slab), i.e. normally ``q_offsets + chunk``.
    """
    B, chunk, H, D = q.shape
    n_pages, Hk, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    assert H % Hk == 0
    group = H // Hk
    # flatten to the [chunk * group, D] MXU tile per (b, kv head);
    # row r is chunk position r // group, q-head (r % group) of kv head h
    q_r = (q.reshape(B, chunk, Hk, group, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, Hk, chunk * group, D))

    def k_index(b, h, p, page_table, q_offsets, kv_lens):
        page = safe_page_index(page_table, kv_lens, b, p, page_size)
        return (page, h, 0, 0)

    def q_index(b, h, p, page_table, q_offsets, kv_lens):
        return (b, h, 0, 0)

    kernel = functools.partial(
        _chunked_prefill_kernel,
        scale=D ** -0.5,
        logit_softcap=logit_softcap,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        chunk=chunk,
        group=group,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, Hk, pages_per_seq),
            in_specs=[
                pl.BlockSpec((1, 1, chunk * group, D), q_index),
                pl.BlockSpec((1, 1, page_size, D), k_index),
                pl.BlockSpec((1, 1, page_size, D), k_index),
            ],
            out_specs=pl.BlockSpec((1, 1, chunk * group, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((chunk * group, D), jnp.float32),
                pltpu.VMEM((chunk * group, 1), jnp.float32),
                pltpu.VMEM((chunk * group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, chunk * group, D), q.dtype),
        interpret=interpret,
        name="chunked_prefill_attention",
    )(page_table, q_offsets, kv_lens, q_r, k_pages, v_pages)
    return (out.reshape(B, Hk, chunk, group, D)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, chunk, H, D))
