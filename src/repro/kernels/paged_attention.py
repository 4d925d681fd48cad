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

``batched_paged_decode_attention``, the engine's kernel, serves a whole
decode set in one call and walks the pool differently: a grid step
covers a block of ``ppb`` pages of one sequence across all its kv heads
(a page is one contiguous ``[Hk, page_size, D]`` slab, one copy for
every head), and the grid holds only the blocks that have keys, in a
schedule computed on the device from ``seq_lens``. ``ppb`` comes from
the page's size and bytes (``pages_per_block``); the current token's
K/V are folded in at each sequence's last block. Oracle:
``ref.batched_paged_decode_attention_ref``.
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




# ``pages_per_block`` bounds: the keys a grid step covers (the width of
# its [Hk, group, keys] score tile), and the bytes of its K (or V) pages
# across the kv heads, so that the double-buffered K and V pages and
# their float32 copies fit the default scoped VMEM
BLOCK_KEYS = 256
BLOCK_BYTES = 512 * 1024


def pages_per_block(page_size: int, page_bytes: int,
                    n_page_steps: int) -> int:
    """Pages one grid step of the batched kernel covers: the largest
    power of two within ``BLOCK_KEYS`` keys and ``BLOCK_BYTES`` of K
    (``page_bytes`` per page, across every kv head), and no more than
    ``n_page_steps``."""
    ppb = 1
    while (2 * ppb <= n_page_steps and 2 * ppb * page_size <= BLOCK_KEYS
           and 2 * ppb * page_bytes <= BLOCK_BYTES):
        ppb *= 2
    return ppb


def block_schedule(page_table, seq_lens, *, page_size: int,
                   n_page_steps: int, ppb: int):
    """The batched kernel's grid steps, one per block of ``ppb`` pages
    holding keys, sequence after sequence; a sequence with no key gets
    one step (its new token). Returns ``(n_steps, step_seq, step_block,
    step_pages)``: the step count, each step's sequence and block, and
    the flat ``[steps * ppb]`` physical page of each slot of each step.

    Pages are read through ``safe_page_index``'s clamp: a slot at or past
    ``ceil(seq_len / page_size)`` takes the sequence's last live page (an
    empty sequence its first table entry, the engine's scratch page), so
    the table's tail, allocator garbage, is never read; the kernel masks
    such a slot. Arrays are sized for the most steps ``max_pages``
    allows; entries past ``n_steps`` are never used."""
    B = seq_lens.shape[0]
    max_steps = B * pl.cdiv(n_page_steps, ppb)
    slot = jnp.arange(ppb, dtype=jnp.int32)
    n_valid = jnp.minimum(pl.cdiv(seq_lens, page_size), n_page_steps)
    n_blocks = jnp.maximum(pl.cdiv(n_valid, ppb), 1)
    ends = jnp.cumsum(n_blocks)
    step = jnp.arange(max_steps, dtype=jnp.int32)
    step_seq = jnp.minimum((ends <= step[:, None]).sum(axis=1), B - 1)
    step_block = step - (ends - n_blocks)[step_seq]
    p = step_block[:, None] * ppb + slot                      # [steps, ppb]
    step_pages = safe_page_index(page_table, seq_lens, step_seq[:, None], p,
                                 page_size)
    return (ends[-1], step_seq.astype(jnp.int32),
            step_block.astype(jnp.int32), step_pages.reshape(-1))


def _batched_kernel(
    # scalar-prefetch operands
    step_seq_ref,                   # [max_steps] int32 (SMEM)
    step_block_ref,                 # [max_steps] int32
    step_pages_ref,                 # [max_steps * ppb] int32
    seq_lens_ref,                   # [B] int32
    # array operands
    q_ref,                          # [1, Hk, group, D]
    *refs,                          # ppb K pages, ppb V pages, each
                                    # [1, Hk, page_size, D]; k_new, v_new
                                    # [1, Hk, 1, D]; out [1, Hk, group, D];
                                    # scratch acc [Hk, group, D], m, l
                                    # [Hk, group, 1] float32
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    n_page_steps: int,
    ppb: int,
):
    del step_pages_ref              # read by the page operands' index maps
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    k_new_ref, v_new_ref, o_ref, acc_ref, m_ref, l_ref = refs[2 * ppb:]
    step = pl.program_id(0)
    b = step_seq_ref[step]
    j = step_block_ref[step]
    seq_len = seq_lens_ref[b]
    block_tokens = ppb * page_size

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale                  # [Hk, group, D]
    # keys past the live length (or past the trimmed page grid) are masked
    limit = jnp.minimum(seq_len, n_page_steps * page_size)
    start = j * block_tokens

    def _softcap(s):
        if logit_softcap is not None:
            return logit_softcap * jnp.tanh(s / logit_softcap)
        return s

    def _accumulate(s, v, mask=None):
        """Online-softmax update from scores ``s`` [Hk, group, n] over
        values ``v`` [Hk, n, D], keys outside ``mask`` left out."""
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        if mask is not None:
            pexp = jnp.where(mask, pexp, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + pexp.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(start < limit)
    def _block_body():
        # the block's pages side by side along the key axis: each page is
        # a whole [Hk, page_size, D] slab, so this stacks whole tiles
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)   # [Hk, T, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        k = k.astype(jnp.float32)
        # slots past the live length repeat the last live page: zero their
        # values so that nothing but finite zeros meets the masked exponents
        v = jnp.where(
            start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) < limit,
            v.astype(jnp.float32), 0.0)
        s = _softcap(jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ))                                                     # [Hk, group, T]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        _accumulate(s, v, pos < limit)

    @pl.when(start + block_tokens >= limit)
    def _new_token_and_finalize():
        # the sequence's last step: fold in the current iteration's own
        # K/V, not yet resident in the pool, so the kernel never reads a
        # page it aliases with a same-step scatter (position seq_len
        # always attends to itself, so the normaliser is never zero)
        k1 = k_new_ref[0].astype(jnp.float32)                  # [Hk, 1, D]
        v1 = v_new_ref[0].astype(jnp.float32)
        _accumulate(_softcap((q * k1).sum(axis=-1, keepdims=True)), v1)
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


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

    Attends each sequence's ``seq_lens[b]`` pooled tokens plus the
    current token, whose K/V ride along as operands: attention covers
    ``seq_lens + 1`` tokens without first scattering into the pool (the
    scatter still happens for the pool carry, but the kernel never reads
    a page it aliases, so XLA needn't sequence a pool copy before it).

    * one grid step covers a block of ``ppb`` pages of one sequence
      across **all** its kv heads. The grid is one sequential axis over
      the blocks that hold keys, sequence after sequence
      (``block_schedule``), its length computed on the device from
      ``seq_lens``: blocks past a sequence's end are not in the grid at
      all, and an empty slot (``seq_len`` 0, its table on the scratch
      page) gets one step, for its new token. ``max_pages`` statically
      caps the pages attended (the engine rounds it to a power of two to
      bound recompiles) and sizes the schedule;
    * the pool is passed ``ppb`` times for K and ``ppb`` times for V, each
      operand a block of one page across the heads, ``[1, Hk, page_size,
      D]``, placed from the schedule's scalar-prefetched page of that
      slot. A page is one contiguous slab of the head-major pool, so one
      copy per page covers every head, and the pipeline fetches the next
      step's pages (the next sequence's first block included) while this
      step computes. A slot past the live length repeats the sequence's
      last live page and is masked. (Copying pages by hand from an HBM
      operand, ``memory_space=pl.ANY``, is refused by the TPU compiler
      at these head dims: a slice of an operand whose last dim, 64 or
      80, is not a multiple of 128 lanes does not lower);
    * ``ppb`` is derived from what the kernel sees (``pages_per_block``):
      the largest power of two whose block spans at most ``BLOCK_KEYS``
      keys and whose K pages, ``ppb * Hk * page_size * D * itemsize``
      bytes, stay within ``BLOCK_BYTES``, capped at ``n_page_steps``; a
      sequence's last block is ragged where its pages run out (the extra
      slots are masked);
    * the GQA group rides the MXU: one batched matmul over the kv-head
      axis, ``[Hk, group, D] x [Hk, ppb * page_size, D]``, with a float32
      online softmax across a sequence's blocks;
    * the current token's K/V are folded in at the sequence's last step,
      which then writes the output.

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
    ppb = pages_per_block(page_size,
                          Hk * page_size * D * k_pages.dtype.itemsize,
                          n_page_steps)
    n_steps, *schedule = block_schedule(
        page_table, seq_lens, page_size=page_size,
        n_page_steps=n_page_steps, ppb=ppb)
    q_r = q.reshape(B, Hk, group, D)
    # a unit row axis makes the new token's [1, D] rows span the array's
    # last two dims
    k_new = k_new.reshape(B, Hk, 1, D)
    v_new = v_new.reshape(B, Hk, 1, D)

    def seq_index(step, step_seq, step_block, step_pages, seq_lens):
        return (step_seq[step], 0, 0, 0)

    def page_spec(i):
        def index(step, step_seq, step_block, step_pages, seq_lens):
            return (step_pages[step * ppb + i], 0, 0, 0)
        return pl.BlockSpec((1, Hk, page_size, D), index)

    kernel = functools.partial(
        _batched_kernel,
        scale=D ** -0.5,
        logit_softcap=logit_softcap,
        page_size=page_size,
        n_page_steps=n_page_steps,
        ppb=ppb,
    )
    page_specs = [page_spec(i) for i in range(ppb)]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((1, Hk, group, D), seq_index),
                *page_specs,
                *page_specs,
                pl.BlockSpec((1, Hk, 1, D), seq_index),
                pl.BlockSpec((1, Hk, 1, D), seq_index),
            ],
            out_specs=pl.BlockSpec((1, Hk, group, D), seq_index),
            scratch_shapes=[
                pltpu.VMEM((Hk, group, D), jnp.float32),
                pltpu.VMEM((Hk, group, 1), jnp.float32),
                pltpu.VMEM((Hk, group, 1), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, group, D), q.dtype),
        interpret=interpret,
        name="batched_paged_decode_attention",
    )(*schedule, seq_lens, q_r, *[k_pages] * ppb, *[v_pages] * ppb,
      k_new, v_new)
    return out.reshape(B, H, D)
