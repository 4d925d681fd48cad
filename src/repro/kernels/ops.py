"""Public kernel ops with platform dispatch.

The models call these — never the kernels or refs directly. Dispatch:

* ``impl='auto'`` (default): Pallas kernels on TPU; on CPU/GPU the
  chunked-jnp forms (identical math, bounded memory) so the whole system
  — including the 512-device dry-run — runs everywhere. The chunked
  forms are also what the dry-run lowers, so roofline FLOPs match the
  kernel's algorithm, not a naive O(L^2)-materialising fallback.
* ``impl='pallas'`` / ``'pallas_interpret'`` / ``'reference'`` force a
  path (tests use ``pallas_interpret`` to execute kernel bodies on CPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .chunked_prefill import chunked_prefill_attention as _chunked_prefill_pallas
from .flash_attention import flash_attention as _flash_pallas
from .paged_attention import (
    batched_paged_decode_attention as _batched_paged_pallas,
    paged_decode_attention as _paged_pallas,
)
from .ssd_scan import ssd_scan as _ssd_pallas

Impl = str  # 'auto' | 'pallas' | 'pallas_interpret' | 'reference'


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(
    q: jax.Array,            # [B, Lq, H, D]
    k: jax.Array,            # [B, Lk, Hk, D]
    v: jax.Array,            # [B, Lk, Hk, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    prefix_len: int = 0,
    impl: Impl = "auto",
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    """Batched multi-head (GQA) attention — prefill / training path."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if impl in ("pallas", "pallas_interpret"):
        return _flash_pallas(
            q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
            prefix_len=prefix_len, block_q=block_q, block_kv=block_kv,
            interpret=(impl == "pallas_interpret"),
        )
    # chunked-jnp: same online-softmax algorithm, XLA-compiled
    return ref.flash_attention_chunked(
        q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
        prefix_len=prefix_len, block_kv=max(block_kv, 512),
    )


def decode_attention(
    q: jax.Array,            # [B, H, D]
    k_cache: jax.Array,      # [B, S, Hk, D]
    v_cache: jax.Array,      # [B, S, Hk, D]
    cache_len: jax.Array,    # [B] int32
    *,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    impl: Impl = "auto",
) -> jax.Array:
    """Single-token decode against a contiguous per-sequence cache.

    This is a pure memory-bound gather+GEMV; XLA handles it well on all
    platforms, so there is no Pallas variant — the paged-pool variant
    below is the kernelised decode path."""
    del impl
    return ref.decode_attention_ref(
        q, k_cache, v_cache, cache_len, window=window, logit_softcap=logit_softcap
    )


def paged_decode_attention(
    q: jax.Array,            # [B, H, D]
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    seq_lens: jax.Array,     # [B] int32
    *,
    logit_softcap: Optional[float] = None,
    impl: Impl = "auto",
) -> jax.Array:
    """Decode attention over the vLLM-style paged KV pool."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if impl in ("pallas", "pallas_interpret"):
        return _paged_pallas(
            q, k_pages, v_pages, page_table, seq_lens,
            logit_softcap=logit_softcap,
            interpret=(impl == "pallas_interpret"),
        )
    return ref.paged_decode_attention_ref(
        q, k_pages, v_pages, page_table, seq_lens, logit_softcap=logit_softcap
    )


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
    impl: Impl = "auto",
) -> jax.Array:
    """One engine iteration's whole decode set in a single call: paged
    decode with the current token's K/V fused as a virtual trailing page
    (see ``paged_attention.batched_paged_decode_attention``)."""
    # the fused new-token K/V must see pool dtype so results are
    # bit-consistent with scatter-then-read on every impl
    k_new = k_new.astype(k_pages.dtype)
    v_new = v_new.astype(v_pages.dtype)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if impl in ("pallas", "pallas_interpret"):
        return _batched_paged_pallas(
            q, k_pages, v_pages, page_table, seq_lens, k_new, v_new,
            max_pages=max_pages, logit_softcap=logit_softcap,
            interpret=(impl == "pallas_interpret"),
        )
    del max_pages  # a DMA-trim hint; the gather oracle reads every page
    return ref.batched_paged_decode_attention_ref(
        q, k_pages, v_pages, page_table, seq_lens, k_new, v_new,
        logit_softcap=logit_softcap,
    )


def chunked_prefill_attention(
    q: jax.Array,            # [B, chunk, H, D] query slab
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    q_offsets: jax.Array,    # [B] int32 absolute position of q[:, 0]
    kv_lens: jax.Array,      # [B] int32 resident tokens incl. this slab
    *,
    logit_softcap: Optional[float] = None,
    impl: Impl = "auto",
) -> jax.Array:
    """Fused chunked-prefill attention over the paged KV pool: one
    prefill slab vs every resident page (cached prefix + prior chunks +
    itself), query-offset causal masked."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if impl in ("pallas", "pallas_interpret"):
        return _chunked_prefill_pallas(
            q, k_pages, v_pages, page_table, q_offsets, kv_lens,
            logit_softcap=logit_softcap,
            interpret=(impl == "pallas_interpret"),
        )
    return ref.chunked_prefill_attention_ref(
        q, k_pages, v_pages, page_table, q_offsets, kv_lens,
        logit_softcap=logit_softcap,
    )


def ssd(
    x: jax.Array,            # [B, L, H, P] dt-scaled
    a: jax.Array,            # [B, L, H]    log decays
    b: jax.Array,            # [B, L, G, N]
    c: jax.Array,            # [B, L, G, N]
    *,
    chunk: int = 256,
    impl: Impl = "auto",
) -> jax.Array:
    """Mamba-2 SSD chunked scan."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if impl in ("pallas", "pallas_interpret"):
        return _ssd_pallas(
            x, a, b, c, chunk=chunk, interpret=(impl == "pallas_interpret")
        )
    return ref.ssd_chunked(x, a, b, c, chunk=chunk)


def ssm_decode_step(h, x_t, a_t, b_t, c_t):
    """Single-token SSM state update (decode)."""
    return ref.ssm_decode_step_ref(h, x_t, a_t, b_t, c_t)
