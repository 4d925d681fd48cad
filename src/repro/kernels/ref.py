"""Pure-jnp oracles for every Pallas kernel.

Each function here is the semantic ground truth the kernels are tested
against (tests/test_kernels.py sweeps shapes/dtypes and asserts
allclose). Two tiers:

* ``*_naive``   — the textbook O(L^2)-materialising forms; used only as
  oracles on small shapes.
* ``*_chunked`` — jnp/lax.scan blockwise forms with identical math but
  bounded memory; these are what the models lower on non-TPU backends
  (and therefore what the dry-run's HLO contains), and they are
  themselves validated against the naive forms.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..xla_scan import scan as _scan


# ---------------------------------------------------------------------------
# Attention oracles
# ---------------------------------------------------------------------------

def mha_naive(
    q: jax.Array,            # [B, Lq, H, D]
    k: jax.Array,            # [B, Lk, Hk, D]
    v: jax.Array,            # [B, Lk, Hk, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    prefix_len: int = 0,
) -> jax.Array:
    """Textbook GQA attention, materialising the full score matrix.

    ``prefix_len`` > 0 gives prefix-LM masking: the first ``prefix_len``
    key positions are visible to every query (PaliGemma image prefix)."""
    B, Lq, H, D = q.shape
    _, Lk, Hk, _ = k.shape
    assert H % Hk == 0, (H, Hk)
    group = H // Hk
    qf = q.astype(jnp.float32) * (D ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads to q heads
    kf = jnp.repeat(kf, group, axis=2)
    vf = jnp.repeat(vf, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    if logit_softcap is not None:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    # causal + sliding-window masking over absolute positions; when
    # Lq < Lk the queries are assumed to be the *last* Lq positions
    # (decode-style alignment).
    q_pos = jnp.arange(Lq)[:, None] + (Lk - Lq)
    k_pos = jnp.arange(Lk)[None, :]
    mask = jnp.ones((Lq, Lk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    if prefix_len:
        mask |= k_pos < prefix_len
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


def flash_attention_chunked(
    q: jax.Array,            # [B, Lq, H, D]
    k: jax.Array,            # [B, Lk, Hk, D]
    v: jax.Array,            # [B, Lk, Hk, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    prefix_len: int = 0,
    block_kv: int = 1024,
) -> jax.Array:
    """Online-softmax attention scanning over KV blocks.

    Memory is O(Lq * block_kv) instead of O(Lq * Lk); this is the form
    the 32k-prefill cells lower on CPU, and the jnp mirror of the Pallas
    flash kernel's math.
    """
    B, Lq, H, D = q.shape
    _, Lk, Hk, _ = k.shape
    group = H // Hk
    n_blocks = -(-Lk // block_kv)
    pad = n_blocks * block_kv - Lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qf = q.astype(jnp.float32) * (D ** -0.5)
    kf = k.astype(jnp.float32).reshape(B, n_blocks, block_kv, Hk, D)
    vf = v.astype(jnp.float32).reshape(B, n_blocks, block_kv, Hk, D)

    q_pos = jnp.arange(Lq)[:, None] + (Lk - Lq)          # [Lq, 1]

    def body(carry, blk):
        m, l, acc = carry                                 # [B,H,Lq], [B,H,Lq], [B,Lq,H,D]
        kb, vb, j = blk                                   # [B,block,Hk,D] x2, scalar
        kb = jnp.repeat(kb, group, axis=2)
        vb = jnp.repeat(vb, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)         # [B,H,Lq,block]
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        k_pos = j * block_kv + jnp.arange(block_kv)[None, :]
        mask = k_pos < Lk                                  # padding
        inner = jnp.ones_like(mask)
        if causal:
            inner &= q_pos >= k_pos
        if window is not None:
            inner &= q_pos - k_pos < window
        if prefix_len:
            inner |= k_pos < prefix_len
        mask &= inner
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new == -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * scale + p.sum(axis=-1)
        acc = acc * scale.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    acc0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    (m, l, acc), _ = _scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0), jnp.arange(n_blocks)),
    )
    l = jnp.where(l == 0.0, 1.0, l)                       # fully-masked rows -> 0 out
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,            # [B, H, D]       one new token per sequence
    k_cache: jax.Array,      # [B, S, Hk, D]
    v_cache: jax.Array,      # [B, S, Hk, D]
    cache_len: jax.Array,    # [B] int32       valid prefix length per seq
    *,
    logit_softcap: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-token decode attention against a contiguous KV cache."""
    B, S, Hk, D = k_cache.shape
    H = q.shape[1]
    group = H // Hk
    qf = q.astype(jnp.float32) * (D ** -0.5)
    qf = qf.reshape(B, Hk, group, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k_cache.astype(jnp.float32))
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    pos = jnp.arange(S)[None, :]                          # [1, S]
    mask = pos < cache_len[:, None]
    if window is not None:
        mask &= pos >= (cache_len[:, None] - window)
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Each sequence's pages of a head-major pool ``[n_pages, Hk,
    page_size, D]`` as one contiguous ``[B, pages_per_seq * page_size,
    Hk, D]`` view."""
    g = pages[page_table]                     # [B, pps, Hk, page, D]
    B, pps, Hk, page_size, D = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, pps * page_size, Hk, D)


def paged_decode_attention_ref(
    q: jax.Array,            # [B, H, D]
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]  global page pool
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32     physical page ids
    seq_lens: jax.Array,     # [B] int32
    *,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """Decode attention over a vLLM-style paged KV pool (oracle).

    Gathers each sequence's pages into a contiguous view, then defers to
    :func:`decode_attention_ref`.
    """
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    return decode_attention_ref(
        q, k, v, seq_lens, logit_softcap=logit_softcap
    )


def batched_paged_decode_attention_ref(
    q: jax.Array,            # [B, H, D]
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    seq_lens: jax.Array,     # [B] int32 tokens resident BEFORE this step
    k_new: jax.Array,        # [B, Hk, D]
    v_new: jax.Array,        # [B, Hk, D]
    *,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """Oracle for the batched decode kernel: scatter the new token into
    the gathered contiguous view at position ``seq_lens[b]``, then attend
    over ``seq_lens + 1`` tokens."""
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    rows = jnp.arange(q.shape[0])
    k = k.at[rows, seq_lens].set(k_new.astype(k.dtype))
    v = v.at[rows, seq_lens].set(v_new.astype(v.dtype))
    return decode_attention_ref(
        q, k, v, seq_lens + 1, logit_softcap=logit_softcap
    )


def chunked_prefill_attention_ref(
    q: jax.Array,            # [B, chunk, H, D] query slab
    k_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    v_pages: jax.Array,      # [n_pages, Hk, page_size, D]
    page_table: jax.Array,   # [B, pages_per_seq] int32
    q_offsets: jax.Array,    # [B] int32 absolute position of q[:, 0]
    kv_lens: jax.Array,      # [B] int32 resident tokens incl. this slab
    *,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """Oracle for the fused chunked-prefill kernel: gather each
    sequence's pages into a contiguous view and apply query-offset causal
    masking at absolute positions (query row i sits at position
    ``q_offsets[b] + i``; rows past ``kv_lens`` come back as zeros)."""
    B, chunk, H, D = q.shape
    k = gather_pages(k_pages, page_table).astype(jnp.float32)
    v = gather_pages(v_pages, page_table).astype(jnp.float32)
    S, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    qf = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, chunk, Hk, group, D)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qf, k)            # [B,Hk,g,chunk,S]
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    q_pos = q_offsets[:, None] + jnp.arange(chunk)[None, :]      # [B, chunk]
    k_pos = jnp.arange(S)[None, :]                               # [1, S]
    mask = (k_pos[:, None, :] <= q_pos[:, :, None])              # causal
    mask &= k_pos[:, None, :] < kv_lens[:, None, None]
    mask &= q_pos[:, :, None] < kv_lens[:, None, None]
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    # safe softmax: fully-masked rows (q_pos >= kv_len) -> zeros
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(jnp.isfinite(s), jnp.exp(s - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)
    l = e.sum(axis=-1, keepdims=True)
    p = e / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p, v)
    return out.reshape(B, chunk, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) oracles
# ---------------------------------------------------------------------------

def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < t <= i} a[..., t].

    (the log-decay matrix of the SSD intra-chunk term; -inf above the
    diagonal)."""
    T = a.shape[-1]
    csum = jnp.cumsum(a, axis=-1)
    out = csum[..., :, None] - csum[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_naive(
    x: jax.Array,            # [B, L, H, P]   (already dt-scaled)
    a: jax.Array,            # [B, L, H]      log decay per step (<= 0)
    b: jax.Array,            # [B, L, G, N]
    c: jax.Array,            # [B, L, G, N]
) -> jax.Array:
    """Quadratic "attention form" of SSD: y_i = sum_{j<=i} C_i^T B_j
    exp(sum_{j<t<=i} a_t) x_j. Oracle for small L."""
    B_, L, H, P = x.shape
    G = b.shape[2]
    rep = H // G
    bf = jnp.repeat(b.astype(jnp.float32), rep, axis=2)   # [B, L, H, N]
    cf = jnp.repeat(c.astype(jnp.float32), rep, axis=2)
    af = a.astype(jnp.float32)
    Lmat = jnp.exp(_segsum(af.transpose(0, 2, 1)))        # [B, H, L, L]
    scores = jnp.einsum("blhn,bshn->bhls", cf, bf) * Lmat
    y = jnp.einsum("bhls,bshp->blhp", scores, x.astype(jnp.float32))
    return y.astype(x.dtype)


def ssd_chunked(
    x: jax.Array,            # [B, L, H, P]
    a: jax.Array,            # [B, L, H]
    b: jax.Array,            # [B, L, G, N]
    c: jax.Array,            # [B, L, G, N]
    *,
    chunk: int = 256,
    return_final_state: bool = False,
):
    """SSD chunked scan (Mamba-2 paper ssd_minimal): intra-chunk quadratic
    term + inter-chunk recurrent state carry. Linear memory in L.

    Sequences that do not divide the chunk are zero-padded: pad tokens
    have x=0 (no state injection) and a=0 (decay exp(0)=1, state
    unchanged), so outputs at valid positions and the carried state are
    exact."""
    B_, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    chunk = max(1, min(chunk, L))
    pad = (-L) % chunk
    L_orig = L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        L = L + pad
    nc = L // chunk

    xf = x.astype(jnp.float32).reshape(B_, nc, chunk, H, P)
    af = a.astype(jnp.float32).reshape(B_, nc, chunk, H)
    bf = b.astype(jnp.float32).reshape(B_, nc, chunk, G, N)
    cf = c.astype(jnp.float32).reshape(B_, nc, chunk, G, N)
    bf = jnp.repeat(bf, rep, axis=3)                      # [B,nc,Q,H,N]
    cf = jnp.repeat(cf, rep, axis=3)

    a_t = af.transpose(0, 1, 3, 2)                        # [B,nc,H,Q]
    a_cs = jnp.cumsum(a_t, axis=-1)                       # inclusive cumsum
    Lmat = jnp.exp(_segsum(a_t))                          # [B,nc,H,Q,Q]

    # 1) intra-chunk (diagonal blocks)
    scores = jnp.einsum("bnqhk,bnshk->bnhqs", cf, bf) * Lmat
    y_diag = jnp.einsum("bnhqs,bnshp->bnqhp", scores, xf)

    # 2) per-chunk final states: state_n = sum_i exp(a_cs[-1]-a_cs[i]) B_i x_i^T
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)         # [B,nc,H,Q]
    states = jnp.einsum(
        "bnhq,bnqhk,bnqhp->bnhpk", decay_states, bf, xf
    )                                                      # [B,nc,H,P,N]

    # 3) inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(a_cs[..., -1])                  # [B,nc,H]

    def scan_body(h, inp):
        st, dec = inp                                      # [B,H,P,N], [B,H]
        h_new = h * dec[..., None, None] + st
        return h_new, h                                    # emit state *before* chunk

    h0 = jnp.zeros((B_, H, P, N), jnp.float32)
    h_final, h_prev = _scan(
        scan_body, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)                   # [B,nc,H,P,N]

    # 4) inter-chunk contribution: y_i += C_i^T (exp(a_cs[i]) * h_prev)
    in_decay = jnp.exp(a_cs)                              # [B,nc,H,Q]
    y_off = jnp.einsum(
        "bnqhk,bnhpk,bnhq->bnqhp", cf, h_prev, in_decay
    )

    y = (y_diag + y_off).reshape(B_, L, H, P)[:, :L_orig].astype(x.dtype)
    if return_final_state:
        return y, h_final
    return y


def ssm_decode_step_ref(
    h: jax.Array,            # [B, H, P, N] recurrent state
    x_t: jax.Array,          # [B, H, P]    dt-scaled input
    a_t: jax.Array,          # [B, H]       log decay this step
    b_t: jax.Array,          # [B, G, N]
    c_t: jax.Array,          # [B, G, N]
) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSM recurrence (decode path): h' = e^a h + B x^T,
    y = C . h'. Constant memory — the SSM answer to a KV cache."""
    B_, H, P, N = h.shape
    G = b_t.shape[1]
    rep = H // G
    bf = jnp.repeat(b_t.astype(jnp.float32), rep, axis=1)  # [B,H,N]
    cf = jnp.repeat(c_t.astype(jnp.float32), rep, axis=1)
    h_new = h * jnp.exp(a_t.astype(jnp.float32))[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", x_t.astype(jnp.float32), bf
    )
    y = jnp.einsum("bhpn,bhn->bhp", h_new, cf)
    return y.astype(x_t.dtype), h_new
