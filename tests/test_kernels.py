"""Pallas kernels vs pure-jnp oracles, executed with interpret=True on
CPU. Shape/dtype sweeps per kernel + chunked-form cross-validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    batched_paged_decode_attention,
    chunked_prefill_attention,
    flash_attention,
    paged_decode_attention,
    ssd_scan,
)
from repro.kernels import ref
from repro.kernels.paged_attention import (
    BLOCK_BYTES,
    block_schedule,
    pages_per_block,
    safe_page_index,
)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Lq,Lk,H,Hk,D", [
    (1, 128, 128, 4, 4, 64),       # MHA square
    (2, 128, 128, 4, 2, 32),       # GQA
    (1, 64, 256, 8, 1, 64),        # MQA, decode-style Lq < Lk
    (2, 200, 200, 3, 3, 48),       # ragged (padding path)
])
def test_flash_attention_causal(B, Lq, Lk, H, Hk, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Lq, H, D), dtype)
    k = _rand(ks[1], (B, Lk, Hk, D), dtype)
    v = _rand(ks[2], (B, Lk, Hk, D), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                          interpret=True)
    expect = ref.mha_naive(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, L, H, Hk, D = 1, 256, 4, 2, 32
    q = _rand(ks[0], (B, L, H, D), jnp.float32)
    k = _rand(ks[1], (B, L, Hk, D), jnp.float32)
    v = _rand(ks[2], (B, L, Hk, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_kv=64, interpret=True)
    expect = ref.mha_naive(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal_and_softcap():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, L, H, D = 1, 128, 2, 64
    q = _rand(ks[0], (B, L, H, D), jnp.float32)
    k = _rand(ks[1], (B, L, H, D), jnp.float32)
    v = _rand(ks[2], (B, L, H, D), jnp.float32)
    out = flash_attention(q, k, v, causal=False, logit_softcap=30.0,
                          block_q=64, block_kv=64, interpret=True)
    expect = ref.mha_naive(q, k, v, causal=False, logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_prefix_lm():
    """PaliGemma-style: prefix keys visible to all queries."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, L, H, D, P = 1, 128, 2, 32, 16
    q = _rand(ks[0], (B, L, H, D), jnp.float32)
    k = _rand(ks[1], (B, L, H, D), jnp.float32)
    v = _rand(ks[2], (B, L, H, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, prefix_len=P,
                          block_q=64, block_kv=64, interpret=True)
    expect = ref.mha_naive(q, k, v, causal=True, prefix_len=P)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_chunked_reference_matches_naive():
    """The jnp chunked form (what non-TPU backends lower) == naive."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, L, H, Hk, D = 2, 160, 4, 2, 32
    q = _rand(ks[0], (B, L, H, D), jnp.float32)
    k = _rand(ks[1], (B, L, Hk, D), jnp.float32)
    v = _rand(ks[2], (B, L, Hk, D), jnp.float32)
    for kw in (dict(causal=True), dict(causal=False),
               dict(causal=True, window=48),
               dict(causal=True, prefix_len=8)):
        got = ref.flash_attention_chunked(q, k, v, block_kv=64, **kw)
        expect = ref.mha_naive(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hk,D,page,pages_per_seq", [
    (2, 4, 2, 64, 16, 8),
    (3, 8, 1, 32, 32, 4),
    (1, 4, 4, 128, 16, 16),
])
def test_paged_decode_attention(B, H, Hk, D, page, pages_per_seq, dtype):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    n_pages = B * pages_per_seq + 3
    q = _rand(ks[0], (B, H, D), dtype)
    k_pages = _rand(ks[1], (n_pages, Hk, page, D), dtype)
    v_pages = _rand(ks[2], (n_pages, Hk, page, D), dtype)
    # each sequence gets a random non-overlapping page set
    perm = jax.random.permutation(ks[3], n_pages)[:B * pages_per_seq]
    page_table = perm.reshape(B, pages_per_seq).astype(jnp.int32)
    seq_lens = jnp.array(
        [1 + (7 * i) % (page * pages_per_seq) for i in range(B)], jnp.int32)
    out = paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens,
                                 interpret=True)
    expect = ref.paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                            seq_lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_paged_equals_contiguous():
    """Paged pool gather == contiguous-cache decode attention."""
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 4)
    B, H, Hk, D, page, pps = 2, 4, 2, 32, 16, 4
    S = page * pps
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k_cache = _rand(ks[1], (B, S, Hk, D), jnp.float32)
    v_cache = _rand(ks[2], (B, S, Hk, D), jnp.float32)
    lens = jnp.array([37, 61], jnp.int32)
    # lay the contiguous cache into head-major pages
    k_pages = k_cache.reshape(B * pps, page, Hk, D).transpose(0, 2, 1, 3)
    v_pages = v_cache.reshape(B * pps, page, Hk, D).transpose(0, 2, 1, 3)
    page_table = jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
    got = ref.paged_decode_attention_ref(q, k_pages, v_pages, page_table, lens)
    expect = ref.decode_attention_ref(q, k_cache, v_cache, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# batched paged decode (whole decode set, fused new-token K/V)
# ---------------------------------------------------------------------------

def _page_scene(key, B, Hk, D, page, pps, dtype, extra=3):
    """Random head-major pool + non-overlapping per-sequence page
    tables."""
    ks = jax.random.split(key, 3)
    n_pages = B * pps + extra
    k_pages = _rand(ks[0], (n_pages, Hk, page, D), dtype)
    v_pages = _rand(ks[1], (n_pages, Hk, page, D), dtype)
    perm = jax.random.permutation(ks[2], n_pages)[:B * pps]
    page_table = perm.reshape(B, pps).astype(jnp.int32)
    return k_pages, v_pages, page_table


# (H, Hk, D, page, pages_per_seq, seq_lens): "mixed" is a small pool;
# the others put lengths at the kernel's block edges (0, 1, one block of
# ``ppb * page`` tokens, one block plus one, a full table), at smollm-135m's
# heads and at h2o-danube-1.8b's (whose float32 pages make the block bytes
# bind); 18 pages per sequence leave a ragged last block in each
_BLOCK_EDGES = "block-edges"
_BATCHED_SCENES = {
    "mixed": (4, 2, 32, 8, 6, [5, 17, 29]),
    "edges": (4, 2, 32, 8, 6, _BLOCK_EDGES),
    "smollm-heads": (9, 3, 64, 16, 18, _BLOCK_EDGES),
    "danube-heads": (32, 8, 80, 16, 18, _BLOCK_EDGES),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("max_pages", [None, "trim"])
@pytest.mark.parametrize("scene", list(_BATCHED_SCENES))
def test_batched_paged_decode_vs_per_sequence(scene, dtype, max_pages):
    """The batched kernel == scatter-then-per-sequence decode calls."""
    key = jax.random.PRNGKey(10)
    ks = jax.random.split(key, 4)
    H, Hk, D, page, pps, lens = _BATCHED_SCENES[scene]
    if lens == _BLOCK_EDGES:
        block = page * pages_per_block(
            page, Hk * page * D * jnp.dtype(dtype).itemsize, pps)
        assert pps * page > block + 1 and pps % (block // page)  # ragged
        lens = [0, 1, block, block + 1, pps * page - 1]
    B = len(lens)
    k_pages, v_pages, page_table = _page_scene(ks[0], B, Hk, D, page, pps,
                                               dtype)
    q = _rand(ks[1], (B, H, D), dtype)
    k_new = _rand(ks[2], (B, Hk, D), dtype)
    v_new = _rand(ks[3], (B, Hk, D), dtype)
    seq_lens = jnp.array(lens, jnp.int32)
    mp = None if max_pages is None else max(1, -(-max(lens) // page))
    got = batched_paged_decode_attention(
        q, k_pages, v_pages, page_table, seq_lens, k_new, v_new,
        max_pages=mp, interpret=True)
    # per-sequence baseline: scatter the new token, then one legacy
    # kernel call per sequence over seq_lens + 1 tokens
    phys = page_table[jnp.arange(B), seq_lens // page]
    k_sc = k_pages.at[phys, :, seq_lens % page].set(k_new)
    v_sc = v_pages.at[phys, :, seq_lens % page].set(v_new)
    for b in range(B):
        single = paged_decode_attention(
            q[b:b + 1], k_sc, v_sc, page_table[b:b + 1],
            seq_lens[b:b + 1] + 1, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got[b], np.float32), np.asarray(single[0], np.float32),
            **_tol(dtype), err_msg=f"seq {b}")
    expect = ref.batched_paged_decode_attention_ref(
        q, k_pages, v_pages, page_table, seq_lens, k_new, v_new)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_batched_paged_decode_softcap():
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 4)
    B, H, Hk, D, page, pps = 2, 8, 2, 16, 8, 4
    k_pages, v_pages, page_table = _page_scene(ks[0], B, Hk, D, page, pps,
                                               jnp.float32)
    q = _rand(ks[1], (B, H, D), jnp.float32)
    k_new = _rand(ks[2], (B, Hk, D), jnp.float32)
    v_new = _rand(ks[3], (B, Hk, D), jnp.float32)
    seq_lens = jnp.array([0, 23], jnp.int32)   # incl. empty pool (first token)
    got = batched_paged_decode_attention(
        q, k_pages, v_pages, page_table, seq_lens, k_new, v_new,
        logit_softcap=30.0, interpret=True)
    expect = ref.batched_paged_decode_attention_ref(
        q, k_pages, v_pages, page_table, seq_lens, k_new, v_new,
        logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("page,page_bytes,n_page_steps,ppb", [
    (16, 3 * 16 * 64 * 2, 64, 16),        # smollm-135m's pool: the keys bind
    (16, 8 * 16 * 80 * 2, 144, 16),       # h2o-danube-1.8b's
    (16, 8 * 16 * 256 * 2, 64, 8),        # 64 KiB pages: the bytes bind
    (8, 2 * 8 * 32 * 4, 6, 4),            # the page grid binds
    (16, 3 * 16 * 64 * 2, 1, 1),
    (16, BLOCK_BYTES + 1, 64, 1),         # one page over the budget
])
def test_pages_per_block(page, page_bytes, n_page_steps, ppb):
    assert pages_per_block(page, page_bytes, n_page_steps) == ppb


def test_block_schedule_steps_live_blocks_and_holds_dead_slots():
    """One step per block holding keys (one for an empty sequence); a slot
    past the live length repeats the sequence's last live page (an empty
    sequence's first entry, its scratch page), so no step reads a table
    entry past the live length."""
    page, ppb = 8, 4
    live = [[3, 9, 1, 4, 7], [5], [20, 21, 22, 23, 24, 25, 26, 27, 28]]
    garbage = 777_777
    page_table = jnp.array([row + [garbage] * (12 - len(row))
                            for row in live], jnp.int32)
    seq_lens = jnp.array([5 * page - 3, 0, 9 * page], jnp.int32)
    n_steps, step_seq, step_block, step_pages = block_schedule(
        page_table, seq_lens, page_size=page, n_page_steps=12, ppb=ppb)
    assert int(n_steps) == 2 + 1 + 3
    assert step_seq.shape == (3 * 3,)                  # B * ceil(12 / ppb)
    assert list(np.asarray(step_seq[:6])) == [0, 0, 1, 2, 2, 2]
    assert list(np.asarray(step_block[:6])) == [0, 1, 0, 0, 1, 2]
    slots = np.asarray(step_pages).reshape(-1, ppb)[:6].tolist()
    assert slots == [
        [3, 9, 1, 4],
        [7, 7, 7, 7],           # slots 1-3 past the end: the last live page
        [5, 5, 5, 5],           # the empty sequence: its scratch page
        [20, 21, 22, 23],
        [24, 25, 26, 27],
        [28, 28, 28, 28],
    ]
    # unused steps past n_steps too
    assert garbage not in np.asarray(step_pages)


# ---------------------------------------------------------------------------
# fused chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk,cached,group", [
    (16, 0, 1),     # first chunk, MHA
    (16, 16, 2),    # resume from one cached page row, GQA
    (8, 24, 2),     # small slab deep in the sequence
    (32, 32, 4),    # wide slab, wide GQA group
    (12, 20, 1),    # non-page-aligned slab boundary
])
def test_chunked_prefill_vs_oracle(chunk, cached, group, dtype):
    key = jax.random.PRNGKey(12)
    ks = jax.random.split(key, 2)
    B, Hk, D, page, pps = 2, 2, 32, 8, 12
    H = Hk * group
    k_pages, v_pages, page_table = _page_scene(ks[0], B, Hk, D, page, pps,
                                               dtype)
    q = _rand(ks[1], (B, chunk, H, D), dtype)
    # second sequence resumes from a non-page-aligned offset
    q_offsets = jnp.array([cached, max(0, cached - 3)], jnp.int32)
    kv_lens = q_offsets + chunk
    got = chunked_prefill_attention(
        q, k_pages, v_pages, page_table, q_offsets, kv_lens, interpret=True)
    expect = ref.chunked_prefill_attention_ref(
        q, k_pages, v_pages, page_table, q_offsets, kv_lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_chunked_prefill_softcap_vs_oracle():
    key = jax.random.PRNGKey(13)
    ks = jax.random.split(key, 2)
    B, chunk, Hk, group, D, page, pps = 1, 16, 2, 2, 16, 8, 8
    k_pages, v_pages, page_table = _page_scene(ks[0], B, Hk, D, page, pps,
                                               jnp.float32)
    q = _rand(ks[1], (B, chunk, Hk * group, D), jnp.float32)
    q_offsets = jnp.array([24], jnp.int32)
    kv_lens = q_offsets + chunk
    got = chunked_prefill_attention(
        q, k_pages, v_pages, page_table, q_offsets, kv_lens,
        logit_softcap=30.0, interpret=True)
    expect = ref.chunked_prefill_attention_ref(
        q, k_pages, v_pages, page_table, q_offsets, kv_lens,
        logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_prefill_resumption_matches_full_causal(chunk):
    """Prefilling T tokens slab by slab — each chunk attending the pages
    written by chunks 0..N-1 — concatenates to one full causal pass."""
    key = jax.random.PRNGKey(14)
    ks = jax.random.split(key, 4)
    T, H, Hk, D, page = 32, 4, 2, 16, 8
    pps = T // page
    n_pages = pps + 2
    q_full = _rand(ks[0], (1, T, H, D), jnp.float32)
    k_full = _rand(ks[1], (1, T, Hk, D), jnp.float32)
    v_full = _rand(ks[2], (1, T, Hk, D), jnp.float32)
    perm = jax.random.permutation(ks[3], n_pages)[:pps].astype(jnp.int32)
    page_table = perm[None, :]
    k_pages = jnp.zeros((n_pages, Hk, page, D), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    outs = []
    for s in range(0, T, chunk):
        # the caller's contract: scatter the slab's K/V first...
        for t in range(s, s + chunk):
            k_pages = k_pages.at[perm[t // page], :, t % page].set(k_full[0, t])
            v_pages = v_pages.at[perm[t // page], :, t % page].set(v_full[0, t])
        # ...then attend it against everything resident so far
        outs.append(chunked_prefill_attention(
            q_full[:, s:s + chunk], k_pages, v_pages, page_table,
            jnp.array([s], jnp.int32), jnp.array([s + chunk], jnp.int32),
            interpret=True))
    got = jnp.concatenate(outs, axis=1)
    expect = ref.mha_naive(q_full, k_full, v_full, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# page-table tail poisoning (index-map clamp)
# ---------------------------------------------------------------------------

def test_safe_page_index_never_reads_poisoned_tail():
    page = 8
    page_table = jnp.array([[3, 9, 1, 777_777, -5, 123_456]], jnp.int32)
    seq_lens = jnp.array([17], jnp.int32)        # 3 valid pages
    valid = {3, 9, 1}
    for p in range(page_table.shape[1]):
        got = int(safe_page_index(page_table, seq_lens, 0, p, page))
        assert got in valid, (p, got)
        assert got == (int(page_table[0, p]) if p < 3 else 1)
    # empty sequence: clamp to the first table entry, never past it
    empty = jnp.array([0], jnp.int32)
    for p in range(page_table.shape[1]):
        assert int(safe_page_index(page_table, empty, 0, p, page)) == 3


def test_paged_kernels_ignore_poisoned_tail_entries():
    """Table slots past ceil(seq_len / page) are allocator garbage; all
    three paged kernels must produce clean-table results anyway."""
    key = jax.random.PRNGKey(15)
    ks = jax.random.split(key, 4)
    B, H, Hk, D, page, pps = 2, 4, 2, 32, 8, 6
    k_pages, v_pages, clean = _page_scene(ks[0], B, Hk, D, page, pps,
                                          jnp.float32)
    n_pages = k_pages.shape[0]
    seq_lens = jnp.array([11, 37], jnp.int32)
    poisoned = np.asarray(clean).copy()
    for b, n in enumerate([11, 37]):
        poisoned[b, -(-n // page):] = n_pages * 13 + b   # far out of range
    poisoned = jnp.asarray(poisoned)

    q = _rand(ks[1], (B, H, D), jnp.float32)
    got = paged_decode_attention(q, k_pages, v_pages, poisoned, seq_lens,
                                 interpret=True)
    expect = ref.paged_decode_attention_ref(q, k_pages, v_pages, clean,
                                            seq_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)

    k_new = _rand(ks[2], (B, Hk, D), jnp.float32)
    v_new = _rand(ks[3], (B, Hk, D), jnp.float32)
    got = batched_paged_decode_attention(
        q, k_pages, v_pages, poisoned, seq_lens, k_new, v_new,
        interpret=True)
    expect = ref.batched_paged_decode_attention_ref(
        q, k_pages, v_pages, clean, seq_lens, k_new, v_new)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)

    chunk = 8
    qc = _rand(ks[1], (B, chunk, H, D), jnp.float32)
    q_offsets = seq_lens - chunk
    got = chunked_prefill_attention(
        qc, k_pages, v_pages, poisoned, q_offsets, seq_lens, interpret=True)
    expect = ref.chunked_prefill_attention_ref(
        qc, k_pages, v_pages, clean, q_offsets, seq_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan (Mamba-2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,H,G,P,N,chunk", [
    (1, 128, 4, 1, 16, 16, 32),
    (2, 256, 8, 2, 32, 64, 64),
    (1, 64, 2, 1, 64, 128, 64),
])
def test_ssd_scan_vs_naive(B, L, H, G, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = _rand(ks[0], (B, L, H, P), dtype) * 0.5
    a = -jnp.abs(_rand(ks[1], (B, L, H), jnp.float32)) * 0.1
    b = _rand(ks[2], (B, L, G, N), dtype) * 0.5
    c = _rand(ks[3], (B, L, G, N), dtype) * 0.5
    out = ssd_scan(x, a.astype(dtype), b, c, chunk=chunk, interpret=True)
    expect = ref.ssd_naive(x, a.astype(dtype), b, c)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_chunked_matches_naive_and_carries_state():
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    B, L, H, G, P, N, Q = 2, 192, 4, 2, 16, 32, 64
    x = _rand(ks[0], (B, L, H, P), jnp.float32) * 0.5
    a = -jnp.abs(_rand(ks[1], (B, L, H), jnp.float32)) * 0.1
    b = _rand(ks[2], (B, L, G, N), jnp.float32) * 0.5
    c = _rand(ks[3], (B, L, G, N), jnp.float32) * 0.5
    y, state = ref.ssd_chunked(x, a, b, c, chunk=Q, return_final_state=True)
    expect = ref.ssd_naive(x, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)
    # final state equals stepping the recurrence token by token
    h = jnp.zeros((B, H, P, N), jnp.float32)
    for t in range(L):
        _, h = ref.ssm_decode_step_ref(h, x[:, t], a[:, t], b[:, t], c[:, t])
    np.testing.assert_allclose(np.asarray(state), np.asarray(h),
                               atol=1e-3, rtol=1e-3)


def test_ssd_decode_step_matches_prefill_continuation():
    """prefill L tokens then decode 1 == full scan over L+1 tokens."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    B, L, H, G, P, N, Q = 1, 64, 2, 1, 16, 16, 32
    x = _rand(ks[0], (B, L + 1, H, P), jnp.float32) * 0.5
    a = -jnp.abs(_rand(ks[1], (B, L + 1, H), jnp.float32)) * 0.1
    b = _rand(ks[2], (B, L + 1, G, N), jnp.float32) * 0.5
    c = _rand(ks[3], (B, L + 1, G, N), jnp.float32) * 0.5
    y_full = ref.ssd_naive(x, a, b, c)
    _, state = ref.ssd_chunked(x[:, :L], a[:, :L], b[:, :L], c[:, :L],
                               chunk=Q, return_final_state=True)
    y_tok, _ = ref.ssm_decode_step_ref(state, x[:, L], a[:, L], b[:, L],
                                       c[:, L])
    np.testing.assert_allclose(np.asarray(y_tok), np.asarray(y_full[:, L]),
                               atol=1e-4, rtol=1e-4)
