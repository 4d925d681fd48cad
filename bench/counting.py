"""Operations and bytes the benchmark's kernels and model steps require.

Counted from shapes and live lengths, never from what a program
happened to execute: padding of the page grid, inactive decode lanes
and recomputed positions are not required work. ``s`` is the dict of
``model.shapes``; every count is for one layer unless it says
otherwise. Activations, keys and values are bfloat16 (2 bytes).
"""

from __future__ import annotations

BF16 = 2


def matmul_flops_per_token(s: dict) -> int:
    """Projections and MLP of every layer for one token (multiply and
    add count as two operations); the head is counted apart."""
    d, H, Hk, D, ff = s["d"], s["H"], s["Hk"], s["D"], s["ff"]
    per_layer = d * H * D + 2 * d * Hk * D + H * D * d + 3 * d * ff
    return 2 * s["L"] * per_layer


def head_flops(s: dict) -> int:
    return 2 * s["V"] * s["d"]


def attention_flops(s: dict, kv_len: int) -> int:
    """Scores and weighted values of one query over ``kv_len`` keys, in
    one layer."""
    return 4 * s["H"] * s["D"] * kv_len


def decode_token_flops(s: dict, kv_len: int) -> int:
    """Model operations of one decoded token that attends ``kv_len``
    keys (itself included), all layers and the head."""
    return (matmul_flops_per_token(s) + head_flops(s)
            + s["L"] * attention_flops(s, kv_len))


def prefill_flops(s: dict, q0: int, n: int) -> int:
    """Model operations of ``n`` prompt tokens at positions ``q0 ..
    q0 + n - 1`` (each attends every position up to its own), all layers,
    and the head once for the position whose logits give the first
    token."""
    keys = n * q0 + n * (n + 1) // 2
    return (n * matmul_flops_per_token(s) + head_flops(s)
            + s["L"] * 4 * s["H"] * s["D"] * keys)


def paged_decode_call(s: dict, kv_lens) -> tuple:
    """(flops, bytes) of one batched paged-decode call in one layer for
    the live sequences, each attending ``kv_len`` keys (the new token's
    included): read every live key and value once, the queries once,
    and write the outputs once."""
    H, Hk, D = s["H"], s["Hk"], s["D"]
    n_keys = sum(kv_lens)
    flops = 4 * H * D * n_keys
    nbytes = BF16 * (2 * Hk * D * n_keys + 2 * H * D * len(kv_lens))
    return flops, nbytes


def chunked_prefill_call(s: dict, q0: int, c: int) -> tuple:
    """(flops, bytes) of one chunked-prefill call in one layer: ``c``
    query rows at positions ``q0 .. q0 + c - 1`` under causal masking,
    reading the ``q0 + c`` resident keys and values once."""
    H, Hk, D = s["H"], s["Hk"], s["D"]
    keys = c * q0 + c * (c + 1) // 2
    flops = 4 * H * D * keys
    nbytes = BF16 * (2 * Hk * D * (q0 + c) + 2 * H * D * c)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time for the work: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
