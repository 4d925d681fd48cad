"""Operations and bytes the benchmark's kernels and model steps require.

Counted from shapes and live lengths, never from what a program
happened to execute: padding of the page grid, inactive decode lanes
and recomputed positions are not required work. ``s`` is the dict of a
family's ``shapes``; its ``windows`` give each layer's attention window
(``None`` for full attention), and a key behind a layer's window is not
required work in that layer. The kernel counts are for one layer with
the window it is given; ``over_layers`` sums them over the model.
Activations, keys and values are bfloat16 (2 bytes).
"""

from __future__ import annotations

from collections import Counter

BF16 = 2


def over_layers(s: dict, per_layer) -> float:
    """``per_layer(window)`` summed over the model's layers, once for
    each distinct window, times the layers that have it."""
    return sum(n * per_layer(w) for w, n in Counter(s["windows"]).items())


def attended(kv_len: int, window) -> int:
    """Keys a query at depth ``kv_len`` attends (itself included)."""
    return kv_len if window is None else min(kv_len, window)


def band_keys(q0: int, n: int, window) -> int:
    """Keys attended by the rows at positions ``q0 .. q0 + n - 1``, each
    the positions up to its own that lie inside ``window``:
    ``sum(min(p + 1, window))``."""
    below = n if window is None else max(0, min(window - q0, n))
    # the first ``below`` rows attend q0 + 1 ...; the rest a full window
    return below * q0 + below * (below + 1) // 2 + (n - below) * (window or 0)


def head_flops(s: dict) -> int:
    return 2 * s["V"] * s["d"]


def attention_flops(s: dict, kv_len: int) -> int:
    """Scores and weighted values of one query over ``kv_len`` keys, in
    one layer."""
    return 4 * s["H"] * s["D"] * kv_len


def decode_token_flops(s: dict, kv_len: int) -> int:
    """Model operations of one decoded token at depth ``kv_len`` (itself
    included), all layers, each over the keys inside its window, and the
    head."""
    return (s["mm_flops_per_token"] + head_flops(s)
            + over_layers(s, lambda w: attention_flops(s, attended(kv_len,
                                                                   w))))


def prefill_flops(s: dict, q0: int, n: int) -> int:
    """Model operations of ``n`` prompt tokens at positions ``q0 ..
    q0 + n - 1`` (each attends the positions up to its own inside each
    layer's window), all layers, and the head once for the position whose
    logits give the first token."""
    return (n * s["mm_flops_per_token"] + head_flops(s)
            + over_layers(s, lambda w: 4 * s["H"] * s["D"]
                          * band_keys(q0, n, w)))


def paged_decode_call(s: dict, kv_lens, window=None) -> tuple:
    """(flops, bytes) of one batched paged-decode call in one layer for
    the live sequences at depths ``kv_lens`` (the new token's included):
    read every key and value inside the window once, the queries once,
    and write the outputs once."""
    H, Hk, D = s["H"], s["Hk"], s["D"]
    n_keys = sum(attended(kv, window) for kv in kv_lens)
    flops = 4 * H * D * n_keys
    nbytes = BF16 * (2 * Hk * D * n_keys + 2 * H * D * len(kv_lens))
    return flops, nbytes


def chunked_prefill_call(s: dict, q0: int, c: int, window=None) -> tuple:
    """(flops, bytes) of one chunked-prefill call in one layer: ``c``
    query rows at positions ``q0 .. q0 + c - 1`` under causal masking
    inside ``window``, reading once the keys and values of the band its
    rows attend (``q0 + c`` of them without a window)."""
    H, Hk, D = s["H"], s["Hk"], s["D"]
    flops = 4 * H * D * band_keys(q0, c, window)
    band = q0 + c if window is None else min(q0 + c, c + window - 1)
    nbytes = BF16 * (2 * Hk * D * band + 2 * H * D * c)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time for the work: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
