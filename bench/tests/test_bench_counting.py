"""The counting functions against hand counts at small shapes."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counting as c  # noqa: E402

# 2 layers, width 4, 2 query heads over 1 KV head of 2, MLP 8, vocab 10
S = dict(L=2, d=4, H=2, Hk=1, D=2, ff=8, V=10)


def test_model_flops_by_hand():
    # per layer: wq 4*2*2 + wk, wv 2*4*1*2 + wo 2*2*4 + gate, up, down
    # 3*4*8 = 144 multiply-adds, two operations each, two layers
    assert c.matmul_flops_per_token(S) == 576
    assert c.head_flops(S) == 2 * 10 * 4
    # 5 keys: scores and weighted values, 2 * 2 operations per head-dim
    assert c.attention_flops(S, 5) == 4 * 2 * 2 * 5
    assert c.decode_token_flops(S, 5) == 576 + 80 + 2 * 80
    # 2 tokens at positions 3 and 4 attend 4 and 5 keys
    assert c.prefill_flops(S, 3, 2) == 2 * 576 + 80 + 2 * 4 * 2 * 2 * (4 + 5)


def test_kernel_counts_by_hand():
    # decode of two live sequences attending 3 and 5 keys: 8 keys read as
    # K and V (1 head of 2, 2 bytes), 2 queries read and 2 outputs
    # written (2 heads of 2, 2 bytes)
    assert c.paged_decode_call(S, [3, 5]) == (4 * 2 * 2 * 8,
                                              2 * (2 * 2 * 8 + 2 * 4 * 2))
    # 3 rows at positions 4..6 attend 5, 6 and 7 keys; 7 keys resident
    assert c.chunked_prefill_call(S, 4, 3) == (4 * 2 * 2 * 18,
                                               2 * (2 * 2 * 7 + 2 * 4 * 3))


@pytest.mark.parametrize("q0,n", [(0, 1), (0, 7), (5, 3), (1024, 256)])
def test_causal_key_counts_match_a_loop(q0, n):
    keys = sum(q0 + i + 1 for i in range(n))
    flops, _ = c.chunked_prefill_call(S, q0, n)
    assert flops == 4 * S["H"] * S["D"] * keys
    assert c.prefill_flops(S, q0, n) == (n * c.matmul_flops_per_token(S)
                                         + c.head_flops(S)
                                         + S["L"] * 4 * 2 * 2 * keys)


def test_least_time_takes_the_bound_that_binds():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert c.least_seconds(288, 104, peaks) == pytest.approx(10.4)
    assert c.least_seconds(5000, 10, peaks) == pytest.approx(50.0)


def test_matmul_count_matches_xla_cost_analysis():
    # one layer's projections and MLP on one token, as XLA counts them
    import jax
    import jax.numpy as jnp
    s = dict(S, L=1)
    d, H, Hk, D, ff = 4, 2, 1, 2, 8

    def layer(x, wq, wk, wv, wo, w1, w3, w2):
        q = jnp.einsum("d,dhk->hk", x, wq)
        k = jnp.einsum("d,dhk->hk", x, wk)
        v = jnp.einsum("d,dhk->hk", x, wv)
        o = jnp.einsum("hk,hkd->d", q + 0 * k.sum() + 0 * v.sum(), wo)
        return (o @ w1) * (o @ w3) @ w2

    shapes = [(d,), (d, H, D), (d, Hk, D), (d, Hk, D), (H, D, d), (d, ff),
              (d, ff), (ff, d)]
    args = [jax.ShapeDtypeStruct(x, jnp.float32) for x in shapes]
    cost = jax.jit(layer).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # XLA adds a handful of elementwise operations to the dots
    assert cost["flops"] == pytest.approx(c.matmul_flops_per_token(s),
                                          rel=0.1)
