"""The counting functions against hand counts at small shapes."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import counting as c  # noqa: E402
import harness  # noqa: E402
from families import dense  # noqa: E402


def tiny(L=2, window=None, layer_types=None):
    """Width 4, 2 query heads over 1 KV head of 2, MLP 8, vocab 10."""
    return dense.shapes(dict(
        num_hidden_layers=L, hidden_size=4, num_attention_heads=2,
        num_key_value_heads=1, intermediate_size=8, vocab_size=10,
        tie_word_embeddings=False, sliding_window=window,
        layer_types=layer_types))


S = tiny()


def test_model_flops_by_hand():
    # per layer: wq 4*2*2 + wk, wv 2*4*1*2 + wo 2*2*4 + gate, up, down
    # 3*4*8 = 144 multiply-adds, two operations each, two layers
    assert S["mm_flops_per_token"] == 576
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
    # behind a window of 4: 3 and 4 keys, and 4, 4, 4 keys of a band of 6
    assert c.paged_decode_call(S, [3, 5], 4) == (4 * 2 * 2 * 7,
                                                 2 * (2 * 2 * 7 + 2 * 4 * 2))
    assert c.chunked_prefill_call(S, 4, 3, 4) == (4 * 2 * 2 * 12,
                                                  2 * (2 * 2 * 6 + 2 * 4 * 3))


def visible(p, w):
    """The keys row ``p`` attends under a window ``w`` (Mistral's: key j
    when p - w < j <= p)."""
    return [j for j in range(p + 1) if w is None or p - j < w]


# full attention, a window of 4 in every layer, and both mixed
WINDOWED = {"full": tiny(), "window4": tiny(window=4),
            "mixed": tiny(L=3, window=4, layer_types=[
                "sliding_attention", "full_attention", "sliding_attention"])}


@pytest.mark.parametrize("shapes", WINDOWED)
@pytest.mark.parametrize("q0,n", [(0, 1), (0, 7), (5, 3), (1024, 256)])
def test_causal_key_counts_match_a_loop(q0, n, shapes):
    s = WINDOWED[shapes]
    hd = 4 * s["H"] * s["D"]
    rows = range(q0, q0 + n)
    per_layer = [sum(len(visible(p, w)) for p in rows) for w in s["windows"]]
    assert c.prefill_flops(s, q0, n) == (n * s["mm_flops_per_token"]
                                         + c.head_flops(s)
                                         + hd * sum(per_layer))
    for w, keys in zip(s["windows"], per_layer):
        band = {j for p in rows for j in visible(p, w)}
        assert c.band_keys(q0, n, w) == keys
        assert c.chunked_prefill_call(s, q0, n, w) == (
            hd * keys, 2 * (2 * s["Hk"] * s["D"] * len(band)
                            + 2 * s["H"] * s["D"] * n))
    # decoded tokens at the rows' depths (the row's position plus one)
    depths = [p + 1 for p in rows]
    for kv in depths:
        assert c.decode_token_flops(s, kv) == (
            s["mm_flops_per_token"] + c.head_flops(s)
            + hd * sum(len(visible(kv - 1, w)) for w in s["windows"]))
    for w in s["windows"]:
        keys = sum(len(visible(kv - 1, w)) for kv in depths)
        assert c.paged_decode_call(s, depths, w)[0] == hd * keys


CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def single_layer(s, q0=None, n=None, kv_lens=None):
    """One layer's counts as they were before windows were counted: every
    key up to each row's own, in every layer."""
    H, Hk, D = s["H"], s["Hk"], s["D"]
    if kv_lens is not None:
        n_keys = sum(kv_lens)
        return 4 * H * D * n_keys, 2 * (2 * Hk * D * n_keys
                                        + 2 * H * D * len(kv_lens))
    keys = n * q0 + n * (n + 1) // 2
    return 4 * H * D * keys, 2 * (2 * Hk * D * (q0 + n) + 2 * H * D * n)


@pytest.mark.parametrize("cell", CELLS)
def test_no_window_binds_in_todays_cells(cell):
    """The per-layer sums are depth times one layer's count, at every
    live length up to the cell's ``max_len``, and so are the roofline
    readings."""
    spec = harness.load_cell(ROOT, cell)
    s, max_len = spec.family.shapes(spec.conf), spec.mix["engine"]["max_len"]
    L = s["L"]
    assert all(w is None or w >= max_len for w in s["windows"])
    mm, head = s["mm_flops_per_token"], c.head_flops(s)
    for kv in range(1, max_len + 1):
        assert c.decode_token_flops(s, kv) == (
            mm + head + L * single_layer(s, kv_lens=[kv])[0])
    lens = list(range(1, max_len + 1, 7))
    assert [c.over_layers(s, lambda w: c.paged_decode_call(s, lens, w)[i])
            for i in (0, 1)] == [L * x for x in single_layer(s, kv_lens=lens)]
    for q0 in range(0, max_len, 97):
        for n in (1, 16, 256, max_len - q0):
            n = min(n, max_len - q0)
            assert c.prefill_flops(s, q0, n) == (
                n * mm + head + L * single_layer(s, q0, n)[0])
            assert [c.over_layers(
                s, lambda w: c.chunked_prefill_call(s, q0, n, w)[i])
                for i in (0, 1)] == [L * x for x in single_layer(s, q0, n)]

    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    steps = [SimpleNamespace(decode=[max_len, 3, 100], prefill=[(0, 64, 40)]),
             SimpleNamespace(decode=[17], prefill=[(96, 160, 160)])]
    # each roofline reads its own kernel's time
    own = {("jit__decode_paged_fn", "batched_paged_decode_attention"),
           ("jit_fn", "chunked_prefill_attention")}
    run = SimpleNamespace(shapes=s, peaks=peaks, window_steps=steps,
                          device=SimpleNamespace(
                              kernel_s=lambda p, k: 1e-3 if (p, k) in own
                              else None))
    least = {
        "paged_decode_roofline": sum(c.least_seconds(*single_layer(
            s, kv_lens=st.decode), peaks) for st in steps),
        "chunked_prefill_roofline": sum(c.least_seconds(*single_layer(
            s, q0 + k - n, n), peaks) for st in steps
            for q0, k, n in st.prefill)}
    for metric, one_layer in least.items():
        assert harness.reader(ROOT, metric)(run) == pytest.approx(
            100.0 * L * one_layer / 1e-3, rel=1e-12)


def test_least_time_takes_the_bound_that_binds():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert c.least_seconds(288, 104, peaks) == pytest.approx(10.4)
    assert c.least_seconds(5000, 10, peaks) == pytest.approx(50.0)


def test_matmul_count_matches_xla_cost_analysis():
    # one layer's projections and MLP on one token, as XLA counts them
    import jax
    import jax.numpy as jnp
    s = tiny(L=1)
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
    assert cost["flops"] == pytest.approx(s["mm_flops_per_token"],
                                          rel=0.1)
