"""Paper Fig 9 + Sec IV-I: GPU execution latency across schedulers
(policy-~invariant) and GPU utilization / memory plateau.

Also reports wall-clock micro-latency for the two fused engine
kernels (chunked-prefill attention and batched paged-decode) against
their unfused dispatch patterns — the per-iteration launch savings
that the engine's per-chunk prefill and single-call decode step buy.
Off-TPU this times the XLA reference path, so treat the rows as a
dispatch-count trend, not device kernel time.
"""

from __future__ import annotations

import time

from .common import POLICIES, SEEDS, fmt_table, mean, run_experiment, \
    save_json


def _time_ms(fn, *args, reps: int = 5) -> float:
    """Steady-state latency of a jitted callable, min over reps."""
    import jax
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))        # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best


def _kernel_micro_latency() -> dict:
    """Fused vs unfused dispatch for the two engine kernels.

    * decode: one ``batched_paged_decode_attention`` call covering the
      whole decode set vs B single-sequence ``paged_decode_attention``
      dispatches (the pre-batching engine inner loop).
    * prefill: per-chunk ``chunked_prefill_attention`` slabs (the
      engine's interleavable unit) vs one whole-prompt flash call —
      the price of chunking, paid back in slot-level interleaving.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    B, H, Hk, D = 8, 8, 4, 64
    page, pps = 16, 8                        # 128-token pool rows
    L, chunk = 128, 32
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    n_pages = B * pps
    k_pages = jax.random.normal(ks[0], (n_pages, Hk, page, D))
    v_pages = jax.random.normal(ks[1], (n_pages, Hk, page, D))
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(B, pps)
    seq_lens = jnp.full((B,), 100, dtype=jnp.int32)
    qd = jax.random.normal(ks[2], (B, H, D))
    k_new = jax.random.normal(ks[3], (B, Hk, D))
    v_new = jax.random.normal(ks[4], (B, Hk, D))

    def decode_batched(q, kp, vp, tab, lens, kn, vn):
        return ops.batched_paged_decode_attention(
            q, kp, vp, tab, lens, kn, vn, impl="reference")

    def decode_per_seq(q, kp, vp, tab, lens):
        return jnp.concatenate([
            ops.paged_decode_attention(
                q[i:i + 1], kp, vp, tab[i:i + 1], lens[i:i + 1],
                impl="reference")
            for i in range(B)])

    qp = jax.random.normal(ks[5], (B, L, H, D))
    kf = jax.random.normal(ks[6], (B, L, Hk, D))
    vf = jax.random.normal(ks[7], (B, L, Hk, D))
    kv_lens = jnp.full((B,), L, dtype=jnp.int32)

    def prefill_single_shot(q, k, v):
        return ops.attention(q, k, v, causal=True, impl="reference")

    def prefill_chunked(q, kp, vp, tab):
        outs = []
        for off in range(0, L, chunk):
            lens = jnp.full((B,), off + chunk, dtype=jnp.int32)
            offs = jnp.full((B,), off, dtype=jnp.int32)
            outs.append(ops.chunked_prefill_attention(
                q[:, off:off + chunk], kp, vp, tab, offs, lens,
                impl="reference"))
        return jnp.concatenate(outs, axis=1)

    out = {
        "shapes": {"B": B, "H": H, "Hk": Hk, "D": D, "page_size": page,
                   "pages_per_seq": pps, "prompt_len": L, "chunk": chunk},
        "decode_batched_ms": _time_ms(
            decode_batched, qd, k_pages, v_pages, table, seq_lens,
            k_new, v_new),
        "decode_per_seq_loop_ms": _time_ms(
            decode_per_seq, qd, k_pages, v_pages, table, seq_lens),
        "prefill_single_shot_ms": _time_ms(
            prefill_single_shot, qp, kf, vf),
        "prefill_chunked_ms": _time_ms(
            prefill_chunked, qp, k_pages, v_pages, table),
    }
    out["decode_batched_speedup"] = (
        out["decode_per_seq_loop_ms"] / max(out["decode_batched_ms"], 1e-9))
    out["prefill_chunk_overhead_x"] = (
        out["prefill_chunked_ms"] / max(out["prefill_single_shot_ms"], 1e-9))
    return out


def run() -> dict:
    out = {}
    for policy in POLICIES:
        p50s, p95s, p99s, utils, mems = [], [], [], [], []
        for seed in SEEDS:
            sched, sim, m = run_experiment(policy, bias=True, seed=seed)
            p50s.append(m.gpu_exec.p50)
            p95s.append(m.gpu_exec.p95)
            p99s.append(m.gpu_exec.p99)
            utils.append(m.gpu_utilization)
            busy = [t.gpu_mem_gb for t in sim.telemetry if t.gpu_util > 0.5]
            mems.append(mean(busy))
        out[policy] = {"p50": mean(p50s), "p95": mean(p95s),
                       "p99": mean(p99s), "gpu_util": mean(utils),
                       "gpu_mem_gb": mean(mems)}
    p50s = [out[p]["p50"] for p in POLICIES if p != "sjf"]
    out["invariance"] = {
        "non_sjf_p50_spread_pct":
            100 * (max(p50s) - min(p50s)) / mean(p50s),
        "paper": "FIFO/Priority/Weighted/Aging ~10.5s P50, ~11.3s P99; "
                 "SJF slightly lower",
    }
    out["kernels"] = _kernel_micro_latency()
    save_json("gpu_exec_latency", out)
    return out


def report(out: dict) -> str:
    rows = []
    for p in POLICIES:
        r = out[p]
        rows.append([p, f"{r['p50']:.2f}", f"{r['p95']:.2f}",
                     f"{r['p99']:.2f}", f"{100*r['gpu_util']:.0f}%",
                     f"{r['gpu_mem_gb']:.1f}"])
    tbl = fmt_table(
        ["scheduler", "P50(s)", "P95(s)", "P99(s)", "util", "mem(GB)"],
        rows, "Fig 9 / Sec IV-I: GPU execution latency + utilization")
    tbl += (f"\nnon-SJF P50 spread: "
            f"{out['invariance']['non_sjf_p50_spread_pct']:.1f}% "
            "(paper: execution cost ~policy-invariant; queue effects "
            "dominate e2e)")
    k = out["kernels"]
    krows = [
        ["paged decode (B=8)", f"{k['decode_per_seq_loop_ms']:.2f}",
         f"{k['decode_batched_ms']:.2f}",
         f"loop/batched {k['decode_batched_speedup']:.2f}x "
         "(1 dispatch vs B on device)"],
        ["prefill (128 tok)", f"{k['prefill_single_shot_ms']:.2f}",
         f"{k['prefill_chunked_ms']:.2f}",
         f"chunked/single {k['prefill_chunk_overhead_x']:.2f}x "
         "(chunk unit buys interleaving)"],
    ]
    tbl += "\n" + fmt_table(
        ["kernel", "unfused(ms)", "fused/chunked(ms)", "ratio"],
        krows, "Engine kernel micro-latency (per-iteration dispatch; "
               "reference path off-TPU — a dispatch-count trend, not "
               "device kernel time)")
    return tbl
