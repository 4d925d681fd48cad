"""Bring-up check of the paged serving engine on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four replicas, one per chip

Run from the root of a checkout; the script puts ``src`` on the path
itself and needs no environment variables. It refuses to run anywhere
but a TPU: there is no CPU branch and no interpret mode.

One chip: serves a seeded plan of shared-prefix requests to completion
through ``ServingEngine`` at smollm-135m's published widths (random
weights), on the paged KV pool with chunked prefill and the prefix
cache, built and sized by the serving entry point
(``repro.launch.serve``), then serves it again from compiled programs.
It checks that every request completed, that every sampled token is in
the vocabulary, that the second pass sampled the same tokens as the
first, that the KV pool holds finite values, that the prefix cache hit,
and that the compiled decode and chunked-prefill programs call the
Pallas kernels (``tpu_custom_call``). It then runs both fused kernels
with ``impl="pallas"`` and ``impl="reference"`` at the model's head
shapes and holds their difference to a stated tolerance.

``--four-chips``: serves one seeded plan through four engine replicas
under ``pd_disaggregated`` routing, replica ``i`` (params and KV pool)
on chip ``i``, so prefilled KV moves across chips at handoff. The same
plan is then served with all four replicas on chip 0, and the two runs
must agree exactly on every request's sampled tokens, completions,
routing and handoff counts (the clock is simulated and decoding is
greedy), so KV that lands wrong on the receiving chip changes the
tokens that follow it.

Lines before the last report times, tokens, kernel differences and peak
device memory. The last line is one JSON object naming the device; a
failed check raises and the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster.driver import make_engine_cluster  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.scheduler import DriftScheduler  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models.registry import get_api  # noqa: E402
from repro.obs import TraceRecorder, set_recorder  # noqa: E402
from repro.obs import events as tr  # noqa: E402
from repro.serving.engine import EngineConfig  # noqa: E402

ARCH = "smollm-135m"
SEED = 0
# the one-chip engine is the entry point's paged engine
# (serve.PAGED_ENGINE); every prompt opens with one of 12 tenant system
# prompts (4 per tenant tier) of serve.PAGED_SHARED_PREFIX_TOKENS
N_REQUESTS = 48
# the four-replica comparison runs twice over the four replicas' engines,
# so it serves fewer, shorter requests through smaller engines
FOUR_CHIP_REQUESTS = 16
FOUR_CHIP_SHARED_PREFIX_TOKENS = serve.PAGED_SHARED_PREFIX_TOKENS // 2
FOUR_CHIP_ENGINE = EngineConfig(n_slots=4, max_len=512,
                                prompt_buckets=(256,), paged=True,
                                chunk_prefill_tokens=serve.PAGED_CHUNK_TOKENS)

# Pallas vs reference kernels on the same bf16 pool, in bf16 steps of
# the largest reference output (2^-8 of it, the spacing of bf16 near
# the top of its binade): both accumulate in f32 and round each output
# to bf16 once, but the Pallas kernel's online softmax rescales in a
# different order and XLA's f32 matmuls on a TPU take one bf16 pass at
# default precision, so single outputs may round one or two steps
# apart. The first chip runs measured one step (2^-9 for outputs just
# under 0.5) for both kernels.
KERNEL_TOL_STEPS = 4


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
                 f"({dev.device_kind}); nothing was run")
    return dev


class CompileClock:
    """Seconds spent getting executables from XLA, how many programs
    that was, and how many of them the persistent compilation cache
    served, from JAX's own monitoring events (tracing and lowering
    excluded: their events nest)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += secs
            self.programs += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1


class TokenLog:
    """Every token the engines sample, per request id, in order: an
    observer of the trace's ``first_token`` and ``decode_step`` events
    (a P/D request's first token comes from its prefill replica, the
    rest from its decode replica)."""

    def __init__(self) -> None:
        self.tokens = defaultdict(list)

    def on_event(self, ev) -> None:
        if ev.kind in (tr.FIRST_TOKEN, tr.DECODE_STEP):
            self.tokens[ev.req_id].append(ev.data["token"])

    def take(self, plan, vocab: int) -> list:
        """The tokens of ``plan``'s requests by plan position (request
        ids differ between two generated plans), checked against the
        vocabulary and each request's output length; then forget
        them."""
        out = [self.tokens.pop(r.req_id, []) for _, r in plan]
        for (_, r), toks in zip(plan, out):
            check(len(toks) == r.observed_output_tokens,
                  f"request {r.req_id}: {len(toks)} sampled tokens, "
                  f"{r.observed_output_tokens} counted")
            check(all(0 <= t < vocab for t in toks),
                  f"request {r.req_id}: sampled token outside "
                  f"[0, {vocab}): {toks}")
        return out


def tracing_tokens() -> tuple:
    """A recorder that keeps (almost) no events itself and feeds a
    ``TokenLog``."""
    log = TokenLog()
    return TraceRecorder(capacity=1, observers=[log]), log


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def serve_pass(eng, log: TokenLog, seed: int) -> dict:
    """Submit the seeded plan to ``eng``'s scheduler and serve it to
    completion through the engine's own loop."""
    plan = serve.request_plan(
        N_REQUESTS, seed,
        shared_prefix_tokens=serve.PAGED_SHARED_PREFIX_TOKENS)
    for t, r in plan:
        eng.sched.submit(r, t)
    n_done_before, steps_before = len(eng.sched.completed), eng.step_count
    t0 = time.perf_counter()
    eng.run_until_drained()
    jax.block_until_ready(eng.pool.k)
    wall = time.perf_counter() - t0
    done = eng.sched.completed[n_done_before:]
    check(len(done) == len(plan),
          f"{len(done)} of {len(plan)} requests completed")
    tokens = log.take(plan, eng.cfg.vocab)
    return {"wall_s": wall, "steps": eng.step_count - steps_before,
            "tokens": tokens, "n_tokens": sum(map(len, tokens))}


def compiled_text(jitted, *args, **kwargs) -> str:
    return jitted.lower(*args, **kwargs).compile().as_text()


def serve_phase() -> None:
    clock = CompileClock()
    rec, log = tracing_tokens()
    set_recorder(rec)
    t0 = time.perf_counter()
    sched = DriftScheduler(policy="fifo")
    eng = serve.jax_engine(ARCH, sched, published=True, paged=True)
    jax.block_until_ready((eng.params, eng.pool.k, eng.pool.v))
    build_s = time.perf_counter() - t0
    cfg = eng.cfg
    print(f"model {cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads of "
          f"{cfg.d_head}, vocab {cfg.vocab}; pool "
          f"{tuple(eng.pool.k.shape)} {eng.pool.k.dtype}")

    # pass 1 compiles every program the plan needs; pass 2 serves the
    # same plan again, with the prefix cache emptied so that it makes
    # the same calls, from compiled programs
    cold = serve_pass(eng, log, SEED)
    cold_compile_s, cold_programs = clock.seconds, clock.programs
    cold_hits = clock.cache_hits
    hits, misses = eng.n_prefix_hits, eng.n_prefix_misses
    check(eng.n_prefill_launches > 0, "no chunked-prefill launch")
    check(hits > 0, "the prefix cache never hit")
    eng.prefix_tree.clear()
    warm = serve_pass(eng, log, SEED)
    warm_programs = clock.programs - cold_programs
    check(warm["tokens"] == cold["tokens"],
          "the steady pass sampled other tokens than the cold pass")
    check(bool(jnp.isfinite(eng.pool.k).all() & jnp.isfinite(eng.pool.v).all()),
          "non-finite values in the KV pool")
    print(f"set-up: engine built in {build_s:.3f} s wall, cold pass "
          f"{cold['wall_s']:.3f} s wall ({cold['steps']} steps); XLA "
          f"gave {cold_programs} programs in {cold_compile_s:.3f} s over "
          f"both, {cold_hits} of them from the persistent compile cache")
    print(f"steady pass: {warm['wall_s']:.3f} s wall, {warm['steps']} "
          f"steps, {warm_programs} programs compiled")
    print(f"tokens generated: {cold['n_tokens']} per pass "
          f"({warm['n_tokens']} in the steady pass, the same tokens); "
          f"{warm['n_tokens'] / warm['wall_s']:.1f} tokens/s steady")
    print(f"prefill launches {eng.n_prefill_launches}, prefix hits "
          f"{hits} of {hits + misses} lookups in the cold "
          f"pass, {eng.prefix_tokens_saved} prompt tokens saved over both")

    # the engine's programs call the Pallas kernels, not the reference
    n, pps = eng.ecfg.n_slots, eng.alloc.pages_per_seq
    pool = {"k": eng.pool.k, "v": eng.pool.v}
    decode = compiled_text(
        eng._decode_paged, eng.params, pool, jnp.zeros((n,), jnp.int32),
        jnp.zeros((n, pps), jnp.int32), jnp.zeros((n,), jnp.int32),
        jax.random.PRNGKey(0), max_pages=pps)
    chunk = eng.ecfg.chunk_prefill_tokens
    prefill = compiled_text(
        eng._chunk_fn_for(chunk), eng.params, pool,
        jnp.zeros((1, chunk), jnp.int32), jnp.zeros((1, pps), jnp.int32),
        jnp.zeros((1,), jnp.int32))
    check("tpu_custom_call" in decode, "decode program has no Pallas kernel")
    check("tpu_custom_call" in prefill,
          "chunked-prefill program has no Pallas kernel")
    print("compiled decode and chunked-prefill programs call "
          "tpu_custom_call")
    kernel_parity(cfg, eng.alloc.n_pages + 1, eng.ecfg.page_size, pps, n)


def kernel_parity(cfg, n_pages: int, page: int, pps: int, B: int) -> None:
    """Both fused kernels, Pallas vs reference, on a random bf16 pool
    of the engine's geometry (``B`` slots) at the model's head
    shapes."""
    H, Hk, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    chunk = serve.PAGED_CHUNK_TOKENS
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    bf = jnp.bfloat16
    kp = jax.random.normal(ks[0], (n_pages, Hk, page, D), bf)
    vp = jax.random.normal(ks[1], (n_pages, Hk, page, D), bf)
    table = jax.random.permutation(ks[2], n_pages)[:B * pps]
    table = table.reshape(B, pps).astype(jnp.int32)
    lens = jax.random.randint(ks[3], (B,), 1, pps * page - 1, jnp.int32)
    q = jax.random.normal(ks[4], (B, H, D), bf)
    kn = jax.random.normal(ks[5], (B, Hk, D), bf)
    vn = jax.random.normal(ks[6], (B, Hk, D), bf)
    qc = jax.random.normal(ks[7], (1, chunk, H, D), bf)
    q_off = jnp.array([serve.PAGED_SHARED_PREFIX_TOKENS],
                      jnp.int32)                           # resumes a prefix

    def run(impl):
        dec = jax.jit(lambda *a: ops.batched_paged_decode_attention(
            *a, impl=impl))(q, kp, vp, table, lens, kn, vn)
        pre = jax.jit(lambda *a: ops.chunked_prefill_attention(
            *a, impl=impl))(qc, kp, vp, table[:1], q_off, q_off + chunk)
        return dec, pre

    for name, got, want in zip(("batched paged decode", "chunked prefill"),
                               run("pallas"), run("reference")):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"{name}: non-finite Pallas output")
        diff = float(np.abs(got - want).max())
        step = 2.0 ** -8 * float(np.abs(want).max())
        tol = KERNEL_TOL_STEPS * step
        print(f"{name} kernel {got.shape}: max |pallas - reference| = "
              f"{diff:.6g}, {diff / step:.3g} bf16 steps of the largest "
              f"output {step * 256:.6g} (tolerance {tol:.6g})")
        check(diff <= tol, f"{name}: Pallas differs from the reference "
                           f"by {diff} > {tol}")


def four_chip_run(cfg, params, devices) -> dict:
    """Serve the seeded plan through four P/D replicas placed on
    ``devices``; return what the comparison reads, keyed by plan
    position (request ids differ between two generated plans)."""
    rec, log = tracing_tokens()
    drv = make_engine_cluster(
        cfg, params, 4, policy="fifo", routing="pd_disaggregated",
        engine_config=FOUR_CHIP_ENGINE, devices=devices, trace=rec)
    plan = serve.request_plan(
        FOUR_CHIP_REQUESTS, SEED,
        shared_prefix_tokens=FOUR_CHIP_SHARED_PREFIX_TOKENS)
    for t, r in plan:
        check(drv.submit(r, t), "request shed")
    t0 = time.perf_counter()
    m = drv.run_until_drained()
    wall = time.perf_counter() - t0
    check(m.n_completed == len(plan),
          f"{m.n_completed} of {len(plan)} requests completed")
    for rep, dev in zip(drv.replicas, devices):
        pool = rep.engine.pool
        check(pool.k.devices() == {dev} and pool.v.devices() == {dev},
              f"replica {rep.rid}'s pool is on {pool.k.devices()}, "
              f"not {dev}")
    pos = {r.req_id: i for i, (_, r) in enumerate(plan)}
    reqs = [r for _, r in plan]
    return {
        "wall_s": wall,
        "tokens": log.take(plan, cfg.vocab),
        "completions": sorted((r.completion_time, pos[r.req_id],
                               r.observed_output_tokens) for r in reqs),
        "routing": [(r.prefill_rid, r.decode_rid) for r in reqs],
        "handoffs": (drv.n_handoffs, drv.n_handoffs_lost),
        "per_replica": [(rep.n_routed, rep.n_handoffs_out,
                         rep.n_handoffs_in) for rep in drv.replicas],
    }


def four_chip_phase() -> None:
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = get_config(ARCH)
    params = get_api(cfg).init(cfg, jax.random.PRNGKey(0))
    spread = four_chip_run(cfg, params, devs[:4])
    print(f"replicas on {[d.id for d in devs[:4]]}: "
          f"{spread['wall_s']:.3f} s wall (compiles included), handoffs "
          f"{spread['handoffs'][0]}, lost {spread['handoffs'][1]}, "
          f"per replica (routed, out, in) {spread['per_replica']}")
    same = four_chip_run(cfg, params, [devs[0]] * 4)
    print(f"replicas all on {devs[0].id}: {same['wall_s']:.3f} s wall "
          f"(compiles included)")
    for key in ("tokens", "completions", "routing", "handoffs",
                "per_replica"):
        check(spread[key] == same[key],
              f"{key} differ between the spread and the one-device run")
    check(spread["handoffs"][0] > 0, "no KV handoff happened")
    print(f"{len(spread['completions'])} completions: every request's "
          f"{sum(map(len, spread['tokens']))} sampled tokens, routing and "
          f"handoff counts match the one-device run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-replica P/D placement check")
    args = ap.parse_args(argv)
    dev = require_tpu()
    serve.use_compile_cache()
    if args.four_chips:
        four_chip_phase()
    else:
        serve_phase()
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print(f"peak_bytes_in_use per device: {peaks}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
