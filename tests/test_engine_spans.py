"""The serving engine's host spans and the model step's device scopes,
read back from what JAX records.

A small paged engine (smoke widths, a chunk budget, a prefix cache)
serves a prefill of several chunks, a resume from the prefix cache and
completions under ``jax.profiler``; the spans on the profiler's host
plane must nest, and count what the engine did."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core.request import Category, Request, TenantTier
from repro.core.scheduler import DriftScheduler
from repro.models import transformer
from repro.models.registry import get_api
from repro.obs import TraceRecorder
from repro.obs import events as tr
from repro.obs import spans
from repro.serving.engine import EngineConfig, ServingEngine

CFG = smoke_config("smollm-135m")
PARAMS = get_api(CFG).init(CFG, jax.random.PRNGKey(0))
BUCKET, PAGE, CHUNK, SHARED = 32, 8, 8, 16
GROUP = ("premium", 0)


class Log:
    """Sampled tokens by request, decoding steps and completions."""

    def __init__(self):
        self.tokens = {}
        self.decode_steps = 0
        self.completions = 0

    def on_event(self, ev):
        if ev.kind in (tr.FIRST_TOKEN, tr.DECODE_STEP):
            self.tokens.setdefault(ev.req_id, []).append(ev.data["token"])
        elif ev.kind == tr.GAUGE and ev.data["name"] == "queue_depth":
            self.decode_steps += 1           # one gauge per decoding step
        elif ev.kind == tr.COMPLETE:
            self.completions += 1


def _request(i):
    return Request(tenant=TenantTier.PREMIUM, category=Category.SHORT_QA,
                   prompt=f"question {i} about shared context",
                   prompt_tokens=30, max_tokens=12,
                   true_output_tokens=6 + i, prefix_group=GROUP,
                   shared_prefix_tokens=SHARED)


def serve():
    """Serve one request alone (four chunks, its prefix donated to the
    cache), then two more of its prefix group (each resumes from the
    cached pages). Returns the engine's counts and tokens."""
    log = Log()
    sched = DriftScheduler(policy="fifo")
    eng = ServingEngine(CFG, PARAMS, sched,
                        EngineConfig(n_slots=3, max_len=96,
                                     prompt_buckets=(BUCKET,), paged=True,
                                     page_size=PAGE,
                                     chunk_prefill_tokens=CHUNK,
                                     prefix_cache=True,
                                     prefix_cache_pages=8),
                        trace=TraceRecorder(capacity=1, observers=[log]))
    reqs = [_request(i) for i in range(3)]
    sched.submit(reqs[0], 0.0)
    steps, now = 0, 0.0
    while sched.queue_depth() or eng.active_slots():
        eng.step(now)
        steps += 1
        now += 1.0
        if steps == 1 + BUCKET // CHUNK:     # the first one has decoded
            for r in reqs[1:]:
                sched.submit(r, now)
    return {"steps": steps, "launches": eng.n_prefill_launches,
            "hits": eng.n_prefix_hits, "decode_steps": log.decode_steps,
            "completions": log.completions,
            "tokens": [log.tokens[r.req_id] for r in reqs]}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("spans")
    jax.profiler.start_trace(str(out))
    try:
        counts = serve()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    pd = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    found = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for line in host.lines for ev in line.events
             if ev.name.startswith(("engine.", "sched."))]
    return counts, found


def _named(found, name):
    return [(a, b) for a, b, n in found if n == name]


def _inside(child, parents):
    return any(a <= child[0] and child[1] <= b for a, b in parents)


def test_the_scenario_exercises_chunks_cache_and_completions(traced):
    counts, _ = traced
    assert counts["launches"] >= BUCKET // CHUNK + 2   # 4 + 2 x resumed
    assert counts["hits"] == 2
    assert counts["completions"] == 3


def test_span_names_come_from_obs_spans(traced):
    _, found = traced
    names = {n for _, _, n in found}
    assert names <= spans.SPAN_NAMES
    assert names == spans.SPAN_NAMES      # every span opened at least once


def test_spans_nest_inside_their_parents(traced):
    _, found = traced
    steps = _named(found, spans.ENGINE_STEP)
    for a, b, name in found:
        if name != spans.ENGINE_STEP:
            assert _inside((a, b), steps), name
    for child, parent in [(spans.SCHED_DISPATCH, spans.ENGINE_ADMIT),
                          (spans.FIRST_TOKEN_SYNC, spans.ENGINE_PREFILL),
                          (spans.SCHED_COMPLETE, spans.ENGINE_RETIRE)]:
        parents = _named(found, parent)
        assert all(_inside(c, parents) for c in _named(found, child))


def test_span_counts_match_what_the_engine_did(traced):
    counts, found = traced
    n = {name: len(_named(found, name)) for name in spans.SPAN_NAMES}
    assert n[spans.ENGINE_STEP] == counts["steps"]
    assert n[spans.ENGINE_ADMIT] == counts["steps"]
    assert n[spans.ENGINE_PREFILL] == counts["launches"]
    assert n[spans.FIRST_TOKEN_SYNC] == 3
    for name in (spans.DECODE_PREP, spans.DECODE_LAUNCH, spans.DECODE_SYNC,
                 spans.ENGINE_RETIRE):
        assert n[name] == counts["decode_steps"], name
    assert n[spans.SCHED_COMPLETE] == counts["completions"]
    assert n[spans.SCHED_DISPATCH] >= 3


def test_tokens_are_the_same_with_the_profiler_off(traced):
    counts, _ = traced
    assert serve() == counts


def test_model_step_scopes_reach_the_compiled_program():
    """Every device scope names operations of the paged decode and chunk
    programs (XLA keeps a scope in each operation's ``op_name``)."""
    n_pages, pps = 9, 4
    pool = {kv: jnp.zeros((CFG.n_layers, n_pages, CFG.n_kv_heads, PAGE,
                           CFG.head_dim), jnp.bfloat16) for kv in "kv"}
    table = jnp.zeros((2, pps), jnp.int32)
    decode = jax.jit(lambda p, pl, t, pt, sl: transformer.decode_step_paged(
        CFG, p, pl, t, pt, sl)).lower(
            PARAMS, pool, jnp.zeros((2,), jnp.int32), table,
            jnp.ones((2,), jnp.int32)).compile().as_text()
    chunk = jax.jit(lambda p, pl, t, pt, q0: transformer.prefill_chunk_paged(
        CFG, p, pl, t, pt, q0)).lower(
            PARAMS, pool, jnp.zeros((1, CHUNK), jnp.int32), table[:1],
            jnp.zeros((1,), jnp.int32)).compile().as_text()
    for text in (decode, chunk):
        for scope in spans.DEVICE_SCOPES:
            assert f"/{scope}/" in text, scope
