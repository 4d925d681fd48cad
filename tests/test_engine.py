"""The real JAX continuous-batching engine driving DriftScheduler."""

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.estimator import DriftConfig
from repro.core.request import Category, Request, TenantTier
from repro.core.scheduler import DriftScheduler
from repro.models.registry import get_api
from repro.serving.engine import EngineConfig, ServingEngine
from repro.workload.generator import GeneratorConfig, WorkloadGenerator


def _engine(policy="fifo", n_slots=4, arch="smollm-135m"):
    cfg = smoke_config(arch)
    api = get_api(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    sched = DriftScheduler(policy=policy)
    eng = ServingEngine(cfg, params, sched,
                        EngineConfig(n_slots=n_slots, max_len=96,
                                     prompt_buckets=(16,)))
    return eng, sched


def _submit_n(sched, n, seed=0):
    gen = WorkloadGenerator(GeneratorConfig(
        total_requests=n, calibration_requests=n,
        max_tokens=48, seed=seed))
    plan = gen.plan(seed=seed)
    for t, r in plan.calibration:
        sched.submit(r, t)
    return [r for _, r in plan.calibration]


def test_engine_completes_all_requests():
    eng, sched = _engine()
    reqs = _submit_n(sched, 12)
    m = eng.run_until_drained(max_steps=5000)
    assert m.n_completed == 12
    assert sched.queue_depth() == 0
    assert not eng.active_slots()


def test_engine_observed_lengths_feed_drift():
    eng, sched = _engine()
    reqs = _submit_n(sched, 10)
    eng.run_until_drained(max_steps=5000)
    assert sum(sched.bias_store.update_counts().values()) == 10
    for r in sched.completed:
        assert r.observed_output_tokens >= 1
        # oracle EOS: observed == min(true, cap, slot budget)
        assert r.observed_output_tokens <= r.max_tokens


def test_engine_continuous_batching_interleaves():
    """More requests than slots: slots must turn over (join/leave)."""
    eng, sched = _engine(n_slots=2)
    _submit_n(sched, 8)
    m = eng.run_until_drained(max_steps=5000)
    assert m.n_completed == 8


def test_engine_sjf_prefers_short_jobs():
    eng, sched = _engine(policy="sjf", n_slots=1)
    # one long report then several short QAs; SJF should run shorts first
    long_r = Request(tenant=TenantTier.BATCH, category=Category.REPORT,
                     prompt="write a detailed report on dns outages",
                     max_tokens=48, true_output_tokens=48)
    shorts = [Request(tenant=TenantTier.PREMIUM, category=Category.SHORT_QA,
                      prompt="what is dns?", max_tokens=48,
                      true_output_tokens=4) for _ in range(3)]
    sched.submit(long_r, 0.0)
    for s in shorts:
        sched.submit(s, 0.01)
    eng.run_until_drained(max_steps=5000)
    order = [r.req_id for r in sched.completed]
    assert order.index(long_r.req_id) == len(order) - 1


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-1.2b"])
def test_engine_runs_ssm_families(arch):
    eng, sched = _engine(arch=arch, n_slots=2)
    _submit_n(sched, 4)
    m = eng.run_until_drained(max_steps=5000)
    assert m.n_completed == 4


def test_paged_engine_matches_contiguous_completions():
    """vLLM-style paged engine mode: same scheduler behaviour, same
    observed lengths, allocator fully drains."""
    import numpy as np
    cfg = smoke_config("smollm-135m")
    api = get_api(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))

    def run(paged):
        sched = DriftScheduler(policy="fifo")
        eng = ServingEngine(cfg, params, sched,
                            EngineConfig(n_slots=3, max_len=96,
                                         prompt_buckets=(16,),
                                         paged=paged, page_size=8))
        gen = WorkloadGenerator(GeneratorConfig(
            total_requests=8, calibration_requests=8,
            max_tokens=24, seed=3))
        for t, r in gen.plan(seed=3).calibration:
            sched.submit(r, t)
        m = eng.run_until_drained(max_steps=5000)
        return eng, sched, m

    eng_p, sched_p, m_p = run(paged=True)
    eng_c, sched_c, m_c = run(paged=False)
    assert m_p.n_completed == m_c.n_completed == 8
    obs_p = sorted(r.observed_output_tokens for r in sched_p.completed)
    obs_c = sorted(r.observed_output_tokens for r in sched_c.completed)
    assert obs_p == obs_c                     # oracle-EOS targets agree
    assert eng_p.alloc.free_pages == eng_p.alloc.n_pages  # all freed


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_paged_decode_ignores_stale_pool_pages(prefix_cache):
    """Every decode step's KV lands in a page of its own sequence, also
    when it opens a new page: a pool full of stale values samples the
    same tokens as a fresh one."""
    import jax.numpy as jnp

    from repro.obs import TraceRecorder
    from repro.obs import events as tr
    from repro.serving.kv_cache import PagedPool
    cfg = smoke_config("smollm-135m")
    params = get_api(cfg).init(cfg, jax.random.PRNGKey(0))

    class TokenLog:
        def __init__(self):
            self.tokens = {}

        def on_event(self, ev):
            if ev.kind in (tr.FIRST_TOKEN, tr.DECODE_STEP):
                self.tokens.setdefault(ev.req_id, []).append(
                    ev.data["token"])

    def run(stale):
        log = TokenLog()
        rec = TraceRecorder(capacity=1, observers=[log])
        sched = DriftScheduler(policy="fifo")
        eng = ServingEngine(cfg, params, sched,
                            EngineConfig(n_slots=3, max_len=96,
                                         prompt_buckets=(16,), paged=True,
                                         page_size=8, chunk_prefill_tokens=8,
                                         prefix_cache=prefix_cache),
                            trace=rec)
        if stale:
            eng.pool = PagedPool(k=jnp.full_like(eng.pool.k, 1e4),
                                 v=jnp.full_like(eng.pool.v, 1e4),
                                 page_size=eng.pool.page_size)
        reqs = _submit_n(sched, 6, seed=3)
        eng.run_until_drained(max_steps=5000)
        # by plan position: request ids differ between two plans
        return [log.tokens[r.req_id] for r in reqs]

    fresh = run(stale=False)
    assert max(map(len, fresh)) > 8            # decode crosses a page
    assert run(stale=True) == fresh


def test_paged_engine_rejects_ssm():
    import pytest as _pytest
    cfg = smoke_config("mamba2-2.7b")
    api = get_api(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    with _pytest.raises(ValueError):
        ServingEngine(cfg, params, DriftScheduler(policy="fifo"),
                      EngineConfig(paged=True))


def test_tokenize_at_a_published_vocab():
    """Prompt bytes map to ids in [1, vocab) at a vocab wider than a
    byte: the legacy layout right-aligns them, padding in front."""
    import dataclasses
    cfg = dataclasses.replace(smoke_config("smollm-135m"), vocab=49152)
    params = get_api(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, DriftScheduler(),
                        EngineConfig(n_slots=1, max_len=32,
                                     prompt_buckets=(16,)))
    req = Request(tenant=TenantTier.STANDARD, category=Category.SHORT_QA,
                  prompt="\xff hi", max_tokens=4)
    tokens = eng._tokenize(req, 16, 4, 0)
    want = np.frombuffer(req.prompt.encode()[:4], np.uint8) + 1
    assert tokens.dtype == np.int32
    np.testing.assert_array_equal(tokens[0, -4:], want)
    assert not tokens[0, :-4].any()
