"""The serving entry point (``repro.launch.serve --engine jax``): the
engine it builds, the traffic it submits, and where it keeps JAX's
persistent compilation cache."""

import json

import jax
import pytest

from repro.configs import get_config, smoke_config
from repro.core.scheduler import DriftScheduler
from repro.launch import serve


def test_jax_engine_defaults_are_the_smoke_slot_ring():
    eng = serve.jax_engine("smollm-135m", DriftScheduler())
    assert eng.cfg == smoke_config("smollm-135m")
    e = eng.ecfg
    assert (e.n_slots, e.max_len, e.prompt_buckets) == (8, 128, (16, 32))
    assert serve.SLOT_RING_ENGINE == dict(n_slots=8, max_len=128,
                                          prompt_buckets=(16, 32))
    assert not e.paged and not e.prefix_cache
    assert e.chunk_prefill_tokens is None


def test_jax_engine_published_paged(monkeypatch):
    assert serve.PAGED_ENGINE == dict(n_slots=16, max_len=1024,
                                      prompt_buckets=(128, 256, 512))
    # the published widths at a pool small enough for the CPU
    monkeypatch.setattr(serve, "PAGED_ENGINE", dict(
        n_slots=2, max_len=64, prompt_buckets=(32,)))
    eng = serve.jax_engine("smollm-135m", DriftScheduler(), published=True,
                           paged=True)
    cfg = get_config("smollm-135m")
    assert eng.cfg == cfg
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab) == (30, 576, 9, 3, 49152)
    e = eng.ecfg
    assert (e.n_slots, e.max_len, e.prompt_buckets) == (2, 64, (32,))
    assert e.paged and e.prefix_cache
    assert e.chunk_prefill_tokens == serve.PAGED_CHUNK_TOKENS
    # head-major pool: [L, n_pages, Hk, page, hd]
    assert eng.pool.k.shape == (30, eng.alloc.n_pages + 1, 3, e.page_size,
                                64)


def test_request_plan_shared_prefix():
    plan = serve.request_plan(12, 5, shared_prefix_tokens=32)
    assert len(plan) == 12
    assert all(r.prefix_group is not None and r.shared_prefix_tokens == 32
               for _, r in plan)
    assert [t for t, _ in plan] == sorted(t for t, _ in plan)


def test_main_serves_paged_engine(monkeypatch, capsys):
    # a directory chosen by the environment keeps the code's hands off
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    monkeypatch.setattr(serve, "PAGED_ENGINE", dict(
        n_slots=2, max_len=96, prompt_buckets=(32, 64)))
    monkeypatch.setattr(serve, "PAGED_SHARED_PREFIX_TOKENS", 16)
    engines = []
    build = serve.jax_engine
    monkeypatch.setattr(serve, "jax_engine",
                        lambda *a, **k: engines.append(build(*a, **k))
                        or engines[-1])
    assert serve.main(["--engine", "jax", "--paged", "--requests", "6",
                       "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["n_completed"] == 6
    # the shared system prompts reached the prefix cache
    assert engines[0].n_prefix_hits > 0


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The environment's choice stands; otherwise one fixed directory
    inside the checkout."""
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        serve.use_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    if env_dir is None:
        assert got == str(serve.COMPILE_CACHE_DIR)
        assert serve.COMPILE_CACHE_DIR.parent.joinpath("src").is_dir()
    else:
        assert got == prev
