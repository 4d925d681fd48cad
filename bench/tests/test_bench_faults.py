"""The check fails a broken timed path: a run of each cell with a fault
planted under the engine (on the CPU, at the configuration's small
widths) comes out not correct, once for each fault a serving cell can
have. Every cell runs on one chip, so there is no exchange between
chips to leave out."""

import io
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture
def short_warmup(monkeypatch):
    load = harness.load_cell

    def quick(root, name):
        cell = load(root, name)
        cell.mix["warmup"] = dict(cell.mix["warmup"], seconds=2,
                                 in_flight=4)
        return cell

    monkeypatch.setattr(harness, "load_cell", quick)


def state_unchanged(monkeypatch):
    """The decode step returns the KV pool it was given: no decoded
    token's keys and values are ever written."""
    from repro.models import transformer
    step = transformer.decode_step_paged

    def broken(cfg, params, pool, *a, **kw):
        return step(cfg, params, pool, *a, **kw)[0], pool

    monkeypatch.setattr(transformer, "decode_step_paged", broken)


def half_batch(monkeypatch):
    """The decode step leaves out half of the slots, those whose sequence
    holds an even number of tokens: their logits are never computed. A
    sequence grows by one token a step, so every request that decodes
    twice is left out at one of those steps, whichever slots the engine
    fills and however few steps the load lets it take."""
    import jax.numpy as jnp
    from repro.models import transformer
    step = transformer.decode_step_paged

    def broken(cfg, params, pool, tokens, page_table, seq_lens, **kw):
        logits, pool = step(cfg, params, pool, tokens, page_table, seq_lens,
                            **kw)
        return jnp.where((seq_lens % 2 == 0)[:, None], 0.0, logits), pool

    monkeypatch.setattr(transformer, "decode_step_paged", broken)


def token_altered(monkeypatch):
    """Sampling hands on the id after the one it picked."""
    from repro.serving import engine
    sample = engine.sample_logits

    def broken(logits, rng, temperature=0.0):
        return (sample(logits, rng, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_logits", broken)


def run(cell, seed=3_000_000_017):
    return harness.run(ROOT, cell, seed, 3.0, False,
                       t_process=time.perf_counter(), smoke=True,
                       log=io.StringIO())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(fault, cell, short_warmup, monkeypatch):
    fault(monkeypatch)
    out = run(cell)
    c = out["checks"]
    assert out["correct"] is False, c
    assert c["requests_compared"]["value"] >= 1
    assert (c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
            or c["bad_served_tokens"]["value"] > 0)
