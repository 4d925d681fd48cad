"""The benchmark's traffic generator: seeded, and true to its mix file."""

import json
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from traffic import generator as gen  # noqa: E402


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def bursty():
    """The chat mix with gamma gaps of coefficient of variation 2."""
    m = mix("chat-poisson")
    m["arrivals"] = {"process": "gamma", "shape": 0.25, "rate_rps": 3.0}
    return m


OPEN = {"chat-poisson": lambda: mix("chat-poisson"), "chat-bursty": bursty}


@pytest.mark.parametrize("name", OPEN)
def test_one_seed_reproduces_the_same_plan(name):
    m = OPEN[name]()
    a = gen.open_loop(m, 3_000_000_017, 30.0)
    b = gen.open_loop(m, 3_000_000_017, 30.0)
    c = gen.open_loop(m, 5, 30.0)
    assert a == b
    assert a != c
    # another seed serves the same work in another order
    assert sorted(s.output_tokens for _, s in a) == sorted(
        s.output_tokens for _, s in c)
    assert sorted(s.prompt_tokens for _, s in a) == sorted(
        s.prompt_tokens for _, s in c)


def test_closed_loop_is_seeded_and_deals_every_request():
    m = mix("chat-closed")
    a = gen.closed_loop(m, 7, 16, 5)
    assert a == gen.closed_loop(m, 7, 16, 5)
    assert [len(q) for q in a] == [5] * 16
    assert sorted(s.output_tokens for q in a for s in q) == sorted(
        s.output_tokens for q in gen.closed_loop(m, 8, 16, 5) for s in q)


@pytest.mark.parametrize("name", OPEN)
def test_window_holds_the_rate_times_its_length(name):
    m = OPEN[name]()
    for seconds in (1.0, 30.0):
        plan = gen.open_loop(m, 9, seconds)
        assert len(plan) == round(m["arrivals"]["rate_rps"] * seconds)
        times = [t for t, _ in plan]
        assert times == sorted(times) and 0.0 <= times[0] and times[-1] < seconds


def test_gaps_follow_the_arrival_process():
    # exponential gaps have a coefficient of variation of 1, gamma gaps
    # of shape k one of k^-1/2; 4000 gaps put the standard error near 3%
    n = 4000
    for arrivals, cv in (({"process": "poisson", "rate_rps": 8.0}, 1.0),
                         ({"process": "gamma", "shape": 0.25,
                           "rate_rps": 3.0}, 2.0)):
        g = gen.draw_gaps(arrivals, n, n / arrivals["rate_rps"])
        assert g.mean() == pytest.approx(1 / arrivals["rate_rps"], rel=1e-9)
        assert g.std() / g.mean() == pytest.approx(cv, rel=0.15)


def test_lengths_match_the_mix():
    m = mix("chat-poisson")
    specs = gen.draw_specs(m, 4000)
    g = m["generator"]
    cats = [s.category for s in specs]
    for c, w in g["category_weights"].items():
        assert cats.count(c) / len(cats) == pytest.approx(w, abs=0.03)
    tiers = [gen.TIER_LABELS[s.tenant] for s in specs]
    for t, w in g["tenant_weights"].items():
        assert tiers.count(t) / len(tiers) == pytest.approx(w, abs=0.03)
    out = [s.output_tokens for s in specs]
    # the paper's mix: outputs average about 234 tokens under the cap
    assert statistics.mean(out) == pytest.approx(234, rel=0.08)
    assert max(out) <= g["max_tokens"]
    assert all(3 <= s.prompt_tokens <= 33 for s in specs)


def test_in_flight_favours_long_requests_and_is_fixed():
    m = mix("chat-poisson")
    held = gen.in_flight(m, 400)
    assert held == gen.in_flight(m, 400)
    pop = gen.draw_specs(m, 1600)
    whole = {(s.prompt, s.tenant, s.category) for s in pop}
    assert all((s.prompt, s.tenant, s.category) in whole for s in held)
    # a request is held with a chance in proportion to its length, and
    # is half done on average: the remaining outputs average E[L^2]/2E[L]
    out = np.array([s.output_tokens for s in pop], float)
    want = (out ** 2).mean() / (2 * out.mean())
    assert statistics.mean(s.output_tokens for s in held) == pytest.approx(
        want, rel=0.15)
    assert all(1 <= s.output_tokens <= m["generator"]["max_tokens"]
               for s in held)


def test_rag_prompts_open_with_a_shared_prefix():
    m = mix("rag-closed")
    g = m["generator"]
    specs = gen.draw_specs(m, 2000)
    assert all(s.shared_prefix_tokens == 1024 for s in specs)
    assert all(s.output_tokens <= 128 for s in specs)
    groups = {s.prefix_group for s in specs}
    assert len(groups) == 3 * g["prefix_groups_per_tenant"]
    suffix = np.array([s.prompt_tokens - 1024 for s in specs])
    assert suffix.min() >= 3 * 32 and suffix.max() <= 33 * 32
