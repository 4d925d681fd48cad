"""The benchmark's seeded traffic generator.

A copy of the paper's multi-tenant generator (Algorithm 1: three tenant
tiers, four semantic categories drawn from a ~1180-prompt corpus,
ground-truth output lengths with a systematic drift below the static
estimate), kept with the benchmark so that no change to the program can
change the traffic it is measured on. It imports nothing of the program
and yields plain :class:`Spec` rows.

A mix file (``bench/traffic/<mix>.json``) gives the generator's
parameters and the arrival process:

* ``poisson``: exponential inter-arrival gaps at ``rate_rps``;
* ``gamma``: gamma gaps of shape ``shape`` at ``rate_rps`` (shape 1/CV^2;
  0.25 is BurstGPT-like burstiness, coefficient of variation 2);
* ``closed``: ``clients_per_slot`` clients per engine slot, each sending
  its next request when its previous one completes.

Every seed serves the same work. The request sizes and the gaps come
from a population drawn once with the mix's ``population_seed``; a run's
``--seed`` only permutes them. The gaps are scaled to sum to the window,
so a window of ``s`` seconds always holds ``round(rate_rps * s)``
arrivals. An open loop's warm-up starts from ``in_flight`` requests
drawn as such a loop holds them at steady state.
"""

from __future__ import annotations

import hashlib
import math
import random
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

TIER_LABELS = ("premium", "standard", "batch")
CATEGORIES = ("short_qa", "summary", "technical", "report")

_TOPICS: Sequence[str] = (
    "DNS", "Kubernetes pod scheduling", "TCP congestion control", "BGP routing",
    "TLS certificate rotation", "OAuth2 token exchange", "container image layers",
    "service mesh sidecars", "etcd consensus", "load balancer health checks",
    "GPU memory paging", "KV-cache management", "continuous batching",
    "speculative decoding", "tensor parallelism", "pipeline parallelism",
    "gradient checkpointing", "mixed precision training", "collective all-reduce",
    "parameter servers", "RDMA networking", "NVMe-oF storage", "RAID rebuild",
    "log-structured merge trees", "B-tree indexes", "write-ahead logging",
    "MVCC snapshot isolation", "two-phase commit", "Raft leader election",
    "vector clocks", "CRDT convergence", "consistent hashing", "bloom filters",
    "cache eviction policies", "memory fragmentation", "NUMA locality",
    "cgroup CPU throttling", "eBPF tracing", "syscall batching", "io_uring",
    "zero-copy networking", "QUIC streams", "HTTP/3 prioritization",
    "CDN edge caching", "rate limiting algorithms", "circuit breakers",
    "blue-green deployment", "canary rollouts", "feature flags",
    "observability pipelines", "distributed tracing spans", "metrics cardinality",
    "alert fatigue", "incident runbooks", "postmortem culture",
    "chaos engineering", "capacity planning", "autoscaling policies",
    "spot instance preemption", "serverless cold starts", "WebAssembly sandboxing",
)

_TEMPLATES: Dict[str, Sequence[str]] = {
    "short_qa": (
        "What is {t}?",
        "How does {t} work?",
        "When should teams use {t}?",
        "Define {t} in one paragraph.",
        "What problem does {t} solve?",
    ),
    "summary": (
        "Summarize how {t} behaves under sustained production load, covering the main failure modes operators should monitor.",
        "Summarize the design of {t} for a new on-call engineer joining the platform team this quarter.",
        "Provide a concise summary of {t}, including when it is preferred over the common alternatives in large deployments.",
        "Summarize the operational trade-offs of {t} in a multi-region, multi-tenant cloud environment with strict latency SLOs.",
        "Summarize recent best practices around {t} and the migration steps legacy systems typically require.",
    ),
    "technical": (
        "Explain {t} in technical depth, including the underlying data structures, protocols, and the failure scenarios that arise under contention.",
        "Explain how {t} interacts with retries, timeouts, and backpressure in a distributed system, and how to reason about its consistency guarantees.",
        "Walk through the implementation details of {t}, covering the hot path, the slow path, and the instrumentation needed to debug production regressions.",
        "Explain the performance characteristics of {t}: asymptotic behavior, constant factors, memory traffic, and the tuning knobs that matter at scale.",
        "Describe {t} for a senior engineer audience, contrasting at least two real-world implementations and their divergent design decisions under load.",
    ),
    "report": (
        "Write a detailed incident report on the {t} outage.",
        "Write a full post-incident report covering {t}.",
        "Write a detailed incident report on a network outage involving {t}, summarizing affected services, the detection timeline, root cause analysis, remediation steps, and long-term action items for the infrastructure team.",
        "Write a comprehensive design review for adopting {t} across the organization, covering current architecture, proposed changes, capacity estimates, rollout phases, risk register, and success metrics.",
        "Write a detailed quarterly reliability report focused on {t}, including SLO attainment, error budgets consumed, major incidents, trend analysis, and prioritized engineering recommendations.",
        "Produce a full migration plan document for replacing the legacy implementation of {t}, with an executive summary, dependency inventory, phased timeline, rollback strategy, and cost analysis.",
    ),
}

_EXTRA_SHORT_QA = (
    "Give a one-line answer: what is {t}?",
    "Briefly, why does {t} matter?",
    "Name the main alternative to {t}.",
    "Is {t} still relevant in 2026? Answer briefly.",
)

# the hidden generation behaviour: mean ratio of true output to the
# static estimate (the drift), lognormal verbosity spread, and the
# exponent coupling prompt length to output length
_PROFILE: Dict[str, Tuple[float, float, float]] = {
    "short_qa": (0.855, 0.12, 0.08),
    "summary": (0.815, 0.15, 0.10),
    "technical": (0.795, 0.20, 0.12),
    "report": (0.825, 0.22, 0.12),
}
_REF_PROMPT_LEN = {"short_qa": 5.9, "summary": 17.3, "technical": 21.7,
                   "report": 27.3}
_T_BASE = {"short_qa": 64.0, "summary": 288.0, "technical": 416.0,
           "report": 600.0}
CORPUS_SIZE = 1180


@dataclass(frozen=True)
class Prompt:
    category: str
    text: str
    words: int
    verbosity: float


@dataclass(frozen=True)
class Spec:
    """One request's sizes and identity, as the generator draws it."""

    tenant: int                      # index into TIER_LABELS
    category: str
    prompt: str
    prompt_tokens: int
    max_tokens: int
    output_tokens: int               # ground-truth output length
    prefix_group: Optional[Tuple[str, int]] = None
    shared_prefix_tokens: int = 0


def _stable_unit(s: str) -> float:
    h = hashlib.sha256(s.encode()).digest()
    return int.from_bytes(h[:8], "big") / float(1 << 64)


def build_corpus() -> Dict[str, List[Prompt]]:
    """The corpus by category: 60 topics x 19 templates plus short
    phrasings up to 1180 prompts; verbosity is a lognormal draw keyed
    on the prompt text."""
    prompts: List[Prompt] = []
    seen = set()

    def add(category: str, text: str) -> None:
        if text in seen:
            return
        seen.add(text)
        sigma = _PROFILE[category][1]
        u1 = max(_stable_unit(text + "#u1"), 1e-12)
        u2 = _stable_unit(text + "#u2")
        g = math.sqrt(-2.0 * math.log(u1)) * math.cos(2.0 * math.pi * u2)
        prompts.append(Prompt(category, text, len(text.split()),
                              math.exp(sigma * g - 0.5 * sigma ** 2)))

    for topic in _TOPICS:
        for cat, templates in _TEMPLATES.items():
            for tpl in templates:
                add(cat, tpl.format(t=topic))
    for tpl in _EXTRA_SHORT_QA:
        for topic in _TOPICS:
            if len(prompts) >= CORPUS_SIZE:
                break
            add("short_qa", tpl.format(t=topic))
    by_cat: Dict[str, List[Prompt]] = {c: [] for c in CATEGORIES}
    for p in prompts[:CORPUS_SIZE]:
        by_cat[p.category].append(p)
    return by_cat


def _output_tokens(p: Prompt, rng: random.Random, sigma: float,
                   max_tokens: int) -> int:
    ratio, _, len_exp = _PROFILE[p.category]
    base = _T_BASE[p.category] * ratio
    scale = (max(p.words, 1) / _REF_PROMPT_LEN[p.category]) ** len_exp
    noise = math.exp(rng.gauss(0.0, sigma) - 0.5 * sigma ** 2)
    return max(1, min(int(round(base * p.verbosity * scale * noise)),
                      max_tokens))


def draw_specs(mix: dict, n: int, salt: int = 0) -> List[Spec]:
    """``n`` requests by Algorithm 1 from the mix's population seed."""
    g = mix["generator"]
    corpus = build_corpus()
    cats = list(g["category_weights"])
    cat_w = [g["category_weights"][c] for c in cats]
    tier_w = [g["tenant_weights"][t] for t in TIER_LABELS]
    shared = int(g.get("shared_prefix_tokens", 0))
    groups = int(g.get("prefix_groups_per_tenant", 1))
    rng = random.Random(int(mix["population_seed"]) * 1_000_003 + salt)
    out = []
    for _ in range(n):
        cat = rng.choices(cats, weights=cat_w)[0]
        tenant = rng.choices(range(len(TIER_LABELS)), weights=tier_w)[0]
        p = rng.choice(corpus[cat])
        true_out = _output_tokens(p, rng, g["output_noise_sigma"],
                                  g["max_tokens"])
        group = None
        if shared > 0:
            group = (TIER_LABELS[tenant], rng.randrange(groups))
        out.append(Spec(
            tenant=tenant, category=cat, prompt=p.text,
            prompt_tokens=max(1, round(p.words * g["prompt_tokens_scale"]))
            + shared,
            max_tokens=g["max_tokens"], output_tokens=true_out,
            prefix_group=group, shared_prefix_tokens=shared))
    return out


def draw_gaps(arrivals: dict, n: int, seconds: float, salt: int = 0
              ) -> np.ndarray:
    """``n`` inter-arrival gaps of the mix's process, scaled to sum to
    ``seconds``."""
    rng = np.random.default_rng(
        [int(arrivals.get("population_seed", 0)), salt])
    rate = float(arrivals["rate_rps"])
    if arrivals["process"] == "poisson":
        gaps = rng.exponential(1.0 / rate, size=n)
    elif arrivals["process"] == "gamma":
        k = float(arrivals["shape"])
        gaps = rng.gamma(k, 1.0 / (rate * k), size=n)
    else:
        raise ValueError(f"no gaps for arrival process "
                         f"{arrivals['process']!r}")
    return gaps * (seconds / gaps.sum())


def _perm(seed: int, salt: int, n: int) -> np.ndarray:
    return np.random.default_rng([seed, salt]).permutation(n)


def open_loop(mix: dict, seed: int, seconds: float, salt: int = 0
              ) -> List[Tuple[float, Spec]]:
    """Arrival times in ``[0, seconds)`` and their requests: the mix's
    population for this window length, permuted by ``seed``."""
    arr = dict(mix["arrivals"], population_seed=mix["population_seed"])
    n = max(1, round(float(arr["rate_rps"]) * seconds))
    specs = draw_specs(mix, n, salt)
    gaps = draw_gaps(arr, n, seconds, salt)
    specs = [specs[i] for i in _perm(seed, 2 * salt, n)]
    gaps = gaps[_perm(seed, 2 * salt + 1, n)]
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return list(zip(times.tolist(), specs))


def in_flight(mix: dict, n: int, salt: int = 0) -> List[Spec]:
    """``n`` requests as an open loop holds them in flight at steady
    state: drawn from the mix's population with a chance in proportion
    to their output length (a long request is in flight for longer), each
    with a remaining output drawn evenly from 1 to its whole length."""
    pop = draw_specs(mix, max(4 * n, 64), salt)
    weight = np.array([s.output_tokens for s in pop], float)
    rng = np.random.default_rng([int(mix["population_seed"]), salt])
    pick = rng.choice(len(pop), size=n, p=weight / weight.sum())
    return [replace(pop[i], output_tokens=int(rng.integers(
        1, pop[i].output_tokens + 1))) for i in pick]


def closed_loop(mix: dict, seed: int, n_clients: int, per_client: int,
                salt: int = 0) -> List[List[Spec]]:
    """Each client's queue of requests: the mix's population of
    ``n_clients * per_client`` requests, permuted by ``seed`` and dealt
    round-robin."""
    n = n_clients * per_client
    specs = draw_specs(mix, n, salt)
    specs = [specs[i] for i in _perm(seed, 2 * salt, n)]
    return [specs[c::n_clients] for c in range(n_clients)]
