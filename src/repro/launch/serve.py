"""Serving launcher: the paper's full experiment protocol.

    PYTHONPATH=src python -m repro.launch.serve --policy sjf --bias on
    PYTHONPATH=src python -m repro.launch.serve --engine jax \
        --arch smollm-135m --requests 24
    PYTHONPATH=src python -m repro.launch.serve --engine jax \
        --arch smollm-135m --published --paged --requests 48

``--engine sim`` (default) runs the discrete-event cluster simulator
with the L4-calibrated cost model — the configuration every paper table
uses. ``--engine jax`` runs the real continuous-batching JAX engine,
same scheduler state machine, on JAX's default backend (the Pallas
kernels on a TPU, their XLA reference forms elsewhere). By default it
serves the arch's reduced ``SMOKE`` config on the slot-ring cache
(``SLOT_RING_ENGINE``); ``--published`` serves the registered
``CONFIG`` at its published widths (random weights), and ``--paged``
switches to the paged KV pool (``PAGED_ENGINE``) with chunked prefill
(``PAGED_CHUNK_TOKENS`` per step) and the shared-prefix cache, under
traffic whose every request opens with a tenant system prompt of
``PAGED_SHARED_PREFIX_TOKENS`` tokens.

The JAX engine keeps its compiled programs in JAX's persistent cache:
at ``JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

from ..configs import ARCHS, get_config, smoke_config
from ..core.estimator import DriftConfig
from ..core.request import Request
from ..core.scheduler import DriftScheduler
from ..serving.simulator import SimConfig, WorkerSimulator
from ..workload.generator import GeneratorConfig, WorkloadGenerator

#: the persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is
#: unset: fixed, so a later run of the same checkout finds it again
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
#: the slot-ring engine: 8 slots of 128 tokens, prompts bucketed to 32
SLOT_RING_ENGINE = dict(n_slots=8, max_len=128, prompt_buckets=(16, 32))
#: the paged engine: 16 slots of 1024 tokens, prompts bucketed to 512
PAGED_ENGINE = dict(n_slots=16, max_len=1024, prompt_buckets=(128, 256, 512))
#: per-step prefill token budget of the paged engine (Sarathi chunking)
PAGED_CHUNK_TOKENS = 256
#: the tenant system prompt every request of the paged traffic opens
#: with: the traffic the prefix cache serves
PAGED_SHARED_PREFIX_TOKENS = 256


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``COMPILE_CACHE_DIR``
    unless ``JAX_COMPILATION_CACHE_DIR`` already chose a directory."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def jax_engine(arch: str, sched: DriftScheduler, *,
               published: bool = False, paged: bool = False):
    """A ``ServingEngine`` over random params of ``arch``: its
    registered config (``published``) or the reduced smoke config, on
    the paged pool with chunked prefill and the prefix cache
    (``paged``, sized by ``PAGED_ENGINE``) or the slot-ring cache
    (``SLOT_RING_ENGINE``)."""
    import jax

    from ..models.registry import get_api
    from ..serving.engine import EngineConfig, ServingEngine
    cfg = get_config(arch) if published else smoke_config(arch)
    params = get_api(cfg).init(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(**(PAGED_ENGINE if paged else SLOT_RING_ENGINE),
                        paged=paged,
                        chunk_prefill_tokens=(PAGED_CHUNK_TOKENS if paged
                                              else None),
                        prefix_cache=paged)
    return ServingEngine(cfg, params, sched, ecfg)


def request_plan(n: int, seed: int, *, shared_prefix_tokens: int = 0
                 ) -> List[Tuple[float, Request]]:
    """The engine's seeded traffic: ``n`` arrival-ordered requests of
    at most 64 output tokens."""
    gen = WorkloadGenerator(GeneratorConfig(
        total_requests=n, calibration_requests=n, max_tokens=64,
        shared_prefix_tokens=shared_prefix_tokens, seed=seed))
    return gen.plan(seed=seed).calibration


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--engine", default="sim", choices=["sim", "jax"])
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "priority", "weighted", "sjf", "aging"])
    ap.add_argument("--bias", default="on", choices=["on", "off"])
    ap.add_argument("--requests", type=int, default=3000)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--arch", default="smollm-135m", choices=ARCHS)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--fail-at", type=float, default=None,
                    help="inject a worker failure at this time (s)")
    jx = ap.add_argument_group("--engine jax")
    jx.add_argument("--published", action="store_true",
                    help="the arch's published config, not its smoke config")
    jx.add_argument("--paged", action="store_true",
                    help="paged KV pool + chunked prefill + prefix cache")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    sched = DriftScheduler(
        policy=args.policy,
        config=DriftConfig(bias_enabled=args.bias == "on"))

    if args.engine == "sim":
        gen = WorkloadGenerator(GeneratorConfig(
            total_requests=args.requests,
            calibration_requests=args.requests // 3,
            seed=args.seed))
        plan = gen.plan(seed=args.seed)
        sim_cfg = SimConfig(
            seed=args.seed, n_workers=args.workers,
            fail_times=(args.fail_at,) if args.fail_at else ())
        sim = WorkerSimulator(sched, plan, sim_cfg)
        metrics = sim.run()
    else:
        use_compile_cache()
        eng = jax_engine(args.arch, sched, published=args.published,
                         paged=args.paged)
        shared = PAGED_SHARED_PREFIX_TOKENS if args.paged else 0
        for t, r in request_plan(args.requests, args.seed,
                                 shared_prefix_tokens=shared):
            sched.submit(r, t)
        metrics = eng.run_until_drained()

    out = metrics.as_dict()
    out["learned_bias"] = sched.bias_store.snapshot()
    if args.json:
        print(json.dumps(out, indent=1, default=float))
    else:
        print(f"policy={args.policy} bias={args.bias} "
              f"completed={metrics.n_completed}")
        print(f"e2e    P50={metrics.e2e.p50:8.2f}s "
              f"P95={metrics.e2e.p95:8.2f}s P99={metrics.e2e.p99:8.2f}s")
        print(f"wait   mean={metrics.queue_wait.mean:7.2f}s")
        print(f"exec   P50={metrics.gpu_exec.p50:8.2f}s "
              f"util={metrics.gpu_utilization:.0%}")
        for t, v in metrics.per_tenant.items():
            print(f"tenant {t:9s} latency={v['latency']['mean']:7.1f}s "
                  f"wait={v['queue_wait']['mean']:7.1f}s")
        print("learned bias:", {k: round(v, 3)
                                for k, v in out["learned_bias"].items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
