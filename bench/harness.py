"""One run of one benchmark cell: set-up, the measured window, the check.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the configuration names its model
family (``"family"``, ``dense`` where it names none), whose module
(``bench/families/<family>.py``) gives the served model's config, its
weights, its shapes for the counts and the plain reference; every
metric is a reader of its own (``bench/metrics/<metric>.py``). This
module knows none of them by name.

The run:

1. set-up (``setup_s``, from process start): the persistent compile
   cache at a fixed path in the checkout; the weights made on the chip
   from the seed; the engine (``repro.serving.ServingEngine`` over a
   ``DriftScheduler``) sized from the configuration and the mix; every
   program the window can call compiled or fetched from the cache; then
   the mix's own traffic from a fixed warm-up seed until the engine is
   in steady state;
2. the window, ``--seconds`` long: the harness's loop releases each
   request at its due time through ``DriftScheduler.submit`` and calls
   ``ServingEngine.step(now)`` with ``now`` on the wall clock. An
   observer of the engine's trace stamps each token with
   ``time.perf_counter()`` as the engine emits it, which is after the
   token has been read back from the device;
3. the check, after the window, with the engine's state freed: a
   sample of the requests finished in the window is run through the
   plain float32 reference, and the widest gap by which a served
   token's reference logit lies below the reference's best is held to
   the configuration's limit.

``run`` returns the result line; ``run.py`` prints it.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reduce as breduce  # noqa: E402
from traffic import generator as gen  # noqa: E402

WARMUP_SPAN = "bench.warmup"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --- the cell, as the files describe it --------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    family: ModuleType           # the configuration's family module
    mix: dict
    metrics: List[dict]          # end-to-end metrics this cell reports
    layer_metrics: List[dict]    # per-layer metrics this cell reports


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(root: Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, ())]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), conf,
                family(root, conf.get("family", "dense")), mix, e2e, layer)


@functools.lru_cache(maxsize=None)
def _module(path: Path) -> ModuleType:
    """The module at ``path``, loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(root: Path, name: str) -> ModuleType:
    """The family module ``bench/families/<name>.py``: ``shapes(conf)``,
    ``model_config(conf, shapes)``, ``init_params(conf, seed, device)``
    and ``logits(conf, params, tokens, *, low, pad_to)``.

    It is imported as ``families.<name>`` from ``root``'s
    ``bench/families``, once per process, so that a family that builds
    on another (``from families import dense``) gets the very module the
    harness runs, with the programs it has compiled."""
    where = str(root.resolve() / "bench" / "families")
    pkg = sys.modules.get("families")
    if pkg is None or list(getattr(pkg, "__path__", ())) != [where]:
        for key in [k for k in sys.modules
                    if k == "families" or k.startswith("families.")]:
            del sys.modules[key]
        pkg = ModuleType("families")
        pkg.__path__ = [where]
        sys.modules["families"] = pkg
    return importlib.import_module(f"families.{name}")


def reader(root: Path, metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return _module(root / "bench" / "metrics" / f"{metric}.py").read


# --- records the readers read -------------------------------------------------

@dataclass
class ReqRec:
    """One request as the harness saw it. Times are seconds on the run's
    clock, whose zero is the end of the warm-up."""

    spec: gen.Spec
    due: float
    bucket: int
    prompt_len: int
    in_window: bool
    req: object = None                    # the program's Request
    next_q0: int = 0                      # where its next prefill starts
    remaining: int = 0
    stamps: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: Optional[float] = None
    client: Optional[int] = None

    @property
    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclass
class StepRec:
    """One ``ServingEngine.step``. ``gauges`` holds every ``gauge`` event
    the program emitted during the step, by its ``name``: the last value
    of that name, a number or a list of numbers, as emitted."""

    start: float
    end: float = 0.0
    launches: int = 0                     # n_prefill_launches delta
    prefill: List[tuple] = field(default_factory=list)   # (q0, c, n_real)
    decode: List[int] = field(default_factory=list)      # kv_len per token
    gauges: Dict[str, object] = field(default_factory=dict)


@dataclass
class Run:
    """Everything a metric reader may read."""

    t0: float                             # the window is [t0, t1)
    t1: float
    setup_s: float
    requests: List[ReqRec]
    steps: List[StepRec]
    shapes: dict
    peaks: dict
    total_pages: int
    open_loop: bool
    prefix_tokens_saved: int
    memory_peak_bytes: Optional[int]
    device: Optional[breduce.Reduced] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 <= t < self.t1

    @property
    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps if self.in_window(s.start)]

    @property
    def due_in_window(self) -> List[ReqRec]:
        return [r for r in self.requests if r.in_window]


# --- the engine under test --------------------------------------------------

def bucket_of(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def prompt_ids(spec: gen.Spec, bucket: int, vocab: int,
               prefix_cache: bool) -> np.ndarray:
    """The token ids the engine serves for ``spec`` in ``bucket``
    positions: a request's prompt bytes right-aligned behind zero
    padding or, where a prefix cache serves a shared prefix, a
    content-hashed group prefix followed by the prompt bytes repeated."""
    import zlib
    v = max(vocab - 1, 1)
    n = min(max(spec.prompt_tokens, 1), bucket)
    shared = (min(spec.shared_prefix_tokens, n)
              if spec.prefix_group is not None else 0)
    ids = np.zeros(bucket, np.int64)
    if prefix_cache and shared > 0:
        seed = zlib.crc32(repr(spec.prefix_group).encode())
        ids[:] = (seed + np.arange(bucket, dtype=np.int64) * 2654435761
                  ) % v + 1
        body = np.frombuffer(spec.prompt.encode() or b"\x01",
                             np.uint8).astype(np.int64)
        if bucket > shared:
            ids[shared:] = np.resize(body, bucket - shared) % v + 1
    else:
        body = np.frombuffer(spec.prompt.encode()[:n * 4],
                             np.uint8)[:n].astype(np.int64)
        if len(body):
            ids[-len(body):] = body % v + 1
    return ids.astype(np.int32)


class Tap:
    """Observer of the engine's trace: stamps tokens on the wall clock
    and files prefill launches, decode contexts and every gauge under
    the step in progress."""

    def __init__(self, clock, recs: Dict[int, ReqRec]) -> None:
        self.clock = clock
        self.recs = recs
        self.step: Optional[StepRec] = None
        self.completed: List[ReqRec] = []

    def on_event(self, ev) -> None:
        kind = ev.kind
        rec = self.recs.get(ev.req_id)
        if kind in ("first_token", "decode_step"):
            rec.stamps.append(self.clock())
            rec.tokens.append(int(ev.data["token"]))
            if kind == "decode_step" and self.step is not None:
                # the token at depth n attends the prompt's bucket and
                # every token before it, itself included
                self.step.decode.append(rec.bucket + ev.data["n"] - 1)
        elif kind == "prefix_hit":
            rec.next_q0 = int(ev.data["tokens"])
            rec.remaining = rec.prompt_len - rec.next_q0
        elif kind == "prefill_chunk":
            take = int(ev.data["tokens"])
            rec.remaining -= take
            q0 = rec.next_q0
            c = take if rec.remaining > 0 else rec.bucket - q0
            rec.next_q0 = q0 + c
            if self.step is not None:
                self.step.prefill.append((q0, c, take))
        elif kind == "complete":
            rec.done = self.clock()
            self.completed.append(rec)
        elif kind == "gauge" and self.step is not None:
            self.step.gauges[ev.data["name"]] = ev.data["value"]


# the keys of a mix's ``engine`` block that the harness reads itself
ENGINE_KEYS = ("max_len", "page_size", "prompt_buckets",
               "chunk_prefill_tokens", "prefix_cache_pages")


def engine_config(e: dict, kv_budget_tokens: int):
    """The ``EngineConfig`` of a mix's ``engine`` block: the paged engine
    with as many slots of ``max_len`` as the configuration's KV budget
    holds beside the prefix cache's pages. Any other key of the block is
    an ``EngineConfig`` field, passed on by name."""
    from repro.serving.engine import EngineConfig
    max_len, page = int(e["max_len"]), int(e["page_size"])
    prefix_pages = int(e.get("prefix_cache_pages", 0))
    own = dict(n_slots=(kv_budget_tokens - prefix_pages * page) // max_len,
               max_len=max_len, prompt_buckets=tuple(e["prompt_buckets"]),
               paged=True, page_size=page,
               chunk_prefill_tokens=e.get("chunk_prefill_tokens"),
               prefix_cache=prefix_pages > 0, prefix_cache_pages=prefix_pages)
    rest = {k: v for k, v in e.items() if k not in ENGINE_KEYS}
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    bad = sorted(k for k in rest if k not in fields or k in own)
    if bad:
        raise ValueError(f"engine keys {bad} are not EngineConfig fields "
                         f"that a mix may set")
    return EngineConfig(**own, **rest)


class Served:
    """The system under test: the serving engine with its scheduler,
    built from the configuration, its family and the mix."""

    def __init__(self, cell: Cell, conf: dict, seed: int, device,
                 clock) -> None:
        from repro.core.estimator import DriftConfig
        from repro.core.scheduler import DriftScheduler
        from repro.obs import TraceRecorder
        from repro.serving.engine import ServingEngine

        fam = cell.family
        self.shapes = fam.shapes(conf)
        sc = cell.mix["scheduler"]
        ec = engine_config(cell.mix["engine"],
                           conf["serving"]["kv_budget_tokens"])
        self.buckets = ec.prompt_buckets
        self.max_len = ec.max_len
        self.prefix_cache = ec.prefix_cache
        self.cfg = fam.model_config(conf, self.shapes)
        self.params = fam.init_params(conf, seed, device)
        self.recs: Dict[int, ReqRec] = {}
        self.tap = Tap(clock, self.recs)
        sched = DriftScheduler(
            policy=sc["policy"],
            config=DriftConfig(bias_enabled=bool(sc["bias_enabled"])),
            max_new_per_step=sc.get("max_new_per_step"))
        self.engine = ServingEngine(
            self.cfg, self.params, sched, ec,
            trace=TraceRecorder(capacity=1, observers=[self.tap]),
            device=device)
        self.n_slots = ec.n_slots
        self.total_pages = self.engine.alloc.n_pages
        self.pages_per_seq = self.engine.alloc.pages_per_seq
        self.page = ec.page_size

    # -- requests ----------------------------------------------------------
    def submit(self, spec: gen.Spec, due: float, in_window: bool,
               client: Optional[int] = None) -> ReqRec:
        from repro.core.request import Category, Request, TenantTier
        req = Request(tenant=TenantTier(spec.tenant),
                      category=Category(spec.category), prompt=spec.prompt,
                      prompt_tokens=spec.prompt_tokens,
                      max_tokens=spec.max_tokens,
                      true_output_tokens=spec.output_tokens,
                      prefix_group=spec.prefix_group,
                      shared_prefix_tokens=spec.shared_prefix_tokens)
        n = max(spec.prompt_tokens, 1)
        b = bucket_of(n, self.buckets)
        rec = ReqRec(spec=spec, due=due, bucket=b, prompt_len=min(n, b),
                     in_window=in_window, req=req, client=client)
        rec.remaining = rec.prompt_len
        self.recs[req.req_id] = rec
        self.engine.sched.submit(req, due)
        return rec

    def busy(self) -> bool:
        return bool(self.engine.sched.queue_depth()
                    or self.engine.active_slots())

    def step(self, now: float, clock) -> StepRec:
        rec = StepRec(start=now)
        self.tap.step = rec
        before = self.engine.n_prefill_launches
        self.engine.step(now)
        rec.launches = self.engine.n_prefill_launches - before
        rec.end = clock()
        self.tap.step = None
        return rec

    # -- programs ------------------------------------------------------------
    def decode_variants(self) -> List[int]:
        """The decode programs' page-grid sizes the window can reach: the
        engine trims the grid to the power of two above the deepest live
        sequence, clamped to the table width."""
        lo = -(-self.buckets[0] // self.page)
        out = set()
        for need in range(lo, self.pages_per_seq + 1):
            m = 1
            while m < need:
                m *= 2
            out.add(min(m, self.pages_per_seq))
        return sorted(out)

    def compile_decode(self) -> None:
        """Run every reachable decode program once, on inactive lanes
        that write only the pool's scratch page."""
        import jax
        import jax.numpy as jnp
        eng = self.engine
        n, pps = self.n_slots, self.pages_per_seq
        table = np.full((n, pps), eng.pool.n_pages - 1, np.int32)
        zeros = np.zeros((n,), np.int32)
        for m in self.decode_variants():
            toks, _ = eng._decode_paged(
                eng.params, {"k": eng.pool.k, "v": eng.pool.v},
                jnp.asarray(zeros), jnp.asarray(table), jnp.asarray(zeros),
                jax.random.PRNGKey(0), max_pages=m)
            np.asarray(toks)

    def release(self) -> None:
        """Free the engine's device state (the KV pool); the weights stay
        for the reference."""
        self.engine.pool = None
        self.engine = None
        gc.collect()


# --- traffic sources --------------------------------------------------------

class OpenLoop:
    def __init__(self, plan, in_window: bool) -> None:
        self.plan = plan                 # [(due, spec)], due-ordered
        self.i = 0
        self.in_window = in_window
        self.late: List[float] = []      # release time minus due time

    def release(self, served: Served, now: float) -> None:
        while self.i < len(self.plan) and self.plan[self.i][0] <= now:
            due, spec = self.plan[self.i]
            served.submit(spec, due, self.in_window)
            self.late.append(now - due)
            self.i += 1

    def next_due(self) -> float:
        return self.plan[self.i][0] if self.i < len(self.plan) else math.inf


class ClosedLoop:
    """Clients that each send their next request when the previous one
    completes; ``phase`` switches the lists they draw from."""

    def __init__(self, queues) -> None:
        self.queues = [list(q) for q in queues]
        self.pos = [0] * len(queues)
        self.ready = list(range(len(queues)))
        self.ready_at = [None] * len(queues)
        self.in_window = False
        self.seen = 0
        self.late: List[float] = []

    def switch(self, queues) -> None:
        """Draw from ``queues`` from now on; a client that had run out of
        requests sends its next one at once."""
        self.queues = [list(q) for q in queues]
        self.pos = [0] * len(queues)
        for c in self.ready:
            self.ready_at[c] = None
        self.in_window = True

    def release(self, served: Served, now: float) -> None:
        for rec in served.tap.completed[self.seen:]:
            if rec.client is not None:
                self.ready.append(rec.client)
                self.ready_at[rec.client] = rec.done
        self.seen = len(served.tap.completed)
        parked = []
        for c in self.ready:
            if self.pos[c] >= len(self.queues[c]):
                parked.append(c)           # waits for the next phase's list
                continue
            spec = self.queues[c][self.pos[c]]
            self.pos[c] += 1
            due = now if self.ready_at[c] is None else self.ready_at[c]
            served.submit(spec, due, self.in_window, client=c)
            if self.in_window:
                self.late.append(now - due)
        self.ready = parked

    def next_due(self) -> float:
        return math.inf


# --- compile counting --------------------------------------------------------

class CompileCount:
    """Programs XLA compiled, and programs the persistent cache served,
    from JAX's own monitoring events. JAX times every request for a
    program under one event, whether the cache serves it or XLA compiles
    it, so a compile is a request that was not a hit."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event == self.REQUEST:
            self.requests += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1

    def snapshot(self) -> tuple:
        """(compiled, served from the cache) so far."""
        return self.requests - self.hits, self.hits


# --- the run --------------------------------------------------------------

def use_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path in the
    checkout; every program is written to it, however fast it
    compiled."""
    import jax
    path = root / ".jax_cache" / "bench"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_device(cell: Cell, smoke: bool):
    import jax
    devs = jax.devices()
    if not smoke and (devs[0].platform == "cpu" or len(devs) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} accelerator chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} device(s)")
    return devs[0]


def coverage_specs(served: Served, mix: dict) -> List[List[gen.Spec]]:
    """Warm-up rounds, served one after another, that reach every
    program the mix can call. Without a prefix cache: one request per
    prompt bucket. With one: first a request made of exactly the shared
    prefix for every prefix group of the mix, which leaves every group's
    prefix resident for the rest of the run, then one request per bucket
    longer than the prefix, each resuming from resident pages."""
    g = mix["generator"]
    shared = int(g.get("shared_prefix_tokens", 0))
    base = gen.draw_specs(mix, len(served.buckets), salt=7)[0]
    short = dataclasses.replace(base, output_tokens=2)
    if not (served.prefix_cache and shared):
        return [[dataclasses.replace(short, prompt_tokens=b)
                 for b in served.buckets]]
    groups = [(t, j) for t in gen.TIER_LABELS
              for j in range(int(g["prefix_groups_per_tenant"]))]
    prime = [dataclasses.replace(short, prompt_tokens=shared, prefix_group=k)
             for k in groups]
    resume = [dataclasses.replace(short, prompt_tokens=b,
                                  prefix_group=groups[i % len(groups)])
              for i, b in enumerate(b for b in served.buckets if b > shared)]
    return [prime, resume]


def drive(served: Served, source, clock, t_end: float, steps: List[StepRec],
          annotate) -> None:
    """The serving loop until ``t_end``: release what is due, step the
    engine while it has work, else wait for the next arrival."""
    while True:
        now = clock()
        if now >= t_end:
            # what fell due before the close counts as attempted
            source.release(served, now)
            return
        with annotate("bench.release"):
            source.release(served, now)
        if served.busy():
            with annotate("bench.step"):
                steps.append(served.step(now, clock))
        else:
            wait = min(source.next_due(), t_end) - now
            if wait > 0:
                with annotate("bench.wait"):
                    time.sleep(wait)


def drain(served: Served, clock, steps: List[StepRec], annotate,
          limit_s: float = 600.0) -> None:
    stop = clock() + limit_s
    while served.busy() and clock() < stop:
        with annotate("bench.step"):
            steps.append(served.step(clock(), clock))


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_process: float, smoke: bool = False, log=sys.stderr) -> dict:
    """One run of ``workload``; returns the result line's object. With
    ``smoke``, a rehearsal: the configuration's small widths on whatever
    device JAX has, with no compile cache and no chip's peaks."""
    cell = load_cell(root, workload)
    conf = dict(cell.conf)
    if smoke:
        conf.update(conf["smoke"])
    import jax
    if not smoke:
        use_compile_cache(root)
    device = find_device(cell, smoke)
    counter = CompileCount()
    peaks = {}
    if not smoke:
        kinds = json.loads((BENCH / "peaks.json").read_text())["devices"]
        if device.device_kind not in kinds:
            raise SystemExit(f"no peaks for device {device.device_kind!r} "
                             f"in bench/peaks.json")
        peaks = kinds[device.device_kind]

    mix = cell.mix
    warm = mix["warmup"]
    w_s = float(warm["seconds"])
    # one clock for the whole run: the warm-up traffic runs at [-w_s, 0)
    origin = time.perf_counter() + 1e9

    def clock() -> float:
        return time.perf_counter() - origin

    annotate = jax.profiler.TraceAnnotation
    served = Served(cell, conf, seed, device, clock)
    jax.block_until_ready(served.params)
    t_built = time.perf_counter() - t_process

    # every program the window can call, then the mix's own traffic
    served.compile_decode()
    steps: List[StepRec] = []
    for specs in coverage_specs(served, mix):
        for spec in specs:
            served.submit(spec, clock(), False)
        drain(served, clock, steps, annotate)
    t_compiled = time.perf_counter() - t_process
    closed = mix["arrivals"]["process"] == "closed"
    if closed:
        n_clients = int(mix["arrivals"]["clients_per_slot"]) * served.n_slots
        per_client = int(mix["arrivals"]["requests_per_client_per_s"]
                         * (w_s + seconds)) + 4
        source = ClosedLoop(gen.closed_loop(mix, int(warm["seed"]),
                                            n_clients, per_client, salt=1))
    else:
        source = OpenLoop([(t - w_s, s) for t, s in gen.open_loop(
            mix, int(warm["seed"]), w_s, salt=1)], False)
    origin = time.perf_counter() + w_s
    # an open loop starts from what it holds in flight at steady state,
    # so that a short warm-up reaches it
    for spec in gen.in_flight(mix, int(warm.get("in_flight", 0)), salt=3):
        served.submit(spec, clock(), False)
    with annotate(WARMUP_SPAN):
        drive(served, source, clock, 0.0, steps, annotate)

    # the window: [t0, t0 + seconds) on the run's clock
    if trace:
        trace_dir = root / ".bench_trace" / f"{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    gc.collect()
    gc.freeze()
    t0 = clock()
    if closed:
        source.switch(gen.closed_loop(mix, seed, n_clients, per_client))
    else:
        source = OpenLoop([(t0 + t, s) for t, s in
                           gen.open_loop(mix, seed, seconds)], True)
    setup_s = origin + t0 - t_process
    before = counter.snapshot()
    n_steps_before = len(steps)
    saved_before = served.engine.prefix_tokens_saved
    with annotate("bench.window"):
        drive(served, source, clock, t0 + seconds, steps, annotate)
    closed_at = clock()
    in_window = tuple(a - b for a, b in zip(counter.snapshot(), before))
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    stats = None if smoke else device.memory_stats()
    memory_peak = int(stats["peak_bytes_in_use"]) if stats else None

    print(f"set-up: built in {t_built:.3f} s, programs ready at "
          f"{t_compiled:.3f} s, window opened at {setup_s:.3f} s "
          f"({n_steps_before} steps before it)", file=log)
    print(f"compiles: {before[0]} compiled and {before[1]} from the "
          f"persistent cache in set-up; {in_window[0]} compiled and "
          f"{in_window[1]} from the cache inside the window", file=log)
    late = np.asarray(source.late or [0.0])
    print(f"generator: {len(source.late)} releases in the window, late by "
          f"mean {late.mean() * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms; "
          f"the loop ended {(closed_at - t0 - seconds) * 1e3:.3f} ms after "
          f"the window closed", file=log)

    rec_run = Run(t0=t0, t1=t0 + seconds, setup_s=setup_s,
                  requests=list(served.recs.values()),
                  steps=steps[n_steps_before:], shapes=served.shapes,
                  peaks=peaks, total_pages=served.total_pages,
                  open_loop=not closed,
                  prefix_tokens_saved=(served.engine.prefix_tokens_saved
                                       - saved_before),
                  memory_peak_bytes=memory_peak)
    breakdown = None
    if trace:
        t = time.perf_counter()
        rec_run.device = breduce.reduce_profile(
            breduce.load(str(trace_dir)),
            **(breduce.CPU_VIEW if smoke else {}))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: read and reduced in {time.perf_counter() - t:.3f} s",
              file=log)
        breakdown = {"device_ops": rec_run.device.top_ops(),
                     "idle_gaps": rec_run.device.top_gaps()}

    # the check: the engine's state freed, the weights kept
    finished = [r for r in served.tap.completed if rec_run.in_window(r.done)]
    params = served.params
    served.release()
    checks, correct = check(cell.family, conf, mix, params, finished, seed,
                            served.prefix_cache, served.max_len, log)

    metrics = {}
    for m in (cell.layer_metrics if trace else cell.metrics):
        value = reader(root, m["name"])(rec_run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if trace:
        dev["busy_s"] = rec_run.device.busy_s
        dev["window_s"] = rec_run.device.window_s
    out = {"correct": correct,
           "attempted": sum(r.in_window for r in rec_run.requests),
           "failed": 0, "metrics": metrics, "device": dev}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


# --- the check ---------------------------------------------------------------

def sample(finished: List[ReqRec], seed: int, k: int) -> List[ReqRec]:
    """``k`` finished requests drawn from the seed, the one with the
    most served tokens among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), -r.due))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed, 99])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps_of(fam, conf: dict, params, rec: ReqRec, vocab: int,
            prefix_cache: bool, pad_to: int, *,
            control: bool = False) -> np.ndarray:
    """Per served token: how far the family's reference logit of the
    token lies below the reference's best. With ``control``, of the token
    that the family's control puts first at the same position."""
    ids = prompt_ids(rec.spec, rec.bucket, vocab, prefix_cache)
    seq = np.concatenate([ids, np.asarray(rec.tokens[:-1], np.int32)])
    rows = slice(rec.bucket - 1, rec.bucket - 1 + len(rec.tokens))
    ref = fam.logits(conf, params, seq, pad_to=pad_to)[rows]
    if control:
        low = fam.logits(conf, params, seq, low=True, pad_to=pad_to)[rows]
        picked = low.argmax(-1)
    else:
        picked = np.asarray(rec.tokens)
    return ref.max(-1) - ref[np.arange(len(picked)), picked]


def check(fam, conf: dict, mix: dict, params, finished: List[ReqRec],
          seed: int, prefix_cache: bool, max_len: int, log) -> tuple:
    """Hold the served tokens of a seeded sample of finished requests to
    the family's plain reference."""
    limit = float(conf["check"]["max_logit_gap"])
    vocab = fam.shapes(conf)["V"]
    picked = sample(finished, seed, int(mix["check"]["sample"]))
    # a request whose token count disagrees with what the engine
    # reported, or that holds a token outside the vocabulary
    bad = sum(len(r.tokens) != r.req.observed_output_tokens
              or not all(0 <= t < vocab for t in r.tokens) for r in picked)
    widest = 0.0
    n_tokens = 0
    t = time.perf_counter()
    for r in picked:
        if bad:
            break
        g = gaps_of(fam, conf, params, r, vocab, prefix_cache, max_len)
        widest = max(widest, float(g.max()))
        n_tokens += len(g)
    ok = bool(picked) and bad == 0 and widest <= limit
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "requests_compared": {"value": len(picked), "limit": 1},
        "bad_served_tokens": {"value": bad, "limit": 0},
    }
    print(f"check: {len(picked)} requests, {n_tokens} served tokens against "
          f"the float32 reference in {time.perf_counter() - t:.3f} s", file=log)
    return checks, ok
