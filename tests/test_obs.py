"""Observability layer: trace recorder semantics, streaming P²/window
aggregates, SLO burn-rate monitors, lifecycle validation, Chrome-trace
export, and — the load-bearing guarantee — traced runs bit-identical
to untraced runs on every execution surface (worker simulator, cluster
simulator, and the live JAX engine)."""

import json
import math
import random
from dataclasses import dataclass

import pytest

from repro.cluster import (ClusterConfig, ClusterSimulator, GlobalAdmission,
                           RoleAutoscaler, RoleAutoscalerConfig)
from repro.core.estimator import DriftConfig
from repro.core.scheduler import DriftScheduler
from repro.obs import (DEFAULT_SAMPLE_EVERY, NULL_RECORDER, P2Quantile,
                       SeriesBank, SlidingWindow, SloMonitor, SloTarget,
                       StreamSummary, TraceEvent, TraceRecorder,
                       get_recorder, percentile, resolve_recorder,
                       set_recorder, to_chrome_trace, validate_chrome_trace,
                       validate_lifecycles, write_chrome_trace)
from repro.obs import events as tr
from repro.serving.cost_model import L4_MAX_DRIVEN
from repro.serving.simulator import SimConfig, WorkerSimulator
from repro.workload.generator import (GeneratorConfig, WorkloadGenerator,
                                      cluster_stress_config)

# full fidelity: every decode step and gauge lands in the ring, so
# lifecycle chains are complete and validatable
FULL = {"decode_step": 1, "gauge": 1}


# --- recorder ----------------------------------------------------------

def test_emit_records_and_counts():
    rec = TraceRecorder()
    rec.emit(1.0, tr.ARRIVE, req_id=7, tenant="premium")
    rec.emit(2.0, tr.COMPLETE, req_id=7, tenant="premium", e2e=1.0)
    evs = rec.events()
    assert [e.kind for e in evs] == ["arrive", "complete"]
    assert evs[0].seq == 0 and evs[1].seq == 1
    assert evs[1].data == {"e2e": 1.0}
    s = rec.stats()
    assert s["emitted"] == 2 and s["recorded"] == 2
    assert s["by_kind"] == {"arrive": 1, "complete": 1}
    assert rec.last_ts == 2.0


def test_unknown_kind_rejected():
    rec = TraceRecorder()
    with pytest.raises(ValueError, match="unknown event kind"):
        rec.emit(0.0, "no_such_kind")
    with pytest.raises(ValueError, match="unknown event kind"):
        TraceRecorder(sample_every={"no_such_kind": 2})
    with pytest.raises(ValueError):
        TraceRecorder(sample_every={tr.DECODE_STEP: 0})
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_stride_sampling_is_counter_based():
    rec = TraceRecorder(sample_every={tr.DECODE_STEP: 4})
    for i in range(10):
        rec.emit(float(i), tr.DECODE_STEP, req_id=1)
    # emissions 0, 4, 8 recorded (first always lands)
    assert [e.ts for e in rec.events()] == [0.0, 4.0, 8.0]
    s = rec.stats()
    assert s["by_kind"]["decode_step"] == 10       # emitted, pre-sampling
    assert s["recorded"] == 3
    # unlisted kinds record 1:1 regardless of the default strides
    assert rec.sample_every[tr.GAUGE] == DEFAULT_SAMPLE_EVERY[tr.GAUGE]


def test_ring_overflow_drops_oldest():
    rec = TraceRecorder(capacity=10)
    for i in range(25):
        rec.emit(float(i), tr.ARRIVE, req_id=i)
    evs = rec.events()
    assert len(evs) == 10
    assert [e.req_id for e in evs] == list(range(15, 25))
    assert rec.stats()["dropped_overflow"] == 15


def test_observers_see_every_emission_pre_sampling():
    seen = []

    class Spy:
        def on_event(self, e):
            seen.append(e.kind)

    rec = TraceRecorder(sample_every={tr.DECODE_STEP: 100}, observers=(Spy(),))
    for i in range(10):
        rec.emit(float(i), tr.DECODE_STEP, req_id=1)
    assert len(seen) == 10                 # observer: all emissions
    assert len(rec.events()) == 1          # ring: strided


def test_null_recorder_is_inert():
    assert NULL_RECORDER.enabled is False
    NULL_RECORDER.emit(0.0, "anything_goes", bogus=1)   # never raises
    assert NULL_RECORDER.events() == []
    assert NULL_RECORDER.stats()["emitted"] == 0
    assert NULL_RECORDER.begin_segment("x") == 0


def test_global_recorder_plumbing():
    assert get_recorder() is NULL_RECORDER
    rec = TraceRecorder()
    try:
        assert set_recorder(rec) is rec
        assert get_recorder() is rec
        # resolve: explicit wins, None falls back to the global
        other = TraceRecorder()
        assert resolve_recorder(other) is other
        assert resolve_recorder(None) is rec
        # components resolve at construction time
        sim = WorkerSimulator(DriftScheduler(), config=SimConfig())
        assert sim.trace is rec
    finally:
        set_recorder(None)
    assert get_recorder() is NULL_RECORDER
    sim = WorkerSimulator(DriftScheduler(), config=SimConfig())
    assert sim.trace is NULL_RECORDER


def test_begin_segment_stamps_events():
    rec = TraceRecorder()
    rec.emit(0.0, tr.ARRIVE, req_id=1)
    rec.begin_segment("arm_a")
    rec.emit(1.0, tr.ARRIVE, req_id=2)
    rec.begin_segment("arm_b")
    rec.emit(2.0, tr.ARRIVE, req_id=3)
    assert [e.seg for e in rec.events()] == [0, 1, 2]
    assert rec.stats()["segments"] == ["arm_a", "arm_b"]


# --- P² quantiles & windows --------------------------------------------

def test_p2_exact_for_small_n():
    q = P2Quantile(0.5)
    assert math.isnan(q.value())
    for x in (5.0, 1.0, 3.0):
        q.add(x)
    assert q.value() == pytest.approx(percentile([5.0, 1.0, 3.0], 50))


def test_p2_tracks_exact_within_sample_range_bound():
    """The accuracy contract docs/observability.md documents: on the
    unimodal latency-like distributions used here, P² estimates stay
    within 5% of the sample range of the exact percentile."""
    rng = random.Random(0)
    for p in (0.50, 0.95, 0.99):
        for dist in ("lognormal", "uniform", "exponential"):
            xs = []
            q = P2Quantile(p)
            for _ in range(5000):
                if dist == "lognormal":
                    x = rng.lognormvariate(0.0, 0.7)
                elif dist == "uniform":
                    x = rng.uniform(0.0, 10.0)
                else:
                    x = rng.expovariate(0.5)
                xs.append(x)
                q.add(x)
            exact = percentile(xs, p * 100.0)
            bound = 0.05 * (max(xs) - min(xs))
            assert abs(q.value() - exact) <= bound, \
                f"P²({p}) on {dist}: {q.value():.4f} vs exact " \
                f"{exact:.4f} (bound {bound:.4f})"


def test_p2_rejects_bad_quantile():
    with pytest.raises(ValueError):
        P2Quantile(0.0)
    with pytest.raises(ValueError):
        P2Quantile(1.0)


def test_stream_summary_mirrors_latency_stats_keys():
    s = StreamSummary()
    empty = s.as_dict()
    assert empty["n"] == 0 and math.isnan(empty["mean"])
    for x in range(1, 101):
        s.add(float(x))
    d = s.as_dict()
    assert d["n"] == 100
    assert d["mean"] == pytest.approx(50.5)
    assert d["min"] == 1.0 and d["max"] == 100.0
    assert d["p50"] == pytest.approx(percentile(
        [float(x) for x in range(1, 101)], 50), rel=0.05)
    assert set(d) >= {"n", "mean", "p50", "p95", "p99"}


def test_sliding_window_trims_and_rates():
    w = SlidingWindow(10.0)
    for t in range(20):
        w.add(float(t))
    assert w.count(19.0) == 11            # ts in [9, 19] survive the cutoff
    assert w.rate(19.0) == pytest.approx(1.1)
    assert w.mean(19.0) == pytest.approx(1.0)
    assert w.count(100.0) == 0
    assert math.isnan(w.mean(100.0))
    with pytest.raises(ValueError):
        SlidingWindow(0.0)


def test_series_bank_aggregates_from_events():
    bank = SeriesBank(window=60.0)
    rec = TraceRecorder(observers=(bank,))
    for i in range(10):
        t = float(i)
        rec.emit(t, tr.ARRIVE, req_id=i, tenant="standard")
        rec.emit(t + 0.1, tr.PREFIX_HIT if i % 2 else tr.PREFIX_MISS,
                 req_id=i)
        rec.emit(t + 0.2, tr.DRIFT, req_id=i, abs_error=2.0)
        rec.emit(t + 0.5, tr.COMPLETE, req_id=i, tenant="standard",
                 e2e=0.5, ttft=0.2, inter_token=0.01)
    rec.emit(9.9, tr.GAUGE, name="queue_depth", value=3)
    snap = bank.snapshot()
    assert snap["e2e"]["n"] == 10
    assert snap["ttft"]["mean"] == pytest.approx(0.2)
    assert snap["windowed"]["drift_mae"] == pytest.approx(2.0)
    assert bank.prefix_hit_rate() == pytest.approx(0.5)
    assert snap["gauges"]["queue_depth"]["value"] == 3
    assert snap["windowed"]["arrival_rate"] == pytest.approx(10 / 60.0)


# --- SLO monitors ------------------------------------------------------

def _mon(**kw):
    return SloMonitor(targets={"premium": SloTarget(ttft=1.0, e2e=10.0,
                                                    attainment=0.90)},
                      windows=(60.0, 600.0), **kw)


def test_slo_ok_warn_page_transitions():
    # budget = 0.10: warn needs >=10% violating, page needs >=60%
    mon = _mon()
    assert mon.status(0.0)["premium"]["state"] == "ok"   # no data = ok
    for i in range(100):
        mon.observe("premium", float(i) * 0.1, e2e=5.0)  # all meeting
    assert mon.status()["premium"]["state"] == "ok"
    mon2 = _mon()
    for i in range(100):                     # 20% violating -> warn
        mon2.observe("premium", float(i) * 0.1,
                     e2e=20.0 if i % 5 == 0 else 5.0)
    st = mon2.status()["premium"]
    assert st["state"] == "warn"
    assert st["metrics"]["e2e"]["burn"]["60s"] == pytest.approx(2.0)
    mon3 = _mon()
    for i in range(100):                     # all violating -> page
        mon3.observe("premium", float(i) * 0.1, e2e=99.0)
    assert mon3.status()["premium"]["state"] == "page"


def test_slo_multi_window_and_resists_blips():
    """A recent burst of violations pages only if the long window
    agrees — the classic multi-window AND."""
    mon = _mon()
    for i in range(50):                      # 500s of healthy traffic
        mon.observe("premium", float(i) * 10.0, e2e=5.0)
    for i in range(20):                      # then a 20-request blip
        mon.observe("premium", 500.0 + i * 0.1, e2e=99.0)
    st = mon.status()["premium"]["metrics"]["e2e"]
    assert st["burn"]["60s"] >= 6.0          # short window is on fire
    assert st["burn"]["600s"] < 6.0          # long window says blip
    assert mon.status()["premium"]["state"] != "page"


def test_slo_monitor_consumes_complete_events():
    mon = _mon()
    rec = TraceRecorder(observers=(mon,))
    rec.emit(1.0, tr.COMPLETE, req_id=1, tenant="premium",
             ttft=5.0, e2e=99.0)
    rec.emit(1.1, tr.COMPLETE, req_id=2, tenant="unknown_tier",
             ttft=5.0, e2e=99.0)             # no target: ignored
    rec.emit(1.2, tr.ARRIVE, req_id=3, tenant="premium")
    st = mon.status()["premium"]
    assert st["metrics"]["ttft"]["n"] == 1
    assert st["state"] == "page"             # 1/1 violating both windows


def test_slo_target_validation():
    with pytest.raises(ValueError):
        SloTarget(ttft=1.0, e2e=1.0, attainment=1.0)
    with pytest.raises(ValueError):
        SloMonitor(windows=())


# --- lifecycle grammar -------------------------------------------------

def _ev(seq, ts, kind, req_id=1, **data):
    return TraceEvent(seq=seq, ts=ts, kind=kind, req_id=req_id, data=data)


def test_validate_accepts_wellformed_chain():
    evs = [_ev(0, 0.0, tr.ARRIVE), _ev(1, 0.0, tr.ADMIT),
           _ev(2, 0.1, tr.ROUTE), _ev(3, 0.2, tr.PREFILL_CHUNK),
           _ev(4, 0.3, tr.FIRST_TOKEN), _ev(5, 0.4, tr.DECODE_STEP),
           _ev(6, 0.5, tr.COMPLETE)]
    assert validate_lifecycles(evs) == []


def test_validate_catches_violations():
    # starts without arrive
    assert validate_lifecycles([_ev(0, 0.0, tr.ADMIT),
                                _ev(1, 0.1, tr.SHED)])
    # events after terminal
    assert validate_lifecycles([_ev(0, 0.0, tr.ARRIVE),
                                _ev(1, 0.1, tr.ADMIT),
                                _ev(2, 0.2, tr.COMPLETE),
                                _ev(3, 0.3, tr.DECODE_STEP)])
    # complete without admit
    assert validate_lifecycles([_ev(0, 0.0, tr.ARRIVE),
                                _ev(1, 0.1, tr.COMPLETE)])
    # timestamp regression
    assert validate_lifecycles([_ev(0, 1.0, tr.ARRIVE),
                                _ev(1, 0.5, tr.ADMIT),
                                _ev(2, 1.1, tr.COMPLETE)])
    # unterminated chain (only with require_terminal)
    open_chain = [_ev(0, 0.0, tr.ARRIVE), _ev(1, 0.1, tr.ADMIT)]
    assert validate_lifecycles(open_chain)
    assert validate_lifecycles(open_chain, require_terminal=False) == []
    # execution before the first route (route-ful stream)
    assert validate_lifecycles([
        _ev(0, 0.0, tr.ARRIVE), _ev(1, 0.0, tr.ADMIT),
        _ev(2, 0.1, tr.PREFILL_CHUNK), _ev(3, 0.2, tr.ROUTE),
        _ev(4, 0.3, tr.COMPLETE)])


def test_validate_prefill_after_first_token_needs_reset():
    bad = [_ev(0, 0.0, tr.ARRIVE), _ev(1, 0.0, tr.ADMIT),
           _ev(2, 0.1, tr.FIRST_TOKEN), _ev(3, 0.2, tr.PREFILL_CHUNK),
           _ev(4, 0.3, tr.COMPLETE)]
    assert validate_lifecycles(bad)
    ok = [_ev(0, 0.0, tr.ARRIVE), _ev(1, 0.0, tr.ADMIT),
          _ev(2, 0.1, tr.FIRST_TOKEN),
          _ev(3, 0.15, tr.PREEMPT, reason="worker_fail"),
          _ev(4, 0.2, tr.PREFILL_CHUNK), _ev(5, 0.3, tr.COMPLETE)]
    assert validate_lifecycles(ok) == []


# --- worker simulator: full-fidelity trace + bit-identity --------------

def _sim_run(trace=None, seed=1):
    gen = WorkloadGenerator(GeneratorConfig(
        total_requests=150, calibration_requests=50, seed=seed))
    plan = gen.plan(seed=seed)
    sched = DriftScheduler(policy="sjf", config=DriftConfig())
    sim = WorkerSimulator(sched, plan,
                          SimConfig(seed=seed, step_engine=True,
                                    chunk_prefill_tokens=256),
                          trace=trace)
    return sched, sim.run()


def _completion_tuples(sched):
    # req_ids come from a process-global counter, so they differ across
    # in-process runs; identity is over the physics, not the ids
    return [(r.completion_time, r.observed_output_tokens, r.tenant.label)
            for r in sched.completed]


def test_sim_trace_lifecycles_valid():
    rec = TraceRecorder(sample_every=FULL)
    sched, m = _sim_run(trace=rec)
    evs = rec.events()
    assert rec.stats()["dropped_overflow"] == 0
    assert validate_lifecycles(evs) == []
    kinds = {e.kind for e in evs}
    assert {"arrive", "admit", "prefill_chunk", "first_token",
            "decode_step", "complete", "drift", "gauge"} <= kinds
    completes = [e for e in evs if e.kind == tr.COMPLETE]
    assert len(completes) == m.n_completed == 150
    # COMPLETE payloads carry the honest latency anchors
    for e in completes:
        assert e.data["e2e"] >= e.data["ttft"] > 0


def test_sim_traced_identical_to_untraced():
    sched_a, m_a = _sim_run(trace=None)
    rec = TraceRecorder(sample_every=FULL)
    sched_b, m_b = _sim_run(trace=rec)
    assert rec.stats()["emitted"] > 0
    assert _completion_tuples(sched_a) == _completion_tuples(sched_b)
    assert m_a.as_dict() == m_b.as_dict()


def test_sim_observers_match_exact_metrics():
    bank = SeriesBank(window=1e9)            # window spans the whole run
    rec = TraceRecorder(sample_every={"decode_step": 64}, observers=(bank,))
    sched, m = _sim_run(trace=rec)
    snap = bank.snapshot()
    # streaming aggregates are exact despite ring thinning: the
    # observer saw every emission pre-sampling
    assert snap["e2e"]["n"] == m.n_completed
    assert snap["e2e"]["mean"] == pytest.approx(m.e2e.mean)
    # step-engine runs anchor TTFT for every request
    assert snap["ttft"]["n"] == m.n_completed
    # P² percentile within the documented 5%-of-range bound
    exact = [r.completion_time - r.arrival_time for r in sched.completed]
    bound = 0.05 * (max(exact) - min(exact))
    assert abs(snap["e2e"]["p95"] - percentile(exact, 95)) <= bound


# --- cluster simulator: full-feature trace + bit-identity --------------

def _cluster_run(trace=None, seed=2):
    gen = WorkloadGenerator(cluster_stress_config(4, seed=seed,
                                                  total_requests=300))
    plan = gen.plan(seed=seed)
    cfg = ClusterConfig(n_replicas=4, routing="pd_disaggregated",
                        step_engine=True, chunk_prefill_tokens=256,
                        work_stealing=True, fail_events=((5.0, 1),),
                        seed=seed)
    sim = ClusterSimulator(
        plan=plan, config=cfg, cost_model=L4_MAX_DRIVEN,
        admission=GlobalAdmission(),
        autoscaler=RoleAutoscaler(RoleAutoscalerConfig(max_replicas=6)),
        trace=trace)
    metrics = sim.run()
    done = []
    for rep in sim.replicas:
        done.extend(rep.sched.completed)
    done.sort(key=lambda r: (r.completion_time, r.observed_output_tokens))
    return sim, metrics, [(r.completion_time, r.observed_output_tokens,
                           r.tenant.label) for r in done]


def test_cluster_trace_lifecycles_valid_under_full_fire():
    """P/D disaggregation + work stealing + replica failure + admission
    + role autoscaling all emitting at once: every surviving chain must
    still parse as a legal lifecycle."""
    rec = TraceRecorder(sample_every=FULL)
    sim, metrics, _ = _cluster_run(trace=rec)
    evs = rec.events()
    assert rec.stats()["dropped_overflow"] == 0
    assert validate_lifecycles(evs) == []
    kinds = {e.kind for e in evs}
    assert {"arrive", "admit", "route", "handoff", "complete",
            "replica_fail", "replica_recover"} <= kinds
    # every handoff 'in' has a replica id; cluster-scope events don't
    for e in evs:
        if e.kind == tr.HANDOFF and e.data.get("edge") == "in":
            assert e.rid is not None
        if e.kind in (tr.SCALE_UP, tr.SCALE_DOWN):
            assert e.req_id is None


def test_cluster_traced_identical_to_untraced():
    _, m_a, tuples_a = _cluster_run(trace=None)
    rec = TraceRecorder(sample_every=FULL)
    _, m_b, tuples_b = _cluster_run(trace=rec)
    assert rec.stats()["emitted"] > 0
    assert tuples_a == tuples_b
    assert m_a.as_dict() == m_b.as_dict()


# --- live JAX engine: trace + bit-identity -----------------------------

def _engine_run(trace=None, seed=0):
    import jax

    from repro.configs import smoke_config
    from repro.models.registry import get_api
    from repro.serving.engine import EngineConfig, ServingEngine
    cfg = smoke_config("smollm-135m")
    api = get_api(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    sched = DriftScheduler(policy="fifo")
    eng = ServingEngine(cfg, params, sched,
                        EngineConfig(n_slots=3, max_len=96,
                                     prompt_buckets=(16,),
                                     chunk_prefill_tokens=8),
                        trace=trace)
    gen = WorkloadGenerator(GeneratorConfig(
        total_requests=8, calibration_requests=8,
        max_tokens=24, seed=seed))
    for t, r in gen.plan(seed=seed).calibration:
        if trace is not None and trace.enabled:
            # front-door events belong to whoever feeds the scheduler
            # (the cluster driver in production, this harness here);
            # ts 0.0 because the standalone engine clock starts there
            trace.emit(0.0, tr.ARRIVE, req_id=r.req_id,
                       tenant=r.tenant.label)
            trace.emit(0.0, tr.ADMIT, req_id=r.req_id,
                       tenant=r.tenant.label)
        sched.submit(r, t)
    m = eng.run_until_drained(max_steps=5000)
    return sched, m


def test_engine_trace_lifecycles_valid():
    rec = TraceRecorder(sample_every=FULL)
    sched, m = _engine_run(trace=rec)
    evs = rec.events()
    assert validate_lifecycles(evs) == []
    assert sum(e.kind == tr.COMPLETE for e in evs) == m.n_completed == 8
    assert any(e.kind == tr.PREFILL_CHUNK for e in evs)
    assert any(e.kind == tr.FIRST_TOKEN for e in evs)
    assert rec.stats()["segments"] == ["engine:fifo"]


def test_engine_trace_carries_sampled_tokens():
    """Observers see every sampled token: the first from first_token,
    the rest from decode_step, one per output token counted."""
    tokens, counted = {}, {}

    class Tokens:
        def on_event(self, ev):
            if ev.kind in (tr.FIRST_TOKEN, tr.DECODE_STEP):
                tokens.setdefault(ev.req_id, []).append(ev.data["token"])
                if ev.kind == tr.DECODE_STEP:
                    assert ev.data["n"] == len(tokens[ev.req_id])
            elif ev.kind == tr.COMPLETE:
                counted[ev.req_id] = ev.data["observed"]

    rec = TraceRecorder(capacity=1, observers=[Tokens()])
    sched, m = _engine_run(trace=rec)
    assert m.n_completed == len(counted) == len(tokens) == 8
    assert {r: len(t) for r, t in tokens.items()} == counted
    assert all(0 <= t < 256 for ts in tokens.values() for t in ts)
    # greedy decoding: a second run samples the same tokens
    again = dict(tokens)
    tokens.clear()
    _engine_run(trace=rec)
    assert sorted(again.values()) == sorted(tokens.values())


def test_engine_traced_identical_to_untraced():
    sched_a, m_a = _engine_run(trace=None)
    rec = TraceRecorder(sample_every=FULL)
    sched_b, m_b = _engine_run(trace=rec)
    assert rec.stats()["emitted"] > 0
    assert _completion_tuples(sched_a) == _completion_tuples(sched_b)
    assert m_a.as_dict() == m_b.as_dict()


# --- timeline export ---------------------------------------------------

def test_chrome_trace_export_validates_and_pairs_flows():
    rec = TraceRecorder(sample_every=FULL)
    _cluster_run(trace=rec)
    doc = to_chrome_trace(rec.events(), recorder_stats=rec.stats())
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C", "s", "f"} <= phases
    n_s = sum(e["ph"] == "s" for e in evs)
    n_f = sum(e["ph"] == "f" for e in evs)
    assert n_s == n_f > 0                   # P/D handoffs drew arrows
    # one lifetime slice per completed/shed request
    lifetimes = [e for e in evs if e["ph"] == "X"
                 and e.get("args", {}).get("kind") == "lifetime"]
    assert lifetimes and all(e["dur"] >= 0 for e in lifetimes)
    # process metadata names segment/replica tracks
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any("replica" in n for n in names)
    assert doc["otherData"]["recorder"]["emitted"] > 0


def test_validate_chrome_trace_catches_breakage():
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    base = {"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 1, "dur": 1}
    assert validate_chrome_trace({"traceEvents": [dict(base, dur=-5)]})
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    # ts regression on one track
    assert validate_chrome_trace({"traceEvents": [
        dict(base, ts=10), dict(base, ts=5)]})
    # unbalanced flow
    assert validate_chrome_trace({"traceEvents": [
        {"name": "h", "ph": "s", "ts": 0, "pid": 1, "tid": 1, "id": 9}]})


def test_write_trace_and_report_cli(tmp_path, capsys):
    from repro.obs import report
    rec = TraceRecorder(sample_every=FULL)
    _sim_run(trace=rec)
    path = str(tmp_path / "trace.json")
    doc = write_chrome_trace(path, rec.events(), recorder_stats=rec.stats())
    assert validate_chrome_trace(doc) == []
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert f"trace OK: {path}" in out
    assert "recorder: emitted=" in out
    # missing / corrupt / structurally invalid files fail loudly
    assert report.main([str(tmp_path / "nope.json")]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert report.main([str(bad)]) == 2
    invalid = tmp_path / "invalid.json"
    invalid.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert report.main([str(invalid)]) == 1


# --- JSON sanitization (silent-NaN footgun) ----------------------------

def test_sanitize_json_nan_to_null_everywhere():
    from benchmarks.common import sanitize_json

    @dataclass
    class Payload:
        p50: float
        nested: dict

    obj = {
        "direct": float("nan"),
        "inf": float("inf"),
        "list": [1.0, float("nan"), 3.0],
        "dc": Payload(p50=float("nan"), nested={"x": float("-inf")}),
        "fine": 1.5,
    }
    out = sanitize_json(obj)
    assert out["direct"] is None and out["inf"] is None
    assert out["list"] == [1.0, None, 3.0]
    assert out["dc"] == {"p50": None, "nested": {"x": None}}
    assert out["fine"] == 1.5
    # strict JSON round-trip: no bare literals, no stringified NaNs
    text = json.dumps(out, allow_nan=False, default=str)
    for leak in ('"nan"', "NaN", "Infinity"):
        assert leak not in text


def test_sanitize_json_unpacks_numpy_before_nan_check():
    np = pytest.importorskip("numpy")
    from benchmarks.common import sanitize_json
    obj = {"scalar": np.float64("nan"), "arr": np.array([1.0, float("nan")]),
           "int": np.int64(7)}
    out = sanitize_json(obj)
    assert out["scalar"] is None
    assert out["arr"] == [1.0, None]
    assert out["int"] == 7
    text = json.dumps(out, allow_nan=False, default=str)
    assert "nan" not in text.lower()


def test_empty_latency_stats_sanitizes_to_null():
    """The exact footgun this PR fixes: an empty LatencyStats used to
    reach json.dump(default=str) as a dataclass full of NaNs and come
    out as the string \"nan\"."""
    from benchmarks.common import sanitize_json
    from repro.serving.metrics import LatencyStats
    empty = LatencyStats.of([])
    out = sanitize_json({"ttft": empty})
    assert out["ttft"]["p50"] is None
    assert "nan" not in json.dumps(out, allow_nan=False).lower()


# --- shared stats helpers (satellite: single source of truth) ----------

def test_metrics_reexports_obs_stats():
    from repro.obs import stats as obs_stats
    from repro.serving import metrics
    assert metrics.percentile is obs_stats.percentile
    assert metrics.jain_index is obs_stats.jain_index
    assert metrics.LatencyStats is obs_stats.LatencyStats
