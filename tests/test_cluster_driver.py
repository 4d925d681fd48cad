"""Cluster router/admission over real JAX ServingEngine replicas."""

import jax
import pytest

from repro.cluster import AdmissionConfig, GlobalAdmission
from repro.cluster.driver import EngineClusterDriver, make_engine_cluster
from repro.configs import smoke_config
from repro.core.request import TenantTier
from repro.core.scheduler import DriftScheduler
from repro.models.registry import get_api
from repro.serving.engine import EngineConfig, ServingEngine
from repro.workload.generator import GeneratorConfig, WorkloadGenerator


def _cluster(n_replicas=2, routing="drift_aware", admission=None):
    cfg = smoke_config("smollm-135m")
    api = get_api(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return make_engine_cluster(
        cfg, params, n_replicas, routing=routing, admission=admission,
        engine_config=EngineConfig(n_slots=2, max_len=96,
                                   prompt_buckets=(16,)))


def _submit_n(driver, n, seed=0):
    gen = WorkloadGenerator(GeneratorConfig(
        total_requests=n, calibration_requests=n, max_tokens=48, seed=seed))
    plan = gen.plan(seed=seed)
    return sum(driver.submit(r, t) for t, r in plan.calibration)


def test_engine_cluster_routes_and_completes():
    driver = _cluster(n_replicas=2)
    accepted = _submit_n(driver, 10)
    assert accepted == 10
    m = driver.run_until_drained(max_steps=5000)
    assert m.n_completed == 10
    # work actually spread over both replicas
    assert all(rep.n_routed > 0 for rep in driver.replicas)
    # shared estimator saw every completion
    assert sum(driver.estimator.bias_store.update_counts().values()) == 10


def test_engine_cluster_rejects_unshared_estimators():
    cfg = smoke_config("smollm-135m")
    api = get_api(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    engines = [ServingEngine(cfg, params, DriftScheduler(),
                             EngineConfig(n_slots=2, max_len=96,
                                          prompt_buckets=(16,)))
               for _ in range(2)]
    with pytest.raises(ValueError):
        EngineClusterDriver(engines)


def test_engine_cluster_admission_sheds():
    adm = GlobalAdmission(AdmissionConfig(
        bucket_capacity={t: 400.0 for t in TenantTier},
        refill_rate={t: 0.0 for t in TenantTier}))
    driver = _cluster(n_replicas=2, admission=adm)
    accepted = _submit_n(driver, 12)
    assert 0 < accepted < 12
    assert driver.n_shed == 12 - accepted
    m = driver.run_until_drained(max_steps=5000)
    assert m.n_completed == accepted


def _pd_run(devices):
    """A seeded P/D run over two paged replicas placed on ``devices``:
    completions, routing and handoff counts, and the replicas' pools."""
    cfg = smoke_config("smollm-135m")
    params = get_api(cfg).init(cfg, jax.random.PRNGKey(0))
    driver = make_engine_cluster(
        cfg, params, 2, routing="pd_disaggregated", devices=devices,
        engine_config=EngineConfig(n_slots=2, max_len=96,
                                   prompt_buckets=(16,), paged=True,
                                   page_size=8, chunk_prefill_tokens=8))
    gen = WorkloadGenerator(GeneratorConfig(
        total_requests=6, calibration_requests=6, max_tokens=24, seed=3))
    reqs = [r for t, r in gen.plan(seed=3).calibration
            if driver.submit(r, t)]
    assert driver.run_until_drained(max_steps=5000).n_completed == 6
    pos = {r.req_id: i for i, r in enumerate(reqs)}
    seen = {
        "completions": sorted((r.completion_time, pos[r.req_id])
                              for r in reqs),
        "routing": [(r.prefill_rid, r.decode_rid) for r in reqs],
        "handoffs": driver.n_handoffs,
    }
    return seen, [rep.engine.pool for rep in driver.replicas]


def test_engine_cluster_places_replicas_on_devices():
    """Replica i's params and pool live on devices[i], KV handed off
    lands there too, and placement changes no scheduling outcome."""
    dev = jax.devices()[0]
    placed, pools = _pd_run([dev, dev])
    default, _ = _pd_run(None)
    assert placed == default
    assert placed["handoffs"] == 6
    for pool in pools:
        assert pool.k.devices() == {dev} and pool.v.devices() == {dev}
        assert pool.k.committed


def test_engine_cluster_needs_one_device_per_replica():
    cfg = smoke_config("smollm-135m")
    params = get_api(cfg).init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="one device per replica"):
        make_engine_cluster(cfg, params, 2, devices=jax.devices()[:1])
