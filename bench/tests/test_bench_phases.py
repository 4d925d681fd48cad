"""The split of device idle time by innermost host span, on hand-built
intervals, on a small profiler trace recorded on the CPU, and on a
cell's rehearsal."""

import io
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import phases as ph  # noqa: E402
import reduce as r  # noqa: E402


def test_idle_split_by_innermost_span_by_hand():
    spans = [(2.5, 4.5, "bench.step"), (4.5, 9.0, "bench.wait")]
    # (3, 5): 1.5 under the step, 0.5 under the wait; (8, 10): 1 under
    # the wait, 1 under no span
    assert r.split([(3, 5), (8, 10)], r.innermost(spans)) == {
        "bench.step": 1.5, "bench.wait": 1.5, "(no span)": 1}


def test_a_gap_that_crosses_into_sibling_spans_is_split():
    spans = [(0, 20, "bench.step"), (1, 15, "engine.step"),
             (10, 14, "engine.retire"), (20, 24, "bench.release"),
             (24, 40, "bench.step"), (25, 35, "engine.step"),
             (25, 27, "engine.admit")]
    assert r.innermost(spans) == [
        (0, 1, "bench.step"), (1, 10, "engine.step"),
        (10, 14, "engine.retire"), (14, 15, "engine.step"),
        (15, 20, "bench.step"), (20, 24, "bench.release"),
        (24, 25, "bench.step"), (25, 27, "engine.admit"),
        (27, 35, "engine.step"), (35, 40, "bench.step")]
    got = r.split([(12, 26), (41, 43)], r.innermost(spans))
    assert got == {"engine.retire": 2, "engine.step": 1, "bench.step": 6,
                   "bench.release": 4, "engine.admit": 1, "(no span)": 2}


def test_a_span_that_outlasts_its_parent_is_cut_at_its_end():
    spans = [(0, 10, "engine.step"), (7, 12, "engine.retire")]
    assert r.innermost(spans) == [(0, 7, "engine.step"),
                                   (7, 10, "engine.retire")]


def test_result_type_of_an_instruction():
    assert ph.result_type(
        "%copy.72 = bf16[8193,3,16,64]{3,2,1,0:T(4,128)(2,1)} copy(bf16["
        "8193,3,16,64]{3,1,2,0} %fusion.134)") == (
            "bf16[8193,3,16,64]{3,2,1,0:T(4,128)(2,1)}")


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """Four steps whose host spans nest as the engine's do: a jitted
    matmul launched and read back inside the step, host work (a sleep)
    under ``engine.retire``, then the harness waiting."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    out = tmp_path_factory.mktemp("trace")
    ann = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(out))
    with ann("bench.window"):
        for _ in range(4):
            with ann("bench.step"), ann("engine.step"):
                with ann("engine.decode_launch"):
                    y = f(x)
                with ann("engine.decode_sync"):
                    y.block_until_ready()
                with ann("engine.retire"):
                    time.sleep(0.01)
            with ann("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return r.load(str(out))


def test_split_of_a_recorded_cpu_trace(engine_trace):
    red = r.reduce_profile(engine_trace, **r.CPU_VIEW)
    rep = ph.phases(engine_trace, red, **r.CPU_VIEW)
    idle = rep["idle_s_by_span"]
    # the attributed gaps add up to the idle time, and each sleep falls
    # under the span it ran in
    assert sum(idle.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert idle["engine.retire"] >= 4 * 0.01 * 0.9
    assert idle["bench.wait"] >= 4 * 0.02 * 0.9
    assert "bench.step" not in idle or idle["bench.step"] < 0.01
    device_idle = 100 * (1 - red.busy_s / red.window_s)
    assert 0 < rep["engine_host_idle_share"] <= device_idle
    assert rep["engine_steps"] == 4
    assert rep["host_ms_per_step"]["engine.retire"] >= 10 * 0.9
    assert isinstance(rep["copy_s_by_type"], list)


@pytest.fixture
def short_warmup(monkeypatch):
    """Two seconds of warm-up traffic instead of the mix's own."""
    load = harness.load_cell

    def quick(root, name):
        cell = load(root, name)
        cell.mix["warmup"] = dict(cell.mix["warmup"], seconds=2)
        return cell

    monkeypatch.setattr(harness, "load_cell", quick)


def test_a_rehearsed_cell_puts_its_idle_time_under_engine_phases(
        short_warmup):
    out, rep = ph.traced_run(ROOT, "danube1.8b-rag-closed", 3_000_000_019,
                             3.0, smoke=True, log=io.StringIO())
    assert out["correct"] is True
    dev = out["device"]
    idle = rep["idle_s_by_span"]
    assert sum(idle.values()) == pytest.approx(
        dev["window_s"] - dev["busy_s"], rel=1e-6)
    assert {"engine.prefill", "engine.decode_sync",
            "engine.retire"} <= set(rep["host_ms_per_step"])
    share = out["metrics"]["device_idle_share"]["value"]
    assert 0 < rep["engine_host_idle_share"] <= share
    # the result line's breakdown names the engine's phases
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert any(n.startswith("engine.") for n in gaps)
    assert all(idle[n] == pytest.approx(s) for n, s in gaps.items())
