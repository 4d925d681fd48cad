"""A program's gauges reach the metric readers by name: the harness
files every ``gauge`` event of a step under ``StepRec.gauges``, whatever
its name, and every cell's traced rehearsal reads its per-layer values
as the readers did when the harness knew one gauge by name."""

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
from test_bench_rehearsal import CELLS, rehearse, short_warmup  # noqa: E402,F401


class StubEngine:
    """Emits gauges as ``ServingEngine.step`` does, on its trace."""

    def __init__(self, trace, gauges):
        self.trace, self.gauges = trace, gauges
        self.n_prefill_launches = 0

    def step(self, now):
        from repro.obs import events as tr
        for name, value in self.gauges:
            self.trace.emit(now, tr.GAUGE, rid=0, name=name, value=value)


def stepped(gauges):
    """One step of a ``Served`` whose engine emits ``gauges``."""
    from repro.obs import TraceRecorder
    served = harness.Served.__new__(harness.Served)
    served.tap = harness.Tap(time.perf_counter, {})
    served.engine = StubEngine(
        TraceRecorder(capacity=1, observers=[served.tap]), gauges)
    return served.step(0.5, lambda: 0.75)


def run_of(steps, total_pages=10):
    return harness.Run(t0=0.0, t1=1.0, setup_s=0.0, requests=[],
                       steps=steps, shapes={}, peaks={},
                       total_pages=total_pages, open_loop=False,
                       prefix_tokens_saved=0, memory_peak_bytes=None)


READER = '''"""Rows routed to each held expert, summed over the window's steps."""


def read(run):
    rows = [s.gauges["expert_rows"] for s in run.window_steps
            if "expert_rows" in s.gauges]
    return [sum(col) for col in zip(*rows)] if rows else None
'''


def test_a_gauge_never_heard_of_reaches_a_reader(tmp_path):
    # a reader added as a file, as a later configuration adds its own
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "expert_rows_total.py").write_text(
        READER)
    a = stepped([("expert_rows", [3, 0, 5]), ("recurrent_slots", 7),
                 ("kv_free_pages", 6), ("kv_free_pages", 4)])
    b = stepped([("expert_rows", [1, 2, 0]), ("kv_free_pages", 2)])
    # the last value of a name in the step, a list kept whole
    assert a.gauges == {"expert_rows": [3, 0, 5], "recurrent_slots": 7,
                        "kv_free_pages": 4}
    assert (a.start, a.end) == (0.5, 0.75)
    run = run_of([a, b])
    assert harness.reader(tmp_path, "expert_rows_total")(run) == [4, 2, 5]
    # the benchmark's own reader reads its gauge the same way
    assert harness.reader(ROOT, "kv_pages_in_use_share")(run) == (
        pytest.approx(100.0 * (1 - 6 / 20)))
    assert harness.reader(ROOT, "kv_pages_in_use_share")(
        run_of([stepped([("queue_depth", 3)])])) is None


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reads_its_per_layer_values_as_before(
        cell, short_warmup, monkeypatch):
    """A traced rehearsal: beside the harness, an observer keeps each
    step's ``kv_free_pages`` the way the harness kept that one gauge
    before it filed gauges by name, and the run's readings are worked
    out again by the readers' former rules."""
    kept, runs = {}, []
    on_event = harness.Tap.on_event

    def keep(tap, ev):
        if (ev.kind == "gauge" and ev.data["name"] == "kv_free_pages"
                and tap.step is not None):
            kept[id(tap.step)] = int(ev.data["value"])
        on_event(tap, ev)

    def kept_run(**fields):
        runs.append(Run(**fields))
        return runs[-1]

    Run = harness.Run
    monkeypatch.setattr(harness.Tap, "on_event", keep)
    monkeypatch.setattr(harness, "Run", kept_run)
    out = rehearse(ROOT, cell, trace=True)
    assert out["correct"] is True, out["checks"]
    run, = runs
    m = {k: v["value"] for k, v in out["metrics"].items()}
    want = {x["name"] for x in harness.load_cell(ROOT, cell).layer_metrics}
    peaks = {"decode_mfu", "prefill_mfu", "paged_decode_roofline",
             "chunked_prefill_roofline", "peak_hbm_share",
             "prefill_mfu.rag", "chunked_prefill_roofline.rag"}
    assert set(m) == want - peaks
    free = [kept[id(s)] for s in run.window_steps if id(s) in kept]
    if "kv_pages_in_use_share" in want:
        assert free
        assert m["kv_pages_in_use_share"] == 100.0 * (
            1.0 - sum(free) / (len(free) * run.total_pages))
    # a metric split off for the cells that report no ``itl_p90_ms``
    # end to end reads as its base reader does
    for name in (n for n in m if n.endswith(".rag")):
        assert m[name] == harness.reader(ROOT, name[:-4])(run)
    dev = run.device
    assert m["device_idle_share"] == 100.0 * (1.0 - dev.busy_s / dev.window_s)
    # the idle split by phase adds up to the idle time
    assert sum(dev.gaps_by_span.values()) == pytest.approx(
        dev.window[1] - dev.window[0] - dev.busy_ns, rel=1e-9)
