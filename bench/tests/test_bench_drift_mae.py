"""The scheduler's drift MAE per window, read from a rehearsed traced run
of the open-loop cell and held to the drift tracker's own reading."""

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

CELL = "smollm135m-chat-poisson"


def test_drift_mae_is_the_trackers_over_the_window(monkeypatch):
    load = harness.load_cell

    def quick(root, name):
        cell = load(root, name)
        cell.mix["warmup"] = dict(cell.mix["warmup"], seconds=2)
        return cell

    seen = {"runs": []}
    release = harness.Served.release

    def keep_tracker(self):
        seen["tracker"] = self.engine.sched.drift
        release(self)

    reader = harness.reader

    def keep_run(root, metric):
        read = reader(root, metric)

        def read_and_keep(run):
            seen["runs"].append(run)
            return read(run)
        return read_and_keep

    monkeypatch.setattr(harness, "load_cell", quick)
    monkeypatch.setattr(harness.Served, "release", keep_tracker)
    monkeypatch.setattr(harness, "reader", keep_run)
    out = harness.run(ROOT, CELL, 3_000_000_023, 3.0, True,
                      t_process=time.perf_counter(), smoke=True,
                      log=io.StringIO())
    run = seen["runs"][0]
    want = seen["tracker"].stats(after=run.t0, before=run.t1)
    assert want.n >= 1
    got = out["metrics"]["drift_mae_tokens"]
    assert got["unit"] == "tokens"
    assert got["value"] == pytest.approx(want.mae)
