"""The check's control: the plain reference put in the program's place
and computed with float8 matmul operands (the precision below the
configurations' bfloat16) comes out not correct, where the program
comes out correct; on the CPU at each configuration's small widths,
with the limit set there from the same two readings."""

import contextlib
import io
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import calibrate  # noqa: E402
import harness  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture
def warmup(monkeypatch):
    load = harness.load_cell

    def quick(root, name):
        cell = load(root, name)
        cell.mix["warmup"] = dict(cell.mix["warmup"], seconds=4,
                                 in_flight=4)
        return cell

    monkeypatch.setattr(harness, "load_cell", quick)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell, warmup):
    for seed in (21, 22, 23):
        with contextlib.redirect_stderr(io.StringIO()):
            r = calibrate.readings(ROOT, cell, seed, 4.0, smoke=True)
        assert r["program"] <= r["limit"] < r["control"], r
        assert r["correct"] is True
