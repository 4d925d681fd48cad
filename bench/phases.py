"""Where the device's idle time falls inside the engine's step: one traced
run of a cell, split by the program's own host spans.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

Runs the cell once, as ``run.py --trace 1`` does, prints the result line,
then one JSON line, ``{"phases": ...}``, read from the same profile:

* ``idle_s_by_span``: the device's idle time in the window, each instant
  under the innermost host span open then among the harness's
  (``bench.*``) and the program's (``engine.*``, ``sched.*``; see
  ``repro.obs.spans``); ``(no span)`` where none is open. This is the
  reduction's own split (``reduce.py``), which the result line's
  ``breakdown`` lists as ``idle_gaps``, in full;
* ``engine_host_idle_share``: the idle time under ``engine.*`` and
  ``sched.*`` spans over the window, %: the part of the device's idle
  share that the program's own host work causes;
* ``host_ms_per_step``: each span's self time (its time less that of the
  spans inside it) over the window, per ``engine.step``;
* ``copy_s_by_type``: the device time of ``copy`` operations by program
  and by the type the copy writes (shape and layout), from the
  operation's HLO text: on a TPU the event carries that text and its
  times, not the ``jax.named_scope`` path of the operation, so this is
  the finest split of the copies the trace itself gives.

Without the program's spans (an older program) the idle time falls under
the harness's spans alone.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, Tuple

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reduce as breduce  # noqa: E402

PROGRAM = ("engine.", "sched.")


def result_type(event: str) -> str:
    """The type an HLO instruction writes, from its text:
    ``%copy.72 = bf16[8193,3,16,64]{3,2,1,0} copy(...)`` ->
    ``bf16[8193,3,16,64]{3,2,1,0}``."""
    return event.split(" = ", 1)[-1].split(" ", 1)[0]


def phases(pd, red: breduce.Reduced, *, window_span: str = "bench.window",
           **view) -> dict:
    """The report of the module docstring, for the window of ``red``
    (``view`` as ``reduce.reduce_profile`` takes it)."""
    v = dict(breduce.TPU_VIEW)
    v.update(view)
    lo, hi = red.window
    spans = [s for s in breduce.host_spans(pd, window_span)
             if s[2] != window_span]
    pieces = [(max(a, lo), min(b, hi), n)
              for a, b, n in breduce.innermost(spans) if b > lo and a < hi]
    copy_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    n_planes = 0
    for plane in pd.planes:
        if not v["device_plane"](plane.name):
            continue
        progs = sorted(breduce._events(plane, v["program_line"], lo, hi,
                                       v["op_event"]))
        if not progs:
            continue
        n_planes += 1
        ops = sorted(breduce._events(plane, v["op_line"], lo, hi,
                                     v["op_event"]))
        i = 0
        for a, b, name in ops:
            if breduce.op_kind(name) != "copy":
                continue
            # each operation belongs to the program running when it starts
            while i < len(progs) and progs[i][1] <= a:
                i += 1
            owner = (breduce.program_name(progs[i][2])
                     if i < len(progs) and progs[i][0] <= a else "(none)")
            copy_ns[(owner, result_type(name))] += b - a
    scale = 1e-9 / max(n_planes, 1)
    steps = sum(1 for a, _, n in spans
                if n == "engine.step" and lo <= a < hi)
    self_ns: Dict[str, float] = defaultdict(float)
    for a, b, name in pieces:
        self_ns[name] += b - a
    idle = red.gaps_by_span
    program_idle = sum(ns for n, ns in idle.items() if n.startswith(PROGRAM))

    def by_value(d):
        return sorted(d.items(), key=lambda kv: -kv[1])

    return {
        "window_s": (hi - lo) / 1e9,
        "engine_steps": steps,
        "idle_s_by_span": {k: ns / 1e9 for k, ns in by_value(idle)},
        "engine_host_idle_share": 100.0 * program_idle / (hi - lo),
        "host_ms_per_step": ({k: ns / 1e6 / steps
                              for k, ns in by_value(self_ns)}
                             if steps else {}),
        "copy_s_by_type": [[f"{p}: {t}", ns * scale]
                           for (p, t), ns in by_value(copy_ns)[:20]],
    }


def traced_run(root: Path, workload: str, seed: int, seconds: float, *,
               smoke: bool = False, t_process: float = None, log=sys.stderr):
    """One ``--trace 1`` run of ``workload``; returns its result line and
    the phases of its profile."""
    import harness
    seen = {}
    plain = harness.breduce.reduce_profile

    def reduce_and_split(pd, **view):
        red = plain(pd, **view)
        seen["phases"] = phases(pd, red, **view)
        return red

    harness.breduce.reduce_profile = reduce_and_split
    try:
        t = time.perf_counter() if t_process is None else t_process
        out = harness.run(root, workload, seed, seconds, True, t_process=t,
                          smoke=smoke, log=log)
    finally:
        harness.breduce.reduce_profile = plain
    return out, seen["phases"]


def main(argv=None) -> int:
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    out, report = traced_run(ROOT, args.workload, args.seed, args.seconds,
                             t_process=t_process)
    print(json.dumps(out), flush=True)
    print(json.dumps({"phases": report}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
