"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) holds planes of
lines of events, each with a start and a duration in nanoseconds on one
clock. On a TPU the device plane (``/device:TPU:<n>``) has a line of
programs (``XLA Modules``: one event per execution of a jitted program,
named ``jit_<function>(<fingerprint>)``) and a line of their operations
(``XLA Ops``: one event per HLO instruction, named by the instruction's
text, ``while`` loops enclosing the operations of their bodies). The
harness's own spans (``jax.profiler.TraceAnnotation``) are events on the
host plane. From them this module takes:

* the traced window: the span ``bench.window``;
* busy time: the union of the program intervals inside the window; the
  idle share is one minus busy over the window;
* device time by program and by kind of operation, so that a Pallas
  kernel's time is that of the ``tpu_custom_call`` operations inside the
  program that calls it;
* the idle gaps between programs, each put under the host span that
  overlaps it most.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

HOST_PLANE = "/host:CPU"
KERNEL = "tpu_custom_call"
#: operations that only enclose others
CONTAINERS = ("while", "conditional", "call")
#: where a TPU trace keeps programs and their operations
TPU_VIEW = dict(
    device_plane=lambda n: n.startswith("/device:TPU:"),
    program_line=lambda n: n == "XLA Modules",
    op_line=lambda n: n == "XLA Ops",
    op_event=lambda n: True)
#: where a CPU trace keeps XLA's operations (rehearsals and tests): the
#: host plane's XLA client threads, each operation its own program
CPU_VIEW = dict(
    device_plane=lambda n: n == HOST_PLANE,
    program_line=lambda n: n.startswith("tf_XLA"),
    op_line=lambda n: n.startswith("tf_XLA"),
    op_event=lambda n: not n.startswith("ThreadpoolListener"))


def program_name(event: str) -> str:
    """``jit__decode_paged_fn(1753...)`` -> ``jit__decode_paged_fn``."""
    return re.sub(r"\(\d+\)$", "", event)


def op_kind(event: str) -> str:
    """The kind of an HLO instruction from its text: ``tpu_custom_call``
    for a Pallas kernel, else the instruction's name without its number
    (``%copy.72 = bf16[...] copy(...)`` -> ``copy``)."""
    if f'custom_call_target="{KERNEL}"' in event:
        return KERNEL
    head = event.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclass
class Reduced:
    window: Tuple[float, float]                  # ns, on the trace's clock
    busy_ns: float
    program_ns: Dict[str, float]                  # by program name
    op_ns: Dict[Tuple[str, str], float]           # by (program, kind)
    gaps_by_span: Dict[str, float] = field(default_factory=dict)  # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def kernel_s(self, program: str, kind: str = KERNEL) -> Optional[float]:
        """Seconds in operations of ``kind`` inside ``program``; None
        when none ran."""
        ns = self.op_ns.get((program, kind))
        return ns / 1e9 if ns else None

    def top_ops(self, k: int = 10) -> List[list]:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]
        return [[f"{p}: {kind}", ns / 1e9] for (p, kind), ns in top]

    def top_gaps(self, k: int = 10) -> List[list]:
        top = sorted(self.gaps_by_span.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi)`` between merged busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(intervals, spans: List[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Length of each interval under the name of the span that overlaps
    it most; an interval no span overlaps goes under ``(no span)``.
    The spans follow one another without overlapping, so one sweep over
    both sorted lists finds them all."""
    spans = sorted(spans)
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for a, b in sorted(intervals):
        while i < len(spans) and spans[i][1] <= a:
            i += 1
        best, best_ov = "(no span)", 0.0
        j = i
        while j < len(spans) and spans[j][0] < b:
            s0, s1, name = spans[j]
            ov = min(b, s1) - max(a, s0)
            if ov > best_ov:
                best, best_ov = name, ov
            j += 1
        out[best] += b - a
    return dict(out)


def _events(plane, line_pred: Callable[[str], bool], lo: float, hi: float,
            event_pred: Callable[[str], bool] = lambda n: True):
    """``(start, end, name)`` of a plane's events inside ``[lo, hi)``."""
    out = []
    for line in plane.lines:
        if not line_pred(line.name):
            continue
        for ev in line.events:
            a, d = ev.start_ns, ev.duration_ns
            if d > 0 and a + d > lo and a < hi and event_pred(ev.name):
                out.append((max(a, lo), min(a + d, hi), ev.name))
    return out


def reduce_profile(pd, *, window_span: str = "bench.window",
                   span_prefix: str = "bench.", device_plane=None,
                   program_line=None, op_line=None, op_event=None
                   ) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` (``TPU_VIEW`` unless the
    keywords say otherwise). Device numbers are averaged over the device
    planes that ran a program inside the window."""
    view = dict(TPU_VIEW)
    view.update({k: v for k, v in dict(
        device_plane=device_plane, program_line=program_line,
        op_line=op_line, op_event=op_event).items() if v is not None})
    host = next((p for p in pd.planes if p.name == HOST_PLANE), None)
    if host is None:
        raise ValueError(f"trace has no plane {HOST_PLANE!r}")
    spans = _events(host, lambda n: True, float("-inf"), float("inf"),
                    lambda n: n.startswith(span_prefix))
    win = [(a, b) for a, b, n in spans if n == window_span]
    if not win:
        raise ValueError(f"trace has no span {window_span!r}")
    lo, hi = win[0]
    inner = [s for s in spans if s[2] != window_span]

    busy_total, n_planes = 0.0, 0
    program_ns: Dict[str, float] = defaultdict(float)
    op_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    for plane in pd.planes:
        if not view["device_plane"](plane.name):
            continue
        progs = _events(plane, view["program_line"], lo, hi,
                        view["op_event"])
        if not progs:
            continue
        n_planes += 1
        busy = union([(a, b) for a, b, _ in progs])
        busy_total += sum(b - a for a, b in busy)
        for name, ns in attribute(gaps(busy, lo, hi), inner).items():
            gap_ns[name] += ns
        for a, b, name in progs:
            program_ns[program_name(name)] += b - a
        # each operation belongs to the program running when it starts
        progs.sort()
        ops = sorted(_events(plane, view["op_line"], lo, hi,
                             view["op_event"]))
        i = 0
        for a, b, name in ops:
            kind = op_kind(name)
            if kind in CONTAINERS:
                continue
            while i < len(progs) and progs[i][1] <= a:
                i += 1
            owner = (program_name(progs[i][2])
                     if i < len(progs) and progs[i][0] <= a else "(none)")
            op_ns[(owner, kind)] += b - a
    if n_planes == 0:
        raise ValueError("no device program ran inside the traced window")
    scale = 1.0 / n_planes
    return Reduced(window=(lo, hi), busy_ns=busy_total * scale,
                   program_ns={k: v * scale for k, v in program_ns.items()},
                   op_ns={k: v * scale for k, v in op_ns.items()},
                   gaps_by_span={k: v * scale for k, v in gap_ns.items()})


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``, read with JAX."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))
