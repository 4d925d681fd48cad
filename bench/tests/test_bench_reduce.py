"""The trace reduction, on synthetic intervals and on a small profiler
trace recorded on the CPU."""

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reduce as r  # noqa: E402


def test_union_gaps_and_attribution_by_hand():
    busy = r.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert r.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert r.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    spans = [(2.5, 4.5, "bench.step"), (4.5, 9.0, "bench.wait")]
    # (3, 5): 1.5 under the step, 0.5 under the wait; (8, 10): 1 under
    # the wait, 1 under no span
    assert r.attribute([(3, 5), (8, 10)], spans) == {"bench.step": 2,
                                                      "bench.wait": 2}
    assert r.attribute([(10, 11)], spans) == {"(no span)": 1}


def test_names_of_programs_and_operations():
    assert r.program_name("jit__decode_paged_fn(17532597494867771324)") == (
        "jit__decode_paged_fn")
    assert r.op_kind("%copy.72 = bf16[8193,3,16,64]{3,2,1,0} copy(bf16[8193,"
                     "3,16,64]{3,1,2,0} %fusion.134)") == "copy"
    assert r.op_kind('%closed_call.8 = bf16[128,3,3,64] custom-call(s32[128]'
                     ' %a), custom_call_target="tpu_custom_call"') == r.KERNEL
    assert r.op_kind("%while.4 = (s32[]) while((s32[]) %t)") == "while"


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return r.load(str(out))


def test_reduction_of_a_recorded_cpu_trace(cpu_trace):
    red = r.reduce_profile(cpu_trace, **r.CPU_VIEW)
    host = next(p for p in cpu_trace.planes if p.name == r.HOST_PLANE)
    spans = {}
    ops = []
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("bench."):
                spans.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
            elif (line.name.startswith("tf_XLA") and ev.duration_ns > 0
                  and not ev.name.startswith("ThreadpoolListener")):
                ops.append((ev.name, ev.start_ns, ev.duration_ns))
    (lo, hi), = spans["bench.window"]
    assert red.window == (lo, hi)
    assert red.window_s == pytest.approx((hi - lo) / 1e9)
    assert red.window_s >= 4 * 0.02
    inside = [(n, a, d) for n, a, d in ops if lo <= a and a + d <= hi]
    assert inside, "the jitted matmul ran inside the window"
    # busy: the union of the op intervals, by a sweep over 1 us cells
    cells = set()
    for _, a, d in inside:
        cells.update(range(int(a // 1000), int((a + d) // 1000)))
    assert red.busy_ns == pytest.approx(len(cells) * 1000, rel=0.05,
                                        abs=5000)
    assert 0 < red.busy_s < red.window_s
    dot = next(n for n, _, _ in inside if n.startswith("dot"))
    assert red.kernel_s(dot, r.op_kind(dot)) == pytest.approx(
        sum(d for n, _, d in inside if n == dot) / 1e9)
    assert red.kernel_s("no such program") is None
    # idle time is split among the harness's spans and adds up
    assert sum(red.gaps_by_span.values()) == pytest.approx(
        (hi - lo) - red.busy_ns)
    assert red.gaps_by_span.get("bench.wait", 0) >= 3 * 0.02e9
    top = red.top_ops()
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_reduction_refuses_a_trace_without_device_ops(cpu_trace):
    with pytest.raises(ValueError, match="no device program"):
        r.reduce_profile(cpu_trace)        # no TPU plane in a CPU trace
