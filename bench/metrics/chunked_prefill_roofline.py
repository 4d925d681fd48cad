"""Kernels: the fused chunked-prefill kernel's least time on the chip's
roofline, from the bytes and operations that each launch's prompt
tokens require in each layer's window (bucket padding is not required
work), summed over the layers, over its device time in the profiler
trace, %."""
from counting import chunked_prefill_call, least_seconds, over_layers

# the kernel's device time: the Pallas calls inside the engine's
# chunked-prefill program (its jitted inner ``fn``), which calls no other
# kernel
PROGRAM = "jit_fn"


def read(run):
    if run.device is None:
        return None
    spent = run.device.kernel_s(PROGRAM)
    launches = [p for s in run.window_steps for p in s.prefill]
    if not spent or not launches:
        return None
    # the prompt's own tokens end where the launch ends
    least = over_layers(run.shapes, lambda w: sum(
        least_seconds(*chunked_prefill_call(run.shapes, q0 + c - n, n, w),
                      run.peaks)
        for q0, c, n in launches))
    return 100.0 * least / spent
