"""Kernels: the batched paged-decode kernel's least time on the chip's
roofline, from the bytes and operations its live lengths require in each
layer's window (not the padded page grid), summed over the layers, over
its device time in the profiler trace, %."""
from counting import least_seconds, over_layers, paged_decode_call

# the kernel's device time: its own Pallas calls inside the engine's
# decode program
PROGRAM, KERNEL = "jit__decode_paged_fn", "batched_paged_decode_attention"


def read(run):
    if run.device is None:
        return None
    spent = run.device.kernel_s(PROGRAM, KERNEL)
    steps = [s for s in run.window_steps if s.decode]
    if not spent or not steps:
        return None
    least = over_layers(run.shapes, lambda w: sum(
        least_seconds(*paged_decode_call(run.shapes, s.decode, w), run.peaks)
        for s in steps))
    return 100.0 * least / spent
