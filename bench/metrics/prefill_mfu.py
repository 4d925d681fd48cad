"""Model step: model operations that the window's uncached prompt
tokens require (each attending the positions before it inside each
layer's window, summed over the layers, the head once per launch) over
the wall time of the steps that launched a prefill times the chip's
bf16 peak, %. Bucket padding is not required work."""
from counting import prefill_flops
from readings import step_seconds


def read(run):
    steps = [s for s in run.window_steps if s.prefill]
    if not steps or not run.peaks:
        return None
    # the prompt's own tokens end where the launch ends
    flops = sum(prefill_flops(run.shapes, q0 + c - n, n)
                for s in steps for q0, c, n in s.prefill)
    return 100.0 * flops / (step_seconds(steps) * run.peaks["bf16_flops"])
