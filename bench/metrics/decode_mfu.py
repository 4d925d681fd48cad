"""Model step: model operations that the window's decoded tokens
require (projections, MLP, head, and attention over each token's live
context inside each layer's window, summed over the layers) over the
wall time of the steps that decoded times the chip's bf16 peak, %."""
from counting import decode_token_flops
from readings import step_seconds


def read(run):
    steps = [s for s in run.window_steps if s.decode]
    if not steps or not run.peaks:
        return None
    flops = sum(decode_token_flops(run.shapes, kv)
                for s in steps for kv in s.decode)
    return 100.0 * flops / (step_seconds(steps) * run.peaks["bf16_flops"])
