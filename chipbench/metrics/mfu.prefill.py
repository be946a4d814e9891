"""mfu.prefill (%): model FLOPs of the prompt chunks in the traced
window, over the window's length times the chip's peak bf16 FLOP/s: the
whole step's share beside ``prefill_roofline``."""

from harness import reduce


def read(run):
    return reduce.mfu(run, ("chunk",))
