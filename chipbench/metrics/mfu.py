"""mfu (%): model FLOPs of every token prefilled and decoded in the traced
window, over the window's length times the chip's peak bf16 FLOP/s."""

from harness import reduce


def read(run):
    return reduce.mfu(run, ("chunk", "decode"))
