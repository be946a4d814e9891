"""mfu.decode (%): model FLOPs of the decode steps in the traced window,
over the window's length times the chip's peak bf16 FLOP/s: the whole
step's share beside ``decode_roofline``."""

from harness import reduce


def read(run):
    return reduce.mfu(run, ("decode",))
