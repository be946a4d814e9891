"""prefill_roofline (%): for the prompt chunks in the traced window, the
least time the algorithm's work allows (each weight read once, the
prefix's K/V read, the chunk's written, causal attention, the head only
for a prompt's last position) at the chip's peaks, over the measured
device time of ``chunk_fn``."""

from harness import reduce


def read(run):
    return reduce.roofline(run, "chunk", "chunk_fn")
