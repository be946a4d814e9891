"""decode_roofline (%): for the decode steps in the traced window, the
least time the algorithm's work allows (each weight read once, the K/V
of the live rows' earlier tokens read, their new K/V written, FLOPs of
the live rows; see ``arch``) at the chip's peaks, over the measured
device time of ``decode_fn``.  Padded and empty rows are not work."""

from harness import reduce


def read(run):
    return reduce.roofline(run, "decode", "decode_fn")
