"""decode_step_ms (ms): device time of one execution of the jitted
``decode_fn`` (``T.paged_step`` over the whole slot batch), from the
trace's ``XLA Modules`` events."""

from harness import reduce


def read(run):
    return reduce.step_ms(run, "decode_fn")
