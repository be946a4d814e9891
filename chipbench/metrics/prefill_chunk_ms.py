"""prefill_chunk_ms (ms): device time of one execution of the jitted
``chunk_fn`` (one 128-token prompt chunk), from the trace's ``XLA
Modules`` events."""

from harness import reduce


def read(run):
    return reduce.step_ms(run, "chunk_fn")
