"""queue_wait_ms_p90 (ms), front end and router: 90th percentile over the
requests due in the window of the time from when a request was due to
the start of the tick in which the engine gave it a slot (its
``admit_seq`` set), as the benchmark sees between ticks."""

from harness import reduce


def read(run):
    return reduce.p(reduce.queue_wait_s(run), 90, 1e3)
