"""ttft_p90_ms (ms): 90th percentile, nearest rank, over every request
due in the window, of the time from when it was due to its first token
streamed by the front end.  A request with no token by the cut counts
as waiting until the cut."""

from harness import reduce


def read(run):
    return reduce.p(reduce.ttft_s(run), 90, 1e3)
