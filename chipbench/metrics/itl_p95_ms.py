"""itl_p95_ms (ms): 95th percentile, nearest rank, of every gap between
two consecutive streamed tokens of a request, over the gaps whose later
token came inside the window."""

from harness import reduce


def read(run):
    return reduce.p(reduce.itl_s(run), 95, 1e3)
