"""host_ms_per_tick (ms), engine scheduler on the host: mean over the
traced ticks of each ``fleet_tick`` span (the benchmark's annotation
around ``front.tick()``) minus the time inside it in which an operation
ran on the device."""

from harness import trace as tr


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    dev = sorted(run.trace["devices"])[0]
    busy = tr.busy(run.trace, dev)
    ticks = tr.host_spans(run.trace, tr.TICK)
    if not ticks:
        return None
    host = [(b - a) - tr.overlap(busy, a, b) for a, b in ticks]
    return sum(host) / len(host) / 1e6
