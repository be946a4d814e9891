"""From a profiler trace to plain events, and from events to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
only what the metrics read: per device, the ``XLA Modules`` events (one
per execution of a jitted program) and the ``XLA Ops`` events (one per
operation); on the host, the benchmark's own spans.  The rest of this
module reduces that plain form, so a recorded trace can be checked in a
test without the chip.  Times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os

#: host spans the benchmark writes (``jax.profiler.TraceAnnotation``)
WINDOW = "trace_window"
TICK = "fleet_tick"
SPANS = (WINDOW, TICK, "await_arrival", "submit")


def load(log_dir: str) -> dict:
    """The plain form of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            devices[plane.name] = {
                kind: [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                       for ev in lines[line].events]
                for kind, line in (("modules", "XLA Modules"),
                                   ("ops", "XLA Ops")) if line in lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                         for ev in line.events if ev.name in SPANS]
    windows = [h for h in host if h[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    _, start, dur = windows[0]
    return {"window": [start, start + dur], "devices": devices,
            "host": sorted(host, key=lambda h: h[1])}


def window_s(tr: dict) -> float:
    lo, hi = tr["window"]
    return (hi - lo) / 1e9


def merged(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of the events' intervals, clipped to [lo, hi], in order."""
    out: list[list[int]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, lo), min(s + d, hi)
        if a >= b:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(tr: dict, device: str) -> list[tuple[int, int]]:
    lo, hi = tr["window"]
    return merged(tr["devices"][device]["ops"], lo, hi)


def busy_s(tr: dict) -> float:
    """Seconds in the window in which some operation ran on the device,
    averaged over the devices in the trace."""
    devs = list(tr["devices"])
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in busy(tr, d)) for d in devs) \
        / len(devs) / 1e9


def overlap(intervals: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of the sorted, disjoint ``intervals`` inside [lo, hi]."""
    i = max(0, bisect.bisect_right(intervals, (lo, lo)) - 1)
    total = 0
    while i < len(intervals) and intervals[i][0] < hi:
        a, b = intervals[i]
        total += max(0, min(b, hi) - max(a, lo))
        i += 1
    return total


def executions(tr: dict, program: str) -> list[int]:
    """Device durations (ns) of each execution of the jitted program
    named ``program`` that started inside the window, over all devices."""
    lo, hi = tr["window"]
    return [d for dev in tr["devices"].values()
            for name, s, d in dev.get("modules", ())
            if _program(name) == program and lo <= s < hi]


def _program(module: str) -> str:
    """``jit_decode_fn(12)`` or ``jit_decode_fn`` -> ``decode_fn``."""
    return module.split("(")[0].removeprefix("jit_")


def host_spans(tr: dict, name: str) -> list[tuple[int, int]]:
    lo, hi = tr["window"]
    return [(s, s + d) for n, s, d in tr["host"]
            if n == name and lo <= s and s + d <= hi]


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events) -> list[tuple[str, int, int]]:
    """(name, start, self time) of each event, where the events nest (a
    ``while`` op holds its body's ops): an event's self time is its
    duration less that of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: list[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], events[i][1], own[i]) for i in range(len(events))]


def top_ops(tr: dict, k: int = 10) -> list[list]:
    """The ``k`` operations with the most device self time in the window,
    summed by program and operation (``decode_fn/fusion.12``), in
    seconds."""
    lo, hi = tr["window"]
    total: dict[str, int] = collections.defaultdict(int)
    for dev in tr["devices"].values():
        mods = sorted((s, s + d, _program(n))
                      for n, s, d in dev.get("modules", ()))
        starts = [m[0] for m in mods]
        for name, s, d in self_times(dev["ops"]):
            if not lo <= s < hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            total[f"{prog}/{op_name(name)}"] += d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(tr: dict, k: int = 10) -> list[list]:
    """The ``k`` longest stretches of the window with no operation on the
    first device, each named by the innermost benchmark span that holds
    its middle (``outside_spans`` if none does), in seconds."""
    lo, hi = tr["window"]
    if not tr["devices"]:
        return []
    b = busy(tr, sorted(tr["devices"])[0])
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, z in gaps[:k]:
        mid = (a + z) // 2
        holders = [(d, n) for n, s, d in tr["host"]
                   if n != WINDOW and s <= mid < s + d]
        out.append([min(holders)[1] if holders else "outside_spans",
                    (z - a) / 1e9])
    return out
