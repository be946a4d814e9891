"""Arithmetic shared by the metric readers in ``metrics/``.

End-to-end numbers come from the host clock over the window; per-layer
numbers from the traced part of the window, where the device trace and
the ticks the loop counted there describe the same work.
"""

from __future__ import annotations

from harness import trace as tr
from harness.stats import percentile


def ttft_s(run) -> list[float]:
    """Due time to first streamed token, for every request due in the
    window; one with no token by the cut counts as waiting until then."""
    return [(r.token_times[0] if r.token_times else run.cut) - r.due
            for r in run.window_reqs]


def itl_s(run) -> list[float]:
    """Every gap between consecutive streamed tokens of a request whose
    later token came inside the window."""
    out = []
    for r in run.reqs:
        t = r.token_times
        out += [b - a for a, b in zip(t, t[1:]) if run.in_window(b)]
    return out


def window_tokens(run) -> int:
    return sum(1 for r in run.reqs for t in r.token_times if run.in_window(t))


def queue_wait_s(run) -> list[float]:
    """Due time to the start of the tick that gave the request a slot."""
    return [(r.admitted if r.admitted is not None else run.cut) - r.due
            for r in run.window_reqs]


def p(values, q: float, scale: float = 1.0) -> float | None:
    return percentile(values, q) * scale if values else None


# -- the traced window -----------------------------------------------------------


def step_ms(run, program: str) -> float | None:
    """Mean device time of one execution of ``program``, in ms."""
    if run.trace is None:
        return None
    ex = tr.executions(run.trace, program)
    return sum(ex) / len(ex) / 1e6 if ex else None


def work(run, phase: str) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every ``decode`` step or prefill ``chunk`` the
    loop counted in the traced window."""
    conf, arch = run.conf, run.arch
    out = []
    for t in run.traced_ticks():
        if phase == "decode" and t.decode:
            out.append(arch.decode_work(conf, t.decode))
        elif phase == "chunk" and t.chunk is not None:
            out.append(arch.chunk_work(conf, *t.chunk))
    return out


def roofline(run, phase: str, program: str) -> float | None:
    """Least time the chip could take for the algorithm's work (the larger
    of FLOPs over peak FLOP/s and bytes over peak bandwidth, per step),
    over the program's measured device time, in %."""
    if run.trace is None:
        return None
    ex = tr.executions(run.trace, program)
    steps = work(run, phase)
    if not ex or not steps:
        return None
    if len(ex) != len(steps):
        raise RuntimeError(f"{program}: {len(ex)} executions in the trace "
                           f"but {len(steps)} counted steps")
    pk = run.peaks
    least = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in steps)
    return 100.0 * least / (sum(ex) / 1e9)


def mfu(run, phases: tuple[str, ...]) -> float | None:
    """Model FLOPs of the steps of ``phases`` in the traced window, over
    the window's length times the chip's peak, in %."""
    if run.trace is None or not run.trace["devices"]:
        return None
    flops = sum(f for ph in phases for f, _ in work(run, ph))
    if not flops:
        return None
    return 100.0 * flops / (tr.window_s(run.trace)
                            * run.peaks["bf16_flops_per_s"])
