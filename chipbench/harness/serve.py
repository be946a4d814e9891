"""Drive a cell's traffic through the fleet front end, on the wall clock.

The served path is the one ``python -m repro.launch.serve --engine fleet``
takes: ``FleetFrontend`` over a one-replica ``FleetEngine`` over
``PagedServeEngine``, with its jitted ``chunk_fn`` and ``decode_fn``.  The
loop here is the client: it submits each request when it is due (open
loop) or when one of its outstanding requests finishes (closed loop),
calls ``front.tick()`` while there is work, and stamps every streamed
token with the host clock.  Between ticks it reads the requests' own
progress (``prefill_pos``, ``generated``, ``admit_seq``) to know what
each tick did: which prompt chunk ran, and which rows decoded at what
context length.  Those counts give the work of every step.
"""

from __future__ import annotations

import dataclasses
import time

import jax

from harness import trace as tr
from harness.traffic import Item, Traffic

#: how long past the window's close the loop waits for the first token of
#: a request that was due inside the window
DRAIN_LIMIT_S = 60.0


@dataclasses.dataclass
class Req:
    item: Item
    due: float                       # host clock (perf_counter seconds)
    submitted: float | None = None
    admitted: float | None = None    # start of the tick that gave it a slot
    slot: int | None = None          # the decode row it was given
    token_times: list[float] = dataclasses.field(default_factory=list)
    handle: object = None            # the front end's stream, while serving
    refused: str | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False

    def detach(self) -> None:
        """Keep the served tokens and drop the stream, which holds the
        program's state through its callback."""
        if self.handle is not None:
            self.tokens, self.done = list(self.handle.tokens), self.handle.done
            self.handle = None

    @property
    def plen(self) -> int:
        return len(self.item.prompt)


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    chunk: tuple[int, int, bool] | None   # (start position, tokens, last)
    decode: list[int]                     # context of every decoded row


@dataclasses.dataclass
class Run:
    """What one run leaves for the metric readers."""

    setup_s: float
    open: float                      # host clock at the window's opening
    close: float
    cut: float                       # when the loop stopped
    reqs: list[Req]
    ticks: list[Tick]
    conf: dict                       # the configuration file
    arch: object                     # its arch module (work counts)
    peaks: dict
    trace: dict | None = None        # harness.trace plain form
    trace_span: tuple[float, float] | None = None   # host clock
    step_hbm_bytes: int | None = None

    def in_window(self, t: float) -> bool:
        return self.open <= t < self.close

    @property
    def window_reqs(self) -> list[Req]:
        """Requests due inside the window."""
        return [r for r in self.reqs if self.in_window(r.due)]

    def traced_ticks(self) -> list[Tick]:
        if self.trace_span is None:
            return []
        a, b = self.trace_span
        return [t for t in self.ticks if a <= t.start and t.end <= b]


class Loop:
    """One run's client loop over a front end."""

    def __init__(self, front, traffic: Traffic, *, t0: float,
                 seconds: float, trace_dir: str | None = None,
                 trace_s: float = 0.0, first_uid: int = 1):
        self.front = front
        self.traffic = traffic
        self.t0 = t0
        self.open = t0 + traffic.ramp_s
        self.close = self.open + seconds
        self.reqs: dict[int, Req] = {}
        self.ticks: list[Tick] = []
        self.backlog: list[Req] = []
        self.live: set[int] = set()
        self.first_uid = first_uid
        self.next_item = 0
        self.trace_dir = trace_dir
        self.trace_from = max(self.open + 1.0, self.close - trace_s)
        self.trace_span: tuple[float, float] | None = None
        self._tracing = None
        self._profiling = False
        self._next: Item | None = None

    # -- requests --------------------------------------------------------------

    def _peek(self) -> Item:
        if self._next is None or self._next.index != self.next_item:
            self._next = self.traffic.item(self.next_item)
        return self._next

    def _new(self, due: float) -> Req:
        item = self._peek()
        self.next_item += 1
        req = Req(item, due)
        self.reqs[self.first_uid + item.index] = req
        self.backlog.append(req)
        return req

    def _arrivals(self, now: float) -> None:
        if self.traffic.closed:
            while len(self.backlog) + len(self.live) \
                    < self.traffic.outstanding:
                self._new(now)
            return
        while True:
            item = self._peek()
            if self.t0 + item.due_s > now:
                return
            self._new(self.t0 + item.due_s)

    def _submit(self) -> None:
        from repro.serve.frontend import Backpressure

        while self.backlog:
            req = self.backlog[0]
            uid = self.first_uid + req.item.index
            try:
                with jax.profiler.TraceAnnotation("submit"):
                    req.handle = self.front.submit(
                        req.item.prompt, req.item.n_new, uid=uid,
                        on_token=self._on_token)
            except Backpressure:
                return
            except ValueError as e:          # unservable: never admitted
                req.refused = str(e)
            else:
                req.submitted = time.perf_counter()
                self.live.add(uid)
            self.backlog.pop(0)

    def _on_token(self, uid: int, token: int) -> None:
        self.reqs[uid].token_times.append(time.perf_counter())

    # -- ticks -----------------------------------------------------------------

    def _tick(self) -> None:
        before = {}
        for uid in self.live:
            r = self.reqs[uid].handle.request
            before[uid] = (r.prefill_pos, len(r.generated), r.admit_seq)
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.TICK):
            self.front.tick()
        end = time.perf_counter()
        chunk, decode = None, []
        for uid, (pp, gen, seq) in before.items():
            req = self.reqs[uid]
            r = req.handle.request
            if r.prefill_pos > pp:
                chunk = (pp, r.prefill_pos - pp, r.prefill_pos == req.plen)
            # the k-th generated token (k >= 2) comes from a decode step
            # that attends to plen + k - 1 tokens; the first comes from the
            # chunk that completes the prompt
            decode += [req.plen + k - 1
                       for k in range(max(gen + 1, 2), len(r.generated) + 1)]
            if seq < 0 <= r.admit_seq:
                req.admitted = start
                req.slot = r.slot
            if req.handle.settled:
                self.live.discard(uid)
        self.ticks.append(Tick(start, end, chunk, decode))

    # -- tracing ---------------------------------------------------------------

    def _trace(self, now: float) -> None:
        """Start the profiler a second before the traced window, so that
        its own start-up falls outside it; open the window's span at
        ``trace_from`` and close both at the window's close."""
        if self.trace_dir is None or self.trace_span is not None \
                and self._tracing is None:
            return
        if not self._profiling and now >= self.trace_from - 1.0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._profiling = True
        if self._profiling and self._tracing is None \
                and now >= self.trace_from:
            self._tracing = jax.profiler.TraceAnnotation(tr.WINDOW)
            self._tracing.__enter__()
            self.trace_span = (time.perf_counter(), None)
        elif self._tracing is not None and now >= self.close:
            # a chunk that decoded nothing may still run: let it finish
            # inside the window it was dispatched in
            for r in self.front.fleet.replicas:
                jax.block_until_ready(r.engine.cache)
            self._tracing.__exit__(None, None, None)
            self.trace_span = (self.trace_span[0], time.perf_counter())
            self._tracing = None
            jax.profiler.stop_trace()

    # -- the loop --------------------------------------------------------------

    def _pending_first_tokens(self) -> bool:
        return any(not r.token_times and r.refused is None
                   for r in self.reqs.values()
                   if self.open <= r.due < self.close)

    def run(self) -> float:
        """Serve until the window has closed and every request due in it
        has its first token, or ``DRAIN_LIMIT_S`` past the close.  Returns
        the host time at which the loop stopped."""
        while True:
            now = time.perf_counter()
            self._trace(now)
            if now >= self.close and (now >= self.close + DRAIN_LIMIT_S
                                      or not self._pending_first_tokens()):
                return now
            self._arrivals(now)
            self._submit()
            if self.live:
                self._tick()
                continue
            wake = self.close if self.traffic.closed else min(
                self.t0 + self._peek().due_s,
                self.close if now < self.close else now + 0.05)
            if self.trace_dir is not None and self.trace_span is None \
                    or self._tracing is not None:
                wake = min(wake, now + 0.05)    # the trace's edges are due
            with jax.profiler.TraceAnnotation("await_arrival"):
                time.sleep(max(0.0, wake - time.perf_counter()))
