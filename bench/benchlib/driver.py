"""The client side of a run: it submits requests to the frontend
(``AsyncEngine.submit``) when they fall due, turns the frontend's serving
loop, and takes every token off its ``TokenStream`` the moment the turn
that delivered it returns.

One thread does all three. The frontend's loop drains submissions only at
the top of a turn, so a request submitted from another thread during a
turn would wait for that turn's end just the same; how late each
submission was against its due time is recorded. A request's clock starts
at its due time.

Besides what the client sees, a run samples a few of the program's
counters around each turn: pages in use (every turn in the window), each
request's prompt tokens with K/V in the pool (``Request.num_computed``) at
the window's edges, and, while a trace is being taken, what the turn's
step carried (``EngineStats`` step counters, the chunks that
``num_computed`` advanced by, and the decode lanes with their contexts).
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from benchlib.traffic import Req

SLOW_TURN_S = 0.75     # a loop turn and its drain longer than any step
@dataclass
class Record:
    req: Req
    due: float                    # perf_counter when due (closed: when sent)
    submit: float = 0.0
    stream: object = None
    tokens: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)
    closed: Optional[float] = None
    reason: Optional[str] = None
    nc0: int = 0                  # prompt tokens in the pool at window start
    nc1: int = 0                  # ...and at window end

    @property
    def prompt_len(self) -> int:
        return len(self.req.prompt)


@dataclass
class StepSample:
    """What one dispatched step carried, from counters around its turn."""
    t: float
    kind: str                     # "decode" (decode lanes only) | "prefill"
    chunks: List[tuple]           # (start, n) prompt chunks
    decode_ctx: List[int]         # keys attended by each decode lane


class OpenSource:
    def __init__(self, reqs: List[Req], t_start: float):
        self.pending = deque(sorted(reqs, key=lambda r: r.due))
        self.t_start = t_start

    def due(self, now: float):
        out = []
        while self.pending and self.t_start + self.pending[0].due <= now:
            r = self.pending.popleft()
            out.append((r, self.t_start + r.due))
        return out

    def next_due(self) -> float:
        return (self.t_start + self.pending[0].due if self.pending
                else float("inf"))

    def on_close(self, rec: Record, now: float) -> None:
        pass


class ClosedSource:
    def __init__(self, per_client: List[List[Req]], t_start: float):
        self.queues = [deque(q) for q in per_client]
        self.ready = [(q.popleft(), t_start) for q in self.queues if q]

    def due(self, now: float):
        out, self.ready = self.ready, []
        return out

    def next_due(self) -> float:
        return float("inf")

    def on_close(self, rec: Record, now: float) -> None:
        q = self.queues[rec.req.client]
        if q:
            self.ready.append((q.popleft(), now))


class Driver:
    def __init__(self, frontend, source, *, t0: float, t1: float,
                 trace: Optional[tuple] = None,
                 annotate: Optional[Callable] = None):
        """``t0``/``t1``: the window; ``trace``: (start, stop) callables
        and their (t_start, t_stop) times, or None; ``annotate``: a context
        manager factory for host spans (``jax.profiler.TraceAnnotation``)."""
        self.fe = frontend
        self.src = source
        self.t0, self.t1 = t0, t1
        self.trace = trace
        self.ann = annotate or (lambda name: contextlib.nullcontext())
        self.records: List[Record] = []
        self.open: List[Record] = []
        self.in_flight: List[Record] = []     # open when the window closed
        self.steps_at_t0 = (0, 0)             # (prefill, decode) step counts
        self.slow_turns: List[tuple] = []     # (seconds into window, length)
        self.peak_pages = 0
        self.pool_pages = 0
        self.steps: List[StepSample] = []
        self.trace_span = None        # (perf_counter start, stop)
        self._in_window = False
        self._tracing = False

    # ------------------------------------------------------------ client --
    def _submit(self, req: Req, due: float) -> None:
        with self.ann("bench.submit"):
            rec = Record(req=req, due=due)
            rec.submit = time.perf_counter()
            rec.stream = self.fe.submit(req.prompt, max_new_tokens=req.max_new)
        self.records.append(rec)
        self.open.append(rec)

    def _drain(self) -> List[Record]:
        now = time.perf_counter()
        closed = []
        with self.ann("bench.drain"):
            for rec in self.open:
                while True:
                    try:
                        tok = rec.stream.get(timeout=0)
                    except TimeoutError:
                        break
                    if tok is None:
                        rec.closed = now
                        fr = rec.stream.finish_reason
                        rec.reason = fr.value if fr is not None else "?"
                        closed.append(rec)
                        break
                    rec.tokens.append(tok)
                    rec.times.append(now)
        if closed:
            self.open = [r for r in self.open if r.closed is None]
        return closed

    # ---------------------------------------------------------- counters --
    def _snapshot_nc(self, attr: str) -> None:
        for rec in self.records:
            setattr(rec, attr, rec.stream.req.num_computed)

    def _turn(self) -> None:
        stats = self.fe.engine.stats
        if not self._tracing:
            with self.ann("bench.turn"):
                self.fe.run_until_idle(max_steps=1)
            return
        before = [(r, r.stream.req.num_computed,
                   len(r.stream.req.output) + r.stream.req.inflight)
                  for r in self.open]
        pc, dc = stats.prefill_calls, stats.decode_steps
        with self.ann("bench.turn"):
            self.fe.run_until_idle(max_steps=1)
        dp, dd = stats.prefill_calls - pc, stats.decode_steps - dc
        if not (dp or dd):
            return
        chunks, ctx = [], []
        for rec, nc, g in before:
            req = rec.stream.req
            if req.num_computed > nc:
                chunks.append((nc, req.num_computed - nc))
            elif req.inflight + len(req.output) > g:
                ctx.append(rec.prompt_len + g)
        self.steps.append(StepSample(time.perf_counter(),
                                     "prefill" if dp else "decode",
                                     chunks, ctx))

    # -------------------------------------------------------------- loop --
    def run(self) -> None:
        """Serve until the window closes."""
        tr_start = self.trace[1][0] if self.trace else float("inf")
        tr_stop = self.trace[1][1] if self.trace else float("inf")
        while True:
            now = time.perf_counter()
            if not self._in_window and now >= self.t0:
                self._in_window = True
                self._snapshot_nc("nc0")
                st = self.fe.engine.stats
                self.steps_at_t0 = (st.prefill_calls, st.decode_steps)
            if now >= self.t1:
                break
            if self.trace and not self._tracing and now >= tr_start \
                    and self.trace_span is None:
                self.trace[0][0]()
                self._tracing = True
                self.trace_span = [time.perf_counter(), None]
            elif self._tracing and now >= tr_stop:
                self.trace_span[1] = time.perf_counter()
                self.trace[0][1]()
                self._tracing = False
            for req, due in self.src.due(now):
                self._submit(req, due)
            if not self.open:
                nxt = min(self.src.next_due(), self.t1, tr_start
                          if self.trace_span is None else float("inf"))
                if nxt > now:
                    time.sleep(nxt - now)
                continue
            t_turn = time.perf_counter()
            self._turn()
            for rec in self._drain():
                self.src.on_close(rec, rec.closed)
            dt = time.perf_counter() - t_turn
            if self._in_window and dt > SLOW_TURN_S:
                self.slow_turns.append((t_turn - self.t0, dt))
            if self._in_window:
                st = self.fe.engine.stats
                self.peak_pages = max(self.peak_pages, st.pages_in_use)
                self.pool_pages = st.pool_pages
        if self._tracing:
            self.trace_span[1] = time.perf_counter()
            self.trace[0][1]()
            self._tracing = False
        self._snapshot_nc("nc1")
        self.in_flight = list(self.open)

    def stop(self) -> None:
        """Cancel what is still open and let the frontend drain it."""
        for rec in self.open:
            self.fe.cancel(rec.stream)
        self.fe.run_until_idle()
        self._drain()
