"""Client-side drivers: an open loop that submits on the due time, and a
closed loop that keeps every slot busy with a backlog.

Both drive ``ServeEngine.submit`` / ``ServeEngine.step`` from one thread
and stamp each output token with the host clock when ``step`` returns it:
the time a client streaming from the engine would see it. Nothing here
reads the engine's own timestamps. A ``TraceAnnotation`` span marks each
call into the engine (``bench.submit``, ``bench.step``), the idle waits
(``bench.wait``) and the measured window (``bench.window``), so a device
trace can say what the host was doing in each gap.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from .traffic import Arrival

# request states, by name: the driver never imports the program's enums
FINAL = ("DONE", "FAILED", "CANCELLED")


@dataclasses.dataclass
class Sent:
    """One request as the client saw it: due and submit times (host
    clock), the engine's request object, and a stamp per output token."""
    arrival: Arrival
    req: object
    due: float
    submitted: float
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def state(self) -> str:
        return self.req.state.name

    @property
    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclasses.dataclass
class Tick:
    """One ``engine.step`` call: when it ran, and the context length (keys
    attended, the new token included) of each slot it decoded."""
    t0: float
    t1: float
    decode_ctx: List[int]


class Driver:
    """Submits requests to one engine and records what comes back."""

    def __init__(self, engine, clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.sent: List[Sent] = []
        self.ticks: List[Tick] = []
        self._live: List[Sent] = []
        self.lateness: List[float] = []   # submit - due, open loop

    def submit(self, arrival: Arrival, due: float) -> Sent:
        with TraceAnnotation("bench.submit"):
            req = self.engine.submit(arrival.prompt, arrival.gen_len)
        now = self.clock()
        s = Sent(arrival=arrival, req=req, due=due, submitted=now)
        self.sent.append(s)
        self._live.append(s)
        return s

    @property
    def busy(self) -> bool:
        return bool(self.engine.scheduler.waiting or self.engine.active)

    def step(self) -> List[Sent]:
        """One engine tick; returns the requests that ended in it."""
        t0 = self.clock()
        with TraceAnnotation("bench.step"):
            self.engine.step()
        t1 = self.clock()
        ctx, ended, live = [], [], []
        for s in self._live:
            n = len(s.req.tokens)
            new = n - len(s.stamps)
            if new > 0:
                decoded = new - (1 if not s.stamps else 0)
                s.stamps.extend([t1] * new)
                if decoded > 0:
                    # the decode fed token n-1 at position prompt + n - 2,
                    # attending every row up to and including it
                    ctx.append(len(s.arrival.prompt) + n - 1)
            if s.state in FINAL:
                ended.append(s)
            else:
                live.append(s)
        self._live = live
        self.ticks.append(Tick(t0, t1, ctx))
        return ended

    def idle_until(self, t: float):
        with TraceAnnotation("bench.wait"):
            time.sleep(max(0.0, min(0.005, t - self.clock())))


@dataclasses.dataclass
class Window:
    """The measured window on the host clock, and the traffic start."""
    traffic_start: float
    start: float
    end: float
    closed: float          # when the driver stopped (after any drain)


class _Hooks:
    """What a run does at the window's edges (tracing); no-ops by default."""

    def before_window(self):
        pass

    def after_drain(self):
        pass


def run_open_loop(driver: Driver, arrivals: List[Arrival], *, pre_roll: float,
                  seconds: float, drain_cap: float, hooks=None,
                  trace_lead: float = 3.0) -> Window:
    """Submit each arrival when it is due, stepping the engine in between.
    The window is ``[pre_roll, pre_roll + seconds)`` after the traffic
    start. After it closes the load goes on until every request due in it
    has its first token (or failed), for at most ``drain_cap`` seconds, so
    that a late answer is timed and not dropped."""
    hooks = hooks or _Hooks()
    clock = driver.clock
    t0 = clock()
    ws, we = t0 + pre_roll, t0 + pre_roll + seconds
    i, hooked, window, window_open = 0, False, None, False
    due_in_window: List[Sent] = []
    while True:
        now = clock()
        if not hooked and now >= ws - trace_lead:
            hooks.before_window()
            hooked = True
            now = clock()
        while i < len(arrivals) and t0 + arrivals[i].due <= now:
            due = t0 + arrivals[i].due
            s = driver.submit(arrivals[i], due)
            driver.lateness.append(s.submitted - due)
            if ws <= due < we:
                due_in_window.append(s)
            i += 1
        if window is None and now >= ws:
            window = TraceAnnotation("bench.window")
            window.__enter__()
            window_open = True
        if window_open and now >= we:
            window.__exit__(None, None, None)
            window_open = False
        if now >= we:
            pending = [s for s in due_in_window
                       if not s.stamps and s.state not in FINAL]
            if not pending or now >= we + drain_cap:
                break
        if driver.busy:
            driver.step()
        elif i < len(arrivals):
            driver.idle_until(t0 + arrivals[i].due)
        else:
            break
    closed = clock()
    hooks.after_drain()
    return Window(traffic_start=t0, start=ws, end=we, closed=closed)


def run_closed_loop(driver: Driver, arrivals: List[Arrival], *, slots: int,
                    backlog: int, seconds: float, fill_cap: float,
                    hooks=None, trace_lead: float = 3.0) -> Window:
    """Keep ``slots + backlog`` requests in the engine: a new one is
    submitted as each ends. Once every slot is decoding (or after
    ``fill_cap`` seconds) the hooks run, and ``trace_lead`` seconds later
    the window opens; it lasts ``seconds``. (A profiler stalls the device
    for a while after it starts; the lead keeps that out of the window.)"""
    hooks = hooks or _Hooks()
    clock = driver.clock
    t0 = clock()
    feed = iter(arrivals)

    def top_up(n: int):
        for _ in range(n):
            a = next(feed, None)
            if a is None:
                raise RuntimeError("closed loop ran out of requests; raise "
                                   "n_requests in the mix")
            driver.submit(a, clock())

    top_up(slots + backlog)
    ready = ws = we = None
    window = None
    while True:
        ended = driver.step()
        top_up(len(ended))
        now = clock()
        if ready is None and (driver.engine.running >= slots
                              or now - t0 >= fill_cap):
            hooks.before_window()
            ready = clock()
        if ws is None and ready is not None and now >= ready + trace_lead:
            ws = now
            we = ws + seconds
            window = TraceAnnotation("bench.window")
            window.__enter__()
        if we is not None and now >= we:
            window.__exit__(None, None, None)
            break
    closed = clock()
    hooks.after_drain()
    return Window(traffic_start=t0, start=ws, end=we, closed=closed)
