"""The program's own spans, read off a run's profiler trace beside the
device's ops, on the same clock.

The engine names each phase of its tick with a ``serve.*`` span
(``serve.step`` around ``serve.admit``, ``serve.prefill`` ⊃
``serve.prefill_chunk`` ⊃ ``serve.splice``, ``serve.decode``,
``serve.sample``, ``serve.emit`` ⊃ ``serve.finish``); the driver adds its
``bench.*`` spans around each call into the engine. ``reduce`` gives,
inside the ``bench.window`` span:

* ``ticks``: the ``serve.step`` spans that start inside the window, each
  with its direct ``serve.*`` children (matched by interval containment)
  and the device's idle time inside it;
* ``idle_by_span``: the device's idle time inside the window (the
  complement of the union of its op intervals, per chip, as
  ``tracefile.reduce`` builds it, averaged over the chips that ran ops),
  cut at span edges, each piece charged to the innermost span covering it
  (``serve.*`` or ``bench.*``), or else to ``driver``;
* ``gaps``: the longest idle gaps, each with the spans its pieces fall in.

A trace with no ``serve.step`` inside the window (a program without the
spans) reduces to None: the readers then return nothing, never 0.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
from typing import Dict, List, Optional, Tuple

from . import cell, tracefile

NAMES = dict(tracefile.NAMES, host_span=r"^(bench|serve)\.")


@dataclasses.dataclass
class Span:
    name: str
    start: float                       # ns, the trace's clock
    end: float
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def ns(self) -> float:
        return self.end - self.start

    def named(self, name: str) -> List["Span"]:
        """The direct children called ``name``."""
        return [c for c in self.children if c.name == name]

    def has(self, name: str) -> bool:
        return any(c.name == name for c in self.children)


@dataclasses.dataclass
class Tick(Span):
    idle_ns: float = 0.0               # device idle inside the tick


@dataclasses.dataclass
class Gap:
    start: float                       # ns
    ns: float
    pieces: Dict[str, float]           # span name -> ns of the gap in it


@dataclasses.dataclass
class Spans:
    window: Tuple[float, float]        # ns
    ticks: List[Tick]
    idle_by_span: Dict[str, float]     # ns, inside the window
    gaps: List[Gap]                    # longest first

    def decode_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if t.has("serve.decode")]


def _tree(spans: List[list]) -> List[Span]:
    """Nest ``serve.*`` spans by interval containment: each span's parent
    is the innermost span that holds it. Returns the top-level spans."""
    top: List[Span] = []
    stack: List[Span] = []
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        node = Span(name, a, b) if name != "serve.step" else Tick(name, a, b)
        while stack and not (stack[-1].start <= a and b <= stack[-1].end):
            stack.pop()
        (stack[-1].children if stack else top).append(node)
        stack.append(node)
    return top


def reduce(trace: dict) -> Optional[Spans]:
    """The window's program spans and the device's idle time in them, from
    a ``tracefile.load`` dict loaded with ``NAMES``; None without a window
    span, without device ops or without a ``serve.step`` in the window."""
    wins = [h for h in trace["host"] if h[0] == "bench.window"]
    if not wins:
        return None
    lo, hi = wins[0][1], wins[0][2]
    host = [h for h in trace["host"] if h[0] != "bench.window"]
    ticks = [t for t in _tree([h for h in host if h[0].startswith("serve.")])
             if isinstance(t, Tick) and lo <= t.start < hi]
    if not ticks:
        return None
    # idle is read up to the end of the last tick that starts in the window
    top = max([hi] + [t.end for t in ticks])

    # elementary segments between span edges, each owned by the innermost
    # span covering it (the latest-starting, as tracefile._host_at names it)
    edges = sorted({lo, hi, top} | {min(max(x, lo), top)
                                    for h in host for x in h[1:]})
    owner: List[Optional[str]] = [None] * (len(edges) - 1)
    for name, a, b in sorted(host, key=lambda h: (h[1], -h[2])):
        i = bisect.bisect_left(edges, max(a, lo))
        j = bisect.bisect_left(edges, min(b, top))
        for k in range(i, j):
            owner[k] = name

    idle = [0.0] * (len(edges) - 1)
    gaps: List[Gap] = []
    chips = 0
    for dev in trace["device"]:
        ivs = [(max(e[1], lo), min(e[2], top)) for e in dev["ops"]]
        union = tracefile._union([iv for iv in ivs if iv[1] > iv[0]])
        if not union:
            continue
        chips += 1
        bounds = [lo] + [x for iv in union for x in iv] + [top]
        for a, b in zip(bounds[0::2], bounds[1::2]):
            if b <= a:
                continue
            gap = Gap(a, min(b, hi) - a if a < hi else 0.0, {})
            k = bisect.bisect_right(edges, a) - 1
            while k < len(idle) and edges[k] < b:
                piece = min(b, edges[k + 1]) - max(a, edges[k])
                idle[k] += piece
                if edges[k] < hi:
                    who = owner[k] or "driver"
                    gap.pieces[who] = gap.pieces.get(who, 0.0) + piece
                k += 1
            if gap.ns > 0:
                gaps.append(gap)
    if not chips:
        return None
    idle = [x / chips for x in idle]

    by_span: Dict[str, float] = {}
    for k, x in enumerate(idle):
        if x > 0 and edges[k] < hi:
            who = owner[k] or "driver"
            by_span[who] = by_span.get(who, 0.0) + x
    for t in ticks:
        i = bisect.bisect_left(edges, t.start)
        j = bisect.bisect_left(edges, t.end)
        t.idle_ns = sum(idle[i:j])
    gaps.sort(key=lambda g: -g.ns)
    return Spans(window=(lo, hi), ticks=ticks, idle_by_span=by_span,
                 gaps=gaps[:10])


def trace_file() -> Optional[str]:
    """The run's ``.xplane.pb``: the newest under ``cell.TRACE_DIR``, as
    ``WindowHooks.after_drain`` finds it."""
    found = sorted(cell.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    return str(found[-1]) if found else None


_loaded: Dict[tuple, Optional[Spans]] = {}


def for_run(run) -> Optional[Spans]:
    """The program spans of a traced run, loaded once per trace file."""
    if run.trace is None:
        return None
    path = trace_file()
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = reduce(tracefile.load(path, NAMES))
    return _loaded[key]
