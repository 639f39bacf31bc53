"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler writes and keeps what
the reduction needs, as plain lists: every event of the device planes' op
and module lines, and the benchmark's own host spans (``bench.*``).
``reduce`` then gives, inside the ``bench.window`` span:

* busy seconds: the union of the intervals in which an op ran, per chip,
  averaged over the chips that ran any; idle share is 1 - busy / window;
* device time by op name (loops, whose events span the ops inside them,
  left out);
* the jitted decode and prefill-chunk steps: how many ran and their
  device time, and the paged-attention kernel's time inside each kind;
* the longest idle gaps, each named by the host span around its middle.

The names matched (``NAMES``) are those the trace of the served program
shows on a TPU v5e, checked by hand on a recorded trace; ``PERF.md`` lists
them. A name that stops matching leaves its numbers empty: the readers
then return nothing, never 0.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

NAMES = {
    "device_plane": r"^/device:TPU:\d+$",
    "op_line": "XLA Ops",
    "module_line": "XLA Modules",
    "decode_module": r"^jit_decode\b",
    "prefill_module": r"^jit_chunk\b",
    "paged_kernel": r"^%paged_(decode|prefill)\.\d+ = ",
    "container_op": r"^%(while|conditional|call)\.\d+ = ",
    "host_span": r"^bench\.",
}

def load(path: str, names: dict = NAMES) -> dict:
    """The parts of an ``.xplane.pb`` the reduction reads, as plain data:
    ``{"device": [{"plane", "ops": [...], "modules": [...]}],
    "host": [...]}``, every event ``[name, start_ns, end_ns]``."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), names)


def from_profile(pd, names: dict = NAMES) -> dict:
    dev_re = re.compile(names["device_plane"])
    host_re = re.compile(names["host_span"])
    out = {"device": [], "host": []}
    for plane in pd.planes:
        if dev_re.match(plane.name):
            rec = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {names["op_line"]: "ops",
                       names["module_line"]: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    rec[key].append([e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)])
            out["device"].append(rec)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if host_re.match(e.name):
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.start_ns
                                                  + e.duration_ns)])
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # mean over the chips that ran ops
    op_s: Dict[str, float]             # device seconds by op name
    decode_steps: int
    decode_s: float                    # device seconds of decode steps
    prefill_steps: int
    prefill_s: float
    paged_decode_s: float              # paged-kernel seconds in decode
    paged_prefill_s: float
    idle_gaps: List[Tuple[str, float]]  # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def _op_key(ev) -> str:
    """An op event's name is its HLO instruction (``%fusion.12 = bf16[...]
    fusion(...)``); ops are grouped by the instruction's name with the
    instance number dropped (``fusion.12`` and ``fusion.7`` are both
    ``fusion``)."""
    m = re.match(r"%?([^\s=]+?)(\.\d+)? = ", ev[0])
    return m.group(1) if m else ev[0]


def reduce(trace: dict, names: dict = NAMES) -> Optional[Reduction]:
    """The window's numbers, or None when the trace has no window span or
    no device ops in it."""
    wins = [h for h in trace["host"] if h[0] == "bench.window"]
    if not wins:
        return None
    lo, hi = wins[0][1], wins[0][2]
    busy, chips = [], 0
    op_s: Dict[str, float] = {}
    dec_n = pre_n = 0
    dec_s = pre_s = pdec = ppre = 0.0
    gaps: List[Tuple[float, float, float]] = []
    host = sorted((h for h in trace["host"] if h[0] != "bench.window"),
                  key=lambda h: h[1])
    for dev in trace["device"]:
        ivs = []
        for ev in dev["ops"]:
            a, b = _clip(ev[1], ev[2], lo, hi)
            if b > a:
                ivs.append((a, b))
                # a loop's event spans the ops inside it: busy, not an op
                if not re.match(names["container_op"], ev[0]):
                    k = _op_key(ev)
                    op_s[k] = op_s.get(k, 0.0) + (b - a) * 1e-9
        if not ivs:
            continue
        chips += 1
        union = _union(ivs)
        busy.append(sum(b - a for a, b in union) * 1e-9)
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
        mods = [m for m in dev["modules"] if lo <= m[1] and m[2] <= hi]
        paged = [ev for ev in dev["ops"] if re.match(names["paged_kernel"],
                                                     ev[0])
                 and lo <= ev[1] and ev[2] <= hi]
        for m in mods:
            inside = sum((e[2] - e[1]) * 1e-9 for e in paged
                         if m[1] <= e[1] and e[2] <= m[2])
            if re.search(names["decode_module"], m[0]):
                dec_n += 1
                dec_s += (m[2] - m[1]) * 1e-9
                pdec += inside
            elif re.search(names["prefill_module"], m[0]):
                pre_n += 1
                pre_s += (m[2] - m[1]) * 1e-9
                ppre += inside
    if not chips:
        return None
    gaps.sort(reverse=True)
    named = [(_host_at(host, (a + b) / 2), d * 1e-9) for d, a, b in gaps[:10]]
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / chips,
                     op_s=op_s, decode_steps=dec_n,
                     decode_s=dec_s, prefill_steps=pre_n, prefill_s=pre_s,
                     paged_decode_s=pdec, paged_prefill_s=ppre,
                     idle_gaps=named)


def _host_at(host: List[list], t: float) -> str:
    """The innermost benchmark span around ``t`` (the latest-starting one
    that covers it), or ``driver``: the benchmark's own loop, between its
    calls into the engine."""
    best = "driver"
    for name, a, b in host:
        if a > t:
            break
        if b >= t:
            best = name
    return best
