"""Seeded traffic from a mix file: arrivals, lengths and shared headers.

A copy of ``repro.serve.workload.generate`` (Poisson arrivals with a burst
window, clipped lognormal lengths), extended in three ways:

* Sizes and arrival times are drawn once from the mix's own
  ``master_seed``; ``--seed`` only orders the sizes within each block of
  ``block`` consecutive requests (a block of 1 keeps the drawn order) and
  draws the token ids. So every seed offers the same work in any stretch
  of time; with blocks above 1, two seeds differ in which request is
  long, not in how much there is to do. Within a block the sizes are
  stratified quantiles of the lognormal, so the heavy tail is present in
  proportion, up to the clip.
* Optional shared headers (system prompts, documents): ``groups`` headers
  with Zipf popularity; a request with a header sends it followed by its
  own suffix, which is what a prefix cache can reuse.
* Open-loop arrivals are times after the start of the traffic; the driver
  counts latency from them (the due time), not from when it got round to
  submitting.

A mix file (``traffic/<name>.json``) holds::

    {"loop": "open" | "closed",
     "prompt_len": {"median": 512, "sigma": 0.8, "min": 32, "max": 3072},
     "gen_len":    {"median": 128, "sigma": 0.7, "min": 8,  "max": 768},
     "block": 16, "master_seed": 0,
     "burst": {"start_frac": 0.4, "len_frac": 0.2, "mult": 3.0},  # open
     "headers": {"groups": 32, "len": {...}, "zipf_s": 1.0, "share": 1.0},
     "pre_roll_s": 20, "drain_cap_s": 60,                        # open
     "backlog": 8, "n_requests": 256}                            # closed

and the cell's workload file adds ``rate_rps`` for an open loop. Burst
fractions are of the measured window.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request: ``prompt`` (header + suffix), ``gen_len`` new tokens,
    due ``due`` seconds after the traffic starts (None in a closed loop),
    ``group`` its header group (-1: none)."""
    due: Optional[float]
    prompt: np.ndarray
    gen_len: int
    group: int = -1


def lognormal_quantile(spec: dict, u: float) -> int:
    """The ``u`` quantile of the clipped lognormal ``spec``, as a length."""
    x = float(np.exp(np.log(spec["median"]) + spec["sigma"]
                     * _NORMAL.inv_cdf(min(max(u, 1e-12), 1 - 1e-12))))
    return int(np.clip(round(x), spec["min"], spec["max"]))


def stratified_lengths(spec: dict, n: int, block: int,
                       master: np.random.Generator) -> np.ndarray:
    """``n`` lengths, block by block: block b holds the quantiles
    ``(i + u_bi) / block`` for i < block, with ``u_bi`` from ``master``."""
    out = []
    for b in range(-(-n // block)):
        u = (np.arange(block) + master.random(block)) / block
        out.extend(lognormal_quantile(spec, float(x)) for x in u)
    return np.asarray(out[:n], np.int64)


def permute_blocks(values: np.ndarray, block: int,
                   rng: np.random.Generator) -> np.ndarray:
    """``values`` reordered within each block of ``block`` by ``rng``."""
    out = values.copy()
    for lo in range(0, len(values), block):
        seg = out[lo:lo + block]
        out[lo:lo + block] = seg[rng.permutation(len(seg))]
    return out


def arrival_times(rate: float, horizon: float, burst_lo: float,
                  burst_hi: float, mult: float,
                  rng: np.random.Generator) -> List[float]:
    """Poisson arrivals at ``rate`` per second up to ``horizon``; the rate
    is multiplied by ``mult`` while the previous arrival lies in
    ``[burst_lo, burst_hi)`` (as ``serve.workload.generate`` does)."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    times, t = [], 0.0
    while True:
        r = rate * (mult if burst_lo <= t < burst_hi else 1.0)
        t += float(rng.exponential(1.0 / r))
        if t >= horizon:
            return times
        times.append(t)


def zipf_groups(n: int, groups: int, s: float,
                master: np.random.Generator) -> np.ndarray:
    """Header group of each of ``n`` requests, Zipf(``s``) over ranks."""
    p = 1.0 / np.arange(1, groups + 1, dtype=np.float64) ** s
    return master.choice(groups, size=n, p=p / p.sum())


def generate(mix: dict, seed: int, vocab: int, *,
             seconds: float) -> List[Arrival]:
    """Materialise a mix for one run. The same (mix, seed, seconds) gives
    byte-identical arrivals. ``seconds`` is the measured window: an open
    loop offers load through pre-roll, window and drain cap, with the
    burst inside the window."""
    block = int(mix.get("block", 16))
    master = np.random.default_rng(int(mix.get("master_seed", 0)))
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        pre = float(mix["pre_roll_s"])
        burst = mix.get("burst") or {}
        lo = pre + float(burst.get("start_frac", 0.0)) * seconds
        hi = lo + float(burst.get("len_frac", 0.0)) * seconds
        horizon = pre + seconds + float(mix["drain_cap_s"])
        dues: List[Optional[float]] = list(arrival_times(
            float(mix["rate_rps"]), horizon, lo, hi,
            float(burst.get("mult", 1.0)), master))
    elif mix["loop"] == "closed":
        dues = [None] * int(mix["n_requests"])
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = len(dues)
    plens = permute_blocks(stratified_lengths(
        mix["prompt_len"], n, block, master), block, rng)
    glens = permute_blocks(stratified_lengths(
        mix["gen_len"], n, block, master), block, rng)
    hdr = mix.get("headers")
    groups = np.full(n, -1)
    headers: List[np.ndarray] = []
    if hdr:
        g = int(hdr["groups"])
        hlens = stratified_lengths(hdr["len"], g, g, master)
        with_header = master.random(n) < float(hdr.get("share", 1.0))
        groups = np.where(with_header, zipf_groups(
            n, g, float(hdr["zipf_s"]), master), -1)
        groups = permute_blocks(groups, block, rng)
        headers = [rng.integers(1, vocab, int(k)).astype(np.int32)
                   for k in hlens]
    out = []
    for i in range(n):
        suffix = rng.integers(1, vocab, int(plens[i])).astype(np.int32)
        prompt = (np.concatenate([headers[groups[i]], suffix])
                  if groups[i] >= 0 else suffix)
        out.append(Arrival(due=dues[i], prompt=prompt,
                           gen_len=int(glens[i]), group=int(groups[i])))
    return out
