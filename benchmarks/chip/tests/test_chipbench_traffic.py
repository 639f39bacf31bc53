"""Traffic generation and the client-side drivers, with a fake engine on a
fake clock: schedules repeat byte for byte, seeds reorder the same work,
and latency runs from the due time."""
import enum
import json

import numpy as np
import pytest

import tinycell
from chipbench import driver as drv
from chipbench import traffic
from chipbench.cell import RunRecord, load_module, warm_groups

CHAT = json.loads((tinycell.BENCH / "traffic" / "chat.json").read_text())
OFFLINE = json.loads((tinycell.BENCH / "traffic" / "offline.json").read_text())
SHARED = dict(CHAT, rate_rps=2.0, headers={
    "groups": 4, "len": {"median": 64, "sigma": 0.3, "min": 16, "max": 128},
    "zipf_s": 1.0, "share": 1.0})


def _flat(arrivals):
    return [(a.due, a.gen_len, a.group, a.prompt.tobytes()) for a in arrivals]


@pytest.mark.parametrize("mix", [dict(CHAT, rate_rps=0.5), OFFLINE, SHARED],
                         ids=["open", "closed", "headers"])
def test_same_seed_same_schedule(mix):
    """The same seed gives the same arrivals, sizes and tokens."""
    a = traffic.generate(mix, 2**31 + 5, 152064, seconds=51)
    b = traffic.generate(mix, 2**31 + 5, 152064, seconds=51)
    assert _flat(a) == _flat(b) and len(a) > 16


def test_seeds_reorder_the_same_work():
    mix = dict(CHAT, rate_rps=0.5, block=16)
    a = traffic.generate(mix, 1, 1000, seconds=51)
    b = traffic.generate(mix, 2, 1000, seconds=51)
    assert [x.due for x in a] == [x.due for x in b]
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    for lo in range(0, len(a), 16):
        for key in (lambda x: len(x.prompt), lambda x: x.gen_len):
            assert (sorted(map(key, a[lo:lo + 16]))
                    == sorted(map(key, b[lo:lo + 16])))
    spec = CHAT["prompt_len"]
    assert all(spec["min"] <= len(x.prompt) <= spec["max"] for x in a)


def test_burst_raises_the_rate_inside_its_share_of_the_window():
    mix = dict(CHAT, rate_rps=2.0)
    dues = [x.due for x in traffic.generate(mix, 0, 100, seconds=50)]
    lo = mix["pre_roll_s"] + 0.4 * 50
    burst = sum(lo <= t < lo + 10 for t in dues)
    before = sum(lo - 10 <= t < lo for t in dues)
    assert burst > 2 * before


def test_headers_are_shared_prefixes_with_zipf_popularity():
    arr = traffic.generate(SHARED, 3, 1000, seconds=51)
    by_group = {}
    for a in arr:
        by_group.setdefault(a.group, []).append(a.prompt)
    counts = sorted((len(v) for v in by_group.values()), reverse=True)
    assert counts[0] > counts[-1]
    for prompts in by_group.values():
        n = min(len(p) for p in prompts)
        head = prompts[0][:16]
        assert all((p[:16] == head).all() for p in prompts) and n >= 16


def test_warm_groups_finds_equal_lengths_admitted_together():
    mk = lambda n: traffic.Arrival(0.0, np.zeros(n, np.int32), 4)  # noqa
    arr = [mk(n) for n in (10, 20, 10, 30, 40, 50, 10, 10)]
    assert warm_groups(arr, slots=3) == {10: 2}
    assert warm_groups(arr, slots=8) == {10: 4}


# --------------------------------------------------------------- drivers
class _State(enum.Enum):
    QUEUED, RUNNING, DONE = range(3)


class _Req:
    def __init__(self, rid, gen_len):
        self.rid, self.gen_len = rid, gen_len
        self.tokens, self.state = [], _State.QUEUED


class _FakeEngine:
    """One slot; every step takes 0.1 s of the fake clock and emits one
    token to the running request (its first token after one step)."""

    def __init__(self, clock):
        self.clock = clock
        self.waiting, self.slot, self.n = [], None, 0
        self.scheduler = self
        self.batch_slots = 1

    def submit(self, prompt, gen_len):
        self.n += 1
        r = _Req(self.n, gen_len)
        self.waiting.append(r)
        return r

    @property
    def active(self):
        return int(self.slot is not None)

    @property
    def running(self):
        return self.active

    def step(self):
        self.clock.t += 0.1
        if self.slot is None and self.waiting:
            self.slot = self.waiting.pop(0)
            self.slot.state = _State.RUNNING
        if self.slot is not None:
            self.slot.tokens.append(7)
            if len(self.slot.tokens) == self.slot.gen_len:
                self.slot.state = _State.DONE
                self.slot = None


class _Clock:
    """Moves 1 ms at every reading, so that idle waits end."""
    t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    clock = _Clock()
    eng = _FakeEngine(clock)
    d = drv.Driver(eng, clock=clock)
    arr = [traffic.Arrival(t, np.ones(4, np.int32), 2)
           for t in (0.05, 0.06, 1.05, 1.30)]
    win = drv.run_open_loop(d, arr, pre_roll=0.0, seconds=2.0, drain_cap=5.0)
    s0, s1 = d.sent[0], d.sent[1]
    # submitted at the first loop turn after their due times ...
    assert s0.submitted >= s0.due and s1.submitted > s1.due
    # ... and queued behind each other: the second waits two steps more
    assert s1.due == pytest.approx(win.traffic_start + 0.06)
    assert s1.first - s1.due > s1.first - s1.submitted
    assert len(d.lateness) == 4 and max(d.lateness) > 0
    rec = RunRecord(config={}, window=win, sent=d.sent, ticks=d.ticks,
                    t_admit={}, batch_slots=1, setup_s=1.0, trace=None,
                    peaks=None)
    ttft = load_module(tinycell.BENCH / "metrics" / "ttft_p95_ms.py")
    want = 1e3 * np.percentile([s.first - s.due for s in d.sent], 95)
    assert ttft.read(rec) == pytest.approx(want)
    tps = load_module(tinycell.BENCH / "metrics" / "tokens_per_s.py")
    assert tps.read(rec) == pytest.approx(
        sum(1 for s in d.sent for t in s.stamps if t < 2.0) / 2.0)


def test_closed_loop_keeps_the_backlog_full():
    clock = _Clock()
    eng = _FakeEngine(clock)
    d = drv.Driver(eng, clock=clock)
    arr = [traffic.Arrival(None, np.ones(4, np.int32), 3) for _ in range(40)]
    win = drv.run_closed_loop(d, arr, slots=1, backlog=2, seconds=2.0,
                              fill_cap=5.0)
    assert win.end - win.start == pytest.approx(2.0)
    done = sum(s.state == "DONE" for s in d.sent)
    assert len(d.sent) == done + 3     # one running + two waiting


def test_block_of_one_fixes_sizes_and_seeds_draw_tokens():
    mix = dict(CHAT, rate_rps=0.5, block=1)
    a = traffic.generate(mix, 1, 1000, seconds=51)
    b = traffic.generate(mix, 2, 1000, seconds=51)
    assert [(x.due, len(x.prompt), x.gen_len) for x in a] == [
        (x.due, len(x.prompt), x.gen_len) for x in b]
    assert a[0].prompt.tobytes() != b[0].prompt.tobytes()
