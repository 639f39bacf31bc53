"""The trace reduction, on a hand-made trace and on a slice recorded on a
TPU v5e."""
import gzip
import json

import pytest

import tinycell
from chipbench import tracefile

MS = 1e6   # ns


def _trace():
    """One chip, a 10 ms window: decode 1-4 ms (a loop around its ops)
    with a paged kernel of 2 ms inside, prefill 5-6 ms with a 0.5 ms
    kernel, an op straddling the window's end; the host is stepping from 0
    to 7 ms and waiting after."""
    ops = [["%while.4 = (s32[]) while(...)", 1 * MS, 4 * MS],
           ["%fusion.1 = bf16[8] fusion(...)", 1 * MS, 1.5 * MS],
           ["%paged_decode.10 = bf16[8] custom-call(...)", 1.5 * MS,
            3.5 * MS],
           ["%fusion.2 = bf16[8] fusion(...)", 3.5 * MS, 4 * MS],
           ["%paged_prefill.15 = bf16[8] custom-call(...)", 5 * MS,
            5.5 * MS],
           ["%copy.3 = bf16[8] copy(...)", 5.5 * MS, 6 * MS],
           ["%fusion.4 = bf16[8] fusion(...)", 9.5 * MS, 12 * MS]]
    mods = [["jit_decode(7)", 1 * MS, 4 * MS],
            ["jit_chunk(9)", 5 * MS, 6 * MS]]
    host = [["bench.window", 0, 10 * MS], ["bench.step", 0, 7 * MS],
            ["bench.wait", 7 * MS, 10 * MS]]
    return {"device": [{"plane": "/device:TPU:0", "ops": ops,
                        "modules": mods}], "host": host}


def test_reduce_busy_steps_kernels_and_gaps():
    r = tracefile.reduce(_trace())
    assert r.window_s == pytest.approx(0.010)
    # busy 1-4, 5-6, 9.5-10 (clipped) = 4.5 ms
    assert r.busy_s == pytest.approx(0.0045)
    assert r.idle_share == pytest.approx(0.55)
    assert (r.decode_steps, r.prefill_steps) == (1, 1)
    assert r.decode_s == pytest.approx(0.003)
    assert r.paged_decode_s == pytest.approx(0.002)
    assert r.paged_prefill_s == pytest.approx(0.0005)
    assert r.op_s["fusion"] == pytest.approx(0.0015)
    assert r.top_ops(1) == [("paged_decode", pytest.approx(0.002))]
    assert "while" not in r.op_s
    # gaps 0-1 and 4-5 under bench.step, 6-9.5 under bench.wait
    assert r.idle_gaps[0] == ("bench.wait", pytest.approx(0.0035))
    assert [g[0] for g in r.idle_gaps[1:]] == ["bench.step", "bench.step"]


def test_reduce_without_a_window_or_ops_reads_nothing():
    t = _trace()
    assert tracefile.reduce(dict(t, host=t["host"][1:])) is None
    t["device"][0]["ops"] = []
    assert tracefile.reduce(t) is None


def test_reduce_a_recorded_v5e_slice():
    """0.8 s of the chat cell's window on one v5e (decode steps of ~58 ms,
    four layers each, one paged_decode call per layer). The numbers were
    read off the trace by hand: 12 whole decode modules in the slice, the
    paged kernel ~79% of their time, the chip idle ~3%."""
    path = tinycell.BENCH / "tests" / "fixtures" / "v5e_chat_slice.json.gz"
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    r = tracefile.reduce(trace)
    assert r.window_s == pytest.approx(0.8)
    assert r.decode_steps == 12 and r.prefill_steps == 0
    assert r.decode_s / 12 == pytest.approx(0.0578, abs=1e-3)
    assert r.paged_decode_s / r.decode_s == pytest.approx(0.79, abs=0.01)
    assert r.idle_share == pytest.approx(0.033, abs=0.002)
    assert r.top_ops(1)[0][0] == "paged_decode"
    assert {g[0] for g in r.idle_gaps} <= {"bench.step", "driver"}
