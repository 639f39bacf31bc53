"""The program-span reduction and its readers, on a hand-made trace and on
a slice recorded on a TPU v5e before the engine had spans."""
import gzip
import json
import types

import pytest

import tinycell
from chipbench import cell, programspans, tracefile

READERS = ("host.step_idle_ms", "host.step_idle_ms.offline",
           "sched.prefill_in_tick_ms")


def ms(x: float) -> float:
    """Whole nanoseconds, so sums of them compare exactly."""
    return float(round(x * 1e6))


def _trace():
    """One chip, a 20 ms window. Tick 1 (2-9.5 ms) prefills a last chunk,
    splices and decodes; tick 2 (12.5-17.5) decodes with an empty prefill
    phase; tick 3 starts at 19 and ends past the window with no decode; a
    tick before the window is left out. The device is busy 0-3, 3.5-4.2,
    6.2-7, 7.5-8.2, 11-16.5 and from 16.8 on (a loop around an op), so it
    idles 3-3.5, 4.2-6.2, 7-7.5, 8.2-11 and 16.5-16.8."""
    spans = [("bench.window", 0, 20),
             ("serve.step", -3, -1),
             ("bench.step", 1, 10), ("serve.step", 2, 9.5),
             ("serve.admit", 2, 2.5),
             ("serve.prefill", 2.5, 5), ("serve.prefill_chunk", 2.5, 5),
             ("serve.splice", 4, 5), ("serve.sample", 4.5, 5),
             ("serve.decode", 5, 6), ("serve.sample", 6, 8),
             ("serve.emit", 8, 9), ("serve.finish", 8.5, 9),
             ("serve.admit", 9, 9.5),
             ("bench.wait", 10, 10.8),
             ("bench.step", 12, 18), ("serve.step", 12.5, 17.5),
             ("serve.admit", 12.5, 13), ("serve.prefill", 13, 13.5),
             ("serve.decode", 13.5, 14), ("serve.sample", 14, 17),
             ("serve.emit", 17, 17.5),
             ("bench.step", 18.5, 21.5), ("serve.step", 19, 21),
             ("serve.admit", 19, 19.5), ("serve.prefill", 19.5, 20.5),
             ("serve.prefill_chunk", 19.5, 20.5)]
    ops = [["%fusion.1 = bf16[8] fusion(...)", 0, 3],
           ["%fusion.2 = bf16[8] fusion(...)", 3.5, 4.2],
           ["%paged_decode.3 = bf16[8] custom-call(...)", 6.2, 7],
           ["%fusion.4 = bf16[8] fusion(...)", 7.5, 8.2],
           ["%fusion.5 = bf16[8] fusion(...)", 11, 16.5],
           ["%while.6 = (s32[]) while(...)", 16.8, 22],
           ["%fusion.7 = bf16[8] fusion(...)", 17, 18]]
    return {"device": [{"plane": "/device:TPU:0",
                        "ops": [[n, ms(a), ms(b)] for n, a, b in ops],
                        "modules": []}],
            "host": [[n, ms(a), ms(b)] for n, a, b in spans]}


def test_ticks_children_and_idle_per_tick():
    s = programspans.reduce(_trace())
    assert s.window == (0.0, ms(20))
    assert [(t.start, t.end) for t in s.ticks] == [
        (ms(2), ms(9.5)), (ms(12.5), ms(17.5)), (ms(19), ms(21))]
    t1, t2, t3 = s.ticks
    assert [c.name for c in t1.children] == [
        "serve.admit", "serve.prefill", "serve.decode", "serve.sample",
        "serve.emit", "serve.admit"]
    assert [(c.name, c.ns) for c in t2.children] == [
        ("serve.admit", ms(0.5)), ("serve.prefill", ms(0.5)),
        ("serve.decode", ms(0.5)), ("serve.sample", ms(3)),
        ("serve.emit", ms(0.5))]
    chunk, = t1.named("serve.prefill")[0].children
    splice, = chunk.children
    assert [c.name for c in splice.children] == ["serve.sample"]
    assert [c.name for c in t1.named("serve.emit")[0].children] == [
        "serve.finish"]
    # idle 3-3.5, 4.2-6.2, 7-7.5, 8.2-9.5 in tick 1; 16.5-16.8 in tick 2;
    # tick 3 is busy to its end, past the window
    assert [t.idle_ns for t in s.ticks] == [ms(4.3), ms(0.3), 0.0]
    assert s.decode_ticks() == [t1, t2]


def test_idle_is_charged_to_the_innermost_span_and_split_at_edges():
    s = programspans.reduce(_trace())
    assert s.idle_by_span == {
        "serve.prefill_chunk": ms(0.5), "serve.splice": ms(0.3),
        "serve.sample": ms(1.5), "serve.decode": ms(1.0),
        "serve.emit": ms(0.3), "serve.finish": ms(0.5),
        "serve.admit": ms(0.5), "bench.step": ms(0.5),
        "bench.wait": ms(0.8), "driver": ms(0.2)}
    assert sum(s.idle_by_span.values()) == ms(6.1)
    g1, g2 = s.gaps[:2]
    assert (g1.start, g1.ns) == (ms(8.2), ms(2.8))
    assert g1.pieces == {"serve.emit": ms(0.3), "serve.finish": ms(0.5),
                         "serve.admit": ms(0.5), "bench.step": ms(0.5),
                         "bench.wait": ms(0.8), "driver": ms(0.2)}
    # one gap across the splice, its sample, the decode dispatch and the
    # next sample: split between them
    assert (g2.start, g2.ns) == (ms(4.2), ms(2.0))
    assert g2.pieces == {"serve.splice": ms(0.3), "serve.sample": ms(0.7),
                         "serve.decode": ms(1.0)}
    assert [g.ns for g in s.gaps[2:]] == [ms(0.5), ms(0.5), ms(0.3)]


def test_without_a_window_ticks_or_ops_reads_nothing():
    t = _trace()
    assert programspans.reduce(dict(t, host=t["host"][1:])) is None
    no_serve = [h for h in t["host"] if not h[0].startswith("serve.")]
    assert programspans.reduce(dict(t, host=no_serve)) is None
    t["device"][0]["ops"] = []
    assert programspans.reduce(t) is None


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Readers pointed at a run whose trace file loads as ``trace[0]``;
    returns the list of paths ``tracefile.load`` was asked for."""
    f = tmp_path / "plugins" / "profile" / "1" / "host.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(b"")
    monkeypatch.setattr(cell, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(programspans, "_loaded", {})
    loads, trace = [], [None]

    def load(path, names):
        assert names["host_span"] == r"^(bench|serve)\."
        loads.append(path)
        return trace[0]

    monkeypatch.setattr(tracefile, "load", load)
    return trace, loads


def _read(name, trace_reduced=True):
    run = types.SimpleNamespace(trace=object() if trace_reduced else None)
    return cell.load_module(cell.metric_reader(name)).read(run)


def test_readers_on_the_hand_made_trace(traced, capsys):
    trace, loads = traced
    trace[0] = _trace()
    assert cell.metric_reader("host.step_idle_ms.offline").name == \
        "host.step_idle_ms.py"
    # (4.3 + 0.3) / 2 decode ticks; tick 1's prefill waited 2.5 ms
    assert _read("host.step_idle_ms") == pytest.approx(2.3, abs=1e-12)
    assert _read("host.step_idle_ms.offline") == pytest.approx(2.3,
                                                               abs=1e-12)
    assert _read("sched.prefill_in_tick_ms") == pytest.approx(2.5, abs=1e-12)
    assert len(loads) == 1          # one load per run, whatever reads it
    err = capsys.readouterr().err
    assert "idle by span (s): serve.sample 0.001500, serve.decode 0.001000" \
        in err
    assert "idle per decode tick (ms): ticks 2, p50 2.300, max 4.300" in err
    assert ("idle gap 2.800 ms at 0.008 s in the window: bench.wait 0.800 ms"
            in err)
    assert ("idle gap 2.000 ms at 0.004 s in the window: serve.decode 1.000 "
            "ms, serve.sample 0.700 ms, serve.splice 0.300 ms") in err


def test_readers_read_nothing_from_a_trace_without_program_spans(traced):
    """The recorded slice has the driver's spans and the device's ops but
    no ``serve.*`` span, as a program without them gives."""
    trace, loads = traced
    path = tinycell.BENCH / "tests" / "fixtures" / "v5e_chat_slice.json.gz"
    with gzip.open(path, "rt") as f:
        trace[0] = json.load(f)
    assert tracefile.reduce(trace[0]) is not None
    assert [_read(n) for n in READERS] == [None, None, None]
    assert len(loads) == 1


def test_readers_read_nothing_from_an_untraced_run(traced):
    _, loads = traced
    assert [_read(n, trace_reduced=False) for n in READERS] == [None] * 3
    assert loads == []
