"""The engine's tick spans: each phase of ``ServeEngine.step`` lands on the
profiler's host plane inside the span of the phase that runs it, and
tracing changes neither the served tokens nor the number of device syncs."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models.registry import get_model, reduced_config
from repro.serve.engine import ServeEngine


def _serve(model, params, trace_dir=None):
    """One request decoding from the first tick, then a 12-token prompt
    admitted beside it and prefilled in three chunks of 4, one per tick.
    With ``trace_dir`` the profiler runs over every tick after the first.
    Returns both token lists and the ``jax.block_until_ready`` calls made."""
    engine = ServeEngine(model, params, batch_slots=2, s_max=32, page_size=8,
                         prefill_chunk_tokens=4)
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(5)
    real = jax.block_until_ready
    calls = []

    def counted(x):
        calls.append(1)
        return real(x)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "block_until_ready", counted)
        a = engine.submit(rng.integers(1, vocab, 4), 10)
        engine.step()
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        b = engine.submit(rng.integers(1, vocab, 12), 3)
        try:
            while engine.scheduler.waiting or engine.active:
                engine.step()
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    return [a.tokens, b.tokens], len(calls)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = reduced_config(configs.get_config("qwen2.5-32b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    off = _serve(model, params)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    on = _serve(model, params, trace_dir)
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    return off, on, path


def _spans(path):
    """Every ``serve.*`` span on the host plane as ``(name, start, end,
    parent)``, the parent being the innermost ``serve.*`` span holding it
    (None at the top)."""
    evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("serve.")),
                 key=lambda e: (e[1], -e[2]))
    out, open_ = [], []
    for name, a, b in evs:
        while open_ and not (open_[-1][1] <= a and b <= open_[-1][2]):
            open_.pop()
        out.append((name, a, b, open_[-1] if open_ else None))
        open_.append((name, a, b))
    return out


def _children(spans, parent):
    return [s[0] for s in spans if s[3] == parent[:3]]


def test_every_tick_holds_its_phases(served):
    _, _, path = served
    spans = _spans(path)
    ticks = [s for s in spans if s[0] == "serve.step"]
    # the first tick gave the first request 2 of its 10 tokens
    assert len(ticks) == 8
    assert all(s[3] is None for s in ticks)
    for tick in ticks:
        kids = _children(spans, tick)
        assert kids.count("serve.admit") == 2
        assert {"serve.decode", "serve.sample", "serve.emit"} <= set(kids)
        assert set(kids) <= {"serve.admit", "serve.prefill", "serve.decode",
                             "serve.sample", "serve.emit"}
    assert sum(1 for s in spans if s[0] == "serve.finish"
               and s[3][0] == "serve.emit") == 2


def test_a_prefill_tick_nests_chunk_splice_and_sample(served):
    _, _, path = served
    spans = _spans(path)
    chunks = [s for s in spans if s[0] == "serve.prefill_chunk"]
    assert len(chunks) == 3
    for c in chunks:
        assert c[3][0] == "serve.prefill"
        prefill = next(s for s in spans if s[:3] == c[3])
        assert prefill[3][0] == "serve.step"
    splices = [s for s in spans if s[0] == "serve.splice"]
    assert len(splices) == 1 and splices[0][3] == chunks[-1][:3]
    assert _children(spans, splices[0]) == ["serve.sample"]


def test_tracing_changes_no_token_and_adds_no_sync(served):
    (tokens_off, syncs_off), (tokens_on, syncs_on), _ = served
    assert tokens_on == tokens_off
    assert [len(t) for t in tokens_on] == [10, 3]
    assert syncs_on == syncs_off > 0
