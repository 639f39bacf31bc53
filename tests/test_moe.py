"""The MoE layer as serving computes it (``layers.moe_dropless``).

* **Routing.** DeepSeek-V3's sigmoid routing with a selection-only
  correction bias, group-limited top-k and renormalised, scaled gates
  matches a plain per-token reading of the published rule.
* **Shares.** Expert parallelism without the exchange: the routed parts
  that every share of the experts computes, plus the shared expert once,
  add up to the whole layer of a plain reference.
* **Dropless.** A token's output does not depend on its neighbours: the
  same request's logits are bit-identical served alone and beside others,
  for the DeepSeek-V3 layer and for the softmax-routed MoE of dbrx, whose
  capacity dispatch used to drop tokens at decode.
* **Counts.** The serving steps return, and the engine records, the rows
  routed to held experts and the held experts that got one.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import layers as L
from repro.models.registry import get_model, reduced_config
from repro.serve.config import ServeConfig
from repro.serve.engine import ServeEngine

DIMS = L.MoEDims(d_model=32, d_ff=16, num_experts=16, top_k=4,
                 shared_d_ff=16, scoring="sigmoid", n_group=4, topk_group=2,
                 routed_scale=2.5)


def _params(dims, key=0):
    p = L.moe_init(jax.random.PRNGKey(key), dims)
    bias = jax.random.normal(jax.random.PRNGKey(key + 1),
                             (dims.num_experts,)) * 0.1
    return dict(p, router_bias=bias)


def _route_by_hand(scores, bias, dims):
    """The published rule, one token at a time in NumPy."""
    E, G = dims.num_experts, dims.n_group
    idx, gates = [], []
    for s, c in zip(scores, scores + bias):
        groups = c.reshape(G, E // G)
        g_score = np.sort(groups, axis=1)[:, -2:].sum(1)
        keep = np.argsort(-g_score, kind="stable")[:dims.topk_group]
        masked = np.full(E, -np.inf)
        for g in keep:
            masked[g * (E // G):(g + 1) * (E // G)] = groups[g]
        top = np.argsort(-masked, kind="stable")[:dims.top_k]
        w = s[top] / s[top].sum() * dims.routed_scale
        idx.append(top)
        gates.append(w)
    return np.array(idx), np.array(gates)


def _reference_layer(p, x, dims):
    """Whole-layer reference: every token through its routed experts by
    the rule above, plus the shared expert; float64 NumPy."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    x = f(x)
    scores = 1 / (1 + np.exp(-(x @ f(p["router"]))))
    idx, gates = _route_by_hand(scores, f(p["router_bias"]), dims)

    def swiglu(v, up, gate, down):
        g = v @ gate
        return (g / (1 + np.exp(-g)) * (v @ up)) @ down
    out = np.zeros_like(x)
    for t in range(len(x)):
        for e, w in zip(idx[t], gates[t]):
            out[t] += w * swiglu(x[t], f(p["w_up"][e]), f(p["w_gate"][e]),
                                 f(p["w_down"][e]))
    sh = p["shared"]
    return out + swiglu(x, f(sh["w_up"]), f(sh["w_gate"]), f(sh["w_down"]))


def test_routing_matches_the_published_rule():
    p = _params(DIMS)
    xt = jax.random.normal(jax.random.PRNGKey(7), (24, DIMS.d_model))
    idx, gates = L.moe_route(p, xt, DIMS)
    scores = 1 / (1 + np.exp(-(np.asarray(xt, np.float64)
                               @ np.asarray(p["router"], np.float64))))
    want_idx, want_gates = _route_by_hand(
        scores, np.asarray(p["router_bias"], np.float64), DIMS)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(want_idx, 1))
    order = np.argsort(np.asarray(idx), 1)
    want_order = np.argsort(want_idx, 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, 1),
        np.take_along_axis(want_gates, want_order, 1), rtol=1e-5)
    # the bias moved the choice: without it some token picks otherwise
    plain, _ = _route_by_hand(scores, 0 * scores[0], DIMS)
    assert (np.sort(plain, 1) != np.sort(want_idx, 1)).any()


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_shares_add_up_to_the_whole_layer(shares):
    """16 experts held as ``shares`` shares: each share's routed part (its
    output less the shared expert every share adds alike), summed, plus
    the shared expert once, is the reference's whole layer."""
    p = _params(DIMS)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, DIMS.d_model))
    shared = L.mlp(p["shared"], x)
    n = DIMS.num_experts // shares
    total = shared
    for s in range(shares):
        dims = dataclasses.replace(DIMS, held=(s * n, n))
        y, counts = L.moe_dropless(_params(dims), x, dims)
        total = total + (y - shared)
    want = _reference_layer(p, x.reshape(-1, DIMS.d_model), DIMS)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape), want,
                               rtol=2e-5, atol=2e-5)


def test_a_share_draws_the_experts_of_the_whole_layer():
    """A share's initialiser gives each held expert the weights the same
    expert has in the whole layer and in every other share (each drawn
    from the layer key and its own index)."""
    whole = L.moe_init(jax.random.PRNGKey(0), DIMS)
    for held in ((4, 8), (8, 4)):
        part = L.moe_init(jax.random.PRNGKey(0),
                          dataclasses.replace(DIMS, held=held))
        lo, n = held
        for k in ("w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(np.asarray(part[k]),
                                          np.asarray(whole[k][lo:lo + n]))
        for k in ("router", "router_bias"):
            np.testing.assert_array_equal(np.asarray(part[k]),
                                          np.asarray(whole[k]))


def test_counts_are_rows_and_experts_hit():
    dims = dataclasses.replace(DIMS, held=(4, 4))
    p = _params(dims)
    for shape, key in (((3, 5), 5), ((1, 2), 0)):
        x = jax.random.normal(jax.random.PRNGKey(key),
                              shape + (DIMS.d_model,))
        _, counts = L.moe_dropless(p, x, dims)
        idx, _ = L.moe_route(p, x.reshape(-1, DIMS.d_model), dims)
        idx = np.asarray(idx)
        held = (idx >= 4) & (idx < 8)
        assert list(np.asarray(counts)) == [held.sum(),
                                            len(np.unique(idx[held]))]
    assert counts[1] < counts[0]    # a held expert took two rows


def _logits_of(eng, req_prompt, others, gen=6):
    """The decode logits of the request for ``req_prompt``, one row per
    decode step it was running in, with ``others`` served beside it."""
    seen = []
    req = eng.submit(req_prompt, gen)
    for p in others:
        eng.submit(p, gen + 3)
    orig = eng._sample_rows

    def spy(logits, *a):
        if logits.shape[0] == eng.batch_slots and req.state.name == "RUNNING":
            seen.append(np.asarray(logits)[req.slot, 0])
        return orig(logits, *a)
    eng._sample_rows = spy
    eng.run()
    return seen, req


@pytest.mark.parametrize("arch", ["deepseek-v3", "dbrx-132b"])
def test_logits_do_not_depend_on_batch_neighbours(arch):
    """The same request's decode logits, served alone and with two other
    requests decoding beside it, are bit-identical: routing has no
    capacity, so no neighbour can push a token out of its experts."""
    cfg = reduced_config(configs.get_config(arch))
    if cfg.moe:   # a softmax MoE with little capacity: 4 experts, top 2
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=0.5))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    others = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
              for n in (7, 9)]
    kw = dict(batch_slots=3, s_max=64, page_size=8, prefill_chunk_tokens=16)
    alone, r1 = _logits_of(ServeEngine(model, params, **kw), prompt, [])
    crowd, r2 = _logits_of(ServeEngine(model, params, **kw), prompt, others)
    assert r1.tokens == r2.tokens
    assert len(alone) == len(crowd) > 0
    for a, b in zip(alone, crowd):
        np.testing.assert_array_equal(a, b)


def test_engine_records_moe_counts():
    """One reading per prefill chunk and per decode step, summed over the
    MoE layers."""
    eng = ServeEngine.build("deepseek-v3", config=ServeConfig(
        reduced=True, batch_slots=2, s_max=64, page_size=8,
        prefill_chunk_tokens=16))
    eng.submit(np.arange(1, 20, dtype=np.int32), 4)
    eng.run()
    s = eng.metrics.summary()
    assert s["moe"]["steps"] == s["prefill_chunks"] + s["decode_steps"] == 6
    assert 0 < s["moe"]["experts_hit"] <= s["moe"]["rows_routed"]
