"""Paged KV cache: paged-vs-dense equivalence (the degenerate
page_size == s_max config must be bit-exact; smaller pages must produce
identical greedy tokens), page allocator exhaustion/recycling, admission
deferral when the free list is short, and admission of requests longer than
an equivalent dense engine's s_max would allow.

Equivalence leans on the design anchor stated in
``models/layers.py::attention_decode_paged``: the gathered block-table view
of a slot's pages holds exactly the rows the dense cache would, in the same
logical order, and masked rows contribute exactly 0 — so greedy argmax
streams must match token-for-token at ANY page size, and bit-for-bit at the
degenerate one.

The serving steps hand each layer the layer-stacked page pools with the
layer's index; a layer given one layer's pool instead (the probes, the VLM
super-layer) computes the same thing, and the tests below pin that the two
forms agree bit for bit, from one layer's write up to the engine's tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import layers as L
from repro.models import transformer
from repro.models.registry import (cache_capacity, extract_cache_slot,
                                   get_model, reduced_config)
from repro.serve.engine import PageAllocator, ServeEngine

S_MAX = 32


@pytest.fixture(scope="module")
def hymba():
    cfg = reduced_config(configs.get_config("hymba-1.5b"))
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def qwen():
    cfg = reduced_config(configs.get_config("qwen2.5-32b"))
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _workload(engine, vocab):
    """requests > batch_slots so slots recycle mid-run (continuous batching
    over page alloc/free, not just a single prefill+decode)."""
    rng = np.random.default_rng(11)
    gens = [6, 4, 8, 5]
    return [engine.submit(rng.integers(0, vocab, 8), g) for g in gens]


def _run_pair(model, params, page_size, **paged_kw):
    dense = ServeEngine(model, params, batch_slots=2, s_max=S_MAX)
    d_reqs = _workload(dense, model.cfg.vocab_size)
    dense.run()
    paged = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                        page_size=page_size, **paged_kw)
    p_reqs = _workload(paged, model.cfg.vocab_size)
    paged.run()
    return dense, d_reqs, paged, p_reqs


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("arch_fixture", ["qwen", "hymba"])
def test_degenerate_page_equals_dense_bit_exact(arch_fixture, request):
    """page_size == s_max (one page per slot): greedy tokens match the dense
    engine for a slot-recycling workload, and a mid-flight slot's cache —
    gathered through its block table — is bit-identical to the dense slot
    (K/V rows, ring positions, recurrent state, pos)."""
    model, params = request.getfixturevalue(arch_fixture)
    dense, d_reqs, paged, p_reqs = _run_pair(model, params, S_MAX)
    for d, p in zip(d_reqs, p_reqs):
        assert d.tokens == p.tokens
    # bit-exactness of live cache state: step both engines mid-request
    de = ServeEngine(model, params, batch_slots=2, s_max=S_MAX)
    pe = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                     page_size=S_MAX)
    dr = de.submit(np.arange(1, 9, dtype=np.int32), 10)
    pr = pe.submit(np.arange(1, 9, dtype=np.int32), 10)
    for _ in range(4):
        de.step()
        pe.step()
    dc = extract_cache_slot(de.cache, dr.slot)
    pc = extract_cache_slot(pe.cache, pr.slot)
    assert set(dc) == set(pc)
    cap = cache_capacity(model.cfg, S_MAX)
    for key in dc:
        d_leaf = np.asarray(dc[key])
        if key in ("k", "v"):
            d_leaf = d_leaf[:, :, :cap]
        np.testing.assert_array_equal(d_leaf, np.asarray(pc[key]),
                                      err_msg=key)


@pytest.mark.parametrize("page_size", [4, 16])
def test_small_pages_identical_greedy_tokens(qwen, page_size):
    """page_size < s_max: same greedy streams; the pool is smaller than the
    dense slots x s_max block for page_size 4 with a workload-sized pool."""
    model, params = qwen
    need_pages = -(-(8 + 8 - 1) // page_size)           # worst request
    dense, d_reqs, paged, p_reqs = _run_pair(
        model, params, page_size, num_pages=2 * need_pages)
    for d, p in zip(d_reqs, p_reqs):
        assert d.tokens == p.tokens
    assert paged.resident_cache_bytes() < dense.resident_cache_bytes()


def test_small_pages_hybrid_ring(hymba):
    """The hybrid ring (width = window) pages too: ring writes/reads go
    through the block table and still match the dense ring exactly."""
    model, params = hymba
    ps = cache_capacity(model.cfg, S_MAX) // 2          # 2 pages per ring
    dense, d_reqs, paged, p_reqs = _run_pair(model, params, ps)
    for d, p in zip(d_reqs, p_reqs):
        assert d.tokens == p.tokens


def test_paged_encdec_equivalence():
    """Whisper decode: paged self-attn KV + dense cross K/V."""
    engine_kw = dict(batch_slots=2, s_max=S_MAX)
    cfg = reduced_config(configs.get_config("whisper-large-v3"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense = ServeEngine(model, params, **engine_kw)
    d = dense.submit(np.arange(1, 7, dtype=np.int32), 5)
    dense.run()
    paged = ServeEngine(model, params, page_size=8, **engine_kw)
    p = paged.submit(np.arange(1, 7, dtype=np.int32), 5)
    paged.run()
    assert d.tokens == p.tokens and len(p.tokens) == 5


def test_paged_vlm_super_layer_equivalence():
    """VLM decode threads block tables through the super-layer unroll
    (self-attn paged, gated image cross-attn untouched)."""
    cfg = reduced_config(configs.get_config("llama-3.2-vision-11b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense = ServeEngine(model, params, batch_slots=2, s_max=S_MAX)
    d = dense.submit(np.arange(1, 9, dtype=np.int32), 5)
    dense.run()
    paged = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                        page_size=8)
    p = paged.submit(np.arange(1, 9, dtype=np.int32), 5)
    paged.run()
    assert d.tokens == p.tokens and len(p.tokens) == 5


# ------------------------------------------------- layer-stacked pools
def _layer_case(step: str, int8: bool):
    """Attention params, inputs and one layer's pools for a GQA layer (8
    query heads over 2 kv heads) reading two slots' pages."""
    dims = L.AttnDims(d_model=32, num_heads=8, num_kv_heads=2, head_dim=16,
                      qkv_bias=True)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    P, ps = 10, 8
    bt = jnp.asarray([[3, 5, -1, -1], [0, 7, 2, -1]], jnp.int32)
    pools = [jax.random.normal(k, (P, ps, 2, 16)) for k in ks[:2]]
    sc = {}
    if int8:
        pools = [jnp.clip(jnp.round(p * 40), -127, 127).astype(jnp.int8)
                 for p in pools]
        scale = ((1 + jnp.arange(P) % 3) / 40.0)[:, None]
        sc = dict(k_scale=scale, v_scale=scale * 0.5)
    else:
        pools = [p.astype(jnp.bfloat16) for p in pools]
    n = 1 if step == "decode" else 4
    x = jax.random.normal(ks[2], (2, n, 32))
    return L.attn_init(ks[3], dims), dims, x, pools, bt, sc


def _layer_step(step, params, dims, x, pk, pv, bt, impl, layer, sc):
    if step == "decode":
        pos = jnp.asarray([10, 17], jnp.int32)
        return L.attention_decode_paged(
            params, x, dims, pk, pv, bt, pos, L.decode_positions(pos, 2),
            impl=impl, layer=layer, **sc)
    positions = 12 + jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (2, 4))
    return L.attention_prefill_chunk_paged(
        params, x, dims, pk, pv, bt, positions, jnp.int32(0), impl=impl,
        layer=layer, **sc)


@pytest.mark.parametrize("impl", ["kernel", "einsum"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_stacked_pool_step_writes_only_its_layer(step, pool, impl):
    """A layer handed the 3-layer stack of pools at its (traced) index
    computes what it computes handed its own pool as a stack of one: the
    same output, the same rows (and int8 page scales) written into its
    layer of the stack, and every other layer of the stack left as it
    was."""
    params, dims, x, (pk, pv), bt, sc = _layer_case(step, pool == "int8")
    one = jax.jit(lambda pk, pv, **sc: _layer_step(
        step, params, dims, x, pk[None], pv[None], bt, impl, 0, sc))(
            pk, pv, **sc)
    stacks = [jnp.stack([p[::-1], p, -p]) for p in (pk, pv)]
    got = jax.jit(lambda sk, sv, i, **sc: _layer_step(
        step, params, dims, x, sk, sv, bt, impl, i, sc))(
            *stacks, jnp.int32(1), **sc)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(one[0]))
    for stack, new, mine in zip(stacks, got[1:3], one[1:3]):
        np.testing.assert_array_equal(np.asarray(new[1]),
                                      np.asarray(mine[0]))
        assert not np.array_equal(np.asarray(new[1]), np.asarray(stack[1]))
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(stack[other]))
    for new_scale, mine in zip(got[3:], one[3:]):
        np.testing.assert_array_equal(np.asarray(new_scale),
                                      np.asarray(mine))


@pytest.fixture(scope="module")
def gqa():
    cfg = reduced_config(configs.get_config("qwen2.5-32b"))
    cfg = dataclasses.replace(cfg, num_heads=8, num_kv_heads=2)
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("impl", ["kernel", "einsum"])
@pytest.mark.parametrize("kv_backend", ["paged_fp32", "paged_int8"],
                         ids=["bf16", "int8"])
def test_stacked_pools_leave_greedy_tokens_unchanged(gqa, monkeypatch,
                                                     kv_backend, impl):
    """The paged engine's greedy tokens, and its pools at the end, are the
    same whether each layer gets the layer-stacked pools (the served form)
    or its own pool sliced out of the stack and written back (forced here
    by giving the layer loop no pool leaves to pass whole, and handing
    each layer's slice to the attention as a stack of one), for a GQA
    model on bf16 and on int8 pages, prompts spanning several chunks."""
    model, params = gqa

    def own_pool(fn):
        def call(params, x, dims, pk, pv, *rest, layer, **kw):
            out = fn(params, x, dims, pk[None], pv[None], *rest, layer=0,
                     **kw)
            return (out[0], out[1][0], out[2][0]) + tuple(out[3:])
        return call

    def serve():
        eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                          page_size=8, kv_backend=kv_backend,
                          cache_dtype=jnp.bfloat16, paged_attn_impl=impl,
                          prefill_chunk_tokens=8, prefix_cache=False)
        rng = np.random.default_rng(11)
        reqs = [eng.submit(rng.integers(0, model.cfg.vocab_size, n), g)
                for n, g in ((19, 6), (8, 4), (12, 8))]
        eng.run()
        return [r.tokens for r in reqs], eng.cache

    jax.clear_caches()
    stacked_tokens, stacked_cache = serve()
    monkeypatch.setattr(transformer, "_POOL_LEAVES", ())
    for name in ("attention_decode_paged", "attention_prefill_chunk_paged"):
        monkeypatch.setattr(L, name, own_pool(getattr(L, name)))
    jax.clear_caches()
    sliced_tokens, sliced_cache = serve()
    jax.clear_caches()
    assert stacked_tokens == sliced_tokens
    assert all(len(t) == g for t, g in zip(stacked_tokens, (6, 4, 8)))
    for key in ("k", "v", "k_scale", "v_scale"):
        if key in stacked_cache:
            np.testing.assert_array_equal(np.asarray(stacked_cache[key]),
                                          np.asarray(sliced_cache[key]),
                                          err_msg=key)


def test_ssm_family_falls_back_to_dense():
    """rwkv state is O(1) in s_max: paging is a no-op, not an error."""
    engine = ServeEngine.build("rwkv6-7b", reduced=True, batch_slots=2,
                               s_max=16, page_size=8)
    assert not engine.paged
    req = engine.submit(np.array([1, 2, 3], np.int32), 4)
    engine.run()
    assert req.done and len(req.tokens) == 4


# ------------------------------------------------------------ allocator
def test_page_allocator_exhaustion_and_recycling():
    a = PageAllocator(4)
    p1 = a.alloc(3)
    assert sorted(p1) == [0, 1, 2] and a.free == 1
    assert a.alloc(2) is None and a.free == 1    # all-or-nothing
    p2 = a.alloc(1)
    assert a.free == 0
    a.release(p1)
    assert a.free == 3
    assert sorted(a.alloc(3) + p2) == [0, 1, 2, 3]
    with pytest.raises(ValueError, match="double free"):
        a.release(p2 + p2)


def test_admission_defers_until_pages_free(qwen):
    """Pool covers ONE request's worst case: the second waits (deferral
    counter ticks) and is admitted only after the first's pages release —
    and both still complete with full token counts."""
    model, params = qwen
    # prefix_cache off: this test pins the RAW free-list recycling contract
    # (every page back after the run); with caching on, prompt pages are
    # deliberately RETAINED by the prefix index — tests/test_prefix.py
    # covers that retention/eviction accounting
    engine = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                         page_size=8, num_pages=3,      # need 2 pages/request
                         prefix_cache=False)
    a = engine.submit(np.arange(1, 9, dtype=np.int32), 5)
    b = engine.submit(np.arange(9, 17, dtype=np.int32), 5)
    engine.step()
    assert a.slot is not None and b.slot is None
    assert engine.deferrals >= 1
    engine.run()
    assert a.done and b.done
    assert len(a.tokens) == 5 and len(b.tokens) == 5
    assert engine.free_pages == engine.num_pages         # fully recycled


def test_pool_exhaustion_recycles_across_many_requests(qwen):
    """8 requests through a pool that can hold ~2 concurrently: slots defer,
    pages recycle, everything completes (the continuous-batching loop cannot
    deadlock on page pressure)."""
    model, params = qwen
    # prefix_cache off: pins full free-list recycling (see the note in
    # test_admission_defers_until_pages_free)
    engine = ServeEngine(model, params, batch_slots=4, s_max=S_MAX,
                         page_size=8, num_pages=4, prefix_cache=False)
    rng = np.random.default_rng(3)
    reqs = [engine.submit(rng.integers(0, model.cfg.vocab_size, 8), 4)
            for _ in range(8)]
    engine.run()
    assert all(r.done and len(r.tokens) == 4 for r in reqs)
    assert engine.deferrals > 0
    assert engine.free_pages == engine.num_pages


def test_long_request_admittable_when_pool_allows(qwen):
    """The acceptance case: rows = prompt+gen-1 = 56 exceeds a dense
    engine's s_max=32 equivalent, but the paged engine admits it because
    admission is bounded by pool capacity (and the block-table span), not a
    per-slot dense preallocation."""
    model, params = qwen
    dense = ServeEngine(model, params, batch_slots=2, s_max=S_MAX)
    with pytest.raises(ValueError, match="s_max"):
        dense.submit(np.arange(0, 40, dtype=np.int32), 17)
    paged = ServeEngine(model, params, batch_slots=2, s_max=2 * S_MAX,
                        page_size=8, num_pages=8)
    req = paged.submit(np.arange(0, 40, dtype=np.int32), 17)
    paged.run()
    assert req.done and len(req.tokens) == 17
    # and the pool is SMALLER than the dense engine's k/v even at 2x s_max:
    # 8 pages x 8 rows = 64 resident rows vs dense 2 slots x 64 rows
    assert paged.resident_cache_bytes() < \
        ServeEngine(model, params, batch_slots=2,
                    s_max=2 * S_MAX).resident_cache_bytes()


def test_submit_rejects_pool_impossible_request(qwen):
    """A request no amount of recycling can serve fails at submit, keeping
    admission infallible."""
    model, params = qwen
    engine = ServeEngine(model, params, batch_slots=1, s_max=S_MAX,
                         page_size=8, num_pages=2)
    with pytest.raises(ValueError, match="pages"):
        engine.submit(np.arange(0, 20, dtype=np.int32), 10)  # 29 rows > 16
