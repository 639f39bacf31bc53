"""MLA latent-page KV backend (serve/kvcache.PagedLatentBackend + the
models/kernels layers underneath it).

Claim groups:

* **Absorb-path math.** The absorbed MLA attention (wkv_b folded into the
  query/output einsums, attention run directly over cached latents) stays
  allclose to the naive per-head expansion oracle
  (``kernels.ref.mla_attention_naive``) — same math, reassociated
  contractions.
* **Latent kernel.** The latent-page Pallas kernel (interpret mode on this
  CPU) matches the masked-gather einsum oracle, including partial last
  pages and a freed slot's all--1 block table returning exact zeros.
* **Serving equivalence anchors.** A dense-latent-cache engine streams
  BIT-IDENTICAL greedy tokens to the degenerate single-page latent engine
  (page_size == s_max: same gather, same reduction order), and the
  multi-page kernel-path engine matches the dense stream greedily. The
  latent cache stores ONE (c_kv + r)-dim row per token — no "v" leaf
  anywhere.
* **Prefix sharing on latent pages.** Alias + COW operate on latent rows
  exactly as they do on per-head K/V pages (the generic page machinery is
  representation-agnostic): hits alias pages, an unaligned repeat COWs,
  and the streams match the prefix-off twin bit-for-bit.
* **Backend guards.** ``paged_latent`` on a per-head-K/V arch is rejected
  up front with a pointer at ``kv_backend='paged'``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ref as kref
from repro.kernels.paged_attention import paged_attention_latent
from repro.models import layers as L
from repro.models.registry import get_model, reduced_config
from repro.models.transformer import _mla_dims
from repro.serve.config import ServeConfig
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import PagedLatentBackend, make_backend

MLA_ARCH = "qwen2.5-32b-mla"
S_MAX = 32
PS = 8


@pytest.fixture(scope="module")
def mla():
    cfg = reduced_config(configs.get_config(MLA_ARCH))
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


# ------------------------------------------------------------ absorb math
def test_absorb_path_matches_naive_expansion():
    """Full prefill attention through the absorbed einsums == materialising
    per-head K/V from the latents and attending conventionally, through the
    shared wo projection."""
    cfg = reduced_config(configs.get_config(MLA_ARCH))
    dims = _mla_dims(cfg)
    key = jax.random.PRNGKey(3)
    params = L.mla_init(key, dims)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, dims.d_model),
                          jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    cache = jnp.zeros((B, S, 1, dims.latent_dim), jnp.float32)
    absorbed, _ = L.mla_attention_prefill_chunk(params, x, dims, cache, 0,
                                                pos)

    # naive expansion: pre-absorption queries + materialised per-head K/V
    H, hd, r = dims.num_heads, dims.head_dim, dims.qk_rope_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, hd + r)
    q_nope, q_pe = q[..., :hd], q[..., hd:]
    q_pe = L.apply_rope(q_pe, pos, dims.rope_theta)
    wb_k, wb_v = L._mla_wkv_b(params, dims, x.dtype)
    latent = L.mla_latent_rows(params, x, dims, pos)[:, :, 0, :]
    attn = kref.mla_attention_naive(q_nope, q_pe, latent, wb_k, wb_v,
                                    pos, pos)
    naive = attn.reshape(B, S, H * hd) @ params["wo"]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(naive),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------- latent kernel
def test_latent_kernel_matches_einsum_oracle():
    """Interpret-mode latent-page kernel vs the masked-gather oracle across
    slots at different depths (partial last pages included)."""
    rng = np.random.default_rng(0)
    B, H, c, r, ps, mps = 3, 4, 8, 2, 8, 4
    L_dim, d_v = c + r, c
    P = B * mps
    pool = jnp.asarray(rng.standard_normal((P, ps, 1, L_dim)), jnp.float32)
    bt = np.full((B, mps), -1, np.int32)
    start = np.asarray([13, 7, 26], np.int32)   # mid-page frontiers
    nxt = 0
    for b in range(B):
        for j in range(-(-int(start[b] + 1) // ps)):
            bt[b, j] = nxt
            nxt += 1
    bt, start = jnp.asarray(bt), jnp.asarray(start)
    for sq in (1, 4):
        q = jnp.asarray(rng.standard_normal((B, sq, H, L_dim)), jnp.float32)
        want = kref.paged_attention_latent(q, pool, bt, start,
                                           scale_dim=L_dim + 6, d_v=d_v)
        got = paged_attention_latent(q, pool, bt, start,
                                     scale_dim=L_dim + 6, d_v=d_v,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_latent_kernel_freed_slot_exact_zero():
    rng = np.random.default_rng(1)
    B, H, ps, mps, L_dim = 2, 2, 8, 2, 10
    pool = jnp.asarray(rng.standard_normal((4, ps, 1, L_dim)), jnp.float32)
    bt = jnp.asarray([[0, 1], [-1, -1]], jnp.int32)   # slot 1 freed
    start = jnp.asarray([9, 0], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, L_dim)), jnp.float32)
    out = paged_attention_latent(q, pool, bt, start, scale_dim=16, d_v=8,
                                 interpret=True)
    assert (np.asarray(out)[1] == 0).all()
    assert np.abs(np.asarray(out)[0]).max() > 0


def _latent_case(rng, B, H, width, ps, mps, ends, dtype=jnp.float32):
    """A pool whose slots hold pages up to ``ends[b]`` (exclusive; 0 = a
    parked slot with an all--1 table), in scattered page order."""
    P = B * mps + 3
    pool = jnp.asarray(rng.standard_normal((P, ps, 1, width)), dtype)
    bt = np.full((B, mps), -1, np.int32)
    free = list(rng.permutation(P))
    for b, end in enumerate(ends):
        for j in range(-(-end // ps)):
            bt[b, j] = free.pop()
    return pool, jnp.asarray(bt)


@pytest.mark.parametrize("H", [16, 128])
def test_latent_kernel_decode_ragged_and_parked(H):
    """Decode: one query row per head at ragged live lengths (a partial
    last page, a block edge, one page, a long slot) and a parked slot,
    against the einsum oracle; the parked slot returns exactly 0."""
    rng = np.random.default_rng(H)
    ps, mps, c, r = 8, 48, 32, 16
    ends = [13, 128, 1, 300, 0]
    pool, bt = _latent_case(rng, len(ends), H, c + r, ps, mps, ends)
    pos = jnp.asarray([max(e - 1, 0) if e else mps * ps for e in ends],
                      jnp.int32)
    q = jnp.asarray(rng.standard_normal((len(ends), 1, H, c + r)),
                    jnp.float32)
    want = kref.paged_attention_latent(q, pool, bt, pos, scale_dim=20.5,
                                       d_v=c)
    got = paged_attention_latent(q, pool, bt, pos, scale_dim=20.5, d_v=c,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert (np.asarray(got)[-1] == 0).all()


@pytest.mark.parametrize("H", [16, 128])
def test_latent_kernel_chunk_frontier_inside_a_page(H):
    """A prefill chunk whose rows start and end mid-page: each query block
    reads up to its own causal frontier, rows of one block see different
    numbers of keys, and a slot holding only the chunk's own rows works."""
    rng = np.random.default_rng(H + 1)
    ps, mps, c, r, C = 8, 16, 32, 16, 12
    starts = [21, 0, 90]
    pool, bt = _latent_case(rng, 3, H, c + r, ps, mps,
                            [s + C for s in starts])
    q = jnp.asarray(rng.standard_normal((3, C, H, c + r)), jnp.float32)
    st = jnp.asarray(starts, jnp.int32)
    want = kref.paged_attention_latent(q, pool, bt, st, scale_dim=24,
                                       d_v=c)
    got = paged_attention_latent(q, pool, bt, st, scale_dim=24, d_v=c,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_latent_kernel_reads_lane_padded_rows():
    """Rows stored wider than the latent (zeros beyond it, as the TPU's
    pool keeps them) with equally padded queries give the unpadded
    result."""
    rng = np.random.default_rng(3)
    ps, mps, c, r, H = 8, 8, 24, 8, 4
    pool, bt = _latent_case(rng, 2, H, c + r, ps, mps, [19, 40])
    pos = jnp.asarray([18, 39], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 1, H, c + r)), jnp.float32)
    want = paged_attention_latent(q, pool, bt, pos, scale_dim=16, d_v=c,
                                  interpret=True)
    got = paged_attention_latent(L.fit_rows(q, 128), L.fit_rows(pool, 128),
                                 bt, pos, scale_dim=16, d_v=c,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------- serving equivalence
def _serve(model, params, **kw):
    eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX, **kw)
    rng = np.random.default_rng(11)
    gens = [6, 4, 8, 5]
    reqs = [eng.submit(rng.integers(0, model.cfg.vocab_size, 8), g)
            for g in gens]
    eng.run()
    return eng, [r.tokens for r in reqs]


def test_dense_vs_degenerate_page_bitexact(mla):
    """page_size == s_max: one page per slot, same gather and reduction
    order as the dense latent cache — greedy streams must be IDENTICAL."""
    model, params = mla
    dense_eng, dense = _serve(model, params)
    eng, paged = _serve(model, params, page_size=S_MAX,
                        kv_backend="paged_latent")
    assert isinstance(eng.backend, PagedLatentBackend)
    assert dense == paged
    # latent representation: one shared row per token, no per-head V pool
    for cache in (dense_eng.cache, eng.cache):
        assert "v" not in cache
        assert cache["k"].shape[-2:] == (1, _mla_dims(model.cfg).latent_dim)


def test_multi_page_kernel_greedy_equal(mla):
    """Multi-page block tables through the latent kernel path (incremental
    splice on): greedy streams match the dense reference."""
    model, params = mla
    _, dense = _serve(model, params)
    eng, paged = _serve(model, params, page_size=PS,
                        kv_backend="paged_latent")
    assert type(eng.backend) is PagedLatentBackend
    assert dense == paged


def test_implicit_paged_matches_explicit_latent(mla):
    """On an MLA arch the implicit layout-follows-page_size backend pages
    the SAME latent rows: explicit paged_latent changes zero tokens."""
    model, params = mla
    _, implicit = _serve(model, params, page_size=PS)
    _, explicit = _serve(model, params, page_size=PS,
                         kv_backend="paged_latent")
    assert implicit == explicit


# ------------------------------------------------------- prefix alias/COW
def test_prefix_alias_and_cow_on_latent_pages(mla):
    """Sequential requests sharing an unaligned header: the second aliases
    full prefix pages and COWs the partial one — latent rows are copied as
    whole page rows (never expanded to per-head K/V) and the streams match
    the prefix-off twin bit-for-bit."""
    model, params = mla
    rng = np.random.default_rng(7)
    head = rng.integers(0, model.cfg.vocab_size, 12).astype(np.int32)
    tails = [rng.integers(0, model.cfg.vocab_size, 6).astype(np.int32)
             for _ in range(2)]
    workload = [(head, 5)] + [(np.concatenate([head, t]), 5) for t in tails]

    def serve(prefix_cache):
        eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                          page_size=PS, kv_backend="paged_latent",
                          prefix_cache=prefix_cache)
        toks = []
        for prompt, gen in workload:
            r = eng.submit(prompt, gen)
            eng.run()
            toks.append(r.tokens)
            eng.assert_page_invariants()
        return eng, toks

    eng_on, toks_on = serve(True)
    _, toks_off = serve(False)
    assert toks_on == toks_off
    prefix = eng_on.metrics.summary()["prefix"]
    assert prefix["hit_rate"] > 0
    assert prefix["cow_copies"] >= 1


# ------------------------------------------------------------------ guards
def test_latent_backend_rejects_per_head_kv_arch():
    with pytest.raises(ValueError, match="kv_lora_rank"):
        ServeEngine.build("qwen2.5-32b", config=ServeConfig(
            batch_slots=2, s_max=S_MAX, page_size=PS,
            kv_backend="paged_latent"))


def test_make_backend_resolves_latent():
    fam = configs.get_config(MLA_ARCH).family
    be = make_backend("paged_latent", family=fam, page_size=PS, num_pages=4)
    assert type(be) is PagedLatentBackend and be.paged
    with pytest.raises(ValueError, match="page_size"):
        make_backend("paged_latent", family=fam)
