"""The pluggable KV-cache backend seam (serve/kvcache.py).

Four claim groups:

* **Backend-swap anchors.** The extraction is behaviour-preserving: an
  engine built with an EXPLICIT ``kv_backend`` name streams bit-identical
  greedy tokens to the implicit layout-follows-page_size engine, for both
  the dense and the paged fp32 representations (test_paged.py already pins
  paged == dense; these pin explicit == implicit through the new seam).
* **Int8 page round-trip.** ``quantize_page`` reconstructs within half a
  quantization step everywhere, masks partial pages' stale rows to exact
  zeros, and maps an all-zero page to scale 1.0 (hypothesis property +
  deterministic anchors).
* **Int8 serving quality.** Per int8-supported family, the quantized
  backend's greedy streams stay close to the fp32 backend's — gated on
  mean per-request prefix-match fraction — and the int8 pools' resident
  K/V bytes are <= 0.30x the fp32 pools'.
* **Int8 x prefix-cache interplay.** Aliased prefix pages carry their
  scale with them (a second hit changes neither payload nor scale), COW
  re-materialisation re-quantizes the fresh page exactly once, and
  ``assert_page_invariants`` rejects a corrupted scale table.

Plus the refactor's structural guard: serve/engine.py must not import
page-layout internals from models/registry (checked against the module AST,
so it cannot silently regress).
"""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.quantize import page_scale, quantize_page
from repro.models.registry import get_model, reduced_config
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import (INT8_KV_FAMILIES, DenseBackend,
                                 PagedFP32Backend, PagedInt8Backend,
                                 make_backend)

try:
    from hypothesis import given, strategies as st
    from hypothesis.extra import numpy as hnp
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

S_MAX = 32
PS = 8

INT8_ARCHS = ["qwen2.5-32b", "moonshot-v1-16b-a3b", "llama-3.2-vision-11b"]


@pytest.fixture(scope="module")
def qwen():
    cfg = reduced_config(configs.get_config("qwen2.5-32b"))
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _workload(engine, vocab):
    """Same slot-recycling workload test_paged.py anchors on."""
    rng = np.random.default_rng(11)
    gens = [6, 4, 8, 5]
    return [engine.submit(rng.integers(0, vocab, 8), g) for g in gens]


def _serve(model, params, **kw):
    eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX, **kw)
    reqs = _workload(eng, model.cfg.vocab_size)
    eng.run()
    return eng, [r.tokens for r in reqs]


# ------------------------------------------------------- registry/resolution
def test_make_backend_resolution():
    fam = configs.get_config("qwen2.5-32b").family
    assert isinstance(make_backend(None, family=fam), DenseBackend)
    assert isinstance(make_backend(None, family=fam, page_size=8,
                                   num_pages=4), PagedFP32Backend)
    for name in ("paged", "paged_fp32"):
        be = make_backend(name, family=fam, page_size=8, num_pages=4)
        assert type(be) is PagedFP32Backend
    be = make_backend("paged_int8", family=fam, page_size=8, num_pages=4)
    assert isinstance(be, PagedInt8Backend) and be.quantized
    # instance passthrough
    assert make_backend(be, family=fam) is be
    with pytest.raises(ValueError, match="conflicts"):
        make_backend("dense", family=fam, page_size=8)
    with pytest.raises(ValueError, match="page_size"):
        make_backend("paged_int8", family=fam)
    with pytest.raises(ValueError, match="unknown"):
        make_backend("latent_mla", family=fam, page_size=8)


def test_int8_unsupported_family_degrades_to_fp32():
    """Hybrid's ring carry is not page-reconstructible: int8 on it no
    longer degrades to fp32 pages behind a log line — both make_backend
    and the engine build refuse it, naming the supported families, and
    the fp32 pool it would have swapped in still serves when asked for."""
    fam = configs.get_config("hymba-1.5b").family
    assert fam not in INT8_KV_FAMILIES
    with pytest.raises(ValueError, match="paged_int8.*supports"):
        make_backend("paged_int8", family=fam, page_size=8, num_pages=8)
    with pytest.raises(ValueError, match="use kv_backend='paged'"):
        ServeEngine.build("hymba-1.5b", batch_slots=2, s_max=S_MAX,
                          page_size=PS, kv_backend="paged_int8")
    be = make_backend("paged", family=fam, page_size=8, num_pages=8)
    assert type(be) is PagedFP32Backend
    eng = ServeEngine.build("hymba-1.5b", batch_slots=2, s_max=S_MAX,
                            page_size=PS, kv_backend="paged")
    assert not eng.backend.quantized
    req = eng.submit(np.arange(1, 9, dtype=np.int32), 4)
    eng.run()
    assert req.done and len(req.tokens) == 4


# ------------------------------------------------------- backend-swap anchors
def test_explicit_dense_backend_bit_exact(qwen):
    model, params = qwen
    _, implicit = _serve(model, params)
    eng, explicit = _serve(model, params, kv_backend="dense")
    assert isinstance(eng.backend, DenseBackend)
    assert implicit == explicit


@pytest.mark.parametrize("page_size", [PS, S_MAX])
def test_explicit_paged_backend_bit_exact(qwen, page_size):
    """Multi-page (kernel path) AND degenerate one-page (einsum anchor)
    configs: the seam changes zero greedy tokens."""
    model, params = qwen
    _, implicit = _serve(model, params, page_size=page_size)
    eng, explicit = _serve(model, params, page_size=page_size,
                           kv_backend="paged_fp32")
    assert type(eng.backend) is PagedFP32Backend
    assert implicit == explicit


# -------------------------------------------------------- page round-trip
def _roundtrip_page(x, valid=None):
    q, scale = quantize_page(jnp.asarray(x), None if valid is None
                             else jnp.asarray(valid))
    q, scale = np.asarray(q), float(scale)
    deq = q.astype(np.float32) * scale
    live = (np.ones(len(x), bool) if valid is None
            else np.asarray(valid, bool))
    err = np.abs(x[live] - deq[live])
    assert (err <= scale * 0.5 + 1e-6).all(), err.max()
    assert (deq[~live] == 0).all()           # masked rows exactly zero
    assert np.isfinite(scale) and scale > 0
    return q, scale


def test_page_roundtrip_deterministic():
    rng = np.random.default_rng(0)
    x = (rng.integers(-10000, 10000, (PS, 2, 4)) / 100.0).astype(np.float32)
    _roundtrip_page(x)
    # partial page: stale tail rows excluded from amax AND zeroed
    x[0] = 1000.0                            # huge stale row
    valid = np.zeros(PS, bool)
    valid[1:] = True
    q, scale = _roundtrip_page(x, valid)
    assert scale <= page_scale(jnp.abs(jnp.asarray(x[1:])).max()) + 1e-6


def test_all_zero_page_scale_is_one():
    q, scale = quantize_page(jnp.zeros((PS, 2, 4), jnp.float32))
    assert float(scale) == 1.0
    assert (np.asarray(q) == 0).all()
    # fully-masked partial page behaves the same
    q, scale = quantize_page(jnp.ones((PS, 2, 4), jnp.float32),
                             jnp.zeros(PS, bool))
    assert float(scale) == 1.0 and (np.asarray(q) == 0).all()


if HAVE_HYPOTHESIS:
    @given(hnp.arrays(np.float32, (PS, 2, 4),
                      elements=st.integers(-100000, 100000).map(
                          lambda i: np.float32(i / 1000.0))),
           st.integers(0, PS))
    def test_page_roundtrip_property(x, n_valid):
        """Round-trip within scale/2 for full AND partial pages (integer-
        derived floats: hypothesis float strategies trip over subnormals
        the quantizer legitimately flushes)."""
        valid = np.arange(PS) < n_valid
        _roundtrip_page(x, valid)
        if n_valid == PS:
            _roundtrip_page(x)


# ----------------------------------------------------- int8 serving quality
def _prefix_match_fraction(a, b):
    if not a and not b:
        return 1.0
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n / max(len(a), len(b))


@pytest.mark.parametrize("arch", INT8_ARCHS)
def test_int8_greedy_divergence_bounded(arch):
    """Per int8 family: quantized-KV greedy streams keep a mean per-request
    prefix-match fraction >= 0.6 vs the fp32 backend (random reduced models
    leave a wide top-1 logit margin, so ~1e-3-relative KV perturbation flips
    few argmaxes; the gate catches a broken scale path, which collapses the
    match to ~0)."""
    cfg = reduced_config(configs.get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, fp32 = _serve(model, params, page_size=PS)
    eng, int8 = _serve(model, params, page_size=PS, kv_backend="paged_int8")
    assert isinstance(eng.backend, PagedInt8Backend)
    match = [_prefix_match_fraction(a, b) for a, b in zip(fp32, int8)]
    assert np.mean(match) >= 0.6, (match, fp32, int8)


def _pool_bytes(cache):
    keys = [k for k in cache if k in ("k", "v") or k.endswith("_scale")]
    return sum(int(cache[k].size * cache[k].dtype.itemsize) for k in keys)


def test_int8_resident_kv_bytes_ratio(qwen):
    """Equal pool geometry: int8 K/V + scale tables <= 0.30x the fp32
    pools (int8 payload is 0.25x; the (L, P) scale tables are noise)."""
    model, params = qwen
    fp32, _ = _serve(model, params, page_size=PS)
    int8, _ = _serve(model, params, page_size=PS, kv_backend="paged_int8")
    ratio = _pool_bytes(int8.cache) / _pool_bytes(fp32.cache)
    assert ratio <= 0.30, ratio
    assert int8.resident_cache_bytes() < fp32.resident_cache_bytes()


# ------------------------------------------------- int8 x prefix interplay
def _scale_tables(cache):
    return {k: np.asarray(v) for k, v in cache.items()
            if k.endswith("_scale")}


def test_int8_prefix_hit_aliases_pages_and_scales(qwen):
    """A repeat prompt aliases the donor's prefix pages; the shared pages'
    payload AND scales are untouched by the second request, and its greedy
    stream matches its prefix-cache-off int8 twin (the int8 analogue of the
    fp32 prefix bit-exactness anchor — same representation both sides, so
    the comparison is exact, not gated)."""
    model, params = qwen
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, model.cfg.vocab_size, 16).astype(np.int32)

    def serve_twice(prefix_cache):
        eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                          page_size=PS, kv_backend="paged_int8",
                          prefix_cache=prefix_cache)
        toks = []
        for _ in range(2):
            r = eng.submit(prompt, 5)
            eng.run()
            toks.append(r.tokens)
            eng.assert_page_invariants()
        return eng, toks

    eng_on, toks_on = serve_twice(True)
    _, toks_off = serve_twice(False)
    assert toks_on == toks_off
    assert eng_on.metrics.summary()["prefix"]["hit_rate"] > 0

    # shared full pages' scales survive the aliasing request: serve the
    # repeat while SNAPSHOTTING the scale tables around it
    eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                      page_size=PS, kv_backend="paged_int8",
                      prefix_cache=True)
    r1 = eng.submit(prompt, 5)
    eng.run()
    donor_pages = sorted(eng.prefix_index.pages)
    before = _scale_tables(eng.cache)
    r2 = eng.submit(prompt, 5)
    eng.run()
    after = _scale_tables(eng.cache)
    assert r1.tokens == r2.tokens
    for key in before:
        np.testing.assert_array_equal(before[key][:, donor_pages],
                                      after[key][:, donor_pages],
                                      err_msg=f"aliased {key} rewritten")


def test_int8_cow_requantizes_fresh_page_once(qwen):
    """An unaligned repeat (prefix ends mid-page) re-materialises the
    partial page copy-on-write: the fresh page's scale equals the SOURCE
    page's right after the copy, then the tail splice re-quantizes exactly
    that one page — and the diverging stream still matches the cache-off
    int8 twin."""
    model, params = qwen
    rng = np.random.default_rng(7)
    # the donor's prompt IS the unaligned head (1 page + 4 rows): its
    # register leaves a partial-page entry the sharers must COW to extend
    head = rng.integers(0, model.cfg.vocab_size, 12).astype(np.int32)
    tails = [rng.integers(0, model.cfg.vocab_size, 6).astype(np.int32)
             for _ in range(2)]
    workload = [(head, 5)] + [(np.concatenate([head, t]), 5) for t in tails]

    def serve(prefix_cache):
        eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                          page_size=PS, kv_backend="paged_int8",
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
    assert eng_on.metrics.summary()["prefix"]["cow_copies"] >= 1


def test_invariants_reject_corrupt_scale_table(qwen):
    model, params = qwen
    eng = ServeEngine(model, params, batch_slots=2, s_max=S_MAX,
                      page_size=PS, kv_backend="paged_int8")
    eng.assert_page_invariants()
    eng.cache["k_scale"] = eng.cache["k_scale"].at[0, 0].set(0.0)
    with pytest.raises(AssertionError, match="k_scale"):
        eng.assert_page_invariants()


# ------------------------------------------------------- structural guard
def test_engine_does_not_import_page_layout_internals():
    """The refactor's contract, checked at the AST so it cannot silently
    regress: engine.py orchestrates through the KVBackend seam and must not
    import the page-layout internals it used to own — nor, since the
    sharding-aware seam, any mesh/axis internals (placement lives behind
    KVBackend.place/pool_axes, trace context and mesh construction behind
    specs.serve_trace/serve_mesh; the engine holds the mesh as an opaque
    token)."""
    banned = {"init_paged_cache", "insert_cache_rows",
              "insert_cache_rows_paged", "copy_pool_rows",
              "seed_prefix_cache", "vectorize_cache_pos",
              "cache_capacity", "extract_cache_slot", "PAGED_POOL_LEAVES",
              # mesh/axis internals: every one of these appearing in
              # engine.py means a layout decision leaked out of the seam
              "NamedSharding", "PartitionSpec", "shard_map", "TP_AXIS",
              "use_mesh", "TP_SERVE_RULES", "TP_POOL_RULES",
              "KV_POOL_AXES", "axis_names", "head_shard_axis",
              "latent_head_shard_axis", "sharding_for", "make_mesh"}
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "src" / "repro" / "serve" / "engine.py")
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
    hit = banned & imported
    assert not hit, (f"engine.py imports page-layout internals {sorted(hit)};"
                     " route them through serve/kvcache.py's KVBackend")
    # and the registry names must not be referenced as bare identifiers
    # either (a `registry.insert_cache_rows` attribute access would dodge
    # the import check only by re-importing the module wholesale)
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    attrs = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    hit = banned & (names | attrs)
    assert not hit, f"engine.py references page-layout internals {sorted(hit)}"
