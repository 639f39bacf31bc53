"""Per-kernel shape/dtype sweeps asserting allclose against the ref.py
pure-jnp oracles (kernels run interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype) * scale


@pytest.mark.parametrize("dataflow", ["output_stationary", "weight_stationary",
                                      "input_stationary"])
@pytest.mark.parametrize("mkn", [(256, 256, 256), (192, 320, 128),
                                 (130, 70, 200), (64, 512, 96)])
def test_matmul_dataflows(dataflow, mkn):
    m, k, n = mkn
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (m, k))
    w = _rand(ks[1], (k, n))
    got = ops.matmul(x, w, block_m=64, block_n=64, block_k=64, dataflow=dataflow)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul(x, w)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_dtypes(dtype):
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (128, 128), dtype)
    w = _rand(ks[1], (128, 128), dtype)
    got = ops.matmul(x, w, block_m=64, block_n=64, block_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.matmul(x, w), np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(128, 256, 192), (256, 128, 64)])
def test_quant_matmul(shape):
    m, k, n = shape
    ks = jax.random.split(KEY, 3)
    x = _rand(ks[0], (m, k))
    wq = jax.random.randint(ks[1], (k, n), -127, 127, jnp.int8)
    sc = jax.random.uniform(ks[2], (n,), jnp.float32, 0.01, 0.1)
    got = ops.quant_matmul(x, wq, sc, block_m=64, block_n=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.quant_matmul(x, wq, sc)),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("cfg", [
    dict(Sq=256, Sk=256, H=4, KV=2, causal=True, window=0),
    dict(Sq=256, Sk=256, H=4, KV=4, causal=False, window=0),
    dict(Sq=256, Sk=256, H=8, KV=2, causal=True, window=64),
    dict(Sq=128, Sk=512, H=2, KV=1, causal=False, window=0),
])
def test_flash_attention(cfg):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (2, cfg["Sq"], cfg["H"], 64))
    k = _rand(ks[1], (2, cfg["Sk"], cfg["KV"], 64))
    v = _rand(ks[2], (2, cfg["Sk"], cfg["KV"], 64))
    got = ops.flash_attention(q, k, v, causal=cfg["causal"],
                              window=cfg["window"], block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=cfg["causal"],
                               window=cfg["window"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cfg", [
    dict(S=256, H=4, KV=2, window=0),
    dict(S=256, H=8, KV=2, window=64),
    dict(S=96, H=2, KV=1, window=0),      # non-block-multiple -> oracle path
])
def test_flash_prefill_exports_kv(cfg):
    """The K/V-exporting prefill variant: O matches flash attention and the
    exported K/V tiles are the inputs bit-for-bit (the cache rows a serving
    prefill scatters through block tables)."""
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (2, cfg["S"], cfg["H"], 64))
    k = _rand(ks[1], (2, cfg["S"], cfg["KV"], 64))
    v = _rand(ks[2], (2, cfg["S"], cfg["KV"], 64))
    o, ko, vo = ops.flash_prefill(q, k, v, causal=True, window=cfg["window"],
                                  block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=True, window=cfg["window"])
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(v))


# --------------------------------------------------------- paged attention
def _paged_setup(key, B, Sq, H, KV, hd, P, ps, mps, fill):
    """Random pool + per-slot block tables whose first ``fill[b] // ps + 1``
    entries are allocated (non-contiguous page ids — the gather must really
    go through the table)."""
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (B, Sq, H, hd))
    pk = _rand(ks[1], (P, ps, KV, hd))
    pv = _rand(ks[2], (P, ps, KV, hd))
    rng = np.random.default_rng(3)
    bt = np.full((B, mps), -1, np.int32)
    perm = rng.permutation(P)
    nxt = 0
    for b in range(B):
        need = -(-fill[b] // ps)
        bt[b, :need] = perm[nxt:nxt + need]
        nxt += need
    return q, pk, pv, jnp.asarray(bt)


def _in_stack(key, pool, layer: int = 1, layers: int = 3):
    """A ``layers``-deep stack of pools shaped like ``pool``, holding
    ``pool`` at ``layer`` and other values at every other layer."""
    noise = [jnp.clip(_rand(k, pool.shape) * 40, -127, 127).astype(pool.dtype)
             for k in jax.random.split(key, layers - 1)]
    return jnp.stack(noise[:layer] + [pool] + noise[layer:])


def _one_layer_then_stacked(cases, ids):
    """(case, stacked) params: each case over one layer's pool under its
    own id, then over a layer-stacked pool as ``<id>-stacked``."""
    return [pytest.param(c, stacked, id=i + "-stacked" * stacked)
            for stacked in (False, True) for c, i in zip(cases, ids)]


@pytest.mark.parametrize("cfg,stacked", _one_layer_then_stacked([
    dict(Sq=8, H=4, KV=2, ps=8, mps=4, window=0),     # GQA prefill chunk
    dict(Sq=1, H=4, KV=4, ps=8, mps=4, window=0),     # decode shape
    dict(Sq=16, H=8, KV=2, ps=4, mps=8, window=6),    # sliding window
    dict(Sq=8, H=2, KV=1, ps=16, mps=2, window=0),    # page > chunk
], ["cfg0", "cfg1", "cfg2", "cfg3"]))
def test_paged_attention_matches_ref(cfg, stacked):
    """Block-table gather + block-skip kernel vs the masked-gather oracle,
    across GQA grouping, decode/prefill query widths, sliding windows, and
    partially-filled last pages (start positions land mid-page). Over a
    layer-stacked pool at a traced layer the kernels read that layer in
    place and give bit for bit what they give over its own pool."""
    Sq, H, KV, ps, mps = (cfg["Sq"], cfg["H"], cfg["KV"], cfg["ps"],
                          cfg["mps"])
    B, hd, P = 2, 16, 2 * mps + 3
    # starts chosen so the last allocated page is PARTIALLY filled
    fill = [ps + ps // 2 + Sq, ps // 2 + Sq]
    q, pk, pv, bt = _paged_setup(KEY, B, Sq, H, KV, hd, P, ps, mps, fill)
    start = jnp.asarray([f - Sq for f in fill], jnp.int32)
    got = ops.paged_prefill(q, pk, pv, bt, start, window=cfg["window"])
    want = ref.paged_attention(q, pk, pv, bt, start, window=cfg["window"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if stacked:
        ks = jax.random.split(KEY, 2)
        from_stack = ops.paged_prefill(
            q, _in_stack(ks[0], pk), _in_stack(ks[1], pv), bt, start,
            jnp.int32(1), window=cfg["window"])
        np.testing.assert_array_equal(np.asarray(from_stack),
                                      np.asarray(got))


def test_paged_attention_skips_unallocated_and_future_pages():
    """Rows of unallocated pages and pages beyond the causal frontier can
    never contribute: poisoning them with huge values must not change the
    output (the block-skip + masking contract), and a freed slot (all--1
    table) returns exactly zero."""
    B, Sq, H, KV, hd, P, ps, mps = 2, 4, 4, 2, 16, 8, 8, 4
    q, pk, pv, bt = _paged_setup(KEY, B, Sq, H, KV, hd, P, ps, mps, [12, 4])
    start = jnp.asarray([8, 0], jnp.int32)
    base = ops.paged_prefill(q, pk, pv, bt, start)
    # poison every pool row that is NOT a valid row of some slot's prefix
    valid = np.zeros(P * ps, bool)
    btn = np.asarray(bt)
    for b, last in enumerate([11, 3]):
        for r in range(last + 1):
            valid[btn[b, r // ps] * ps + r % ps] = True
    poison = jnp.where(jnp.asarray(valid)[:, None, None],
                       pk.reshape(P * ps, KV, hd), 1e9).reshape(pk.shape)
    poison_v = jnp.where(jnp.asarray(valid)[:, None, None],
                         pv.reshape(P * ps, KV, hd), 1e9).reshape(pv.shape)
    got = ops.paged_prefill(q, poison, poison_v, bt, start)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    freed = ops.paged_decode(q[:, :1], pk, pv,
                             jnp.full((B, mps), -1, jnp.int32),
                             jnp.asarray([1 << 30, (1 << 30) + 7], jnp.int32))
    assert np.all(np.asarray(freed) == 0)


def test_paged_attention_prefix_aliased_pages_shared_across_slots():
    """Two slots whose block tables alias the SAME physical prefix pages
    (the prefix-cache layout) read identical prefix rows: with identical
    queries and identical tail pages, their outputs coincide."""
    Sq, H, KV, hd, P, ps, mps = 4, 4, 2, 16, 6, 8, 3
    ks = jax.random.split(KEY, 3)
    q1 = _rand(ks[0], (1, Sq, H, hd))
    q = jnp.concatenate([q1, q1], axis=0)
    pk = _rand(ks[1], (P, ps, KV, hd))
    pv = _rand(ks[2], (P, ps, KV, hd))
    flat_k = pk.reshape(P * ps, KV, hd)
    flat_v = pv.reshape(P * ps, KV, hd)
    # shared prefix page 2 for both slots; tail pages 0 vs 4 hold the SAME
    # rows copied across (so outputs must match exactly)
    rows = jnp.arange(ps)
    flat_k = flat_k.at[4 * ps + rows].set(flat_k[0 * ps + rows])
    flat_v = flat_v.at[4 * ps + rows].set(flat_v[0 * ps + rows])
    pk = flat_k.reshape(P, ps, KV, hd)
    pv = flat_v.reshape(P, ps, KV, hd)
    bt = jnp.asarray([[2, 0, -1], [2, 4, -1]], jnp.int32)
    start = jnp.asarray([ps + 2, ps + 2], jnp.int32)   # mid tail page
    out = ops.paged_prefill(q, pk, pv, bt, start)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


def test_paged_attention_degenerate_one_page_spans_s_max():
    """page_size == s_max (one page per slot): the paged kernel collapses to
    plain causal attention over the slot's rows — cross-checked against the
    flash-attention oracle on the same rows."""
    B, S, H, KV, hd = 2, 16, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, S, H, hd))
    k = _rand(ks[1], (B, S, KV, hd))
    v = _rand(ks[2], (B, S, KV, hd))
    # pool with one page per slot holding that slot's rows
    pk = jnp.stack([k[0], k[1]])
    pv = jnp.stack([v[0], v[1]])
    bt = jnp.asarray([[0], [1]], jnp.int32)
    start = jnp.zeros((B,), jnp.int32)
    got = ops.paged_prefill(q, pk, pv, bt, start)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _decode_case(H, KV, ps, mps, pos, hole=None):
    """q (B, 1, H, 16), pools and a block table covering each slot's
    ``pos``; ``hole`` = (slot, table index) unallocates one live page."""
    q, pk, pv, bt = _paged_setup(KEY, len(pos), 1, H, KV, 16,
                                 sum(p // ps + 1 for p in pos) + 3, ps, mps,
                                 [p + 1 for p in pos])
    if hole is not None:
        bt = bt.at[hole].set(-1)
    return q, pk, pv, bt, jnp.asarray(pos, jnp.int32)


# blocks are pa.DECODE_BLOCK_KEYS (256) keys: positions 255 and 511 end a
# block exactly, 256 starts one
@pytest.mark.parametrize("case,stacked", _one_layer_then_stacked([
    dict(H=4, KV=4, ps=8, mps=48, pos=[19, 300]),             # G 1, part page
    dict(H=10, KV=2, ps=8, mps=48, pos=[255, 256]),           # G 5, boundary
    dict(H=7, KV=1, ps=16, mps=40, pos=[100, 511]),           # G 7, boundary
    dict(H=4, KV=2, ps=8, mps=96, pos=[2, 700, 131]),         # ragged lengths
    dict(H=4, KV=2, ps=8, mps=96, pos=[40, 600], window=100),
    dict(H=4, KV=2, ps=8, mps=96, pos=[511, 300], window=256),
    dict(H=4, KV=2, ps=8, mps=48, pos=[300, 40], hole=(0, 1)),
    dict(H=8, KV=4, ps=8, mps=48, pos=[77, 260], int8=True),
    dict(H=8, KV=2, ps=16, mps=40, pos=[77, 300], bf16=True),
], ["g1", "g5-block-edge", "g7-block-edge", "ragged", "window",
    "window-block-edge", "unallocated-live-page", "int8", "bf16"]))
def test_paged_decode_kernel_matches_ref(case, stacked):
    """The decode kernel (one program per slot, a loop over the slot's live
    blocks, every page read once for its whole GQA group) against the
    masked-gather oracle. Over a 3-layer stack at a traced layer it DMAs
    that layer's pages out of the stack and gives bit for bit what it
    gives over the layer's own pool."""
    from repro.kernels import paged_attention as pa
    q, pk, pv, bt, pos = _decode_case(case["H"], case["KV"], case["ps"],
                                      case["mps"], case["pos"],
                                      case.get("hole"))
    sc = {}
    tol = 2e-5
    if case.get("int8"):
        pk, pv = (jnp.clip(jnp.round(x * 40), -127, 127).astype(jnp.int8)
                  for x in (pk, pv))
        scale = (1 + jnp.arange(pk.shape[0]) % 3) / 40.0
        sc = dict(k_scale=scale, v_scale=scale * 0.5)
    if case.get("bf16"):
        # the oracle is exact on the same bf16 inputs; both round the
        # output to bf16 once
        q, pk, pv = (x.astype(jnp.bfloat16) for x in (q, pk, pv))
        tol = 1e-2
    assert pa.takes_decode_kernel(q, pk)
    w = case.get("window", 0)
    kernel = jax.jit(pa.paged_attention,
                     static_argnames=("window", "interpret"))
    # one layer's pools: a stack of one, as ops.paged_decode passes them
    got = kernel(q, pk[None], pv[None], bt, pos, 0, window=w,
                 interpret=True, **sc)
    want = ref.paged_attention(q, pk, pv, bt, pos, window=w, **sc)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    if stacked:
        ks = jax.random.split(KEY, 2)
        stack_k, stack_v = _in_stack(ks[0], pk), _in_stack(ks[1], pv)
        assert pa.takes_decode_kernel(q, stack_k)
        from_stack = kernel(q, stack_k, stack_v, bt, pos, jnp.int32(1),
                            window=w, interpret=True, **sc)
        np.testing.assert_array_equal(np.asarray(from_stack),
                                      np.asarray(got))


def test_paged_decode_skips_unallocated_and_future_pages():
    """Decode reads no row past a slot's position and no unallocated page:
    poisoning every other pool row with huge values changes nothing, and a
    parked slot (all--1 table) returns exactly zero."""
    H, KV, ps, mps = 4, 2, 8, 48
    q, pk, pv, bt, pos = _decode_case(H, KV, ps, mps, [300, 5])
    base = ops.paged_decode(q, pk, pv, bt, pos)
    P = pk.shape[0]
    valid = np.zeros(P * ps, bool)
    btn = np.asarray(bt)
    for b, last in enumerate([300, 5]):
        for r in range(last + 1):
            valid[btn[b, r // ps] * ps + r % ps] = True
    keep = jnp.asarray(valid)[:, None, None]
    poison = [jnp.where(keep, x.reshape(P * ps, KV, 16), 1e9).reshape(x.shape)
              for x in (pk, pv)]
    got = ops.paged_decode(q, *poison, bt, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    parked = ops.paged_decode(q, pk, pv, jnp.full((2, mps), -1, jnp.int32),
                              jnp.asarray([1 << 30, (1 << 30) + 7], jnp.int32))
    assert np.all(np.asarray(parked) == 0)


def _pallas_grids(fn, *args) -> list:
    """The grid of every pallas_call in ``fn``'s jaxpr, nested jits too."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


def test_paged_decode_work_follows_live_pages_not_table_width():
    """Widening the block table with -1 columns (mps 4 -> 64) leaves decode
    bit-identical: the loop is bounded by each slot's position, not by the
    table. Routing: ``paged_decode`` (one row) runs the decode kernel, one
    program per slot; ``paged_prefill`` (a chunk) runs the page-grid
    kernel, one program per slot, query head and table page."""
    H, KV, ps = 4, 2, 8
    q, pk, pv, bt, pos = _decode_case(H, KV, ps, 4, [30, 9])
    wide = jnp.concatenate([bt, jnp.full((2, 60), -1, jnp.int32)], axis=1)
    narrow_out = ops.paged_decode(q, pk, pv, bt, pos)
    wide_out = ops.paged_decode(q, pk, pv, wide, pos)
    np.testing.assert_array_equal(np.asarray(wide_out),
                                  np.asarray(narrow_out))
    assert _pallas_grids(ops.paged_decode, q, pk, pv, wide, pos) == [(2,)]
    qc = jnp.concatenate([q, q], axis=1)
    assert _pallas_grids(ops.paged_prefill, qc, pk, pv, wide,
                         pos - 1) == [(2, H, 64)]
    # a bf16 pool with one kv head (a tp shard's slice) is narrower than a
    # 32-bit sublane: its decode stays on the page-grid kernel
    bf = lambda x: x[:, :, :1].astype(jnp.bfloat16)  # noqa: E731
    assert _pallas_grids(ops.paged_decode, bf(q), bf(pk), bf(pv), wide,
                         pos) == [(2, 1, 64)]


def test_mask_value_dtype_aware():
    """The -1e30 sentinel satellite: finite in every dtype (fp16's max is
    65504, so the historical constant overflowed to -inf there and a fully
    masked row softmaxed to NaN), unchanged for f32/bf16."""
    from repro.models.layers import mask_value
    assert mask_value(jnp.float32) == -1e30
    assert mask_value(jnp.bfloat16) == -1e30
    f16 = mask_value(jnp.float16)
    assert np.isfinite(np.float16(f16))
    assert f16 < -1e4
    for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
        assert np.isfinite(np.asarray(jnp.asarray(mask_value(dt), dt)))


@pytest.mark.parametrize("T,chunk", [(64, 16), (64, 32), (128, 64), (33, 16)])
def test_wkv6(T, chunk):
    B, H, N = 2, 3, 16
    ks = jax.random.split(KEY, 6)
    r, k, v = (_rand(kk, (B, T, H, N), scale=0.5) for kk in ks[:3])
    w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, N))) * 0.5 + 0.5
    u = _rand(ks[4], (H, N), scale=0.1)
    s0 = _rand(ks[5], (B, H, N, N), scale=0.1)
    y1, sT1 = ops.wkv6(r, k, v, w, u, s0, chunk=chunk)
    y2, sT2 = ref.wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT2), rtol=3e-4, atol=3e-4)


def test_wkv6_strong_decay():
    """Numerical safety with aggressive decays (w near 0)."""
    B, T, H, N = 1, 64, 2, 8
    ks = jax.random.split(KEY, 5)
    r, k, v = (_rand(kk, (B, T, H, N), scale=0.5) for kk in ks[:3])
    w = jnp.full((B, T, H, N), 0.05)
    u = _rand(ks[3], (H, N), scale=0.1)
    s0 = jnp.zeros((B, H, N, N))
    y1, _ = ops.wkv6(r, k, v, w, u, s0, chunk=16)
    y2, _ = ref.wkv6(r, k, v, w, u, s0)
    assert np.isfinite(np.asarray(y1)).all()
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("T,chunk", [(64, 16), (128, 64)])
def test_selective_scan(T, chunk):
    B, D, N = 2, 32, 8
    ks = jax.random.split(KEY, 6)
    x = _rand(ks[0], (B, T, D))
    dt = jax.nn.softplus(_rand(ks[1], (B, T, D)))
    b = _rand(ks[2], (B, T, N))
    c = _rand(ks[3], (B, T, N))
    a = -jnp.exp(_rand(ks[4], (D, N), scale=0.5))
    h0 = _rand(ks[5], (B, D, N), scale=0.1)
    y1, h1 = ops.selective_scan(x, dt, b, c, a, h0, chunk=chunk)
    y2, h2 = ref.selective_scan(x, dt, b, c, a, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4, atol=2e-4)


def test_matmul_grad_flows():
    """Kernels are differentiable via interpret mode (training usability)."""
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (64, 64))
    w = _rand(ks[1], (64, 64))

    def f(x, w):
        return jnp.sum(ops.matmul(x, w, block_m=64, block_n=64, block_k=64) ** 2)
    gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx2), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw2), rtol=1e-3, atol=1e-3)
