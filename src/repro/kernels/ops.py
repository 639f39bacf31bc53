"""Public jit'd wrappers for the Pallas kernels.

Off the TPU kernels execute with ``interpret=True`` — the kernel body runs
as traced JAX ops so correctness is validated end-to-end; on TPU the same
calls compile to Mosaic. Wrappers pad inputs to block multiples and crop.
Off the TPU, shapes a kernel does not take run the jnp oracle; on the TPU
they raise, because a silent oracle there would report a kernel-less run
as a kernel run. Which path serves a model is decided once, from shapes,
where the engine is built (``attention_kernel_fits``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import linear_scan as _ls
from repro.kernels import matmul as _mm
from repro.kernels import paged_attention as _pa
from repro.kernels import quant_matmul as _qm
from repro.kernels import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def attention_kernel_fits(head_dim: int) -> bool:
    """Can the per-head attention kernels (flash, paged) tile this head
    width here? On the TPU a head is one block's lane axis, so it must be
    a multiple of 128; interpret mode tiles any width."""
    return _interpret() or head_dim % 128 == 0


def _oracle(kernel: str, why: str):
    """Gate every jnp-oracle substitution: allowed in interpret mode only.
    On the TPU the caller asked for a kernel the shapes do not fit — an
    engine-build or caller error, raised instead of hidden."""
    if not _interpret():
        raise ValueError(f"{kernel}: no TPU kernel for {why}; choose the "
                         f"einsum path for these shapes instead")


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _matmul_vjp(x, w, block_m, block_n, block_k, dataflow):
    M, K = x.shape
    _, N = w.shape
    bm, bn, bk = (min(block_m, M), min(block_n, N), min(block_k, K))
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    out = _mm.matmul(xp, wp, block_m=bm, block_n=bn, block_k=bk,
                     dataflow=dataflow, interpret=_interpret(), out_dtype=x.dtype)
    return out[:M, :N]


def _matmul_fwd(x, w, bm, bn, bk, df):
    return _matmul_vjp(x, w, bm, bn, bk, df), (x, w)


def _matmul_bwd(bm, bn, bk, df, res, g):
    x, w = res
    # dX = g @ W^T ; dW = X^T @ g — both through the systolic kernel
    dx = _matmul_vjp(g, w.T, bm, bn, bk, df)
    dw = _matmul_vjp(x.T, g, bm, bn, bk, df)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_matmul_vjp.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "dataflow"))
def matmul(x, w, *, block_m: int = 128, block_n: int = 128, block_k: int = 128,
           dataflow: str = "output_stationary"):
    """Systolic tiled matmul; pads to block multiples, crops the result.
    Differentiable: the custom VJP routes both gradient GEMMs back through
    the kernel (training-usable, not just inference)."""
    return _matmul_vjp(x, w, block_m, block_n, block_k, dataflow)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def quant_matmul(x, w_q, scales, *, block_m: int = 128, block_n: int = 128,
                 block_k: int = 128):
    M, K = x.shape
    _, N = w_q.shape
    bm, bn, bk = (min(block_m, M), min(block_n, N), min(block_k, K))
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
    sp = _pad_to(scales, bn, 0)
    out = _qm.quant_matmul(xp, wp, sp, block_m=bm, block_n=bn, block_k=bk,
                           interpret=_interpret(), out_dtype=x.dtype)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    q_positions=None, k_positions=None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd). Positions args accepted for API
    parity with ref; the kernel derives prefill positions from block indices
    (non-standard positions fall back to the oracle)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if q_positions is not None or k_positions is not None:
        _oracle("flash_attention", "explicit positions")
        return _ref.flash_attention(q, k, v, causal=causal, window=window,
                                    q_positions=q_positions,
                                    k_positions=k_positions)
    if Sq % bq or Sk % bk or not attention_kernel_fits(hd):
        _oracle("flash_attention", f"q {q.shape} / k {k.shape}")
        return _ref.flash_attention(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                  block_q: int = 128, block_k: int = 128):
    """K/V-exporting prefill attention: returns ``(O, K, V)`` where K/V are
    the post-RoPE tiles ready for the serving cache scatter (paged block
    tables or dense rows). On TPU the export rides the kernel's existing
    VMEM residency (one fused HBM pass); non-block-multiple shapes run the
    jnp oracle in interpret mode and raise on the TPU."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if Sq % bq or Sk % bk or not attention_kernel_fits(hd):
        _oracle("flash_prefill", f"q {q.shape} / k {k.shape}")
        return _ref.flash_attention_kv(q, k, v, causal=causal, window=window)
    return _fa.flash_attention_kv(q, k, v, causal=causal, window=window,
                                  block_q=bq, block_k=bk,
                                  interpret=_interpret())


# trace-size guard for the paged kernel: interpret mode inlines one kernel
# body per grid step (B * H * mps), so an oversized grid would explode trace
# time off the TPU and routes to the jnp oracle (the Mosaic grid is free)
_PAGED_MAX_INTERPRET_GRID = 4096


def _paged_dispatch_local(q, pool_k, pool_v, block_tables, start, window: int,
                          k_scale=None, v_scale=None):
    """Single-device paged-attention dispatch (also the per-shard body under
    the tp shard_map — the interpret-grid guard then sees per-shard H,
    which is the point of passing this in whole)."""
    B, Sq, H, hd = q.shape
    mps = block_tables.shape[1]
    sc = dict(k_scale=k_scale, v_scale=v_scale)
    if not attention_kernel_fits(hd):
        _oracle("paged_attention", f"head_dim {hd}")
    if _interpret() and B * H * mps > _PAGED_MAX_INTERPRET_GRID:
        return _ref.paged_attention(q, pool_k, pool_v, block_tables,
                                    start, window=window, **sc)
    return _pa.paged_attention(q, pool_k, pool_v, block_tables, start,
                               window=window, interpret=_interpret(), **sc)


def _squeeze_scale(s):
    """Accept a (P,) scale table or the int8 backend's (P, 1) single-group
    column (tp=1 keeps one whole-page group; multi-group tables only ever
    meet the kernel inside the head-sharded shard_map, which slices each
    shard's own column)."""
    if s is not None and s.ndim == 2:
        s = s[:, 0]
    return s


def _paged_dispatch(q, pool_k, pool_v, block_tables, start, window: int,
                    k_scale=None, v_scale=None, mesh=None, shard_axis=None):
    if mesh is not None and shard_axis is not None:
        return _pa.paged_attention_head_sharded(
            _paged_dispatch_local, mesh, shard_axis, q, pool_k, pool_v,
            block_tables, start, window=window,
            k_scale=k_scale, v_scale=v_scale)
    return _paged_dispatch_local(q, pool_k, pool_v, block_tables, start,
                                 window, k_scale=_squeeze_scale(k_scale),
                                 v_scale=_squeeze_scale(v_scale))


# mesh/shard_axis are STATIC jit args (Mesh is hashable), not read from the
# sharding contextvar inside the traced body: these wrappers are module-level
# jits whose trace cache keys on abstract args only, so a contextvar read
# could silently reuse a non-mesh trace across engines. Callers resolve the
# head-shard decision at their own trace time (sharding.specs.head_shard_axis)
# and pass it down explicitly.
@functools.partial(jax.jit, static_argnames=("window", "mesh", "shard_axis"))
def paged_decode(q, pool_k, pool_v, block_tables, cache_pos, *,
                 window: int = 0, mesh=None, shard_axis=None):
    """Single-token decode attention against a paged KV cache.

    q: (B, 1, H, hd); pool_k/pool_v: (P, page_size, KV, hd) — one layer's
    slice of the shared pool; block_tables: (B, mps) int32 (-1 =
    unallocated); cache_pos: (B,) int32 per-slot positions (the new K/V row
    must already be WRITTEN at logical row cache_pos[b] — the write stays a
    plain block-table scatter outside the kernel). Gathers K/V blocks
    through the block table inside the kernel and skips fully-masked pages;
    a freed slot (all--1 table) returns exactly 0. mesh/shard_axis (from
    specs.head_shard_axis) route through the head-sharded shard_map."""
    return _paged_dispatch(q, pool_k, pool_v, block_tables, cache_pos,
                           window, mesh=mesh, shard_axis=shard_axis)


@functools.partial(jax.jit, static_argnames=("window", "mesh", "shard_axis"))
def paged_prefill(q, pool_k, pool_v, block_tables, start, *,
                  window: int = 0, mesh=None, shard_axis=None):
    """Continuation-chunk prefill attention against a paged KV cache.

    q: (B, C, H, hd) — C consecutive prompt positions, row i of slot b at
    position ``start[b] + i``; the chunk's post-RoPE K/V rows must already
    be spliced into the slot's pages (the engine's incremental per-chunk
    scatter), so prior chunks, aliased prefix pages, and the current chunk
    are all read uniformly through the block table. Causal masking is
    ``k_pos <= q_pos`` over the slot's logical rows; pages wholly beyond
    the chunk's causal frontier (or unallocated) are skipped, so mask work
    scales with the slot's LIVE pages instead of O(C x s_max)."""
    return _paged_dispatch(q, pool_k, pool_v, block_tables, start, window,
                           mesh=mesh, shard_axis=shard_axis)


@functools.partial(jax.jit, static_argnames=("window", "mesh", "shard_axis"))
def paged_decode_q8(q, pool_k, pool_v, k_scale, v_scale, block_tables,
                    cache_pos, *, window: int = 0, mesh=None,
                    shard_axis=None):
    """paged_decode over INT8 pools: pool_k/pool_v are int8, k_scale/v_scale
    are (P,) — or per-kv-head-group (P, tp) — f32 per-page symmetric
    scales. Dequant happens inside the kernel's gather (scales prefetched
    to SMEM) — HBM traffic stays int8. mesh/shard_axis (from
    specs.head_shard_axis) route through the head-sharded shard_map, where
    each shard dequantizes with its own group's scale column."""
    return _paged_dispatch(q, pool_k, pool_v, block_tables, cache_pos,
                           window, k_scale=k_scale, v_scale=v_scale,
                           mesh=mesh, shard_axis=shard_axis)


@functools.partial(jax.jit, static_argnames=("window", "mesh", "shard_axis"))
def paged_prefill_q8(q, pool_k, pool_v, k_scale, v_scale, block_tables,
                     start, *, window: int = 0, mesh=None, shard_axis=None):
    """paged_prefill over INT8 pools (see paged_decode_q8)."""
    return _paged_dispatch(q, pool_k, pool_v, block_tables, start,
                           window, k_scale=k_scale, v_scale=v_scale,
                           mesh=mesh, shard_axis=shard_axis)


def _paged_dispatch_latent(q, pool_c, block_tables, start, scale_dim: int,
                           d_v: int):
    """MLA latent-page dispatch: same interpret-grid guard as the per-head
    paged dispatch, over the single shared latent pool. The latent kernel
    takes whole latent rows, so every width tiles on the TPU."""
    B, Sq, H, L = q.shape
    mps = block_tables.shape[1]
    if _interpret() and B * H * mps > _PAGED_MAX_INTERPRET_GRID:
        return _ref.paged_attention_latent(q, pool_c, block_tables, start,
                                           scale_dim=scale_dim, d_v=d_v)
    return _pa.paged_attention_latent(q, pool_c, block_tables, start,
                                      scale_dim=scale_dim, d_v=d_v,
                                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale_dim", "d_v", "mesh",
                                             "shard_axis"))
def paged_decode_latent(q, pool_c, block_tables, cache_pos, *,
                        scale_dim: int, d_v: int, mesh=None,
                        shard_axis=None):
    """Single-token decode attention over MLA latent pages.

    q: (B, 1, H, c+r) ABSORBED queries; pool_c: (P, page_size, 1, c+r) —
    one shared latent row per token, gathered once per page for both the
    score contraction and (its leading ``d_v`` columns) the value
    accumulation. ``scale_dim`` is the logical head width the softmax
    divides by. Returns (B, 1, H, d_v) in latent space — the caller owns
    the wkv_b value-half and ``wo`` projections. The latent pool itself
    has no kv-head axis (it stays replicated under tp); mesh/shard_axis
    (from specs.latent_head_shard_axis) shard the ABSORBED queries/outputs
    on their head axis through the latent shard_map wrapper."""
    if mesh is not None and shard_axis is not None:
        return _pa.paged_attention_latent_head_sharded(
            _paged_dispatch_latent, mesh, shard_axis, q, pool_c,
            block_tables, cache_pos, scale_dim=scale_dim, d_v=d_v)
    return _paged_dispatch_latent(q, pool_c, block_tables, cache_pos,
                                  scale_dim, d_v)


@functools.partial(jax.jit, static_argnames=("scale_dim", "d_v", "mesh",
                                             "shard_axis"))
def paged_prefill_latent(q, pool_c, block_tables, start, *,
                         scale_dim: int, d_v: int, mesh=None,
                         shard_axis=None):
    """Continuation-chunk prefill attention over MLA latent pages (see
    paged_decode_latent). q: (B, C, H, c+r); the chunk's latent rows must
    already be spliced into the slot's pages."""
    if mesh is not None and shard_axis is not None:
        return _pa.paged_attention_latent_head_sharded(
            _paged_dispatch_latent, mesh, shard_axis, q, pool_c,
            block_tables, start, scale_dim=scale_dim, d_v=d_v)
    return _paged_dispatch_latent(q, pool_c, block_tables, start,
                                  scale_dim, d_v)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, w, u, s0, *, chunk: int = 32):
    T = r.shape[1]
    c = min(chunk, T)
    if T % c:
        _oracle("wkv6", f"T={T} with chunk {c}")
        return _ref.wkv6(r, k, v, w, u, s0)
    y, sT = _ls.wkv6(r, k, v, w, u, s0, chunk=c, interpret=_interpret())
    return y, sT


@functools.partial(jax.jit, static_argnames=("chunk",))
def selective_scan(x, dt, b, c, a, h0, *, chunk: int = 64):
    T = x.shape[1]
    ck = min(chunk, T)
    if T % ck:
        _oracle("selective_scan", f"T={T} with chunk {ck}")
        return _ref.selective_scan(x, dt, b, c, a, h0)
    return _ls.selective_scan(x, dt, b, c, a, h0, chunk=ck, interpret=_interpret())
