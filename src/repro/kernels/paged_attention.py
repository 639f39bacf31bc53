"""Paged attention: block-table-indirect blockwise softmax over a shared
KV page pool (the serving engine's vLLM-style cache layout).

The repo's first kernel whose memory access pattern is INDIRECT: K/V blocks
are not a function of grid indices alone — each (slot, kv-page) grid step
reads the page named by ``block_tables[slot, page_idx]`` out of the shared
pool ``(num_pages, page_size, KV, hd)``. The block table and the per-slot
start positions ride in as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``) so the index map can steer each block's
DMA before the body runs — the same "compute never waits on a dense,
oversized buffer" dataflow the paper builds around Ultra RAM placement.

One kernel serves both serving attention shapes:

* **decode** — Sq == 1, one new query row per slot at position ``start[b]``;
* **prefill chunk** — Sq == C consecutive prompt positions starting at
  ``start[b]`` (the engine's incremental per-chunk splice writes the chunk's
  K/V rows into the pool FIRST, so the kernel reads prior chunks, aliased
  prefix pages, and the current chunk uniformly through the block table).

Fully-masked pages are SKIPPED (``pl.when``): unallocated block-table slots
(page id -1), pages wholly beyond the causal frontier
(``page_start > start + Sq - 1``), and — for windowed layers — pages wholly
behind the sliding window. Work therefore scales with each slot's LIVE
pages, not with the block-table span (s_max), which is exactly the
O(C x s_max) masked-einsum cost this kernel replaces. Partially-filled last
pages and partially-visible pages are handled by per-row masking inside the
body; masked probabilities are explicitly zeroed (not just sentinel-masked)
so a row with no valid key in a visited page contributes nothing, and a row
with no valid key anywhere (a freed slot parked at INACTIVE_POS with an
all--1 block table) returns exactly 0 through the ``l == 0`` guard.

Grid: (B, H, mps) with the kv page index innermost so the online-softmax
accumulators (m, l, acc) persist in VMEM scratch across a slot's pages —
the paper's "accumulators in on-chip RAM" structure, same as the flash
kernel. GQA shares each K/V block across ``H // KV`` query heads via the
``h // G`` index map.

Block layout (Mosaic's rule: the last two dims of every block are
divisible by (8, 128) or equal the array's own). The head axis is folded
into the lane axis — q ``(B, Sq, H * hd)``, pools ``(P, ps, KV * hd)`` —
so one head's block is ``(Sq, hd)`` / ``(ps, hd)``: Sq and ps are whole
array dims and hd is a lane multiple. The fold is a reshape, which for a
bf16 pool on the TPU's tiled layout compiles to a relayout copy. The latent pool
has no head axis (``(P, ps, c + r)``, a whole-row block at any width), and
its absorbed queries are moved head-major, ``(B, H, Sq, c + r)``, because
c + r (576 for qwen2.5-32b-mla) is not a lane multiple.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30   # f32 scratch sentinel (never materialized in low precision)


def _kernel(*refs, scale: float, window: int, block_q: int, page_size: int,
            quantized: bool, d_v: int):
    """One (slot b, head h, page j) step of the online softmax. Operand
    order follows the pallas_call: scalar prefetch (block table, starts[,
    k/v page scales]), then q, k[, v], out, then the m/l/acc scratch.

    ``quantized``: int8 pools, DEQUANTIZED in-register right after the DMA
    with the page's symmetric scale (a scalar-prefetch operand), so HBM
    only ever moves int8 payload. ``d_v > 0``: MLA latent pages — there is
    no v pool; values are the leading ``d_v`` columns of the same latent
    rows, so each page is DMA'd once for both roles."""
    if quantized:
        bt_ref, start_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref = refs[:8]
    elif d_v:
        bt_ref, start_ref, q_ref, k_ref, o_ref = refs[:5]
    else:
        bt_ref, start_ref, q_ref, k_ref, v_ref, o_ref = refs[:6]
    m_ref, l_ref, acc_ref = refs[-3:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page = bt_ref[b, j]
    start = start_ref[b]
    k_start = j * page_size

    def visit():
        q = q_ref[...].astype(jnp.float32)                     # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                     # (ps, hd)
        if quantized:
            k = k * ks_ref[jnp.maximum(page, 0)]
        # dot-then-scale in f32: the same operation order as the masked-
        # einsum reference, so the degenerate one-page config stays
        # numerically aligned with it
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
        ok = k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # explicit zeroing, not exp(sentinel): a row fully masked in THIS
        # page while m is still NEG_INF would otherwise turn exp(0) == 1
        # into garbage mass from rows it may never attend
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        if d_v:
            v = k[:, :d_v]
        else:
            v = v_ref[...].astype(jnp.float32)
            if quantized:
                v = v * vs_ref[jnp.maximum(page, 0)]
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # whole-page skip: unallocated, beyond the causal frontier of the LAST
    # query row, or (windowed) wholly behind the FIRST query row's window
    relevant = (page >= 0) & (k_start <= start + block_q - 1)
    if window > 0:
        relevant &= (k_start + page_size - 1) > (start - window)
    pl.when(relevant)(visit)

    @pl.when(j == nj - 1)
    def _():
        # l == 0 (no valid key anywhere — freed slot, all pages skipped)
        # yields exactly 0, matching the reference oracle
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _scratch(rows: int, width: int):
    """Online-softmax state: running max and denominator as (rows, 1)
    columns (2-D, so they tile like every other VMEM operand), and the f32
    accumulator."""
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, width), jnp.float32)]


def paged_attention_latent(q, pool_c, block_tables, start, *,
                           scale_dim: int, d_v: int, interpret: bool = False):
    """Paged attention over MLA latent pages.

    q: (B, Sq, H, c+r) ABSORBED queries (q_nope pushed through wkv_b's key
    half, concat decoupled RoPE head); pool_c: (P, page_size, 1, c+r) — one
    latent row per token, no per-head K/V; block_tables/start as in
    :func:`paged_attention`. ``scale_dim`` is the logical attention width
    (qk_nope_head_dim + qk_rope_head_dim) the softmax is scaled by — NOT
    the latent width the dot products contract over. Values are the leading
    ``d_v`` (= kv_lora_rank) columns of the same latent rows; output is
    (B, Sq, H, d_v), still in latent space (the caller applies wkv_b's
    value half and wo)."""
    B, Sq, H, L = q.shape
    P, ps, KV, _ = pool_c.shape
    assert KV == 1, "latent pool carries one shared row per token"
    mps = block_tables.shape[1]
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(scale_dim),
                               window=0, block_q=Sq, page_size=ps,
                               quantized=False, d_v=d_v)
    # one shared latent block per (slot, page) step — every query head h
    # reads the page named by the prefetched block table
    kv_map = lambda b, h, j, bt, st: (jnp.maximum(bt[b, j], 0), 0, 0)
    q_map = lambda b, h, j, bt, st: (b, h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, mps),
        in_specs=[pl.BlockSpec((None, None, Sq, L), q_map),
                  pl.BlockSpec((None, ps, L), kv_map)],
        out_specs=pl.BlockSpec((None, None, Sq, d_v), q_map),
        scratch_shapes=_scratch(Sq, d_v),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d_v), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(start, jnp.int32),
      q.transpose(0, 2, 1, 3), pool_c.reshape(P, ps, L))
    return out.transpose(0, 2, 1, 3)


def paged_attention(q, pool_k, pool_v, block_tables, start, *,
                    window: int = 0, interpret: bool = False,
                    k_scale=None, v_scale=None):
    """q: (B, Sq, H, hd); pool_k/pool_v: (P, page_size, KV, hd);
    block_tables: (B, mps) int32 page ids (-1 = unallocated);
    start: (B,) int32 — the position of each slot's FIRST query row (query
    row i is at ``start[b] + i``; logical key row r lives in page ``r // ps``
    at offset ``r % ps``). Returns (B, Sq, H, hd) in q.dtype.

    k_scale/v_scale: optional (P,) f32 per-page symmetric scales for int8
    pools; when given, the kernel dequantizes each gathered page inside
    its body (scales prefetched to SMEM alongside the block table)."""
    B, Sq, H, hd = q.shape
    P, ps, KV, _ = pool_k.shape
    assert H % KV == 0
    G = H // KV
    mps = block_tables.shape[1]
    quantized = k_scale is not None
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(hd),
                               window=window, block_q=Sq, page_size=ps,
                               quantized=quantized, d_v=0)
    # the kv index maps read the PREFETCHED block table: the page a grid
    # step streams is data-dependent (clamped at 0 for unallocated slots —
    # the body skips those steps entirely, the clamp only keeps the
    # prefetch in bounds). Scalar-prefetch operands land FIRST in the
    # kernel signature and as trailing index-map params; the q8 path adds
    # the two scale tables after (bt, start).
    prefetch = [jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(start, jnp.int32)]
    if quantized:
        prefetch += [jnp.asarray(k_scale, jnp.float32),
                     jnp.asarray(v_scale, jnp.float32)]
    kv_map = lambda b, h, j, bt, *_: (jnp.maximum(bt[b, j], 0), 0, h // G)
    q_map = lambda b, h, j, *_: (b, 0, h)
    kv_spec = pl.BlockSpec((None, ps, hd), kv_map)
    q_spec = pl.BlockSpec((None, Sq, hd), q_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, H, mps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=_scratch(Sq, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
        interpret=interpret,
    )(*prefetch, q.reshape(B, Sq, H * hd), pool_k.reshape(P, ps, KV * hd),
      pool_v.reshape(P, ps, KV * hd))
    return out.reshape(B, Sq, H, hd)


def paged_attention_head_sharded(dispatch, mesh, axis, q, pool_k, pool_v,
                                 block_tables, start, *, window: int = 0,
                                 k_scale=None, v_scale=None):
    """Tensor-parallel head-shard dispatch around the paged kernel.

    ``pallas_call`` lowers to a CustomCall that GSPMD cannot partition, so
    the tp serve path wraps the local dispatch in an explicit ``shard_map``:
    q and both pools split on their head axes over the ``axis`` mesh axis
    (the pool leaves are already RESIDENT with exactly this sharding, so no
    data moves for them); block tables and start positions are replicated —
    page ids are shard-invariant. The q8 page scales arrive as (P, tp)
    tables — one column per kv-head GROUP, resident sharded on the group
    axis alongside their kv heads — so each shard slices out its own (P, 1)
    column and squeezes it to the (P,) layout the local dispatch expects:
    the scale each shard dequantizes with was computed from that shard's
    kv heads alone and never crosses the mesh. Each shard runs the
    unmodified kernel on its (B, H/tp, pages) sub-grid, and the outputs
    concatenate back on the head axis. Per-head attention is independent,
    so every output element is computed by exactly one shard with the same
    op sequence as tp=1 — the basis of the bitwise tp equivalence anchor.

    ``dispatch`` is the single-device dispatch to run per shard
    (``ops._paged_dispatch_local`` — passed in so the interpret-grid guard
    and the einsum oracle fallback see per-shard grid sizes). The caller
    guarantees the axis size divides both H and KV on whole-GQA-group
    boundaries (see sharding.specs.head_shard_axis)."""
    from jax.sharding import PartitionSpec as SP

    heads = SP(None, None, axis, None)    # q/out (B,Sq,H,hd); pools (P,ps,KV,hd)
    repl1 = SP(None)
    repl2 = SP(None, None)

    if k_scale is not None:
        scales = SP(None, axis)           # (P, tp) -> per-shard (P, 1)
        def body(q_, pk_, pv_, bt_, st_, ks_, vs_):
            return dispatch(q_, pk_, pv_, bt_, st_, window,
                            k_scale=ks_[:, 0], v_scale=vs_[:, 0])
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(heads, heads, heads, repl2, repl1, scales, scales),
            out_specs=heads, check_vma=False,
        )(q, pool_k, pool_v, block_tables, start, k_scale, v_scale)

    def body(q_, pk_, pv_, bt_, st_):
        return dispatch(q_, pk_, pv_, bt_, st_, window)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(heads, heads, heads, repl2, repl1),
        out_specs=heads, check_vma=False,
    )(q, pool_k, pool_v, block_tables, start)


def paged_attention_latent_head_sharded(dispatch, mesh, axis, q, pool_c,
                                        block_tables, start, *,
                                        scale_dim: int, d_v: int):
    """Tensor-parallel dispatch around the LATENT paged kernel.

    The latent pool has no kv-head axis (KV == 1; every query head reads
    the same compressed rows) and is resident REPLICATED, so the split
    lives entirely on the ABSORBED queries/outputs: q (B, Sq, H, c+r) and
    the (B, Sq, H, d_v) output shard on their head axis while pool, block
    tables, and start positions replicate. Per-head attention over the
    shared latent is head-independent — each output element is computed by
    exactly one shard with the same op sequence as tp=1, so the latent tp
    path inherits the bitwise equivalence anchor (the caller's all-gather
    before ``wo`` does the rest).

    ``dispatch`` is the single-device latent dispatch
    (``ops._paged_dispatch_latent`` — passed in so the interpret-grid guard
    sees per-shard H). The caller guarantees the axis size divides H
    (sharding.specs.latent_head_shard_axis)."""
    from jax.sharding import PartitionSpec as SP

    heads = SP(None, None, axis, None)    # q (B,Sq,H,c+r) / out (B,Sq,H,d_v)
    repl4 = SP(None, None, None, None)    # pool_c (P,ps,1,c+r)
    repl2 = SP(None, None)
    repl1 = SP(None)

    def body(q_, pc_, bt_, st_):
        return dispatch(q_, pc_, bt_, st_, scale_dim, d_v)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(heads, repl4, repl2, repl1),
        out_specs=heads, check_vma=False,
    )(q, pool_c, block_tables, start)
