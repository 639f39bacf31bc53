"""Paged attention: block-table-indirect blockwise softmax over a shared
KV page pool (the serving engine's vLLM-style cache layout).

K/V pages are not a function of grid indices alone: each slot reads the
pages named by ``block_tables[slot, :]`` out of the shared pool
``(num_pages, page_size, KV, hd)``, or out of layer ``layer`` of the
layer-stacked pool ``(L, num_pages, page_size, KV, hd)`` the serving steps
keep. The block table, the per-slot positions and the layer index ride in
as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), so they steer the DMAs before the body
computes — the same "compute never waits on a dense, oversized buffer"
dataflow the paper builds around Ultra RAM placement.

Three kernels: two for per-head K/V pools, chosen by the query shape
(:func:`paged_attention`), and one for MLA latent pages
(:func:`paged_attention_latent`):

* **decode** (:func:`paged_decode_attention`) — one query row per slot at
  position ``pos[b]``. Grid ``(B,)``: program b loops over only its slot's
  live blocks of ``decode_pages_per_block(ps)`` pages, from the first page
  a sliding window can see to the page holding ``pos[b]``, so its work
  follows the slot's live pages and not the table's width (s_max). The
  pool is read as stored from HBM (``memory_space=pl.ANY``): each step
  DMAs the block's whole ``(ps, KV, hd)`` pages into double-buffered VMEM
  while the previous block computes, and each kv head's ``G = H // KV``
  query rows attend that head's rows together, so a page is fetched once
  for its whole GQA group. A layer-stacked pool is addressed in place at
  the prefetched layer index. A parked slot (``pos >= mps * ps``, all--1
  table) runs no block.
* **prefill chunk** (the page-grid kernel) — Sq == C consecutive prompt
  positions starting at ``start[b]`` (the engine's per-chunk splice writes
  the chunk's K/V rows into the pool FIRST, so the kernel reads prior
  chunks, aliased prefix pages, and the current chunk uniformly through
  the block table). Grid ``(B, H, mps)``, the page index innermost so the
  online-softmax accumulators persist in VMEM scratch across a slot's
  pages; GQA shares a K/V block across ``H // KV`` query heads through the
  ``h // G`` index map. Every program of the grid is issued: ``pl.when``
  skips the body of a fully-masked page (unallocated, beyond the causal
  frontier, or wholly behind the window), so its compute, not its grid,
  follows the live pages. Each live page is fetched once per query head.
* **latent** (:func:`paged_attention_latent`) — decode and prefill chunks
  over pages of one shared (c + r)-wide latent row per token. The decode
  kernel's structure with the heads as rows: grid ``(B, Sq // block_q)``,
  each program a block of ``block_q`` positions times every head, looping
  over only its slot's live blocks up to the block's causal frontier, the
  pages DMA'd whole and double-buffered, so a page is read once per query
  block for all heads. Values are the leading c columns of the same rows.

Both mask partially-filled last pages and partially-visible pages per row,
and zero masked probabilities explicitly (not just sentinel-masked), so a
row with no valid key in a visited block contributes nothing and a row with
no valid key anywhere (a freed slot parked at INACTIVE_POS with an all--1
block table) returns exactly 0 through the ``l == 0`` guard.

Page-grid block layout (Mosaic's rule: the last two dims of every block
are divisible by (8, 128) or equal the array's own). The head axis is
folded into the lane axis — q ``(B, Sq, H * hd)``, pools
``(P, ps, KV * hd)`` — so one head's block is ``(Sq, hd)`` / ``(ps, hd)``.
The fold is a reshape, which for a bf16 pool on the TPU's tiled layout
compiles to a relayout copy of the layer's pool on every chunk call; out
of a layer-stacked pool the layer is sliced out first (XLA does not fuse
the slice into the relayout), and nothing is written back. The
latent kernel DMAs whole ``(ps, width)`` rows, which Mosaic slices only in
whole 128-lane tiles: on the TPU the pool stores each 576-wide latent row
as 640 lanes, zeros beyond it (``ops.latent_row_width``), and the queries
come padded to match.
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
            quantized: bool):
    """One (slot b, head h, page j) step of the online softmax. Operand
    order follows the pallas_call: scalar prefetch (block table, starts[,
    k/v page scales]), then q, k, v, out, then the m/l/acc scratch.

    ``quantized``: int8 pools, DEQUANTIZED in-register right after the DMA
    with the page's symmetric scale (a scalar-prefetch operand), so HBM
    only ever moves int8 payload."""
    if quantized:
        bt_ref, start_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref = refs[:8]
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


# keys per decode block: each loop step of the decode kernel DMAs this many
# keys' worth of whole pages (16 pages at page size 16)
DECODE_BLOCK_KEYS = 256


def decode_pages_per_block(page_size: int) -> int:
    """Whole pages per decode block (at least one)."""
    return max(1, DECODE_BLOCK_KEYS // page_size)


def takes_decode_kernel(q, pool) -> bool:
    """Does :func:`paged_attention` run this call on the decode kernel?
    One query row per slot, and a kv-head axis that fills a 32-bit sublane
    (bf16: KV >= 2, int8: KV >= 4): Mosaic slices no page out of a
    narrower pool. Only a tp shard's slice of a pool is that narrow; it
    stays on the page-grid kernel. ``pool`` is one layer's or the
    layer-stacked pool (the kv-head axis is second to last in both)."""
    return q.shape[1] == 1 and pool.shape[-2] * pool.dtype.itemsize >= 4


def _decode_kernel(*refs, scale: float, window: int, page_size: int,
                   ppb: int, mps: int, quantized: bool):
    """Program b: slot b's decode row for every query head. Operand order:
    scalar prefetch (block table, positions, layer[, k/v page scales]), q
    block (KV, G, hd), the layer-stacked K and V pools in HBM, the output
    block, then scratch (K/V page buffers, DMA semaphores, m/l/acc).

    The loop runs over the slot's live blocks only: blocks of ``ppb``
    pages from the first page the window can see to the page holding
    ``pos``. Each step starts the next block's page DMAs into the other
    buffer before it waits on and computes the current one."""
    bt_ref, pos_ref, layer_ref = refs[:3]
    if quantized:
        ks_ref, vs_ref, q_ref, k_hbm, v_hbm, o_ref = refs[3:9]
    else:
        q_ref, k_hbm, v_hbm, o_ref = refs[3:7]
    kbuf, vbuf, sems, m_ref, l_ref, acc_ref = refs[-6:]
    kv_heads, G, _ = q_ref.shape
    keys = ppb * page_size
    b = pl.program_id(0)
    pos = pos_ref[b]

    @pl.when(b == 0)
    def _():
        # pages past the live span are never copied: their buffer rows
        # meet p == 0 exactly, which needs them finite, never stale VMEM
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # a parked slot (pos >= mps * ps, all--1 table) has no live block
    live = (pos >= 0) & (pos < mps * page_size)
    first = 0
    if window > 0:
        first = jnp.maximum(pos - window + 1, 0) // page_size
    last = pos // page_size
    n_blocks = jnp.where(live, (last - first) // ppb + 1, 0)

    def copies(idx0, p, slot):
        # an unallocated (-1) entry inside the span copies page 0; the
        # mask drops its keys
        src = jnp.maximum(bt_ref[b, idx0 + p], 0)
        layer = layer_ref[0]
        return (pltpu.make_async_copy(k_hbm.at[layer, src], kbuf.at[slot, p],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, src], vbuf.at[slot, p],
                                      sems.at[1, slot]))

    def each_live_page(blk, slot, fn):
        """Apply ``fn`` to the K and V copies of every page of block
        ``blk`` that lies in the live span (a loop, not ``ppb`` branches)."""
        idx0 = first + blk * ppb

        def body(p, carry):
            for c in copies(idx0, p, slot):
                fn(c)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(ppb, last - idx0 + 1), body, 0)

    def table(blk):
        """Block ``blk``'s page ids (entries past the table read its last)."""
        idx0 = first + blk * ppb
        return [bt_ref[b, jnp.minimum(idx0 + p, mps - 1)] for p in range(ppb)]

    def dequant(x, scale_ref, ids):
        # (ppb, ps, hd) int8 -> f32, each page times its own scale
        return jnp.concatenate(
            [x[p] * scale_ref[jnp.maximum(page, 0)]
             for p, page in enumerate(ids)], axis=0)

    def step(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            each_live_page(blk + 1, 1 - slot, lambda c: c.start())

        each_live_page(blk, slot, lambda c: c.wait())
        ids = table(blk)
        col = jax.lax.broadcasted_iota(jnp.int32, (G, keys), 1)
        k_pos = (first + blk * ppb) * page_size + col
        ok = k_pos <= pos
        if window > 0:
            ok &= k_pos > pos - window
        for p, page in enumerate(ids):
            ok &= (col // page_size != p) | (page >= 0)
        for h in range(kv_heads):
            q = q_ref[h].astype(jnp.float32)                   # (G, hd)
            k = kbuf[slot, :, :, h, :].astype(jnp.float32)     # (ppb, ps, hd)
            v = vbuf[slot, :, :, h, :].astype(jnp.float32)
            if quantized:
                k = dequant(k, ks_ref, ids)
                v = dequant(v, vs_ref, ids)
            k = k.reshape(keys, -1)
            v = v.reshape(keys, -1)
            # f32 dot-then-scale and explicitly zeroed masked
            # probabilities, as in the page-grid kernel
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]                                  # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    @pl.when(n_blocks > 0)
    def _():
        each_live_page(0, 0, lambda c: c.start())

    jax.lax.fori_loop(0, n_blocks, step, 0)
    # l == 0 (no live key: a parked slot) yields exactly 0
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_decode_attention(q, pool_k, pool_v, block_tables, pos, layer, *,
                           window: int = 0, interpret: bool = False,
                           k_scale=None, v_scale=None):
    """Decode attention over the paged pool, one program per slot.

    q: (B, 1, H, hd); pool_k/pool_v: the layer-stacked (L, P, page_size,
    KV, hd) pools, read as stored from HBM, with ``layer`` the layer to
    read (a traced scalar, prefetched: the kernel DMAs that layer's pages
    straight out of the stack, so no copy of the layer's pool is made);
    block_tables: (B, mps) int32 (-1 = unallocated); pos: (B,) int32, each
    slot's query position; k_scale/v_scale: optional (P,) f32 page scales
    of the layer read (int8 pools). Returns (B, 1, H, hd).

    Work follows each slot's live pages: the loop visits only the blocks
    of ``decode_pages_per_block(page_size)`` pages between the window's
    first visible page and the page holding ``pos``, and each page is
    copied once for all ``H // KV`` query heads that read it. A slot at
    ``pos >= mps * page_size`` (parked) runs no block and returns 0."""
    B, Sq, H, hd = q.shape
    assert Sq == 1, "decode kernel takes one query row per slot"
    _, P, ps, KV, _ = pool_k.shape
    assert H % KV == 0
    G = H // KV
    mps = block_tables.shape[1]
    ppb = decode_pages_per_block(ps)
    quantized = k_scale is not None
    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(hd),
                               window=window, page_size=ps, ppb=ppb, mps=mps,
                               quantized=quantized)
    prefetch = [jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(pos, jnp.int32),
                jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))]
    if quantized:
        prefetch += [jnp.asarray(k_scale, jnp.float32),
                     jnp.asarray(v_scale, jnp.float32)]
    q_spec = pl.BlockSpec((None, KV, G, hd), lambda b, *_: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, ppb, ps, KV, hd), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((KV, G, 1), jnp.float32),
                        pltpu.VMEM((KV, G, 1), jnp.float32),
                        pltpu.VMEM((KV, G, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        # sequential: the buffers are zeroed once, by program 0
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q.reshape(B, KV, G, hd), pool_k, pool_v)
    return out.reshape(B, 1, H, hd)


# query rows per prefill-chunk program of the latent kernel: a block of
# chunk positions times every head (4 positions at 128 heads)
LATENT_BLOCK_ROWS = 512


def latent_block_q(chunk: int, heads: int) -> int:
    """Chunk positions per program of :func:`paged_attention_latent`: the
    largest power of two that divides ``chunk`` and keeps a block's rows
    (positions times heads) within :data:`LATENT_BLOCK_ROWS`, at least 1."""
    bq = 1
    while chunk % (2 * bq) == 0 and 2 * bq * heads <= LATENT_BLOCK_ROWS:
        bq *= 2
    return bq


def _latent_kernel(bt_ref, start_ref, layer_ref, q_ref, pool_hbm, o_ref,
                   kbuf, sems, m_ref, l_ref, acc_ref, *, scale: float,
                   page_size: int, ppb: int, mps: int, d_v: int):
    """Program (b, i): query block i of slot b — ``block_q`` consecutive
    positions times every head, one row each, attending together. The
    loop runs over the slot's blocks of ``ppb`` latent pages from the
    first up to the block's causal frontier (its last position): each
    step starts the next block's page DMAs into the other buffer, then
    waits on and computes the current one. Scores contract the whole
    latent row; values are its leading ``d_v`` columns, so a page is
    read once for both roles and for every head."""
    block_q, heads, width = q_ref.shape
    rows = block_q * heads
    keys = ppb * page_size
    b = pl.program_id(0)
    pos0 = start_ref[b] + pl.program_id(1) * block_q

    @pl.when((b == 0) & (pl.program_id(1) == 0))
    def _():
        # pages past the live span are never copied: their buffer rows
        # meet p == 0 exactly, which needs them finite, never stale VMEM
        kbuf[...] = jnp.zeros_like(kbuf)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # a parked slot (start >= mps * ps, all--1 table) has no live block
    live = (pos0 >= 0) & (pos0 < mps * page_size)
    last = jnp.minimum(pos0 + block_q - 1, mps * page_size - 1) // page_size
    n_blocks = jnp.where(live, last // ppb + 1, 0)

    def each_live_page(blk, slot, fn):
        """Apply ``fn`` to the copy of every page of block ``blk`` up to
        the frontier (an unallocated -1 entry copies page 0; the mask
        drops its keys)."""
        def body(p, carry):
            src = jnp.maximum(bt_ref[b, blk * ppb + p], 0)
            fn(pltpu.make_async_copy(pool_hbm.at[layer_ref[0], src],
                                     kbuf.at[slot, p], sems.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(ppb, last - blk * ppb + 1), body, 0)

    q = q_ref[...].reshape(rows, width)
    # row r is position pos0 + r // heads; the mask is built from a column
    # of row positions and a row of key offsets, broadcast once
    q_pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    col = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)

    def step(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            each_live_page(blk + 1, 1 - slot, lambda c: c.start())

        each_live_page(blk, slot, lambda c: c.wait())
        k = kbuf[slot].reshape(keys, width)
        allocated = col >= 0
        for p in range(ppb):
            page = bt_ref[b, jnp.minimum(blk * ppb + p, mps - 1)]
            allocated &= (col // page_size != p) | (page >= 0)
        ok = (blk * keys + col <= q_pos) & allocated           # (rows, keys)
        # f32 dot-then-scale and explicitly zeroed masked probabilities,
        # as in the per-head kernels
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                    # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, k[:, :d_v].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    @pl.when(n_blocks > 0)
    def _():
        each_live_page(0, 0, lambda c: c.start())

    jax.lax.fori_loop(0, n_blocks, step, 0)
    # l == 0 (no live key: a parked slot) yields exactly 0
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = out.reshape(block_q, heads, d_v).astype(o_ref.dtype)


def paged_attention_latent(q, pool_c, block_tables, start, layer=None, *,
                           scale_dim: float, d_v: int,
                           interpret: bool = False):
    """Paged attention over MLA latent pages, for decode and prefill
    chunks alike.

    q: (B, Sq, H, c+r) ABSORBED queries (q_nope pushed through wkv_b's key
    half, concat decoupled RoPE head), row i of slot b at position
    ``start[b] + i``; pool_c: (P, page_size, 1, c+r) — one latent row per
    token, no per-head K/V — or the layer-stacked (L, P, page_size, 1,
    c+r) pool with ``layer`` the layer to read (a traced scalar; the
    kernel addresses that layer's pages in the stack, so no copy of the
    layer's pool is made); block_tables: (B, mps) int32 (-1 =
    unallocated). Scores are divided by sqrt(``scale_dim``), the logical
    attention width (qk_nope + qk_rope, YaRN's temperature folded in) —
    NOT the latent width the dot products contract over. Values are the
    leading ``d_v`` (= kv_lora_rank) columns of the same latent rows;
    output is (B, Sq, H, d_v), still in latent space (the caller applies
    wkv_b's value half and wo).

    Grid ``(B, Sq // block_q)``: each program takes ``block_q``
    consecutive positions (:func:`latent_block_q`; 1 in decode) times all
    H heads as one block of query rows, and loops over only its slot's
    live blocks of ``decode_pages_per_block(page_size)`` pages, up to the
    block's causal frontier. Whole ``(page_size, c+r)`` pages are DMA'd
    from the pool as stored, double-buffered, and every head's rows
    attend a page together, so a page is read once per query block, not
    once per head. A slot at ``start >= mps * page_size`` (parked) runs
    no block and returns exactly 0."""
    B, Sq, H, width = q.shape
    if pool_c.ndim == 4:
        pool_c, layer = pool_c[None], 0
    Lr, P, ps, KV, _ = pool_c.shape
    assert KV == 1, "latent pool carries one shared row per token"
    mps = block_tables.shape[1]
    ppb = decode_pages_per_block(ps)
    bq = latent_block_q(Sq, H)
    kernel = functools.partial(_latent_kernel,
                               scale=1.0 / math.sqrt(scale_dim),
                               page_size=ps, ppb=ppb, mps=mps, d_v=d_v)
    rows = bq * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Sq // bq),
        in_specs=[pl.BlockSpec((None, bq, H, width),
                               lambda b, i, *_: (b, i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, bq, H, d_v),
                               lambda b, i, *_: (b, i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, width), pool_c.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        *_scratch(rows, d_v)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, d_v), q.dtype),
        # sequential: the page buffer is zeroed once, by program (0, 0)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(start, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), q,
      pool_c.reshape(Lr, P, ps, width))


def paged_attention(q, pool_k, pool_v, block_tables, start, layer, *,
                    window: int = 0, interpret: bool = False,
                    k_scale=None, v_scale=None):
    """q: (B, Sq, H, hd); pool_k/pool_v: the layer-stacked (L, P,
    page_size, KV, hd) pools with ``layer`` the layer to read (a traced
    scalar);
    block_tables: (B, mps) int32 page ids (-1 = unallocated);
    start: (B,) int32 — the position of each slot's FIRST query row (query
    row i is at ``start[b] + i``; logical key row r lives in page ``r // ps``
    at offset ``r % ps``). Returns (B, Sq, H, hd) in q.dtype.

    k_scale/v_scale: optional (P,) f32 per-page symmetric scales for int8
    pools; when given, the kernel dequantizes each gathered page inside
    its body (scales prefetched to SMEM alongside the block table).

    One query row per slot (decode) runs :func:`paged_decode_attention`,
    which visits only each slot's live pages and reads a stacked pool in
    place (see :func:`takes_decode_kernel`); a chunk of rows (prefill,
    speculative verification) runs the page-grid kernel below, over the
    layer's pool read out of the stack and folded."""
    B, Sq, H, hd = q.shape
    if takes_decode_kernel(q, pool_k):
        return paged_decode_attention(q, pool_k, pool_v, block_tables, start,
                                      layer, window=window,
                                      interpret=interpret,
                                      k_scale=k_scale, v_scale=v_scale)
    pool_k, pool_v = (jax.lax.dynamic_index_in_dim(p, layer, 0, False)
                      for p in (pool_k, pool_v))
    P, ps, KV, _ = pool_k.shape
    assert H % KV == 0
    G = H // KV
    mps = block_tables.shape[1]
    quantized = k_scale is not None
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(hd),
                               window=window, block_q=Sq, page_size=ps,
                               quantized=quantized)
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
                                 block_tables, start, layer, *,
                                 window: int = 0, k_scale=None,
                                 v_scale=None):
    """Tensor-parallel head-shard dispatch around the paged kernel.

    ``pallas_call`` lowers to a CustomCall that GSPMD cannot partition, so
    the tp serve path wraps the local dispatch in an explicit ``shard_map``:
    q and both layer-stacked (L, P, ps, KV, hd) pools split on their head
    axes over the ``axis`` mesh axis (the pool leaves are already RESIDENT
    with exactly this sharding, so no data moves for them); block tables,
    start positions and the layer index are replicated — page ids are
    shard-invariant. The q8 page scales arrive as (P, tp)
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

    heads = SP(None, None, axis, None)    # q/out (B,Sq,H,hd)
    pools = SP(None, None, None, axis, None)   # (L,P,ps,KV,hd)
    repl1 = SP(None)
    repl2 = SP(None, None)
    layer = jnp.asarray(layer, jnp.int32)

    if k_scale is not None:
        scales = SP(None, axis)           # (P, tp) -> per-shard (P, 1)
        def body(q_, pk_, pv_, bt_, st_, layer_, ks_, vs_):
            return dispatch(q_, pk_, pv_, bt_, st_, layer_, window,
                            k_scale=ks_[:, 0], v_scale=vs_[:, 0])
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(heads, pools, pools, repl2, repl1, SP(), scales,
                      scales),
            out_specs=heads, check_vma=False,
        )(q, pool_k, pool_v, block_tables, start, layer, k_scale, v_scale)

    def body(q_, pk_, pv_, bt_, st_, layer_):
        return dispatch(q_, pk_, pv_, bt_, st_, layer_, window)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(heads, pools, pools, repl2, repl1, SP()),
        out_specs=heads, check_vma=False,
    )(q, pool_k, pool_v, block_tables, start, layer)


def paged_attention_latent_head_sharded(dispatch, mesh, axis, q, pool_c,
                                        block_tables, start, layer, *,
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
    pool = SP(*([None] * pool_c.ndim))    # (L,P,ps,1,c+r), replicated
    repl2 = SP(None, None)
    repl1 = SP(None)

    def body(q_, pc_, bt_, st_, layer_):
        return dispatch(q_, pc_, bt_, st_, layer_, scale_dim, d_v)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(heads, pool, repl2, repl1, SP()),
        out_specs=heads, check_vma=False,
    )(q, pool_c, block_tables, start, layer)
