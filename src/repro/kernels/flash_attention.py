"""Blockwise online-softmax attention (FlashAttention on TPU, GQA-aware).

Grid: (B, H, Sq/bq, Sk/bk) with the KV index derived as h // (H // KV) so GQA
shares K/V blocks across grouped query heads. Running max/denominator/acc live
in VMEM scratch and persist across the innermost (kv) grid steps — the same
"accumulators in on-chip RAM" structure as the paper's systolic design.

Positions are block-index-derived (prefill layout: positions 0..S-1), causal
and sliding-window masks are applied in-kernel; fully-masked kv blocks are
skipped (pl.when), which is how the kernel keeps the long-context windowed
archs sub-quadratic in *work*, not just memory.

Block layout: the head axis is folded into the lane axis — q
``(B, Sq, H * hd)``, k/v ``(B, Sk, KV * hd)`` — so one head's block is
``(bq, hd)`` / ``(bk, hd)``, which meets Mosaic's rule that a block's last
two dims are divisible by (8, 128) or equal the array's own.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, block_q: int, block_k: int,
            k_out_ref=None, v_out_ref=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    if k_out_ref is not None:
        # K/V-exporting prefill variant: the K/V block is already resident in
        # VMEM for the attention pass, so emitting it to the export outputs
        # costs no extra HBM read — the fused path a serving prefill uses to
        # land post-RoPE K/V tiles ready for the cache (block-table) scatter.
        # Every (h, qi) grid step that maps to this kv block writes the same
        # bytes, so output-block revisiting is well-defined.
        k_out_ref[...] = k_ref[...]
        v_out_ref[...] = v_ref[...]

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # whole-block skip test (static per grid step under interpret; cheap on TPU)
    def in_range():
        q = q_ref[...].astype(jnp.float32)                         # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                         # (bk, hd)
        # dot-then-scale, as the reference does: the MXU takes bf16 q and k
        # exactly, while a pre-scaled q would be rounded to bf16 on the way
        # in (Mosaic's default f32 matmul)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), bool)
        if causal:
            ok &= q_pos >= k_pos
        if window > 0:
            ok &= (q_pos - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                        # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal or window > 0:
        # block fully below the causal diagonal or outside the window -> skip
        relevant = jnp.array(True)
        if causal:
            relevant &= (q_start + block_q - 1) >= k_start
        if window > 0:
            relevant &= (k_start + block_k - 1) > (q_start - window)
        pl.when(relevant)(in_range)
    else:
        in_range()

    @pl.when(ki == nk - 1)
    def _():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _specs(B, Sq, Sk, H, KV, hd, block_q, block_k):
    """Grid and per-head block specs over the lane-folded layouts."""
    G = H // KV
    grid = (B, H, Sq // block_q, Sk // block_k)
    q_spec = pl.BlockSpec((None, block_q, hd), lambda b, h, qi, ki: (b, qi, h))
    kv_spec = pl.BlockSpec((None, block_k, hd),
                           lambda b, h, qi, ki: (b, ki, h // G))
    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, hd), jnp.float32)]
    return grid, q_spec, kv_spec, scratch


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    grid, q_spec, kv_spec, scratch = _specs(B, Sq, Sk, H, KV, hd,
                                            block_q, block_k)
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(hd),
                               causal=causal, window=window,
                               block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd),
      v.reshape(B, Sk, KV * hd))
    return out.reshape(B, Sq, H, hd)


def flash_attention_kv(q, k, v, *, causal: bool = True, window: int = 0,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool = False):
    """Causal prefill variant that returns ``(O, K, V)``.

    Same grid/accumulator structure as :func:`flash_attention`, but the
    kernel additionally EXPORTS the K/V tiles it streams through VMEM as two
    extra outputs shaped ``(B, Sk, KV, hd)`` — the per-layer cache rows a
    serving prefill scatters into its (paged) KV cache. Today the projection
    and RoPE happen outside the kernel (layers._qkv), so the export is a
    passthrough of the inputs: what this variant establishes is the
    (O, K, V) OUTPUT CONTRACT the serving path consumes, so a future kernel
    that fuses qkv projection + RoPE in-kernel (where K/V first materialize
    in VMEM and an HBM round-trip really is saved) can drop in without
    touching any caller. Under ``interpret`` (CPU CI) the same body runs as
    traced JAX ops.

    q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (O (B,Sq,H,hd), K, V (B,Sk,KV,hd)).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    grid, q_spec, kv_spec, scratch = _specs(B, Sq, Sk, H, KV, hd,
                                            block_q, block_k)
    scale = 1.0 / math.sqrt(hd)

    def kernel(q_ref, k_ref, v_ref, o_ref, k_out_ref, v_out_ref,
               m_ref, l_ref, acc_ref):
        _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                scale=scale, causal=causal, window=window, block_q=block_q,
                block_k=block_k, k_out_ref=k_out_ref, v_out_ref=v_out_ref)

    kv_shape = jax.ShapeDtypeStruct((B, Sk, KV * hd), k.dtype)
    o, k_out, v_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
                   kv_shape, kv_shape],
        scratch_shapes=scratch,
        interpret=interpret,
    )(q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd),
      v.reshape(B, Sk, KV * hd))
    return (o.reshape(B, Sq, H, hd), k_out.reshape(B, Sk, KV, hd),
            v_out.reshape(B, Sk, KV, hd))
