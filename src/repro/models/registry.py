"""Uniform model facade: every architecture family exposes
init / param_logical / forward / init_cache / cache_logical / decode_step /
input_specs through a single ``Model`` object keyed by arch id.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Family, ShapeConfig
from repro.models import encdec, hybrid, ssm, transformer
from repro.models.encdec import ENC_LEN
from repro.models.layers import INACTIVE_POS, fit_rows

_FAMILY_MODULES = {
    Family.DENSE: transformer,
    Family.MOE: transformer,
    Family.VLM: transformer,
    Family.ENCDEC: encdec,
    Family.SSM: ssm,
    Family.HYBRID: hybrid,
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    mod: Any

    def init(self, key):
        return self.mod.init(self.cfg, key)

    def param_logical(self):
        return self.mod.param_logical(self.cfg)

    def forward(self, params, tokens, **kw):
        return self.mod.forward(params, self.cfg, tokens, **kw)

    def init_cache(self, batch: int, s_max: int, dtype=jnp.bfloat16):
        return self.mod.init_cache(self.cfg, batch, s_max, dtype)

    def cache_logical(self):
        return self.mod.cache_logical(self.cfg)

    def decode_step(self, params, token, cache, **kw):
        return self.mod.decode_step(params, self.cfg, token, cache, **kw)

    def prefill_chunk(self, params, tokens, cache, **kw):
        """Full-width parallel prefill over one prompt chunk (all families):
        (last logits (B,1,Vp), cache with pos advanced by the chunk length).
        See launch/steps.py::make_prefill_chunk for the serving contract."""
        return self.mod.prefill_chunk(params, self.cfg, tokens, cache, **kw)

    @property
    def supports_paged_prefill(self) -> bool:
        """True for families whose prompt state is exactly (k, v, pos) — the
        ones the incremental paged prefill (chunks splicing straight into
        pages, attention through the block table) can serve. Mirrors the
        prefix-cache support set: hybrid's recurrent carry and encdec's
        encoder/cross-K/V are not page-resident."""
        return hasattr(self.mod, "prefill_chunk_paged")

    def prefill_chunk_paged(self, params, tokens, cache, **kw):
        """Prompt chunk computed at full width and spliced into the RESIDENT
        paged cache incrementally (no transient request cache). See
        launch/steps.py::make_prefill_chunk_paged for the serving contract."""
        return self.mod.prefill_chunk_paged(params, self.cfg, tokens, cache,
                                            **kw)

    # -------------------------------------------------- input specs
    def extra_inputs(self, batch: int, seq: int, dtype=jnp.bfloat16) -> dict:
        """Modality-frontend STUB inputs (precomputed embeddings), per assignment."""
        cfg = self.cfg
        if cfg.family == Family.VLM:
            return {"image_embeds": jax.ShapeDtypeStruct(
                (batch, cfg.num_image_tokens, cfg.d_model), dtype)}
        if cfg.family == Family.ENCDEC:
            return {"frames": jax.ShapeDtypeStruct((batch, ENC_LEN, cfg.d_model), dtype)}
        return {}

    def input_specs(self, shape: ShapeConfig, dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        elif shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        else:  # decode: one new token against a cache of length S
            specs = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
        specs.update(self.extra_inputs(B, S, dtype))
        return specs


def get_model(cfg: ArchConfig) -> Model:
    if cfg.family == Family.CNN:
        raise ValueError("resnet20 uses models.resnet directly (paper pipeline)")
    return Model(cfg=cfg, mod=_FAMILY_MODULES[cfg.family])


# ------------------------------------------------------------------ serving
# Cache conventions. DENSE: every family's cache leaves are (L, B, ...) with
# the slot/batch axis at position 1, plus a "pos" leaf that is a scalar
# (lockstep batch) or a (B,) per-slot position vector. PAGED
# (init_paged_cache): the attention K/V leaves (and the hybrid ring's
# "slot_pos") are replaced by SHARED page pools (L, num_pages, page_size, ...)
# with NO batch axis, plus a "block_tables" leaf (B, max_pages_per_slot)
# int32 mapping each slot's logical blocks to pool pages (-1 = unallocated) —
# memory scales with allocated pages, not slots x s_max. Per-slot leaves
# without a sequence axis (SSM state, encdec cross K/V, "pos") keep the dense
# layout. A slot whose pos is >= layers.INACTIVE_POS is free: its writes are
# dropped by every decode path, so freed rows are bit-stable. The serving
# engine relies on these conventions to splice per-request prefill caches
# into the resident cache without touching other slots.

# pool leaves of a paged cache (when "block_tables" is present); everything
# else keeps the dense (L, B, ...) per-slot layout
PAGED_POOL_LEAVES = ("k", "v", "slot_pos")


def vectorize_cache_pos(cache, batch: int, inactive: bool = False):
    """Scalar-pos cache (init_cache output) -> per-slot (B,) position cache
    for the continuous-batching decode path. ``inactive=True`` starts every
    slot at the INACTIVE_POS sentinel (no slot admitted yet), so empty slots
    never scatter stale K/V rows while idle."""
    pos = cache["pos"]
    if jnp.ndim(pos) == 0:
        fill = INACTIVE_POS if inactive else pos
        cache = dict(cache, pos=jnp.full((batch,), fill, jnp.int32))
    return cache


def insert_cache_slot(cache, request_cache, slot):
    """Write a batch-1 request cache (a fresh prefill) into slot ``slot`` of a
    batched per-slot-pos serving cache — other slots' entries are untouched
    bit-for-bit. Thin wrapper over insert_cache_rows so there is exactly one
    implementation of the batch-axis splice. ``slot`` may be a traced scalar,
    so one jit covers every slot."""
    return insert_cache_rows(cache, request_cache,
                             jnp.reshape(jnp.asarray(slot, jnp.int32), (1,)))


def insert_cache_rows(cache, request_cache, slots):
    """Write a batch-K request cache (one joint prefill of K same-length
    prompts) into rows ``slots`` (a (K,) index vector) of a batched serving
    cache. Same isolation contract as insert_cache_slot: a scatter on the
    batch axis only."""
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for key, leaf in cache.items():
        req = request_cache[key]
        if key == "pos":
            # prefill pos is a scalar (all K rows at prompt_len) or (K,)
            out[key] = leaf.at[slots].set(jnp.asarray(req, leaf.dtype))
        else:
            out[key] = leaf.at[:, slots].set(req.astype(leaf.dtype))
    return out


# ------------------------------------------------------------------ paged
def cache_capacity(cfg: ArchConfig, s_max: int) -> int:
    """Per-slot sequence capacity of the attention cache: the hybrid family
    keeps a ring buffer of width min(window, s_max); everything else stores
    the full s_max rows. This is the row count the page allocator must be
    able to cover for one slot."""
    if cfg.family == Family.HYBRID:
        return min(cfg.window, s_max)
    return s_max


def init_paged_cache(model: Model, batch: int, s_max: int, *, page_size: int,
                     num_pages: int, dtype=jnp.bfloat16,
                     row_width: Optional[int] = None):
    """Paged serving cache: the dense per-slot K/V (and hybrid ring
    ``slot_pos``) leaves become shared page pools (L, num_pages, page_size,
    ...) addressed through per-slot ``block_tables`` (B, max_pages_per_slot).
    All other leaves (SSM state, encdec cross K/V, conv carries) keep the
    dense per-slot layout — they are O(1) in sequence length. ``pos`` starts
    at the INACTIVE_POS sentinel for every slot (nothing admitted).

    s_max must be a page_size multiple so the paged logical view is exactly
    s_max rows (the bit-exactness anchor vs the dense path). Hybrid caches
    additionally carry a ``ring_iota`` (W,) leaf whose shape tells the decode
    path the ring width. The SSM family has no K/V to page — callers should
    keep it dense. ``row_width``: the pools' last axis, when rows are
    stored wider than the model's (zeros beyond it). The dense cache is
    only shaped, never allocated, for the leaves the pools replace."""
    cfg = model.cfg
    if cfg.family == Family.SSM:
        raise ValueError("rwkv/ssm caches are O(1) in s_max; use init_cache")
    if s_max % page_size:
        raise ValueError(f"s_max {s_max} must be a multiple of page_size "
                         f"{page_size} (paged view == dense view)")
    shapes = jax.eval_shape(lambda: model.init_cache(batch, s_max, dtype))
    dense = None
    mps = s_max // page_size
    out = {}
    for key, leaf in shapes.items():
        if key in ("k", "v"):               # (L, B, C, KV, hd) -> pool
            Lr, _, _, KV, hd = leaf.shape
            out[key] = jnp.zeros((Lr, num_pages, page_size, KV,
                                  row_width or hd), leaf.dtype)
        elif key == "slot_pos":             # hybrid ring positions -> pool
            out[key] = jnp.full((leaf.shape[0], num_pages, page_size), -1,
                                jnp.int32)
        elif key == "pos":
            out[key] = jnp.full((batch,), INACTIVE_POS, jnp.int32)
        else:
            if dense is None:
                dense = model.init_cache(batch, s_max, dtype)
            out[key] = dense[key]
    out["block_tables"] = jnp.full((batch, mps), -1, jnp.int32)
    if cfg.family == Family.HYBRID:
        out["ring_iota"] = jnp.arange(cache_capacity(cfg, s_max),
                                      dtype=jnp.int32)
    return out


def insert_cache_rows_paged(cache, request_cache, slots, phys_rows):
    """Paged variant of insert_cache_rows: splice a batch-K DENSE prefill
    cache into the page pools of a paged serving cache.

    ``phys_rows`` is a (K, C) int32 map from each request's logical cache row
    (C = the family's per-slot capacity, s_max or the ring width) to a
    flattened pool row (page * page_size + offset); entries >= num_pages *
    page_size (unallocated logical blocks beyond the request's reservation)
    are DROPPED by the scatter, so a short request can never write into pages
    it does not own. Per-slot leaves and "pos" splice exactly like the dense
    path; "block_tables" is host-managed by the engine and passes through."""
    slots = jnp.asarray(slots, jnp.int32)
    phys_rows = jnp.asarray(phys_rows, jnp.int32)
    out = {}
    for key, leaf in cache.items():
        if key in ("block_tables", "ring_iota"):
            out[key] = leaf
            continue
        req = request_cache[key]
        if key in PAGED_POOL_LEAVES:
            Lr, P, ps = leaf.shape[:3]
            flat = leaf.reshape((Lr, P * ps) + leaf.shape[3:])
            C = phys_rows.shape[1]
            rows = req[:, :, :C]
            if key in ("k", "v"):           # rows stored wider than req's
                rows = fit_rows(rows, leaf.shape[-1])
            flat = flat.at[:, phys_rows].set(rows.astype(leaf.dtype),
                                             mode="drop")
            out[key] = flat.reshape(leaf.shape)
        elif key == "pos":
            out[key] = leaf.at[slots].set(jnp.asarray(req, leaf.dtype))
        else:
            out[key] = leaf.at[:, slots].set(req.astype(leaf.dtype))
    return out


def copy_pool_rows(cache, src_rows, dst_rows):
    """Copy K/V rows between flattened pool positions — the incremental
    prefill's copy-on-write materialisation: a prefix hit's PARTIAL source
    page rows are copied into the fresh page standing in for it, using the
    same gather/scatter the per-chunk splice uses (no transient cache, no
    extra device pass shape).

    ``src_rows``/``dst_rows``: (K, R) int32 flattened pool rows
    (page * page_size + offset); entries with dst >= num_pages * page_size
    are DROPPED (the masked tail of a partial copy). Only the pool K/V
    leaves move; everything else passes through untouched."""
    src_rows = jnp.asarray(src_rows, jnp.int32)
    dst_rows = jnp.asarray(dst_rows, jnp.int32)
    out = dict(cache)
    for key in ("k", "v"):
        if key not in cache:                # MLA latent pool: "k" only
            continue
        pool = cache[key]                   # (L, P, ps, KV, hd)
        Lr, P, ps = pool.shape[:3]
        flat = pool.reshape((Lr, P * ps) + pool.shape[3:])
        rows = flat[:, jnp.clip(src_rows, 0, P * ps - 1)]
        flat = flat.at[:, dst_rows].set(rows, mode="drop")
        out[key] = flat.reshape(pool.shape)
    return out


def seed_prefix_cache(model: Model, cache, phys_rows, row_ok, pos,
                      s_max: int, dtype=jnp.float32):
    """Build a dense batch-K transient prefill cache whose leading rows are
    GATHERED from a paged serving cache's page pools — the prefix-cache hit
    path: instead of recomputing a shared prompt prefix, the engine seeds the
    request's transient cache with the prefix K/V already resident in shared
    pages and runs only the uncached tail through ``prefill_chunk``.

    ``phys_rows`` is a (K, s_max) int32 map from each request's logical cache
    row to a flattened pool row (page * page_size + offset) covering exactly
    the cached prefix; ``row_ok`` masks rows beyond it (gathered as zeros —
    identical to the never-written rows of a fresh transient cache, and
    causally invisible: their k_pos exceeds every tail query position).
    ``pos`` is the scalar position the tail continuation chunks start at.

    Only valid for families whose transient prefill state is exactly
    (k, v, pos) — dense / MoE / VLM transformers; the engine gates on this
    (hybrid ring carry and SSM state are not reconstructible from pages)."""
    K = phys_rows.shape[0]
    out = model.init_cache(K, s_max, dtype)
    idx = jnp.where(row_ok, phys_rows, 0)
    for key in ("k", "v"):
        if key not in out:                  # MLA latent cache: "k" only
            continue
        pool = cache[key]                   # (L, P, ps, KV, hd)
        Lr, P, ps = pool.shape[:3]
        flat = pool.reshape((Lr, P * ps) + pool.shape[3:])
        rows = fit_rows(flat[:, idx], out[key].shape[-1])
        mask = row_ok.reshape((1,) + row_ok.shape + (1,) * (rows.ndim - 3))
        out[key] = jnp.where(mask, rows, 0).astype(out[key].dtype)
    out["pos"] = jnp.asarray(pos, jnp.int32)
    return out


def extract_cache_slot(cache, slot: int):
    """Batch-1 view of one slot's cache entries (testing/debug helper). For a
    paged cache, pool leaves are gathered through the slot's block table into
    the dense per-slot layout (rows of unallocated pages read as zeros / -1,
    matching a never-written dense cache). Int8 pools (an ``<key>_scale``
    leaf rides alongside) are DEQUANTIZED page-wise, so the view is a
    directly comparable f32 dense cache; the scale leaves themselves are
    per-page pool metadata with no dense counterpart and are skipped."""
    bt = cache.get("block_tables")
    out = {}
    for key, leaf in cache.items():
        if key in ("block_tables", "ring_iota") or key.endswith("_scale"):
            continue
        if key == "pos":
            out[key] = leaf if jnp.ndim(leaf) == 0 else leaf[slot]
        elif bt is not None and key in PAGED_POOL_LEAVES:
            from repro.models.layers import paged_row_indices
            Lr, P, ps = leaf.shape[:3]
            n_rows = bt.shape[1] * ps
            if key == "slot_pos":
                n_rows = cache["ring_iota"].shape[0]
            phys, ok = paged_row_indices(bt[slot:slot + 1], ps, n_rows)
            flat = leaf.reshape((Lr, P * ps) + leaf.shape[3:])
            view = flat[:, phys[0]]
            if key + "_scale" in cache:
                pg = jnp.clip(phys[0] // ps, 0, P - 1)
                view = (view.astype(jnp.float32)
                        * cache[key + "_scale"][:, pg][..., None, None])
            fill = -1 if key == "slot_pos" else 0
            mask = ok[0].reshape((1, -1) + (1,) * (view.ndim - 2))
            view = jnp.where(mask, view, fill)
            if key in ("k", "v") and "ring_iota" in cache:
                view = view[:, : cache["ring_iota"].shape[0]]
            out[key] = view[:, None]        # (L, 1, C, ...)
        else:
            out[key] = leaf[:, slot:slot + 1]
    return out


def reduced_config(cfg: ArchConfig, **overrides) -> ArchConfig:
    """Smoke-test-sized config of the same family (small dims, same structure)."""
    defaults = dict(
        num_layers=2 if not cfg.cross_attn_every else cfg.cross_attn_every,
        d_model=64,
        num_heads=4, num_kv_heads=max(1, 4 * cfg.num_kv_heads // max(cfg.num_heads, 1)),
        d_ff=128, vocab_size=512, head_dim=16,
        encoder_layers=2 if cfg.encoder_layers else 0,
        num_image_tokens=8 if cfg.cross_attn_every else 0,
        window=8 if cfg.window else 0,
        ssm_state=cfg.ssm_state and 4,
        kv_lora_rank=8 if cfg.kv_lora_rank else 0,
        qk_rope_head_dim=2 if cfg.qk_rope_head_dim else 0,
        q_lora_rank=16 if cfg.q_lora_rank else 0,
        first_k_dense=min(cfg.first_k_dense, 1),
    )
    if cfg.moe:
        # grouped routing keeps its structure: 4 groups of 2, the best 2
        grouped = cfg.moe.n_group > 1
        defaults["moe"] = dataclasses.replace(
            cfg.moe, num_experts=8 if grouped else 4, top_k=2,
            d_ff=32 if cfg.moe.d_ff else 0, held=(0, 0),
            n_group=4 if grouped else 1, topk_group=2 if grouped else 1)
    if cfg.cross_attn_every:
        defaults["num_layers"] = 2 * cfg.cross_attn_every  # 2 super-layers
    defaults.update(overrides)
    return dataclasses.replace(cfg, **defaults)
