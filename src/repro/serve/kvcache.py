"""Pluggable KV-cache backends: the single seam between the serving engine's
ORCHESTRATION (scheduling, admission, page accounting) and the cache's
REPRESENTATION (pool dtype/shape, splice math, scale metadata).

The engine never touches page-layout internals directly — it holds a
:class:`KVBackend` and calls five representation operations:

    capacity(cfg, s_max)           per-slot row capacity the allocator covers
    init_cache(model, B, s_max)    build the resident cache pytree
    insert_rows(cache, rcache,     completion splice of a transient prefill
                slots, phys_rows)  cache (dense batch scatter / paged pool
                                   scatter, quantizing on the way in for q8)
    copy_rows(cache, src, dst)     COW re-materialisation of a partial
                                   prefix page (q8: the scale rides along)
    seed_prefix(model, s_max, dt)  gather shared prefix rows into a dense
                                   transient cache (q8: dequantized)

plus `resolve_attn_impl` (kernel vs einsum dispatch policy) and the
`page_meta`/`check_page_meta` hooks for per-page metadata invariants.
Everything a representation owns lives here or below (models/layers.py
write/read paths, kernels/paged_attention.py); everything the engine owns
(allocator, block tables, prefix index, job lifecycle) stays in engine.py.

Backends:

* :class:`DenseBackend` — the non-paged (B, s_max) per-slot cache.
* :class:`PagedFP32Backend` — the vLLM-style shared page pool, extracted
  behaviour-preservingly from the pre-backend engine (all bit-exact anchors
  — degenerate page == dense, prefix on == off — hold through this class).
* :class:`PagedInt8Backend` — pages stored int8 with symmetric f32 scales
  (the page is the quantization block, DeepSeek-V3 ``act_quant`` style):
  `k`/`v` pools are int8 and `(L, P, tp)` `k_scale`/`v_scale` leaves ride
  the cache pytree — one scale per page per KV-HEAD GROUP, where group t
  covers the contiguous ``KV/tp`` kv heads shard t owns, so every scale is
  an amax over shard-local values and the quantizing writes never cross
  the mesh (tp=1 keeps one whole-page scale, bitwise the pre-sharding
  layout). Dequant happens inside the paged Pallas kernel's gather (scales
  are scalar-prefetch operands), so decode's HBM KV traffic is ~4x smaller
  where it is bandwidth-bound. Prefix aliasing shares a page's scales with
  its payload; COW re-quantizes the fresh page exactly once (the chunk
  splice that follows the row copy).

* :class:`PagedLatentBackend` — MLA latent pages: each pool row is ONE
  per-token ``(kv_lora_rank + qk_rope_head_dim)``-dim compressed latent
  (shared by every query head via the absorb path) instead of per-head
  K/V. Same allocator/block-table/COW contract as the fp32 pool — COW
  copies a latent row, never per-head K/V — with resident KV per token
  shrunk from ``2 * KV * hd`` to ``c + r`` floats.

Sharding is a first-class property of the protocol, not an engine special
case: ``pool_axes()`` declares each leaf's logical sharding axes (scale
leaves included), ``place(cache, mesh)`` commits a cache pytree onto a
serving mesh from that declaration, and ``tp_compatible(mesh)`` is the
capability query ``ServeConfig.validate`` / ``make_backend`` consult
instead of maintaining a per-backend rejection ladder. A backend that
declares nothing still works under tp — its cache replicates (with a
warning) — so every future representation composes with the mesh for free.

Adding a backend = subclass KVBackend, implement the five operations (and
the layers-level write/read path if the representation changes attention's
view), and register it under a string key with :func:`register_backend`;
:func:`make_backend` resolves names through that :data:`BACKENDS` registry.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Family
from repro.core.quantize import page_scale
from repro.models.registry import (Model, cache_capacity, copy_pool_rows,
                                   init_paged_cache, insert_cache_rows,
                                   insert_cache_rows_paged, seed_prefix_cache,
                                   vectorize_cache_pos)

log = logging.getLogger("repro.serve")

# families whose transient prefill state is exactly (k, v, pos) — the ones
# page-level prefix caching (and the int8 backend's dequantizing prefix
# seed) can serve. Hybrid's ring carry and encdec's cross-K/V are not
# reconstructible from pages.
PREFIX_CACHE_FAMILIES = (Family.DENSE, Family.MOE, Family.VLM)

# families whose paged decode/prefill can route through the Pallas
# block-gather kernel (plain causal/windowed attention over the pool; the
# hybrid ring's modular positions need the einsum path)
PAGED_KERNEL_FAMILIES = (Family.DENSE, Family.MOE, Family.VLM, Family.ENCDEC)

# families the int8 backend supports: the quantized write paths live in the
# transformer chunk/decode attention (layers.py); the hybrid ring and
# encdec/ssm extra state keep fp32 representations
INT8_KV_FAMILIES = PREFIX_CACHE_FAMILIES


# ---------------------------------------------------------- jitted helpers
# module-level lru_cache'd jit factories (moved from engine.py): one
# compilation per distinct signature, shared by every engine instance
@functools.lru_cache(maxsize=1)
def _jitted_insert_rows():
    return jax.jit(insert_cache_rows, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _jitted_insert_rows_paged():
    return jax.jit(insert_cache_rows_paged, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _jitted_copy_rows():
    return jax.jit(copy_pool_rows, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _jitted_prefix_seed(model: Model, s_max: int, dtype):
    def seed(cache, phys_rows, row_ok, pos):
        return seed_prefix_cache(model, cache, phys_rows, row_ok, pos,
                                 s_max, dtype)
    return jax.jit(seed)


# ------------------------------------------------------------ int8 splices
def _quantize_pool_rows(req, C: int, ps: int, groups: int = 1):
    """Quantize a transient-cache leaf (L, K, >=C, KV, hd) page-block-wise.
    Returns (q (L,K,C,KV,hd) int8, scale (L,K,C//ps,groups) f32) — one
    symmetric scale per logical page per kv-head GROUP. ``groups`` is the
    serving tp degree: group t covers the contiguous ``KV/groups`` kv heads
    shard t owns, so under a kv-head-sharded pool each scale entry is an
    amax over shard-LOCAL values only and the quantizing write partitions
    comm-free (GSPMD splits the group axis exactly along the shards).
    ``groups=1`` reproduces the original whole-page scale bitwise. The
    engine's write floor is page-aligned, so a splice drops whole pages at
    a time and payload/scale stay consistent."""
    rows = req[:, :, :C].astype(jnp.float32)
    Lr, K = rows.shape[:2]
    KV, hd = rows.shape[3], rows.shape[4]
    blocks = rows.reshape(Lr, K, C // ps, ps, groups, KV // groups, hd)
    scale = page_scale(jnp.max(jnp.abs(blocks), axis=(3, 5, 6)))
    q = jnp.clip(jnp.round(blocks / scale[:, :, :, None, :, None, None]),
                 -127, 127).astype(jnp.int8)
    return q.reshape(Lr, K, C, KV, hd), scale


def insert_cache_rows_paged_q8(cache, request_cache, slots, phys_rows):
    """Int8 completion splice: like ``registry.insert_cache_rows_paged`` but
    the fp32 transient K/V rows are QUANTIZED page-by-page on the way into
    the int8 pools, and each written page's scales land in the (L, P, tp)
    scale tables (the group count rides the scale leaf's trailing dim).
    Rows/pages outside the request's reservation (phys >= P * ps —
    including everything below a page-aligned write floor) are dropped
    from payload AND scale alike."""
    slots = jnp.asarray(slots, jnp.int32)
    phys_rows = jnp.asarray(phys_rows, jnp.int32)
    out = {}
    for key, leaf in cache.items():
        if key == "block_tables" or key.endswith("_scale"):
            out.setdefault(key, leaf)       # scales overwritten with k/v
            continue
        req = request_cache[key]
        if key in ("k", "v"):
            Lr, P, ps = leaf.shape[:3]
            C = phys_rows.shape[1]
            q, scale = _quantize_pool_rows(req, C, ps,
                                           cache[key + "_scale"].shape[-1])
            flat = leaf.reshape((Lr, P * ps) + leaf.shape[3:])
            flat = flat.at[:, phys_rows].set(q, mode="drop")
            out[key] = flat.reshape(leaf.shape)
            # every logical page's rows are pool-contiguous, so the page id
            # is the first covered row's phys // ps (oob rows land on page
            # P and drop, exactly like their payload)
            page_idx = phys_rows[:, ::ps] // ps              # (K, C // ps)
            # scale (L, K, C//ps, T) scatters onto the (L, P, T) table
            out[key + "_scale"] = cache[key + "_scale"].at[:, page_idx].set(
                scale, mode="drop")
        elif key == "pos":
            out[key] = leaf.at[slots].set(jnp.asarray(req, leaf.dtype))
        else:
            out[key] = leaf.at[:, slots].set(req.astype(leaf.dtype))
    return out


def copy_pool_rows_q8(cache, src_rows, dst_rows):
    """Int8 COW materialisation: the int8 rows copy verbatim (the gather/
    scatter in ``registry.copy_pool_rows`` is dtype-agnostic), and the
    DESTINATION page inherits the SOURCE page's scale — the copied payload
    only decodes correctly under it. The tail chunk's splice then
    re-quantizes the fresh page (payload + scale together), so divergence
    re-quantizes exactly once."""
    src_rows = jnp.asarray(src_rows, jnp.int32)
    dst_rows = jnp.asarray(dst_rows, jnp.int32)
    out = dict(copy_pool_rows(cache, src_rows, dst_rows))
    for key in ("k", "v"):
        P, ps = cache[key].shape[1:3]
        src_pg = jnp.clip(src_rows[:, 0] // ps, 0, P - 1)
        dst_pg = jnp.where(dst_rows[:, 0] < P * ps, dst_rows[:, 0] // ps, P)
        sc = out[key + "_scale"]
        out[key + "_scale"] = sc.at[:, dst_pg].set(sc[:, src_pg], mode="drop")
    return out


def seed_prefix_cache_q8(model: Model, cache, phys_rows, row_ok, pos,
                         s_max: int, dtype=jnp.float32):
    """Int8 prefix seed: gather the shared prefix rows like
    ``registry.seed_prefix_cache`` and DEQUANTIZE them with each row's
    per-group page scales, so the transient tail-prefill cache is a
    faithful f32 view of the aliased int8 pages."""
    K = phys_rows.shape[0]
    out = model.init_cache(K, s_max, dtype)
    idx = jnp.where(row_ok, phys_rows, 0)
    for key in ("k", "v"):
        pool = cache[key]                   # (L, P, ps, KV, hd) int8
        Lr, P, ps = pool.shape[:3]
        T = cache[key + "_scale"].shape[-1]
        flat = pool.reshape((Lr, P * ps) + pool.shape[3:])
        pg = jnp.clip(idx // ps, 0, P - 1)
        raw = flat[:, idx].astype(jnp.float32)       # (L, Kr, KV, hd)
        KV, hd = raw.shape[2], raw.shape[3]
        grouped = raw.reshape(Lr, raw.shape[1], T, KV // T, hd)
        sc = cache[key + "_scale"][:, pg]            # (L, Kr, T)
        rows = (grouped * sc[..., None, None]).reshape(raw.shape)
        mask = row_ok.reshape((1,) + row_ok.shape + (1,) * (rows.ndim - 3))
        out[key] = jnp.where(mask, rows, 0).astype(out[key].dtype)
    out["pos"] = jnp.asarray(pos, jnp.int32)
    return out


@functools.lru_cache(maxsize=1)
def _jitted_insert_rows_q8():
    return jax.jit(insert_cache_rows_paged_q8, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _jitted_copy_rows_q8():
    return jax.jit(copy_pool_rows_q8, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _jitted_prefix_seed_q8(model: Model, s_max: int, dtype):
    def seed(cache, phys_rows, row_ok, pos):
        return seed_prefix_cache_q8(model, cache, phys_rows, row_ok, pos,
                                    s_max, dtype)
    return jax.jit(seed)


# -------------------------------------------------------------- the seam
# string-keyed backend registry: name -> KVBackend subclass. Populated by
# the @register_backend decorations below; external representations can
# register their own class under a fresh key and every engine entry point
# (ServeConfig.kv_backend, make_backend) resolves it by name.
BACKENDS: dict = {}


def register_backend(cls=None, *, aliases=()):
    """Class decorator registering a :class:`KVBackend` subclass in
    :data:`BACKENDS` under its ``name`` attribute (plus any ``aliases``).
    Re-registering an existing key raises — a silent overwrite would let a
    typo'd plugin shadow a built-in representation."""
    def _register(cls):
        for key in (cls.name, *aliases):
            if key in BACKENDS:
                raise ValueError(
                    f"KV backend name {key!r} already registered "
                    f"(by {BACKENDS[key].__name__}); pick a fresh key")
            BACKENDS[key] = cls
        return cls
    return _register(cls) if cls is not None else _register


class KVBackend:
    """Protocol every cache representation implements. Attributes:
    ``name`` (registry key), ``paged`` (pool + block tables vs per-slot
    rows), ``quantized`` (carries per-page scale metadata)."""

    name = "abstract"
    paged = False
    quantized = False

    @staticmethod
    def capacity(cfg: ArchConfig, s_max: int) -> int:
        """Per-slot row capacity the page allocator must cover."""
        return cache_capacity(cfg, s_max)

    def init_cache(self, model: Model, batch_slots: int, s_max: int, dtype):
        raise NotImplementedError

    def insert_rows(self, cache, request_cache, slots, phys_rows=None):
        """Completion splice of a transient batch-K prefill cache into the
        resident cache (phys_rows: the paged row map, None for dense)."""
        raise NotImplementedError

    def copy_rows(self, cache, src_rows, dst_rows):
        """COW re-materialisation (paged only)."""
        raise NotImplementedError(f"{self.name} backend has no pages")

    def seed_prefix(self, model: Model, s_max: int, dtype):
        """-> jitted fn(cache, phys_rows, row_ok, pos) building the dense
        transient cache for a prefix-hit tail prefill (paged only)."""
        raise NotImplementedError(f"{self.name} backend has no pages")

    def kernel_supports(self, cfg) -> bool:
        """Can the paged Pallas kernel read this representation for this
        arch, on this platform? Decided from the family and shapes."""
        return False

    def resolve_attn_impl(self, cfg, multi_page: bool) -> str:
        """'auto' policy: which paged read path serves this config. The
        degenerate one-page-per-slot config (``multi_page`` False) stays on
        the einsum path: it IS the dense bit-exactness anchor."""
        return ("kernel" if multi_page and self.kernel_supports(cfg)
                else "einsum")

    def page_meta(self, cache) -> dict:
        """Per-page metadata leaves this representation adds (name ->
        (L, P, ...) array); empty for unquantized backends."""
        return {}

    def check_page_meta(self, cache, num_pages: int) -> None:
        """Invariant hook for per-page metadata (assert_page_invariants)."""

    # ------------------------------------------------------ sharding hooks
    @classmethod
    def pool_axes(cls) -> dict:
        """Logical sharding axes per cache leaf (leaf name -> logical-axis
        tuple, resolved under ``specs.TP_POOL_RULES``), SCALE leaves
        included. The base declares nothing — every leaf replicates — so a
        backend without mesh knowledge still places correctly; see
        :meth:`place`."""
        return {}

    @classmethod
    def tp_compatible(cls, mesh) -> bool:
        """Capability query: can this representation serve under the given
        tensor parallelism? ``mesh`` may be a Mesh, None, or a plain int tp
        degree (``ServeConfig.validate`` runs before any mesh exists). The
        base says yes — :meth:`place` has a safe replicated fallback and
        every built-in paged representation composes with tp."""
        return True

    def place(self, cache, mesh):
        """Commit a freshly built cache pytree onto ``mesh``: each leaf
        named in :meth:`pool_axes` gets its declared logical axes (resolved
        under ``specs.TP_POOL_RULES``; non-divisible dims drop to
        replicated), every other leaf replicates. No-op without a mesh.
        A backend that never overrode :meth:`pool_axes` gets a fully
        replicated cache under tp>1 plus a warning — correct, just not
        memory-scaled per shard."""
        if mesh is None:
            return cache
        from repro.sharding import specs as _sp
        axes_map = self.pool_axes()
        if (type(self).pool_axes.__func__ is KVBackend.pool_axes.__func__
                and _tp_degree(mesh) > 1):
            log.warning(
                "KV backend %r declares no pool_axes(); placing its cache "
                "fully replicated on the tp=%d mesh (correct, but the pool "
                "does not shrink per shard)", self.name, _tp_degree(mesh))
        shardings = {}
        with _sp.use_mesh(mesh, _sp.TP_POOL_RULES):
            for key, leaf in cache.items():
                axes = axes_map.get(key)
                if axes is None or len(axes) != leaf.ndim:
                    axes = (None,) * leaf.ndim
                shardings[key] = _sp.sharding_for(leaf.shape, axes)
        return jax.device_put(cache, shardings)


@register_backend
class DenseBackend(KVBackend):
    """The page_size == None degenerate: per-slot (B, s_max) rows, batch-axis
    completion splice, no pages/COW/prefix sharing."""

    name = "dense"

    def init_cache(self, model: Model, batch_slots: int, s_max: int, dtype):
        return vectorize_cache_pos(model.init_cache(batch_slots, s_max, dtype),
                                   batch_slots, inactive=True)

    def insert_rows(self, cache, request_cache, slots, phys_rows=None):
        return _jitted_insert_rows()(cache, request_cache, slots)

    @classmethod
    def tp_compatible(cls, mesh) -> bool:
        # tensor-parallel serving shards the PAGED pool (page indices are
        # shard-invariant); the per-slot dense cache has no mesh layout
        return _tp_degree(mesh) <= 1


def _tp_degree(mesh) -> int:
    """Size of the serving mesh's tensor-parallel axis (1 if no mesh).
    Also accepts a plain int tp degree — ``ServeConfig.validate`` consults
    the capability query before any mesh exists."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        return mesh
    from repro.sharding import specs as _sp
    if _sp.TP_AXIS not in mesh.axis_names:
        return 1
    return mesh.shape[_sp.TP_AXIS]


def _shards_kv_heads(cls) -> bool:
    """Does this backend's declared pool layout shard the kv-head axis?
    (Gates the num_kv_heads % tp divisibility requirement — a backend with
    a replicated or head-free pool, e.g. paged_latent, has no such
    constraint.)"""
    return any("kv_heads" in axes for axes in cls.pool_axes().values())


def check_tp_support(spec, tp: int) -> None:
    """Raise the pinned tp-incompatibility error when ``spec``'s (a registry
    name or KVBackend class) capability query refuses the given tp degree.
    Shared by ``ServeConfig.validate`` (preflight) and :func:`make_backend`
    (direct-construction defense)."""
    cls = BACKENDS[spec] if isinstance(spec, str) else spec
    if tp > 1 and not cls.tp_compatible(tp):
        raise ValueError(
            f"kv_backend={cls.name!r} reports tp_compatible=False for "
            f"tp={tp}: this cache representation does not compose with "
            f"tensor-parallel serving; use kv_backend='paged' with tp>1 "
            f"or drop tp")


@register_backend(aliases=("paged_fp32",))
class PagedFP32Backend(KVBackend):
    """The vLLM-style shared fp32/bf16 page pool (the pre-backend layout,
    bit-for-bit).

    ``mesh``: optional serving mesh. When set, ``init_cache`` COMMITS the
    K/V pool leaves sharded on their kv-head axis over the mesh's tp axis
    (each device then holds a ``(L, P, ps, KV/tp, hd)`` resident slice) and
    every other leaf replicated — page ids are shard-invariant, so block
    tables, positions, and the host-side allocator/prefix index never learn
    the mesh exists. The splice/COW/seed jits below need no shard_map: they
    are elementwise scatters/gathers over replicated row indices, which
    GSPMD partitions along the already-sharded kv-head axis without
    introducing any cross-shard reduction (bitwise-safe)."""

    name = "paged"
    paged = True

    def __init__(self, page_size: int, num_pages: int, mesh=None):
        self.page_size = page_size
        self.num_pages = num_pages
        self.mesh = mesh

    @classmethod
    def pool_axes(cls) -> dict:
        from repro.sharding import specs as _sp
        return {"k": _sp.KV_POOL_AXES, "v": _sp.KV_POOL_AXES}

    def init_cache(self, model: Model, batch_slots: int, s_max: int, dtype):
        cache = init_paged_cache(model, batch_slots, s_max,
                                 page_size=self.page_size,
                                 num_pages=self.num_pages, dtype=dtype)
        return self.place(cache, self.mesh)

    def insert_rows(self, cache, request_cache, slots, phys_rows=None):
        return _jitted_insert_rows_paged()(cache, request_cache, slots,
                                           phys_rows)

    def copy_rows(self, cache, src_rows, dst_rows):
        return _jitted_copy_rows()(cache, src_rows, dst_rows)

    def seed_prefix(self, model: Model, s_max: int, dtype):
        return _jitted_prefix_seed(model, s_max, dtype)

    def kernel_supports(self, cfg) -> bool:
        # one head is one block's lane axis: on the TPU its width must tile
        from repro.kernels.ops import attention_kernel_fits
        return (cfg.family in PAGED_KERNEL_FAMILIES
                and attention_kernel_fits(cfg.head_dim))


@register_backend
class PagedInt8Backend(PagedFP32Backend):
    """Int8 page pools + per-page symmetric scales. Same block tables,
    allocator contract, and attention dispatch as the fp32 pool — only the
    representation ops differ (quantizing splice, scale-carrying COW,
    dequantizing seed/read)."""

    name = "paged_int8"
    quantized = True

    @classmethod
    def pool_axes(cls) -> dict:
        axes = dict(super().pool_axes())
        # scale leaves (L, P, tp): one scale per page per kv-head GROUP,
        # group t covering the contiguous KV/tp heads shard t owns — the
        # trailing group column shards WITH its kv heads, so each shard
        # computes its scales from purely local pool values
        axes["k_scale"] = (None, None, "kv_heads")
        axes["v_scale"] = (None, None, "kv_heads")
        return axes

    def init_cache(self, model: Model, batch_slots: int, s_max: int, dtype):
        base = super().init_cache(model, batch_slots, s_max, dtype)
        out = dict(base)
        tp = _tp_degree(self.mesh)
        for key in ("k", "v"):
            out[key] = jnp.zeros(base[key].shape, jnp.int8)
            # scale 1.0 everywhere: a never-written page dequants to exact
            # zeros, same as the fp32 pool's zero init
            out[key + "_scale"] = jnp.ones(base[key].shape[:2] + (tp,),
                                           jnp.float32)
        return self.place(out, self.mesh)

    def insert_rows(self, cache, request_cache, slots, phys_rows=None):
        return _jitted_insert_rows_q8()(cache, request_cache, slots,
                                        phys_rows)

    def copy_rows(self, cache, src_rows, dst_rows):
        return _jitted_copy_rows_q8()(cache, src_rows, dst_rows)

    def seed_prefix(self, model: Model, s_max: int, dtype):
        return _jitted_prefix_seed_q8(model, s_max, dtype)

    def page_meta(self, cache) -> dict:
        return {"k_scale": cache["k_scale"], "v_scale": cache["v_scale"]}

    def check_page_meta(self, cache, num_pages: int) -> None:
        import numpy as np
        tp = _tp_degree(self.mesh)
        for key in ("k_scale", "v_scale"):
            sc = np.asarray(cache[key])
            L = cache[key[0]].shape[0]
            assert sc.shape == (L, num_pages, tp), \
                f"{key} shape {sc.shape} != {(L, num_pages, tp)}"
            assert np.isfinite(sc).all() and (sc > 0).all(), \
                f"{key} has non-finite or non-positive entries"


@register_backend
class PagedLatentBackend(PagedFP32Backend):
    """MLA latent pages: each pool row is one per-token ``(kv_lora_rank +
    qk_rope_head_dim)``-dim compressed latent shared by EVERY query head
    (the absorb path folds ``wkv_b`` into the query/output einsums, so
    attention reads the latent directly — values are the leading
    ``kv_lora_rank`` columns of the same rows). The cache therefore has a
    single ``k`` pool of shape (L, P, page_size, 1, c + r) and NO ``v``
    leaf; the generic splice/COW/seed machinery is key-generic, so this
    backend inherits every representation op from the fp32 pool — COW
    copies a latent row, never per-head K/V. Block tables, the allocator,
    and the prefix index are untouched: a page is a page.

    Under tensor parallelism the latent pool REPLICATES (see
    :meth:`pool_axes`) and tp instead shards the ABSORBED queries/outputs
    on their head axis (models/layers.py mla paths): per-head attention
    over the shared latent is head-independent, and the all-gather before
    ``wo`` keeps tp>1 greedy streams bitwise equal to tp=1."""

    name = "paged_latent"

    @classmethod
    def pool_axes(cls) -> dict:
        # a latent row has no kv-head axis (KV == 1; every query head reads
        # the same compressed row), and at (c + r) floats per token the
        # pool is small enough to hold per shard — so it replicates, and
        # the head axis of the absorbed queries carries the tp split
        return {}

    def kernel_supports(self, cfg) -> bool:
        # the latent kernel's blocks are whole latent rows: any width tiles
        return cfg.family in PAGED_KERNEL_FAMILIES

    def init_cache(self, model: Model, batch_slots: int, s_max: int, dtype):
        if getattr(model.cfg, "kv_lora_rank", 0) <= 0:
            raise ValueError(
                f"kv_backend='paged_latent' needs an MLA arch "
                f"(kv_lora_rank > 0); {model.cfg.name!r} caches per-head "
                f"K/V — use kv_backend='paged' (its pages would hold the "
                f"same rows anyway)")
        return super().init_cache(model, batch_slots, s_max, dtype)


def make_backend(spec, *, family: Family, page_size=None, num_pages=None,
                 mesh=None, num_kv_heads=None):
    """Resolve an engine ``kv_backend`` spec: None (layout follows
    page_size), a name registered in :data:`BACKENDS` ('dense' | 'paged' |
    'paged_fp32' | 'paged_int8' | 'paged_latent'), or a ready KVBackend
    instance. Int8 on an unsupported family raises: a silent swap to fp32
    pages would serve something other than what was asked for.
    ``mesh``: optional serving mesh the backend's :meth:`KVBackend.place`
    commits its pool onto. ``num_kv_heads``: when given with a tp>1 mesh,
    checked against the backend's declared layout (a kv-head-sharded pool
    needs tp to divide the kv-head count; a replicated/head-free pool does
    not) — the engine passes it so direct ``ServeEngine(...)`` construction
    hits the same preflight as ``ServeConfig.validate``."""
    if isinstance(spec, KVBackend):
        if mesh is not None and getattr(spec, "mesh", None) is not mesh:
            raise ValueError("a ready KVBackend instance must be built with "
                             "the engine's mesh (pass mesh= to its ctor)")
        return spec
    if spec is None:
        spec = "paged" if page_size is not None else "dense"
    cls = BACKENDS.get(spec)
    if cls is None:
        raise ValueError(f"unknown kv_backend {spec!r}; available: "
                         f"{sorted(BACKENDS)}")
    tp = _tp_degree(mesh)
    if not cls.paged:
        if page_size is not None:
            raise ValueError(f"kv_backend={spec!r} conflicts with page_size="
                             f"{page_size}; drop one of them")
        if tp > 1:
            raise ValueError("tensor-parallel serving shards the PAGED pool "
                             "(page indices are shard-invariant); the dense "
                             "backend has no mesh layout — pass page_size=")
        return cls()
    if page_size is None:
        raise ValueError(f"kv_backend={spec!r} needs page_size")
    if cls is PagedInt8Backend and family not in INT8_KV_FAMILIES:
        raise ValueError(f"kv_backend='paged_int8' supports "
                         f"{[f.name for f in INT8_KV_FAMILIES]} (got "
                         f"{family}); use kv_backend='paged'")
    check_tp_support(cls, tp)
    if (tp > 1 and num_kv_heads is not None and _shards_kv_heads(cls)
            and num_kv_heads % tp):
        raise ValueError(
            f"num_kv_heads={num_kv_heads} is not divisible by tp={tp}; "
            f"pick a tp dividing the kv-head count (whole GQA groups must "
            f"stay shard-local)")
    return cls(page_size, num_pages, mesh=mesh)
