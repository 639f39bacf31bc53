"""Batched-prefill continuous-batching serve engine with a paged KV cache.

Core invariants (see the package docstring for the request lifecycle):

* **One dispatch per prefill wave.** New requests are prefilled by a single
  jitted ``make_prefill(return_cache=True)`` call — prompts are
  teacher-forced under one ``lax.scan``, not one device dispatch per token,
  and never at the full batch width (the legacy path's O(prompt_len)
  full-batch stepping). Same-length requests admitted on the same tick are
  prefilled jointly at batch K (the batched-prefill fan-in); a lone request
  runs at batch 1.
* **Slot isolation.** The batch-K prefill cache is spliced into the resident
  cache through the KV backend's ``insert_rows`` (dense: a batch-row
  scatter; paged: a scatter into exactly the pages the admitted slots own)
  — other slots' cache entries and positions are untouched bit-for-bit.
* **Per-slot positions, inactive sentinel.** The resident cache's ``pos`` is
  a (B,) vector, so slots at different sequence depths decode together in
  one tick. A freed (or never-admitted) slot's pos is parked at
  ``layers.INACTIVE_POS``: every decode path drops its cache writes and
  freezes its recurrent state, so inactive rows are bit-stable — they cannot
  scatter stale K/V into recycled pages.
* **Paged KV (vLLM-style block tables).** With ``page_size`` set, K/V live
  in a shared page pool ``(L, num_pages, page_size, KV, hd)`` addressed
  through per-slot block tables; a host-side free-list ``PageAllocator``
  hands pages out at admission and reclaims them on completion. Memory
  scales with allocated pages — s_max bounds a single request's length (the
  block-table width), not the pool's footprint, so a long request no longer
  dictates every slot's memory. ``page_size == s_max`` is the degenerate
  one-page-per-slot config and reproduces the dense path bit-for-bit.
* **Continuous batching with page-aware admission.** The scheduler admits
  waiting requests the moment a slot frees, on the same tick; paged
  admission PEEKS first and defers (in strict priority/FIFO order) when the
  free list cannot cover the request's worst-case page count.
* **Parallel chunked prefill (default).** Prompts are ingested by the
  matmul-wide ``make_prefill_chunk`` path: every chunk position is computed
  in one full-width pass per layer and the per-layer K/V (ring + recurrent
  carry for hybrid, O(1) state for ssm/rwkv) land in a transient request
  cache that is spliced into the resident cache when the prompt completes.
  Chunks are INTERLEAVED with decode ticks — at most one chunk of at most
  ``prefill_chunk_tokens`` tokens runs between consecutive decode ticks, so
  a max-length prompt cannot stall in-flight decodes (head-of-line bound).
  Chunk lengths are BUCKETED to a fixed ladder (the chunk size plus the
  powers of two below it), so prefill compiles O(ladder), not O(distinct
  prompt lengths); the trace count is hard-capped (jit caches are cleared
  past ``max_prefill_traces``). ``prefill_mode='scan'`` keeps the
  teacher-forced scan prefill as the bit-exactness anchor.

* **Page-level prefix caching (paged dense/MoE/VLM, default on).** Completed
  prompt pages are chain-hashed into a refcounted ``PrefixIndex``; admission
  aliases the longest cached page-aligned prefix into the request's block
  table and runs only the uncached tail. Shared pages are immutable: a
  write that would land in one (partial-page tails, decode appending past
  the prefix) instead targets a fresh page that is re-materialised by the
  same pool scatter — copy-on-write with no extra device pass. Eviction is
  LRU over pages only the index references, and runs before admission ever
  defers.

* **Paged-attention kernel + incremental splice (default with the kernel).**
  With ``paged_attn_impl='kernel'`` (auto on multi-page dense/MoE/VLM/encdec
  pools) decode reads go through the Pallas block-table-gather kernel
  (``kernels/paged_attention.py``) that SKIPS fully-masked pages, and —
  for dense/MoE/VLM parallel prefill — continuation chunks splice their
  K/V into the reserved pages INCREMENTALLY per chunk and attend the pages
  directly: the transient dense request cache disappears, per-chunk mask
  work stops scaling with s_max, prefix hits read aliased pages in place
  (no gather seeding), and COW re-materialisation reuses the same scatter.
  ``paged_attn_impl='einsum'`` keeps the masked-gather transient path (the
  bit-exactness anchor; auto for the degenerate one-page config).

* **Failure / cancellation release.** A prefill chunk dispatch that raises
  aborts its job through ``release_job`` — slots freed, reserved pages and
  aliased prefix refcounts released, requests marked FAILED — and
  ``cancel()`` does the same from every request state, so an errored or
  cancelled mid-prefill job can no longer strand pages until process exit.

* **Pluggable KV-cache backends.** The engine is pure ORCHESTRATION: every
  representation decision (pool dtype/shape, splice math, COW copy, prefix
  seed, per-page metadata) lives behind the :class:`~repro.serve.kvcache
  .KVBackend` seam — ``DenseBackend``, ``PagedFP32Backend`` (the layout
  above, bit-for-bit), and ``PagedInt8Backend`` (int8 pages + per-page
  symmetric scales, dequantized inside the paged kernel's gather). Select
  with ``kv_backend=``; None keeps the historical layout-follows-page_size
  behaviour.

Multi-host serving is a ROADMAP follow-on.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import warnings
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import Family
from repro.kernels import ops as kops
from repro.launch import steps as steps_mod
from repro.models.layers import INACTIVE_POS, cast_on_use
from repro.models.registry import Model, get_model, reduced_config
from repro.serve.kvcache import (PAGED_KERNEL_FAMILIES, PREFIX_CACHE_FAMILIES,
                                 KVBackend, make_backend)
from repro.serve.metrics import MetricsRecorder
from repro.serve.prefix import PrefixIndex, PrefixPlan
from repro.serve.scheduler import (Request, RequestState, SchedPolicy,
                                   Scheduler)

# PREFIX_CACHE_FAMILIES / PAGED_KERNEL_FAMILIES moved to serve/kvcache.py
# with the rest of the representation layer; re-imported above so existing
# callers (`engine.PREFIX_CACHE_FAMILIES`) keep working.

log = logging.getLogger("repro.serve.engine")


def _under_mesh(mesh, fn):
    """Trace ``fn`` inside the tensor-parallel serving mesh context
    (identity when mesh is None). The engine only forwards the mesh token —
    which rules apply and what they mean lives in sharding/specs.py
    (:func:`specs.serve_trace`), keeping mesh internals out of this
    module."""
    if mesh is None:
        return fn
    from repro.sharding import specs as _specs
    return _specs.serve_trace(mesh, fn)


# Jitted step functions are cached at module level keyed on the (frozen,
# hashable) Model so several engine instances over the same architecture —
# e.g. benchmark repetitions — share one compiled executable instead of
# re-tracing per instance (compile time would otherwise dominate short runs).
# The (hashable) mesh is part of every key: a mesh trace bakes shard_map
# calls into the jaxpr, so mesh and no-mesh engines must never share one.
@functools.lru_cache(maxsize=64)
def _jitted_decode(model: Model, compute_dtype, paged_impl=None, mesh=None):
    return jax.jit(_under_mesh(mesh, steps_mod.make_decode_step(
        model, compute_dtype=compute_dtype, paged_attn_impl=paged_impl)),
        donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def _jitted_prefill(model: Model, compute_dtype, s_max: int, cache_dtype,
                    mesh=None):
    return jax.jit(_under_mesh(mesh, steps_mod.make_prefill(
        model, compute_dtype=compute_dtype, return_cache=True, s_max=s_max,
        cache_dtype=cache_dtype)))


@functools.lru_cache(maxsize=64)
def _jitted_prefill_chunk(model: Model, compute_dtype, s_max: int,
                          cache_dtype, first: bool, attn_impl: str,
                          mesh=None):
    """Parallel-prefill chunk executables. One jitted callable per
    (model, first) pair; jax retraces it per (batch K, chunk C) SHAPE — the
    engine's bucketed chunk ladder is what keeps that inner cache O(buckets)
    rather than O(distinct prompt lengths), and ``_note_prefill_trace``
    clears these caches if a caller defeats the bucketing."""
    fn = _under_mesh(mesh, steps_mod.make_prefill_chunk(
        model, compute_dtype=compute_dtype, s_max=s_max,
        cache_dtype=cache_dtype, first=first, attn_impl=attn_impl))
    if first:
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=(1,))     # donate the transient cache


def _init_params(model: Model, seed: int, compute_dtype, quantize_int8: bool):
    """The engine's parameter tree, built inside one jit (see ``build``)."""
    params = model.init(jax.random.PRNGKey(seed))
    if quantize_int8:
        from repro.core.quantize import dequantize_params, quantize_params
        params = dequantize_params(quantize_params(params), compute_dtype)
    return cast_on_use(params, compute_dtype)


def chunk_ladder(chunk_tokens: int) -> List[int]:
    """The bucketed chunk-length ladder: the chunk size plus every power of
    two below it, descending. Any prompt length decomposes greedily into
    ladder chunks, so prefill compile count is O(len(ladder)) under mixed
    traffic instead of O(distinct prompt lengths)."""
    if chunk_tokens < 1:
        raise ValueError(f"chunk_tokens must be >= 1, got {chunk_tokens}")
    ladder = {chunk_tokens}
    p = 1
    while p < chunk_tokens:
        ladder.add(p)
        p <<= 1
    return sorted(ladder, reverse=True)


def chunk_plan(prompt_len: int, ladder: List[int]) -> List[int]:
    """Greedy largest-first decomposition of a prompt into ladder chunks —
    every token is real (no padding/masking), the last chunks just narrow."""
    plan, rem = [], prompt_len
    for c in ladder:
        while rem >= c:
            plan.append(c)
            rem -= c
    return plan


@dataclasses.dataclass
class _PrefillJob:
    """One in-flight chunked prefill: K same-length requests being ingested
    jointly. ``cache`` is the dense transient request cache at batch K
    (created inside the first-chunk jit — or PRE-SEEDED with gathered
    shared-prefix rows on a prefix-cache hit, in which case every chunk is a
    continuation); slots/pages are already reserved, so completion (the
    splice) cannot fail. ``prompts`` holds only the TAIL the chunks compute
    (positions ``tail_start`` onward); ``write_floor`` is the first cache
    row the completion splice may write — rows below it live in shared
    immutable pages (aliased full pages) and are dropped by the scatter."""
    slots: List[int]
    reqs: List[Request]
    prompts: np.ndarray            # (K, P - tail_start) uncached tail tokens
    plan: List[int]                # bucketed chunk lengths, sums to the tail
    idx: int = 0                   # next chunk index
    filled: int = 0                # tail tokens already ingested
    cache: Optional[dict] = None   # None until the first chunk runs
    tail_start: int = 0            # first prompt position the chunks compute
    write_floor: int = 0           # splice drops rows below this
    prefix_plans: Optional[List[PrefixPlan]] = None   # per-request, for
    # registration at splice (None in scan mode / prefix-cache off)
    deficit: int = 0               # DRR chunk-token credit (policy.drr only)


@functools.lru_cache(maxsize=64)
def _jitted_prefill_chunk_paged(model: Model, compute_dtype, attn_impl: str,
                                mesh=None):
    """Incremental paged-prefill chunk executables: ONE callable per model
    (no first/continuation split — every chunk writes into pages and attends
    them through the block table), retraced per (group K, chunk C) shape
    like the transient chunk path. The resident cache is donated: the pools
    update in place each chunk instead of round-tripping a transient copy."""
    fn = _under_mesh(mesh, steps_mod.make_prefill_chunk_paged(
        model, compute_dtype=compute_dtype, attn_impl=attn_impl))
    return jax.jit(fn, donate_argnums=(1,))


class PageAllocator:
    """Host-side REFCOUNTED free-list allocator over a fixed pool of KV-cache
    pages.

    Pure bookkeeping: page ids index the device pool's page axis; nothing
    here touches device memory. ``alloc`` is all-or-nothing (a request's
    worst case is reserved up front, so admission can never strand a
    half-allocated request) and hands pages out at refcount 1. ``share``
    adds a reference — a prefix-cache index entry, or a second block table
    aliasing the same immutable prefix page — and ``release`` drops one: a
    page returns to the free list only when its LAST reference goes (so a
    page can never be simultaneously free and referenced by a live block
    table or prefix entry), and releasing a page with no references raises
    (the double-free guard the property tests exercise)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ref: Dict[int, int] = {}

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def held(self) -> set:
        """Pages with at least one live reference (test/debug view)."""
        return set(self._ref)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Reserve n pages at refcount 1; returns their ids or None if the
        free list is short (caller defers admission — nothing is partially
        allocated)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def share(self, page: int):
        """Add a reference to a held page (block-table alias or prefix-index
        entry). Sharing an unreferenced page is a bookkeeping bug."""
        if page not in self._ref:
            raise ValueError(f"share of unheld page {page}")
        self._ref[page] += 1

    def release(self, pages: List[int]):
        """Drop one reference per page; pages reaching zero return to the
        free list. Releasing an already-free page raises."""
        for p in pages:
            n = self._ref.get(p, 0)
            if n <= 0:
                raise ValueError(f"double free of page {p}")
            if n == 1:
                del self._ref[p]
                self._free.append(p)
            else:
                self._ref[p] = n - 1


class ServeEngine:
    """Slot-based continuous-batching engine over a per-slot-position cache,
    dense or paged (``page_size``/``num_pages``).

    sampling: ``temperature == 0`` is greedy argmax; ``temperature > 0``
    samples from softmax(logits / temperature) — optionally restricted to the
    ``top_k`` highest logits and/or the smallest ``top_p`` nucleus — with a
    per-event PRNG fold so runs are reproducible for a fixed seed.

    prefill: ``prefill_mode='parallel'`` (default) ingests prompts with the
    matmul-wide chunked path, at most one chunk of ``prefill_chunk_tokens``
    tokens between decode ticks; ``'scan'`` is the teacher-forced
    one-dispatch scan prefill (the bit-exactness anchor).
    ``prefill_attn_impl='auto'`` resolves to the K/V-exporting flash kernel
    on TPU when the head width tiles, and the jnp reference otherwise.
    """

    def __init__(self, model: Model, params, *, batch_slots: int, s_max: int,
                 compute_dtype=jnp.float32, cache_dtype=None,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int = 0, top_p: float = 1.0,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 kv_backend=None,
                 prefix_cache: Optional[bool] = None,
                 prefill_mode: str = "parallel",
                 prefill_chunk_tokens: int = 64,
                 prefill_attn_impl: str = "auto",
                 paged_attn_impl: str = "auto",
                 max_prefill_traces: Optional[int] = None,
                 scheduler: Optional[Scheduler] = None,
                 metrics: Optional[MetricsRecorder] = None,
                 policy: Optional[SchedPolicy] = None,
                 mesh=None):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.batch_slots = batch_slots
        self.s_max = s_max
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype or compute_dtype
        self.temperature = float(temperature)
        if int(top_k) < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got {top_k}")
        if not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        if prefill_mode not in ("parallel", "scan"):
            raise ValueError(f"prefill_mode must be 'parallel' or 'scan', "
                             f"got {prefill_mode!r}")
        self.prefill_mode = prefill_mode
        self.prefill_chunk_tokens = min(int(prefill_chunk_tokens), s_max)
        self.prefill_ladder = chunk_ladder(self.prefill_chunk_tokens)
        if prefill_attn_impl == "auto":
            # decided once, from the platform and the head width the flash
            # kernel must tile; the wrappers raise on the TPU rather than
            # swap in the oracle, so the choice recorded here is the path
            prefill_attn_impl = (
                "pallas" if (jax.default_backend() == "tpu"
                             and kops.attention_kernel_fits(self.cfg.head_dim))
                else "einsum")
        self.prefill_attn_impl = prefill_attn_impl
        # hard cap on distinct prefill trace shapes: first/cont x ladder x
        # group widths; past it the chunk jit caches are cleared (and the
        # overflow counted) so a bucketing-defeating caller cannot leak
        # compiled executables without bound
        self.max_prefill_traces = (max_prefill_traces if max_prefill_traces
                                   is not None else
                                   2 * len(self.prefill_ladder) * batch_slots)
        self._trace_keys: set = set()
        self.prefill_trace_evictions = 0
        self._jobs: List[_PrefillJob] = []
        self.max_prefill_tokens_per_tick = 0   # head-of-line bound witness
        # SLO-aware scheduling policy: every SchedPolicy default is OFF, so
        # policy=None keeps greedy token streams bit-identical to the
        # pre-policy engine (the standing anchor discipline). Resolved
        # before the scheduler so a default-built Scheduler inherits
        # policy.edf.
        self.policy = SchedPolicy() if policy is None else policy
        # explicit None checks: an EMPTY Scheduler is falsy (__bool__ tracks
        # queue depth), so `scheduler or Scheduler()` would silently discard
        # a caller's configured (e.g. prefix-aware) scheduler
        self.scheduler = (Scheduler(edf=self.policy.edf)
                          if scheduler is None else scheduler)
        self.metrics = MetricsRecorder() if metrics is None else metrics
        self._drr_cursor = 0          # rotates the DRR starting job per tick
        self._consec_prefill_ticks = 0  # starvation-guard state

        # tensor-parallel serving mesh: the cache leaves commit through the
        # backend's place() hook, params/activations replicate, and the
        # attention cores route through shard_map wrappers resolved at the
        # kernels layer. Every mesh/axis-name decision lives behind the
        # backend seam or the sharding/specs helpers — the engine holds the
        # mesh as an opaque token and never reads its internals (pinned by
        # the AST guard in tests/test_kvcache.py).
        self.mesh = mesh
        if mesh is not None:
            from repro.sharding import specs as _specs
            if page_size is None:
                raise ValueError(
                    "tensor-parallel serving needs a PAGED cache (pass "
                    "page_size=): only the page pool has a mesh layout")
            self.params = _specs.replicate_params(self.params, mesh)

        if page_size is not None and model.cfg.family == Family.SSM:
            log.warning("ssm/rwkv state is O(1) in s_max — ignoring paging")
            page_size = None
        self.page_size = page_size
        self.paged = page_size is not None
        if self.paged:
            if s_max % page_size:
                raise ValueError(f"s_max {s_max} must be a multiple of "
                                 f"page_size {page_size}")
            self.max_pages_per_slot = s_max // page_size
            self.num_pages = (num_pages if num_pages is not None
                              else batch_slots * self.max_pages_per_slot)
            # the backend owns every REPRESENTATION decision (pool layout,
            # splice/COW/seed math, per-page metadata); the engine keeps the
            # orchestration state that follows (allocator, block tables)
            self.backend: KVBackend = make_backend(
                kv_backend, family=self.cfg.family, page_size=page_size,
                num_pages=self.num_pages, mesh=mesh,
                num_kv_heads=self.cfg.num_kv_heads)
            # rows one slot's attention cache can hold (ring width for hybrid)
            self.capacity = self.backend.capacity(self.cfg, s_max)
            self.allocator = PageAllocator(self.num_pages)
            self.slot_pages: List[List[int]] = [[] for _ in range(batch_slots)]
            self._bt_host = np.full((batch_slots, self.max_pages_per_slot),
                                    -1, np.int32)
        else:
            self.backend = make_backend(kv_backend, family=self.cfg.family,
                                        mesh=mesh,
                                        num_kv_heads=self.cfg.num_kv_heads)
        self.cache = self.backend.init_cache(model, batch_slots, s_max,
                                             self.cache_dtype)

        # prefix cache: paged + parallel prefill + an attention-pure family
        # only (the tail-only restart needs the full mid-prompt state to be
        # reconstructible from K/V pages). None = auto-enable when supported;
        # an explicit True on an unsupported config warns and falls back to
        # full prefill rather than erroring (serving keeps working).
        supported = (self.paged and self.prefill_mode == "parallel"
                     and self.cfg.family in PREFIX_CACHE_FAMILIES)
        if prefix_cache is None:
            prefix_cache = supported
        elif prefix_cache and not supported:
            log.warning("prefix_cache unsupported here (needs paged cache, "
                        "parallel prefill, and a dense/MoE/VLM family; got "
                        "paged=%s mode=%s family=%s) — falling back to full "
                        "prefill", self.paged, self.prefill_mode,
                        self.cfg.family)
            prefix_cache = False
        self.prefix_cache = bool(prefix_cache)
        self.prefix_index = (PrefixIndex(self.allocator, self.page_size)
                             if self.prefix_cache else None)

        # paged attention read path: 'kernel' = the Pallas block-gather
        # kernel (and, with parallel prefill on a supported family, the
        # INCREMENTAL per-chunk page splice — no transient request cache);
        # 'einsum' = the masked-gather reference read + transient-cache
        # prefill with a completion splice (the PR 2-4 path, kept as the
        # bit-exactness anchor and the unsupported-family fallback).
        if paged_attn_impl not in ("auto", "kernel", "einsum"):
            raise ValueError(f"paged_attn_impl must be 'auto', 'kernel' or "
                             f"'einsum', got {paged_attn_impl!r}")
        if paged_attn_impl == "auto":
            # the backend's dispatch policy, from the family and shapes; for
            # paged pools the degenerate one-page-per-slot config (page_size
            # == s_max) is the dense bit-exactness anchor and has no pages
            # to skip — auto keeps it on the einsum path so the anchor stays
            # bit-for-bit
            paged_attn_impl = (self.backend.resolve_attn_impl(
                self.cfg, self.max_pages_per_slot > 1)
                if self.paged else "einsum")
        elif paged_attn_impl == "kernel" and not (
                self.paged and self.backend.kernel_supports(self.cfg)):
            raise ValueError(
                f"paged_attn_impl='kernel' is not available here (needs a "
                f"paged cache on a dense/MoE/VLM/encdec family whose heads "
                f"the kernel tiles; got paged={self.paged} "
                f"family={self.cfg.family} head_dim={self.cfg.head_dim} on "
                f"{jax.default_backend()}); use 'auto' or 'einsum'")
        self.paged_attn_impl = paged_attn_impl
        # incremental splice: continuation chunks write K/V straight into
        # their reserved pages and attend them through the block table —
        # the transient dense request cache disappears and per-chunk mask
        # work stops scaling with s_max
        self.incremental_splice = (
            self.paged and self.prefill_mode == "parallel"
            and self.paged_attn_impl == "kernel"
            and model.supports_paged_prefill)
        self.prefill_failures = 0
        self.max_transient_cache_bytes = 0
        self._cancel_at_splice: set = set()
        self._decode = _jitted_decode(
            model, compute_dtype,
            self.paged_attn_impl if self.paged else None, mesh)

        # (head rid, free pages, index version) at the last deferral: admit()
        # short-circuits while nothing that could change the outcome has
        # changed, instead of re-running the O(prompt) prefix lookup, the
        # share/release churn, and a futile whole-index eviction walk on
        # every decode tick a head request spends waiting for pages
        self._defer_state: Optional[tuple] = None
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.cur_token = np.zeros((batch_slots, 1), np.int32)
        self.requests: Dict[int, Request] = {}
        self.deferrals = 0    # admissions postponed for lack of free pages
        self._next_rid = 0
        self._key = jax.random.PRNGKey(seed)
        self._events = 0      # PRNG fold counter (one per sampling event)

    # ------------------------------------------------------------ factory
    @classmethod
    def build(cls, arch: str = "hymba-1.5b", *, config=None, devices=None,
              **legacy) -> "ServeEngine":
        """Construct model + params from an arch id and a
        :class:`~repro.serve.config.ServeConfig`:

            ServeEngine.build("qwen2.5-32b-mla", config=ServeConfig(
                page_size=16, kv_backend="paged_latent"))

        ``config.validate(cfg)`` runs against the resolved arch BEFORE any
        weights are built, so cross-field mistakes (dense + tp, a backend
        whose capability query refuses the tp degree, unknown backend name,
        page misalignment) fail fast. The int8
        PTQ path is the same structural quantize->dequant-on-load as the
        paper's C5 (the pallas quant_matmul kernel consumes q directly on
        TPU). ``config.tp`` builds a 1-axis serving mesh over the first
        ``tp`` local devices (tp=1 is a legal 1-device mesh: it exercises
        the whole mesh code path and is the bit-exactness anchor against
        mesh=None); ``devices`` spans the mesh over those devices instead —
        ``devices=[d]`` pins a one-chip replica, params and cache, to ``d``.
        Params are initialised on the device inside one jit, with the
        weights every step casts on use already in ``compute_dtype``
        (``layers.cast_on_use``): no float32 copy of the whole tree is ever
        held. ``config.cfg_overrides``: dataclasses.replace fields
        applied AFTER reduction — reduced configs can shrink num_kv_heads
        to 1, which blocks kv-head sharding; the tp tests/bench override
        the head counts while keeping everything else reduced.

        DEPRECATED spelling: ``build(arch, page_size=..., s_max=...)`` —
        the pre-ServeConfig kwarg surface. Still accepted (each kwarg maps
        onto the ServeConfig field of the same name, so behaviour is
        identical by construction) but emits a DeprecationWarning; passing
        both ``config`` and legacy kwargs is an error."""
        from repro.serve.config import ServeConfig
        if legacy:
            if config is not None:
                raise ValueError(
                    "pass either config=ServeConfig(...) or the legacy "
                    "keyword arguments, not both; the legacy kwargs are "
                    f"deprecated (got {sorted(legacy)})")
            known = {f.name for f in dataclasses.fields(ServeConfig)}
            unknown = sorted(set(legacy) - known)
            if unknown:
                raise TypeError(f"unknown ServeEngine.build arguments "
                                f"{unknown}; ServeConfig fields: "
                                f"{sorted(known)}")
            warnings.warn(
                "ServeEngine.build(**kwargs) is deprecated; pass "
                "config=ServeConfig(...) instead (same field names)",
                DeprecationWarning, stacklevel=2)
            config = ServeConfig(**legacy)
        elif config is None:
            config = ServeConfig()
        cfg = configs.get_config(arch)
        if config.reduced:
            cfg = reduced_config(cfg)
        if config.cfg_overrides:
            cfg = dataclasses.replace(cfg, **config.cfg_overrides)
        # the device-count guard outranks validate(): "you don't have the
        # devices" is the actionable error on a 1-device host even when the
        # reduced config's kv-head count would also reject the tp degree
        if devices is not None:
            if config.tp not in (None, len(devices)):
                raise ValueError(f"tp={config.tp} conflicts with "
                                 f"{len(devices)} devices given")
            config = dataclasses.replace(config, tp=len(devices))
        mesh = None
        if config.tp is not None:
            tp = config.tp
            found = jax.devices()
            if tp < 1 or tp > len(found):
                raise ValueError(
                    f"tp={tp} needs 1..{len(found)} local devices; found "
                    f"{len(found)} x {found[0].platform} "
                    f"({found[0].device_kind})")
        config.validate(cfg)
        from repro.sharding import specs as _specs
        if config.tp is not None:
            mesh = _specs.serve_mesh(config.tp, devices)
        model = get_model(cfg)
        params = jax.jit(
            functools.partial(_init_params, model, config.seed,
                              config.compute_dtype, config.quantize_int8),
            out_shardings=_specs.replicated(mesh))()
        return cls(model, params, mesh=mesh, **config.engine_kwargs())

    # ------------------------------------------------------------ extras
    def _decode_extras(self) -> dict:
        return self._prefill_extras(self.batch_slots)

    def _prefill_extras(self, batch: int) -> dict:
        if self.cfg.cross_attn_every:
            return {"image_embeds": jnp.zeros(
                (batch, self.cfg.num_image_tokens, self.cfg.d_model),
                self.compute_dtype)}
        return {}

    def _prefill_fn(self) -> Callable:
        return _jitted_prefill(self.model, self.compute_dtype, self.s_max,
                               self.cache_dtype, self.mesh)

    def _chunk_fn(self, first: bool) -> Callable:
        return _jitted_prefill_chunk(self.model, self.compute_dtype,
                                     self.s_max, self.cache_dtype, first,
                                     self.prefill_attn_impl, self.mesh)

    def _chunk_paged_fn(self) -> Callable:
        return _jitted_prefill_chunk_paged(self.model, self.compute_dtype,
                                           self.paged_attn_impl, self.mesh)

    @property
    def prefill_trace_count(self) -> int:
        """Distinct (first, group K, chunk C) prefill shapes traced so far —
        bucketing keeps this O(ladder x group widths) under mixed-length
        traffic (the compile-count bound tests assert on it)."""
        return len(self._trace_keys)

    def _note_prefill_trace(self, first: bool, K: int, C: int):
        key = (first, K, C)
        if key in self._trace_keys:
            return
        self._trace_keys.add(key)
        if len(self._trace_keys) > self.max_prefill_traces:
            # bucketing was defeated (e.g. a pathological chunk ladder):
            # drop the compiled executables instead of leaking them forever
            log.warning("prefill trace count %d exceeded cap %d; clearing "
                        "chunk jit caches", len(self._trace_keys),
                        self.max_prefill_traces)
            for f in (True, False):
                self._chunk_fn(f).clear_cache()
            if self.incremental_splice:
                self._chunk_paged_fn().clear_cache()
            self._trace_keys = {key}
            self.prefill_trace_evictions += 1

    # ------------------------------------------------------------ sampling
    def _filter_logits(self, scaled):
        """Restrict temperature-scaled logits to the top-k highest and then
        the nucleus (smallest prefix of the remaining sorted distribution
        whose cumulative probability reaches top_p); masked entries go to
        -inf so ``jax.random.categorical`` can never draw them. Hot-path
        cost: top-k alone is one O(V) ``lax.top_k`` threshold; with top_p
        one full sort is shared by both filters (the kept set is a prefix
        of the sorted order, so a single scalar threshold per row masks the
        unsorted logits)."""
        V = scaled.shape[-1]
        neg = jnp.asarray(-jnp.inf, scaled.dtype)
        use_k = 0 < self.top_k < V
        if self.top_p >= 1.0:
            if not use_k:
                return scaled
            kth = jax.lax.top_k(scaled, self.top_k)[0][:, -1:]
            return jnp.where(scaled < kth, neg, scaled)
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]
        if use_k:
            srt = jnp.where(jnp.arange(V) < self.top_k, srt, neg)
        probs = jax.nn.softmax(srt, axis=-1)    # -inf rows carry zero mass
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < self.top_p         # minimal prefix reaching p
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        return jnp.where(scaled < thresh, neg, scaled)

    def _sample_rows(self, logits) -> np.ndarray:
        """logits: (B, 1, V_padded) -> (B,) sampled token per row."""
        row = logits[:, 0, : self.cfg.vocab_size]
        if self.temperature <= 0.0:
            return np.asarray(jnp.argmax(row, axis=-1), np.int32)
        key = jax.random.fold_in(self._key, self._events)
        self._events += 1
        toks = jax.random.categorical(
            key, self._filter_logits(row / self.temperature), axis=-1)
        return np.asarray(toks, np.int32)

    # ------------------------------------------------------------ paging
    @staticmethod
    def _rows_needed(prompt_len: int, gen_len: int) -> int:
        """Cache rows a request writes: prefill writes positions
        0..prompt_len-1; the gen_len-1 fed-back decode tokens write at
        prompt_len..prompt_len+gen_len-2 (the final sampled token is never
        written)."""
        return prompt_len + max(int(gen_len) - 1, 0)

    def _pages_for_rows(self, rows: int) -> int:
        """THE page-accounting rule — submit() validation and admit()
        reservation must agree on it or admission stops being infallible."""
        return -(-min(rows, self.capacity) // self.page_size)

    def _pages_needed(self, req: Request) -> int:
        # ``remaining`` (== gen_len for a fresh request) rather than gen_len:
        # a PREEMPTED request re-admits with its generated tokens folded into
        # the prompt, and charging full gen_len again would overcount its
        # reservation by len(tokens) — past s_max in the worst case
        return self._pages_for_rows(
            self._rows_needed(len(req.prompt), req.remaining))

    def _phys_rows(self, slots: List[int], floor: int = 0) -> np.ndarray:
        """(K, capacity) flattened pool-row index per logical cache row for a
        prefill group; rows beyond a slot's reservation map out of bounds and
        are dropped by the paged splice. ``floor`` additionally maps rows
        BELOW it out of bounds — a prefix-hit group's leading rows live in
        shared immutable pages aliased by other block tables, and the splice
        must never write them (copy-on-write's no-write half)."""
        ps = self.page_size
        C = self.capacity
        oob = self.num_pages * ps
        phys = np.full((len(slots), C), oob, np.int32)
        j = np.arange(C)
        for i, slot in enumerate(slots):
            pages = np.asarray(self.slot_pages[slot], np.int64)
            cov = min(C, len(pages) * ps)
            phys[i, :cov] = pages[j[:cov] // ps] * ps + j[:cov] % ps
        if floor > 0:
            phys[:, :min(floor, C)] = oob
        return phys

    def _prefix_gather_rows(self, plans: List[PrefixPlan], cached_len: int):
        """(K, s_max) flattened pool rows + validity mask covering each
        request's cached prefix: rows [0, cached_len) map through the hit's
        full pages and (for an unaligned hit) the partial COW SOURCE page —
        NOT the fresh page the block table holds in its place."""
        ps = self.page_size
        K = len(plans)
        phys = np.zeros((K, self.s_max), np.int32)
        ok = np.zeros((K, self.s_max), bool)
        j = np.arange(cached_len)
        for i, plan in enumerate(plans):
            pages = list(plan.shared_pages)
            if plan.partial is not None:
                pages.append(plan.partial[0])
            pages = np.asarray(pages, np.int64)
            phys[i, :cached_len] = pages[j // ps] * ps + j % ps
            ok[i, :cached_len] = True
        return phys, ok

    def resident_cache_bytes(self) -> int:
        """Device bytes held by the resident serving cache (the paged pool
        plus per-slot leaves; for dense, the full slots x s_max block).
        GLOBAL logical bytes — under a tp mesh the pool is spread over the
        shards; see per_shard_kv_bytes for the per-device footprint."""
        return int(sum(l.size * l.dtype.itemsize
                       for l in jax.tree.leaves(self.cache)))

    def per_shard_kv_bytes(self) -> int:
        """PER-DEVICE resident bytes of the cache's pool leaves (payload
        plus per-page scale metadata — every leaf the backend declared,
        not a hardcoded k/v tuple, so a single-leaf latent pool or a
        custom backend's extra leaves count too), via each leaf's
        committed sharding — the number the tp bench gates against the
        global pool. Orchestration metadata (block tables, positions) is
        excluded. Works unmeshed too (single-device sharding: per-shard ==
        global)."""
        if not isinstance(self.cache, dict):
            return 0
        total = 0
        for key, leaf in self.cache.items():
            if key in ("block_tables", "pos"):
                continue
            shard_shape = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard_shape)) * leaf.dtype.itemsize
        return total

    @property
    def free_pages(self) -> int:
        return self.allocator.free if self.paged else 0

    # ------------------------------------------------------------ lifecycle
    def submit(self, prompt, gen_len: int, priority: int = 0,
               deadline: Optional[float] = None) -> Request:
        """Enqueue a request; admission happens on the next step()/run().

        ``deadline``: optional absolute completion deadline (caller's
        clock). Consumed by an EDF scheduler (SchedPolicy.edf) to order
        same-priority admissions earliest-deadline-first; inert otherwise.

        Rejects up front anything that can never be served, so admission is
        infallible and a bad request cannot strand already-popped good ones:
        empty prompts (a zero-length prefill scan has undefined logits),
        negative gen_len, and requests whose written rows
        (prompt_len + gen_len - 1, see _rows_needed) exceed the per-slot
        bound — s_max for the dense cache (a write past s_max would be
        silently DROPPED by the scatter and attention would read
        never-written rows), the block-table span AND total pool capacity
        for the paged cache. Transient page shortage is NOT rejected here:
        admit() defers until enough pages free up."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be a 1-D token vector, got shape "
                             f"{prompt.shape}")
        if prompt.size == 0:
            raise ValueError("empty prompt: prefill needs at least one token")
        if int(gen_len) < 0:
            raise ValueError(f"gen_len must be >= 0, got {gen_len}")
        rows = self._rows_needed(len(prompt), gen_len)
        if len(prompt) > self.s_max or rows > self.s_max:
            raise ValueError(
                f"prompt_len {len(prompt)} + gen_len {gen_len} does not fit "
                f"s_max {self.s_max}; raise s_max or shorten the request")
        if self.paged:
            need = self._pages_for_rows(rows)
            if need > self.num_pages:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.num_pages}; grow num_pages")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      gen_len=int(gen_len), priority=priority)
        if deadline is not None:
            req.deadline = float(deadline)
        if (self.prefix_index is not None
                and getattr(self.scheduler, "prefix_aware", False)):
            # advisory ordering hint for a prefix-aware scheduler; does not
            # touch the LRU order and is re-resolved authoritatively at
            # admission (the index may have churned by then). Skipped for
            # the default FIFO scheduler — the hint would be dead weight
            # (an O(prompt) hash walk per submit with no consumer).
            req.prefix_hint = self.prefix_index.probe_len(prompt)
        self.requests[rid] = req
        self.metrics.on_submit(rid, len(req.prompt), priority)
        self.scheduler.submit(req)
        return req

    @property
    def free_slots(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_req) if r is None]

    @property
    def active(self) -> int:
        return sum(1 for r in self.slot_req if r is not None)

    def admit(self) -> int:
        """Admit waiting requests into free slots; returns #admitted.

        Requests admitted on the same tick are grouped by prompt length and
        prefilled JOINTLY — one dispatch (or one chunk stream) fills K slots
        (the batched-prefill fan-in; mixed lengths fall back to one group
        each). Isolation holds either way: the group's batch-K cache rows
        scatter into exactly the group's slots (dense) or pages (paged).

        In ``parallel`` mode admission only RESERVES (slot + pages) and
        enqueues a chunked :class:`_PrefillJob`; the prompt is ingested one
        bucketed chunk per tick by ``_prefill_tick`` so in-flight decodes
        are never stalled behind a long prompt. In ``scan`` mode the whole
        prompt is prefilled here in one teacher-forced scan dispatch.

        Paged admission PEEKS before popping: when the free-page list cannot
        cover the head request's worst case, admission stops — the request
        stays queued at the head (strict priority/FIFO, no skip-ahead that
        could starve long requests) until completions release pages. With
        the prefix cache enabled, admission first resolves the longest
        cached page-aligned prefix: hit pages alias into the block table
        (one allocator reference each) and only the remainder is freshly
        allocated — and when the free list is still short, LRU index-only
        pages are EVICTED before deferring, so caching never makes
        admission defer earlier than the uncached engine would."""
        pairs = []
        plans: Dict[int, Optional[PrefixPlan]] = {}
        for slot in self.free_slots:
            # lazily-cancelled heads are pruned inside Scheduler.peek — the
            # scheduler is the single source of truth for queue liveness
            req = self.scheduler.peek()
            while req is not None and self._shed_head(req):
                req = self.scheduler.peek()
            if req is None:
                break
            if self._defer_head(req):
                break
            plan = None
            if self.paged:
                defer_state = (req.rid, self.allocator.free,
                               self.prefix_index.version
                               if self.prefix_index is not None else 0)
                if defer_state == self._defer_state:
                    break       # same head, same pages, same index: still short
                shared: List[int] = []
                refs: List[int] = []
                if self.prefix_index is not None:
                    plan = self.prefix_index.lookup(req.prompt)
                    shared = list(plan.shared_pages)
                    # ref every page the plan READS — block-table aliases
                    # AND the partial COW source (gathered at seed time, not
                    # aliased) — so eviction for a later slot in this same
                    # loop can never free-and-reallocate them out from under
                    # the plan. The partial ref is dropped after the seed
                    # gather (_seed_prefix_job); the aliases at _finish.
                    refs = shared + ([plan.partial[0]] if plan.partial
                                     else [])
                    for pg in refs:
                        self.allocator.share(pg)
                need = self._pages_needed(req) - len(shared)
                fresh = self.allocator.alloc(need)
                if fresh is None and self.prefix_index is not None:
                    evicted = self.prefix_index.evict(
                        need - self.allocator.free)
                    if evicted:
                        self.metrics.on_prefix_evict(evicted)
                    fresh = self.allocator.alloc(need)
                if fresh is None and self.policy.preemption:
                    # pool pressure: pause strictly-lower-priority RUNNING
                    # slots (recompute-style re-queue) until the head fits
                    # or no eligible victim remains. Each preemption demotes
                    # the victim's registered prompt pages to index-only, so
                    # eviction re-runs before the retry — otherwise a cached
                    # victim frees nothing and admission deadlocks
                    while fresh is None and \
                            self._preempt_lowest(below=req.priority):
                        if (self.prefix_index is not None
                                and need > self.allocator.free):
                            evicted = self.prefix_index.evict(
                                need - self.allocator.free)
                            if evicted:
                                self.metrics.on_prefix_evict(evicted)
                        fresh = self.allocator.alloc(need)
                if fresh is None:
                    if refs:
                        self.allocator.release(refs)     # back to index-only
                    self.deferrals += 1
                    self._defer_state = (req.rid, self.allocator.free,
                                         self.prefix_index.version
                                         if self.prefix_index is not None
                                         else 0)
                    break
                if plan is not None:
                    self.metrics.on_prefix_lookup(
                        plan.cached_len, len(shared), plan.cow)
                pages = shared + fresh
                self.slot_pages[slot] = pages
                self._bt_host[slot, :] = -1
                self._bt_host[slot, :len(pages)] = pages
            self.scheduler.next_request()       # pop the peeked head
            req.state = RequestState.PREFILLING
            req.slot = slot
            self.slot_req[slot] = req
            self.metrics.on_admit(req.rid)
            self.metrics.on_prefill(req.rid, len(req.prompt))
            plans[slot] = plan
            pairs.append((slot, req))
        if self.paged and pairs:
            self.cache["block_tables"] = jnp.array(self._bt_host)
        # group by (prompt_len, cached_len): joint prefill needs equal tail
        # shapes AND an equal gather offset across the group's requests
        groups: Dict[tuple, list] = {}
        for slot, req in pairs:
            plan = plans[slot]
            cached = plan.cached_len if plan is not None else 0
            groups.setdefault((len(req.prompt), cached), []).append(
                (slot, req))
        for (plen, cached), group in groups.items():
            if self.prefill_mode == "scan":
                self._prefill_group_scan(group)
                continue
            # a tail of at least one position always runs: the splice needs
            # last-position logits to sample the first token, so a full-hit
            # prompt recomputes (only) its final position
            tail_start = min(cached, plen - 1)
            group_plans = ([plans[s] for s, _ in group]
                           if self.prefix_index is not None else None)
            job = _PrefillJob(
                slots=[s for s, _ in group],
                reqs=[r for _, r in group],
                prompts=np.stack([r.prompt[tail_start:] for _, r in group]),
                plan=chunk_plan(plen - tail_start, self.prefill_ladder),
                tail_start=tail_start,
                write_floor=(cached // self.page_size * self.page_size
                             if cached else 0),
                prefix_plans=group_plans)
            if cached:
                if self.incremental_splice:
                    # aliased full pages are read IN PLACE by the paged
                    # chunk attention — only a partial hit's COW page needs
                    # materialising, with the same pool scatter
                    self._cow_materialise_job(job, cached)
                else:
                    self._seed_prefix_job(job, cached)
            self._jobs.append(job)
        return len(pairs)

    # ------------------------------------------- admission control / preempt
    def _admission_pressure(self) -> bool:
        """True when the AVAILABLE-page fraction is below the policy's
        low-water mark — the signal admission control sheds/defers on.
        Available counts the free list PLUS the prefix index's reclaimable
        (index-only) pages: a warm cache parks most of the free list in
        evictable pages, and a raw free-list reading would shed load the
        pool could trivially serve. Always False for dense caches and with
        the default policy (low_water == 0)."""
        pol = self.policy
        if not (self.paged and pol.admission_low_water > 0.0):
            return False
        avail = self.allocator.free
        if self.prefix_index is not None:
            avail += self.prefix_index.reclaimable
        return avail < pol.admission_low_water * self.num_pages

    def _gated(self, req: Request) -> bool:
        pol = self.policy
        return (pol.admission_shed_priority is not None
                and req.priority >= pol.admission_shed_priority
                and self._admission_pressure())

    def _shed_head(self, req: Request) -> bool:
        """Admission control, shedding flavor: under pool pressure a queued
        head at/below the shed priority is popped and FAILED outright so the
        pool's remaining headroom serves the load the SLO protects. Returns
        True when the head was shed (the caller re-peeks)."""
        if not (self.policy.admission_shed and self._gated(req)):
            return False
        self.scheduler.next_request()
        req.state = RequestState.FAILED
        req.error = "shed: free pages below admission low water"
        self.metrics.on_shed(req.rid)
        self.metrics.on_aborted(req.rid)
        return True

    def _defer_head(self, req: Request) -> bool:
        """Admission control, deferring flavor (``admission_shed=False``):
        the gated head stays queued — strict order, no skip-ahead — until
        completions lift the pool back over the low-water mark."""
        return (not self.policy.admission_shed) and self._gated(req)

    def _preempt_lowest(self, below: int) -> bool:
        """Preempt the worst-priority RUNNING slot whose priority is
        STRICTLY greater (worse) than ``below``; among equals the most
        recently submitted loses (least generated work to recompute).
        Returns False when no eligible victim exists."""
        victim_slot, victim = None, None
        for slot, r in enumerate(self.slot_req):
            if r is None or r.state is not RequestState.RUNNING:
                continue
            if r.priority <= below:
                continue
            if victim is None or (r.priority, r.rid) > (victim.priority,
                                                        victim.rid):
                victim_slot, victim = slot, r
        if victim is None:
            return False
        self._preempt(victim_slot)
        return True

    def _preempt(self, slot: int):
        """Pause a RUNNING request recompute-style: release its slot and
        pages (K/V is reproducible — vLLM's recompute preemption), fold the
        tokens generated so far into the prompt, and re-queue it under its
        ORIGINAL arrival seq. On re-admission the folded prompt re-prefills
        (through the prefix cache when enabled, which typically still holds
        its pages) and the completion splice samples exactly the token the
        uninterrupted decode would have produced — greedy streams stay
        bit-identical across a preemption. The request record stays open:
        the pause surfaces as one long inter-token gap, which is precisely
        what preemption trades against higher-priority TTFT."""
        req = self.slot_req[slot]
        fresh = req.tokens[req.folded:]   # tokens[:folded] are already in
        if fresh:                         # the prompt from an earlier pause
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(fresh, np.int32)])
            req.folded = len(req.tokens)
        req.state = RequestState.QUEUED
        req.slot = None
        self.slot_req[slot] = None
        self.cur_token[slot, 0] = 0
        self.cache["pos"] = self.cache["pos"].at[slot].set(INACTIVE_POS)
        if self.paged:
            self.allocator.release(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self._bt_host[slot, :] = -1
            self.cache["block_tables"] = jnp.array(self._bt_host)
        self.metrics.on_preempt(req.rid)
        self._defer_state = None      # freed pages can change the outcome
        self.scheduler.submit(req)

    def _seed_prefix_job(self, job: _PrefillJob, cached_len: int):
        """Materialise a prefix-hit group's transient cache: gather the
        cached rows out of the shared pages (full pages AND the partial COW
        source) into a fresh dense batch-K cache positioned at the tail
        start. Every subsequent chunk is a continuation; the gather wall is
        charged to prefill so hit-path rates stay honest."""
        phys, ok = self._prefix_gather_rows(job.prefix_plans, cached_len)
        t0 = self.metrics.now()
        job.cache = self.backend.seed_prefix(self.model, self.s_max,
                                             self.cache_dtype)(
            self.cache, jnp.asarray(phys), jnp.asarray(ok),
            jnp.asarray(job.tail_start, jnp.int32))
        jax.block_until_ready(job.cache["k"])
        self.metrics.on_prefix_gather(self.metrics.now() - t0)
        # the gather has consumed the partial COW sources; drop the temporary
        # admission-time references (aliased full pages stay ref'd via
        # slot_pages until _finish)
        for plan in job.prefix_plans:
            if plan.partial is not None:
                self.allocator.release([plan.partial[0]])

    def _cow_materialise_job(self, job: _PrefillJob, cached_len: int):
        """Incremental-path half of a prefix hit: aliased FULL pages need no
        work at all (the paged chunk attention reads them through the block
        table), but a partial hit's rows ``[write_floor, cached_len)`` live
        in a shared SOURCE page while the block table holds a fresh page in
        that position — copy them across with the same flattened-pool
        scatter the per-chunk splice uses (the backend's ``copy_rows``;
        the int8 backend carries the source page's scale with the payload),
        then drop the admission-time source references. The copy wall is
        charged to prefill like the transient path's gather, so hit-path
        rates stay honest."""
        ps = self.page_size
        n = cached_len - job.write_floor          # partial rows to copy
        if n > 0:
            oob = self.num_pages * ps
            K = len(job.slots)
            src = np.zeros((K, ps), np.int64)
            dst = np.full((K, ps), oob, np.int64)
            offs = np.arange(ps)
            for i, (slot, plan) in enumerate(zip(job.slots,
                                                 job.prefix_plans)):
                if plan.partial is None:
                    continue
                fresh = self.slot_pages[slot][cached_len // ps]
                src[i, :n] = plan.partial[0] * ps + offs[:n]
                dst[i, :n] = fresh * ps + offs[:n]
            t0 = self.metrics.now()
            self.cache = self.backend.copy_rows(self.cache, jnp.asarray(src),
                                                jnp.asarray(dst))
            jax.block_until_ready(self.cache["k"])
            self.metrics.on_prefix_gather(self.metrics.now() - t0)
        for plan in job.prefix_plans:
            if plan.partial is not None:
                self.allocator.release([plan.partial[0]])

    def _prefill_group_scan(self, group):
        """Jointly prefill K same-length requests in ONE teacher-forced scan
        dispatch (the bit-exactness anchor path). Cannot fail on request
        contents: submit() already validated capacity and admit() already
        reserved pages, so popped requests are never stranded."""
        plen = len(group[0][1].prompt)
        prompts = jnp.asarray(np.stack([r.prompt for _, r in group]))  # (K,P)
        t0 = self.metrics.now()
        logits, rcache = self._prefill_fn()(
            self.params,
            {"tokens": prompts, **self._prefill_extras(len(group))})
        jax.block_until_ready(logits)
        self.metrics.on_prefill_chunk(len(group) * plen,
                                      self.metrics.now() - t0)
        self._splice_and_start([s for s, _ in group], [r for _, r in group],
                               rcache, logits)

    # ------------------------------------------------- chunked prefill
    def _prefill_tick(self) -> int:
        """Ingest at most ``prefill_chunk_tokens`` prompt positions of
        queued prefill work — the engine's head-of-line bound: between any
        two decode ticks the prefill interleave is capped by the chunk
        budget, whatever the longest queued prompt is. Bucketed ladder
        chunks that fit the remaining budget run back-to-back (a 12-token
        prompt under a 64 budget still completes in one tick as 8 + 4), in
        strict job-FIFO order — or deficit-round-robin across jobs when
        ``policy.drr`` is set (same budget, fairly split; see
        ``_prefill_tick_drr``). Returns prompt positions ingested.

        With ``incremental_splice`` the chunk dispatch writes its K/V rows
        straight into the group's reserved pages and attends them through
        the block table (``make_prefill_chunk_paged``) — no transient
        request cache exists and completion only flips the group's ``pos``.

        A chunk dispatch that RAISES aborts its whole job through
        :meth:`release_job` (slots freed, pages and aliased prefix
        refcounts released, requests marked FAILED) and the tick moves on —
        an errored prompt can neither strand pages until process exit nor
        wedge the queue behind it."""
        budget = self.prefill_chunk_tokens
        if self.policy.drr and len(self._jobs) > 1:
            ingested = self._prefill_tick_drr(budget)
        else:
            # default: strict job-FIFO (the pre-policy behavior, bit-exact)
            ingested = 0
            while self._jobs and budget > 0:
                job = self._jobs[0]
                if job.plan[job.idx] > budget:
                    break
                got = self._run_chunk(job)
                if got is None:     # dispatch raised; job released/pool reset
                    continue
                budget -= got
                ingested += got
        self.max_prefill_tokens_per_tick = max(
            self.max_prefill_tokens_per_tick, ingested)
        return ingested

    def _prefill_tick_drr(self, budget: int) -> int:
        """Deficit round-robin across pending prefill jobs: every job earns
        a quantum of chunk-token credit per tick (carry capped at 2x the
        tick budget) and spends it in rotation, so K concurrent prompts
        interleave at chunk granularity instead of the head job draining
        the whole budget every tick until it completes. The rotation start
        advances each tick so leftover budget is not always offered to the
        same job first. The per-tick budget (head-of-line bound) is
        unchanged — DRR only redistributes it."""
        ingested = 0
        q = self.policy.drr_quantum or max(1, budget // len(self._jobs))
        for job in self._jobs:
            job.deficit = min(job.deficit + q, 2 * self.prefill_chunk_tokens)
        self._drr_cursor += 1
        while budget > 0 and self._jobs:
            n = len(self._jobs)
            order = [self._jobs[(self._drr_cursor + k) % n] for k in range(n)]
            ran = False
            for job in order:
                if budget <= 0 or job not in self._jobs:
                    continue        # completed/released by an earlier chunk
                C = job.plan[job.idx]
                if C > budget or C > job.deficit:
                    continue
                got = self._run_chunk(job)
                ran = True
                if got is None:     # failure path mutated the job list:
                    break           # rebuild the rotation from live state
                job.deficit -= got
                budget -= got
                ingested += got
            if not ran:
                break               # nobody could spend: credit accrues
        return ingested

    def _run_chunk(self, job: _PrefillJob) -> Optional[int]:
        """Dispatch ``job``'s next bucketed chunk; on the final chunk,
        splice-and-start the group. Returns the chunk length ingested, or
        None when the dispatch raised — the job was released (or the whole
        poisoned pool reset) and the caller must re-read the job list."""
        C = job.plan[job.idx]
        K = len(job.slots)
        toks = jnp.asarray(job.prompts[:, job.filled:job.filled + C])
        t0 = self.metrics.now()
        try:
            if self.incremental_splice:
                self._note_prefill_trace(False, K, C)
                batch = {
                    "tokens": toks,
                    "bt": jnp.asarray(self._bt_host[job.slots]),
                    "start": jnp.asarray(job.tail_start + job.filled,
                                         jnp.int32),
                    "floor": jnp.asarray(job.write_floor, jnp.int32),
                    **self._prefill_extras(K)}
                logits, self.cache = self._chunk_paged_fn()(
                    self.params, self.cache, batch)
            else:
                # a prefix-seeded job already has its transient cache
                # (gathered from shared pages): every chunk continues
                first = job.cache is None
                self._note_prefill_trace(first, K, C)
                batch = {"tokens": toks, **self._prefill_extras(K)}
                if first:
                    logits, job.cache = self._chunk_fn(True)(self.params,
                                                             batch)
                else:
                    logits, job.cache = self._chunk_fn(False)(
                        self.params, job.cache, batch)
            jax.block_until_ready(logits)
        except Exception as err:  # noqa: BLE001 — released, not resumed
            log.exception("prefill chunk failed for rids %s; releasing "
                          "the job", [r.rid for r in job.reqs])
            self.prefill_failures += 1
            # the incremental dispatch DONATES the resident cache: a
            # failure at EXECUTION time (not trace time) may have
            # consumed or poisoned the shared pools every other live
            # slot reads. Check BEFORE release_job — its _finish writes
            # into the cache and would raise on dead buffers — and fail
            # over to a fresh pool instead of crashing the next tick.
            if self.incremental_splice and not self._cache_healthy():
                self._reset_poisoned_cache(err)
            else:
                self.release_job(job, error=err)
            return None
        self.metrics.on_prefill_chunk(K * C, self.metrics.now() - t0)
        self.max_transient_cache_bytes = max(
            self.max_transient_cache_bytes, self.transient_cache_bytes())
        job.idx += 1
        job.filled += C
        if job.idx == len(job.plan):
            self._jobs.remove(job)
            self._splice_and_start(
                job.slots, job.reqs,
                None if self.incremental_splice else job.cache, logits,
                write_floor=job.write_floor,
                prefix_plans=job.prefix_plans)
        return C

    def _splice_and_start(self, slot_ids, reqs, rcache, logits, *,
                          write_floor: int = 0, prefix_plans=None):
        """Complete a group prefill: land its K/V in the resident cache,
        sample each request's first token from the prefill logits, and flip
        the group to RUNNING.

        ``rcache`` is the group's transient request cache (dense row scatter
        or paged page scatter — other slots untouched bit-for-bit), or None
        on the INCREMENTAL path, where every chunk already spliced its rows
        into the group's pages and completion only flips the group's
        ``pos`` from the INACTIVE sentinel to prompt_len.

        Prefix caching rides the same scatter: rows below ``write_floor``
        (aliased immutable full pages) are dropped, while a partial hit's
        gathered rows land in the FRESH page standing in for the shared
        source — the copy-on-write copy costs no extra device pass. After
        the splice the group's freshly computed prompt pages (now complete
        and never written again) register in the prefix index."""
        slots = jnp.asarray(np.array(slot_ids, np.int32))
        if rcache is None:
            plens = jnp.asarray([len(r.prompt) for r in reqs], jnp.int32)
            self.cache["pos"] = self.cache["pos"].at[slots].set(plens)
        elif self.paged:
            self.cache = self.backend.insert_rows(
                self.cache, rcache, slots,
                jnp.asarray(self._phys_rows(slot_ids, write_floor)))
        else:
            self.cache = self.backend.insert_rows(self.cache, rcache, slots)
        if self.prefix_index is not None and prefix_plans is not None:
            for slot, req, plan in zip(slot_ids, reqs, prefix_plans):
                self.prefix_index.register(plan, self.slot_pages[slot],
                                           len(req.prompt))
        toks = self._sample_rows(logits)
        for i, (slot, req) in enumerate(zip(slot_ids, reqs)):
            req.state = RequestState.RUNNING
            if req.rid in self._cancel_at_splice:   # grouped mid-prefill
                self._cancel_at_splice.discard(req.rid)   # cancel lands here
                self._finish(slot, RequestState.CANCELLED)
                continue
            if req.gen_len <= 0:                 # nothing to generate
                self._finish(slot)
                continue
            # a request resumed after preemption already streamed tokens:
            # this splice's sample is its NEXT token, not its first —
            # on_first_token is idempotent and would silently drop it
            resumed = bool(req.tokens)
            req.tokens.append(int(toks[i]))
            self.cur_token[slot, 0] = int(toks[i])
            if resumed:
                self.metrics.on_token(req.rid)
            else:
                self.metrics.on_first_token(req.rid)
            if req.done:
                self._finish(slot)

    def _finish(self, slot: int, state: RequestState = RequestState.DONE):
        """Retire a slot: park its cache position at the INACTIVE_POS
        sentinel (decode drops its writes from now on — freed rows stay
        bit-stable), zero its feedback token, and return its pages to the
        free list. Idempotent: a second call is a no-op. ``state`` records
        WHY the slot retired (DONE / FAILED / CANCELLED) — the resource
        reclamation is identical."""
        req = self.slot_req[slot]
        if req is None:
            return
        req.state = state
        if state is RequestState.DONE:
            self.metrics.on_done(req.rid)
        else:                       # FAILED/CANCELLED: finalized, not served
            self.metrics.on_aborted(req.rid)
        self.slot_req[slot] = None
        self.cur_token[slot, 0] = 0
        self.cache["pos"] = self.cache["pos"].at[slot].set(INACTIVE_POS)
        if self.paged:
            self.allocator.release(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self._bt_host[slot, :] = -1
            self.cache["block_tables"] = jnp.array(self._bt_host)

    def _cache_healthy(self) -> bool:
        """True when every resident-cache buffer is live and readable. A
        failed donated dispatch leaves either deleted input buffers (the
        exception fired mid-execution) or error-poisoned output buffers
        (async backends surface execution errors on first access)."""
        try:
            jax.block_until_ready(self.cache["k"])
        except Exception:  # noqa: BLE001 — any access error means poisoned
            return False
        return not any(getattr(leaf, "is_deleted", lambda: False)()
                       for leaf in jax.tree.leaves(self.cache))

    def _reset_poisoned_cache(self, error):
        """Scorched-earth failover after a donated dispatch destroyed the
        shared paged cache: every in-flight request is FAILED (their K/V
        lived in the poisoned pools — there is nothing to resume), the
        allocator and prefix index rebuild from scratch (index entries
        would otherwise point at zeroed pages), and a FRESH pool cache is
        installed so queued and future requests keep being served. Pure
        host-side bookkeeping plus one cache re-init; never touches the
        poisoned buffers."""
        log.error("resident paged cache lost to a failed donated dispatch; "
                  "failing %d in-flight request(s) and rebuilding the pool",
                  self.active)
        for job in list(self._jobs):        # PREFILLING jobs not yet failed
            self._jobs.remove(job)
            job.cache = None
        msg = f"cache lost to failed dispatch: {error!r}"
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            req.state = RequestState.FAILED
            req.error = msg
            self.metrics.on_aborted(req.rid)
            self.slot_req[slot] = None
            self.cur_token[slot, 0] = 0
        self._cancel_at_splice.clear()
        self.allocator = PageAllocator(self.num_pages)
        if self.prefix_index is not None:
            self.prefix_index = PrefixIndex(self.allocator, self.page_size)
        self.slot_pages = [[] for _ in range(self.batch_slots)]
        self._bt_host[:] = -1
        self._defer_state = None
        self.cache = self.backend.init_cache(
            self.model, self.batch_slots, self.s_max, self.cache_dtype)

    def release_job(self, job: _PrefillJob, error=None,
                    state: RequestState = RequestState.FAILED):
        """Abort an in-flight prefill job and reclaim EVERYTHING it holds:
        the group's slots, reserved pages (including aliased prefix-page
        refcounts — released through the same ``_finish`` path completion
        uses), the transient request cache, and the feedback tokens.
        Invoked by ``_prefill_tick`` when a chunk dispatch raises and by
        :meth:`cancel` — before this path existed, an errored or cancelled
        mid-prefill job held its pages until process exit."""
        if job in self._jobs:
            self._jobs.remove(job)
        job.cache = None
        msg = "cancelled" if state is RequestState.CANCELLED else repr(error)
        for slot, req in zip(job.slots, job.reqs):
            req.error = msg
            self._cancel_at_splice.discard(req.rid)
            self._finish(slot, state)

    def cancel(self, rid: int) -> bool:
        """Cancel a request; returns True if it was still live. QUEUED
        requests are marked and skipped at the next admission (lazy heap
        removal); a PREFILLING request aborts immediately when it is its
        job's only member (``release_job``) and at group completion
        otherwise (the splice retires its slot without sampling — the
        group's batch shape cannot change mid-stream); RUNNING requests
        retire their slot on the spot. Either way every reserved page and
        aliased prefix refcount is released."""
        req = self.requests.get(rid)
        if req is None or req.state in (RequestState.DONE,
                                        RequestState.FAILED,
                                        RequestState.CANCELLED):
            return False
        if req.state is RequestState.QUEUED:
            req.state = RequestState.CANCELLED
            req.error = "cancelled"
            self.metrics.on_aborted(rid)
            return True
        if req.state is RequestState.PREFILLING:
            job = next((j for j in self._jobs if req in j.reqs), None)
            if job is None:                  # no chunk job (scan-mode window)
                self._finish(req.slot, RequestState.CANCELLED)
                req.error = "cancelled"
                return True
            if len(job.reqs) == 1:
                self.release_job(job, state=RequestState.CANCELLED)
            else:
                req.error = "cancelled"
                self._cancel_at_splice.add(rid)
            return True
        self._finish(req.slot, RequestState.CANCELLED)   # RUNNING
        req.error = "cancelled"
        return True

    def transient_cache_bytes(self) -> int:
        """Device bytes held RIGHT NOW by in-flight prefill jobs' transient
        request caches. On the incremental-splice path this is 0 by
        construction — chunks write straight into the resident pools and
        only one chunk's activations are ever live — which is the
        acceptance bound the bench records (``max_transient_cache_bytes``
        tracks the high-water mark across a run)."""
        total = 0
        for job in self._jobs:
            if job.cache is not None:
                total += int(sum(l.size * l.dtype.itemsize
                                 for l in jax.tree.leaves(job.cache)))
        return total

    def assert_page_invariants(self):
        """Walk the allocator / block-table / prefix-index bookkeeping and
        raise on any violated invariant: no page simultaneously free and
        referenced, every live block-table or index page holds >= 1
        reference, and nothing leaks (free + held partitions the pool).
        Host-side only — tests call this per tick; release_job keeps it
        true through failures and cancellations."""
        if not self.paged:
            return
        free = set(self.allocator._free)
        held = self.allocator.held
        assert not (free & held), f"pages both free and referenced: {free & held}"
        assert free | held == set(range(self.num_pages)), "page leaked"
        live = {pg for pages in self.slot_pages for pg in pages}
        assert not (free & live), "page both free and in a live block table"
        for pg in live:
            assert self.allocator.refcount(pg) >= 1, f"live page {pg} unref'd"
        if self.prefix_index is not None:
            idx = set(self.prefix_index.pages)
            assert not (free & idx), "page both free and in the prefix index"
            for pg in idx:
                assert self.allocator.refcount(pg) >= 1, \
                    f"indexed page {pg} unref'd"
        # per-page metadata invariants (int8: scale tables well-formed)
        self.backend.check_page_meta(self.cache, self.num_pages)

    @property
    def running(self) -> int:
        """Slots actively decoding (excludes slots still being prefilled)."""
        return sum(1 for r in self.slot_req
                   if r is not None and r.state == RequestState.RUNNING)

    def step(self) -> int:
        """One engine tick: admit waiting requests, ingest at most one
        prefill-chunk BUDGET of prompt work (the interleave that bounds
        decode inter-token latency under long-prompt ingestion), then one
        decode tick for every RUNNING slot; returns #active after the tick.

        With ``policy.max_consecutive_prefill_ticks`` set, the decode-
        starvation guard skips the prefill interleave for one tick after N
        consecutive ticks in which prefill dispatched work while slots were
        decoding — under sustained admission pressure the per-tick chunk
        budget alone bounds each tick's prefill share, but nothing else
        guarantees decode ever gets a prefill-free tick."""
        self.admit()
        pol = self.policy
        if (pol.max_consecutive_prefill_ticks > 0 and self._jobs
                and self.running > 0
                and self._consec_prefill_ticks
                >= pol.max_consecutive_prefill_ticks):
            self._consec_prefill_ticks = 0
            self.metrics.on_starvation_skip()
        else:
            ingested = self._prefill_tick()
            if ingested > 0 and self.running > 0:
                self._consec_prefill_ticks += 1
            else:
                self._consec_prefill_ticks = 0
        if self.running:
            # jnp.array, not asarray: on the CPU asarray may alias the host
            # buffer, which is rewritten below and by admit() while a
            # dispatch can still read it (the block tables likewise)
            batch = {"token": jnp.array(self.cur_token),
                     **self._decode_extras()}
            logits, self.cache = self._decode(self.params, self.cache, batch)
            self.metrics.on_decode_step()
            nxt = self._sample_rows(logits)
            for slot, req in enumerate(self.slot_req):
                if req is None or req.state != RequestState.RUNNING:
                    continue
                req.tokens.append(int(nxt[slot]))
                self.cur_token[slot, 0] = int(nxt[slot])
                self.metrics.on_token(req.rid)
                if req.done:
                    self._finish(slot)
        self.admit()        # refill freed slots/pages on the SAME tick
        return self.active

    def drain_completed(self) -> List[Request]:
        """Remove and return finished requests (the engine otherwise retains
        every request — prompt and token list — for its lifetime; a
        long-running deployment should drain periodically). Metric records
        are kept so summary() percentiles stay complete."""
        done = [r for r in self.requests.values()
                if r.state in (RequestState.DONE, RequestState.FAILED,
                               RequestState.CANCELLED)]
        for r in done:
            del self.requests[r.rid]
        return done

    def run(self) -> dict:
        """Serve until queue and slots drain; returns the metrics summary."""
        self.metrics.on_start()
        while self.scheduler.waiting or self.active:
            self.step()
        self.metrics.on_stop()
        return self.metrics.summary()
