"""Logical-axis sharding: model code names *logical* axes; a rule table maps
them to mesh axes. Keeps model definitions mesh-agnostic (single-pod, multi-pod,
pipeline) — the same pattern MaxText/flax-linen use, reimplemented standalone.

Usage::

    with use_mesh(mesh, DEFAULT_RULES):
        y = shard(x, "batch", "seq", None)   # inside jit: with_sharding_constraint

Outside a mesh context ``shard`` is the identity, so models run untouched in
single-device tests.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Logical = Union[str, None, Tuple[str, ...]]

# logical axis -> mesh axis (or tuple of mesh axes). Entries whose mesh axes
# are absent from the active mesh are dropped at resolution time.
DEFAULT_RULES: Tuple[Tuple[str, Logical], ...] = (
    ("batch", ("pod", "data")),      # data parallel over pod x data
    ("seq_sp", "model"),             # sequence parallelism at layer boundaries
    ("heads", "model"),              # tensor parallel attention heads
    ("kv_heads", "model"),
    ("d_ff", "model"),               # tensor parallel MLP
    ("vocab", "model"),
    ("expert", "model"),             # expert parallel
    ("fsdp", "data"),                # ZeRO-3 weight sharding
    ("kv_seq", None),                # KV-cache sequence dim (kept unsharded)
    ("stage", "pod"),                # pipeline axis (when PP enabled)
)

# Rules for pure-DP pods (default production config): identical to DEFAULT_RULES.
# Rules for pipeline-parallel pods: batch only over "data", stage over "pod".
PIPELINE_RULES: Tuple[Tuple[str, Logical], ...] = tuple(
    ("batch", "data") if k == "batch" else (k, v) for k, v in DEFAULT_RULES
)

# Serving rules: weights sharded over the model axis ONLY (replicated across
# data) — no optimizer state exists at serve time, so ZeRO-3 'fsdp' sharding
# buys nothing and costs a full per-layer weight all-gather every step; with
# model-only sharding each chip streams its resident 1/TP weight slice.
# (hillclimb A iteration 1 — EXPERIMENTS.md §Perf.)
SERVE_RULES: Tuple[Tuple[str, Logical], ...] = tuple(
    (k, None) if k == "fsdp" else (k, v) for k, v in DEFAULT_RULES
)

# Tensor-parallel SERVING rules (the serve engine's mesh trace context):
# every logical axis resolves to None, so each existing with_sharding_
# constraint in model code becomes a replicate — the entire decode/prefill
# dataflow outside the head-sharded attention core stays replicated. That is
# deliberate, not a placeholder: replicated projections + per-head-
# independent attention + an all-gather of head outputs before the output
# projection make a tp>1 tick BITWISE identical to tp=1 (no float sum is
# ever split across shards), which is the anchor the tp equivalence tests
# gate on. The KV pool is the one sharded resident — its placement goes
# through TP_POOL_RULES below, and the kernel's head slicing through
# shard_map (see kernels/paged_attention.py::paged_attention_head_sharded).
TP_SERVE_RULES: Tuple[Tuple[str, Logical], ...] = tuple(
    (k, None) for k, _ in DEFAULT_RULES
)

# Rules used ONLY to place the paged KV pool: the kv-head axis shards over
# 'model'; page geometry (page ids, page rows) is shard-invariant so block
# tables and the host-side allocator/prefix index stay replicated.
TP_POOL_RULES: Tuple[Tuple[str, Logical], ...] = (("kv_heads", "model"),)

# Logical axes of one paged K/V pool leaf (L, num_pages, page_size, KV, hd):
# only the kv-head axis is shardable — every page holds all of a shard's
# kv-head slice for its rows, so page indices mean the same thing on every
# shard and the block tables replicate untouched.
KV_POOL_AXES: Tuple[Logical, ...] = (None, None, None, "kv_heads", None)


class _Ctx:
    def __init__(self, mesh: Optional[Mesh], rules):
        self.mesh = mesh
        self.rules = dict(rules) if rules else {}


_CTX: contextvars.ContextVar[_Ctx] = contextvars.ContextVar(
    "shard_ctx", default=_Ctx(None, DEFAULT_RULES)
)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules=DEFAULT_RULES):
    token = _CTX.set(_Ctx(mesh, rules))
    try:
        # NamedShardings built here carry the mesh explicitly, so no global
        # jax mesh context is required; `with mesh:` also works but is not
        # needed for with_sharding_constraint/jit in_shardings.
        yield mesh
    finally:
        _CTX.reset(token)


def active_mesh() -> Optional[Mesh]:
    return _CTX.get().mesh


def _resolve_one(logical: Logical, mesh: Mesh) -> Logical:
    if logical is None:
        return None
    rules = _CTX.get().rules
    mapped = rules.get(logical, None) if isinstance(logical, str) else logical
    if mapped is None:
        return None
    if isinstance(mapped, str):
        mapped = (mapped,)
    present = tuple(a for a in mapped if a in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def resolve(*logical_axes: Logical) -> P:
    """Resolve logical axes to a PartitionSpec under the active mesh."""
    mesh = active_mesh()
    if mesh is None:
        return P(*([None] * len(logical_axes)))
    return P(*(_resolve_one(a, mesh) for a in logical_axes))


def named_sharding(*logical_axes: Logical) -> Optional[NamedSharding]:
    mesh = active_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(*logical_axes))


def axis_size(logical: Logical) -> int:
    """Product of mesh-axis sizes a logical axis resolves to (1 if unmapped)."""
    mesh = active_mesh()
    if mesh is None:
        return 1
    resolved = _resolve_one(logical, mesh)
    if resolved is None:
        return 1
    if isinstance(resolved, str):
        resolved = (resolved,)
    size = 1
    for a in resolved:
        size *= mesh.shape[a]
    return size


def _fit_axes(shape, logical_axes):
    """Drop logical axes whose resolved mesh size does not divide the dim —
    the shape-aware fallback (replicate) for non-divisible dims (e.g. kv=5
    heads on a 16-way model axis, or batch=1 long-context cells)."""
    out = []
    for dim, ax in zip(shape, logical_axes):
        out.append(ax if (ax is not None and dim % max(axis_size(ax), 1) == 0
                          and axis_size(ax) > 1) else None)
    return tuple(out)


def shard(x, *logical_axes: Logical):
    """with_sharding_constraint against the active mesh (identity if none).
    Non-divisible axes are dropped (replicated) rather than erroring."""
    mesh = active_mesh()
    if mesh is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(
            f"rank mismatch: array rank {x.ndim} vs {len(logical_axes)} logical axes"
        )
    fitted = _fit_axes(x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(x, named_sharding(*fitted))


def sharding_for(shape, logical_axes) -> Optional[NamedSharding]:
    """Shape-aware ``named_sharding`` for ONE array: logical axes whose mesh
    size does not divide the dim are dropped (replicated). None if no mesh."""
    mesh = active_mesh()
    if mesh is None:
        return None
    return named_sharding(*_fit_axes(shape, logical_axes))


def replicate(x):
    """Constrain x fully replicated under the active mesh (identity if none).

    The tensor-parallel serve path calls this on the head-sharded attention
    output right BEFORE the output projection: it is the one all-gather of
    the tp decode tick, and putting it before (not after, as a psum of
    partial projections) keeps the wo contraction un-split and the tick
    bitwise equal to tp=1."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim))))


# Mesh axis the serve engine uses for tensor parallelism. Checked directly
# against mesh.axis_names (not through the rules table) because the tp serve
# trace context deliberately maps every logical axis to None — the kernel's
# head slicing happens inside shard_map, not via GSPMD constraints.
TP_AXIS = "model"


def head_shard_axis(num_heads: int, num_kv_heads: int):
    """Resolve the head-sharding decision for a paged-attention call site.

    Returns ``(mesh, axis_name)`` when the active mesh has a >1-sized
    ``TP_AXIS`` that divides BOTH head counts (each shard then owns whole
    GQA groups: kv head ``k`` and its query heads ``k*G..k*G+G-1`` land on
    the same shard, so the kernel's ``h // G`` pool indexing stays local).
    Returns ``(None, None)`` otherwise — callers fall back to the exact
    single-device dispatch, keeping non-divisible configs correct."""
    mesh = active_mesh()
    if mesh is None or TP_AXIS not in mesh.axis_names:
        return None, None
    tp = mesh.shape[TP_AXIS]
    if tp <= 1 or num_kv_heads % tp or num_heads % tp:
        return None, None
    return mesh, TP_AXIS


def latent_head_shard_axis(num_heads: int):
    """``head_shard_axis`` for the MLA latent path: the latent pool has no
    kv-head axis (every head reads the same compressed rows), so only the
    query-head count needs to divide the mesh. Returns ``(mesh, axis_name)``
    when the active mesh has a >1-sized ``TP_AXIS`` dividing ``num_heads``,
    else ``(None, None)`` (callers fall back to the exact replicated
    dispatch)."""
    mesh = active_mesh()
    if mesh is None or TP_AXIS not in mesh.axis_names:
        return None, None
    tp = mesh.shape[TP_AXIS]
    if tp <= 1 or num_heads % tp:
        return None, None
    return mesh, TP_AXIS


def serve_trace(mesh: Optional[Mesh], fn):
    """Wrap a step function so it TRACES inside the tensor-parallel serving
    mesh context (identity when mesh is None): the with-block runs at trace
    time, so every shard/replicate/head_shard_axis call in model code
    resolves against this mesh. :data:`TP_SERVE_RULES` maps every logical
    axis to None — the whole dataflow stays replicated except the cache
    pool (committed sharded by the KV backend) and the attention cores'
    shard_map wrappers; that split is what keeps tp>1 ticks bitwise equal
    to tp=1."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with use_mesh(mesh, TP_SERVE_RULES):
            return fn(*args, **kwargs)
    return wrapped


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """THE mesh constructor: every axis typed ``Auto``. ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which sharding-in-types rejects
    this code's ``with_sharding_constraint`` calls and mixed-sharding
    updates; with ``Auto`` axes GSPMD propagates shardings as the logical
    rules above assume. ``devices`` (optional) pins the mesh to a device
    list — e.g. the devices of a described topology for a compile-only
    rehearsal."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def serve_mesh(tp: int, devices=None) -> Mesh:
    """Build the canonical 1-axis serving mesh over ``devices`` (default:
    the first ``tp`` local devices). The axis is named :data:`TP_AXIS`;
    keeping the construction here means callers (notably the serve engine)
    never spell the axis name themselves — the backend seam and these
    helpers own every mesh internal."""
    devices = list(devices) if devices is not None else jax.devices()[:tp]
    return make_mesh((tp,), (TP_AXIS,), devices=devices)


def replicated(mesh: Optional[Mesh]):
    """The fully replicated sharding on ``mesh`` (None without a mesh: the
    default placement), e.g. as a jit's ``out_shardings``."""
    return None if mesh is None else NamedSharding(mesh, P())


def replicate_params(params, mesh: Optional[Mesh]):
    """Place a parameter pytree fully replicated on ``mesh`` (identity when
    mesh is None). Replicated weights keep every contraction — in particular
    the output projection after the attention all-gather — un-split across
    shards, which is what makes a tp>1 serve tick bitwise equal to tp=1."""
    if mesh is None:
        return params
    return jax.device_put(params, replicated(mesh))


def _is_logical_leaf(v):
    return isinstance(v, tuple) and all(
        isinstance(a, (str, type(None), tuple)) for a in v)


def spec_tree(tree_of_logical):
    """Map a pytree of logical-axis tuples to NamedShardings (for in_shardings)."""
    return jax.tree.map(lambda ax: named_sharding(*ax), tree_of_logical,
                        is_leaf=_is_logical_leaf)


def shardings_for(tree_of_logical, sds_tree):
    """Shape-aware spec_tree: builds NamedShardings per leaf, dropping logical
    axes whose mesh size does not divide that leaf's dim (pjit *arguments*
    require exact divisibility, unlike internal constraints)."""
    flat_log, _ = jax.tree.flatten(tree_of_logical, is_leaf=_is_logical_leaf)
    flat_sds, treedef = jax.tree.flatten(sds_tree)
    assert len(flat_log) == len(flat_sds), (len(flat_log), len(flat_sds))
    out = []
    for ax, s in zip(flat_log, flat_sds):
        fitted = _fit_axes(s.shape, ax)
        out.append(named_sharding(*fitted))
    return jax.tree.unflatten(treedef, out)
