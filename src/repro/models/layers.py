"""Shared model layers: norms, RoPE, GQA attention (einsum / chunked / pallas),
gated MLP, and the grouped-capacity MoE layer with expert parallelism.

All layers are pure functions over pytrees of parameters. Initializers return
param trees whose leaves carry a ``.logical`` sharding hint consumed by
``sharding.specs.spec_tree`` via the companion ``*_logical`` functions.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.sharding.specs import shard


# ---------------------------------------------------------------- numerics
# canonical definition lives in kernels/ref.py (the dependency-free numerics
# layer); re-exported here because every model-side masking site uses it
from repro.kernels.ref import mask_value  # noqa: E402  (re-export)


def cast_compute(x, dtype):
    return x.astype(dtype) if dtype is not None else x


def rmsnorm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params["bias"])


def norm_init(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def norm_logical(kind: str):
    if kind == "rmsnorm":
        return {"scale": (None,)}
    return {"scale": (None,), "bias": (None,)}


# ---------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    sin = sin[..., :, None, :]  # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- dense init
def scaled_normal(key, shape, std, dtype=jnp.float32):
    """``std`` times a standard normal draw, with the same bits inside a
    jit as eagerly: the barrier stops XLA from folding ``std`` into the
    sampler's own constant factor, which rounds differently."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, dtype)) * std


def _dense(key, shape, scale_dim=None, dtype=jnp.float32):
    fan_in = scale_dim if scale_dim is not None else shape[0]
    return scaled_normal(key, shape, 1.0 / math.sqrt(fan_in), dtype)


# Parameter leaves every step casts to the activation dtype where it uses
# them (``params[name].astype(x.dtype)``): storing them in that dtype gives
# the same numbers at half the bytes in bf16. Every other leaf (norm
# scales, the MoE router, recurrent-state parameters) keeps its dtype: the
# router and the RWKV decay terms are used in float32.
CAST_ON_USE = frozenset({
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "wkv_a", "wkv_b",
    "w_up", "w_gate", "w_down", "b_up", "b_down", "w_in", "w_out", "table"})


def cast_on_use(params, dtype):
    """Cast the :data:`CAST_ON_USE` leaves (and the untied unembedding) of
    a parameter tree to ``dtype``; every other leaf is returned as is."""
    def cast(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if keys[-1] in CAST_ON_USE or keys[-2:] == ["unembed", "w"]:
            return leaf.astype(dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(cast, params)


# ---------------------------------------------------------------- attention
@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    window: int = 0          # 0 = full causal
    rope_theta: float = 10000.0
    causal: bool = True


def attn_init(key, dims: AttnDims):
    ks = jax.random.split(key, 4)
    D, H, KV, hd = dims.d_model, dims.num_heads, dims.num_kv_heads, dims.head_dim
    p = {
        "wq": _dense(ks[0], (D, H * hd)),
        "wk": _dense(ks[1], (D, KV * hd)),
        "wv": _dense(ks[2], (D, KV * hd)),
        "wo": _dense(ks[3], (H * hd, D), scale_dim=H * hd),
    }
    if dims.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    return p


def attn_logical(dims: AttnDims):
    p = {
        "wq": ("fsdp", "heads"),
        "wk": ("fsdp", "kv_heads"),
        "wv": ("fsdp", "kv_heads"),
        "wo": ("heads", "fsdp"),
    }
    if dims.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)})
    return p


def _qkv(params, x, dims: AttnDims, positions):
    B, S, _ = x.shape
    H, KV, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if dims.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if dims.rope_theta > 0:
        q = apply_rope(q, positions, dims.rope_theta)
        k = apply_rope(k, positions, dims.rope_theta)
    # Adaptive TP: shard heads when they divide the model axis; otherwise fall
    # back to sequence-parallel q (context parallelism) with replicated KV —
    # keeps e.g. 25-head/5-kv archs runnable on a 16-way model axis.
    from repro.sharding import specs as _sp
    if H % max(_sp.axis_size("heads"), 1) == 0:
        q = shard(q, "batch", None, "heads", None)
    elif S > 1:
        q = shard(q, "batch", "seq_sp", None, None)
    if KV % max(_sp.axis_size("kv_heads"), 1) == 0:
        k = shard(k, "batch", None, "kv_heads", None)
        v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _mask_bias(q_pos, k_pos, window: int, causal: bool):
    """(..., Sq, Sk) additive mask from absolute positions."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok &= diff >= 0
    if window > 0:
        ok &= diff < window
    return jnp.where(ok, 0.0, mask_value(jnp.float32)).astype(jnp.float32)


def _sdpa_einsum(q, k, v, q_pos, k_pos, dims: AttnDims):
    """Reference attention. q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd).

    GQA K/V are expanded to H heads so every attention tensor carries ONE
    consistent head axis — a (KV,G) split head axis forces the SPMD
    partitioner into 'involuntary full rematerialization' (replication) at
    fwd/bwd sharding transitions. The expansion is a broadcast that shards
    over 'heads' with everything else; the flash kernel path keeps true GQA."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        from repro.sharding import specs as _sp
        if H % max(_sp.axis_size("heads"), 1) == 0:
            k = shard(k, "batch", None, "heads", None)
            v = shard(v, "batch", None, "heads", None)
    scores = jnp.einsum("bqhe,bshe->bhqs", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = scores + _mask_bias(q_pos, k_pos, dims.window, dims.causal)[:, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshe->bqhe", probs, v)
    return out


def _sdpa_chunked(q, k, v, q_pos, k_pos, dims: AttnDims, q_chunk: int = 1024):
    """Flash-style chunked attention in pure jnp: scan over query blocks —
    bounds live memory to O(q_chunk * Sk). The chunk body is checkpointed so
    scan-backward stores only chunk INPUTS (not scores/probs residuals) and
    recomputes the chunk forward — without this, bwd stacks O(S^2) residuals
    across chunks and defeats the memory bound entirely."""
    B, Sq, H, hd = q.shape
    n_chunks = max(1, Sq // q_chunk)
    q_chunk = Sq // n_chunks

    qs = q.reshape(B, n_chunks, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(B, n_chunks, q_chunk).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_fwd(qc, qpc):
        return _sdpa_einsum(qc, k, v, qpc, k_pos, dims)

    def one_chunk(carry, inp):
        qc, qpc = inp
        return carry, chunk_fwd(qc, qpc)

    _, outs = jax.lax.scan(one_chunk, None, (qs, qp))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def _sdpa_banded(q, k, v, dims: AttnDims, q_chunk: int = 1024):
    """Sliding-window attention computing ONLY the diagonal band: each query
    chunk attends to k/v rows [chunk_start - window, chunk_end) — work is
    O(S * (window + chunk)), not O(S^2). Assumes prefill layout (positions
    0..S-1). Unrolled over chunks so HLO FLOPs are exact (no scan-once
    undercount); this is the beyond-paper optimization for windowed archs
    (EXPERIMENTS.md §Perf, hymba prefill hillclimb)."""
    B, Sq, H, hd = q.shape
    W = dims.window
    n_chunks = max(1, Sq // q_chunk)
    q_chunk = Sq // n_chunks

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_fn(qc, kc, vc, q_pos, k_pos):
        return _sdpa_einsum(qc, kc, vc, q_pos, k_pos, dims)

    outs = []
    for ci in range(n_chunks):
        qs = ci * q_chunk
        ks = max(0, qs - W)
        ke = qs + q_chunk
        qc = jax.lax.slice_in_dim(q, qs, qs + q_chunk, axis=1)
        kc = jax.lax.slice_in_dim(k, ks, ke, axis=1)
        vc = jax.lax.slice_in_dim(v, ks, ke, axis=1)
        q_pos = jnp.broadcast_to(jnp.arange(qs, qs + q_chunk), (B, q_chunk))
        k_pos = jnp.broadcast_to(jnp.arange(ks, ke), (B, ke - ks))
        outs.append(chunk_fn(qc, kc, vc, q_pos, k_pos))
    return jnp.concatenate(outs, axis=1)


def _sdpa_banded_cp(q, k, v, dims: AttnDims, q_chunk: int = 1024):
    """Context-parallel banded attention: the chunk axis is sharded over the
    'seq_sp' mesh axis via shard_map — every model-shard computes its OWN
    whole chunks against (replicated) K/V band slices, so no per-chunk
    resharding collectives occur (hillclimb C iteration 2; iteration 1's
    plain banded form re-sharded a seq-sharded q at every slice)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import specs as _sp

    mesh = _sp.active_mesh()
    B, S, H, hd = q.shape
    n_chunks = max(1, S // q_chunk)
    C = S // n_chunks
    seq_ax = _sp._resolve_one("seq_sp", mesh) if mesh is not None else None
    batch_ax = _sp._resolve_one("batch", mesh) if mesh is not None else None
    n_seq = 1 if seq_ax is None else (
        mesh.shape[seq_ax] if isinstance(seq_ax, str)
        else int(np_prod([mesh.shape[a] for a in seq_ax])))
    if mesh is None or seq_ax is None or n_chunks % n_seq or S < dims.window + C:
        return _sdpa_banded(q, k, v, dims, q_chunk)
    nc_local = n_chunks // n_seq
    W = dims.window
    band = W + C

    if W > C * nc_local:   # halo wider than a shard's rows: fall back
        return _sdpa_banded(q, k, v, dims, q_chunk)
    n_shards = n_seq
    perm = [(s, s + 1) for s in range(n_shards - 1)]   # send tail to next

    def local(q_r, k_r, v_r):
        # q_r: (B_l, nc_local, C, H, hd); k_r/v_r: (B_l, nc_local, C, KV, hd)
        # K/V stay sequence-sharded; only a window-sized halo moves between
        # neighbouring shards (ppermute) instead of all-gathering full K/V.
        ci0 = jax.lax.axis_index(seq_ax) * nc_local
        Bl = q_r.shape[0]
        k_flat = k_r.reshape(Bl, nc_local * C, *k_r.shape[3:])
        v_flat = v_r.reshape(Bl, nc_local * C, *v_r.shape[3:])
        halo_k = jax.lax.ppermute(k_flat[:, -W:], seq_ax, perm)
        halo_v = jax.lax.ppermute(v_flat[:, -W:], seq_ax, perm)
        k_ext = jnp.concatenate([halo_k, k_flat], axis=1)  # rows [loc0-W, locN)
        v_ext = jnp.concatenate([halo_v, v_flat], axis=1)

        @functools.partial(jax.checkpoint,
                           policy=jax.checkpoint_policies.nothing_saveable)
        def chunk_fn(qc, kc, q_pos, k_pos, vc):
            with _sp.use_mesh(None):
                return _sdpa_einsum(qc, kc, vc, q_pos, k_pos, dims)

        outs = []
        for i in range(nc_local):
            ci = ci0 + i
            kc = jax.lax.slice_in_dim(k_ext, i * C, i * C + band, axis=1)
            vc = jax.lax.slice_in_dim(v_ext, i * C, i * C + band, axis=1)
            q_pos = jnp.broadcast_to(ci * C + jnp.arange(C), (Bl, C))
            # k_ext row j holds global position ci0*C - W + i*C + j; rows
            # before position 0 are shard-0's zero halo -> sentinel-masked
            raw = (ci0 * C - W) + i * C + jnp.arange(band)
            raw = jnp.where(raw >= 0, raw, S + W + 1)   # causal-masks zeros
            k_pos = jnp.broadcast_to(raw, (Bl, band))
            outs.append(chunk_fn(q_r[:, i], kc, q_pos, k_pos, vc))
        return jnp.stack(outs, axis=1)

    q_r = q.reshape(B, n_chunks, C, H, hd)
    KV = k.shape[2]
    k_r = k.reshape(B, n_chunks, C, KV, hd)
    v_r = v.reshape(B, n_chunks, C, KV, hd)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_ax, seq_ax, None, None, None),
                  P(batch_ax, seq_ax, None, None, None),
                  P(batch_ax, seq_ax, None, None, None)),
        out_specs=P(batch_ax, seq_ax, None, None, None),
        check_vma=False)(q_r, k_r, v_r)
    return out.reshape(B, S, H, hd)


def np_prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def attention(params, x, dims: AttnDims, positions, impl: str = "einsum",
              kv_override=None):
    """Self-attention (or cross-attention when kv_override=(k,v,k_pos))."""
    q, k, v = _qkv(params, x, dims, positions)
    k_pos = positions
    if kv_override is not None:
        k, v, k_pos = kv_override
    if impl == "banded" or (impl == "chunked" and dims.window > 0
                            and dims.causal and kv_override is None):
        out = _sdpa_banded_cp(q, k, v, dims)
    elif impl == "chunked":
        out = _sdpa_chunked(q, k, v, positions, k_pos, dims)
    elif impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=dims.causal, window=dims.window,
                                   q_positions=positions, k_positions=k_pos)
    else:
        out = _sdpa_einsum(q, k, v, positions, k_pos, dims)
    B, S, H, hd = out.shape
    out = out.reshape(B, S, H * hd)
    out = out @ params["wo"].astype(x.dtype)
    if S > 1:  # row-parallel wo output -> sequence-parallel (reduce-scatter)
        out = shard(out, "batch", "seq_sp", None)
    return out


# Sentinel cache position for an INACTIVE (freed / never-admitted) serving
# slot. It is >= any reachable sequence position, so the dense decode scatter
# drops the slot's K/V write (index out of range, mode="drop") and the paged /
# ring-buffer paths gate on ``pos < INACTIVE_POS`` explicitly. The engine sets
# a slot's pos to this on _finish; pos keeps advancing by +1 per tick but
# stays >= INACTIVE_POS, so freed rows are bit-stable indefinitely.
INACTIVE_POS = 1 << 30


def freeze_inactive_rows(pos, new, old):
    """Per-slot recurrent-state update gate for serving decode: rows of
    INACTIVE slots (vector ``pos`` at the sentinel) keep their ``old`` value
    bit-for-bit; scalar (lockstep) pos is a no-op. ``new``/``old`` are
    matching pytrees whose leaves lead with the batch axis. The single
    implementation of the sentinel convention for recurrent families
    (hybrid SSM branch, rwkv state) — keep them from diverging."""
    if jnp.ndim(pos) != 1:
        return new
    act = pos < INACTIVE_POS
    return jax.tree.map(
        lambda n, o: jnp.where(act.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
        new, old)


def decode_positions(pos, batch: int):
    """(B,1) query positions from a cache ``pos`` that is either a scalar
    (lockstep batch) or a (B,) per-slot vector — THE cross-family convention
    for serving decode (see models/registry.py); every family's decode_step
    goes through here so the two layouts cannot desynchronize."""
    if jnp.ndim(pos) == 1:
        return pos[:, None]
    return jnp.full((batch, 1), pos, jnp.int32)


def _decode_sdpa_local(q, ck, cv, cache_pos, k_positions, window, hd):
    """Partial-softmax decode attention over a LOCAL cache slice.
    q: (B,1,KV,G,hd); ck/cv: (B,S_loc,KV,hd); k_positions: (S_loc,) global or
    (B,S_loc) per-row (the paged path, where each slot views its own pages);
    cache_pos: scalar (lockstep) or (B,1) per-slot positions.
    Returns (m (B,KV,G,1), l, acc (B,KV,G,1,hd)) for cross-shard combining."""
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, ck.astype(q.dtype)
                        ).astype(jnp.float32) / math.sqrt(hd)
    kp = k_positions if jnp.ndim(k_positions) == 2 else k_positions[None, :]
    valid = kp <= cache_pos
    if window > 0:
        valid &= kp > cache_pos - window
    scores = jnp.where(valid[:, None, None, None, :], scores,
                       mask_value(scores.dtype))
    m = scores.max(axis=-1)                                   # (B,KV,G,1)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(q.dtype),
                     cv.astype(q.dtype)).astype(jnp.float32)
    return m, l, acc


def attention_decode(params, x, dims: AttnDims, cache_k, cache_v, cache_pos,
                     positions):
    """Single-token decode: x (B,1,D); cache_{k,v}: (B,S_max,KV,hd).
    Returns (out, new_k, new_v). Cache positions < cache_pos are valid.

    ``cache_pos`` is either a scalar (every batch row at the same position —
    the lockstep train/dryrun path) or a (B,) vector of PER-SLOT positions
    (the serving engine's continuous-batching path, where each slot is at a
    different point in its own sequence). The vector path writes the new K/V
    row with a per-batch scatter and masks per-row; out-of-range positions
    (already-finished slots) are dropped by the scatter.

    When the cache sequence dim is sharded (adaptive cache_logical), attention
    runs as flash-decode context parallelism via shard_map: each shard scans
    ONLY its local cache rows and partial softmax stats (m, l, acc) combine
    with three tiny psums — without this the SPMD partitioner replicates the
    whole cache per chip (hillclimb A iteration 2)."""
    q, k, v = _qkv(params, x, dims, positions)
    B, S_max, KV, hd = cache_k.shape
    H = dims.num_heads
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    vector_pos = jnp.ndim(cache_pos) == 1

    from repro.sharding import specs as _sp
    mesh = _sp.active_mesh()
    seq_ax = _sp._resolve_one("seq_sp", mesh) if mesh is not None else None
    kv_sharded = KV % max(_sp.axis_size("kv_heads"), 1) == 0 and \
        _sp.axis_size("kv_heads") > 1
    use_cp = (mesh is not None and seq_ax is not None and not kv_sharded
              and not vector_pos
              and isinstance(seq_ax, str) and S_max % mesh.shape[seq_ax] == 0)

    if use_cp:
        from jax.sharding import PartitionSpec as P
        batch_ax = _sp._resolve_one("batch", mesh)
        n_shards = mesh.shape[seq_ax]
        s_loc = S_max // n_shards

        def local(qg, k_new, v_new, ck, cv, pos):
            sid = jax.lax.axis_index(seq_ax)
            # cache write happens LOCALLY on the owning shard (a global DUS
            # on the sharded dim makes the partitioner replicate the cache)
            rel = pos - sid * s_loc
            safe = jnp.clip(rel, 0, s_loc - 1)
            in_rng = (rel >= 0) & (rel < s_loc)
            cur_k = jax.lax.dynamic_slice_in_dim(ck, safe, 1, axis=1)
            cur_v = jax.lax.dynamic_slice_in_dim(cv, safe, 1, axis=1)
            wk = jnp.where(in_rng, k_new.astype(ck.dtype), cur_k)
            wv = jnp.where(in_rng, v_new.astype(cv.dtype), cur_v)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, wk, safe, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, wv, safe, axis=1)

            k_positions = sid * s_loc + jnp.arange(s_loc)
            m, l, acc = _decode_sdpa_local(qg, ck, cv, pos, k_positions,
                                           dims.window, hd)
            m_g = jax.lax.pmax(m, seq_ax)
            corr = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * corr, seq_ax)
            acc_g = jax.lax.psum(acc * corr[..., None], seq_ax)
            out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(qg.dtype)
            return out, ck, cv

        out, cache_k, cache_v = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(batch_ax, None, None, None, None),
                      P(batch_ax, None, None, None),
                      P(batch_ax, None, None, None),
                      P(batch_ax, seq_ax, None, None),
                      P(batch_ax, seq_ax, None, None), P()),
            out_specs=(P(batch_ax, None, None, None, None),
                       P(batch_ax, seq_ax, None, None),
                       P(batch_ax, seq_ax, None, None)),
            check_vma=False)(qg, k, v, cache_k, cache_v, cache_pos)
        out = out.transpose(0, 3, 1, 2, 4)       # (B,1,KV,G,hd)
    else:
        if vector_pos:
            # per-slot positions: scatter row b's new K/V at cache_pos[b];
            # OOB rows (finished slots stepped past S_max) are dropped
            b_idx = jnp.arange(B)
            cache_k = cache_k.at[b_idx, cache_pos].set(
                k[:, 0].astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[b_idx, cache_pos].set(
                v[:, 0].astype(cache_v.dtype), mode="drop")
            mask_pos = cache_pos[:, None]                    # (B,1) -> (B,S)
        else:
            cache_k = jax.lax.dynamic_update_slice_in_dim(
                cache_k, k.astype(cache_k.dtype), cache_pos, axis=1)
            cache_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v, v.astype(cache_v.dtype), cache_pos, axis=1)
            mask_pos = cache_pos
        k_positions = jnp.arange(S_max)
        m, l, acc = _decode_sdpa_local(qg, cache_k, cache_v, mask_pos,
                                       k_positions, dims.window, hd)
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        out = out.transpose(0, 3, 1, 2, 4)

    out = out.reshape(B, 1, H * hd)
    return out @ params["wo"].astype(x.dtype), cache_k, cache_v


# ------------------------------------------------------- parallel prefill
def attention_prefill_chunk(params, x, dims: AttnDims, cache_k, cache_v,
                            start, positions, use_kernel: bool = False):
    """Multi-token prefill-chunk attention against a dense per-request cache.

    The matmul-wide counterpart of ``attention_decode``: instead of one query
    row per dispatch, a whole CHUNK of prompt positions is projected, its
    post-RoPE K/V written into cache rows ``[start, start + C)`` in one
    dynamic-update, and all C queries attend jointly — full matmul width on
    the q axis, which is the loop-width/tiling lever the paper pulls for
    throughput (and the reason parallel prefill beats teacher-forcing
    ``decode_step`` under a scan).

    x: (B, C, D); cache_k/v: (B, S_max, KV, hd); ``start`` is the chunk's
    first absolute position (a traced scalar for continuation chunks, the
    literal 0 for a first chunk); positions: (B, C) absolute query positions.
    Validity is ``k_pos <= q_pos`` (and the sliding window) over ALL cache
    rows, so a continuation chunk sees every previously-written row and
    never a future/unwritten one (unwritten rows have k_pos > q_pos).

    ``use_kernel`` routes the chunk-local causal attention through the
    K/V-exporting flash kernel (``kernels.ops.flash_prefill``) — only valid
    when the cache holds NO prior rows (a first chunk at start == 0), where
    chunk-local causal+window attention IS the full mask. Returns
    (out (B, C, H*hd) @ wo, new_ck, new_cv)."""
    q, k, v = _qkv(params, x, dims, positions)
    B, C, KV, hd = k.shape
    H = dims.num_heads
    if use_kernel:
        from repro.kernels import ops as kops
        out, k_tiles, v_tiles = kops.flash_prefill(
            q, k, v, causal=dims.causal, window=dims.window)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_tiles.astype(cache_k.dtype), start, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_tiles.astype(cache_v.dtype), start, axis=1)
        out = out.reshape(B, C, H * hd)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), start, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), start, axis=1)
        S_max = ck.shape[1]
        G = H // KV
        qg = q.reshape(B, C, KV, G, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck.astype(q.dtype)
                            ).astype(jnp.float32) / math.sqrt(hd)
        k_pos = jnp.arange(S_max)
        valid = k_pos[None, None, :] <= positions[:, :, None]      # (B,C,S)
        if dims.window > 0:
            valid &= k_pos[None, None, :] > positions[:, :, None] - dims.window
        scores = jnp.where(valid[:, None, None, :, :], scores,
                           mask_value(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs, cv.astype(q.dtype)
                         ).reshape(B, C, H * hd)
    return out @ params["wo"].astype(x.dtype), ck, cv


# ------------------------------------------------------- paged KV decode
def paged_row_indices(block_tables, page_size: int, n_rows: int):
    """Flattened pool-row index of each LOGICAL row of every slot.

    block_tables: (B, mps) int32 page ids, -1 = unallocated. Returns
    ((B, n_rows) int32 physical rows into a (P*page_size, ...) flattened pool,
    (B, n_rows) bool page-allocated mask). Rows of unallocated pages map to 0
    (callers must mask with the bool) — keeps the gather in-bounds."""
    j = jnp.arange(n_rows)
    page = jnp.take_along_axis(
        block_tables, jnp.broadcast_to(j // page_size,
                                       (block_tables.shape[0], n_rows)), axis=1)
    ok = page >= 0
    phys = jnp.where(ok, page * page_size + j[None, :] % page_size, 0)
    return phys, ok


def paged_write_target(block_tables, idx, page_size: int):
    """Write-side block-table lookup shared by every paged decode path.
    idx: (B,) logical row per slot (sequence position, or ring index for the
    hybrid ring). Returns ((B,) flattened pool row, (B,) bool valid — false
    where the page is unallocated). Callers add their own in-range gate on
    idx before passing it (it must be >= 0 here)."""
    mps = block_tables.shape[1]
    page = jnp.take_along_axis(
        block_tables, jnp.clip(idx // page_size, 0, mps - 1)[:, None],
        axis=1)[:, 0]
    return page * page_size + idx % page_size, page >= 0


def paged_write_rows(pool, rows, row_idx, valid):
    """Scatter one new row per slot into a flattened page pool.
    pool: (P, ps, ...) -> returns same shape; rows: (B, ...) new values;
    row_idx: (B,) flattened pool row per slot; valid: (B,) bool (invalid
    writes are dropped — freed slots, unallocated pages)."""
    P, ps = pool.shape[:2]
    flat = pool.reshape((P * ps,) + pool.shape[2:])
    idx = jnp.where(valid, row_idx, P * ps)          # OOB -> dropped
    flat = flat.at[idx].set(rows.astype(flat.dtype), mode="drop")
    return flat.reshape(pool.shape)


# ------------------------------------------- int8 page writes (q8 backend)
def _requant_page(blk, content, groups: int = 1):
    """Symmetric int8 scales per page from its LIVE rows only — one scale
    per kv-head GROUP (``groups`` is the serving tp degree; group t covers
    the contiguous KV/groups kv heads shard t owns, so each scale is an
    amax over shard-local values and the requant write partitions comm-free
    under a kv-head-sharded pool; groups=1 is the original whole-page
    scale, bitwise). blk: (B, ps, KV, hd) f32 dequantized page content;
    content: (B, ps) bool — rows beyond the sequence frontier may hold
    stale payload from a recycled page, so they are excluded from the amax
    AND zeroed in the output. Returns (q (B,ps,KV,hd) int8,
    scale (B, groups) f32)."""
    from repro.core.quantize import page_scale
    B, ps, KV, hd = blk.shape
    vm = content[..., None, None]
    masked = jnp.where(vm, blk, 0.0)
    g = masked.reshape(B, ps, groups, KV // groups, hd)
    scale = page_scale(jnp.max(jnp.abs(g), axis=(1, 3, 4)))
    q = jnp.clip(jnp.round(g / scale[:, None, :, None, None]),
                 -127, 127).astype(jnp.int8)
    return q.reshape(B, ps, KV, hd), scale


def _dequant_page_block(pool_pg, scale_pg):
    """Dequantize gathered int8 pages (B, ps, KV, hd) with their per-group
    scales (B, T) — group t scales the contiguous KV/T kv-head slab t."""
    B, ps, KV, hd = pool_pg.shape
    T = scale_pg.shape[-1]
    g = pool_pg.astype(jnp.float32).reshape(B, ps, T, KV // T, hd)
    return (g * scale_pg[:, None, :, None, None]).reshape(B, ps, KV, hd)


def paged_append_row_q8(pool, scale, rows, block_tables, safe_pos, valid):
    """Decode-append one K/V row per slot into an INT8 page pool.

    The page is a quantization block: appending a row changes the page's
    max-abs, so the slot's CURRENT page is dequantized (one page per slot —
    never the full pool), the new row overlaid at ``safe_pos % ps``, and the
    page re-quantized with fresh symmetric per-group scales. Rows past the
    append offset are treated as stale (recycled-page payload) and zeroed.
    Invalid writes (freed slots, unallocated pages) drop both the page and
    its scale update. pool: (P, ps, KV, hd) int8; scale: (P, T) f32 — one
    column per kv-head group (T = serving tp degree, 1 when unsharded);
    rows: (B, KV, hd); safe_pos: (B,) clipped positions; valid: (B,)."""
    P, ps = pool.shape[:2]
    mps = block_tables.shape[1]
    B = rows.shape[0]
    T = scale.shape[-1]
    page = jnp.take_along_axis(
        block_tables, jnp.clip(safe_pos // ps, 0, mps - 1)[:, None],
        axis=1)[:, 0]
    pg = jnp.clip(page, 0, P - 1)
    blk = _dequant_page_block(pool[pg], scale[pg])
    off = safe_pos % ps
    blk = blk.at[jnp.arange(B), off].set(rows.astype(jnp.float32))
    content = jnp.arange(ps)[None, :] <= off[:, None]
    q, new_scale = _requant_page(blk, content, T)
    tgt = jnp.where(valid & (page >= 0), pg, P)      # OOB -> dropped
    pool = pool.at[tgt].set(q, mode="drop")
    scale = scale.at[tgt].set(new_scale, mode="drop")
    return pool, scale


def paged_splice_chunk_q8(pool, scale, rows, block_tables, positions,
                          write_floor):
    """Chunk-splice C rows per slot into an INT8 page pool (the incremental
    prefill splice, quantized). Visits each logical page the chunk overlaps
    (a static loop of at most C//ps + 2 pages), overlays the chunk's rows on
    the page's dequantized live content, and re-quantizes the whole page —
    so a COW-rematerialised partial page gets its fresh scales here, exactly
    once. Pages the chunk does NOT write (aliased prefix pages below
    ``write_floor``, including a full-hit's recomputed last row) are left
    untouched: their payload AND scales stay shared.

    pool: (P, ps, KV, hd) int8; scale: (P, T) f32 — one column per kv-head
    group (T = serving tp degree, 1 when unsharded); rows: (B, C, KV, hd);
    positions: (B, C) absolute query positions (contiguous, shared start);
    write_floor: scalar first writable logical row."""
    P, ps = pool.shape[:2]
    B, C = positions.shape
    mps = block_tables.shape[1]
    n_rows = mps * ps
    T = scale.shape[-1]
    start = positions[:, :1]                          # (B, 1)
    b_idx = jnp.arange(B)[:, None]
    for t in range((C - 1) // ps + 2):
        lpg = positions[:, 0] // ps + t               # (B,) logical page
        page = jnp.take_along_axis(
            block_tables, jnp.clip(lpg, 0, mps - 1)[:, None], axis=1)[:, 0]
        in_range = (lpg < mps) & (page >= 0)
        pg = jnp.clip(page, 0, P - 1)
        blk = _dequant_page_block(pool[pg], scale[pg])
        row_pos = lpg[:, None] * ps + jnp.arange(ps)[None, :]   # (B, ps)
        ci = row_pos - start                          # chunk-relative index
        from_chunk = ((ci >= 0) & (ci < C) & (row_pos >= write_floor)
                      & (row_pos < n_rows))
        chunk_rows = rows[b_idx, jnp.clip(ci, 0, C - 1)]        # (B,ps,KV,hd)
        blk = jnp.where(from_chunk[..., None, None],
                        chunk_rows.astype(jnp.float32), blk)
        content = (row_pos <= start + C - 1) & (row_pos < n_rows)
        q, new_scale = _requant_page(blk, content, T)
        writable = from_chunk.any(axis=1) & in_range
        tgt = jnp.where(writable, pg, P)
        pool = pool.at[tgt].set(q, mode="drop")
        scale = scale.at[tgt].set(new_scale, mode="drop")
    return pool, scale


def dequant_paged_view(view, phys, scale, page_size: int, dtype):
    """Dequantize a block-table-gathered int8 view (B, n_rows, KV, hd) using
    the per-page — (P,), or per-kv-head-group (P, T) — scales of the pages
    each row was gathered from."""
    P = scale.shape[0]
    pg = jnp.clip(phys // page_size, 0, P - 1)
    sc = scale[pg]                       # (B, n_rows) or (B, n_rows, T)
    if sc.ndim == 2:
        sc = sc[..., None]
    B, n, KV, hd = view.shape
    T = sc.shape[-1]
    g = view.astype(jnp.float32).reshape(B, n, T, KV // T, hd)
    return (g * sc[..., None, None]).reshape(view.shape).astype(dtype)


def attention_decode_paged(params, x, dims: AttnDims, pool_k, pool_v,
                           block_tables, cache_pos, positions,
                           impl: str = "einsum", *, k_scale=None,
                           v_scale=None):
    """Single-token decode against a PAGED KV cache (vLLM-style block tables).

    x: (B,1,D); pool_k/pool_v: (P, page_size, KV, hd) — ONE layer's slice of
    the shared page pool (no batch axis: memory scales with allocated pages,
    not slots x s_max); block_tables: (B, mps) int32, -1 = unallocated;
    cache_pos: (B,) per-slot positions (the paged path is serving-only, so
    positions are always a vector). Returns (out, new_pool_k, new_pool_v).

    Writes go through block-table indirection: slot b's new K/V row lands in
    page block_tables[b, pos//ps] at offset pos % ps; writes from slots whose
    position is out of range (>= mps*ps — freed slots at INACTIVE_POS) or
    whose page is unallocated are DROPPED.

    Reads: ``impl='kernel'`` routes through the Pallas paged-attention
    kernel (``kernels.ops.paged_decode``) — K/V blocks are gathered through
    the block table INSIDE the kernel and fully-masked pages (unallocated,
    or beyond the causal frontier) are skipped, so read work scales with a
    slot's live pages. ``impl='einsum'`` is the masked-gather reference:
    materialize the slot's logical view (B, mps*ps, KV, hd) and mask to
    allocated-page AND position <= pos (AND the sliding window) — rows of
    never-allocated trailing pages carry an INACTIVE_POS key position, so
    they can never win the causal mask for a live slot.

    With page_size == s_max (one page per slot) the einsum path reproduces
    the dense ``attention_decode`` vector path bit-for-bit (the gathered
    view IS the slot's dense cache row and the masks coincide); the kernel
    path matches it to greedy-token exactness (its online softmax uses the
    same dot-then-scale f32 operation order).

    ``k_scale``/``v_scale``: optional (P, T) f32 per-page per-kv-head-group
    symmetric scales (T = serving tp degree, 1 when unsharded) — the
    int8-backend path. The new row's write re-quantizes the slot's
    current page in place (``paged_append_row_q8``), reads dequantize
    per-page (inside the Pallas kernel's gather on the kernel path, each
    tp shard using its own group's scale column), and the return grows to
    (out, pool_k, pool_v, k_scale, v_scale)."""
    q, k, v = _qkv(params, x, dims, positions)
    P, ps, KV, hd = pool_k.shape
    B = q.shape[0]
    mps = block_tables.shape[1]
    n_rows = mps * ps
    H = dims.num_heads
    G = H // KV
    quantized = k_scale is not None

    # ---- write the new K/V row via the block table
    safe_pos = jnp.clip(cache_pos, 0, n_rows - 1)
    w_row, page_ok = paged_write_target(block_tables, safe_pos, ps)
    w_ok = (cache_pos >= 0) & (cache_pos < n_rows) & page_ok
    if quantized:
        pool_k, k_scale = paged_append_row_q8(pool_k, k_scale, k[:, 0],
                                              block_tables, safe_pos, w_ok)
        pool_v, v_scale = paged_append_row_q8(pool_v, v_scale, v[:, 0],
                                              block_tables, safe_pos, w_ok)
    else:
        pool_k = paged_write_rows(pool_k, k[:, 0], w_row, w_ok)
        pool_v = paged_write_rows(pool_v, v[:, 0], w_row, w_ok)

    if impl == "kernel":
        from repro.kernels import ops as kops
        from repro.sharding import specs as _sp
        # freed slots (cache_pos >= n_rows) carry an all--1 table: every
        # page is skipped and the kernel returns 0 rows for them, so no
        # clamping of start is needed for the skip logic to stay sound
        tp_mesh, tp_axis = _sp.head_shard_axis(H, KV)
        if quantized:
            out = kops.paged_decode_q8(q, pool_k, pool_v, k_scale, v_scale,
                                       block_tables, cache_pos,
                                       window=dims.window,
                                       mesh=tp_mesh, shard_axis=tp_axis)
        else:
            out = kops.paged_decode(q, pool_k, pool_v, block_tables,
                                    cache_pos, window=dims.window,
                                    mesh=tp_mesh, shard_axis=tp_axis)
        out = out.reshape(B, 1, H * hd)
    else:
        # ---- gather each slot's logical view and attend
        qg = q.reshape(B, 1, KV, G, hd)
        phys, ok = paged_row_indices(block_tables, ps, n_rows)
        flat_k = pool_k.reshape(P * ps, KV, hd)
        flat_v = pool_v.reshape(P * ps, KV, hd)
        view_k = flat_k[phys]                        # (B, n_rows, KV, hd)
        view_v = flat_v[phys]
        if quantized:
            view_k = dequant_paged_view(view_k, phys, k_scale, ps, q.dtype)
            view_v = dequant_paged_view(view_v, phys, v_scale, ps, q.dtype)
        k_positions = jnp.where(ok, jnp.arange(n_rows)[None, :], INACTIVE_POS)
        m, l, acc = _decode_sdpa_local(qg, view_k, view_v, cache_pos[:, None],
                                       k_positions, dims.window, hd)
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H * hd)
    # tp serving: all-gather the head-sharded attention output BEFORE the
    # output projection (NOT a psum of per-shard partial projections — an
    # un-split wo contraction is what keeps tp>1 bitwise equal to tp=1)
    from repro.sharding import specs as _sp
    out = _sp.replicate(out)
    out = out @ params["wo"].astype(x.dtype)
    if quantized:
        return out, pool_k, pool_v, k_scale, v_scale
    return out, pool_k, pool_v


def attention_prefill_chunk_paged(params, x, dims: AttnDims, pool_k, pool_v,
                                  block_tables, positions, write_floor,
                                  impl: str = "kernel", *, k_scale=None,
                                  v_scale=None):
    """Multi-token prefill-chunk attention DIRECTLY against the paged pool —
    the incremental-splice counterpart of ``attention_prefill_chunk``.

    x: (B, C, D); pool_k/pool_v: one layer's (P, ps, KV, hd) pool slice;
    block_tables: (B, mps) rows for the chunk's slots; positions: (B, C)
    absolute query positions (row i at ``positions[:, 0] + i`` — the engine
    groups jobs so a chunk's positions are contiguous and share a start);
    write_floor: scalar — the first logical row this request may WRITE.

    The chunk's post-RoPE K/V scatter straight into the slot's own pages
    (the per-chunk incremental splice: there is no transient request cache
    to fill and no completion splice to pay). Rows below ``write_floor``
    are DROPPED — they live in shared immutable prefix pages aliased by
    other block tables (copy-on-write's no-write half); the COW partial
    page is re-materialised by the engine with the same scatter before the
    first chunk runs. Attention then reads prior chunks, aliased prefix
    pages, and the current chunk uniformly through the block table:
    ``impl='kernel'`` uses the block-skipping Pallas kernel
    (``ops.paged_prefill``); ``impl='einsum'`` is the masked-gather
    reference over the full block-table span. Returns
    (out (B, C, H*hd) @ wo, new_pool_k, new_pool_v).

    ``k_scale``/``v_scale``: optional (P, T) f32 per-page per-kv-head-group
    scales (T = serving tp degree) — the int8 backend. The splice
    re-quantizes each page the chunk writes
    (``paged_splice_chunk_q8``; untouched aliased prefix pages keep their
    shared scale), reads dequantize per-page, and the return grows to
    (out, pool_k, pool_v, k_scale, v_scale)."""
    q, k, v = _qkv(params, x, dims, positions)
    B, C, KV, hd = k.shape
    P, ps = pool_k.shape[:2]
    mps = block_tables.shape[1]
    n_rows = mps * ps
    H = dims.num_heads
    quantized = k_scale is not None

    # ---- incremental splice: scatter the chunk's K/V rows via block table
    if quantized:
        pool_k, k_scale = paged_splice_chunk_q8(pool_k, k_scale, k,
                                                block_tables, positions,
                                                write_floor)
        pool_v, v_scale = paged_splice_chunk_q8(pool_v, v_scale, v,
                                                block_tables, positions,
                                                write_floor)
        flat_k = pool_k.reshape(P * ps, KV, hd)
        flat_v = pool_v.reshape(P * ps, KV, hd)
    else:
        page = jnp.take_along_axis(
            block_tables, jnp.clip(positions // ps, 0, mps - 1), axis=1)
        w_ok = ((page >= 0) & (positions >= write_floor)
                & (positions >= 0) & (positions < n_rows))
        w_rows = jnp.where(w_ok, page * ps + positions % ps, P * ps)  # drop
        flat_k = pool_k.reshape(P * ps, KV, hd)
        flat_v = pool_v.reshape(P * ps, KV, hd)
        flat_k = flat_k.at[w_rows].set(k.astype(flat_k.dtype), mode="drop")
        flat_v = flat_v.at[w_rows].set(v.astype(flat_v.dtype), mode="drop")
        pool_k = flat_k.reshape(pool_k.shape)
        pool_v = flat_v.reshape(pool_v.shape)

    if impl == "kernel":
        from repro.kernels import ops as kops
        from repro.sharding import specs as _sp
        tp_mesh, tp_axis = _sp.head_shard_axis(H, KV)
        if quantized:
            out = kops.paged_prefill_q8(q, pool_k, pool_v, k_scale, v_scale,
                                        block_tables, positions[:, 0],
                                        window=dims.window,
                                        mesh=tp_mesh, shard_axis=tp_axis)
        else:
            out = kops.paged_prefill(q, pool_k, pool_v, block_tables,
                                     positions[:, 0], window=dims.window,
                                     mesh=tp_mesh, shard_axis=tp_axis)
        out = out.reshape(B, C, H * hd)
    else:
        G = H // KV
        qg = q.reshape(B, C, KV, G, hd)
        phys, ok = paged_row_indices(block_tables, ps, n_rows)
        view_k = flat_k[phys]                        # (B, n_rows, KV, hd)
        view_v = flat_v[phys]
        if quantized:
            view_k = dequant_paged_view(view_k, phys, k_scale, ps, q.dtype)
            view_v = dequant_paged_view(view_v, phys, v_scale, ps, q.dtype)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, view_k.astype(q.dtype)
                            ).astype(jnp.float32) / math.sqrt(hd)
        k_pos = jnp.where(ok, jnp.arange(n_rows)[None, :], INACTIVE_POS)
        valid = k_pos[:, None, :] <= positions[:, :, None]       # (B,C,S)
        if dims.window > 0:
            valid &= k_pos[:, None, :] > positions[:, :, None] - dims.window
        scores = jnp.where(valid[:, None, None, :, :], scores,
                           mask_value(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs, view_v.astype(q.dtype)
                         ).reshape(B, C, H * hd)
    # all-gather head-sharded chunk outputs before wo (see decode path note)
    from repro.sharding import specs as _sp
    out = _sp.replicate(out)
    out = out @ params["wo"].astype(x.dtype)
    if quantized:
        return out, pool_k, pool_v, k_scale, v_scale
    return out, pool_k, pool_v


# ------------------------------------------------- MLA (latent attention)
# Multi-head latent attention (DeepSeek-V3 style). The cache stores, per
# token, ONE row of ``kv_lora_rank + qk_rope_head_dim`` floats: a compressed
# KV latent (wkv_a output, rms-normed) concatenated with a small decoupled
# RoPE key head shared by all query heads. Decode runs the ABSORB path:
# wkv_b's key half is folded into the query projection (q_nope -> latent
# space) and its value half into the output projection, so attention's
# score/value contractions run directly over the latent rows — per-head K/V
# never materialize. Every dense/paged variant below shares the same
# absorbed operation order, which is what makes the dense-MLA path and the
# degenerate-page latent path bit-exact (the house anchor rule).
@dataclasses.dataclass(frozen=True)
class MLADims:
    d_model: int
    num_heads: int
    kv_lora_rank: int        # c_kv: compressed KV latent width
    qk_rope_head_dim: int    # r: decoupled RoPE key head width
    head_dim: int            # qk_nope width == value head width
    rope_theta: float = 10000.0

    @property
    def latent_dim(self) -> int:
        """Cached floats per token: c_kv + r (one latent page row)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale_dim(self) -> int:
        """Softmax scale denominator: the EFFECTIVE per-head query width
        (qk_nope + rope), not the latent width the absorbed dot runs over."""
        return self.head_dim + self.qk_rope_head_dim


def mla_init(key, dims: MLADims):
    ks = jax.random.split(key, 4)
    D, H = dims.d_model, dims.num_heads
    c, r, hd = dims.kv_lora_rank, dims.qk_rope_head_dim, dims.head_dim
    return {
        "wq": _dense(ks[0], (D, H * (hd + r))),
        "wkv_a": _dense(ks[1], (D, c + r)),
        "kv_norm": jnp.zeros((c,), jnp.float32),
        "wkv_b": _dense(ks[2], (c, H * 2 * hd), scale_dim=c),
        "wo": _dense(ks[3], (H * hd, D), scale_dim=H * hd),
    }


def mla_logical(dims: MLADims):
    return {
        "wq": ("fsdp", "heads"),
        "wkv_a": ("fsdp", None),
        "kv_norm": (None,),
        "wkv_b": (None, "heads"),
        "wo": ("heads", "fsdp"),
    }


def _mla_wkv_b(params, dims: MLADims, dtype):
    """Split wkv_b into its absorbable halves:
    (wb_k (H, hd, c) — folds q_nope into latent space,
     wb_v (H, c, hd) — expands latent attention output to value heads)."""
    c, H, hd = dims.kv_lora_rank, dims.num_heads, dims.head_dim
    wb = params["wkv_b"].astype(dtype).reshape(c, H, 2 * hd)
    wb_k = wb[:, :, :hd].transpose(1, 2, 0)      # (H, hd, c)
    wb_v = wb[:, :, hd:].transpose(1, 0, 2)      # (H, c, hd)
    return wb_k, wb_v


def mla_absorbed_queries(params, x, dims: MLADims, positions):
    """Project x to ABSORBED queries (B, S, H, c_kv + r): the nope half is
    pushed through wb_k into latent space, the rope half gets RoPE; their
    concatenation dots directly against cached latent rows."""
    B, S, _ = x.shape
    H, hd, r = dims.num_heads, dims.head_dim, dims.qk_rope_head_dim
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, H, hd + r)
    q_nope, q_pe = q[..., :hd], q[..., hd:]
    q_pe = apply_rope(q_pe, positions, dims.rope_theta)
    wb_k, _ = _mla_wkv_b(params, dims, x.dtype)
    q_abs = jnp.einsum("bshd,hdc->bshc", q_nope, wb_k)
    return jnp.concatenate([q_abs, q_pe], axis=-1)


def mla_latent_rows(params, x, dims: MLADims, positions):
    """Per-token latent cache rows (B, S, 1, c_kv + r): rms-normed compressed
    KV latent ++ RoPE'd decoupled key head (a single shared 'kv head')."""
    c = dims.kv_lora_rank
    kv = x @ params["wkv_a"].astype(x.dtype)     # (B, S, c + r)
    ckv = rmsnorm(kv[..., :c], params["kv_norm"])
    k_pe = apply_rope(kv[..., None, c:], positions, dims.rope_theta)
    return jnp.concatenate([ckv[:, :, None, :], k_pe], axis=-1)


def _mla_out(params, attn, dims: MLADims, x):
    """Absorbed output projection: latent attention output (B, S, H, c_kv)
    -> value heads via wb_v -> wo. The wb_v einsum contracts only the
    latent width c (head-local), so a head-sharded ``attn`` stays
    head-sharded through it; the tp serve path then all-gathers the value
    heads BEFORE wo (one un-split contraction — the same replicate-before-
    wo structure as the K/V paths, and what keeps latent tp>1 bitwise
    equal to tp=1). Identity outside a mesh context."""
    from repro.sharding import specs as _sp
    B, S, H, _ = attn.shape
    _, wb_v = _mla_wkv_b(params, dims, x.dtype)
    out = jnp.einsum("bshc,hcd->bshd", attn, wb_v)
    out = _sp.replicate(out.reshape(B, S, H * dims.head_dim))
    return out @ params["wo"].astype(x.dtype)


def mla_attention_decode(params, x, dims: MLADims, cache_c, cache_pos,
                         positions):
    """Single-token MLA decode against a DENSE latent cache — the reference
    path. x: (B,1,D); cache_c: (B, S_max, 1, c_kv + r). Same scalar/vector
    ``cache_pos`` contract as ``attention_decode``. Returns (out, new_cache).

    Scores and values both read the latent rows (values = the leading c_kv
    columns); shares ``_decode_sdpa_local`` with the standard path so the
    dense and degenerate-page gathers stay bit-identical."""
    B = x.shape[0]
    H, c = dims.num_heads, dims.kv_lora_rank
    q = mla_absorbed_queries(params, x, dims, positions)     # (B,1,H,c+r)
    rows = mla_latent_rows(params, x, dims, positions)       # (B,1,1,c+r)
    if jnp.ndim(cache_pos) == 1:
        b_idx = jnp.arange(B)
        cache_c = cache_c.at[b_idx, cache_pos].set(
            rows[:, 0].astype(cache_c.dtype), mode="drop")
        mask_pos = cache_pos[:, None]
    else:
        cache_c = jax.lax.dynamic_update_slice_in_dim(
            cache_c, rows.astype(cache_c.dtype), cache_pos, axis=1)
        mask_pos = cache_pos
    qg = q.reshape(B, 1, 1, H, dims.latent_dim)              # KV=1, G=H
    k_positions = jnp.arange(cache_c.shape[1])
    m, l, acc = _decode_sdpa_local(qg, cache_c, cache_c[..., :c], mask_pos,
                                   k_positions, 0, dims.scale_dim)
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    attn = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, c)
    return _mla_out(params, attn, dims, x), cache_c


def mla_attention_prefill_chunk(params, x, dims: MLADims, cache_c, start,
                                positions):
    """Multi-token MLA prefill chunk against a dense latent cache — the
    absorb-path counterpart of ``attention_prefill_chunk`` (einsum branch).
    Returns (out (B,C,D), new_cache)."""
    c = dims.kv_lora_rank
    q = mla_absorbed_queries(params, x, dims, positions)     # (B,C,H,c+r)
    rows = mla_latent_rows(params, x, dims, positions)       # (B,C,1,c+r)
    cache_c = jax.lax.dynamic_update_slice_in_dim(
        cache_c, rows.astype(cache_c.dtype), start, axis=1)
    B, C, H, _ = q.shape
    S_max = cache_c.shape[1]
    qg = q.reshape(B, C, 1, H, dims.latent_dim)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, cache_c.astype(q.dtype)
                        ).astype(jnp.float32) / math.sqrt(dims.scale_dim)
    k_pos = jnp.arange(S_max)
    valid = k_pos[None, None, :] <= positions[:, :, None]    # (B,C,S)
    scores = jnp.where(valid[:, None, None, :, :], scores,
                       mask_value(scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    attn = jnp.einsum("bkgqs,bskh->bqkgh", probs,
                      cache_c[..., :c].astype(q.dtype)).reshape(B, C, H, c)
    return _mla_out(params, attn, dims, x), cache_c


def mla_attention_decode_paged(params, x, dims: MLADims, pool_c,
                               block_tables, cache_pos, positions,
                               impl: str = "einsum"):
    """Single-token MLA decode against a LATENT page pool.

    pool_c: one layer's (P, page_size, 1, c_kv + r) latent pool slice — a
    page row is the whole per-token cache. Write/gather indirection is the
    standard block-table machinery (same helpers as the K/V path); the read
    is the absorbed dot over latent rows, values = the leading c_kv columns
    of the SAME gathered block. ``impl='kernel'`` routes through the
    latent-page Pallas kernel (``ops.paged_decode_latent``); 'einsum' is the
    masked-gather reference, bit-exact with ``mla_attention_decode`` at
    page_size == s_max. Returns (out, new_pool)."""
    H, c = dims.num_heads, dims.kv_lora_rank
    q = mla_absorbed_queries(params, x, dims, positions)     # (B,1,H,c+r)
    rows = mla_latent_rows(params, x, dims, positions)       # (B,1,1,c+r)
    P, ps = pool_c.shape[:2]
    B = q.shape[0]
    n_rows = block_tables.shape[1] * ps
    safe_pos = jnp.clip(cache_pos, 0, n_rows - 1)
    w_row, page_ok = paged_write_target(block_tables, safe_pos, ps)
    w_ok = (cache_pos >= 0) & (cache_pos < n_rows) & page_ok
    pool_c = paged_write_rows(pool_c, rows[:, 0], w_row, w_ok)

    if impl == "kernel":
        from repro.kernels import ops as kops
        from repro.sharding import specs as _sp
        # tp shards the ABSORBED queries/outputs on their head axis; the
        # latent pool itself is replicated (no kv-head axis to shard)
        tp_mesh, tp_axis = _sp.latent_head_shard_axis(H)
        attn = kops.paged_decode_latent(q, pool_c, block_tables, cache_pos,
                                        scale_dim=dims.scale_dim, d_v=c,
                                        mesh=tp_mesh, shard_axis=tp_axis)
    else:
        qg = q.reshape(B, 1, 1, H, dims.latent_dim)
        phys, ok = paged_row_indices(block_tables, ps, n_rows)
        view = pool_c.reshape(P * ps, 1, dims.latent_dim)[phys]
        k_positions = jnp.where(ok, jnp.arange(n_rows)[None, :], INACTIVE_POS)
        m, l, acc = _decode_sdpa_local(qg, view, view[..., :c],
                                       cache_pos[:, None], k_positions, 0,
                                       dims.scale_dim)
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        attn = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, c)
    return _mla_out(params, attn, dims, x), pool_c


def mla_attention_prefill_chunk_paged(params, x, dims: MLADims, pool_c,
                                      block_tables, positions, write_floor,
                                      impl: str = "kernel"):
    """Multi-token MLA prefill chunk splicing latent rows DIRECTLY into the
    page pool (incremental splice) and attending through the block table —
    the latent twin of ``attention_prefill_chunk_paged``. Rows below
    ``write_floor`` (aliased prefix pages) are dropped, exactly as in the
    K/V path: COW materialisation copies latent rows, never per-head K/V.
    Returns (out (B,C,D), new_pool)."""
    H, c = dims.num_heads, dims.kv_lora_rank
    q = mla_absorbed_queries(params, x, dims, positions)     # (B,C,H,c+r)
    rows = mla_latent_rows(params, x, dims, positions)       # (B,C,1,c+r)
    B, C = positions.shape
    P, ps = pool_c.shape[:2]
    mps = block_tables.shape[1]
    n_rows = mps * ps

    page = jnp.take_along_axis(
        block_tables, jnp.clip(positions // ps, 0, mps - 1), axis=1)
    w_ok = ((page >= 0) & (positions >= write_floor)
            & (positions >= 0) & (positions < n_rows))
    w_rows = jnp.where(w_ok, page * ps + positions % ps, P * ps)  # drop
    flat = pool_c.reshape(P * ps, 1, dims.latent_dim)
    flat = flat.at[w_rows].set(rows.astype(flat.dtype), mode="drop")
    pool_c = flat.reshape(pool_c.shape)

    if impl == "kernel":
        from repro.kernels import ops as kops
        from repro.sharding import specs as _sp
        tp_mesh, tp_axis = _sp.latent_head_shard_axis(H)
        attn = kops.paged_prefill_latent(q, pool_c, block_tables,
                                         positions[:, 0],
                                         scale_dim=dims.scale_dim, d_v=c,
                                         mesh=tp_mesh, shard_axis=tp_axis)
    else:
        qg = q.reshape(B, C, 1, H, dims.latent_dim)
        phys, ok = paged_row_indices(block_tables, ps, n_rows)
        view = flat[phys]                        # (B, n_rows, 1, c+r)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, view.astype(q.dtype)
                            ).astype(jnp.float32) / math.sqrt(dims.scale_dim)
        k_pos = jnp.where(ok, jnp.arange(n_rows)[None, :], INACTIVE_POS)
        valid = k_pos[:, None, :] <= positions[:, :, None]   # (B,C,S)
        scores = jnp.where(valid[:, None, None, :, :], scores,
                           mask_value(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        attn = jnp.einsum("bkgqs,bskh->bqkgh", probs,
                          view[..., :c].astype(q.dtype)).reshape(B, C, H, c)
    return _mla_out(params, attn, dims, x), pool_c


# ---------------------------------------------------------------- MLP
def mlp_init(key, d_model: int, d_ff: int, gated: bool = True, bias: bool = False):
    ks = jax.random.split(key, 3)
    p = {"w_up": _dense(ks[0], (d_model, d_ff)),
         "w_down": _dense(ks[1], (d_ff, d_model), scale_dim=d_ff)}
    if gated:
        p["w_gate"] = _dense(ks[2], (d_model, d_ff))
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), jnp.float32)
        p["b_down"] = jnp.zeros((d_model,), jnp.float32)
    return p


def mlp_logical(gated: bool = True, bias: bool = False):
    p = {"w_up": ("fsdp", "d_ff"), "w_down": ("d_ff", "fsdp")}
    if gated:
        p["w_gate"] = ("fsdp", "d_ff")
    if bias:
        p["b_up"] = ("d_ff",)
        p["b_down"] = (None,)
    return p


def mlp(params, x, act: str = "silu"):
    up = x @ params["w_up"].astype(x.dtype)
    if "b_up" in params:
        up = up + params["b_up"].astype(x.dtype)
    if "w_gate" in params:
        gate = x @ params["w_gate"].astype(x.dtype)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up) if act == "gelu" else jax.nn.silu(up)
    h = shard(h, "batch", None, "d_ff")
    out = h @ params["w_down"].astype(x.dtype)
    if "b_down" in params:
        out = out + params["b_down"].astype(x.dtype)
    # constrain the row-parallel output to sequence-parallel BEFORE the
    # residual add so the TP reduction lowers to reduce-scatter, not
    # all-reduce (hillclimb C iteration 4: 1/TP the reduction wire bytes)
    if out.ndim == 3 and out.shape[1] > 1:
        out = shard(out, "batch", "seq_sp", None)
    return out


# ---------------------------------------------------------------- MoE
@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    d_ff: int
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 128     # tokens per dispatch group (GShard-style)


def moe_init(key, dims: MoEDims):
    ks = jax.random.split(key, 4)
    E, D, F = dims.num_experts, dims.d_model, dims.d_ff
    return {
        "router": _dense(ks[0], (D, E)),
        "w_gate": _dense(ks[1], (E, D, F), scale_dim=D),
        "w_up": _dense(ks[2], (E, D, F), scale_dim=D),
        "w_down": _dense(ks[3], (E, F, D), scale_dim=F),
    }


def moe_logical():
    return {
        "router": (None, None),
        "w_gate": ("expert", "fsdp", None),
        "w_up": ("expert", "fsdp", None),
        "w_down": ("expert", None, "fsdp"),
    }


def moe(params, x, dims: MoEDims):
    """Grouped-capacity top-k MoE (GShard dispatch), expert-parallel over the
    'expert' logical axis. x: (B, S, D) -> (B, S, D), plus aux losses."""
    B, S, D = x.shape
    E, K = dims.num_experts, dims.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt @ params["router"].astype(jnp.float32)).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)                            # (T,K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses (Switch-style load balance + router z-loss)
    me = probs.mean(0)                                     # (E,)
    onehot_top1 = jax.nn.one_hot(expert_idx[:, 0], E)
    ce = onehot_top1.mean(0)
    aux_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- grouped dispatch with fixed capacity
    G = max(1, T // dims.group_size)
    Sg = T // G
    cap = max(1, int(math.ceil(Sg * K / E * dims.capacity_factor)))
    xg = shard(xt.reshape(G, Sg, D), "batch", None, None)
    idx_g = expert_idx.reshape(G, Sg, K)
    gate_g = gate_vals.reshape(G, Sg, K)

    # position of each (token, k) within its expert's capacity buffer.
    # Everything carrying an E axis is sharded over 'expert' as well as the
    # token-group axis — these (G,Sg,K,E[,cap]) tensors are the MoE dispatch
    # working set and dominate backward memory if left expert-replicated.
    eo = jax.nn.one_hot(idx_g, E, dtype=jnp.int32)          # (G,Sg,K,E)
    eo = shard(eo, "batch", None, None, "expert")
    flat = eo.reshape(G, Sg * K, E)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat              # (G,Sg*K,E)
    pos = pos_in_e.reshape(G, Sg, K, E)
    slot = (pos * eo).sum(-1)                               # (G,Sg,K)
    keep = (slot < cap) & (gate_g > 0)
    gate_g = jnp.where(keep, gate_g, 0.0)

    # dispatch/combine one-hots: (G,Sg,K,E,cap) folded over K -> (G,Sg,E,cap)
    kec = (jax.nn.one_hot(idx_g, E, dtype=jnp.float32)[..., None]
           * jax.nn.one_hot(slot, cap, dtype=jnp.float32)[..., None, :]
           * keep[..., None, None].astype(jnp.float32))
    kec = shard(kec, "batch", None, None, "expert", None)
    disp = shard(kec.sum(2).astype(x.dtype), "batch", None, "expert", None)
    comb = shard((kec * gate_g[..., None, None]).sum(2),
                 "batch", None, "expert", None)

    # expert inputs: (E, G, cap, D) — sharded 'expert' x 'batch' (all_to_all here)
    ein = jnp.einsum("gsec,gsd->egcd", disp, xg)
    ein = shard(ein, "expert", "batch", None, None)
    h = jnp.einsum("egcd,edf->egcf", ein, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("egcd,edf->egcf", ein, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(h) * u
    eout = jnp.einsum("egcf,efd->egcd", h, params["w_down"].astype(x.dtype))
    eout = shard(eout, "expert", "batch", None, None)

    out = jnp.einsum("gsec,egcd->gsd", comb.astype(x.dtype), eout)
    return out.reshape(B, S, D), {"moe_aux": aux_loss, "moe_z": z_loss}


# ---------------------------------------------------------------- embeddings
def embed_init(key, padded_vocab: int, d_model: int):
    """Table rows are the PADDED vocab (configs.base.ArchConfig.padded_vocab)
    so the vocab dim shards evenly; lm_logits masks the padding columns."""
    return {"table": scaled_normal(key, (padded_vocab, d_model), 0.02)}


def embed_logical():
    return {"table": ("vocab", "fsdp")}


def embed_lookup(params, ids, dtype):
    return params["table"].astype(dtype)[ids]


def lm_logits(params_embed, x, w_unembed=None, vocab: Optional[int] = None):
    """x:(B,S,D) -> (B,S,V_padded), padding columns masked to -inf.
    Uses the tied embedding table if w_unembed is None."""
    table = w_unembed if w_unembed is not None else params_embed["table"]
    logits = x @ table.astype(x.dtype).T if w_unembed is None else x @ table.astype(x.dtype)
    logits = shard(logits, "batch", None, "vocab")
    vp = logits.shape[-1]
    if vocab is not None and vocab < vp:
        mask = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0) < vocab
        logits = jnp.where(mask, logits,
                           jnp.asarray(mask_value(logits.dtype), logits.dtype))
    return logits
