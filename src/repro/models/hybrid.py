"""Hymba — hybrid-head LM: each layer runs sliding-window GQA attention and a
selective-SSM (mamba-style, state=16) branch in parallel on the same input and
averages the normalized branch outputs (arXiv:2411.13676, simplified: meta
tokens omitted; windowed attention keeps long_500k sub-quadratic).

Decode uses a ring-buffer window KV cache (O(window), not O(seq)) + SSM state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.sharding.specs import shard

DT_RANK = 64
CONV_K = 4


def _attn_dims(cfg: ArchConfig) -> L.AttnDims:
    return L.AttnDims(d_model=cfg.d_model, num_heads=cfg.num_heads,
                      num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                      qkv_bias=False, window=cfg.window, rope_theta=cfg.rope_theta)


# ------------------------------------------------------------------ init
def _layer_init(key, cfg: ArchConfig):
    D, Nst = cfg.d_model, cfg.ssm_state
    ks = jax.random.split(key, 10)
    return {
        "ln1": L.norm_init(D, cfg.norm),
        "ln2": L.norm_init(D, cfg.norm),
        "attn": L.attn_init(ks[0], _attn_dims(cfg)),
        "attn_norm": L.norm_init(cfg.num_heads * cfg.head_dim, "rmsnorm"),
        "ssm_norm": L.norm_init(D, "rmsnorm"),
        "mlp": L.mlp_init(ks[1], D, cfg.d_ff, gated=True),
        # mamba branch
        "w_in": L._dense(ks[2], (D, D)),
        "w_out": L._dense(ks[3], (D, D)),
        "conv": L._dense(ks[4], (CONV_K, D)) * 0.1,
        "w_B": L._dense(ks[5], (D, Nst)),
        "w_C": L._dense(ks[6], (D, Nst)),
        "w_dtA": L._dense(ks[7], (D, DT_RANK)),
        "w_dtB": L._dense(ks[8], (DT_RANK, D)),
        "dt_bias": jnp.full((D,), -4.0, jnp.float32),
        "logA": jnp.zeros((D, Nst), jnp.float32),
        "d_skip": jnp.ones((D,), jnp.float32),
    }


def _layer_logical(cfg: ArchConfig):
    return {
        "ln1": L.norm_logical(cfg.norm), "ln2": L.norm_logical(cfg.norm),
        "attn": L.attn_logical(_attn_dims(cfg)),
        "attn_norm": L.norm_logical("rmsnorm"),
        "ssm_norm": L.norm_logical("rmsnorm"),
        "mlp": L.mlp_logical(gated=True),
        "w_in": ("fsdp", "d_ff"), "w_out": ("d_ff", "fsdp"),
        "conv": (None, "d_ff"),
        "w_B": ("fsdp", None), "w_C": ("fsdp", None),
        "w_dtA": ("fsdp", None), "w_dtB": (None, "d_ff"),
        "dt_bias": ("d_ff",), "logA": ("d_ff", None), "d_skip": ("d_ff",),
    }


def init(cfg: ArchConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    keys = jax.random.split(k2, cfg.num_layers)
    return {
        "embed": L.embed_init(k1, cfg.padded_vocab, cfg.d_model),
        "layers": jax.vmap(lambda kk: _layer_init(kk, cfg))(keys),
        "final_norm": L.norm_init(cfg.d_model, cfg.norm),
        "unembed": {"w": L._dense(k3, (cfg.d_model, cfg.padded_vocab))},
    }


def param_logical(cfg: ArchConfig):
    def stacked(tree):
        return jax.tree.map(lambda ax: (None,) + ax, tree,
                            is_leaf=lambda v: isinstance(v, tuple))
    return {
        "embed": L.embed_logical(),
        "layers": stacked(_layer_logical(cfg)),
        "final_norm": L.norm_logical(cfg.norm),
        "unembed": {"w": ("fsdp", "vocab")},
    }


# ------------------------------------------------------------------ SSM branch
def _ssm_scan(xin, dt, B_t, C_t, A, h0):
    """Selective scan. xin,dt: (B,T,D); B_t,C_t: (B,T,N); A: (D,N); h0: (B,D,N)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(A[None] * dt_t[..., None])               # (B,D,N)
        h = decay * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xin, dt, B_t, C_t))
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h


def _ssm_scan_assoc(xin, dt, B_t, C_t, A, h0):
    """Parallel (associative-scan) selective scan: the recurrence
    ``h_t = a_t * h_{t-1} + b_t`` is associative under
    ``(a1,b1) ∘ (a2,b2) = (a1*a2, a2*b1 + b2)``, so all T states come out of
    a log-depth ``lax.associative_scan`` instead of a length-T sequential
    scan — the recurrent carry stops being the prefill's critical path
    (same loop-width lever as the attention chunk). Same signature/returns
    as ``_ssm_scan``; h0 folds into step 0's additive term."""
    a = jnp.exp(A[None, None] * dt[..., None])               # (B,T,D,N)
    b = (dt * xin)[..., None] * B_t[:, :, None, :]           # (B,T,D,N)
    b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, hs = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = jnp.einsum("btdn,btn->btd", hs, C_t)
    return y, hs[:, -1]


def _mamba_branch(lp, x, cfg: ArchConfig, state, impl: str = "scan"):
    Bsz, T, D = x.shape
    xin = x @ lp["w_in"].astype(x.dtype)
    xin = shard(xin, "batch", None, "d_ff")
    # depthwise causal conv over time (kernel CONV_K)
    conv_w = lp["conv"].astype(x.dtype)                          # (K, D)
    tail = (state["conv"].astype(x.dtype) if state is not None
            else jnp.zeros((Bsz, CONV_K - 1, D), x.dtype))
    xpad = jnp.concatenate([tail, xin], axis=1)
    xc = sum(xpad[:, i:i + T] * conv_w[i] for i in range(CONV_K))
    xc = jax.nn.silu(xc)

    f32 = jnp.float32
    dt = jax.nn.softplus((xc.astype(f32) @ lp["w_dtA"].astype(f32))
                         @ lp["w_dtB"].astype(f32) + lp["dt_bias"])
    B_t = xc.astype(f32) @ lp["w_B"].astype(f32)
    C_t = xc.astype(f32) @ lp["w_C"].astype(f32)
    A = -jnp.exp(lp["logA"].astype(f32))
    h0 = (state["h"].astype(f32) if state is not None
          else jnp.zeros((Bsz, D, cfg.ssm_state), f32))
    if impl == "pallas":
        from repro.kernels import ops as kops
        y, h = kops.selective_scan(xc.astype(f32), dt, B_t, C_t, A, h0)
    elif impl == "assoc":
        y, h = _ssm_scan_assoc(xc.astype(f32), dt, B_t, C_t, A, h0)
    else:
        y, h = _ssm_scan(xc.astype(f32), dt, B_t, C_t, A, h0)
    y = y + lp["d_skip"].astype(f32) * xc.astype(f32)
    out = y.astype(x.dtype) @ lp["w_out"].astype(x.dtype)
    new_state = {"h": h, "conv": xpad[:, -(CONV_K - 1):].astype(f32)}
    return out, new_state


def _layer_apply(cfg, lp, x, positions, attn_impl, ssm_impl="scan"):
    h = L.apply_norm(x, lp["ln1"], cfg.norm)
    a = L.attention(lp["attn"], h, _attn_dims(cfg), positions, impl=attn_impl)
    s, _ = _mamba_branch(lp, h, cfg, None, ssm_impl)
    a = L.rmsnorm(a, lp["attn_norm"]["scale"])
    s = L.rmsnorm(s, lp["ssm_norm"]["scale"])
    x = x + 0.5 * (a + s)
    x = shard(x, "batch", "seq_sp", None)
    h = L.apply_norm(x, lp["ln2"], cfg.norm)
    x = shard(x + L.mlp(lp["mlp"], h), "batch", "seq_sp", None)
    return x


# ------------------------------------------------------------------ public
def forward(params, cfg: ArchConfig, tokens, *, compute_dtype=jnp.bfloat16,
            attn_impl: str = "einsum", remat: bool = False, scan_impl: str = "scan",
            return_features: bool = False, **_):
    B, S = tokens.shape
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)
    x = shard(x, "batch", "seq_sp", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, lp):
        return _layer_apply(cfg, lp, x, positions, attn_impl, scan_impl), jnp.zeros(())
    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    if return_features:
        return x, {"moe_aux": jnp.zeros(()), "moe_z": jnp.zeros(())}
    logits = L.lm_logits(params["embed"], x, params["unembed"]["w"], vocab=cfg.vocab_size)
    return logits.astype(jnp.float32), {"moe_aux": jnp.zeros(()), "moe_z": jnp.zeros(())}


def init_cache(cfg: ArchConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    """Ring-buffer window KV cache + SSM state: O(window + state), not O(s_max)."""
    W = min(cfg.window, s_max)
    Lr, KV, hd, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "k": jnp.zeros((Lr, batch, W, KV, hd), dtype),
        "v": jnp.zeros((Lr, batch, W, KV, hd), dtype),
        "slot_pos": jnp.full((Lr, batch, W), -1, jnp.int32),
        "h": jnp.zeros((Lr, batch, D, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((Lr, batch, CONV_K - 1, D), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_logical(cfg: ArchConfig):
    return {"k": (None, "batch", None, "kv_heads", None),
            "v": (None, "batch", None, "kv_heads", None),
            "slot_pos": (None, "batch", None),
            "h": (None, "batch", "d_ff", None),
            "conv": (None, "batch", None, None),
            "pos": ()}


def _ring_sdpa(lp, h, q, ck, cv, valid, dims):
    """Masked attention over a ring/key view. q: (B,Sq,H,hd) as produced by
    ``L._qkv`` (an equivalent flat (B,Sq,H*hd) also works — (H, hd) and
    (KV, G, hd) are the same contiguous layout); ck/cv: (B,S,KV,hd);
    valid: (B,S) bool (decode: one query, mask shared) or (B,Sq,S)
    (prefill chunk: per-query mask). Shared by the dense ring path, the
    paged path, and the parallel prefill chunk so all three produce
    bit-identical outputs for equal views."""
    B, Sq = q.shape[0], q.shape[1]
    H, KV, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck.astype(q.dtype)) / math.sqrt(hd)
    if valid.ndim == 2:
        valid = valid[:, None, :]                        # (B,1,S): all queries
    scores = jnp.where(valid[:, None, None, :, :], scores.astype(jnp.float32),
                       L.mask_value(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, cv.astype(q.dtype)
                     ).reshape(B, Sq, H * hd)
    return out @ lp["attn"]["wo"].astype(h.dtype)


def _window_attn_decode(lp, h, cfg, ck, cv, slot_pos, pos, positions):
    """Decode attention over a ring-buffer window cache. ``pos`` is a scalar
    (lockstep batch) or a (B,) per-slot position vector (serving engine).
    Vector-pos writes from INACTIVE slots (pos >= layers.INACTIVE_POS — freed
    serving slots) are dropped, so a finished request's ring rows stay
    bit-stable while other slots keep decoding."""
    dims = _attn_dims(cfg)
    q, k, v = L._qkv(lp["attn"], h, dims, positions)
    W = ck.shape[1]
    B = q.shape[0]
    if jnp.ndim(pos) == 1:
        # per-slot ring-buffer writes: row b lands in ring slot pos[b] % W;
        # inactive rows are steered to index W and dropped by the scatter
        slot = jnp.where(pos < L.INACTIVE_POS, pos % W, W)
        b_idx = jnp.arange(B)
        ck = ck.at[b_idx, slot].set(k[:, 0].astype(ck.dtype), mode="drop")
        cv = cv.at[b_idx, slot].set(v[:, 0].astype(cv.dtype), mode="drop")
        slot_pos = slot_pos.at[b_idx, slot].set(pos, mode="drop")
        mask_pos = pos[:, None]                              # (B,1) -> (B,W)
    else:
        slot = pos % W
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), slot, axis=1)
        slot_pos = jax.lax.dynamic_update_slice_in_dim(
            slot_pos, jnp.broadcast_to(pos, slot_pos[:, :1].shape), slot, axis=1)
        mask_pos = pos
    valid = (slot_pos >= 0) & (slot_pos <= mask_pos) & \
        (slot_pos > mask_pos - cfg.window)
    out = _ring_sdpa(lp, h, q, ck, cv, valid, dims)
    return out, ck, cv, slot_pos


def _window_attn_decode_paged(lp, h, cfg, pool_k, pool_v, pool_spos,
                              block_tables, ring_w: int, pos, positions):
    """Paged ring-buffer decode attention: the ring's W rows live in shared
    pages reached through per-slot block tables (models/layers.py paged
    helpers). pool_k/v: (P, ps, KV, hd); pool_spos: (P, ps) absolute position
    per pool row (-1 = never written); pos: (B,) per-slot positions.

    The gathered view is exactly ``ring_w`` rows in ring order, so for equal
    page contents this is bit-identical to the dense ring path (_ring_sdpa is
    shared); rows of unallocated pages are masked out, matching the dense
    ring's never-written slot_pos == -1 rows."""
    dims = _attn_dims(cfg)
    q, k, v = L._qkv(lp["attn"], h, dims, positions)
    ps = pool_k.shape[1]

    # write: ring index pos % W -> page block_tables[b, idx // ps]
    ridx = jnp.where(pos < L.INACTIVE_POS, pos % ring_w, 0)
    w_row, page_ok = L.paged_write_target(block_tables, ridx, ps)
    w_ok = (pos < L.INACTIVE_POS) & page_ok
    def write(pool, rows):               # one layer's pool: a stack of one
        return L.paged_write_layer(pool[None], 0, rows, w_row, w_ok)[0]
    pool_k = write(pool_k, k[:, 0])
    pool_v = write(pool_v, v[:, 0])
    pool_spos = write(pool_spos, pos)

    # read: gather the W-row ring view through the block table
    phys, ok = L.paged_row_indices(block_tables, ps, ring_w)
    KV, hd = dims.num_kv_heads, dims.head_dim
    view_k = pool_k.reshape(-1, KV, hd)[phys]        # (B, W, KV, hd)
    view_v = pool_v.reshape(-1, KV, hd)[phys]
    spos = jnp.where(ok, pool_spos.reshape(-1)[phys], -1)
    mask_pos = pos[:, None]
    valid = (spos >= 0) & (spos <= mask_pos) & (spos > mask_pos - cfg.window)
    out = _ring_sdpa(lp, h, q, view_k, view_v, valid, dims)
    return out, pool_k, pool_v, pool_spos


# ------------------------------------------------------- parallel prefill
def _window_attn_prefill_chunk(lp, h, cfg, ck, cv, slot_pos, positions,
                               use_kernel: bool):
    """Chunk-wide windowed attention against the ring cache: all C queries
    attend jointly over [pre-chunk ring rows (validity from slot_pos), the
    chunk's own K/V (causal + window)], then the chunk's LAST min(C, W)
    positions — exactly the rows a sequential ring write would leave behind
    — are scattered into the ring. ``use_kernel`` (first chunk only: the
    pre-ring is empty, so chunk-local causal+window IS the full mask) routes
    through the K/V-exporting flash kernel."""
    dims = _attn_dims(cfg)
    q, k, v = L._qkv(lp["attn"], h, dims, positions)         # (B,C,·)
    B, C = q.shape[:2]
    W = ck.shape[1]
    if use_kernel:
        from repro.kernels import ops as kops
        out, k, v = kops.flash_prefill(q, k, v, causal=True,
                                       window=cfg.window)
        out = out.reshape(B, C, -1) @ lp["attn"]["wo"].astype(h.dtype)
    else:
        keys = jnp.concatenate([ck.astype(q.dtype), k], axis=1)   # (B,W+C,·)
        vals = jnp.concatenate([cv.astype(q.dtype), v], axis=1)
        kp = jnp.concatenate([slot_pos, positions], axis=1)       # (B,W+C)
        qp = positions[:, :, None]
        valid = (kp[:, None, :] >= 0) & (kp[:, None, :] <= qp) & \
            (kp[:, None, :] > qp - cfg.window)                    # (B,C,W+C)
        out = _ring_sdpa(lp, h, q, keys, vals, valid, dims)
    # ring write: the last min(C, W) chunk positions have distinct ring
    # slots and are exactly the survivors of C sequential modular writes
    nw = min(C, W)
    tail_pos = positions[:, C - nw:]                              # (B,nw)
    ridx = tail_pos % W
    b_idx = jnp.arange(B)[:, None]
    ck = ck.at[b_idx, ridx].set(k[:, C - nw:].astype(ck.dtype))
    cv = cv.at[b_idx, ridx].set(v[:, C - nw:].astype(cv.dtype))
    slot_pos = slot_pos.at[b_idx, ridx].set(tail_pos)
    return out, ck, cv, slot_pos


def _prefill_chunk_layer(cfg, lp, x, ck, cv, sp, hst, conv, positions,
                         use_kernel):
    """One hybrid layer over a whole prompt chunk: windowed ring attention at
    chunk width + the mamba branch with its recurrent carry computed by the
    parallel associative scan. Mirrors ``_decode_layer``'s residual math."""
    h = L.apply_norm(x, lp["ln1"], cfg.norm)
    a, ck, cv, sp = _window_attn_prefill_chunk(lp, h, cfg, ck, cv, sp,
                                               positions, use_kernel)
    s, st = _mamba_branch(lp, h, cfg, {"h": hst, "conv": conv}, "assoc")
    a = L.rmsnorm(a, lp["attn_norm"]["scale"])
    s = L.rmsnorm(s, lp["ssm_norm"]["scale"])
    x = x + 0.5 * (a + s)
    h = L.apply_norm(x, lp["ln2"], cfg.norm)
    x = x + L.mlp(lp["mlp"], h)
    return x, ck, cv, sp, st["h"], st["conv"]


def prefill_chunk(params, cfg: ArchConfig, tokens, cache, *,
                  compute_dtype=jnp.bfloat16, attn_impl: str = "einsum",
                  first: bool = False, **_):
    """Matmul-wide parallel prefill over one prompt chunk (hybrid family):
    the attention branch runs chunk-wide against the ring, the selective-SSM
    carry comes out of a log-depth associative scan, and the ring + recurrent
    state land in the request cache exactly as C sequential ``decode_step``
    calls would have left them. Returns (last logits (B,1,Vp), cache)."""
    B, C = tokens.shape
    start = jnp.zeros((), jnp.int32) if first else cache["pos"]
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    use_kernel = first and attn_impl == "pallas"
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)

    def body(x, xs):
        lp, ck, cv, sp, hst, conv = xs
        x, ck, cv, sp, hh, cc = _prefill_chunk_layer(
            cfg, lp, x, ck, cv, sp, hst, conv, positions, use_kernel)
        return x, (ck, cv, sp, hh, cc)

    x, (ck, cv, sp, hst, conv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["slot_pos"],
                  cache["h"], cache["conv"]))
    x = L.apply_norm(x[:, -1:], params["final_norm"], cfg.norm)
    logits = L.lm_logits(params["embed"], x, params["unembed"]["w"],
                         vocab=cfg.vocab_size)
    return logits.astype(jnp.float32), dict(cache, k=ck, v=cv, slot_pos=sp,
                                            h=hst, conv=conv, pos=start + C)


def _decode_layer(cfg, lp, x, ck, cv, sp, hst, conv, pos, positions,
                  block_tables=None, ring_w: int = 0):
    """One hybrid decode layer (windowed ring-buffer attention + SSM state).
    Exposed for roofline probes. With ``block_tables``, ck/cv/sp are one
    layer's page-pool slices and attention uses the paged ring path."""
    h = L.apply_norm(x, lp["ln1"], cfg.norm)
    if block_tables is not None:
        a, ck, cv, sp = _window_attn_decode_paged(
            lp, h, cfg, ck, cv, sp, block_tables, ring_w, pos, positions)
    else:
        a, ck, cv, sp = _window_attn_decode(lp, h, cfg, ck, cv, sp, pos,
                                            positions)
    # freed serving slots keep their recurrent h/conv bit-for-bit
    s, st = _mamba_branch(lp, h, cfg, {"h": hst, "conv": conv})
    st = L.freeze_inactive_rows(pos, st, {"h": hst, "conv": conv})
    a = L.rmsnorm(a, lp["attn_norm"]["scale"])
    s = L.rmsnorm(s, lp["ssm_norm"]["scale"])
    x = x + 0.5 * (a + s)
    h = L.apply_norm(x, lp["ln2"], cfg.norm)
    x = x + L.mlp(lp["mlp"], h)
    return x, ck, cv, sp, st["h"], st["conv"]


def decode_step(params, cfg: ArchConfig, token, cache, *, compute_dtype=jnp.bfloat16,
                **_):
    B = token.shape[0]
    pos = cache["pos"]
    bt = cache.get("block_tables")
    # paged caches carry a (W,) iota leaf whose SHAPE is the ring width — the
    # one static the paged ring path needs that pool shapes cannot express
    ring_w = cache["ring_iota"].shape[0] if bt is not None else 0
    positions = L.decode_positions(pos, B)
    x = L.embed_lookup(params["embed"], token, compute_dtype)

    def body(x, xs):
        lp, ck, cv, sp, hst, conv = xs
        x, ck, cv, sp, hh, cc = _decode_layer(cfg, lp, x, ck, cv, sp, hst, conv,
                                              pos, positions, bt, ring_w)
        return x, (ck, cv, sp, hh, cc)

    x, (ck, cv, sp, hst, conv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["slot_pos"],
                  cache["h"], cache["conv"]))
    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    logits = L.lm_logits(params["embed"], x, params["unembed"]["w"], vocab=cfg.vocab_size)
    new_cache = dict(cache, k=ck, v=cv, slot_pos=sp, h=hst, conv=conv,
                     pos=pos + 1)
    return logits.astype(jnp.float32), new_cache
