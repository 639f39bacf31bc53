"""Decoder-only transformer LM covering the dense, MoE, and VLM (interleaved
cross-attention) families. Layers are stacked along a leading axis and executed
with ``lax.scan`` so 40–64-layer configs lower/compile quickly at 512 devices.

Public API (used by models.registry):
    init(cfg, key)                      -> params
    param_logical(cfg)                  -> pytree of logical axis tuples
    forward(params, cfg, tokens, ...)   -> logits, aux      (train / prefill)
    init_cache(cfg, batch, s_max, ...)  -> cache
    decode_step(params, cfg, token, cache, ...) -> logits, cache
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Family
from repro.models import layers as L
from repro.sharding.specs import shard


# ------------------------------------------------------------------ helpers
def _attn_dims(cfg: ArchConfig, causal: bool = True) -> L.AttnDims:
    return L.AttnDims(
        d_model=cfg.d_model, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, qkv_bias=cfg.qkv_bias, window=cfg.window,
        rope_theta=cfg.rope_theta, causal=causal)


def _mla_dims(cfg: ArchConfig) -> L.MLADims:
    return L.MLADims(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank, qk_rope_head_dim=cfg.qk_rope_head_dim,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        q_lora_rank=cfg.q_lora_rank, rope_scaling=cfg.rope_scaling)


def _is_mla(cfg: ArchConfig) -> bool:
    """Static (trace-time) MLA gate. MLA replaces per-head K/V with a latent
    cache; it is defined for plain causal decoder stacks only (no sliding
    window, no interleaved cross-attention)."""
    if not cfg.kv_lora_rank:
        return False
    if cfg.window or cfg.cross_attn_every:
        raise ValueError("MLA (kv_lora_rank > 0) supports only full-causal "
                         "decoder stacks (no window / cross-attn)")
    return True


def _cross_dims(cfg: ArchConfig) -> L.AttnDims:
    d = _attn_dims(cfg, causal=False)
    return L.AttnDims(**{**d.__dict__, "causal": False, "window": 0, "rope_theta": 0.0})


def _moe_dims(cfg: ArchConfig) -> L.MoEDims:
    m = cfg.moe
    f = m.d_ff or cfg.d_ff
    return L.MoEDims(d_model=cfg.d_model, d_ff=f,
                     num_experts=m.num_experts, top_k=m.top_k,
                     capacity_factor=m.capacity_factor,
                     shared_d_ff=m.shared_experts * f, scoring=m.scoring,
                     n_group=m.n_group, topk_group=m.topk_group,
                     routed_scale=m.routed_scale, held=m.held)


def _n_dense(cfg: ArchConfig) -> int:
    """Leading dense layers of a MoE stack (kept in ``dense_layers``)."""
    return cfg.first_k_dense if cfg.moe else 0


def _remat_policy(remat):
    """remat=True/'nothing' -> save only layer inputs; 'save_outs' -> also
    keep the named post-collective attention/MLP outputs (skips their
    recompute — and the recomputed collectives — in backward)."""
    if remat == "save_outs":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_out")
    return jax.checkpoint_policies.nothing_saveable


def _gated(cfg: ArchConfig) -> bool:
    return cfg.norm == "rmsnorm" or cfg.family in (Family.DENSE, Family.MOE, Family.VLM)


# ------------------------------------------------------------------ init
def _layer_init(key, cfg: ArchConfig, moe: bool = True):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.norm_init(cfg.d_model, cfg.norm),
        "attn": (L.mla_init(ks[0], _mla_dims(cfg)) if _is_mla(cfg)
                 else L.attn_init(ks[0], _attn_dims(cfg))),
        "ln2": L.norm_init(cfg.d_model, cfg.norm),
    }
    if cfg.moe and moe:
        p["moe"] = L.moe_init(ks[1], _moe_dims(cfg))
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, gated=_gated(cfg),
                              bias=cfg.mlp_bias)
    return p


def _layer_logical(cfg: ArchConfig, moe: bool = True):
    p = {
        "ln1": L.norm_logical(cfg.norm),
        "attn": (L.mla_logical(_mla_dims(cfg)) if _is_mla(cfg)
                 else L.attn_logical(_attn_dims(cfg))),
        "ln2": L.norm_logical(cfg.norm),
    }
    if cfg.moe and moe:
        p["moe"] = L.moe_logical(_moe_dims(cfg))
    else:
        p["mlp"] = L.mlp_logical(gated=_gated(cfg), bias=cfg.mlp_bias)
    return p


def _cross_init(key, cfg: ArchConfig):
    return {"ln": L.norm_init(cfg.d_model, cfg.norm),
            "attn": L.attn_init(key, _cross_dims(cfg)),
            "gate": jnp.zeros((), jnp.float32)}


def _cross_logical(cfg: ArchConfig):
    return {"ln": L.norm_logical(cfg.norm),
            "attn": L.attn_logical(_cross_dims(cfg)),
            "gate": ()}


def _stack(key, n, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init(cfg: ArchConfig, key) -> dict:
    k_emb, k_layers, k_cross, k_head = jax.random.split(key, 4)
    params = {
        "embed": L.embed_init(k_emb, cfg.padded_vocab, cfg.d_model),
        "final_norm": L.norm_init(cfg.d_model, cfg.norm),
    }
    if cfg.cross_attn_every:
        per = cfg.cross_attn_every
        n_super = cfg.num_layers // per
        def super_init(k):
            k1, k2 = jax.random.split(k)
            return {"blocks": _stack(k1, per, lambda kk: _layer_init(kk, cfg)),
                    "cross": _cross_init(k2, cfg)}
        params["super"] = _stack(k_layers, n_super, super_init)
    else:
        # layer i takes key i of one split, whichever stack holds it
        keys = jax.random.split(k_layers, cfg.num_layers)
        k = _n_dense(cfg)
        if k:
            params["dense_layers"] = jax.vmap(
                lambda kk: _layer_init(kk, cfg, moe=False))(keys[:k])
        params["layers"] = jax.vmap(lambda kk: _layer_init(kk, cfg))(keys[k:])
    if not cfg.tie_embeddings:
        params["unembed"] = {"w": L._dense(k_head, (cfg.d_model, cfg.padded_vocab))}
    return params


def param_logical(cfg: ArchConfig) -> dict:
    def stacked(tree):  # prepend None for the layer-stack dim
        return jax.tree.map(lambda ax: (None,) + ax, tree,
                            is_leaf=lambda v: isinstance(v, tuple) and not isinstance(v, dict))
    out = {
        "embed": L.embed_logical(),
        "final_norm": L.norm_logical(cfg.norm),
    }
    if cfg.cross_attn_every:
        out["super"] = {"blocks": stacked(stacked(_layer_logical(cfg))),
                        "cross": stacked(_cross_logical(cfg))}
    else:
        out["layers"] = stacked(_layer_logical(cfg))
        if _n_dense(cfg):
            out["dense_layers"] = stacked(_layer_logical(cfg, moe=False))
    if not cfg.tie_embeddings:
        out["unembed"] = {"w": ("fsdp", "vocab")}
    return out


def _super_apply_unrolled(cfg: ArchConfig, sp, x, positions, img, attn_impl):
    """One VLM super-layer (cross_attn_every dense layers + cross block) with
    the inner loop unrolled — used by roofline probes so no nested scan hides
    FLOPs from cost_analysis."""
    for i in range(cfg.cross_attn_every):
        lp = jax.tree.map(lambda t: t[i], sp["blocks"])
        x, _ = _layer_apply(cfg, lp, x, positions, attn_impl)
    return _cross_apply(cfg, sp["cross"], x, img, attn_impl)


def _super_decode_unrolled(cfg: ArchConfig, sp, x, ck, cv, img, pos, positions,
                           block_tables=None, paged_impl: str = "einsum",
                           kscale=None, vscale=None):
    quantized = kscale is not None
    cks, cvs, kss, vss = [], [], [], []
    for i in range(cfg.cross_attn_every):
        lp = jax.tree.map(lambda t: t[i], sp["blocks"])
        if quantized:
            x, c1, c2, s1, s2 = _decode_layer(cfg, lp, x, ck[i], cv[i], pos,
                                              positions, block_tables,
                                              paged_impl, kscale[i], vscale[i])
            kss.append(s1)
            vss.append(s2)
        else:
            x, c1, c2 = _decode_layer(cfg, lp, x, ck[i], cv[i], pos, positions,
                                      block_tables, paged_impl)
        cks.append(c1)
        cvs.append(c2)
    x = _cross_apply(cfg, sp["cross"], x, img, "einsum")
    if quantized:
        return x, jnp.stack(cks), jnp.stack(cvs), jnp.stack(kss), jnp.stack(vss)
    return x, jnp.stack(cks), jnp.stack(cvs)


# ------------------------------------------------------------------ forward
def _mla_full_attention(cfg: ArchConfig, lp_attn, h, positions):
    """Full-sequence MLA attention for the train/forward path: one prefill
    chunk spanning the whole sequence against a transient latent cache —
    the same absorbed op order every serving path uses."""
    dims = _mla_dims(cfg)
    B, S, _ = h.shape
    cache_c = jnp.zeros((B, S, 1, dims.latent_dim), h.dtype)
    out, _ = L.mla_attention_prefill_chunk(lp_attn, h, dims, cache_c,
                                           jnp.zeros((), jnp.int32), positions)
    return out


def _layer_apply(cfg: ArchConfig, lp, x, positions, attn_impl):
    from jax.ad_checkpoint import checkpoint_name
    h = L.apply_norm(x, lp["ln1"], cfg.norm)
    if _is_mla(cfg):
        a = _mla_full_attention(cfg, lp["attn"], h, positions)
    else:
        a = L.attention(lp["attn"], h, _attn_dims(cfg), positions,
                        impl=attn_impl)
    # named saves: under the 'save_outs' remat policy the backward pass reuses
    # these post-collective tensors instead of re-running attention/MLP (and
    # their all-to-all / all-reduce resharding) — hillclimb B iteration 2
    x = x + checkpoint_name(a, "attn_out")
    x = shard(x, "batch", "seq_sp", None)
    h = L.apply_norm(x, lp["ln2"], cfg.norm)
    no_aux = {"moe_aux": jnp.zeros(()), "moe_z": jnp.zeros(())}
    if "moe" in lp and _moe_dims(cfg).gshard:
        # training keeps the GShard capacity dispatch and its aux losses
        y, aux = L.moe(lp["moe"], h, _moe_dims(cfg))
    elif "moe" in lp:
        y, aux = L.moe_dropless(lp["moe"], h, _moe_dims(cfg))[0], no_aux
    else:
        y, aux = L.mlp(lp["mlp"], h), no_aux
    x = shard(x + checkpoint_name(y, "mlp_out"), "batch", "seq_sp", None)
    return x, aux


def _cross_apply(cfg: ArchConfig, cp, x, image_kv, attn_impl):
    """Gated cross-attention to (precomputed) image K/V embeds: (B, T_img, D)."""
    h = L.apply_norm(x, cp["ln"], cfg.norm)
    B, S, _ = x.shape
    t_img = image_kv.shape[1]
    img_pos = jnp.zeros((B, t_img), jnp.int32)
    dims = _cross_dims(cfg)
    # project image tokens with this layer's k/v weights
    k = (image_kv @ cp["attn"]["wk"].astype(x.dtype)).reshape(B, t_img, dims.num_kv_heads, dims.head_dim)
    v = (image_kv @ cp["attn"]["wv"].astype(x.dtype)).reshape(B, t_img, dims.num_kv_heads, dims.head_dim)
    out = L.attention(cp["attn"], h, dims, jnp.zeros((B, S), jnp.int32),
                      impl="einsum", kv_override=(k, v, img_pos))
    return x + jnp.tanh(cp["gate"]).astype(x.dtype) * out


def forward(params, cfg: ArchConfig, tokens, *, image_embeds=None,
            compute_dtype=jnp.bfloat16, attn_impl: str = "einsum",
            remat: bool = False, positions=None, return_features: bool = False):
    """tokens: (B, S) int32 -> logits (B, S, V) in float32, aux dict."""
    B, S = tokens.shape
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)
    x = shard(x, "batch", "seq_sp", None)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, lp):
        return _layer_apply(cfg, lp, x, positions, attn_impl)

    if remat:
        body = jax.checkpoint(body, policy=_remat_policy(remat))

    if cfg.cross_attn_every:
        assert image_embeds is not None, "VLM forward needs image_embeds"
        img = image_embeds.astype(compute_dtype)

        def super_body(x, sp):
            x, aux = jax.lax.scan(body, x, sp["blocks"])
            x = _cross_apply(cfg, sp["cross"], x, img, attn_impl)
            return x, jax.tree.map(jnp.sum, aux)
        if remat:
            super_body = jax.checkpoint(super_body,
                                        policy=jax.checkpoint_policies.nothing_saveable)
        x, aux = jax.lax.scan(super_body, x, params["super"])
    else:
        if "dense_layers" in params:
            x, aux_d = jax.lax.scan(body, x, params["dense_layers"])
        x, aux = jax.lax.scan(body, x, params["layers"])
        if "dense_layers" in params:
            aux = jax.tree.map(lambda a, b: jnp.sum(a) + jnp.sum(b), aux,
                               aux_d)

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    aux = jax.tree.map(jnp.sum, aux)
    if return_features:
        return x, aux
    w_un = params["unembed"]["w"] if not cfg.tie_embeddings else None
    logits = L.lm_logits(params["embed"], x, w_un, vocab=cfg.vocab_size)
    return logits.astype(jnp.float32), aux


# ------------------------------------------------------------------ decode
def init_cache(cfg: ArchConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    if _is_mla(cfg):
        # latent cache: ONE (c_kv + r)-wide row per token under the "k" key
        # (shaped like a single-kv-head cache so every generic splice/page
        # path applies unchanged); there is no "v" leaf — values are the
        # leading c_kv columns of the same rows, read via the absorb path.
        d = _mla_dims(cfg)
        shape = (cfg.num_layers, batch, s_max, 1, d.latent_dim)
        return {"k": jnp.zeros(shape, dtype), "pos": jnp.zeros((), jnp.int32)}
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (cfg.num_layers, batch, s_max, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def cache_logical(cfg: ArchConfig):
    """Adaptive: shard kv heads when they divide the model axis, else shard
    the cache sequence dim (context-parallel decode)."""
    from repro.sharding import specs as _sp
    if _is_mla(cfg):
        # the latent axis is shared by all heads — nothing head-like to shard
        return {"k": (None, "batch", None, None, None), "pos": ()}
    if cfg.num_kv_heads % max(_sp.axis_size("kv_heads"), 1) == 0:
        kv = (None, "batch", None, "kv_heads", None)
    else:
        kv = (None, "batch", "seq_sp", None, None)
    return {"k": kv, "v": kv, "pos": ()}


# The serving layers. Each takes one layer's cache as a dict ("k", and "v"
# for per-head K/V, "k_scale"/"v_scale" beside int8 pools; an MLA latent
# cache has "k" only) — per-layer arrays, but for a paged cache's pools,
# which come layer-stacked with the layer's index — and returns the
# layer's output, the updated dict and its MoE counts; ``_run_layers``
# drives them.
def _ffn(cfg: ArchConfig, lp, h):
    """(y, counts) of the layer's MLP, or of its MoE as serving computes
    it: dropless, only the held experts' part (``layers.moe_dropless``)."""
    if "moe" in lp:
        return L.moe_dropless(lp["moe"], h, _moe_dims(cfg))
    return L.mlp(lp["mlp"], h), jnp.zeros((2,), jnp.int32)


def _residual(cfg: ArchConfig, lp, x, attn_fn):
    """x + attention, then + the feed-forward: (x, cache dict, counts)."""
    out, c = attn_fn(L.apply_norm(x, lp["ln1"], cfg.norm))
    x = x + out
    y, counts = _ffn(cfg, lp, L.apply_norm(x, lp["ln2"], cfg.norm))
    return x + y, c, counts


def _decode_attn(cfg: ArchConfig, lp, h, c, pos, positions, bt, impl,
                 layer):
    """One decode token's attention against this layer's cache ``c``:
    dense rows, or a page pool read through ``bt`` with the Pallas kernel
    (``impl='kernel'``) or the masked-einsum reference. Page pools come
    layer-stacked, addressed at ``layer``."""
    if _is_mla(cfg):
        if bt is not None:
            out, k = L.mla_attention_decode_paged(
                lp["attn"], h, _mla_dims(cfg), c["k"], layer, bt, pos,
                positions, impl=impl)
        else:
            out, k = L.mla_attention_decode(lp["attn"], h, _mla_dims(cfg),
                                            c["k"], pos, positions)
        return out, {"k": k}
    dims = _attn_dims(cfg)
    if bt is None:
        out, k, v = L.attention_decode(lp["attn"], h, dims, c["k"], c["v"],
                                       pos, positions)
        return out, {"k": k, "v": v}
    if "k_scale" in c:
        out, k, v, ks, vs = L.attention_decode_paged(
            lp["attn"], h, dims, c["k"], c["v"], bt, pos, positions,
            impl=impl, layer=layer, k_scale=c["k_scale"],
            v_scale=c["v_scale"])
        return out, {"k": k, "v": v, "k_scale": ks, "v_scale": vs}
    out, k, v = L.attention_decode_paged(lp["attn"], h, dims, c["k"],
                                         c["v"], bt, pos, positions,
                                         impl=impl, layer=layer)
    return out, {"k": k, "v": v}


def _chunk_attn(cfg: ArchConfig, lp, h, c, start, positions, use_kernel):
    """A prompt chunk's attention, its rows written into the request's
    dense cache ``c`` (the transient prefill path)."""
    if _is_mla(cfg):
        out, k = L.mla_attention_prefill_chunk(lp["attn"], h, _mla_dims(cfg),
                                               c["k"], start, positions)
        return out, {"k": k}
    out, k, v = L.attention_prefill_chunk(lp["attn"], h, _attn_dims(cfg),
                                          c["k"], c["v"], start, positions,
                                          use_kernel=use_kernel)
    return out, {"k": k, "v": v}


def _chunk_paged_attn(cfg: ArchConfig, lp, h, c, bt, positions, write_floor,
                      impl, layer):
    """A prompt chunk's attention, its rows spliced into the page pool
    (rows below ``write_floor`` dropped) and read through ``bt``; the
    pools come layer-stacked, addressed at ``layer``."""
    if _is_mla(cfg):
        out, k = L.mla_attention_prefill_chunk_paged(
            lp["attn"], h, _mla_dims(cfg), c["k"], layer, bt, positions,
            write_floor, impl=impl)
        return out, {"k": k}
    dims = _attn_dims(cfg)
    if "k_scale" in c:
        out, k, v, ks, vs = L.attention_prefill_chunk_paged(
            lp["attn"], h, dims, c["k"], c["v"], bt, positions, write_floor,
            impl=impl, layer=layer, k_scale=c["k_scale"],
            v_scale=c["v_scale"])
        return out, {"k": k, "v": v, "k_scale": ks, "v_scale": vs}
    out, k, v = L.attention_prefill_chunk_paged(
        lp["attn"], h, dims, c["k"], c["v"], bt, positions, write_floor,
        impl=impl, layer=layer)
    return out, {"k": k, "v": v}


def _index_tree(tree, i):
    return jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False), tree)


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_params(stack, j):
    """Layer ``j``'s parameters out of a layer stack, but for a MoE
    layer's expert weights, which stay stacked with the layer's index
    beside them (``layers.moe_dropless``): XLA's grouped matmul takes its
    operand whole, so a slice of them would copy the layer's experts."""
    lp = _index_tree({k: v for k, v in stack.items() if k != "moe"}, j)
    if "moe" in stack:
        moe = stack["moe"]
        lp["moe"] = _index_tree({k: v for k, v in moe.items()
                                 if k not in _EXPERT_WEIGHTS}, j)
        lp["moe"].update({k: moe[k] for k in _EXPERT_WEIGHTS}, layer=j)
    return lp


# the cache leaves stacked over layers (everything else is per slot)
_LAYER_LEAVES = ("k", "v", "k_scale", "v_scale")
# of those, the page pools of a paged cache
_POOL_LEAVES = ("k", "v")


def _run_layers(params, cfg: ArchConfig, x, cache, layer_fn):
    """Every decoder layer over ``x``: a fori_loop per stack (the leading
    ``dense_layers``, then ``layers``) carrying x, the cache's layer-
    stacked leaves and the MoE counts summed over layers. A paged cache
    (one with ``block_tables``) gives its page pools — K and V, or the
    latent pool — to every layer whole, with the layer's index, and takes
    them back whole: the layer writes its new rows into the stack and
    its kernel reads the layer's pages there, so no layer's pool is copied
    out of the stack and back. Every other stacked leaf (a dense cache's
    rows, the int8 pools' few-KB scale tables, which the kernels prefetch
    whole into scalar memory) goes as slice i, updated back into the
    stack: a lax.scan over per-layer cache slices would stack fresh output
    buffers (a full extra cache copy in HBM), because XLA cannot alias
    scan ys to donated inputs. ``layer_fn(lp, x, c, i) -> (x, c, counts)``
    with ``c`` layer i's dict of those leaves. Returns (x, stacked leaves,
    counts)."""
    stacked = {k: cache[k] for k in _LAYER_LEAVES if k in cache}
    whole = _POOL_LEAVES if "block_tables" in cache else ()

    def loop(name, lo, hi, carry):
        def body(i, carry):
            x, st, counts = carry
            c = {k: v if k in whole else
                 jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                 for k, v in st.items()}
            x, c, n = layer_fn(_layer_params(params[name], i - lo), x, c, i)
            st = {k: c[k] if k in whole else
                  jax.lax.dynamic_update_index_in_dim(st[k], c[k], i, 0)
                  for k in st}
            return x, st, counts + n
        return jax.lax.fori_loop(lo, hi, body, carry)

    carry = (x, stacked, jnp.zeros((2,), jnp.int32))
    k = _n_dense(cfg)
    if k:
        carry = loop("dense_layers", 0, k, carry)
    return loop("layers", k, cfg.num_layers, carry)


def _logits(params, cfg: ArchConfig, x):
    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    w_un = params["unembed"]["w"] if not cfg.tie_embeddings else None
    return L.lm_logits(params["embed"], x, w_un,
                       vocab=cfg.vocab_size).astype(jnp.float32)


def _decode_layer(cfg: ArchConfig, lp, x, ck, cv, pos, positions,
                  block_tables=None, paged_impl: str = "einsum",
                  kscale=None, vscale=None):
    """One per-head-K/V decode layer: returns (x, new_ck, new_cv). Exposed
    for roofline probes (launch/probes.py) and the VLM super-layer. When
    ``block_tables`` is given, ck/cv are one layer's (P, ps, KV, hd)
    page-pool slice, handed to the paged path (models/layers.py) as a
    stack of one; ``paged_impl`` selects the Pallas kernel or the
    masked-einsum reference read. ``kscale``/``vscale`` are this layer's
    (P, tp) per-page per-kv-head-group dequant scales for int8 pools; when
    given the return grows to (x, ck, cv, kscale, vscale)."""
    paged = block_tables is not None
    c = {"k": ck[None], "v": cv[None]} if paged else {"k": ck, "v": cv}
    if kscale is not None:
        c.update(k_scale=kscale, v_scale=vscale)
    x, c, _ = _residual(cfg, lp, x, lambda h: _decode_attn(
        cfg, lp, h, c, pos, positions, block_tables, paged_impl,
        0 if paged else None))
    if paged:
        c.update(k=c["k"][0], v=c["v"][0])
    if kscale is not None:
        return x, c["k"], c["v"], c["k_scale"], c["v_scale"]
    return x, c["k"], c["v"]


# ------------------------------------------------------- parallel prefill
def _prefill_chunk_layer(cfg: ArchConfig, lp, x, ck, cv, start, positions,
                         use_kernel: bool):
    """One layer over a whole prompt chunk (matmul-wide ``_decode_layer``):
    writes the chunk's K/V rows into the per-request cache and attends all
    chunk positions jointly. Mirrors ``_decode_layer``'s math exactly (same
    residual structure, same masked-softmax validity) so the parallel
    prefill reproduces the scan-prefill anchor's greedy tokens."""
    x, c, _ = _residual(cfg, lp, x, lambda h: _chunk_attn(
        cfg, lp, h, {"k": ck, "v": cv}, start, positions, use_kernel))
    return x, c["k"], c["v"]


def _super_prefill_chunk_unrolled(cfg: ArchConfig, sp, x, ck, cv, img, start,
                                  positions, use_kernel):
    cks, cvs = [], []
    for i in range(cfg.cross_attn_every):
        lp = jax.tree.map(lambda t: t[i], sp["blocks"])
        x, c1, c2 = _prefill_chunk_layer(cfg, lp, x, ck[i], cv[i], start,
                                         positions, use_kernel)
        cks.append(c1)
        cvs.append(c2)
    x = _cross_apply(cfg, sp["cross"], x, img, "einsum")
    return x, jnp.stack(cks), jnp.stack(cvs)


def prefill_chunk(params, cfg: ArchConfig, tokens, cache, *, image_embeds=None,
                  compute_dtype=jnp.bfloat16, attn_impl: str = "einsum",
                  first: bool = False, return_stats: bool = False, **_):
    """Full-width parallel prefill over one prompt chunk.

    tokens: (B, C) — C consecutive prompt positions starting at
    ``cache["pos"]`` (0 for a first chunk, where the position is static so
    the flash prefill kernel path applies). Every position is computed in
    ONE matmul-wide pass per layer — prompt ingestion runs at prefill
    arithmetic intensity instead of the decode_step-under-scan's one token
    of matmul width per step — and the per-layer post-RoPE K/V land
    directly in the request cache, ready for the engine's (paged) splice.
    Returns (last-position logits (B, 1, Vp) float32, cache with pos += C
    [, MoE counts with ``return_stats``: rows routed to held experts and
    held experts with a row, summed over layers]); the same output
    contract as the scan prefill, which stays the bit-exactness anchor."""
    B, C = tokens.shape
    start = jnp.zeros((), jnp.int32) if first else cache["pos"]
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    use_kernel = first and attn_impl == "pallas" and not _is_mla(cfg)
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)
    counts = jnp.zeros((2,), jnp.int32)

    if cfg.cross_attn_every:
        assert image_embeds is not None, "VLM prefill needs image_embeds"
        img = image_embeds.astype(compute_dtype)
        per = cfg.cross_attn_every
        n_super = cfg.num_layers // per
        ck0 = cache["k"].reshape(n_super, per, *cache["k"].shape[1:])
        cv0 = cache["v"].reshape(n_super, per, *cache["v"].shape[1:])

        def body(i, carry):
            x, ck_all, cv_all = carry
            sp = _index_tree(params["super"], i)
            ck = jax.lax.dynamic_index_in_dim(ck_all, i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv_all, i, 0, keepdims=False)
            x, ck, cv = _super_prefill_chunk_unrolled(
                cfg, sp, x, ck, cv, img, start, positions, use_kernel)
            ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, i, 0)
            cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, i, 0)
            return x, ck_all, cv_all

        x, ck, cv = jax.lax.fori_loop(0, n_super, body, (x, ck0, cv0))
        new = dict(k=ck.reshape(cache["k"].shape),
                   v=cv.reshape(cache["v"].shape))
    else:
        x, new, counts = _run_layers(
            params, cfg, x, cache,
            lambda lp, x, c, i: _residual(cfg, lp, x, lambda h: _chunk_attn(
                cfg, lp, h, c, start, positions, use_kernel)))

    out = (_logits(params, cfg, x[:, -1:]), dict(cache, pos=start + C, **new))
    return out + (counts,) if return_stats else out


# ------------------------------------------------- paged parallel prefill
def _prefill_chunk_layer_paged(cfg: ArchConfig, lp, x, pk, pv, bt, positions,
                               write_floor, impl, kscale=None, vscale=None):
    """One per-head-K/V layer over a prompt chunk attending the PAGED pool
    directly: the chunk's K/V rows scatter into the slot's own pages (the
    incremental splice) and attention reads everything — prior chunks,
    aliased prefix pages, the current chunk — through the block table.
    Same residual structure as ``_prefill_chunk_layer``/``_decode_layer``.
    pk/pv are one layer's pools, handed on as a stack of one. Int8 pools
    carry per-layer (P, tp) per-group scales and the return grows
    accordingly."""
    c = {"k": pk[None], "v": pv[None]}
    if kscale is not None:
        c.update(k_scale=kscale, v_scale=vscale)
    x, c, _ = _residual(cfg, lp, x, lambda h: _chunk_paged_attn(
        cfg, lp, h, c, bt, positions, write_floor, impl, 0))
    c.update(k=c["k"][0], v=c["v"][0])
    if kscale is not None:
        return x, c["k"], c["v"], c["k_scale"], c["v_scale"]
    return x, c["k"], c["v"]


def _super_prefill_chunk_paged_unrolled(cfg: ArchConfig, sp, x, pk, pv, bt,
                                        img, positions, write_floor, impl,
                                        kscale=None, vscale=None):
    quantized = kscale is not None
    pks, pvs, kss, vss = [], [], [], []
    for i in range(cfg.cross_attn_every):
        lp = jax.tree.map(lambda t: t[i], sp["blocks"])
        if quantized:
            x, p1, p2, s1, s2 = _prefill_chunk_layer_paged(
                cfg, lp, x, pk[i], pv[i], bt, positions, write_floor, impl,
                kscale[i], vscale[i])
            kss.append(s1)
            vss.append(s2)
        else:
            x, p1, p2 = _prefill_chunk_layer_paged(cfg, lp, x, pk[i], pv[i],
                                                   bt, positions, write_floor,
                                                   impl)
        pks.append(p1)
        pvs.append(p2)
    x = _cross_apply(cfg, sp["cross"], x, img, "einsum")
    if quantized:
        return x, jnp.stack(pks), jnp.stack(pvs), jnp.stack(kss), jnp.stack(vss)
    return x, jnp.stack(pks), jnp.stack(pvs)


def _layer_groups(tree, n_super: int, per: int):
    """(L, ...) leaves -> (n_super, per, ...): the VLM super-layer view."""
    return {k: v.reshape(n_super, per, *v.shape[1:]) for k, v in tree.items()}


def prefill_chunk_paged(params, cfg: ArchConfig, tokens, cache, *, bt_rows,
                        start, write_floor, image_embeds=None,
                        compute_dtype=jnp.bfloat16, attn_impl: str = "kernel",
                        return_stats: bool = False, **_):
    """Full-width prefill over one prompt chunk, spliced into the RESIDENT
    paged cache incrementally (no transient request cache, no completion
    splice — the tentpole path).

    tokens: (K, C) — C consecutive prompt positions for a group of K slots,
    starting at the traced scalar ``start``; ``cache`` is the engine's
    resident PAGED cache (page pools + per-slot leaves); ``bt_rows``:
    (K, mps) the group's block-table rows; ``write_floor``: traced scalar —
    rows below it live in shared immutable prefix pages and are dropped by
    the scatter. Every chunk is uniform (no first/continuation split): the
    chunk writes its K/V rows into the group's pages, then attends the
    pages through the block table, so a prefix-cache hit needs NO gather
    seeding — aliased pages are read in place. The pools go to each layer
    whole, with its index (``_run_layers``). An int8-backend cache carries
    (L, P, tp) per-page per-group scale leaves beside the pools, which the
    layer loop slices per layer. Returns
    (last-position logits (K, 1, Vp) float32, cache with updated pools
    [, MoE counts with ``return_stats``]); the engine advances the group's
    ``pos`` at job completion."""
    K, C = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    write_floor = jnp.asarray(write_floor, jnp.int32)
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (K, C))
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)
    counts = jnp.zeros((2,), jnp.int32)

    if cfg.cross_attn_every:
        assert image_embeds is not None, "VLM prefill needs image_embeds"
        img = image_embeds.astype(compute_dtype)
        per = cfg.cross_attn_every
        n_super = cfg.num_layers // per
        leaves = {k: cache[k] for k in _LAYER_LEAVES if k in cache}
        quantized = "k_scale" in leaves

        def body(i, carry):
            x, st = carry
            sp = _index_tree(params["super"], i)
            c = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                 for k, v in st.items()}
            out = _super_prefill_chunk_paged_unrolled(
                cfg, sp, x, c["k"], c["v"], bt_rows, img, positions,
                write_floor, attn_impl, c.get("k_scale"), c.get("v_scale"))
            x = out[0]
            c = dict(zip(("k", "v", "k_scale", "v_scale")
                         if quantized else ("k", "v"), out[1:]))
            st = {k: jax.lax.dynamic_update_index_in_dim(st[k], c[k], i, 0)
                  for k in st}
            return x, st

        x, st = jax.lax.fori_loop(0, n_super, body,
                                  (x, _layer_groups(leaves, n_super, per)))
        new = {k: v.reshape(cache[k].shape) for k, v in st.items()}
    else:
        x, new, counts = _run_layers(
            params, cfg, x, cache,
            lambda lp, x, c, i: _residual(cfg, lp, x, lambda h: (
                _chunk_paged_attn(cfg, lp, h, c, bt_rows, positions,
                                  write_floor, attn_impl, i))))

    out = (_logits(params, cfg, x[:, -1:]), dict(cache, **new))
    return out + (counts,) if return_stats else out


def decode_step(params, cfg: ArchConfig, token, cache, *, image_embeds=None,
                compute_dtype=jnp.bfloat16, paged_attn_impl: str = "einsum",
                return_stats: bool = False):
    """token: (B, 1) int32. Returns (logits (B,1,V), new cache[, MoE counts
    with ``return_stats``]).

    Layers run in a fori_loop carrying the FULL (L,B,S,KV,hd) cache with
    in-place dynamic updates, a paged cache's pools going to each layer
    whole (``_run_layers``).

    cache["pos"] may be a scalar (lockstep batch) or a (B,) per-slot vector
    (serving engine with continuous batching). A cache carrying a
    "block_tables" leaf is PAGED (models/registry.py::init_paged_cache):
    "k"/"v" are (L, P, page_size, KV, hd) page pools and decode routes
    through the block-table-indirect attention path — through the Pallas
    kernel with ``paged_attn_impl='kernel'``, the masked-einsum reference
    otherwise. An int8-backend cache carries (L, P, tp) per-page per-group
    scale leaves, which the layer loop slices per layer."""
    B = token.shape[0]
    pos = cache["pos"]
    bt = cache.get("block_tables")
    positions = L.decode_positions(pos, B)
    x = L.embed_lookup(params["embed"], token, compute_dtype)
    counts = jnp.zeros((2,), jnp.int32)

    if cfg.cross_attn_every:
        assert image_embeds is not None
        img = image_embeds.astype(compute_dtype)
        per = cfg.cross_attn_every
        n_super = cfg.num_layers // per
        leaves = {k: cache[k] for k in _LAYER_LEAVES if k in cache}
        quantized = bt is not None and "k_scale" in leaves

        def body(i, carry):
            x, st = carry
            sp = _index_tree(params["super"], i)
            c = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                 for k, v in st.items()}
            out = _super_decode_unrolled(
                cfg, sp, x, c["k"], c["v"], img, pos, positions, bt,
                paged_attn_impl, c.get("k_scale") if quantized else None,
                c.get("v_scale") if quantized else None)
            x = out[0]
            c = dict(zip(("k", "v", "k_scale", "v_scale")
                         if quantized else ("k", "v"), out[1:]))
            st = {k: jax.lax.dynamic_update_index_in_dim(st[k], c[k], i, 0)
                  for k in c}
            return x, st

        x, st = jax.lax.fori_loop(0, n_super, body,
                                  (x, _layer_groups(leaves, n_super, per)))
        new = {k: v.reshape(cache[k].shape) for k, v in st.items()}
    else:
        x, new, counts = _run_layers(
            params, cfg, x, cache,
            lambda lp, x, c, i: _residual(cfg, lp, x, lambda h: _decode_attn(
                cfg, lp, h, c, pos, positions, bt, paged_attn_impl, i)))

    out = (_logits(params, cfg, x), dict(cache, pos=pos + 1, **new))
    return out + (counts,) if return_stats else out
