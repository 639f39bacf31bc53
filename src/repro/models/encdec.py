"""Whisper-large-v3-style encoder-decoder. The conv/mel frontend is a STUB per
the assignment: ``input_specs()`` provides precomputed frame embeddings
(B, S_enc, d_model). Encoder: bidirectional attention + GELU MLP + learned
positions. Decoder: causal self-attn + cross-attn to encoder states.

Shape-cell convention (DESIGN.md): decoder length = the cell's seq_len;
encoder length = ENC_LEN (1500, whisper's 30 s window).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.sharding.specs import shard

ENC_LEN = 1500


def _self_dims(cfg: ArchConfig, causal: bool) -> L.AttnDims:
    return L.AttnDims(d_model=cfg.d_model, num_heads=cfg.num_heads,
                      num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                      qkv_bias=True, rope_theta=0.0, causal=causal)


def _enc_layer_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": L.norm_init(cfg.d_model, "layernorm"),
            "attn": L.attn_init(ks[0], _self_dims(cfg, causal=False)),
            "ln2": L.norm_init(cfg.d_model, "layernorm"),
            "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, gated=False, bias=True)}


def _dec_layer_init(key, cfg):
    ks = jax.random.split(key, 3)
    return {"ln1": L.norm_init(cfg.d_model, "layernorm"),
            "attn": L.attn_init(ks[0], _self_dims(cfg, causal=True)),
            "ln_x": L.norm_init(cfg.d_model, "layernorm"),
            "xattn": L.attn_init(ks[1], _self_dims(cfg, causal=False)),
            "ln2": L.norm_init(cfg.d_model, "layernorm"),
            "mlp": L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, gated=False, bias=True)}


def _enc_layer_logical(cfg):
    return {"ln1": L.norm_logical("layernorm"),
            "attn": L.attn_logical(_self_dims(cfg, False)),
            "ln2": L.norm_logical("layernorm"),
            "mlp": L.mlp_logical(gated=False, bias=True)}


def _dec_layer_logical(cfg):
    return {"ln1": L.norm_logical("layernorm"),
            "attn": L.attn_logical(_self_dims(cfg, True)),
            "ln_x": L.norm_logical("layernorm"),
            "xattn": L.attn_logical(_self_dims(cfg, False)),
            "ln2": L.norm_logical("layernorm"),
            "mlp": L.mlp_logical(gated=False, bias=True)}


def init(cfg: ArchConfig, key):
    ks = jax.random.split(key, 6)
    enc_keys = jax.random.split(ks[0], cfg.encoder_layers)
    dec_keys = jax.random.split(ks[1], cfg.num_layers)
    return {
        "embed": L.embed_init(ks[2], cfg.padded_vocab, cfg.d_model),
        "pos_dec": L.scaled_normal(ks[3], (8192, cfg.d_model), 0.01),
        "enc_layers": jax.vmap(lambda kk: _enc_layer_init(kk, cfg))(enc_keys),
        "enc_norm": L.norm_init(cfg.d_model, "layernorm"),
        "dec_layers": jax.vmap(lambda kk: _dec_layer_init(kk, cfg))(dec_keys),
        "final_norm": L.norm_init(cfg.d_model, "layernorm"),
    }


def param_logical(cfg: ArchConfig):
    def stacked(tree):
        return jax.tree.map(lambda ax: (None,) + ax, tree,
                            is_leaf=lambda v: isinstance(v, tuple))
    return {
        "embed": L.embed_logical(),
        "pos_dec": (None, "fsdp"),
        "enc_layers": stacked(_enc_layer_logical(cfg)),
        "enc_norm": L.norm_logical("layernorm"),
        "dec_layers": stacked(_dec_layer_logical(cfg)),
        "final_norm": L.norm_logical("layernorm"),
    }


def _sinusoid(s, d):
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encode(params, cfg: ArchConfig, frames, *, compute_dtype=jnp.bfloat16,
           attn_impl="einsum", remat=False):
    """frames: (B, S_enc, D) precomputed frame embeddings (stub frontend)."""
    B, S, _ = frames.shape
    x = frames.astype(compute_dtype) + _sinusoid(S, cfg.d_model).astype(compute_dtype)
    x = shard(x, "batch", "seq_sp", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, lp):
        return _enc_layer(cfg, lp, x, positions, attn_impl), None
    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.apply_norm(x, params["enc_norm"], "layernorm")


def _enc_layer(cfg, lp, x, positions, attn_impl):
    h = L.apply_norm(x, lp["ln1"], "layernorm")
    x = x + L.attention(lp["attn"], h, _self_dims(cfg, False), positions,
                        impl=attn_impl)
    h = L.apply_norm(x, lp["ln2"], "layernorm")
    return shard(x + L.mlp(lp["mlp"], h, act="gelu"), "batch", "seq_sp", None)


def _dec_layer(cfg, lp, x, positions, enc_out, enc_pos, attn_impl):
    h = L.apply_norm(x, lp["ln1"], "layernorm")
    x = x + L.attention(lp["attn"], h, _self_dims(cfg, True), positions,
                        impl=attn_impl)
    h = L.apply_norm(x, lp["ln_x"], "layernorm")
    dims = _self_dims(cfg, False)
    B, Se, _ = enc_out.shape
    k = (enc_out @ lp["xattn"]["wk"].astype(x.dtype)
         + lp["xattn"]["bk"].astype(x.dtype)).reshape(B, Se, dims.num_kv_heads, dims.head_dim)
    v = (enc_out @ lp["xattn"]["wv"].astype(x.dtype)
         + lp["xattn"]["bv"].astype(x.dtype)).reshape(B, Se, dims.num_kv_heads, dims.head_dim)
    x = x + L.attention(lp["xattn"], h, dims, positions, impl="einsum",
                        kv_override=(k, v, enc_pos))
    h = L.apply_norm(x, lp["ln2"], "layernorm")
    x = shard(x + L.mlp(lp["mlp"], h, act="gelu"), "batch", "seq_sp", None)
    return x


def forward(params, cfg: ArchConfig, tokens, *, frames=None,
            compute_dtype=jnp.bfloat16, attn_impl="einsum", remat=False,
            return_features: bool = False, **_):
    """tokens: (B, S_dec); frames: (B, S_enc, D). Returns decoder logits."""
    B, S = tokens.shape
    if frames is None:
        frames = jnp.zeros((B, ENC_LEN, cfg.d_model), compute_dtype)
    enc_out = encode(params, cfg, frames, compute_dtype=compute_dtype,
                     attn_impl=attn_impl, remat=remat)
    enc_pos = jnp.broadcast_to(jnp.arange(enc_out.shape[1], dtype=jnp.int32),
                               (B, enc_out.shape[1]))
    x = L.embed_lookup(params["embed"], tokens, compute_dtype)
    pos_emb = jax.lax.dynamic_slice_in_dim(params["pos_dec"], 0, min(S, 8192), axis=0)
    if S > 8192:  # tile learned positions beyond table (structural stand-in)
        reps = -(-S // 8192)
        pos_emb = jnp.tile(pos_emb, (reps, 1))[:S]
    x = x + pos_emb.astype(compute_dtype)[None]
    x = shard(x, "batch", "seq_sp", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, lp):
        return _dec_layer(cfg, lp, x, positions, enc_out, enc_pos, attn_impl), None
    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = L.apply_norm(x, params["final_norm"], "layernorm")
    if return_features:
        return x, {"moe_aux": jnp.zeros(()), "moe_z": jnp.zeros(())}
    logits = L.lm_logits(params["embed"], x, None, vocab=cfg.vocab_size)  # tied embeddings
    return logits.astype(jnp.float32), {"moe_aux": jnp.zeros(()), "moe_z": jnp.zeros(())}


# ------------------------------------------------------------------ decode
def init_cache(cfg: ArchConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    Lr = cfg.num_layers
    return {
        "k": jnp.zeros((Lr, batch, s_max, kv, hd), dtype),
        "v": jnp.zeros((Lr, batch, s_max, kv, hd), dtype),
        # cross-attn K/V precomputed once from encoder output at prefill time
        "xk": jnp.zeros((Lr, batch, ENC_LEN, kv, hd), dtype),
        "xv": jnp.zeros((Lr, batch, ENC_LEN, kv, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_logical(cfg: ArchConfig):
    from repro.sharding import specs as _sp
    if cfg.num_kv_heads % max(_sp.axis_size("kv_heads"), 1) == 0:
        kv = (None, "batch", None, "kv_heads", None)
        xkv = (None, "batch", None, "kv_heads", None)
    else:
        kv = (None, "batch", "seq_sp", None, None)
        xkv = (None, "batch", "seq_sp", None, None)
    return {"k": kv, "v": kv, "xk": xkv, "xv": xkv, "pos": ()}


def precompute_cross_kv(params, cfg: ArchConfig, enc_out):
    """(L, B, S_enc, KV, hd) cross K/V from encoder output."""
    dims = _self_dims(cfg, False)
    B, Se, _ = enc_out.shape

    def per_layer(lp):
        k = (enc_out @ lp["xattn"]["wk"].astype(enc_out.dtype)
             + lp["xattn"]["bk"].astype(enc_out.dtype))
        v = (enc_out @ lp["xattn"]["wv"].astype(enc_out.dtype)
             + lp["xattn"]["bv"].astype(enc_out.dtype))
        return (k.reshape(B, Se, dims.num_kv_heads, dims.head_dim),
                v.reshape(B, Se, dims.num_kv_heads, dims.head_dim))
    return jax.lax.map(per_layer, params["dec_layers"])


def _decode_layer(cfg, lp, x, ck, cv, xk, xv, pos, positions, enc_pos,
                  block_tables=None, paged_impl: str = "einsum"):
    """One decoder decode layer (self-attn against cache + cross-attn).
    Exposed for roofline probes. With ``block_tables``, ck/cv are one layer's
    (P, ps, KV, hd) page-pool slices (paged self-attn KV; the cross-attn
    xk/xv stay dense per slot — they are written once at prefill and fixed
    at ENC_LEN, so paging buys nothing); ``paged_impl`` selects the Pallas
    block-gather kernel or the masked-einsum reference read."""
    h = L.apply_norm(x, lp["ln1"], "layernorm")
    if block_tables is not None:
        out, ck, cv = L.attention_decode_paged(
            lp["attn"], h, _self_dims(cfg, True), ck, cv, block_tables, pos,
            positions, impl=paged_impl)
    else:
        out, ck, cv = L.attention_decode(lp["attn"], h, _self_dims(cfg, True),
                                         ck, cv, pos, positions)
    x = x + out
    h = L.apply_norm(x, lp["ln_x"], "layernorm")
    x = x + L.attention(lp["xattn"], h, _self_dims(cfg, False), positions,
                        impl="einsum", kv_override=(xk.astype(h.dtype),
                                                    xv.astype(h.dtype), enc_pos))
    h = L.apply_norm(x, lp["ln2"], "layernorm")
    x = x + L.mlp(lp["mlp"], h, act="gelu")
    return x, ck, cv


# ------------------------------------------------------- parallel prefill
def _prefill_chunk_dec_layer(cfg, lp, x, ck, cv, xk, xv, start, positions,
                             enc_pos, use_kernel):
    """One decoder layer over a whole prompt chunk: chunk-wide causal
    self-attention against the request cache plus full-width cross-attention
    to the precomputed encoder K/V. Mirrors ``_decode_layer``'s math."""
    h = L.apply_norm(x, lp["ln1"], "layernorm")
    out, ck, cv = L.attention_prefill_chunk(lp["attn"], h,
                                            _self_dims(cfg, True), ck, cv,
                                            start, positions,
                                            use_kernel=use_kernel)
    x = x + out
    h = L.apply_norm(x, lp["ln_x"], "layernorm")
    x = x + L.attention(lp["xattn"], h, _self_dims(cfg, False), positions,
                        impl="einsum", kv_override=(xk.astype(h.dtype),
                                                    xv.astype(h.dtype),
                                                    enc_pos))
    h = L.apply_norm(x, lp["ln2"], "layernorm")
    x = x + L.mlp(lp["mlp"], h, act="gelu")
    return x, ck, cv


def prefill_chunk(params, cfg: ArchConfig, tokens, cache, *,
                  compute_dtype=jnp.bfloat16, attn_impl: str = "einsum",
                  first: bool = False, **_):
    """Matmul-wide parallel prefill over one decoder prompt chunk. The cache
    must already carry the encoder cross K/V (``xk``/``xv`` — precomputed
    exactly once by the first-chunk builder in launch/steps.py, same as the
    scan prefill). Returns (last logits (B,1,Vp), cache with pos += C)."""
    B, C = tokens.shape
    start = jnp.zeros((), jnp.int32) if first else cache["pos"]
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    use_kernel = first and attn_impl == "pallas"
    x_pos = params["pos_dec"][jnp.minimum(positions, 8191)].astype(compute_dtype)
    x = L.embed_lookup(params["embed"], tokens, compute_dtype) + x_pos
    Se = cache["xk"].shape[2]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(i, carry):
        x, ck_all, cv_all = carry
        lp = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            params["dec_layers"])
        ck = jax.lax.dynamic_index_in_dim(ck_all, i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, i, 0, keepdims=False)
        xk = jax.lax.dynamic_index_in_dim(cache["xk"], i, 0, keepdims=False)
        xv = jax.lax.dynamic_index_in_dim(cache["xv"], i, 0, keepdims=False)
        x, ck, cv = _prefill_chunk_dec_layer(cfg, lp, x, ck, cv, xk, xv,
                                             start, positions, enc_pos,
                                             use_kernel)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, i, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, i, 0)
        return x, ck_all, cv_all

    x, ck, cv = jax.lax.fori_loop(0, cfg.num_layers, body,
                                  (x, cache["k"], cache["v"]))
    x = L.apply_norm(x[:, -1:], params["final_norm"], "layernorm")
    logits = L.lm_logits(params["embed"], x, None, vocab=cfg.vocab_size)
    return logits.astype(jnp.float32), dict(cache, k=ck, v=cv, pos=start + C)


def decode_step(params, cfg: ArchConfig, token, cache, *, compute_dtype=jnp.bfloat16,
                paged_attn_impl: str = "einsum", **_):
    B = token.shape[0]
    pos = cache["pos"]
    bt = cache.get("block_tables")
    positions = L.decode_positions(pos, B)
    # learned decoder position embedding, per-row: (B,1) -> (B,1,D)
    x_pos = params["pos_dec"][jnp.minimum(positions, 8191)].astype(compute_dtype)
    x = L.embed_lookup(params["embed"], token, compute_dtype)
    x = x + x_pos
    Se = cache["xk"].shape[2]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(i, carry):
        x, ck_all, cv_all = carry
        lp = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            params["dec_layers"])
        ck = jax.lax.dynamic_index_in_dim(ck_all, i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, i, 0, keepdims=False)
        xk = jax.lax.dynamic_index_in_dim(cache["xk"], i, 0, keepdims=False)
        xv = jax.lax.dynamic_index_in_dim(cache["xv"], i, 0, keepdims=False)
        x, ck, cv = _decode_layer(cfg, lp, x, ck, cv, xk, xv, pos, positions,
                                  enc_pos, bt, paged_attn_impl)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, i, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, i, 0)
        return x, ck_all, cv_all

    x, ck, cv = jax.lax.fori_loop(0, cfg.num_layers, body,
                                  (x, cache["k"], cache["v"]))
    x = L.apply_norm(x, params["final_norm"], "layernorm")
    logits = L.lm_logits(params["embed"], x, None, vocab=cfg.vocab_size)
    new_cache = dict(cache, k=ck, v=cv, pos=pos + 1)
    return logits.astype(jnp.float32), new_cache
