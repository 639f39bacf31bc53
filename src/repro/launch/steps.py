"""Distributed step builders: train_step (value_and_grad + AdamW, microbatch
accumulation, remat, mixed precision), serve prefill and decode steps — all
mesh-agnostic via logical shardings (sharding/specs.py).

These are the functions the multi-pod dry-run lowers/compiles and the
train.py / serve.py drivers execute.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.registry import Model
from repro.optim.adamw import AdamW, apply_updates
from repro.sharding import specs
from repro.sharding.specs import shard

MOE_AUX_WEIGHT = 0.01
MOE_Z_WEIGHT = 1e-3


# ------------------------------------------------------------------ loss
CE_CHUNK = 1024


def cross_entropy(logits, labels):
    """logits: (B,S,V) fp32 (possibly vocab-sharded); labels: (B,S) int32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def head_ce_chunk(x_c, head_w, labels_c, vocab: int, tied: bool):
    """CE over one sequence chunk without keeping logits alive.
    x_c: (B,C,D); head_w: (D,Vp) or tied table (Vp,D); labels_c: (B,C)."""
    from repro.kernels.ref import mask_value
    w = head_w.astype(x_c.dtype)
    logits = (x_c @ w.T if tied else x_c @ w).astype(jnp.float32)
    vp = logits.shape[-1]
    if vocab < vp:
        mask = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0) < vocab
        logits = jnp.where(mask, logits, mask_value(logits.dtype))
    logits = shard(logits, "batch", None, "vocab")
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def chunked_cross_entropy(features, head_w, labels, vocab: int, tied: bool,
                          chunk: int = CE_CHUNK):
    """Never materializes (B,S,V) logits: scans S in chunks with a remat'd
    body (logits recomputed in backward) — the memory-side requirement for
    150k+ vocabs at 4k sequence (DESIGN.md; same trick as fused-CE kernels)."""
    B, S, D = features.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    body = jax.checkpoint(
        lambda x_c, l_c: head_ce_chunk(x_c, head_w, l_c, vocab, tied),
        policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(acc, xs):
        x_c, l_c = xs
        return acc + body(x_c, l_c), None

    xs = (features[:, :n * chunk].reshape(B, n, chunk, D).swapaxes(0, 1),
          labels[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1))
    total, _ = jax.lax.scan(scan_body, jnp.zeros((), jnp.float32), xs)
    if rem:
        total = total + body(features[:, n * chunk:], labels[:, n * chunk:])
    return total / (B * S)


def _batch_extras(model: Model, batch: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    if "image_embeds" in batch:
        out["image_embeds"] = batch["image_embeds"]
    if "frames" in batch:
        out["frames"] = batch["frames"]
    return out


def head_weight(model: Model, params):
    """(weights, tied?) for the LM head."""
    if "unembed" in params:
        return params["unembed"]["w"], False
    return params["embed"]["table"], True


def make_loss_fn(model: Model, *, compute_dtype=jnp.bfloat16,
                 attn_impl: str = "einsum", remat: bool = True):
    def loss_fn(params, batch):
        feats, aux = model.forward(params, batch["tokens"],
                                   compute_dtype=compute_dtype,
                                   attn_impl=attn_impl, remat=remat,
                                   return_features=True,
                                   **_batch_extras(model, batch))
        w, tied = head_weight(model, params)
        ce = chunked_cross_entropy(feats, w, batch["labels"],
                                   model.cfg.vocab_size, tied)
        loss = ce + MOE_AUX_WEIGHT * aux.get("moe_aux", 0.0) \
                  + MOE_Z_WEIGHT * aux.get("moe_z", 0.0)
        return loss, {"ce": ce, **aux}
    return loss_fn


# ------------------------------------------------------------------ train
def init_train_state(model: Model, optimizer: AdamW, key) -> Dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def _strip_fsdp(tree):
    """Remove the 'fsdp' (data-axis) factor from a logical tree: ZeRO-1
    params — replicated across data, sharded across model only."""
    def fix(ax):
        return tuple(None if a == "fsdp" else a for a in ax)
    return jax.tree.map(fix, tree,
                        is_leaf=lambda v: isinstance(v, tuple) and not isinstance(v, dict))


def train_state_logical(model: Model, zero_stage: int = 3) -> Dict[str, Any]:
    """zero_stage=3: params AND optimizer state sharded over data x model
    (ZeRO-3; weights all-gathered per layer — minimum memory).
    zero_stage=1: params model-sharded only (resident per chip, NO per-layer
    weight all-gathers); m/v stay data-sharded — the classic memory/collective
    trade (hillclimb B iteration 1)."""
    pl = model.param_logical()
    p_log = pl if zero_stage >= 3 else _strip_fsdp(pl)
    return {"params": p_log, "opt": {"m": pl, "v": pl, "count": ()},
            "step": ()}


def batch_logical(model: Model, batch_keys) -> Dict[str, Any]:
    out = {}
    for k in batch_keys:
        if k in ("tokens", "labels"):
            out[k] = ("batch", None)
        elif k == "token":
            out[k] = ("batch", None)
        elif k in ("image_embeds", "frames"):
            out[k] = ("batch", None, None)
        else:
            raise KeyError(k)
    return out


def make_train_step(model: Model, optimizer: AdamW, *,
                    compute_dtype=jnp.bfloat16, attn_impl: str = "einsum",
                    remat: bool = True, microbatches: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    With microbatches>1, gradients are accumulated over sequential microbatch
    slices (lax.scan) — the standard activation-memory / collective-overlap
    trade at scale (each microbatch's backward overlaps the next's compute
    under XLA async collectives).
    """
    loss_fn = make_loss_fn(model, compute_dtype=compute_dtype,
                           attn_impl=attn_impl, remat=remat)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_body(carry, mbatch):
                (loss_a, grads_a) = carry
                (loss, metrics), grads = grad_fn(params, mbatch)
                grads = jax.tree.map(jnp.add, grads_a, grads)
                return (loss_a + loss, grads), metrics

            zero_grads = jax.tree.map(jnp.zeros_like, params)
            (loss, grads), metrics_seq = jax.lax.scan(
                acc_body, (jnp.zeros(()), zero_grads), mb)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics_seq)
        updates, opt, gnorm = optimizer.update(grads, state["opt"], params)
        params = apply_updates(params, updates)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        metrics = {"loss": loss, "grad_norm": gnorm, **metrics}
        return new_state, metrics

    return train_step


# ------------------------------------------------------------------ serve
def make_prefill(model: Model, *, compute_dtype=jnp.bfloat16,
                 attn_impl: str = "chunked", batch_chunks: int = 1,
                 return_cache: bool = False, s_max: int = 0,
                 cache_dtype=jnp.float32):
    """Prefill step builder.

    Default (``return_cache=False``): full-sequence forward returning
    LAST-position logits only (the decode bootstrap a serving system actually
    needs — avoids a (B,S,V) output). batch_chunks > 1 processes the request
    batch in sequential slices (lax.scan) — bounds prefill activation memory
    exactly like gradient-accumulation microbatching does for training.

    ``return_cache=True`` (the serving engine's path): returns
    ``(last_logits, cache)`` where the cache holds every prompt position's
    K/V / recurrent state at pos == prompt_len, ready for decode. The prompt
    is teacher-forced through ``decode_step`` under a single ``lax.scan``
    inside ONE jitted call — one dispatch per request instead of one per
    prompt token, and crucially at the REQUEST's batch size (1 in the engine)
    so it never touches other slots' cache entries. ``s_max`` sizes the
    returned cache's sequence capacity: for a dense serving cache it must
    match the resident cache; for a PAGED one it is the per-slot LOGICAL
    capacity (the block-table span) — the returned cache is always the dense
    per-request layout, a transient at the group's batch size that
    ``registry.insert_cache_rows_paged`` then scatters into exactly the pages
    the admitted slots reserved. For encoder-decoder models the
    cross-attention K/V are precomputed from the encoder pass first, exactly
    once."""
    if return_cache:
        if s_max <= 0:
            raise ValueError("return_cache=True requires s_max > 0")
        from repro.configs.base import Family

        def prefill_cache(params, batch):
            tokens = batch["tokens"]
            B, S = tokens.shape
            cache = model.init_cache(B, s_max, cache_dtype)
            extras = _batch_extras(model, batch)
            if model.cfg.family == Family.ENCDEC:
                from repro.models import encdec
                frames = batch.get("frames")
                if frames is None:
                    frames = jnp.zeros((B, encdec.ENC_LEN, model.cfg.d_model),
                                       compute_dtype)
                enc_out = encdec.encode(params, model.cfg,
                                        frames.astype(compute_dtype),
                                        compute_dtype=compute_dtype,
                                        attn_impl="einsum", remat=False)
                xk, xv = encdec.precompute_cross_kv(params, model.cfg, enc_out)
                cache = dict(cache, xk=xk.astype(cache["xk"].dtype),
                             xv=xv.astype(cache["xv"].dtype))
                extras = {}

            def body(carry, tok):
                cache, _ = carry
                logits, cache = model.decode_step(params, tok, cache,
                                                  compute_dtype=compute_dtype,
                                                  **extras)
                return (cache, logits), None

            logits0 = jnp.zeros((B, 1, model.cfg.padded_vocab), jnp.float32)
            toks = jnp.moveaxis(tokens, 1, 0)[:, :, None]        # (S, B, 1)
            (cache, logits), _ = jax.lax.scan(body, (cache, logits0), toks)
            return logits, cache
        return prefill_cache

    def one(params, batch):
        feats, _ = model.forward(params, batch["tokens"],
                                 compute_dtype=compute_dtype,
                                 attn_impl=attn_impl, remat=False,
                                 return_features=True,
                                 **_batch_extras(model, batch))
        w, tied = head_weight(model, params)
        last = feats[:, -1:]
        wd = w.astype(last.dtype)
        return (last @ wd.T if tied else last @ wd).astype(jnp.float32)

    def prefill(params, batch):
        if batch_chunks == 1:
            return one(params, batch)
        def split(x):
            return x.reshape((batch_chunks, x.shape[0] // batch_chunks)
                             + x.shape[1:])
        mb = jax.tree.map(split, batch)
        def body(_, mbatch):
            return None, one(params, mbatch)
        _, outs = jax.lax.scan(body, None, mb)
        return outs.reshape((-1,) + outs.shape[2:])
    return prefill


def _stats_kw(moe_stats: bool) -> dict:
    """``moe_stats``: the step also returns the MoE layers' counts (rows
    routed to held experts, held experts hit), summed over layers. Only
    the transformer family's MoE stacks count them."""
    return {"return_stats": True} if moe_stats else {}


def make_prefill_chunk(model: Model, *, compute_dtype=jnp.bfloat16,
                       s_max: int = 0, cache_dtype=jnp.float32,
                       first: bool = False, attn_impl: str = "einsum",
                       moe_stats: bool = False):
    """Parallel (matmul-wide) chunked prefill step builder — the serving
    engine's fast path; the scan prefill (``make_prefill(return_cache=True)``)
    stays the bit-exactness anchor.

    Each call computes ALL of a chunk's prompt positions in one full-width
    pass per layer and exports the per-layer K/V (ring + recurrent carry for
    hybrid, O(1) state for ssm/rwkv) directly into the request's dense
    transient cache, which the engine then splices into the resident cache
    (``insert_cache_rows`` / ``insert_cache_rows_paged``) when the prompt
    completes.

    ``first=True``: returns ``first_chunk(params, batch) -> (logits, cache)``
    — creates the transient cache inside the jit, runs the encoder +
    cross-KV precompute exactly once for encoder-decoder models, and
    processes the chunk at STATIC position 0 (which is what lets
    ``attn_impl='pallas'`` route chunk-local causal attention through the
    K/V-exporting flash kernel). ``first=False``: returns
    ``chunk(params, cache, batch) -> (logits, cache)`` — a continuation at
    the traced ``cache['pos']``; callers should donate the cache. With
    ``moe_stats`` both return (logits, cache, counts)."""
    if s_max <= 0 and first:
        raise ValueError("first=True requires s_max > 0")
    from repro.configs.base import Family

    def run_chunk(params, cache, batch):
        return model.prefill_chunk(params, batch["tokens"], cache,
                                   compute_dtype=compute_dtype,
                                   attn_impl=attn_impl, first=first,
                                   **_stats_kw(moe_stats),
                                   **_batch_extras(model, batch))

    if not first:
        return run_chunk

    def first_chunk(params, batch):
        tokens = batch["tokens"]
        B = tokens.shape[0]
        cache = model.init_cache(B, s_max, cache_dtype)
        if model.cfg.family == Family.ENCDEC:
            from repro.models import encdec
            frames = batch.get("frames")
            if frames is None:
                frames = jnp.zeros((B, encdec.ENC_LEN, model.cfg.d_model),
                                   compute_dtype)
            enc_out = encdec.encode(params, model.cfg,
                                    frames.astype(compute_dtype),
                                    compute_dtype=compute_dtype,
                                    attn_impl="einsum", remat=False)
            xk, xv = encdec.precompute_cross_kv(params, model.cfg, enc_out)
            cache = dict(cache, xk=xk.astype(cache["xk"].dtype),
                         xv=xv.astype(cache["xv"].dtype))
            batch = {k: v for k, v in batch.items() if k != "frames"}
        return run_chunk(params, cache, batch)

    return first_chunk


def make_prefill_chunk_paged(model: Model, *, compute_dtype=jnp.bfloat16,
                             attn_impl: str = "kernel",
                             moe_stats: bool = False):
    """Incremental paged prefill step builder: each call computes one prompt
    chunk for a group of K slots and splices its per-layer K/V STRAIGHT into
    the resident paged cache's pools through the group's block tables —
    there is no transient request cache and no completion splice, and a
    prefix-cache hit's aliased pages are read in place (no gather seeding).

    Returns ``chunk(params, cache, batch) -> (last_logits, cache)`` where
    ``cache`` is the engine's resident paged cache (callers donate it) and
    ``batch`` carries ``tokens`` (K, C), ``bt`` (K, mps) block-table rows,
    and traced scalars ``start`` (the chunk's first absolute position — the
    engine groups jobs so the whole group shares it) and ``floor`` (the
    first row the group may write; rows below live in shared immutable
    prefix pages — copy-on-write's no-write half). ``attn_impl='kernel'``
    attends through the block-skipping Pallas kernel, ``'einsum'`` through
    the masked-gather reference. Only families with
    ``supports_paged_prefill`` (dense/MoE/VLM) accept this path. With
    ``moe_stats`` it returns (last_logits, cache, counts)."""
    def chunk(params, cache, batch):
        return model.prefill_chunk_paged(
            params, batch["tokens"], cache, bt_rows=batch["bt"],
            start=batch["start"], write_floor=batch["floor"],
            compute_dtype=compute_dtype, attn_impl=attn_impl,
            **_stats_kw(moe_stats),
            **_batch_extras(model, batch))
    return chunk


def make_decode_step(model: Model, *, compute_dtype=jnp.bfloat16,
                     paged_attn_impl: Optional[str] = None,
                     moe_stats: bool = False):
    """One-token decode against a KV/state cache; cache buffers are donated.
    ``paged_attn_impl`` ('kernel' | 'einsum') selects the paged-cache read
    path for the families that page through ``attention_decode_paged``
    (dense/MoE/VLM/encdec); None keeps each family's default (the
    masked-einsum reference) — hybrid's ring path has its own gather.
    With ``moe_stats`` the step returns (logits, cache, counts)."""
    # function-level import: launch.steps is imported by serve.engine, and
    # serve/__init__ imports engine — a module-level kvcache import here
    # would cycle through the serve package at import time
    from repro.serve.kvcache import PAGED_KERNEL_FAMILIES
    extra = {}
    if (paged_attn_impl is not None
            and model.cfg.family in PAGED_KERNEL_FAMILIES):
        extra["paged_attn_impl"] = paged_attn_impl

    def decode(params, cache, batch):
        return model.decode_step(params, batch["token"], cache,
                                 compute_dtype=compute_dtype, **extra,
                                 **_stats_kw(moe_stats),
                                 **_batch_extras(model, batch))
    return decode


# ------------------------------------------------------------------ shardings
# All builders are shape-aware (specs.shardings_for): logical axes that do not
# divide a leaf's dim are dropped per-leaf (pjit arguments require exact
# divisibility; e.g. batch=1 long-context cells, kv=5 heads on 16-way TP).
def state_shardings(model: Model, state_sds, zero_stage: int = 3):
    return specs.shardings_for(train_state_logical(model, zero_stage), state_sds)


def param_shardings(model: Model, params_sds):
    return specs.shardings_for(model.param_logical(), params_sds)


def batch_shardings(model: Model, batch_sds):
    return specs.shardings_for(batch_logical(model, batch_sds.keys()), batch_sds)


def cache_shardings(model: Model, cache_sds):
    return specs.shardings_for(model.cache_logical(), cache_sds)
