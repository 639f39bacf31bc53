"""Operations and bytes DeepSeek-V3's decoder needs, counted from shapes
(the MLA and MoE counterpart of ``workcount``, same rules: what the
algorithm requires, never what one implementation happens to do).

Attention counts the absorbed form the program serves: per query row
and layer, every head's 576-wide absorbed query against each key's
latent row and its 512-wide value part, so a key costs
2 * H * (kv_lora + rope + kv_lora) FLOPs and is read once, as one
(kv_lora + rope)-wide row, for all heads. Routed experts count at this
chip's share: a token's top k experts are held here with probability
held / router outputs, so it costs top_k * held / router_outputs expert
passes on average.

``c`` is a configuration file's dict (published key names:
``hidden_size``, ``intermediate_size``, ``moe_intermediate_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``num_hidden_layers``, ``first_k_dense_replace``, ``n_routed_experts``
(held here), ``held_experts.router_outputs``, ``n_shared_experts``,
``num_experts_per_tok``, ``vocab_size``).
"""
from __future__ import annotations

from typing import Iterable


def router_outputs(c: dict) -> int:
    return int((c.get("held_experts") or {}).get("router_outputs",
                                                  c["n_routed_experts"]))


def mla_token_params(c: dict) -> int:
    """Weights one token multiplies in one layer's attention: the query
    LoRA, the latent projection, the absorption of q_nope into the latent
    and of the latent output into the value heads, and the output."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vh = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + h * nope * kl + h * kl * vh + h * vh * d)


def ffn_token_params(c: dict, moe: bool) -> float:
    """Weights one token multiplies in one layer's feed-forward: the dense
    SwiGLU MLP, or the router, the shared expert and the routed experts at
    this chip's share."""
    d = c["hidden_size"]
    if not moe:
        return 3 * d * c["intermediate_size"]
    fe = c["moe_intermediate_size"]
    share = c["num_experts_per_tok"] * c["n_routed_experts"] / router_outputs(c)
    return (d * router_outputs(c) + 3 * d * fe * c["n_shared_experts"]
            + 3 * d * fe * share)


def attention_flops(c: dict, ctx: int) -> int:
    """One query row over ``ctx`` keys in one layer, absorbed: scores over
    the (kv_lora + rope)-wide rows and values over their kv_lora part."""
    kl = c["kv_lora_rank"]
    return 2 * c["num_attention_heads"] * (2 * kl + c["qk_rope_head_dim"]) * ctx


def token_flops(c: dict, ctx: int, logits: bool) -> float:
    """Model FLOPs of one token at context ``ctx`` (keys it attends,
    itself included), through every layer, with the unembedding when its
    logits are needed."""
    n, k = c["num_hidden_layers"], c["first_k_dense_replace"]
    per = 2 * mla_token_params(c) + attention_flops(c, ctx)
    out = (n * per + 2 * k * ffn_token_params(c, False)
           + 2 * (n - k) * ffn_token_params(c, True))
    if logits:
        out += 2 * c["hidden_size"] * c["vocab_size"]
    return out


def latent_decode_work(c: dict, ctxs: Iterable[int], kv_bytes: int = 2,
                       q_bytes: int = 2) -> tuple:
    """(flops, bytes) of the latent attention calls of one decode step,
    all layers: ``ctxs`` holds the keys each live slot attends. Each key's
    latent row is read once for every head; each slot's absorbed queries
    and latent outputs once each."""
    h, kl, r = (c["num_attention_heads"], c["kv_lora_rank"],
                c["qk_rope_head_dim"])
    ctxs = list(ctxs)
    total = sum(ctxs)
    flops = attention_flops(c, total)
    nbytes = ((kl + r) * total * kv_bytes
              + len(ctxs) * h * ((kl + r) + kl) * q_bytes)
    layers = c["num_hidden_layers"]
    return layers * flops, layers * nbytes
