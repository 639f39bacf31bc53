"""Operations and bytes the algorithm needs, counted from shapes.

Every count here follows what the computation requires, never what one
implementation happens to do: attention reads the tokens each slot really
attends at that call, each KV head once, plus the queries and the output.
Nothing is counted from a kernel's grid, from ``s_max``, or from how often
a kernel re-reads a page, so a kernel that reads less shows a higher share
under the same yardstick.

``c`` is a configuration file's dict (published key names:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``num_hidden_layers``,
``vocab_size``).
"""
from __future__ import annotations

from typing import Iterable


def dims(c: dict):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, c["num_key_value_heads"], hd, c["intermediate_size"]


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the gated
    MLP's up, gate and down projections."""
    d, h, kv, hd, f = dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def attention_flops(c: dict, ctx: int) -> int:
    """One query row over ``ctx`` keys in one layer: q.k and p.v for every
    query head."""
    _, h, _, hd, _ = dims(c)
    return 4 * h * hd * ctx


def token_flops(c: dict, ctx: int, logits: bool) -> int:
    """Model FLOPs of one token at context ``ctx`` (keys it attends,
    itself included), through every layer, with the unembedding when its
    logits are needed."""
    n = c["num_hidden_layers"] * (2 * layer_matmul_params(c)
                                  + attention_flops(c, ctx))
    if logits:
        n += 2 * c["hidden_size"] * c["vocab_size"]
    return n


def prompt_flops(c: dict, prompt_len: int) -> int:
    """Model FLOPs of prefilling a prompt: every position attends the
    positions up to itself, and only the last one's logits are needed."""
    p = prompt_len
    per_layer = (2 * layer_matmul_params(c) * p
                 + attention_flops(c, 1) * p * (p + 1) // 2)
    return (c["num_hidden_layers"] * per_layer
            + 2 * c["hidden_size"] * c["vocab_size"])


def paged_decode_work(c: dict, ctxs: Iterable[int], kv_bytes: int = 2,
                      q_bytes: int = 2) -> tuple:
    """(flops, bytes) of the paged-attention calls of one decode step, all
    layers: ``ctxs`` holds the keys each live slot attends. Each KV head's
    keys and values are read once; the query and the output once each."""
    _, h, kv, hd, _ = dims(c)
    ctxs = list(ctxs)
    total = sum(ctxs)
    flops = 4 * h * hd * total
    nbytes = 2 * kv * hd * total * kv_bytes + 2 * len(ctxs) * h * hd * q_bytes
    layers = c["num_hidden_layers"]
    return layers * flops, layers * nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, 'flops' | 'bytes'): the least time the chip could take,
    and which of its two peaks binds."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
