"""Plain float32 DeepSeek-V3 reference: the forward pass of a DeepSeek-V3
decoder in straightforward ``jax.numpy``, independent of the code under
test, following the published equations (DeepSeek-V3 Technical Report,
arXiv:2412.19437, section 2.1; the model's ``config.json``).

Per layer: RMSNorm; multi-head latent attention with a query LoRA
(``wq_a`` -> RMSNorm -> ``wq_b``) and a key/value LoRA (``wkv_a`` -> the
512-wide latent, RMSNorm, ``wkv_b`` expanding it to every head's 128-wide
key and value; 64-wide decoupled RoPE key shared by the heads); YaRN on the
RoPE head (frequencies blended between beta_fast and beta_slow over the
original 4096 positions, softmax scale (128 + 64)**-0.5 * mscale**2); then
RMSNorm and either the dense SwiGLU MLP (the leading dense layers) or the
MoE layer: a float32 router over all its experts, sigmoid scores, a
per-expert correction bias added for selection only, 8 groups scored by
the sum of their two best biased scores, the best 4 groups kept, the top 8
experts taken inside them, gates the unbiased scores renormalised and
times 2.5; the held experts' SwiGLU outputs weighted by their gates, plus
the shared expert. Then the final RMSNorm and the untied head. All in
float32 with every matrix product at ``Precision.HIGHEST``.

Departures, each noted: attention is not absorbed (per-head keys and
values are expanded from the latent, as the published equations write
it; the program absorbs ``wkv_b`` into its queries and outputs). RoPE
rotates halves; the published checkpoint interleaves its pairs, a fixed
permutation of the RoPE columns of ``wq_b`` and ``wkv_a`` that random
weights do not see. The multi-token-prediction layer is not part of the
serving model. Only the held experts' part of each MoE layer is added,
as on one chip of an expert-parallel group; routing still runs over
every expert, from the reference's own float32 scores.

No kernels, no cache, no batching: each sequence runs whole, layer by
layer, with attention in blocks of query rows and the experts computed
for every row and weighted by their gates (zero where a row was not
routed), so that it fits on one chip beside nothing else.

Weights. The matrices are random, made from the configuration's fixed
``weights_seed`` by the served program's initialiser, and the reference
draws the same numbers by that initialiser's recipe: ``PRNGKey(seed)``
split into (embedding, layers, unused, head); the layer key split once
per layer (dense and MoE layers alike), each layer key into four
(attention, MLP or MoE, two unused); the attention key into five (q_a,
kv_a, kv_b, o, q_b); a dense MLP key into (up, down, gate); a MoE key into
four (router, gate, up, down), each expert's matrix drawn from that key
folded with the expert's index, and the shared expert drawn as a dense MLP
from the MoE key folded with the router's width. Each matrix is a
standard normal times 1/sqrt(fan-in), the embedding times 0.02, the tables
drawn at the vocabulary rounded up to a multiple of 512 rows. Every
matrix except the router is rounded to bfloat16, the type it is served
in; the router is served in float32.

The small leaves (``small_leaves``): each RMSNorm weight (the layers',
the query and latent norms', the final one) 1 + an offset N(0, 0.2**2)
from the run's seed, and each MoE layer's correction bias N(0, 0.1**2)
from a fixed stream (``BIAS_SEED``), in float32. The benchmark writes the
same draws into the program (``PROGRAM_LEAVES`` says where). The bias is
fixed because it decides how many rows the held experts get, which a
trained model's bias balances: drawn per run, it moved the held experts'
rows per step from 50 to 125 and the decode step by 9% between seeds.

``controls=("int8", "fp8")`` give the controls of the correctness check:
the same forward with every matrix (embedding, head and router included)
rounded to int8, or to float8 e4m3's mantissa, with one symmetric scale
per output channel. The class is exported as ``Qwen2Reference``, the name
the harness looks the reference up by.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256        # query rows per attention block
LOGIT_BLOCK = 256    # positions per unembedding block
NORM_OFFSET_STD = 0.2
ROUTER_BIAS_STD = 0.1
BIAS_SEED = 13
# The check reads only positions whose held experts' routing is settled:
# no change of this size in any biased router score, at any MoE layer,
# could change what the held experts add (``held_margin``). Routing is
# discrete, so where two experts or groups nearly tie, bfloat16 rounding
# legitimately picks the other one, and that position's logits move by
# far more than rounding; elsewhere they move by rounding alone. On the
# chip every flip (gap over FLIP_GAP) of 11 runs sat below this margin
# but one of 0.110 that stayed at twice it, and it keeps 14-31% of the
# positions read (PERF.md).
ROUTING_MARGIN = 5e-3
# Margins the readings report the check at, besides ROUTING_MARGIN.
MARGIN_SWEEP = (0.0, 3e-4, 1e-3, 2e-3, 3e-3, 5e-3, 1e-2, 2e-2)
# A gap wider than this is counted as a flip in the readings (the Qwen
# cells' programs read at most 0.0185 on dense layers).
FLIP_GAP = 0.1

# Where the served program keeps each small leaf (tree path from the
# parameters' root); dense-layer leaves are stacked over the leading dense
# layers, the others over the MoE layers. A norm leaf holds the weight's
# offset from one.
PROGRAM_LEAVES = {
    "dense_ln1": ("dense_layers", "ln1", "scale"),
    "dense_ln2": ("dense_layers", "ln2", "scale"),
    "dense_q_norm": ("dense_layers", "attn", "q_norm"),
    "dense_kv_norm": ("dense_layers", "attn", "kv_norm"),
    "ln1": ("layers", "ln1", "scale"),
    "ln2": ("layers", "ln2", "scale"),
    "q_norm": ("layers", "attn", "q_norm"),
    "kv_norm": ("layers", "attn", "kv_norm"),
    "router_bias": ("layers", "moe", "router_bias"),
    "final_norm": ("final_norm", "scale"),
}

_BITS = {"bfloat16": (8, 7), "float32": (8, 23), "fp8": (4, 3)}


def _round(x, kind):
    e, m = _BITS[kind]
    return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)


def _lower(w, axis, kind):
    """Round ``w`` to ``kind`` with one symmetric scale per slice along
    ``axis`` (the output channel), back in float32."""
    red = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    top = {"int8": 127.0, "fp8": 240.0}[kind]
    scale = jnp.max(jnp.abs(w), axis=red, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if kind == "int8":
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return _round(w / scale, "fp8") * scale


def _normal(key, shape, std):
    # the barrier keeps XLA from folding ``std`` into the sampler's own
    # constant, which rounds differently from drawing, then scaling
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32)) * std


class Dims:
    """The configuration file's sizes, by their published names."""

    def __init__(self, c: dict):
        self.d = c["hidden_size"]
        self.f = c["intermediate_size"]
        self.fe = c["moe_intermediate_size"]
        self.h = c["num_attention_heads"]
        self.ql = c["q_lora_rank"]
        self.kl = c["kv_lora_rank"]
        self.nope = c["qk_nope_head_dim"]
        self.rope = c["qk_rope_head_dim"]
        self.vh = c["v_head_dim"]
        self.layers = c["num_hidden_layers"]
        self.dense = c["first_k_dense_replace"]
        held = c.get("held_experts") or {}
        self.first = int(held.get("first", 0))
        self.held = c["n_routed_experts"]
        self.experts = int(held.get("router_outputs", self.held))
        self.shared = c["n_shared_experts"]
        self.top_k = c["num_experts_per_tok"]
        self.groups = c["n_group"]
        self.topk_group = c["topk_group"]
        self.route_scale = float(c["routed_scaling_factor"])
        self.vocab = c["vocab_size"]
        self.vp = -(-self.vocab // 512) * 512
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.yarn = c.get("rope_scaling")


def small_leaves(c: dict, seed: int, served: str = "bfloat16") -> dict:
    """The run's small leaves, drawn on the host, the norms from ``seed``
    (any size of int) and the router bias from ``BIAS_SEED``: float32
    arrays; a norm leaf is the offset of its weight from one."""
    m = Dims(c)
    n_moe = m.layers - m.dense
    rng = np.random.default_rng([seed, 11])
    out = {}
    for name, shape, std in (
            ("dense_ln1", (m.dense, m.d), NORM_OFFSET_STD),
            ("dense_ln2", (m.dense, m.d), NORM_OFFSET_STD),
            ("dense_q_norm", (m.dense, m.ql), NORM_OFFSET_STD),
            ("dense_kv_norm", (m.dense, m.kl), NORM_OFFSET_STD),
            ("ln1", (n_moe, m.d), NORM_OFFSET_STD),
            ("ln2", (n_moe, m.d), NORM_OFFSET_STD),
            ("q_norm", (n_moe, m.ql), NORM_OFFSET_STD),
            ("kv_norm", (n_moe, m.kl), NORM_OFFSET_STD),
            ("final_norm", (m.d,), NORM_OFFSET_STD)):
        out[name] = (rng.standard_normal(shape) * std).astype(np.float32)
    out["router_bias"] = (np.random.default_rng(BIAS_SEED).standard_normal(
        (n_moe, m.experts)) * ROUTER_BIAS_STD).astype(np.float32)
    return out


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(dim: int, theta: float, yarn) -> jnp.ndarray:
    """RoPE inverse frequencies of a ``dim``-wide head under YaRN, as
    DeepSeek-V3's modelling code computes them."""
    freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not yarn:
        return jnp.asarray(freq, jnp.float32)

    def corr(rot):
        return (dim * math.log(yarn["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))
    low = max(math.floor(corr(yarn["beta_fast"])), 0)
    high = min(math.ceil(corr(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 - ramp                     # weight of the original frequency
    mixed = freq / yarn["factor"] * (1 - extra) + freq * extra
    return jnp.asarray(mixed, jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, pos, inv):
    """x: (S, heads, dim); rotate-half RoPE at absolute positions."""
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(m: Dims, scores, bias):
    """DeepSeek-V3's noaux_tc routing of one block of rows: (top-k expert
    ids, their gates) from float32 sigmoid ``scores`` (rows, experts)."""
    choice = scores + bias
    rows = scores.shape[0]
    g = choice.reshape(rows, m.groups, m.experts // m.groups)
    g_score = jax.lax.top_k(g, 2)[0].sum(-1)
    _, g_idx = jax.lax.top_k(g_score, m.topk_group)
    keep = jnp.zeros((rows, m.groups), bool).at[
        jnp.arange(rows)[:, None], g_idx].set(True)
    choice = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(rows,
                                                              m.experts)
    _, idx = jax.lax.top_k(choice, m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True) * m.route_scale


def held_margin(m: Dims, scores, bias):
    """Per row: the least change of every biased score, each by up to this
    much, that could change what the held experts add. Where a held expert
    is chosen, any expert or group crossing a top-k boundary could (its
    gate is renormalised over the chosen); where none is, one has to enter:
    its group has to be kept, and it has to pass the k-th candidate or a
    group swap has to lower that. A group's score is a sum of two, so it
    moves twice as far as one score; two of them cross at a quarter of
    their difference, two experts at half."""
    choice = scores + bias
    rows = choice.shape[0]
    per = m.experts // m.groups
    g = choice.reshape(rows, m.groups, per)
    g_score = jax.lax.top_k(g, 2)[0].sum(-1)                    # (rows, G)
    ranked = jax.lax.top_k(g_score, m.groups)[0]
    kth_group = ranked[:, m.topk_group - 1]
    m_group = ((kth_group - ranked[:, m.topk_group]) / 4
               if m.groups > m.topk_group else jnp.full(rows, jnp.inf))
    _, g_idx = jax.lax.top_k(g_score, m.topk_group)
    keep = jnp.zeros((rows, m.groups), bool).at[
        jnp.arange(rows)[:, None], g_idx].set(True)
    cand = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(rows, m.experts)
    top = jax.lax.top_k(cand, m.top_k + 1)[0]
    kth = top[:, m.top_k - 1]
    m_expert = (kth - top[:, m.top_k]) / 2
    ids = np.arange(m.first, m.first + m.held)
    held = choice[:, ids]                                       # (rows, n)
    kept = keep[:, ids // per]
    enter = jnp.where(kept,
                      jnp.minimum((kth[:, None] - held) / 2,
                                  m_group[:, None]),
                      (kth_group[:, None] - g_score[:, ids // per]) / 4)
    chosen = (kept & (held >= kth[:, None])).any(-1)
    return jnp.where(chosen, jnp.minimum(m_group, m_expert), enter.min(-1))


class Qwen2Reference:
    """The DeepSeek-V3 reference for one configuration file (``c``) and
    one run, under the name the harness looks a reference up by."""

    def __init__(self, c: dict, weights_seed: int, run_seed: int,
                 served: str = "bfloat16"):
        """``weights_seed`` draws the matrices, ``run_seed`` the small
        leaves; ``served``: the type the matrices are served in, which
        they are rounded to (the tests compare a float32 engine too)."""
        self.seed = weights_seed
        self.m = m = Dims(c)
        self._small = {k: jnp.asarray(v)
                       for k, v in small_leaves(c, run_seed, served).items()}
        self.layers = m.layers
        self.vocab = m.vocab
        inv = yarn_inv_freq(m.rope, m.theta, m.yarn)
        scale = (m.nope + m.rope) ** -0.5
        if m.yarn and m.yarn.get("mscale_all_dim"):
            scale *= yarn_mscale(m.yarn["factor"],
                                 m.yarn["mscale_all_dim"]) ** 2

        def dense(key, shape, control, round_to=served):
            w = _normal(key, shape, 1 / math.sqrt(shape[0]))
            if round_to:
                w = _round(w, round_to)
            return _lower(w, -1, control) if control else w

        def mlp_weights(key, width, control):
            k = jax.random.split(key, 3)
            return {"up": dense(k[0], (m.d, width), control),
                    "down": dense(k[1], (width, m.d), control),
                    "gate": dense(k[2], (m.d, width), control)}

        def layer_weights(key, moe, control):
            ks = jax.random.split(key, 4)
            a = jax.random.split(ks[0], 5)
            w = {"wq_a": dense(a[0], (m.d, m.ql), control),
                 "wkv_a": dense(a[1], (m.d, m.kl + m.rope), control),
                 "wkv_b": dense(a[2], (m.kl, m.h * (m.nope + m.vh)), control),
                 "wo": dense(a[3], (m.h * m.vh, m.d), control),
                 "wq_b": dense(a[4], (m.ql, m.h * (m.nope + m.rope)),
                               control)}
            if not moe:
                w["mlp"] = mlp_weights(ks[1], m.f, control)
                return w
            e = jax.random.split(ks[1], 4)
            w["router"] = dense(e[0], (m.d, m.experts), control,
                                round_to=None)
            ids = jnp.arange(m.first, m.first + m.held)
            w["experts"] = {
                name: jax.vmap(lambda i, k=e[j], s=shape: dense(
                    jax.random.fold_in(k, i), s, control))(ids)
                for j, name, shape in ((1, "gate", (m.d, m.fe)),
                                       (2, "up", (m.d, m.fe)),
                                       (3, "down", (m.fe, m.d)))}
            w["shared"] = mlp_weights(jax.random.fold_in(ks[1], m.experts),
                                      m.shared * m.fe, control)
            return w

        def table(key, shape, std, axis, control):
            w = _round(_normal(key, shape, std), served)
            return _lower(w, axis, control) if control else w

        def swiglu(x, w):
            g = jnp.dot(x, w["gate"], precision=HI)
            u = jnp.dot(x, w["up"], precision=HI)
            return jnp.dot(jax.nn.silu(g) * u, w["down"], precision=HI)

        def attention(x, w, small, pos):
            """Non-absorbed MLA over the S rows of ``x``, causal."""
            s = x.shape[0]
            q = jnp.dot(_rms(jnp.dot(x, w["wq_a"], precision=HI), m.eps)
                        * small["q_norm"], w["wq_b"], precision=HI)
            q = q.reshape(s, m.h, m.nope + m.rope)
            kv = jnp.dot(x, w["wkv_a"], precision=HI)
            latent = _rms(kv[:, :m.kl], m.eps) * small["kv_norm"]
            k_pe = _rope(kv[:, None, m.kl:], pos, inv)           # (S, 1, r)
            kvb = jnp.dot(latent, w["wkv_b"], precision=HI).reshape(
                s, m.h, m.nope + m.vh)
            k = jnp.concatenate([kvb[..., :m.nope],
                                 jnp.broadcast_to(k_pe, (s, m.h, m.rope))],
                                -1)
            v = kvb[..., m.nope:]
            q = jnp.concatenate([q[..., :m.nope],
                                 _rope(q[..., m.nope:], pos, inv)], -1)

            def block(args):
                qb, qpos = args
                sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
                sc = jnp.where(pos[None, None, :] <= qpos[None, :, None],
                               sc, -jnp.inf)
                p = jax.nn.softmax(sc, axis=-1)
                return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

            nb = s // Q_BLOCK
            o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, m.h,
                                              m.nope + m.rope),
                                    pos.reshape(nb, Q_BLOCK)))
            return jnp.dot(o.reshape(s, m.h * m.vh), w["wo"], precision=HI)

        def moe(x, w, bias):
            scores = jax.nn.sigmoid(jnp.dot(x, w["router"], precision=HI))
            idx, gates = route(m, scores, bias)
            # the gate of held expert j on each row (0 where not routed)
            ids = jnp.arange(m.first, m.first + m.held)
            g = jnp.sum(jnp.where(idx[:, :, None] == ids, gates[:, :, None],
                                  0.0), axis=1)                   # (S, held)

            def expert(carry, args):
                ew, gj = args
                return carry + gj[:, None] * swiglu(x, ew), None
            out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                                  (w["experts"], g.T))
            return (out + swiglu(x, w["shared"]),
                    held_margin(m, scores, bias))

        def layer(x, w, small, pos):
            """x: (S, d) float32 -> ((S, d), the rows' ``held_margin``, inf
            in a dense layer); causal over the S rows."""
            x = x + attention(_rms(x, m.eps) * small["ln1"], w, small, pos)
            hn = _rms(x, m.eps) * small["ln2"]
            if "mlp" in w:
                return x + swiglu(hn, w["mlp"]), jnp.full(x.shape[0],
                                                          jnp.inf)
            y, margin = moe(hn, w, small["router_bias"])
            return x + y, margin

        def head(x, unembed, fnorm, tokens):
            """Per row: the reference's best logit and the logit of
            ``tokens``."""
            lg = jnp.dot(_rms(x, m.eps) * fnorm, unembed,
                         precision=HI)[:, :m.vocab]
            at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
            return lg.max(axis=-1), at

        def pick(x, unembed, fnorm):
            """Per row: the token these logits put first."""
            lg = jnp.dot(_rms(x, m.eps) * fnorm, unembed,
                         precision=HI)[:, :m.vocab]
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        self._layer_weights = jax.jit(layer_weights, static_argnums=(1, 2))
        self._table = jax.jit(table, static_argnums=(1, 2, 3, 4))
        self._layer = jax.jit(layer)
        self._head = jax.jit(head)
        self._pick = jax.jit(pick)

    def _keys(self):
        key = jax.random.PRNGKey(self.seed)
        k_emb, k_layers, _, k_head = jax.random.split(key, 4)
        return k_emb, jax.random.split(k_layers, self.layers), k_head

    def _layer_small(self, i: int) -> dict:
        """Layer ``i``'s small leaves as weights (1 + offset for norms)."""
        m = self.m
        pre, j = ("dense_", i) if i < m.dense else ("", i - m.dense)
        out = {k: 1.0 + self._small[pre + k][j]
               for k in ("ln1", "ln2", "q_norm", "kv_norm")}
        if i >= m.dense:
            out["router_bias"] = self._small["router_bias"][j]
        return out

    def _hidden(self, toks, poss, control):
        """Final hidden states (before the last norm) of padded token rows,
        layer by layer, each layer's weights drawn once for all rows, and
        each row's least ``held_margin`` over the MoE layers."""
        m = self.m
        k_emb, layer_keys, _ = self._keys()
        emb = self._table(k_emb, (m.vp, m.d), 0.02, 0, control)
        hidden = [emb[jnp.asarray(t)] for t in toks]
        margin = [np.full(len(t), np.inf, np.float32) for t in toks]
        del emb
        for i in range(self.layers):
            w = self._layer_weights(layer_keys[i], i >= m.dense, control)
            small = self._layer_small(i)
            out = [self._layer(x, w, small, jnp.asarray(p))
                   for x, p in zip(hidden, poss)]
            hidden = [x for x, _ in out]
            margin = [np.minimum(a, np.asarray(b))
                      for a, (_, b) in zip(margin, out)]
            del w, out
        return hidden, margin

    def _unembed(self, control):
        m = self.m
        return self._table(self._keys()[2], (m.d, m.vp), 1 / math.sqrt(m.d),
                           -1, control)

    @staticmethod
    def _pad(seqs):
        toks, poss = [], []
        for t in seqs:
            s_pad = max(Q_BLOCK, 1 << (len(t) - 1).bit_length())
            toks.append(np.pad(t, (0, s_pad - len(t))).astype(np.int32))
            poss.append(np.arange(s_pad, dtype=np.int32))
        return toks, poss

    def logits(self, tokens: np.ndarray) -> np.ndarray:
        """Full (len(tokens), vocab) float32 logits of one sequence (for
        tests at small sizes)."""
        toks, poss = self._pad([np.asarray(tokens)])
        x = self._hidden(toks, poss, None)[0][0][:len(tokens)]
        hn = _rms(x, self.m.eps) * (1.0 + self._small["final_norm"])
        return np.asarray(jnp.dot(hn, self._unembed(None),
                                  precision=HI))[:, :self.vocab]

    def gaps(self, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
             controls: Sequence[str] = ()) -> dict:
        """For each (prompt, served tokens): run prompt + served[:-1]
        through the reference and read, at every position that produced a
        served token, how far that token's logit lies below the
        reference's best. ``max_gap`` is the widest over the positions
        whose routing is settled (``ROUTING_MARGIN``; ``settled`` their
        number), ``tokens`` the number of positions read. For each of ``controls`` a second forward runs in that
        precision, and ``control_max_gap[kind]`` is the same reading for
        the token the control puts first, judged by the float32 logits, at
        the same positions. The controls run one after another, each
        dropping its tables before the next, so that two heads at most are
        held at once. ``readings`` holds the same reading at each margin
        of ``MARGIN_SWEEP``, with the positions kept, the program's flips
        (gaps over ``FLIP_GAP``) and those among the unsettled."""
        toks, poss = self._pad([np.concatenate([np.asarray(p),
                                                np.asarray(s[:-1])])
                                for p, s in seqs])
        fnorm = 1.0 + self._small["final_norm"]
        hidden, margins = self._hidden(toks, poss, None)
        unembed = self._unembed(None)
        margin = np.concatenate([
            margins[j][len(p) - 1:len(p) - 1 + len(s)]
            for j, (p, s) in enumerate(seqs)])

        def per_position(picker):
            out = []
            for j, (prompt, served) in enumerate(seqs):
                served = np.asarray(served, np.int32)
                lo = len(prompt) - 1
                for b in range(0, len(served), LOGIT_BLOCK):
                    n = min(LOGIT_BLOCK, len(served) - b)
                    rows = jnp.asarray(np.arange(lo + b, lo + b + LOGIT_BLOCK)
                                       % len(toks[j]))
                    tk = np.zeros(LOGIT_BLOCK, np.int32)
                    tk[:n] = served[b:b + n]
                    best, at = self._head(hidden[j][rows], unembed, fnorm,
                                          picker(j, rows, tk))
                    out.append(np.asarray(best - at)[:n])
            return np.concatenate(out)

        gap = {"program": per_position(lambda j, rows, tk: jnp.asarray(tk))}
        for kind in controls:
            low, _ = self._hidden(toks, poss, kind)
            head = self._unembed(kind)
            gap[kind] = per_position(lambda j, rows, tk: self._pick(
                low[j][rows], head, fnorm))
            del low, head

        def widest(g, at):
            return float(g[margin >= at].max(initial=0.0))

        flips = gap["program"] > FLIP_GAP
        readings = {}
        for at in sorted({*MARGIN_SWEEP, ROUTING_MARGIN}):
            settled = margin >= at
            readings[f"{at:g}"] = dict(
                kept=int(settled.sum()), flips=int(flips.sum()),
                flips_unsettled=int((flips & ~settled).sum()),
                **{k: widest(g, at) for k, g in gap.items()})
        return {"max_gap": widest(gap["program"], ROUTING_MARGIN),
                "tokens": len(margin),
                "settled": int((margin >= ROUTING_MARGIN).sum()),
                "control_max_gap": {k: widest(gap[k], ROUTING_MARGIN)
                                    for k in controls},
                "readings": readings}
