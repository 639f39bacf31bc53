"""Plain float32 Qwen2 reference: the forward pass of a Qwen2 decoder in
straightforward ``jax.numpy``, independent of the code under test.

RMSNorm (eps from the configuration), RoPE at the configured theta with the
rotate-half layout, grouped-query attention with q/k/v biases, a SwiGLU MLP
and an untied output head, all in float32 with every matrix product at
``Precision.HIGHEST`` (a float32 product on a TPU is otherwise computed in
bfloat16 passes). No kernels, no cache, no batching: each sequence runs
whole, layer by layer, with attention in blocks of query rows so that it
fits on one chip beside nothing else.

Weights. The benchmark's matrices are random, made from the
configuration's fixed ``weights_seed`` by the served program's
initialiser, so that one compiled initialiser serves every run. The
reference draws the same numbers itself, from the same seed, by that
initialiser's published recipe:
``PRNGKey(seed)`` split into (embedding, layers, unused, head); the layer
key split once per layer, each layer key into four (attention, MLP, two
unused), the attention key into (q, k, v, o) and the MLP key into (up,
down, gate); each matrix a standard normal times 1/sqrt(fan-in), the
embedding times 0.02, the tables drawn at the vocabulary rounded up to a
multiple of 512 rows. Every matrix is rounded to bfloat16, the type it
is served in, and computed on in float32.

The small leaves, which that initialiser leaves at zero bias and unit norm
weight, come from the run's seed instead (``small_leaves``): q/k/v biases
N(0, 0.5²) rounded to the served type, and each RMSNorm weight 1 + an
offset N(0, 0.2²) in float32, the final norm included. The benchmark
writes the same draws into the program (``PROGRAM_LEAVES`` says where),
so a program that drops a bias or a norm weight reads wrong.

``controls=("int8", "fp8")`` give the controls of the correctness check:
the same forward with every matrix (embedding and head included) rounded
to int8, or to float8 e4m3's mantissa, with one symmetric scale per
output channel: the precisions below the bfloat16 the configuration
states.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512        # query rows per attention block
LOGIT_BLOCK = 256    # positions per unembedding block
BIAS_STD = 0.5
NORM_OFFSET_STD = 0.2

# Where the served program keeps each small leaf: its tree path, the
# leaf's shape (stacked over layers) and its type there. A norm leaf holds
# the weight's offset from one (the program applies 1 + offset).
PROGRAM_LEAVES = {
    "bq": ("layers", "attn", "bq"), "bk": ("layers", "attn", "bk"),
    "bv": ("layers", "attn", "bv"), "ln1": ("layers", "ln1", "scale"),
    "ln2": ("layers", "ln2", "scale"), "final_norm": ("final_norm", "scale"),
}


def _dims(c: dict):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    vp = -(-c["vocab_size"] // 512) * 512
    return d, h, c["num_key_value_heads"], hd, c["intermediate_size"], vp


# Roundings are explicit ``reduce_precision`` ops: XLA on the TPU may drop
# a float32 -> narrower -> float32 pair of converts as excess precision.
_BITS = {"bfloat16": (8, 7), "float32": (8, 23), "fp8": (4, 3)}


def _round(x, kind):
    e, m = _BITS[kind]
    return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)


def _lower(w, axis, kind):
    """Round ``w`` to ``kind`` with one symmetric scale per slice along
    ``axis`` (the output channel), back in float32: ``int8`` to 255
    levels, ``fp8`` to e4m3's 3 mantissa bits with the largest magnitude
    at 240."""
    red = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    top = {"int8": 127.0, "fp8": 240.0}[kind]
    scale = jnp.max(jnp.abs(w), axis=red, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if kind == "int8":
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return _round(w / scale, "fp8") * scale


def _normal(key, shape, std, served):
    # the barrier keeps XLA from folding ``std`` into the sampler's own
    # constant, which rounds differently from drawing, then scaling
    w = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32)) * std
    return _round(w, served)


def small_leaves(c: dict, seed: int, served: str = "bfloat16") -> dict:
    """The run's small leaves, drawn on the host from ``seed`` (any size of
    int): float32 arrays, biases already rounded to ``served``; a norm
    leaf is the offset of its weight from one."""
    d, h, kv, hd, _, _ = _dims(c)
    n = int(c["num_hidden_layers"])
    rng = np.random.default_rng([seed, 11])
    out = {}
    for name, shape, std in (("bq", (n, h * hd), BIAS_STD),
                             ("bk", (n, kv * hd), BIAS_STD),
                             ("bv", (n, kv * hd), BIAS_STD),
                             ("ln1", (n, d), NORM_OFFSET_STD),
                             ("ln2", (n, d), NORM_OFFSET_STD),
                             ("final_norm", (d,), NORM_OFFSET_STD)):
        x = (rng.standard_normal(shape) * std).astype(np.float32)
        if name[0] == "b":
            x = np.asarray(x.astype(jnp.dtype(served)), np.float32)
        out[name] = x
    return out


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half RoPE at absolute positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Qwen2Reference:
    """The reference for one configuration file (``c``) and one run."""

    def __init__(self, c: dict, weights_seed: int, run_seed: int,
                 served: str = "bfloat16"):
        """``weights_seed`` draws the matrices, ``run_seed`` the small
        leaves; ``served``: the type the weights are served in, which they
        are rounded to (the tests compare a float32 engine too)."""
        self.seed = weights_seed
        small = {k: jnp.asarray(v)
                 for k, v in small_leaves(c, run_seed, served).items()}
        self._small = small
        d, h, kv, hd, f, vp = _dims(c)
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.layers = int(c["num_hidden_layers"])
        self.vocab = int(c["vocab_size"])

        def dense(key, shape):
            return _normal(key, shape, 1 / math.sqrt(shape[0]), served)

        def layer_weights(key, small, control):
            ks = jax.random.split(key, 4)
            a = jax.random.split(ks[0], 4)
            m = jax.random.split(ks[1], 3)
            w = {"wq": dense(a[0], (d, h * hd)),
                 "wk": dense(a[1], (d, kv * hd)),
                 "wv": dense(a[2], (d, kv * hd)),
                 "wo": dense(a[3], (h * hd, d)),
                 "w_up": dense(m[0], (d, f)),
                 "w_down": dense(m[1], (f, d)),
                 "w_gate": dense(m[2], (d, f))}
            if control:
                w = {k: _lower(v, -1, control) for k, v in w.items()}
            w.update(bq=small["bq"], bk=small["bk"], bv=small["bv"],
                     ln1=1.0 + small["ln1"], ln2=1.0 + small["ln2"])
            return w

        def table(key, shape, std, axis, control):
            w = _normal(key, shape, std, served)
            return _lower(w, axis, control) if control else w

        def layer(x, w, pos):
            """x: (S, d) float32 -> (S, d); causal over the S rows."""
            s = x.shape[0]
            hn = _rms(x, self.eps) * w["ln1"]
            q = jnp.dot(hn, w["wq"], precision=HI) + w["bq"]
            k = jnp.dot(hn, w["wk"], precision=HI) + w["bk"]
            v = jnp.dot(hn, w["wv"], precision=HI) + w["bv"]
            q = _rope(q.reshape(s, h, hd), pos, self.theta)
            k = _rope(k.reshape(s, kv, hd), pos, self.theta)
            k = jnp.repeat(k, h // kv, axis=1)              # head i -> i // G
            v = jnp.repeat(v.reshape(s, kv, hd), h // kv, axis=1)

            def block(args):
                qb, qpos = args                             # (Q, h, hd)
                sc = jnp.einsum("qhd,khd->hqk", qb, k,
                                precision=HI) / math.sqrt(hd)
                sc = jnp.where(pos[None, None, :] <= qpos[None, :, None],
                               sc, -jnp.inf)
                p = jax.nn.softmax(sc, axis=-1)
                return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

            nb = s // Q_BLOCK
            o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, h, hd),
                                    pos.reshape(nb, Q_BLOCK)))
            x = x + jnp.dot(o.reshape(s, h * hd), w["wo"], precision=HI)
            hn = _rms(x, self.eps) * w["ln2"]
            g = jnp.dot(hn, w["w_gate"], precision=HI)
            u = jnp.dot(hn, w["w_up"], precision=HI)
            return x + jnp.dot(jax.nn.silu(g) * u, w["w_down"], precision=HI)

        def head(x, unembed, fnorm, tokens):
            """Per row: the reference's best logit and the logit of
            ``tokens``."""
            lg = jnp.dot(_rms(x, self.eps) * fnorm, unembed,
                         precision=HI)[:, :self.vocab]
            at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
            return lg.max(axis=-1), at

        def pick(x, unembed, fnorm):
            """Per row: the token these logits put first."""
            lg = jnp.dot(_rms(x, self.eps) * fnorm, unembed,
                         precision=HI)[:, :self.vocab]
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        self._layer_weights = jax.jit(layer_weights, static_argnums=2)
        self._table = jax.jit(table, static_argnums=(1, 2, 3, 4))
        self._layer = jax.jit(layer)
        self._head = jax.jit(head)
        self._pick = jax.jit(pick)
        self._dims = (d, h, kv, hd, f, vp)

    def _keys(self):
        key = jax.random.PRNGKey(self.seed)
        k_emb, k_layers, _, k_head = jax.random.split(key, 4)
        return k_emb, jax.random.split(k_layers, self.layers), k_head

    def _hidden(self, toks, poss, control):
        """Final hidden states (before the last norm) of padded token rows,
        layer by layer, each layer's weights drawn once for all rows."""
        d, *_, vp = self._dims
        k_emb, layer_keys, _ = self._keys()
        emb = self._table(k_emb, (vp, d), 0.02, 0, control)
        hidden = [emb[jnp.asarray(t)] for t in toks]
        del emb
        for i in range(self.layers):
            w = self._layer_weights(layer_keys[i], self._layer_small(i),
                                    control)
            hidden = [self._layer(x, w, jnp.asarray(p))
                      for x, p in zip(hidden, poss)]
            del w
        return hidden

    def _layer_small(self, i):
        return {k: v[i] for k, v in self._small.items()
                if k != "final_norm"}

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
        d, *_, vp = self._dims
        toks, poss = self._pad([np.asarray(tokens)])
        x = self._hidden(toks, poss, None)[0][:len(tokens)]
        unembed = self._table(self._keys()[2], (d, vp), 1 / math.sqrt(d),
                              -1, None)
        hn = _rms(x, self.eps) * (1.0 + self._small["final_norm"])
        return np.asarray(jnp.dot(hn, unembed, precision=HI))[:, :self.vocab]

    def gaps(self, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
             controls: Sequence[str] = ()) -> dict:
        """For each (prompt, served tokens): run prompt + served[:-1]
        through the reference and read, at every position that produced a
        served token, how far that token's logit lies below the
        reference's best. ``max_gap`` is the widest over all of them. For
        each of ``controls`` a second forward runs in that precision, and
        ``control_max_gap[kind]`` is the same reading for the token the
        control puts first, judged by the float32 logits."""
        d, *_, vp = self._dims
        k_head = self._keys()[2]
        toks, poss = self._pad([np.concatenate([np.asarray(p),
                                                np.asarray(s[:-1])])
                                for p, s in seqs])
        hidden = {None: self._hidden(toks, poss, None)}
        heads = {None: self._table(k_head, (d, vp), 1 / math.sqrt(d), -1,
                                   None)}
        for kind in controls:
            hidden[kind] = self._hidden(toks, poss, kind)
            heads[kind] = self._table(k_head, (d, vp), 1 / math.sqrt(d), -1,
                                      kind)
        fnorm = 1.0 + self._small["final_norm"]
        widest = {k: 0.0 for k in hidden}
        n_tok = 0
        for j, (prompt, served) in enumerate(seqs):
            served = np.asarray(served, np.int32)
            lo = len(prompt) - 1
            n_tok += len(served)
            for b in range(0, len(served), LOGIT_BLOCK):
                n = min(LOGIT_BLOCK, len(served) - b)
                rows = jnp.asarray(
                    np.arange(lo + b, lo + b + LOGIT_BLOCK) % len(toks[j]))
                tk = np.zeros(LOGIT_BLOCK, np.int32)
                tk[:n] = served[b:b + n]
                x = hidden[None][j][rows]
                for kind in hidden:
                    pick = (jnp.asarray(tk) if kind is None else
                            self._pick(hidden[kind][j][rows], heads[kind],
                                       fnorm))
                    best, at = self._head(x, heads[None], fnorm, pick)
                    gap = float(np.max(np.asarray(best - at)[:n]))
                    widest[kind] = max(widest[kind], gap)
        return {"max_gap": widest.pop(None), "tokens": n_tok,
                "control_max_gap": widest}
