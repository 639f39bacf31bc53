"""Config dataclasses for the repro framework.

Every assigned architecture is expressed as an ``ArchConfig``; the four
assigned input shapes are ``ShapeConfig``s. ``MemoryStrategy`` names the
paper's four optimization rungs (baseline / dual_clock / ultra_ram /
compiler_large_local) — see DESIGN.md §2 for the FPGA→TPU mapping.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple


class Family(str, enum.Enum):
    DENSE = "dense"
    MOE = "moe"
    ENCDEC = "encdec"  # [audio] whisper
    SSM = "ssm"        # rwkv6
    HYBRID = "hybrid"  # hymba
    VLM = "vlm"        # llama-3.2-vision
    CNN = "cnn"        # resnet20 (the paper's own model)


class MemoryStrategy(str, enum.Enum):
    """The paper's optimization ladder (§4.1-4.4), adapted to TPU VMEM."""

    BASELINE = "baseline"                # small VMEM budget, no overlap credit
    DUAL_CLOCK = "dual_clock"            # + movement/compute overlap (double buffering)
    ULTRA_RAM = "ultra_ram"              # + large VMEM budget (fewer partitions)
    COMPILER_LARGE_LOCAL = "compiler_large_local"  # + whole-layer residency planning


def replace_nested(obj, updates: dict):
    """``dataclasses.replace`` that also takes a dict for a field holding a
    dataclass (``{"moe": {"held": [0, 8]}}`` replaces inside ``moe``), and
    stores lists as tuples so the result stays hashable."""
    fields = {}
    for k, v in updates.items():
        cur = getattr(obj, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(cur):
            v = replace_nested(cur, v)
        elif isinstance(v, list):
            v = tuple(v)
        fields[k] = v
    return dataclasses.replace(obj, **fields)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input shape (seq_len x global_batch)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

ALL_SHAPES: Tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int          # routed experts the router scores
    top_k: int
    capacity_factor: float = 1.25   # GShard training dispatch only
    router_jitter: float = 0.0
    d_ff: int = 0             # routed expert width (0 = the arch's d_ff)
    shared_experts: int = 0   # always-on experts of width d_ff, summed into one
    scoring: str = "softmax"  # softmax | sigmoid (sigmoid adds a per-expert
    #                           correction bias for selection only)
    n_group: int = 1          # group-limited routing: experts in n_group
    topk_group: int = 1       # groups, top-k drawn from the best topk_group
    routed_scale: float = 1.0  # gates renormalised over top-k, then scaled
    # experts this device holds, (first, count); count 0 = all of them. The
    # router still scores every expert; the layer adds only its held
    # experts' part (expert parallelism without the exchange)
    held: Tuple[int, int] = (0, 0)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN context extension of the RoPE frequencies (DeepSeek-V3's
    ``rope_scaling`` block): frequencies blend between interpolated and
    original across the band set by beta_fast/beta_slow, and attention
    logits are scaled by mscale(factor, mscale_all_dim)**2."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int          # query heads (0 for attn-free)
    num_kv_heads: int       # GQA kv heads
    d_ff: int
    vocab_size: int
    head_dim: int = 0       # 0 => d_model // num_heads
    qkv_bias: bool = False
    mlp_bias: bool = False
    tie_embeddings: bool = False
    norm: str = "rmsnorm"   # rmsnorm | layernorm
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    moe: Optional[MoEConfig] = None
    # leading dense layers (width d_ff) before the MoE layers
    first_k_dense: int = 0
    # MLA (multi-head latent attention): >0 => cache a per-token
    # kv_lora_rank-dim latent + a qk_rope_head_dim decoupled RoPE head
    # instead of per-head K/V; head_dim doubles as qk_nope/v head width
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    q_lora_rank: int = 0     # >0 => queries via wq_a -> RMSNorm -> wq_b
    # hybrid / ssm
    ssm_state: int = 0
    window: int = 0              # sliding-window size for attention heads (0 = full)
    # enc-dec
    encoder_layers: int = 0      # >0 => enc-dec; num_layers is decoder depth
    # vlm
    cross_attn_every: int = 0    # >0 => cross-attn image layers every N layers
    num_image_tokens: int = 0
    # training shape overrides / skips
    skip_shapes: Tuple[str, ...] = ()
    notes: str = ""

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def padded_vocab(self) -> int:
        """Embedding/unembedding tables are padded to a 512 multiple so the
        vocab dim shards evenly on any mesh axis up to 512; logits beyond
        vocab_size are masked to -inf (layers.lm_logits)."""
        return ((self.vocab_size + 511) // 512) * 512

    # ----- parameter counting (for 6ND roofline + FSDP sizing) -----
    def _attn_params(self) -> int:
        if self.num_heads == 0:
            return 0
        hd = self.head_dim
        if self.kv_lora_rank:
            c, r = self.kv_lora_rank, self.qk_rope_head_dim
            q_out = self.num_heads * (hd + r)
            ql = self.q_lora_rank
            q = (self.d_model * ql + ql + ql * q_out if ql
                 else self.d_model * q_out)
            kv_a = self.d_model * (c + r) + c  # wkv_a + latent rmsnorm
            kv_b = c * self.num_heads * 2 * hd
            o = self.num_heads * hd * self.d_model
            return q + kv_a + kv_b + o
        q = self.d_model * self.num_heads * hd
        kv = 2 * self.d_model * self.num_kv_heads * hd
        o = self.num_heads * hd * self.d_model
        b = (self.num_heads * hd + 2 * self.num_kv_heads * hd) if self.qkv_bias else 0
        return q + kv + o + b

    def _ffn_params(self, gated: bool = True) -> int:
        mult = 3 if gated else 2
        return mult * self.d_model * self.d_ff

    def _moe_ffn_params(self) -> int:
        """Routed experts (all of them), shared experts and the router."""
        m = self.moe
        f = m.d_ff or self.d_ff
        return (3 * self.d_model * f * (m.num_experts + m.shared_experts)
                + self.d_model * m.num_experts)

    def layer_params(self) -> int:
        """Params of one decoder layer (dense part + routed experts); the
        MoE layer's when the model has one."""
        p = self._attn_params() + 2 * self.d_model  # 2 norms
        if self.moe:
            p += self._moe_ffn_params()
        else:
            p += self._ffn_params()
        if self.family == Family.SSM:
            # rwkv6: replaces attention with time-mix (r,k,v,w,g,o ~ 6 d^2) + channel-mix
            p = 6 * self.d_model * self.d_model + 2 * self.d_model * self.d_ff + 2 * self.d_model
        if self.family == Family.HYBRID:
            p += 2 * self.d_model * self.d_model  # parallel SSM in/out projections
        return p

    def total_params(self) -> int:
        emb = self.vocab_size * self.d_model
        unemb = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        layers = self.num_layers * self.layer_params()
        if self.moe and self.first_k_dense:
            layers -= self.first_k_dense * (self._moe_ffn_params()
                                            - self._ffn_params())
        if self.encoder_layers:
            enc = self.encoder_layers * (self._attn_params() + self._ffn_params(gated=False)
                                         + 2 * self.d_model)
            # decoder cross-attn blocks
            layers += self.num_layers * self._attn_params()
            layers += enc
        if self.cross_attn_every:
            n_cross = self.num_layers // self.cross_attn_every
            layers += n_cross * self._attn_params()
        return emb + unemb + layers + self.d_model  # final norm

    def active_params(self) -> int:
        """Activated params per token (= total for dense; routed top-k for MoE)."""
        if not self.moe:
            return self.total_params()
        m = self.moe
        f = m.d_ff or self.d_ff
        dense_layer = self._attn_params() + 2 * self.d_model + self.d_model * m.num_experts
        active_ffn = (m.top_k + m.shared_experts) * 3 * self.d_model * f
        n_moe = self.num_layers - self.first_k_dense
        layers = (n_moe * (dense_layer + active_ffn) + self.first_k_dense
                  * (self._attn_params() + 2 * self.d_model + self._ffn_params()))
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        return emb + layers + self.d_model
