"""The served path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described (``topologies.get_topology_desc``) but not attached.
Interpret mode cannot show what this does — a block whose last two dims
break Mosaic's (8, 128) tiling rule, or scratch the chip refuses, passes
every interpret test and fails here.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
so describing it at import (or in ``conftest.py``) would make xdist
workers collect different tests. Every test here skips when no topology
can be described. Compiles run with the persistent compilation cache off:
an executable for an absent chip could be written but never read back.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa

# qwen2.5-32b at its published widths, the chip smoke's serving shapes
B, S_MAX, PS, CHUNK = 8, 4096, 16, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_hlo(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_args(sq, H, KV, hd, pool_dtype, b=B):
    mps = S_MAX // PS
    P = b * mps
    return [((b, sq, H, hd), jnp.bfloat16), ((P, PS, KV, hd), pool_dtype),
            ((P, PS, KV, hd), pool_dtype), ((b, mps), jnp.int32),
            ((b,), jnp.int32)]


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "chunk"])
def test_paged_kernel_compiles(one_chip, sq):
    cfg = configs.get_config("qwen2.5-32b")
    hlo = _compile_hlo(
        lambda q, k, v, bt, st: pa.paged_attention(q, k, v, bt, st),
        one_chip, *_paged_args(sq, cfg.num_heads, cfg.num_kv_heads,
                               cfg.head_dim, jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "chunk"])
def test_paged_int8_kernel_compiles(one_chip, sq):
    cfg = configs.get_config("qwen2.5-32b")
    P = B * S_MAX // PS
    args = _paged_args(sq, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       jnp.int8)
    hlo = _compile_hlo(
        lambda q, k, v, bt, st, ks, vs: pa.paged_attention(
            q, k, v, bt, st, k_scale=ks, v_scale=vs),
        one_chip, *args, ((P,), jnp.float32), ((P,), jnp.float32))
    assert "tpu_custom_call" in hlo


def _stored_width(cfg) -> int:
    """A latent page row as the TPU's pool stores it: c + r rounded up to
    whole 128-lane tiles (``ops.latent_row_width`` on the chip)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _latent_hlo(one_chip, cfg, b, sq, s_max, layers=None):
    """The latent kernel over one layer's pool, or with ``layers`` over
    the layer-stacked pool at a traced layer index (the served form)."""
    width = _stored_width(cfg)
    mps = s_max // PS
    stack = (layers,) if layers else ()
    args = [((b, sq, cfg.num_heads, width), jnp.bfloat16),
            (stack + (b * mps, PS, 1, width), jnp.bfloat16),
            ((b, mps), jnp.int32), ((b,), jnp.int32), ((), jnp.int32)]
    hlo = _compile_hlo(
        lambda q, c, bt, st, i: pa.paged_attention_latent(
            q, c, bt, st, i if layers else None,
            scale_dim=cfg.head_dim + cfg.qk_rope_head_dim,
            d_v=cfg.kv_lora_rank),
        one_chip, *args)
    return hlo, math.prod(args[1][0])


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "chunk"])
def test_paged_latent_kernel_compiles(one_chip, sq):
    cfg = configs.get_config("qwen2.5-32b-mla")   # 576 lanes, stored as 640
    hlo, _ = _latent_hlo(one_chip, cfg, B, sq, S_MAX)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sq,b", [(1, 64), (CHUNK, 1)],
                         ids=["decode", "chunk"])
def test_latent_kernel_compiles_at_deepseek_widths(one_chip, sq, b):
    """The latent kernel at DeepSeek-V3's cell: 128 heads, 576-wide rows
    (stored as 640), 64 slots of s_max 8192 in decode and a 256-row chunk,
    reading layer i of the cell's 5-layer stacked pool: the pool goes to
    the kernel straight from the parameters, with no copy of a layer."""
    cfg = configs.get_config("deepseek-v3")
    hlo, pool_elems = _latent_hlo(one_chip, cfg, b, sq, 8192, layers=5)
    assert "tpu_custom_call" in hlo
    sized = [m[3] for m in map(_INSTR.match, hlo.splitlines())
             if m and m[2] and math.prod(map(int, m[2].split(",")))
             in (pool_elems, pool_elems // 5)]      # the stack, or a layer
    assert sized and set(sized) <= {"parameter", "bitcast"}, sized


@pytest.mark.parametrize("tokens", [64, CHUNK], ids=["decode", "chunk"])
def test_expert_matmul_compiles_at_deepseek_widths(one_chip, tokens):
    """The dropless MoE layer at DeepSeek-V3's widths, 8 of 256 experts
    held: its grouped expert matmuls compile as the TPU's ragged dot."""
    from repro.models import layers as L
    from repro.models.transformer import _moe_dims
    cfg = configs.get_config("deepseek-v3")
    dims = dataclasses.replace(_moe_dims(cfg), held=(0, 8))
    shapes = jax.eval_shape(lambda: L.moe_init(jax.random.PRNGKey(0), dims))
    leaves, tree = jax.tree.flatten(shapes)
    hlo = _compile_hlo(
        lambda x, *w: L.moe_dropless(jax.tree.unflatten(tree, w), x,
                                     dims)[0],
        one_chip, ((tokens, 1, cfg.d_model), jnp.bfloat16),
        *[(l.shape, jnp.bfloat16 if l.ndim == 3 else l.dtype)
          for l in leaves])
    assert "ragged" in hlo


def test_flash_prefill_kernel_compiles(one_chip):
    cfg = configs.get_config("qwen2.5-32b")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hlo = _compile_hlo(
        lambda q, k, v: fa.flash_attention_kv(q, k, v),
        one_chip, ((B, CHUNK, H, hd), jnp.bfloat16),
        ((B, CHUNK, KV, hd), jnp.bfloat16), ((B, CHUNK, KV, hd), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


# the benchmark cells' decode geometries (benchmarks/chip/configs): slots,
# query heads, kv heads, at head width 128, s_max 4096 and page 16
DECODE_CELLS = {"qwen2.5-32b": (8, 40, 8), "qwen2.5-7b": (16, 28, 4)}
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_paged_decode_kernel_reads_pool_as_stored(one_chip, cell):
    """The decode kernel compiles at the cells' widths and takes both
    (P, ps, KV, hd) pools straight from the parameters: no instruction
    the size of a pool (a copy, reshape or relayout) precedes it."""
    b, H, KV = DECODE_CELLS[cell]
    args = _paged_args(1, H, KV, 128, jnp.bfloat16, b)
    hlo = _compile_hlo(
        lambda q, k, v, bt, st: pa.paged_attention(q, k, v, bt, st),
        one_chip, *args)
    assert "tpu_custom_call" in hlo
    pool_elems = math.prod(args[1][0])
    pool_sized = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m[2] and math.prod(map(int, m[2].split(","))) == pool_elems:
            pool_sized[m[1]] = m[3]
    assert len(pool_sized) == 2
    assert set(pool_sized.values()) == {"parameter"}, pool_sized
    call = next(line for line in hlo.splitlines() if "tpu_custom_call" in line)
    assert all(f"%{name}" in call for name in pool_sized)
