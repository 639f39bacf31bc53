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
import os

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


def _paged_args(sq, H, KV, hd, pool_dtype):
    mps = S_MAX // PS
    P = B * mps
    return [((B, sq, H, hd), jnp.bfloat16), ((P, PS, KV, hd), pool_dtype),
            ((P, PS, KV, hd), pool_dtype), ((B, mps), jnp.int32),
            ((B,), jnp.int32)]


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


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "chunk"])
def test_paged_latent_kernel_compiles(one_chip, sq):
    cfg = configs.get_config("qwen2.5-32b-mla")
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim      # 576 lanes
    mps = S_MAX // PS
    hlo = _compile_hlo(
        lambda q, c, bt, st: pa.paged_attention_latent(
            q, c, bt, st, scale_dim=cfg.head_dim + cfg.qk_rope_head_dim,
            d_v=cfg.kv_lora_rank),
        one_chip, ((B, sq, cfg.num_heads, width), jnp.bfloat16),
        ((B * mps, PS, 1, width), jnp.bfloat16), ((B, mps), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_flash_prefill_kernel_compiles(one_chip):
    cfg = configs.get_config("qwen2.5-32b")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hlo = _compile_hlo(
        lambda q, k, v: fa.flash_attention_kv(q, k, v),
        one_chip, ((B, CHUNK, H, hd), jnp.bfloat16),
        ((B, CHUNK, KV, hd), jnp.bfloat16), ((B, CHUNK, KV, hd), jnp.bfloat16))
    assert "tpu_custom_call" in hlo
