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


def _paged_args(sq, H, KV, hd, pool_dtype, b=B, layers=None):
    """q, both pools, block table, starts and a layer index: the pools are
    one layer's, or with ``layers`` the layer-stacked pools read at the
    traced layer index (the served form)."""
    mps = S_MAX // PS
    stack = (layers,) if layers else ()
    pool = stack + (b * mps, PS, KV, hd)
    return [((b, sq, H, hd), jnp.bfloat16), (pool, pool_dtype),
            (pool, pool_dtype), ((b, mps), jnp.int32), ((b,), jnp.int32),
            ((), jnp.int32)]


def _paged_hlo(one_chip, args, layers, **scales):
    """``paged_attention`` compiled over ``args``, at the traced layer
    index when the pools are stacked; one layer's pools go as a stack of
    one at 0, as ``ops._paged_dispatch`` passes them."""
    def fn(q, k, v, bt, st, i, *sc):
        if not layers:
            k, v, i = k[None], v[None], 0
        return pa.paged_attention(q, k, v, bt, st, i,
                                  **dict(zip(scales, sc)))
    return _compile_hlo(fn, one_chip, *args, *scales.values())


# (query rows, layers): one layer's pools under the step's own id, then
# the layer-stacked pools at a traced layer as ``<step>-stacked``
_ONE_LAYER_THEN_STACKED = [
    pytest.param(sq, layers, id=step + "-stacked" * bool(layers))
    for layers in (None, 3) for sq, step in ((1, "decode"), (CHUNK, "chunk"))]


@pytest.mark.parametrize("sq,layers", _ONE_LAYER_THEN_STACKED)
def test_paged_kernel_compiles(one_chip, sq, layers):
    cfg = configs.get_config("qwen2.5-32b")
    hlo = _paged_hlo(one_chip, _paged_args(
        sq, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, jnp.bfloat16,
        layers=layers), layers)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sq,layers", _ONE_LAYER_THEN_STACKED)
def test_paged_int8_kernel_compiles(one_chip, sq, layers):
    cfg = configs.get_config("qwen2.5-32b")
    P = B * S_MAX // PS
    args = _paged_args(sq, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       jnp.int8, layers=layers)
    hlo = _paged_hlo(one_chip, args, layers,
                     k_scale=((P,), jnp.float32), v_scale=((P,), jnp.float32))
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
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")


def _sized(hlo: str, elems: int, fused: bool = True) -> dict:
    """name -> (opcode, the jax op it came from) of every instruction with
    ``elems`` elements; with ``fused`` False, only those that run as their
    own step (not inside a fusion's computation)."""
    inside = set(re.findall(r" fusion\(.*?calls=%([\w.-]+)", hlo))
    out, skip = {}, False
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            skip = not fused and head[1] in inside
        m = _INSTR.match(line)
        if (m and m[2] and not skip
                and math.prod(map(int, m[2].split(","))) == elems):
            op = _OP_NAME.search(line)
            out[m[1]] = (m[3], op[1].rsplit("/", 1)[-1] if op else "")
    return out


@pytest.mark.parametrize("cell,layers", [
    pytest.param(cell, layers, id=cell + "-stacked" * bool(layers))
    for layers in (None, 2) for cell in sorted(DECODE_CELLS)])
def test_paged_decode_kernel_reads_pool_as_stored(one_chip, cell, layers):
    """The decode kernel compiles at the cells' widths and takes both
    pools straight from the parameters — one layer's (P, ps, KV, hd)
    pools, or the layer-stacked pools at a traced layer: no instruction
    the size of a pool or of a layer (a copy, slice, reshape or relayout)
    precedes it. One layer's pools reach it as parameters or as a bitcast
    of a parameter to a stack of one, which moves nothing."""
    b, H, KV = DECODE_CELLS[cell]
    args = _paged_args(1, H, KV, 128, jnp.bfloat16, b, layers=layers)
    hlo = _paged_hlo(one_chip, args, layers)
    assert "tpu_custom_call" in hlo
    stack_elems = math.prod(args[1][0])
    sized = {**_sized(hlo, stack_elems),
             **_sized(hlo, stack_elems // (layers or 1))}
    params = {name for name, (op, _) in sized.items() if op == "parameter"}
    assert len(params) == 2, sized
    call = next(line for line in hlo.splitlines() if "tpu_custom_call" in line)
    operands = [name for name in sized if f"%{name}" in call]
    assert len(operands) == 2, (operands, sized)
    if layers:
        assert set(operands) == params, (operands, sized)
        assert set(sized) == params, sized
        return
    for name in operands:
        if name in params:
            continue
        line = next(l for l in hlo.splitlines()
                    if re.match(rf"\s*(?:ROOT )?%{re.escape(name)} = ", l))
        assert sized[name][0] == "bitcast", (name, sized)
        assert re.search(r"bitcast\([^)]*%(\S+)\)", line)[1] in params, line
    assert {op for op, _ in sized.values()} <= {"parameter", "bitcast"}, sized


# the pool-sized instructions a served step may hold: the pools as passed
# in and threaded through the layer loop, views of them, and the writes of
# a step's rows (or int8 pages) into them in place
_IN_PLACE = {"parameter", "get-tuple-element", "bitcast"}
_WRITES = {"scatter", "dynamic_update_slice"}


def _writes_in_place(op: str, src: str) -> bool:
    """A scatter or update-slice instruction (or a fusion around one) that
    comes from a write: not a copy or relayout on the write's behalf."""
    return (op in ("scatter", "dynamic-update-slice", "fusion")
            and src in _WRITES)


def _served_step_hlo(cell: str, step: str, pool: str, layers: int = 2):
    """The engine's jitted decode or prefill-chunk step for a Qwen2.5
    model at ``cell``'s slots and heads (published width otherwise),
    ``layers`` deep, over a paged cache of ``pool`` pages (int8 with its
    per-page scales), for the kernels' TPU path. Returns (the step, its
    argument shapes, elements of one layer's pool)."""
    from repro.launch import steps
    from repro.models.layers import cast_on_use
    from repro.models.registry import get_model, init_paged_cache
    b, H, KV = DECODE_CELLS[cell]
    cfg = dataclasses.replace(configs.get_config("qwen2.5-32b"),
                              num_layers=layers, num_heads=H,
                              num_kv_heads=KV, head_dim=128)
    model = get_model(cfg)
    mps = S_MAX // PS
    params = jax.eval_shape(lambda: cast_on_use(
        model.init(jax.random.PRNGKey(0)), jnp.bfloat16))
    cache = jax.eval_shape(lambda: init_paged_cache(
        model, b, S_MAX, page_size=PS, num_pages=b * mps,
        dtype=getattr(jnp, pool)))
    if pool == "int8":
        scale = jax.ShapeDtypeStruct((layers, b * mps, 1), jnp.float32)
        cache = dict(cache, k_scale=scale, v_scale=scale)
    if step == "decode":
        fn = steps.make_decode_step(model, compute_dtype=jnp.bfloat16,
                                    paged_attn_impl="kernel")
        batch = {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    else:
        fn = steps.make_prefill_chunk_paged(model, compute_dtype=jnp.bfloat16,
                                            attn_impl="kernel")
        batch = {"tokens": jax.ShapeDtypeStruct((1, CHUNK), jnp.int32),
                 "bt": jax.ShapeDtypeStruct((1, mps), jnp.int32),
                 "start": jax.ShapeDtypeStruct((), jnp.int32),
                 "floor": jax.ShapeDtypeStruct((), jnp.int32)}
    return fn, (params, cache, batch), math.prod(cache["k"].shape[1:])


@pytest.fixture
def tpu_kernels(monkeypatch):
    """The kernel wrappers take their TPU path while a step is lowered for
    the described chip; fresh traces, so no interpret-mode trace answers."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_served_steps_address_the_stacked_pool(one_chip, tpu_kernels, cell,
                                                step, pool):
    """The served steps hand each layer the layer-stacked K/V pools with
    the layer's index: no layer's pool is copied out of the stack and back,
    and no pool is relaid out. In the decode step no instruction has the
    size of one layer's pool, and the stack-sized ones are the pools as
    passed in and the writes of the step's rows (int8: pages) in place.
    The chunk step keeps only the page-grid kernel's read of the layer,
    per leaf a slice out of the stack and its fold to ``(P, ps, KV *
    hd)``; nothing is written back."""
    fn, args, layer_elems = _served_step_hlo(cell, step, pool)
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), args)
    hlo = jax.jit(fn, donate_argnums=(1,)).lower(*sds).compile().as_text()
    assert "tpu_custom_call" in hlo
    stacked = _sized(hlo, 2 * layer_elems)
    assert all(op in _IN_PLACE or _writes_in_place(op, src)
               for op, src in stacked.values()), stacked
    assert any(_writes_in_place(*v) for v in stacked.values()), stacked
    layer_sized = _sized(hlo, layer_elems)
    if step == "decode":
        assert not layer_sized, layer_sized
        return
    moved = [(op, src) for op, src in _sized(hlo, layer_elems,
                                             fused=False).values()
             if op not in _IN_PLACE]
    assert len(moved) <= 2 * 2, layer_sized        # per leaf: slice, fold
    assert all(src in ("dynamic_slice", "reshape;squeeze")
               for _, src in moved), moved
    assert not any("update" in op for op, _ in layer_sized.values())
