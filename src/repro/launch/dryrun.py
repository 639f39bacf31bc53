import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
against the production meshes, prove memory fit and shardability, and record
cost/memory/collective statistics + per-layer roofline probes as JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-gate]

The XLA_FLAGS line above MUST precede any jax import (device count locks at
first init); smoke tests and benchmarks never import this module.
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import hlo_stats, steps
from repro.launch.mesh import make_production_mesh
from repro.launch.probes import probes_for, recurrence_extra
from repro.models.registry import get_model
from repro.optim.adamw import AdamW
from repro.optim.schedules import cosine
from repro.sharding import specs

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _attn_impl_for(shape):
    # chunked (flash-style) attention bounds live scores to O(q_chunk * S);
    # einsum attention at S>=2k materializes multi-GB score tensors in bwd.
    return "chunked" if shape.seq_len >= 2048 else "einsum"


def _serve_param_sds(model, int8: bool = False):
    """Serve-time parameter shapes: bf16, or int8 for >=2-D (matmul/embed)
    weights — the paper's C5 quantization as it lands on the TPU weight
    stream (models upcast with .astype at use; per-channel scales add O(N)
    negligible work and are folded into the upcast on the real kernel path
    via kernels/quant_matmul.py)."""
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def conv(s):
        if not jnp.issubdtype(s.dtype, jnp.floating):
            return s
        if int8 and len(s.shape) >= 2:
            return jax.ShapeDtypeStruct(s.shape, jnp.int8)
        return jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
    return jax.tree.map(conv, p)


def build_gate(model, shape, mesh, *, microbatches: int = 1,
               int8_weights: bool = False, zero_stage: int = 3,
               remat="nothing"):
    """Returns (jitted_fn, args_sds) for the cell's step under `mesh`."""
    cfg = model.cfg
    batch_sds = model.input_specs(shape)
    batch_sh = steps.batch_shardings(model, batch_sds)
    if shape.kind == "train":
        opt = AdamW(learning_rate=cosine(3e-4, 100, 10000))
        state_sds = jax.eval_shape(
            lambda k: steps.init_train_state(model, opt, k), jax.random.PRNGKey(0))
        state_sh = steps.state_shardings(model, state_sds, zero_stage)
        step = steps.make_train_step(model, opt, attn_impl=_attn_impl_for(shape),
                                     remat=remat, microbatches=microbatches)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     donate_argnums=(0,))
        return fn, (state_sds, batch_sds)
    if shape.kind == "prefill":
        params_sds = _serve_param_sds(model, int8=int8_weights)
        params_sh = steps.param_shardings(model, params_sds)
        fn = jax.jit(steps.make_prefill(model, attn_impl=_attn_impl_for(shape),
                                        batch_chunks=microbatches),
                     in_shardings=(params_sh, batch_sh))
        return fn, (params_sds, batch_sds)
    # decode; int8 serving also quantizes the KV cache (per-head scales are
    # O(B*KV) extra — negligible; kernels/quant_matmul holds the real path)
    params_sds = _serve_param_sds(model, int8=int8_weights)
    params_sh = steps.param_shardings(model, params_sds)
    kv_dtype = jnp.int8 if int8_weights else jnp.bfloat16
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len, kv_dtype))
    cache_sh = steps.cache_shardings(model, cache_sds)
    fn = jax.jit(steps.make_decode_step(model),
                 in_shardings=(params_sh, cache_sh, batch_sh),
                 donate_argnums=(1,))
    return fn, (params_sds, cache_sds, batch_sds)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, with_probes: bool,
             verbose: bool = True, int8_weights: bool = False,
             zero_stage: int = 3, remat="nothing", mesh_shape=None) -> dict:
    cfg = configs.get_config(arch)
    shape = configs.get_shape(shape_name)
    model = get_model(cfg)
    if mesh_shape:
        mesh = specs.make_mesh(mesh_shape,
                               ("pod", "data", "model")[-len(mesh_shape):])
        mesh_name = "pod" + "x".join(map(str, mesh_shape))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "chips": int(mesh.devices.size),
           "params_total": cfg.total_params(),
           "params_active": cfg.active_params(),
           "int8_weights": int8_weights, "zero_stage": zero_stage,
           "remat": remat}
    t0 = time.time()
    HBM_BUDGET = 15.5 * 2**30   # v5e 16 GB minus runtime reserve
    # Serving cells: replicate weights across the data axis (SERVE_RULES)
    # whenever the bf16 model fits its 1/TP slice — kills the per-layer FSDP
    # weight all-gathers (hillclimb A); fall back to ZeRO-style fsdp sharding
    # for models too large (dbrx: 263 GB bf16 > 16-way TP slice).
    rules = specs.DEFAULT_RULES
    if shape.kind in ("prefill", "decode"):
        model_axis = 16
        if 2 * cfg.total_params() / model_axis <= 6 * 2**30:
            rules = specs.SERVE_RULES
            rec["serve_rules"] = "model_only"
    with specs.use_mesh(mesh, rules):
        # auto-microbatching: grow gradient-accumulation splits until the
        # per-device footprint fits HBM (production frameworks auto-tune this).
        # A split is only valid if the per-microbatch batch still divides the
        # data axes -- otherwise the batch de-shards and replicates (worse!).
        dp = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp *= mesh.shape[ax]
        mb_candidates = tuple(
            m for m in (1, 2, 4, 8)
            if (shape.global_batch // max(m, 1)) % dp == 0) or (1,)
        if shape.kind not in ("train", "prefill"):
            mb_candidates = (1,)
        for mb in mb_candidates:
            fn, args = build_gate(model, shape, mesh, microbatches=mb,
                                  int8_weights=int8_weights,
                                  zero_stage=zero_stage, remat=remat)
            compiled = fn.lower(*args).compile()
            m = hlo_stats.memory_stats(compiled)
            footprint = m["argument_bytes"] + m["temp_bytes"] + m["output_bytes"] \
                - m["alias_bytes"]
            if footprint <= HBM_BUDGET or mb == mb_candidates[-1]:
                break
            print(f"  [mb] {arch} x {shape_name}: mb={mb} footprint="
                  f"{footprint/2**30:.1f}GiB > budget; retrying mb={mb*2}",
                  flush=True)
        rec["microbatches"] = mb
        rec["gate"] = {
            "cost": hlo_stats.cost_stats(compiled),
            "memory": hlo_stats.memory_stats(compiled),
            "collectives": hlo_stats.collective_bytes(compiled.as_text()),
        }
        rec["gate"]["compile_s"] = round(time.time() - t0, 1)
        if with_probes:
            rec["probes"] = []
            # windowed archs probe with banded attention (exact sub-quadratic
            # flops); full-attention archs probe with einsum (exact O(S^2))
            probe_attn = "banded" if cfg.window else "einsum"
            for pr in probes_for(model, shape, attn_impl=probe_attn,
                                 remat=(remat if shape.kind == "train" else False),
                                 microbatches=mb, zero_stage=zero_stage):
                t1 = time.time()
                shd = tuple(specs.shardings_for(lg, sd)
                            for lg, sd in zip(pr.shardings, pr.args)) \
                    if pr.shardings else None
                pfn = jax.jit(pr.fn, in_shardings=shd)
                pcomp = pfn.lower(*pr.args).compile()
                rec["probes"].append({
                    "name": pr.name, "mult": pr.mult,
                    "cost": hlo_stats.cost_stats(pcomp),
                    "collectives": hlo_stats.collective_bytes(pcomp.as_text()),
                    "compile_s": round(time.time() - t1, 1),
                })
            rec["recurrence_extra"] = recurrence_extra(cfg, shape, shape.kind)
    rec["wall_s"] = round(time.time() - t0, 1)
    if verbose:
        g = rec["gate"]
        print(f"[OK] {arch} x {shape_name} x {mesh_name}: "
              f"flops={g['cost']['flops']:.3g} bytes={g['cost']['bytes']:.3g} "
              f"coll={g['collectives'].get('total', 0):.3g}B "
              f"arg={g['memory']['argument_bytes']/2**30:.2f}GiB/dev "
              f"temp={g['memory']['temp_bytes']/2**30:.2f}GiB/dev "
              f"({rec['wall_s']}s)", flush=True)
    return rec


def save(rec: dict):
    ART_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    (ART_DIR / name).write_text(json.dumps(rec, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="run the 2x16x16 multi-pod mesh (default single-pod)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--int8-weights", action="store_true")
    ap.add_argument("--zero", type=int, default=3)
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--mesh-shape", type=int, nargs="*", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for cfg, shape, skipped in configs.cells(include_skips=True):
            if skipped:
                print(f"[SKIP] {cfg.name} x {shape.name}: rule-based skip "
                      f"({cfg.notes.split(';')[-1].strip()})", flush=True)
                continue
            cells.append((cfg.name, shape.name))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            out = ART_DIR / f"{arch}__{shape}__{mesh_name}.json"
            if args.skip_existing and out.exists():
                print(f"[CACHED] {arch} x {shape} x {mesh_name}", flush=True)
                continue
            try:
                # probes only needed on the single-pod mesh (roofline table)
                rec = run_cell(arch, shape, multi_pod=mp,
                               with_probes=(not args.no_probes and not mp),
                               int8_weights=args.int8_weights,
                               zero_stage=args.zero, remat=args.remat,
                               mesh_shape=args.mesh_shape)
                save(rec)
            except Exception as e:
                failures.append((arch, shape, mesh_name, repr(e)))
                print(f"[FAIL] {arch} x {shape} x {mesh_name}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", *f[:3], f[3][:200])
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
