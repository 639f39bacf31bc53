#!/usr/bin/env python3
"""Chip smoke test: serve qwen2.5-32b at its published widths on one TPU.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # the multi-chip paths only

One chip: builds the paged serving engine through
``ServeEngine.build(arch, config=ServeConfig(...))`` — qwen2.5-32b at its
published widths (d_model 5120, 40 q / 8 kv heads of 128, d_ff 27648, qkv
bias, the untied 152,064-token vocabulary) cut to 4 layers, one chip's
share of a 16-stage pipeline, random weights from ``--seed`` in bfloat16 —
then serves 8 greedy requests of 1024 prompt tokens and 32 new tokens each
through ``submit`` and ``run``. Before that it checks the paged-attention
kernel (decode and prefill-chunk shapes) and the K/V-exporting flash
prefill kernel against the float32 references of ``kernels/ref.py`` on the
chip, at the served shapes.

Four chips (``--four-chips``): the same model and prompts at tp=4 against
tp=1 (greedy streams bitwise equal, per-device KV bytes at 1/4), then four
one-chip replicas behind ``ReplicaRouter``, each replica's params and cache
on its own device, serving prefix-sharing sessions.

The last line of stdout is ``{"ok": true, "device": {...}}`` when every
check passed on a TPU. Without a TPU, or on any failure, the script exits
nonzero and never prints it. Earlier lines are informational.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.serve.config import ServeConfig  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.serve.router import ReplicaRouter  # noqa: E402
from repro.serve.scheduler import RequestState  # noqa: E402

ARCH = "qwen2.5-32b"
NUM_LAYERS = 4            # one chip's share of a 16-stage pipeline (64 / 16)
N_REQUESTS = 8
PROMPT_LEN = 1024
GEN_LEN = 32

# Kernel-vs-reference tolerance: |got - want| <= ATOL + RTOL |want|
# + PROB_ROUND * sum_i p_i |v_i|, elementwise.
# RTOL: the kernels return bf16 (2^-8 relative rounding), with margin.
# ATOL: outputs near zero, where relative error means nothing.
# PROB_ROUND: the MXU multiplies the probabilities as bf16 (unit roundoff
# 2^-8), so each term p_i v_i may be off by that much relative; where terms
# cancel (rows with few keys), the error follows sum p|v|, not |out|.
# A dropped page, a wrong mask or a wrong head mapping moves outputs by
# O(0.1-1).
RTOL, ATOL, PROB_ROUND = 2e-2, 2e-3, 2.0 ** -8


def serve_config(*, reduced: bool = False, tp=None, **over) -> ServeConfig:
    """The smoke's serving configuration (``reduced`` for CPU tests)."""
    kw = dict(reduced=reduced, cfg_overrides={"num_layers": NUM_LAYERS},
              compute_dtype=jnp.bfloat16, page_size=16, batch_slots=8,
              s_max=4096, prefill_chunk_tokens=256, temperature=0.0, tp=tp)
    if reduced:
        kw.update(cfg_overrides=None, s_max=128, prefill_chunk_tokens=32)
    kw.update(over)
    return ServeConfig(**kw)


def prompts_from_seed(seed: int, vocab: int, n: int, length: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, length).astype(np.int32) for _ in range(n)]


def tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


# ------------------------------------------------------------------ phases
def _close(name: str, got, want, mag) -> dict:
    """``mag``: the reference attention over |v|, i.e. sum_i p_i |v_i|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    bound = ATOL + RTOL * np.abs(want) + PROB_ROUND * np.asarray(mag)
    over = err / bound
    worst = np.unravel_index(np.nanargmax(over), over.shape)
    return {"check": name, "max_abs_err": float(err.max()),
            "worst_over_bound": float(over.max()),
            # where the worst element sits (batch, row, head, lane) and how
            # many elements miss: a failure's pattern names its cause
            "worst_at": tuple(int(i) for i in worst),
            "n_over": int((~(err <= bound)).sum()),
            "ok": bool(np.isfinite(got).all() and (err <= bound).all())}


def kernel_checks(cfg, *, batch: int, s_max: int, page_size: int,
                  chunk: int, seed: int) -> list:
    """The paged kernel at decode (Sq=1) and prefill-chunk (Sq=chunk)
    shapes and ``flash_prefill`` at (batch, chunk), each against the
    float32 reference at highest matmul precision. Block tables are a
    random permutation of the pool, so pages are scattered."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    mps = s_max // page_size
    P = batch * mps
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool_k = jax.random.normal(ks[0], (P, page_size, KV, hd), bf)
    pool_v = jax.random.normal(ks[1], (P, page_size, KV, hd), bf)
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(P).reshape(batch, mps), jnp.int32)
    # decode positions end mid-page; chunk starts leave partial pages
    pos = jnp.asarray(rng.integers(s_max // 4, s_max, batch), jnp.int32)
    start = jnp.asarray(rng.integers(0, s_max - chunk + 1, batch), jnp.int32)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def ref_paged(q, starts, pool_v):
        # one slot at a time bounds the reference's score tensor
        with jax.default_matmul_precision("highest"):
            fn = jax.jit(kref.paged_attention)
            return np.concatenate([np.asarray(fn(
                f32(q[b:b + 1]), f32(pool_k), f32(pool_v), bt[b:b + 1],
                starts[b:b + 1])) for b in range(batch)])

    def ref_flash(q, k, v):
        with jax.default_matmul_precision("highest"):
            return jax.jit(kref.flash_attention)(f32(q), f32(k), f32(v))

    out = []
    q1 = jax.random.normal(ks[2], (batch, 1, H, hd), bf)
    out.append(_close("paged_decode", kops.paged_decode(
        q1, pool_k, pool_v, bt, pos), ref_paged(q1, pos, pool_v),
        ref_paged(q1, pos, jnp.abs(pool_v))))
    qc = jax.random.normal(ks[3], (batch, chunk, H, hd), bf)
    out.append(_close("paged_prefill_chunk", kops.paged_prefill(
        qc, pool_k, pool_v, bt, start), ref_paged(qc, start, pool_v),
        ref_paged(qc, start, jnp.abs(pool_v))))
    k = jax.random.normal(ks[4], (batch, chunk, KV, hd), bf)
    v = jax.random.normal(ks[5], (batch, chunk, KV, hd), bf)
    o, k_out, v_out = kops.flash_prefill(qc, k, v)
    res = _close("flash_prefill", o, ref_flash(qc, k, v),
                 ref_flash(qc, k, jnp.abs(v)))
    exported = bool((np.asarray(k_out) == np.asarray(k)).all()
                    and (np.asarray(v_out) == np.asarray(v)).all())
    res.update(kv_export_exact=exported, ok=res["ok"] and exported)
    out.append(res)
    return out


def serve(engine, prompts, gen_len: int) -> dict:
    """Submit every prompt, run the engine dry, and report what came out."""
    t0 = time.perf_counter()
    reqs = [engine.submit(p, gen_len) for p in prompts]
    engine.run()
    wall = time.perf_counter() - t0
    done = [r for r in reqs if r.state is RequestState.DONE
            and len(r.tokens) == gen_len]
    failed = [r for r in reqs if r.state is RequestState.FAILED]
    return {"requests": len(reqs), "done": len(done), "failed": len(failed),
            "errors": [r.error for r in failed],
            "tokens": [list(map(int, r.tokens)) for r in reqs],
            "tokens_served": sum(len(r.tokens) for r in reqs),
            "wall_s": wall,
            "ok": len(done) == len(reqs) and not failed}


def placed_on(engine) -> set:
    """Every device holding a piece of the engine's params or cache."""
    devs = set()
    for leaf in jax.tree.leaves((engine.params, engine.cache)):
        devs |= set(leaf.devices())
    return devs


def tp_compare(arch: str, config: ServeConfig, prompts, gen_len: int,
               tp: int) -> dict:
    """The same model and prompts at tp=1 and at ``tp``: greedy streams
    must be bitwise equal and per-device KV bytes exactly 1/tp. Each engine
    is released before the next is built."""
    import dataclasses
    runs = {}
    for degree in (1, tp):
        eng = ServeEngine.build(arch, config=dataclasses.replace(
            config, tp=degree))
        res = serve(eng, prompts, gen_len)
        res["kv_bytes_per_device"] = eng.per_shard_kv_bytes()
        runs[degree] = res
        del eng
        gc.collect()
    one, many = runs[1], runs[tp]
    ratio = many["kv_bytes_per_device"] / one["kv_bytes_per_device"]
    return {"tp": tp, "streams_equal": one["tokens"] == many["tokens"],
            "kv_bytes_ratio": ratio, "served": [one["ok"], many["ok"]],
            "ok": (one["ok"] and many["ok"]
                   and one["tokens"] == many["tokens"] and ratio == 1 / tp)}


def router_replicas(arch: str, config: ServeConfig, devices, *, groups: int,
                    per_group: int, header_len: int, suffix_len: int,
                    gen_len: int, seed: int) -> dict:
    """One one-chip replica per device behind ``ReplicaRouter``; sessions
    in ``groups`` share a ``header_len``-token prefix. The first session of
    each group runs alone, the rest follow and must find the header in
    their replica's prefix cache. Every replica's params and cache must sit
    on its own device and every session must complete."""
    engines = [ServeEngine.build(arch, config=config, devices=[d])
               for d in devices]
    placement = [placed_on(e) == {d} for e, d in zip(engines, devices)]
    router = ReplicaRouter(engines)
    rng = np.random.default_rng(seed)
    vocab = engines[0].cfg.vocab_size
    headers = [rng.integers(1, vocab, header_len).astype(np.int32)
               for _ in range(groups)]
    reqs = []
    for wave in (range(1), range(1, per_group)):
        for header in headers:
            for _ in wave:
                tail = rng.integers(1, vocab, suffix_len).astype(np.int32)
                routed = router.submit(np.concatenate([header, tail]),
                                       gen_len)
                reqs.append(routed[0])
        router.drain()
    done = sum(r.state is RequestState.DONE and len(r.tokens) == gen_len
               for r in reqs)
    hits = sum(e.metrics.prefix_hits for e in engines)
    return {"replicas": len(engines), "own_device": placement,
            "routed": router.routed, "sessions": len(reqs), "done": done,
            "prefix_hits": hits,
            "ok": (all(placement) and done == len(reqs)
                   and hits == groups * (per_group - 1))}


# -------------------------------------------------------------------- main
class _CompileStats:
    """Counts backend compiles and persistent-cache hits, with seconds."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _say(**fields):
    """One informational line on stdout; a line with ``ok=False`` goes to
    stderr as well, where a failed run's tail is read."""
    line = " ".join(f"{k}={v}" for k, v in fields.items())
    print(line, flush=True)
    if fields.get("ok") is False:
        print(f"chip_smoke: failed: {line}", file=sys.stderr, flush=True)


def one_chip(seed: int) -> bool:
    config = serve_config()
    t0 = time.perf_counter()
    engine = ServeEngine.build(ARCH, config=config)
    cfg = engine.cfg
    impls_ok = (engine.paged_attn_impl == "kernel"
                and engine.prefill_attn_impl == "pallas")
    _say(phase="build", arch=ARCH, layers=cfg.num_layers, d_model=cfg.d_model,
         heads=f"{cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim}",
         d_ff=cfg.d_ff, vocab=cfg.vocab_size, dtype="bfloat16",
         page_size=engine.page_size, batch_slots=engine.batch_slots,
         s_max=engine.s_max, paged_attn_impl=engine.paged_attn_impl,
         prefill_attn_impl=engine.prefill_attn_impl,
         param_bytes=tree_bytes(engine.params),
         cache_bytes=engine.resident_cache_bytes(),
         build_s=f"{time.perf_counter() - t0:.2f}", ok=impls_ok)

    checks = kernel_checks(cfg, batch=config.batch_slots, s_max=config.s_max,
                           page_size=config.page_size,
                           chunk=config.prefill_chunk_tokens, seed=seed)
    for c in checks:
        _say(phase="kernel", **c)

    prompts = prompts_from_seed(seed, cfg.vocab_size, N_REQUESTS, PROMPT_LEN)
    res = serve(engine, prompts, GEN_LEN)
    _say(phase="serve", done=f"{res['done']}/{res['requests']}",
         failed=res["failed"], gen_len=GEN_LEN,
         tokens_served=res["tokens_served"], wall_s=f"{res['wall_s']:.2f}",
         ok=res["ok"])
    if res["errors"]:
        _say(phase="serve", errors=res["errors"])
    return impls_ok and res["ok"] and all(c["ok"] for c in checks)


def four_chips(seed: int) -> bool:
    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    config = serve_config()
    from repro import configs
    vocab = configs.get_config(ARCH).vocab_size
    prompts = prompts_from_seed(seed, vocab, N_REQUESTS, PROMPT_LEN)
    tp = tp_compare(ARCH, config, prompts, GEN_LEN, tp=4)
    _say(phase="tp", **tp)
    rt = router_replicas(ARCH, serve_config(s_max=1024), devices[:4],
                         groups=4, per_group=3, header_len=256,
                         suffix_len=32, gen_len=8, seed=seed)
    _say(phase="router", **rt)
    return tp["ok"] and rt["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4-vs-tp=1 and router-replica paths")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is {jax.default_backend()})",
              file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import setup_compile_cache
    _say(phase="setup", compile_cache=setup_compile_cache())
    stats = _CompileStats()
    t0 = time.perf_counter()
    try:
        ok = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    except Exception:  # noqa: BLE001 — any failure is a failed smoke
        traceback.print_exc()
        return 1
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    _say(phase="done", compiles=stats.compiles,
         compile_s=f"{stats.compile_s:.2f}", cache_hits=stats.cache_hits,
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         wall_s=f"{time.perf_counter() - t0:.2f}")
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
