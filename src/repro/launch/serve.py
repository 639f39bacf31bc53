"""Serving driver — thin CLI shim over ``repro.serve.ServeEngine``.

The engine owns the real serving path: single-dispatch batched prefill per
request (never stepping other slots), per-slot cache positions, continuous
batching with a priority/FIFO scheduler, greedy or temperature sampling, and
TTFT / tokens-per-s / p50-p95 metrics (see ``repro/serve/__init__.py`` for
the request lifecycle).

  PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --reduced \
      --requests 8 --gen-len 16

``LegacyServer`` preserves the seed's token-by-token prefill path, which
stepped the ENTIRE batch once per prompt token — O(prompt_len) dispatches
and, worse, it advanced every other active slot's cache while doing so
(cross-slot corruption). It exists only as the regression baseline for
``tests/test_serve.py`` and ``benchmarks/serve_bench.py``. Do not serve with
it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models.registry import get_model, reduced_config
from repro.serve.config import ServeConfig as EngineConfig
from repro.serve.engine import ServeEngine

log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class ServeConfig:
    """CLI run description: the engine build knobs (mapped onto
    :class:`repro.serve.config.ServeConfig` by :func:`build_engine`) plus
    the synthetic-traffic shape (``requests``/``prompt_len``/``gen_len``)
    this driver generates."""
    arch: str = "hymba-1.5b"
    reduced: bool = True
    batch_slots: int = 4
    s_max: int = 64
    requests: int = 8
    prompt_len: int = 8
    gen_len: int = 16
    seed: int = 0
    quantize_int8: bool = False
    temperature: float = 0.0
    top_k: int = 0            # 0 = off; >0 restricts sampling to k best
    top_p: float = 1.0        # 1.0 = off; <1 nucleus sampling
    page_size: int = 0        # 0 = dense cache; >0 enables paged KV
    num_pages: int = 0        # 0 = dense-equivalent pool (slots x s_max/ps)
    kv_backend: str = ""      # "" = layout follows page_size; else a
    #                           kvcache.BACKENDS name (e.g. paged_latent)
    prefill_mode: str = "parallel"   # 'parallel' (chunked) | 'scan' (anchor)
    prefill_chunk: int = 64   # max prompt tokens ingested between decode ticks
    # True = auto (page-level prefix caching whenever the config supports it:
    # paged + parallel prefill + dense/MoE/VLM family); False = hard off
    prefix_cache: bool = True


def build_engine(sc: ServeConfig) -> ServeEngine:
    return ServeEngine.build(sc.arch, config=EngineConfig(
        reduced=sc.reduced, batch_slots=sc.batch_slots,
        s_max=sc.s_max, seed=sc.seed, quantize_int8=sc.quantize_int8,
        temperature=sc.temperature, top_k=sc.top_k, top_p=sc.top_p,
        page_size=sc.page_size or None, num_pages=sc.num_pages or None,
        kv_backend=sc.kv_backend or None,
        prefix_cache=None if sc.prefix_cache else False,
        prefill_mode=sc.prefill_mode,
        prefill_chunk_tokens=sc.prefill_chunk))


class Server:
    """Backwards-compatible slot API over the engine.

    ``add_request`` prefills into a free slot with ONE jitted batch-1 call —
    it can no longer advance other active slots' caches (the seed bug).
    """

    def __init__(self, sc: ServeConfig):
        self.sc = sc
        self.engine = build_engine(sc)
        self.cfg = self.engine.cfg
        self.model = self.engine.model
        self.params = self.engine.params
        # last request to occupy each slot (outputs survive slot recycling
        # until the slot is reused, matching the legacy outputs[] contract)
        self._slot_hist: List[Optional[object]] = [None] * sc.batch_slots

    @property
    def cache(self):
        return self.engine.cache

    @property
    def slot_free(self) -> List[bool]:
        return [r is None for r in self.engine.slot_req]

    @property
    def outputs(self) -> List[List[int]]:
        return [list(r.tokens) if r is not None else []
                for r in self._slot_hist]

    def add_request(self, prompt: np.ndarray, gen_len: int) -> Optional[int]:
        """Prefill a prompt into a free slot; returns the slot or None."""
        free = self.engine.free_slots
        if not free:
            return None
        req = self.engine.submit(prompt, gen_len)
        self.engine.admit()
        self._slot_hist[req.slot] = req
        return req.slot

    def step_all(self) -> int:
        """One decode tick for every active slot; returns #active."""
        return self.engine.step()


class LegacyServer:
    """SEED-PATH REPLICA (quarantined): token-by-token full-batch prefill.

    Prefill reuses the lockstep decode step once per prompt token at the FULL
    batch width, so every other active slot's cache advances too — the
    cross-slot corruption the engine's isolated prefill fixes. Kept verbatim
    so tests can demonstrate the bug and benchmarks can quantify the win.
    """

    def __init__(self, sc: ServeConfig):
        cfg = configs.get_config(sc.arch)
        if sc.reduced:
            cfg = reduced_config(cfg)
        self.cfg, self.sc = cfg, sc
        self.model = get_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(sc.seed))
        if sc.quantize_int8:
            from repro.core.quantize import dequantize_params, quantize_params
            self.params = dequantize_params(quantize_params(self.params),
                                            jnp.float32)
        self.cache = self.model.init_cache(sc.batch_slots, sc.s_max, jnp.float32)
        # share the engine's jit cache so legacy-vs-engine benchmarks compare
        # steady-state serving, not compile amortization
        from repro.serve.engine import _jitted_decode
        self.decode = _jitted_decode(self.model, jnp.float32)
        self.slot_free = [True] * sc.batch_slots
        self.slot_remaining = [0] * sc.batch_slots
        self.cur_token = np.zeros((sc.batch_slots, 1), np.int32)
        self.outputs: List[List[int]] = [[] for _ in range(sc.batch_slots)]

    def add_request(self, prompt: np.ndarray, gen_len: int) -> Optional[int]:
        if True not in self.slot_free:
            return None
        slot = self.slot_free.index(True)
        self.slot_free[slot] = False
        self.slot_remaining[slot] = gen_len
        self.outputs[slot] = []
        for tok in prompt:
            self.cur_token[slot, 0] = tok
            logits, self.cache = self._step()
        return slot

    def _step(self):
        # a host copy: the CPU backend may read a NumPy array after
        # jnp.array/jnp.asarray return, and the prefill loop rewrites
        # cur_token before this async dispatch has read it
        batch = {"token": jnp.asarray(self.cur_token.copy())}
        if self.cfg.cross_attn_every:
            batch["image_embeds"] = jnp.zeros(
                (self.sc.batch_slots, self.cfg.num_image_tokens, self.cfg.d_model),
                jnp.float32)
        logits, cache, *_ = self.decode(self.params, self.cache, batch)
        return logits, cache

    def step_all(self) -> int:
        logits, self.cache = self._step()
        nxt = np.asarray(jnp.argmax(logits[:, 0, : self.cfg.vocab_size], -1))
        active = 0
        for s in range(self.sc.batch_slots):
            if self.slot_free[s]:
                continue
            self.outputs[s].append(int(nxt[s]))
            self.cur_token[s, 0] = nxt[s]
            self.slot_remaining[s] -= 1
            if self.slot_remaining[s] <= 0:
                self.slot_free[s] = True
            else:
                active += 1
        return active


def make_prompts(sc: ServeConfig, vocab: int) -> List[np.ndarray]:
    rng = np.random.default_rng(sc.seed)
    return [rng.integers(0, vocab, sc.prompt_len) for _ in range(sc.requests)]


def run(sc: ServeConfig) -> dict:
    """Serve sc.requests synthetic prompts through the engine; returns stats
    (legacy keys ``requests``/``wall_s``/``tokens_per_s`` plus the full
    engine metrics summary under ``metrics``)."""
    engine = build_engine(sc)
    for prompt in make_prompts(sc, engine.cfg.vocab_size):
        engine.submit(prompt, sc.gen_len)
    summary = engine.run()
    return {"requests": summary["requests"], "wall_s": summary["wall_s"],
            "tokens_per_s": summary["throughput_tokens_per_s"],
            "metrics": summary}


def run_legacy(sc: ServeConfig) -> dict:
    """Seed-path driver loop over LegacyServer (benchmark baseline only)."""
    server = LegacyServer(sc)
    pending = make_prompts(sc, server.cfg.vocab_size)
    t0 = time.time()
    while pending or not all(server.slot_free):
        while pending and True in server.slot_free:
            server.add_request(pending.pop(), sc.gen_len)
        server.step_all()
    dt = time.time() - t0
    total = sc.requests * sc.gen_len
    return {"requests": sc.requests, "wall_s": dt, "tokens_per_s": total / dt}


def main():
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(ServeConfig):
        name = "--" + f.name.replace("_", "-")
        if isinstance(f.default, bool):
            # BooleanOptionalAction also emits --no-<name>: a True default
            # (e.g. --reduced) was previously impossible to turn off
            ap.add_argument(name, action=argparse.BooleanOptionalAction,
                            default=f.default)
        else:
            ap.add_argument(name, type=type(f.default), default=f.default)
    ap.add_argument("--json", action="store_true", help="print full metrics")
    args = ap.parse_args()
    from repro.runtime.compile_cache import setup_compile_cache
    setup_compile_cache()
    sc = ServeConfig(**{f.name: getattr(args, f.name)
                        for f in dataclasses.fields(ServeConfig)})
    stats = run(sc)
    if args.json:
        print(json.dumps(stats["metrics"], indent=2, default=float))
    m = stats["metrics"]
    print(f"served {stats['requests']} requests, "
          f"{stats['tokens_per_s']:.1f} tok/s | "
          f"ttft p50 {m['ttft_s']['p50'] * 1e3:.1f} ms | "
          f"latency p95 {m['latency_s']['p95'] * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
