"""One run of one cell: build, warm up, drive the traffic, check, report.

Everything that belongs to a cell is found by name in files: the cell's
entry in ``BENCHMARK.json`` names its configuration and its traffic mix;
``configs/<config>.json`` holds the model and serving settings and names
its reference; ``traffic/<traffic>.json`` holds the mix;
``workloads/<cell>.json`` holds what is the cell's own (the offered rate,
the check's sample and limit); ``metrics/<metric>.py`` reads one metric.
A metric kept apart per cell because it moves another end-to-end metric
there (``step.decode_ms.offline``) is read by the reader of its longest
dotted prefix that has one (``metrics/step.decode_ms.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import statistics
import sys
from collections import Counter
from typing import Callable, Dict, List, Optional

import numpy as np

from . import driver as drv
from . import tracefile
from .peaks import peaks_for
from .traffic import Arrival, generate

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
CACHE_DIR = BENCH_DIR / ".jax_cache"
TRACE_DIR = BENCH_DIR / ".out" / "trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    mix: dict
    workload: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def cell_spec(name: str, bench: Optional[dict] = None) -> CellSpec:
    """The cell ``name`` as ``BENCHMARK.json`` and its files define it."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    workload = load_json(BENCH_DIR / "workloads" / f"{name}.json")
    mix = dict(load_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json"),
               **workload.get("traffic", {}))

    def mine(m):
        return name in m.get("workloads", [name])

    return CellSpec(
        name=name, chips=int(entry["chips"]),
        config=load_json(BENCH_DIR / "configs" / f"{entry['config']}.json"),
        mix=mix, workload=workload,
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def metric_reader(name: str) -> pathlib.Path:
    """``metrics/<name>.py``, or that of the longest dotted prefix of
    ``name`` that has a reader."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = BENCH_DIR / "metrics" / (".".join(parts[:n]) + ".py")
        if path.exists():
            return path
    raise FileNotFoundError(f"no reader for metric {name!r} in "
                            f"{BENCH_DIR / 'metrics'}")


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The configuration's plain reference (``configs/<reference>.py``)."""
    return load_module(BENCH_DIR / "configs" / f"{config['reference']}.py")


# ------------------------------------------------------------ the program
def build_engine(config: dict, seed: int):
    """``ServeEngine.build`` with the configuration file's settings: the
    matrices from its fixed ``weights_seed`` (one initialiser program for
    every run, found in the compile cache), then the small leaves the
    reference draws from the run's ``seed`` written over the program's."""
    import jax
    import jax.numpy as jnp
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine
    serve = dict(config["serve"])
    served = serve["compute_dtype"]
    serve["compute_dtype"] = getattr(jnp, served)
    kw = dict(reduced=False, cfg_overrides=config["cfg_overrides"],
              seed=int(config["weights_seed"]), **serve)
    engine = ServeEngine.build(config["arch"], config=ServeConfig(**kw))
    ref = reference_module(config)
    for name, value in ref.small_leaves(config["model"], seed,
                                        served).items():
        *path, last = ref.PROGRAM_LEAVES[name]
        node = engine.params
        for k in path:
            node = node[k]
        old = node[last]
        if old.shape != value.shape:
            raise ValueError(f"{name}: the program's leaf is {old.shape}, "
                             f"the reference draws {value.shape}")
        # placed as the program placed the leaf it replaces: a committed
        # leaf among uncommitted ones changes the jitted steps' cache key,
        # and they recompile inside the window
        value = value.astype(old.dtype)
        node[last] = (jax.device_put(value, old.sharding) if old.committed
                      else jnp.asarray(value))
        old.delete()
    return engine


def warm_groups(arrivals: List[Arrival], slots: int) -> Dict[int, int]:
    """{prompt length: largest group} for every length that can be admitted
    in one tick by more than one request: requests admitted together are
    consecutive in the FIFO queue, at most ``slots`` of them, and only
    equal lengths are prefilled as one group."""
    out: Dict[int, int] = {}
    lens = [len(a.prompt) for a in arrivals]
    for i in range(len(lens)):
        for ln, m in Counter(lens[i:i + slots]).items():
            if m > 1:
                out[ln] = max(out.get(ln, 1), m)
    return out


def warm_up(engine, arrivals: List[Arrival], slots: int, vocab: int,
            rng: np.random.Generator):
    """Run every shape the traffic can reach once, through submit/step:
    one request whose chunk plan holds every chunk size of the engine's
    ladder, and, per length that can be grouped, groups of 2..m requests
    of that length. Two output tokens each: a prefill and a decode."""
    ladder = list(engine.prefill_ladder)
    lengths = [min(sum(ladder), engine.s_max - 1)]
    batches = [[lengths[0]]]
    for ln, m in sorted(warm_groups(arrivals, slots).items()):
        batches.extend([ln] * k for k in range(2, m + 1))
    for batch in batches:
        for ln in batch:
            engine.submit(rng.integers(1, vocab, ln).astype(np.int32), 2)
        while engine.scheduler.waiting or engine.active:
            engine.step()


class CompileCounter:
    """Counts compiles and compilation-cache hits while ``active``, and
    names each program that compiled or loaded then."""

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.compiles = 0
        self.cache_hits = 0
        self.names: List[str] = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="?", **_):
        if (self.active
                and event == "/jax/core/compile/backend_compile_duration"):
            self.compiles += 1
            self.names.append(f"{fun_name}:{secs:.3f}s")

    def _event(self, event, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class WindowHooks:
    """What a run does at the window's edges: count compiles from just
    before it opens until the drain ends, and with ``trace`` run the
    profiler over the same stretch."""

    def __init__(self, counter: CompileCounter, trace: bool):
        self.counter = counter
        self.trace = trace
        self.path: Optional[str] = None

    def before_window(self):
        self.counter.active = True
        if not self.trace:
            return
        if TRACE_DIR.exists():
            shutil.rmtree(TRACE_DIR)
        TRACE_DIR.mkdir(parents=True)
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def after_drain(self):
        self.counter.active = False
        if not self.trace:
            return
        import jax
        jax.profiler.stop_trace()
        found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        self.path = str(found[-1]) if found else None


# ------------------------------------------------------------ the record
@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""
    config: dict
    window: drv.Window
    sent: List[drv.Sent]
    ticks: List[drv.Tick]
    t_admit: Dict[int, Optional[float]]   # program span, by request id
    batch_slots: int
    setup_s: float
    trace: Optional[tracefile.Reduction]
    peaks: Optional[dict]

    @property
    def seconds(self) -> float:
        return self.window.end - self.window.start

    def in_window(self, t: float) -> bool:
        return self.window.start <= t < self.window.end

    def due_in_window(self) -> List[drv.Sent]:
        return [s for s in self.sent if self.in_window(s.due)]

    def window_ticks(self) -> List[drv.Tick]:
        return [t for t in self.ticks if self.in_window(t.t1)]


def percentile(xs, q: float) -> float:
    """The ``q`` percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ------------------------------------------------------------ the check
def sample_for_check(sent: List[drv.Sent], seed: int, min_tokens: int,
                     max_requests: int) -> List[drv.Sent]:
    """Requests whose served tokens the reference checks: the finished
    request with the most tokens, then others drawn from the seed until
    ``min_tokens`` are covered. Unfinished requests' tokens so far are
    drawn from only when the finished ones fall short."""
    done = [s for s in sent if s.state == "DONE"
            and len(s.req.tokens) == s.arrival.gen_len]
    part = [s for s in sent if s.state not in ("DONE", "FAILED", "CANCELLED")
            and len(s.req.tokens) > 1]
    rng = np.random.default_rng([seed, 7])
    out: List[drv.Sent] = []
    for pool in (done, part):
        if not pool:
            continue
        pool = sorted(pool, key=lambda s: (-len(s.req.tokens), s.req.rid))
        order = [pool[0]] + [pool[1:][i]
                             for i in rng.permutation(len(pool) - 1)]
        for s in order:
            if (sum(len(x.req.tokens) for x in out) >= min_tokens
                    or len(out) >= max_requests):
                return out
            out.append(s)
    return out


def run_check(spec: CellSpec, seed: int, sample: List[drv.Sent],
              controls=()) -> dict:
    """The reference's widest logit gap over the sampled served tokens."""
    ref = reference_module(spec.config).Qwen2Reference(
        spec.config["model"], int(spec.config["weights_seed"]), seed,
        spec.config["serve"]["compute_dtype"])
    seqs = [(s.arrival.prompt, np.asarray(s.req.tokens, np.int32))
            for s in sample]
    if not seqs:
        return {"max_gap": float("inf"), "tokens": 0,
                "control_max_gap": {}}
    return ref.gaps(seqs, controls=controls)


def stored_bits(engine) -> Dict[str, int]:
    """Bits per element the program stores its matrices and its KV pool
    in: the fewest over the parameter leaves of two or more axes, and over
    the cache leaves of three or more (the pools and any scales beside
    them, not the block tables or positions)."""
    import jax
    def fewest(tree, ndim):
        return min(8 * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree)
                   if leaf.ndim >= ndim)
    return {"weight_bits": fewest(engine.params, 2),
            "kv_cache_bits": fewest(engine.cache, 3)}


def free_engine(engine):
    """Release the program's device state before the reference runs."""
    import jax
    for leaf in jax.tree.leaves((engine.params, engine.cache)):
        try:
            leaf.delete()
        except Exception:  # noqa: BLE001 — already donated or deleted
            pass
    engine.params = engine.cache = None
    gc.collect()


# ------------------------------------------------------------ the run
def say(*parts):
    print(" ".join(str(p) for p in parts), file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             patch: Optional[dict] = None,
             engine_hook: Optional[Callable] = None,
             controls: tuple = ()) -> dict:
    """One run; returns the result object the last stdout line prints.
    ``patch`` ({"config"|"mix"|"workload": {key: value}}, merged one level
    deep) and ``engine_hook`` (called on the built engine) exist for the
    CPU tests, which run the harness at a small size with the chip check
    skipped, and for ``control.py`` (a rate sweep, another master seed,
    the program's own int8 KV path). ``controls`` ("int8", "fp8") also
    reads those controls' widest gaps on the same tokens (the readings a
    limit is set from); checked runs never ask for them."""
    import time
    import jax
    import jax.numpy as jnp
    clock = time.perf_counter
    spec = cell_spec(name)
    for part, upd in (patch or {}).items():
        merged = getattr(spec, part)
        for k, v in upd.items():
            merged[k] = (dict(merged[k], **v) if isinstance(v, dict)
                         and isinstance(merged.get(k), dict) else v)
    devices = jax.devices()
    peaks = None
    if require_chip:
        if jax.default_backend() != "tpu":
            raise NoChip(f"no TPU: JAX backend is {jax.default_backend()}")
        if len(devices) < spec.chips:
            raise NoChip(f"{name} needs {spec.chips} chips, JAX found "
                         f"{len(devices)}")
        peaks = peaks_for(devices[0].device_kind)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()

    engine = build_engine(spec.config, seed)
    if engine_hook is not None:
        engine_hook(engine)
    vocab = engine.cfg.vocab_size
    arrivals = generate(spec.mix, seed, vocab, seconds=seconds)
    slots = engine.batch_slots
    warm_up(engine, arrivals, slots, vocab,
            np.random.default_rng([seed, 1]))
    say("build", f"arch={spec.config['arch']}",
        f"paged_attn_impl={engine.paged_attn_impl}",
        f"prefill_attn_impl={engine.prefill_attn_impl}",
        f"slots={slots}", f"s_max={engine.s_max}",
        f"prefill_traces={engine.prefill_trace_count}")

    hooks = WindowHooks(counter, trace)
    d = drv.Driver(engine)
    setup_s = clock() - t_start
    if spec.mix["loop"] == "open":
        win = drv.run_open_loop(
            d, arrivals, pre_roll=float(spec.mix["pre_roll_s"]),
            seconds=seconds, drain_cap=float(spec.mix["drain_cap_s"]),
            hooks=hooks)
    else:
        win = drv.run_closed_loop(
            d, arrivals, slots=slots, backlog=int(spec.mix["backlog"]),
            seconds=seconds, fill_cap=float(spec.mix["fill_cap_s"]),
            hooks=hooks)
    mem = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    t_admit = {s.req.rid: engine.metrics.requests[s.req.rid].t_admit
               for s in d.sent}
    bits = stored_bits(engine)
    free_engine(engine)
    del engine

    red = None
    if hooks.path:
        red = tracefile.reduce(tracefile.load(hooks.path))
    rec = RunRecord(config=spec.config, window=win,
                    sent=d.sent, ticks=d.ticks, t_admit=t_admit,
                    batch_slots=slots, setup_s=setup_s, trace=red,
                    peaks=peaks)

    # what the window covered, and what came back
    if spec.mix["loop"] == "open":
        attempted = rec.due_in_window()
    else:
        attempted = [s for s in d.sent if s.submitted < win.end and not (
            s.stamps and s.state in drv.FINAL and s.stamps[-1] < win.start)]
    failed = [s for s in attempted
              if s.state in ("FAILED", "CANCELLED")
              or (spec.mix["loop"] == "open" and not s.stamps)]
    late = d.lateness
    say("window", f"seconds={rec.seconds:.3f}", f"requests_sent={len(d.sent)}",
        f"attempted={len(attempted)}", f"failed={len(failed)}",
        f"compiles_in_window={counter.compiles}",
        f"cache_loads_in_window={counter.cache_hits}",
        f"compiled_in_window={','.join(counter.names) or 'none'}",
        f"decode_ticks={sum(1 for t in rec.window_ticks() if t.decode_ctx)}",
        "generator_late_ms_p50={:.3f} max={:.3f}".format(
            1e3 * statistics.median(late), 1e3 * max(late)) if late else
        "generator_late_ms=none",
        f"drain_s={win.closed - win.end:.3f}")

    chk = spec.workload["check"]
    sample = sample_for_check(d.sent, seed, int(chk["min_tokens"]),
                              int(chk["max_requests"]))
    res = run_check(spec, seed, sample, controls)
    limit = float(chk["max_logit_gap"])
    checks = {
        "max_logit_gap": {"value": res["max_gap"], "limit": limit},
        "tokens_compared": {"value": res["tokens"],
                            "limit": int(chk["min_tokens"]), "at_least": True},
        "requests_failed": {"value": len(failed), "limit": 0},
    }
    # the precision the configuration states is what the program stores:
    # a narrower KV pool or matrix is another configuration, whatever its
    # logits read
    stated = 8 * jnp.dtype(spec.config["serve"]["compute_dtype"]).itemsize
    for k, v in bits.items():
        checks[k] = {"value": v, "limit": stated, "at_least": True}
    correct = (res["max_gap"] <= limit and res["tokens"] >= chk["min_tokens"]
               and not failed and min(bits.values()) >= stated)

    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        mod = load_module(metric_reader(m["name"]))
        v = mod.read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if red is not None:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops()],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
        say("trace", f"decode_steps={red.decode_steps}",
            f"prefill_steps={red.prefill_steps}",
            f"paged_decode_s={red.paged_decode_s}",
            f"paged_prefill_s={red.paged_prefill_s}")
    elif trace:
        say("trace", "none read")
    for k, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        say("check", k, c["value"], rel, c["limit"])
    if controls:
        out["control_max_logit_gap"] = res["control_max_gap"]
        say("control", "max_logit_gap", json.dumps(res["control_max_gap"]))
    out["checks"] = checks
    return out
