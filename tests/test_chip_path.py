"""CPU coverage of the path that runs on the chip.

* ``chip_smoke.py``'s phases at ``reduced_config`` (kernel checks, serving,
  tp=4 against tp=1, router replicas each on its own device) — ``main()``
  itself only ever runs on a TPU;
* the compile-cache helper's directory choice;
* params cast to the compute dtype at build serve the same greedy streams
  as the uncast tree;
* on a TPU the kernel wrappers raise for shapes they do not take instead
  of swapping in the jnp oracle (steered here by patching the wrappers'
  platform probe), and the engine refuses a kernel it cannot run.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.models.layers import CAST_ON_USE
from repro.models.registry import get_model, reduced_config
from repro import configs
from repro.serve.config import ServeConfig
from repro.serve.engine import ServeEngine

REPO = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- chip_smoke phases
def test_chip_smoke_refuses_without_tpu(capsys):
    """main() exits nonzero and prints no result line off the TPU."""
    cs = _chip_smoke()
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_kernel_checks_reduced():
    cs = _chip_smoke()
    cfg = reduced_config(configs.get_config(cs.ARCH))
    checks = cs.kernel_checks(cfg, batch=2, s_max=64, page_size=16,
                              chunk=16, seed=0)
    assert [c["check"] for c in checks] == [
        "paged_decode", "paged_prefill_chunk", "flash_prefill"]
    assert all(c["ok"] for c in checks), checks


def test_chip_smoke_serve_reduced():
    cs = _chip_smoke()
    engine = ServeEngine.build(cs.ARCH, config=cs.serve_config(reduced=True))
    assert engine.paged_attn_impl == "kernel"
    prompts = cs.prompts_from_seed(0, engine.cfg.vocab_size, 3, 40)
    res = cs.serve(engine, prompts, 5)
    assert res["ok"] and res["done"] == 3 and res["failed"] == 0, res
    assert all(len(t) == 5 for t in res["tokens"])


def test_chip_smoke_multichip_phases_reduced(multidevice):
    """tp=4 streams bitwise equal to tp=1 at 1/4 the KV bytes per device,
    and four replicas each wholly on its own device, sharing headers."""
    out = multidevice(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(REPO / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        cfg = cs.serve_config(reduced=True,
                              cfg_overrides=dict(num_heads=8, num_kv_heads=4))
        prompts = cs.prompts_from_seed(0, 512, 3, 40)
        tp = cs.tp_compare(cs.ARCH, cfg, prompts, 5, tp=4)
        assert tp["ok"] and tp["kv_bytes_ratio"] == 0.25, tp
        rt = cs.router_replicas(cs.ARCH, cs.serve_config(reduced=True),
                                jax.devices()[:4], groups=3, per_group=3,
                                header_len=64, suffix_len=8, gen_len=3,
                                seed=0)
        assert rt["ok"] and all(rt["own_device"]), rt
        print("OK")
    """, n_devices=4)
    assert "OK" in out


# ------------------------------------------------------------ compile cache
def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    from repro.runtime import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.setup_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ------------------------------------------------------- cast at build time
@pytest.mark.parametrize("arch", ["qwen2.5-32b", "moonshot-v1-16b-a3b",
                                  "qwen2.5-32b-mla", "hymba-1.5b"])
def test_cast_at_build_keeps_bf16_greedy_streams(arch):
    """build() stores the cast-on-use weights in bf16; the uncast float32
    tree served at compute_dtype=bf16 gives the same greedy tokens."""
    config = ServeConfig(batch_slots=2, s_max=64, page_size=16,
                         compute_dtype=jnp.bfloat16)
    built = ServeEngine.build(arch, config=config)
    dtypes = {jax.tree_util.keystr(p): x.dtype for p, x in
              jax.tree_util.tree_leaves_with_path(built.params)}
    assert any(d == jnp.bfloat16 for d in dtypes.values())
    for path, d in dtypes.items():
        name = path.rsplit("'", 2)[-2]
        if name not in CAST_ON_USE and not path.endswith("['unembed']['w']"):
            assert d == jnp.float32, path
    model = get_model(built.cfg)
    uncast = model.init(jax.random.PRNGKey(config.seed))
    plain = ServeEngine(model, uncast, **config.engine_kwargs())
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 400, n).astype(np.int32) for n in (19, 35, 7)]
    streams = []
    for eng in (built, plain):
        reqs = [eng.submit(p, 6) for p in prompts]
        eng.run()
        streams.append([r.tokens for r in reqs])
    assert streams[0] == streams[1]


# ---------------------------------------------- no oracle behind the device
@pytest.fixture
def as_tpu(monkeypatch):
    """Make the kernel wrappers believe they run on a TPU (their only
    platform probe); fresh traces, so no cached CPU trace answers."""
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _bf(*shape):
    return jnp.ones(shape, jnp.bfloat16)


@pytest.mark.parametrize("case", [
    "flash_positions", "flash_head_width", "flash_prefill_head_width",
    "paged_head_width", "wkv6_chunk", "selective_scan_chunk"])
def test_wrappers_raise_on_tpu_instead_of_oracle(as_tpu, case):
    q, k = _bf(1, 8, 2, 64), _bf(1, 8, 1, 64)
    calls = {
        "flash_positions": lambda: kops.flash_attention(
            _bf(1, 8, 2, 128), _bf(1, 8, 1, 128), _bf(1, 8, 1, 128),
            q_positions=jnp.zeros((1, 8), jnp.int32)),
        "flash_head_width": lambda: kops.flash_attention(q, k, k),
        "flash_prefill_head_width": lambda: kops.flash_prefill(q, k, k),
        "paged_head_width": lambda: kops.paged_decode(
            _bf(1, 1, 2, 64), _bf(4, 16, 1, 64), _bf(4, 16, 1, 64),
            jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32)),
        "wkv6_chunk": lambda: kops.wkv6(*(jnp.ones((1, 5, 1, 4)),) * 4,
                                        jnp.ones((1, 4)),
                                        jnp.zeros((1, 1, 4, 4)), chunk=4),
        "selective_scan_chunk": lambda: kops.selective_scan(
            jnp.ones((1, 5, 4)), jnp.ones((1, 5, 4)), jnp.ones((1, 5, 2)),
            jnp.ones((1, 5, 2)), -jnp.ones((4, 2)), jnp.zeros((1, 4, 2)),
            chunk=4),
    }
    with pytest.raises(ValueError, match="no TPU kernel"):
        calls[case]()


def test_engine_resolves_einsum_for_untileable_heads_on_tpu(as_tpu):
    """Head width 16 cannot be a lane-axis block: on a TPU the engine
    records the einsum path for both reads (and refuses 'kernel'),
    instead of a kernel that would fall back behind its back."""
    config = ServeConfig(batch_slots=2, s_max=64, page_size=16)
    eng = ServeEngine.build("qwen2.5-32b", config=config)
    assert eng.cfg.head_dim % 128
    assert eng.paged_attn_impl == "einsum"
    with pytest.raises(ValueError, match="paged_attn_impl='kernel'"):
        ServeEngine.build("qwen2.5-32b", config=ServeConfig(
            batch_slots=2, s_max=64, page_size=16, paged_attn_impl="kernel"))


def test_engine_refuses_kernel_for_ring_family():
    with pytest.raises(ValueError, match="paged_attn_impl='kernel'"):
        ServeEngine.build("hymba-1.5b", config=ServeConfig(
            batch_slots=2, s_max=64, page_size=16, paged_attn_impl="kernel"))


def test_tp_guard_names_the_devices_found():
    n = len(jax.devices())
    with pytest.raises(ValueError, match=rf"found {n} x cpu"):
        ServeEngine.build("qwen2.5-32b", config=ServeConfig(
            batch_slots=2, s_max=64, page_size=16, tp=n + 1))
