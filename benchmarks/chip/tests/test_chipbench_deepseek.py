"""The DeepSeek-V3 cell on the CPU at a small size: the plain float32
reference against the served program (the same weights from the same
seed; the same logits through chunked prefill into latent pages and decode
through them), the cell through ``run_cell`` with its check, and the work
counts its per-layer metrics read."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import tinyds
from chipbench import workcount_deepseek as wd
from chipbench.cell import BENCH_DIR, build_engine, load_module, run_cell

REF = load_module(BENCH_DIR / "configs" / "deepseek_v3_ref.py")
WEIGHTS_SEED = 2**31 + 9


def _config(dtype):
    return {"arch": "deepseek-v3", "cfg_overrides": tinyds.SMALL_OVERRIDES,
            "model": tinyds.SMALL_MODEL, "reference": "deepseek_v3_ref",
            "weights_seed": WEIGHTS_SEED,
            "serve": {"compute_dtype": dtype, "batch_slots": 2, "s_max": 256,
                      "page_size": 16, "prefill_chunk_tokens": 32,
                      "temperature": 0.0, "kv_backend": "paged_latent"}}


def test_reference_draws_the_programs_weights():
    seed = 2**33 + 1
    eng = build_engine(_config("bfloat16"), seed)
    ref = REF.Qwen2Reference(tinyds.SMALL_MODEL, WEIGHTS_SEED, seed)
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))  # noqa: E731
    k_emb, layer_keys, k_head = ref._keys()
    m = ref.m
    np.testing.assert_array_equal(
        f32(eng.params["embed"]["table"]),
        f32(ref._table(k_emb, (m.vp, m.d), 0.02, 0, None)))
    np.testing.assert_array_equal(f32(eng.params["unembed"]["w"]),
                                  f32(ref._unembed(None)))
    for i in range(m.layers):
        w = ref._layer_weights(layer_keys[i], i >= m.dense, None)
        stack, j = (("dense_layers", i) if i < m.dense
                    else ("layers", i - m.dense))
        lp = eng.params[stack]
        for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"):
            np.testing.assert_array_equal(f32(lp["attn"][name][j]),
                                          f32(w[name]))
        small = ref._layer_small(i)
        for name in ("ln1", "ln2"):
            np.testing.assert_array_equal(1.0 + f32(lp[name]["scale"][j]),
                                          f32(small[name]))
        for name in ("q_norm", "kv_norm"):
            np.testing.assert_array_equal(1.0 + f32(lp["attn"][name][j]),
                                          f32(small[name]))
        if i < m.dense:
            for name in ("up", "gate", "down"):
                np.testing.assert_array_equal(
                    f32(lp["mlp"]["w_" + name][j]), f32(w["mlp"][name]))
            continue
        moe = lp["moe"]
        np.testing.assert_array_equal(f32(moe["router"][j]), f32(w["router"]))
        np.testing.assert_array_equal(f32(moe["router_bias"][j]),
                                      f32(small["router_bias"]))
        assert np.abs(f32(small["router_bias"])).max() > 0.05
        for name in ("up", "gate", "down"):
            np.testing.assert_array_equal(f32(moe["w_" + name][j]),
                                          f32(w["experts"][name]))
            np.testing.assert_array_equal(f32(moe["shared"]["w_" + name][j]),
                                          f32(w["shared"][name]))
    np.testing.assert_array_equal(f32(eng.params["final_norm"]["scale"]),
                                  ref._small["final_norm"])


def _served_logits(dtype, seed=5):
    """A 62-token prompt through chunks of 32, 16, 8, 4 and 2 into latent
    pages, then 6 decode steps reading them back: the program's logits at
    every served position, and the reference's."""
    eng = build_engine(_config(dtype), seed)
    seen = []
    orig = eng._sample_rows

    def spy(logits, *a):
        seen.append(np.asarray(logits)[:, 0, :512])
        return orig(logits, *a)
    eng._sample_rows = spy
    prompt = np.random.default_rng(0).integers(1, 512, 62).astype(np.int32)
    req = eng.submit(prompt, 7)
    while eng.scheduler.waiting or eng.active:
        eng.step()
    got = np.stack([s[req.slot if s.shape[0] > 1 else 0] for s in seen])
    return got, np.concatenate([prompt, req.tokens[:-1]]), req


def test_reference_logits_match_chunked_prefill_then_latent_decode():
    """In float32 the engine and the reference agree to rounding (the
    program absorbs wkv_b and reads latent pages through the kernel, the
    reference expands per-head keys and values): 2e-4 over logits of
    magnitude ~5 is float32 reassociation across three layers, while the
    same forward with its matrices in bfloat16 misses by far more
    (asserted), so the tolerance would catch a path computed a precision
    lower."""
    got, seq, req = _served_logits("float32")
    ref = REF.Qwen2Reference(tinyds.SMALL_MODEL, WEIGHTS_SEED, 5,
                             served="float32")
    want = ref.logits(seq)[61:]
    assert got.shape == want.shape == (7, 512)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert list(np.argmax(want, -1)) == req.tokens
    low = REF.Qwen2Reference(tinyds.SMALL_MODEL, WEIGHTS_SEED, 5,
                             served="bfloat16").logits(seq)[61:]
    assert np.abs(low - want).max() > 10 * 2e-4


def test_held_margin_bounds_what_moves_the_held_experts():
    """No change of every biased score by less than a row's
    ``held_margin`` changes which held experts it chooses, nor, where it
    chooses one, any expert it chooses; scores on a coarse grid make many
    near ties."""
    m = REF.Dims(tinyds.SMALL_MODEL)
    rng = np.random.default_rng(0)
    rows, trials = 300, 40
    scores = np.round(1 / (1 + np.exp(-rng.standard_normal(
        (rows, m.experts)))), 2).astype(np.float32)
    bias = (rng.standard_normal(m.experts) * 0.1).astype(np.float32)
    margin = np.asarray(REF.held_margin(m, jnp.asarray(scores),
                                        jnp.asarray(bias)))
    assert (margin < 3e-3).any() and np.median(margin) > 3e-3
    held = np.zeros(m.experts, bool)
    held[m.first:m.first + m.held] = True
    sign = np.where(held, 1.0, -1.0)
    unit = np.concatenate([rng.uniform(-1, 1, (trials - 2, m.experts)),
                           sign[None], -sign[None]])
    moved = (scores[:, None, :] + 0.99 * margin[:, None, None]
             * unit[None]).reshape(-1, m.experts).astype(np.float32)

    def chosen(sc):
        idx, _ = REF.route(m, jnp.asarray(sc), jnp.asarray(bias))
        out = np.zeros((len(sc), m.experts), bool)
        np.put_along_axis(out, np.asarray(idx), True, axis=1)
        return out
    base = chosen(scores)
    after = chosen(moved).reshape(rows, trials, m.experts)
    with_held = (base & held).any(-1)
    same = np.where(with_held[:, None],
                    (after == base[:, None]).all(-1),
                    ~(after & held).any(-1))
    assert same.all(), np.argwhere(~same)[:5]


@pytest.mark.parametrize("seed", [1, 2**33 + 5])
def test_check_passes_the_program_and_fails_fp8(seed):
    """The cell through ``run_cell`` at the small size: the program in
    bfloat16 reads a widest gap under 0.5 at the settled positions and
    the fp8 control over it; most positions are settled."""
    out = run_cell("deepseek-v3.reasoning", seed, 2.0, False,
                   t_start=time.perf_counter(), require_chip=False,
                   patch=tinyds.tiny_patch(0.5), controls=("int8", "fp8"))
    assert out["correct"]
    assert out["failed"] == 0
    assert out["checks"]["max_logit_gap"]["value"] <= 0.5
    assert out["control_max_logit_gap"]["fp8"] > 0.5
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


C = {"hidden_size": 7168, "intermediate_size": 18432,
     "moe_intermediate_size": 2048, "num_attention_heads": 128,
     "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
     "qk_rope_head_dim": 64, "v_head_dim": 128, "num_hidden_layers": 5,
     "first_k_dense_replace": 1, "n_routed_experts": 8,
     "held_experts": {"first": 0, "count": 8, "router_outputs": 256},
     "n_shared_experts": 1, "num_experts_per_tok": 8, "vocab_size": 129280}


def test_work_counts_by_hand():
    """One layer of each kind at the cell's widths, counted by hand."""
    # attention: q_a 7168x1536, q_b 1536x(128*192), kv_a 7168x576,
    # absorb 128x128x512 and 128x512x128, o (128*128)x7168
    mla = (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 128 * 128 * 512 * 2
           + 16384 * 7168)
    assert wd.mla_token_params(C) == mla == 187_105_280
    assert wd.ffn_token_params(C, False) == 3 * 7168 * 18432
    # router 7168x256, shared 3x7168x2048, routed 8 * 8/256 expert passes
    assert wd.ffn_token_params(C, True) == (7168 * 256 + 3 * 7168 * 2048
                                            + 0.25 * 3 * 7168 * 2048)
    # a key: 2 * 128 heads * (576 + 512)
    assert wd.attention_flops(C, 1) == 278_528
    ctx = 1000
    want = (5 * (2 * mla + 278_528 * ctx) + 2 * 3 * 7168 * 18432
            + 4 * 2 * wd.ffn_token_params(C, True) + 2 * 7168 * 129280)
    assert wd.token_flops(C, ctx, logits=True) == want
    flops, nbytes = wd.latent_decode_work(C, [1000, 24])
    assert flops == 5 * 278_528 * 1024
    assert nbytes == 5 * (576 * 1024 * 2 + 2 * 128 * (576 + 512) * 2)


def test_cell_config_states_the_published_keys():
    """The configuration file gives the published config.json's keys at its
    top level, as its ``model`` group (the one the harness reads) gives
    them, and changes only the keys that ``reduced`` lists."""
    import json
    config = json.loads(
        (BENCH_DIR / "configs" / "deepseek-v3-5L.json").read_text())
    model = config["model"]
    for key, value in model.items():
        if key != "held_experts":
            assert config[key] == value, key
    assert set(config["published"]) == set(config["reduced"])
    for key in config["reduced"]:
        assert config["published"][key] != config[key], key
    bench = json.loads((BENCH_DIR.parent.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
