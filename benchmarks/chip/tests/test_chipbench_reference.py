"""The plain float32 reference against the served program at a small size
on the CPU: the same weights from the same seed, the same logits through
chunked prefill into the paged cache and decode through it, and the
check's control (the reference in int8 or fp8) reading wider gaps than
the program."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import tinycell
from chipbench.cell import BENCH_DIR, build_engine, load_module, run_cell

REF = load_module(BENCH_DIR / "configs" / "qwen2_ref.py")


WEIGHTS_SEED = 2**31 + 9


def _config(dtype):
    return {"arch": "qwen2.5-32b", "cfg_overrides": tinycell.SMALL_OVERRIDES,
            "model": tinycell.SMALL_MODEL, "reference": "qwen2_ref",
            "weights_seed": WEIGHTS_SEED,
            "serve": {"compute_dtype": dtype, "batch_slots": 2, "s_max": 256,
                      "page_size": 16, "prefill_chunk_tokens": 32,
                      "temperature": 0.0}}


def test_reference_draws_the_programs_weights():
    seed = 2**33 + 1
    eng = build_engine(_config("bfloat16"), seed)
    ref = REF.Qwen2Reference(tinycell.SMALL_MODEL, WEIGHTS_SEED, seed)
    k_emb, layer_keys, k_head = ref._keys()
    d, _, _, _, _, vp = ref._dims
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))  # noqa: E731
    np.testing.assert_array_equal(
        f32(eng.params["embed"]["table"]),
        f32(ref._table(k_emb, (vp, d), 0.02, 0, None)))
    np.testing.assert_array_equal(
        f32(eng.params["unembed"]["w"]),
        f32(ref._table(k_head, (d, vp), 1 / np.sqrt(d), -1, None)))
    for i in range(2):
        w = ref._layer_weights(layer_keys[i], ref._layer_small(i), None)
        lp = eng.params["layers"]
        for name, got in (("wq", lp["attn"]["wq"]), ("wk", lp["attn"]["wk"]),
                          ("wo", lp["attn"]["wo"]),
                          ("w_up", lp["mlp"]["w_up"]),
                          ("w_down", lp["mlp"]["w_down"]),
                          ("w_gate", lp["mlp"]["w_gate"])):
            np.testing.assert_array_equal(f32(got[i]), f32(w[name]))
        for name, got in (("bq", lp["attn"]["bq"]), ("bk", lp["attn"]["bk"]),
                          ("bv", lp["attn"]["bv"])):
            assert np.abs(f32(got[i])).max() > 0.1
            np.testing.assert_array_equal(f32(got[i]), f32(w[name]))
        for name in ("ln1", "ln2"):
            np.testing.assert_array_equal(1.0 + f32(lp[name]["scale"][i]),
                                          f32(w[name]))
    np.testing.assert_array_equal(
        f32(eng.params["final_norm"]["scale"]),
        ref._small["final_norm"])


def test_reference_logits_match_chunked_prefill_then_paged_decode():
    """In float32 the engine and the reference agree to rounding: a
    62-token prompt runs as chunks of 32, 16, 8, 4 and 2 written into the
    paged cache, then 6 decode steps read it back."""
    eng = build_engine(_config("float32"), 5)
    seen = []
    orig = eng._sample_rows

    def spy(logits):
        seen.append(np.asarray(logits)[:, 0, :1024])
        return orig(logits)
    eng._sample_rows = spy
    prompt = np.random.default_rng(0).integers(1, 1024, 62).astype(np.int32)
    req = eng.submit(prompt, 7)
    while eng.scheduler.waiting or eng.active:
        eng.step()
    got = np.stack([s[req.slot if s.shape[0] > 1 else 0] for s in seen])
    ref = REF.Qwen2Reference(tinycell.SMALL_MODEL, WEIGHTS_SEED, 5,
                             served="float32")
    want = ref.logits(np.concatenate([prompt, req.tokens[:-1]]))[61:]
    assert got.shape == want.shape == (7, 1024)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert list(np.argmax(want, -1)) == req.tokens


@pytest.mark.parametrize("seed", [1, 5])
def test_control_reads_wider_gaps_than_the_program(seed):
    """At this size (two layers of 256, ~33 served tokens) the program in
    bfloat16 reads a widest gap well under 0.05 and the fp8 control over
    it: the limit 0.05 passes the one and fails the other."""
    out = run_cell("qwen2.5-32b.chat", seed, 2.0, False,
                   t_start=time.perf_counter(), require_chip=False,
                   patch=tinycell.tiny_patch(0.05),
                   controls=("int8", "fp8"))
    assert out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] <= 0.05
    assert out["control_max_logit_gap"]["fp8"] > 0.05
