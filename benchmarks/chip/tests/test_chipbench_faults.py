"""A whole run at a small size on the CPU, with the chip check skipped:
sound, it is correct; with the timed path broken underneath (a token
altered where it is produced, a decode step that hands back its cache
unchanged, the q/k/v biases or the norm weights dropped) or with the
control in the program's place (its matrices rounded to fp8 per output
channel) the check reads ``correct`` false. The other faults a check must
catch do not exist in these cells: no batch is averaged over, and no
exchange between chips takes place on one chip."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinycell
from chipbench.cell import BENCH_DIR, load_module, run_cell

REF = load_module(BENCH_DIR / "configs" / "qwen2_ref.py")


def _alter_tokens(engine):
    orig = engine._sample_rows
    calls = [0]

    def altered(logits):
        toks = np.array(orig(logits))
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks[0] = (toks[0] + 1) % engine.cfg.vocab_size
        return toks
    engine._sample_rows = altered


def _stale_cache(engine):
    orig = engine._decode

    def stale(params, cache, batch):
        logits, _ = orig(params, jax.tree.map(jnp.copy, cache), batch)
        return logits, cache
    engine._decode = stale


def _zero(names):
    def hook(engine):
        for name in names:
            *path, last = REF.PROGRAM_LEAVES[name]
            node = engine.params
            for k in path:
                node = node[k]
            node[last] = jnp.zeros_like(node[last])
    return hook


def _fp8_matrices(engine):
    def lower(path, w):
        if w.ndim < 2:
            return w
        return REF._lower(w.astype(jnp.float32), -1, "fp8").astype(w.dtype)
    engine.params = jax.tree_util.tree_map_with_path(lower, engine.params)


@pytest.mark.parametrize("fault,correct", [
    (None, True), (_alter_tokens, False), (_stale_cache, False),
    (_zero(["bq", "bk", "bv"]), False),
    (_zero(["ln1", "ln2", "final_norm"]), False),
    (_fp8_matrices, False)],
    ids=["sound", "token_altered", "state_unchanged", "bias_dropped",
         "norm_weight_dropped", "fp8_control"])
def test_check_catches_a_broken_timed_path(fault, correct, capsys):
    out = run_cell("qwen2.5-32b.chat", 2**31 + 3, 2.0, False,
                   t_start=time.perf_counter(), require_chip=False,
                   patch=tinycell.tiny_patch(0.05), engine_hook=fault)
    assert out["correct"] is correct
    gap = out["checks"]["max_logit_gap"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    if fault is None:
        # the biases and norm weights written after build keep the
        # program's placement: nothing recompiles inside the window
        assert "compiles_in_window=0 " in capsys.readouterr().err


def test_check_holds_the_program_to_the_stated_precision():
    """The program's own int8 KV path reads logits within the bfloat16
    program's spread, so the logit gap alone cannot fail it: the stored
    bits do."""
    patch = tinycell.tiny_patch(0.05)
    patch["config"]["serve"]["kv_backend"] = "paged_int8"
    out = run_cell("qwen2.5-32b.chat", 2**31 + 3, 2.0, False,
                   t_start=time.perf_counter(), require_chip=False,
                   patch=patch)
    assert out["correct"] is False
    assert out["checks"]["kv_cache_bits"] == {"value": 8, "limit": 16,
                                              "at_least": True}
    assert out["checks"]["weight_bits"]["value"] == 16
