"""Work counts and peaks, pinned on hand-worked shapes."""
import pytest

import tinycell  # noqa: F401  (puts the benchmark on the import path)
from chipbench import workcount
from chipbench.peaks import peaks_for

# d 8, 2 q heads / 1 kv head of 4, d_ff 16, 3 layers, vocab 10
TOY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 3,
       "vocab_size": 10}
QWEN32 = {"hidden_size": 5120, "num_attention_heads": 40,
          "num_key_value_heads": 8, "head_dim": 128,
          "intermediate_size": 27648, "num_hidden_layers": 4,
          "vocab_size": 152064}


def test_layer_params_and_token_flops():
    # q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 64 + 64 + 64 + 384
    assert workcount.layer_matmul_params(TOY) == 576
    # 4 * heads 2 * hd 4 * ctx 5
    assert workcount.attention_flops(TOY, 5) == 160
    # 3 layers * (2*576 + 160) + unembed 2*8*10
    assert workcount.token_flops(TOY, 5, logits=True) == 4096
    assert workcount.token_flops(TOY, 5, logits=False) == 3936
    # 3 layers * (2*576*3 + 32 * (1+2+3)) + 160
    assert workcount.prompt_flops(TOY, 3) == 11104
    assert workcount.layer_matmul_params(QWEN32) == 487_587_840


def test_paged_decode_work_counts_live_context_once_per_kv_head():
    flops, nbytes = workcount.paged_decode_work(TOY, [3, 5])
    # per layer: 4*2*4*(3+5) flops; k+v 2*1*4*8 rows*2 B + q,out 2*2*2*4*2 B
    assert flops == 3 * 256
    assert nbytes == 3 * (128 + 64)
    # the qwen2.5-32b decode of 8 slots at 1,000 keys each, all layers
    f, b = workcount.paged_decode_work(QWEN32, [1000] * 8)
    assert f == 4 * 4 * 40 * 128 * 8000
    assert b == 4 * (2 * 8 * 128 * 8000 * 2 + 2 * 8 * 40 * 128 * 2)


def test_least_time_names_the_binding_peak():
    p = peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    t, bound = workcount.least_time(1e9, 819e9, p)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = workcount.least_time(197e12, 1.0, p)
    assert bound == "flops" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
