"""Cost functions against counts worked by hand at the cell's sizes."""

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness.peaks import least_seconds, peaks_of

SPEC = mf.load_json("configs", "mistral7b_widths_l8")["lm_spec"]
costs = mf.load_module("costs", "dense_gqa_lm")

# by hand, per layer: qkv 4096 x (4096 + 2*8*128) = 4096 x 6144; proj
# 4096 x 4096; up and down 2 x 4096 x 14336
LAYER = 4096 * 6144 + 4096 * 4096 + 2 * 4096 * 14336
HEAD = 4096 * 32000


def test_matmul_parameters():
    assert LAYER == 159_383_552
    assert costs.layer_matmul_params(SPEC) == LAYER
    assert costs.matmul_params(SPEC) == 8 * LAYER + HEAD == 1_406_140_416


def test_reference_param_count_adds_embedding_and_norms():
    ref = mf.load_module("references", "dense_gqa_lm")
    assert ref.param_count(SPEC) == 8 * LAYER + 2 * HEAD + (2 * 8 + 1) * 4096


def test_decode_bytes_at_8_layers_with_400_live_tokens():
    # K and V, 8 KV heads x 128, bf16, 8 layers: 2*8*128*2*8 = 32 KiB a token
    assert costs.kv_bytes_per_token(SPEC) == 32768
    want = 1_406_140_416 * 2 + 400 * 32768
    assert costs.decode_step_bytes(SPEC, 400) == want == 2_825_388_032
    # at 819 GB/s that is 3.45 ms a step, whatever the slot count
    assert least_seconds(0, want, "TPU v5 lite") == pytest.approx(3.4498e-3, rel=1e-3)


def test_prefill_flops_of_a_256_token_prompt():
    t = 256
    want = 2 * 8 * LAYER * t + 2 * 4096 * t * t * 8 + 2 * HEAD
    assert costs.prefill_flops(SPEC, t) == want
    assert want == pytest.approx(6.574e11, rel=1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_of("TPU v9 imaginary")
    assert peaks_of("TPU v5 lite")["bf16_flops"] == 197e12
