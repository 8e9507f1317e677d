"""The benchmark's own arithmetic: FLOPs of a token, the peaks table,
percentiles."""
import pytest

from bench import common, flops
from bench.drivers.lm import TINY_LM
from bench.common import load_json

QWEN3 = load_json(common.BENCH / "configs" / "qwen3-0.6b.json")


def test_qwen3_token_flops_against_a_hand_count():
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024,
    # gate/up/down 3 x 1024x3072; 28 layers; head 1024 x 151936
    layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    params = 28 * layer + 1024 * 151936
    assert params == 595_984_384
    assert flops.lm_matmul_params(QWEN3) == params
    # attention at context 100: QK and AV, 2 FLOPs each, 16 heads x 128
    attn = 28 * (2 * 16 * 128 * 100) * 2
    assert flops.lm_token_flops(QWEN3, 100) == 2 * params + attn


def test_request_flops_sum_prefill_and_decode_tokens():
    cfg = TINY_LM
    want = sum(flops.lm_token_flops(cfg, c) for c in range(1, 15))
    # 10 prompt tokens (contexts 1..10), 4 decode steps (contexts 11..14)
    assert flops.lm_request_flops(cfg, 10, 5) == want


def test_peaks_lookup():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(common.BenchError):
        common.peaks("TPU v9 imaginary")


def test_percentile_is_linear_between_order_statistics():
    assert common.percentile([], 95) is None
    assert common.percentile([3.0], 95) == 3.0
    assert common.percentile(list(range(101)), 95) == 95.0
    assert common.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
