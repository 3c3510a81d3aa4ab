"""The flop and byte functions against hand-worked values."""
import pytest

from benchmarks import counts as C, spec

SPEC = spec.Spec()
GPT = SPEC.data('configs', 'gpt3-1.3B-en')
ILM = SPEC.data('configs', 'internlm2-1_8b')


def test_gpt3_parameter_count():
    h, ff, L, V = 2048, 8192, 24, 50304
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * ff + ff) \
        + (ff * h + h) + 4 * h
    want = V * h + 1024 * h + L * per_layer + 2 * h
    assert C.total_params(GPT) == want == GPT['params'] == 1313722368


def test_internlm2_parameter_count():
    h, ff, L, V = 2048, 8192, 24, 92544
    per_layer = h * 2048 * 2 + h * 1024 * 2 + 3 * h * ff + 2 * h
    want = 2 * V * h + L * per_layer + h
    assert C.total_params(ILM) == want == ILM['params'] == 1889110016


def test_train_flops_per_token_gpt3_at_1024():
    h, ff, L, V, s = 2048, 8192, 24, 50304, 1024
    matmul = L * (4 * h * h + 2 * h * ff) + h * V
    attn = L * 4 * (s / 2) * h           # QK^T and PV over the causal half
    assert C.matmul_params(GPT) == matmul
    assert C.train_flops_per_token(GPT, s) == pytest.approx(
        3 * (2 * matmul + attn))
    # 14,006 tokens/s (ledger, PR 22) is 58.1% of 197 TFLOP/s by this count
    assert C.mfu_percent(GPT, s, 14006.0, 197e12) == pytest.approx(
        58.07, abs=0.01)


def test_kv_bytes_and_decode_need():
    assert C.kv_row_bytes(GPT) == 2 * 24 * 16 * 128 * 4 == 384 * 1024
    assert C.kv_row_bytes(ILM) == 2 * 24 * 8 * 128 * 4 == 192 * 1024
    # the pools of the two serve cells: 24 x 1024 and 12 x 4096 rows
    assert 24 * 1024 * C.kv_row_bytes(GPT) == 9 * 2**30
    assert 12 * 4096 * C.kv_row_bytes(ILM) == 9 * 2**30
    need = C.decode_substep_bytes(ILM, real_rows=1000)
    assert need == 1889110016 * 2 + 1000 * 192 * 1024


def test_flash_counts_and_roofline():
    b, s = 4, 1024
    one = 2 * b * 16 * s * (s / 2) * 128
    assert C.flash_train_flops(GPT, b, s) == pytest.approx(24 * 7 * one)
    q = b * s * 16 * 128 * 2
    assert C.flash_train_bytes(GPT, b, s) == pytest.approx(24 * 12 * q)
    pct, bound = C.roofline_percent(197e12, 1.0, 2.0, 197e12, 819e9)
    assert pct == pytest.approx(50.0) and bound == 'compute'
    pct, bound = C.roofline_percent(1.0, 819e9, 4.0, 197e12, 819e9)
    assert pct == pytest.approx(25.0) and bound == 'memory'
