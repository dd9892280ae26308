"""FLOP and byte counts of the dense architecture module, found through
each configuration's ``"arch"``, against hand-worked values at the two
configurations' published widths."""
import pytest

from bench import spec


@pytest.fixture(scope="module")
def qwen():
    return spec.load_config("qwen3-1.7b")


@pytest.fixture(scope="module")
def yi():
    return spec.load_config("yi-34b-6l")


def test_layer_params_by_hand(qwen, yi):
    pf = spec.load_counts("prefill_step", qwen)
    # qwen3-1.7b: q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3x2048x6144
    assert pf.layer_matmul_params(qwen) == (
        2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144)
    assert pf.layer_matmul_params(qwen) == 50_331_648
    # yi-34b: q 7168x7168, k and v 7168x1024, o 7168x7168, MLP 3x7168x20480
    assert spec.load_counts("prefill_step", yi).layer_matmul_params(yi) \
        == 557_842_432


def test_prefill_flops_by_hand(qwen, yi):
    pf = spec.load_counts("prefill_step", qwen)
    S = 2000
    pairs = S * (S + 1) // 2                      # 2,001,000
    # qwen: 2*S*28*50,331,648 + 4*16*128*pairs*28 + 2*2048*151936
    want = (2 * S * 28 * 50_331_648 + 4 * 16 * 128 * pairs * 28
            + 2 * 2048 * 151936)
    assert pf.flops(qwen, S) == want
    assert pf.flops(qwen, S) == pytest.approx(6.097e12, rel=1e-3)
    # yi-34b-6l: 2*S*6*557,842,432 + 4*56*128*pairs*6 + 2*7168*64000
    assert spec.load_counts("prefill_step", yi).flops(yi, S) \
        == pytest.approx(1.3737e13, rel=1e-3)


def test_flash_counts_by_hand(qwen, yi):
    fl = spec.load_counts("flash_prefill", qwen)
    S = 2000
    assert fl.flops(qwen, S) == 4 * 16 * 128 * 2_001_000      # 16.39 GFLOP
    # q and out [S, 16, 128] plus k, v [S, 8, 128], bf16
    assert fl.bytes(qwen, S) == 2 * S * 128 * (2 * 16 + 2 * 8)
    assert fl.bytes(qwen, S) == 24_576_000
    fl = spec.load_counts("flash_prefill", yi)
    assert fl.flops(yi, S) == 4 * 56 * 128 * 2_001_000
    assert fl.bytes(yi, S) == 2 * S * 128 * (2 * 56 + 2 * 8)


def test_decode_counts_by_hand(qwen, yi):
    dc = spec.load_counts("decode_step", qwen)
    # kv per token: 2 (k, v) x 28 layers x 8 heads x 128 x 2 B
    assert dc.kv_bytes_per_token(qwen) == 114_688
    assert spec.load_counts("decode_step", yi).kv_bytes_per_token(yi) \
        == 2 * 6 * 8 * 128 * 2
    # qwen weights: 28 x 50,331,648 + tied head 151936 x 2048 + norms
    norms = 28 * 2 * 2048 + 2048 + 2 * 28 * 128
    w = 2 * (28 * 50_331_648 + 151936 * 2048 + norms)
    assert dc.weight_bytes(qwen) == w
    assert w == pytest.approx(3.441e9, rel=1e-3)
    ctx = [2000] * 8
    assert dc.bytes(qwen, ctx) == w + 114_688 * (8 * 2000 + 8)
    per_token = 28 * 50_331_648 + 2048 * 151936
    assert dc.flops(qwen, ctx) == (2 * 8 * per_token
                                   + 4 * 16 * 128 * 28 * 8 * 2001)
    # yi: untied head 7168 x 64000 counted once
    yw = 2 * (6 * 557_842_432 + 7168 * 64000 + 6 * 2 * 7168 + 7168)
    assert spec.load_counts("decode_step", yi).weight_bytes(yi) == yw
