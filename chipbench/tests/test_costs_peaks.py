"""Operation and byte counts from shapes, and the table of peaks."""
import pytest

from chipbench import costs, peaks


def test_sparse_axpy_bytes():
    flops, nbytes = costs.sparse_axpy(10, 47236, 74, 4)
    assert flops is None  # float32: no published v5e peak, bytes bound
    assert nbytes == 2 * 10 * 47236 * 4 + 10 * 74 * 8 + 2 * 10 * 4


def test_flash_attention_counts():
    flops, nbytes = costs.flash_attention(1, 32, 8, 2048, 128, 2)
    assert flops == 4 * 32 * 2048 * 2048 * 128 / 2
    assert nbytes == 2 * 2048 * 128 * (2 * 32 + 2 * 8)
    full, _ = costs.flash_attention(1, 32, 8, 2048, 128, 2, causal=False)
    assert full == 2 * flops


def test_decode_attention_counts():
    flops, nbytes = costs.decode_attention(1000, 64, 32, 8, 128, 2)
    assert flops == 4 * 1000 * 32 * 128
    assert nbytes == 2 * (2 * 1000 * 8 * 128 + 2 * 64 * 32 * 128)


def test_model_flops():
    m = dict(d_model=8, head_dim=2, n_heads=4, n_kv_heads=2, d_ff=16,
             n_layers=3, vocab_size=10)
    assert costs.layer_params(m) == 8 * 2 * 12 + 3 * 8 * 16
    per_tok = 2 * costs.layer_params(m) * 3
    head = 2 * 8 * 10
    attn = 4 * 4 * 2 * 3
    assert costs.prefill_flops(m, prompt_tokens=5, prompt_sq=25,
                               prefills=1) == per_tok * 5 + head + attn * 12.5
    assert costs.decode_flops(m, decode_tokens=7, ctx_tokens=40) == \
        (per_tok + head) * 7 + attn * 40


def test_peaks_lookup():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_roofline_share_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    # bytes alone: 819e6 bytes take 1 ms at the HBM peak
    assert peaks.roofline_share(None, 819e6, 2e-3, p) == pytest.approx(50.0)
    # compute bound wins: 197e9 FLOPs take 1 ms
    assert peaks.roofline_share(197e9 * 2, 819e6, 4e-3, p) == pytest.approx(
        50.0)
    assert peaks.roofline_share(None, 1.0, 0.0, p) is None
