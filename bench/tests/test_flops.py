"""Operation and byte counts: useful shapes, causal halving, shares
that cannot pass 100% for work done once at the peak."""
import pytest

from bench.harness import flops as F

M = {"num_layers": 2, "d_model": 1536, "num_heads": 12, "num_kv_heads": 2,
     "head_dim": 128, "d_ff": 8960, "vocab_size": 151936}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_causal_attention_counts_half_the_pairs():
    fl, _ = F.flash_fwd(M, [1000], layers=1)
    full = 4 * 12 * 128 * 1000 * 1000
    assert fl == 4 * 12 * 128 * (1000 * 1001 // 2)
    assert abs(fl / full - 0.5) < 1e-3


def test_counts_follow_actual_lengths_not_buckets():
    # two prompts that the engine pads to the same 1024 bucket
    a = F.prefill_flops(M, 600)
    b = F.prefill_flops(M, 1000)
    assert a < b
    fl, by = F.flash_fwd(M, [600, 1000])
    assert fl == F.flash_fwd(M, [600])[0] + F.flash_fwd(M, [1000])[0]
    assert by == 2 * (600 + 1000) * (2 * 12 + 2 * 2) * 128 * 2


def test_prefill_and_decode_flops():
    P = 1536 * 12 * 128 + 2 * 1536 * 2 * 128 + 12 * 128 * 1536 + 3 * 1536 * 8960
    assert F.layer_matmul_params(M) == P
    head = 2 * 1536 * 151936
    assert F.decode_flops(M, 10) == 2 * (2 * P + 4 * 12 * 128 * 10) + head
    assert F.prefill_flops(M, 3) == 2 * (2 * P * 3 + 4 * 12 * 128 * 6) + head


def test_train_flops_three_forwards_no_recompute():
    B, S = 4, 1024
    fwd = (2 * (2 * F.layer_matmul_params(M) * S + 4 * 12 * 128 * S * (S + 1) // 2)
           + 2 * 1536 * 151936 * (S - 1))
    assert F.train_flops(M, B, S) == 3 * B * fwd


def test_paged_bytes_are_live_lengths():
    fl, by = F.paged_decode(M, [100, 7])
    assert by == 2 * ((2 * 2 * 107 * 128) + 2 * (2 * 12 * 128)) * 2
    assert fl == 2 * 4 * 12 * 128 * 107


def test_roofline_share():
    # exactly the compute bound in the time taken reads 100%
    assert F.roofline_share(197e12, 0, 1.0, PEAK) == pytest.approx(100.0)
    assert F.roofline_share(0, 819e9, 2.0, PEAK) == pytest.approx(50.0)
    # the larger bound counts
    assert F.roofline_share(197e12, 819e9 * 3, 3.0, PEAK) == pytest.approx(100.0)
