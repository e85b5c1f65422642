"""The FLOP and byte counters against hand counts."""
import numpy as np

import flops
import harness as H

SMOL = H.load_config("smollm-360m")


def test_matmul_params_by_hand():
    d, F, V, L = 960, 2560, 49152, 32
    per_layer = d * 960 + 2 * d * 320 + 960 * d + 3 * d * F
    assert flops.matmul_params(SMOL) == L * per_layer + d * V
    assert flops.matmul_params(SMOL, head=False) == L * per_layer


def test_causal_pairs_by_hand():
    assert flops.causal_pairs(None, 4) == 10          # 1 + 2 + 3 + 4
    seg = np.array([0, 0, 0, 1, 1, 2])                # docs of 3, 2, 1
    assert flops.causal_pairs(seg, 6) == 6 + 3 + 1


def test_attention_counts_by_hand():
    pairs = 8192 * 8193 // 2
    fwd = flops.attn_fwd_flops(SMOL, pairs)
    assert fwd == 2 * 2 * 15 * 64 * pairs             # QK^T and PV
    assert flops.attn_bwd_flops(SMOL, pairs) == 5 * fwd // 2
    # q and o (15 heads), k and v (5 heads), bf16, and the f32 lse
    assert flops.attn_fwd_bytes(SMOL, 10) == 10 * (2 * 64 * 40 + 4 * 15)
    assert flops.attn_bwd_bytes(SMOL, 10) == 10 * (2 * 64 * 80 + 8 * 15)


def test_document_mask_counts_within_document_pairs():
    seg = np.repeat(np.arange(4), 2048)
    assert flops.causal_pairs(seg, 8192) == 4 * (2048 * 2049 // 2)


def test_train_step_flops_match_the_issue_counts():
    # 6 N T with N = 361.8M at T = 8192 is 1.78e13; causal attention
    # forward over 32 layers is 4.1e12, counted three times
    pairs = 8192 * 8193 // 2
    total = flops.train_step_flops(SMOL, 8192, pairs)
    dense = 6 * flops.matmul_params(SMOL) * 8192
    assert abs(dense - 1.78e13) / 1.78e13 < 0.01
    assert abs((total - dense) / 3 - 4.1e12) / 4.1e12 < 0.02


def test_roofline_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline(100, 1, 2.0, peaks) == (50.0, "compute")
    assert flops.roofline(1, 100, 20.0, peaks) == (50.0, "memory")
