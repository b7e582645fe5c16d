"""Operation and byte counts of chip_bench/costs.py against hand counts."""
import numpy as np
import pytest

from chip_bench import costs


def test_attention_pairs_decode_and_prefill():
    # decode: rows at lengths 5 and 9, one new token each: 6 + 10 pairs
    assert costs.attention_pairs([5, 9], [1, 1]) == 16
    # prefill chunk of 3 tokens after 4 cached: 5 + 6 + 7
    assert costs.attention_pairs([4], [3]) == 18
    # padding rows count nothing
    assert costs.attention_pairs([7, 0], [1, 0]) == 8


def test_paged_attend_call_by_hand():
    h, kh, hd = 4, 2, 8
    flops, nbytes = costs.paged_attend_call([5], [1], h, kh, hd)
    # 6 pairs x 4 heads x 8 dims x 2 matmuls x 2 flops
    assert flops == 6 * 4 * 8 * 2 * 2
    # K,V of 6 positions read + the new token's K,V written + q, o
    kv = 2 * kh * hd * 2
    assert nbytes == 6 * kv + 1 * kv + 2 * h * hd * 2


def test_decoder_step_flops_by_hand():
    class Cfg:
        d_model, n_heads, n_kv_heads, resolved_head_dim = 8, 2, 1, 4
        d_ff, n_layers, vocab_size = 16, 3, 10

    p = 3 * (8 * 4 * (2 * 2 + 2 * 1) + 3 * 8 * 16)
    assert costs.decoder_matmul_params(8, 2, 1, 4, 16, 3) == p
    # two rows decoding at lengths 2 and 4: 2 tokens, 3 + 5 pairs
    want = 2 * p * 2 + 3 * 4 * 8 * 2 * 4 + 2 * 8 * 10 * 2
    assert costs.decoder_step_flops(Cfg, [2, 4], [1, 1]) == want


def test_roofline_share_by_hand():
    work = [(128_000, 68_000)]
    # bytes bound: 68,000 B at 1e6 B/s is 68 ms; measured 136 ms -> 50 %
    share = costs.roofline_share(work, 0.136, 1e12, 1e6)
    assert share == pytest.approx(50.0)
    # ops bound when the peak rate is low: 128,000 ops at 1.25e5 op/s
    share = costs.roofline_share(work, 1.024, 1.25e5, 1e12)
    assert share == pytest.approx(100.0)
    # each item bounded by its own larger term: 1 s + 2 s over 6 s
    share = costs.roofline_share([(1, 10), (20, 1)], 6.0, 10.0, 10.0)
    assert share == pytest.approx(50.0)


def test_phi4_decode_step_is_weight_sized():
    """2 N per token at phi4-mini's widths: N is the 3.2 B weights of the
    layer stack (the output head adds 2 x 3072 x 200,064 per row)."""
    p = costs.decoder_matmul_params(3072, 24, 8, 128, 8192, 32)
    assert p == 32 * (3072 * 128 * 64 + 3 * 3072 * 8192)
    assert 3.2e9 < p < 3.3e9
    assert np.isclose(p / 1e9, 3.221225472)
