import statistics

import numpy as np
import pytest

from benchmarks import device, flops, stats


@pytest.mark.parametrize("p", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys(p):
    xs = np.random.default_rng(p).lognormal(size=137).tolist()
    assert stats.percentile(xs, p) == pytest.approx(np.percentile(xs, p))


def test_spread_and_tail_count():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_spread(xs) == pytest.approx((q3 - q1) / 10.05)
    assert stats.samples_beyond(180, 95) == 9
    assert stats.median([3, 1, 2]) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


FALCON_7B = dict(hidden=4544, layers=32, heads=71, kv_heads=1, head_dim=64,
                 ffn=18176, vocab=65024, glu=False, tied=True)


def test_flops_against_a_hand_count_for_falcon_7b():
    # by hand: q 4544x4544, k and v 4544x64 each, o 4544x4544,
    # two MLP matrices 4544x18176; head 65024x4544 used once (tied)
    layer = 4544 * 4544 + 2 * 4544 * 64 + 4544 * 4544 + 2 * 4544 * 18176
    assert layer == 207_060_992
    head = 65024 * 4544
    groups = flops.matmul_params(FALCON_7B)
    assert sum(groups.values()) == 32 * layer + head == 6_921_420_800
    fwd = 2 * (32 * layer + head) + 32 * 2 * 71 * 64 * 2048
    assert flops.forward_flops_per_token(FALCON_7B, 2048) == fwd
    assert flops.train_flops_per_token(FALCON_7B, 2048) == 3 * fwd
    one = dict(FALCON_7B, layers=1)
    assert flops.train_flops_per_token(one, 2048) == pytest.approx(
        3.07e9, rel=0.01)
    share = flops.matmul_params(one)["head"] / sum(
        flops.matmul_params(one).values())
    assert share == pytest.approx(0.59, abs=0.01)


def test_decode_bytes_for_falcon_7b():
    assert flops.weight_bytes(FALCON_7B) == 2 * 6_921_420_800
    assert flops.kv_bytes_per_token(FALCON_7B) == 8192      # MQA: 8 KiB
    assert flops.decode_step_bytes(FALCON_7B, 1000) == \
        2 * 6_921_420_800 + 8_192_000


def test_glu_and_gqa_are_counted():
    s = dict(hidden=8, layers=2, heads=4, kv_heads=2, head_dim=2, ffn=16,
             vocab=10, glu=True, tied=False)
    g = flops.matmul_params(s)
    assert g == {"attention_proj": 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8),
                 "mlp": 2 * 3 * 8 * 16, "head": 80}


def test_sizes_come_from_the_programs_config():
    from megatron_llm_tpu.config import falcon_config

    assert flops.sizes_of(falcon_config("7b")) == FALCON_7B


def test_peaks_table_knows_the_v5e_and_nothing_it_was_not_told():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    for unknown in ("TPU v9", "cpu", "source"):
        with pytest.raises(KeyError):
            device.peaks(unknown)
