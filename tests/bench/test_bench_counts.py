"""The dense decoder's counts (``bench/models/dense_decoder.py``) against
flops and bytes worked out by hand, and ``bench/counts.py``'s least time.

qwen2.5-3b: d 2048, 36 layers, 16 heads over 2 KV heads of 128, d_ff
11008, vocabulary 151936, tied, q/k/v bias.  One layer's matrices:
2048 * (16 + 2 * 2) * 128 + 16 * 128 * 2048 + 3 * 2048 * 11008 =
77,070,336; with 2,560 bias and 4,096 norm entries, 77,076,992.

phi3-mini-3.8b: d 3072, 32 layers, 32 heads of 96 (MHA), d_ff 8192,
vocabulary 32064, untied.  One layer's matrices: 3072 * 96 * 96 +
32 * 96 * 3072 + 3 * 3072 * 8192 = 113,246,208.
"""

from __future__ import annotations

import json

import pytest

from bench import counts, harness

dense_decoder = harness._module("models", "dense_decoder")


def _counts(config):
    path = harness.BENCH / "configs" / f"{config}.json"
    return dense_decoder.counts(json.loads(path.read_text()))


def test_qwen_parameters_weights_and_cache():
    c = _counts("qwen2.5-3b")
    assert c.layer_matmul_params == 77_070_336
    assert c.params == 36 * 77_076_992 + 151_936 * 2048 + 2048  # 3.09 B
    assert c.weight_bytes == 6_171_877_376            # bf16, head = table
    assert c.kv_bytes_per_token == 36 * 2 * 2 * 128 * 2  # 36,864


def test_qwen_prefill_and_decode():
    c = _counts("qwen2.5-3b")
    # 2 * 128 tokens * 36 * 77,070,336 + 4 * 36 * 16 * 128 * (128 * 129 / 2)
    # + 2 * 2048 * 151936 (logits of the last position only)
    assert c.prefill(1, 128) == (713_337_339_904, 6_171_877_376
                                 + 128 * 36_864 + 151_936 * 4)
    # 32 rows writing position 255: attention over 256 positions
    assert c.decode(32, 255) == (199_900_528_640, 6_494_494_720)


def test_phi3_prefill_and_decode():
    c = _counts("phi3-mini-3.8b")
    assert c.layer_matmul_params == 113_246_208
    assert c.params == 3_821_079_552
    kv = 32 * 2 * 32 * 96 * 2                          # 393,216 per token
    assert c.kv_bytes_per_token == kv
    weights = 7_445_157_888        # layers, lm_head and final norm, bf16
    assert c.weight_bytes == weights
    # 4 rows of 2048: the embedding rows read, the cache written
    assert c.prefill(4, 2048) == (62_674_561_400_832, weights
                                  + 4 * 2048 * 3072 * 2 + 4 * 2048 * kv
                                  + 4 * 32064 * 4)
    assert c.decode(4, 2048) == (33_001_832_448, 10_670_066_688)


@pytest.mark.parametrize("config", ["qwen2.5-3b",
                                      "phi3-mini-3.8b"])
def test_served_row_is_its_prefill_and_decode_steps(config):
    c = _counts(config)
    assert c.served_row_flops(16, 4) == c.prefill(1, 16)[0] + sum(
        c.decode(1, 16 + i)[0] for i in range(3))


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 1e12}
    assert counts.least_time(4e14, 1e12, peak) == (2.0, "compute")
    assert counts.least_time(2e14, 3e12, peak) == (3.0, "memory")


# What the dense decoder's counts read at the cells' own shapes, as the
# class read when it lived in bench/counts.py: prefill of each chunk size
# the cells run, the last decode step, one row served, and a chunk split
# over three devices.
AT_CELL_SHAPES = {
    "qwen2.5-3b": (70, 215, (64, 128, 43), [
        (24946539495424.0, 6375923712.0), (49893078990848.0, 6579970048.0),
        (16760956223488.0, 6308971008.0)], [
        (400329539584.0, 6883172352.0), (800659079168.0, 7594467328.0),
        (268971409408.0, 6649778688.0)], 1721670238208.0,
        (264206199466.66666, 6311053994.666667)),
    "phi3-mini-3.8b": (1024, 32, (2, 4, 8), [
        (15256520491008.0, 8263303680.0), (30513040982016.0, 9081449472.0),
        (61026081964032.0, 10717741056.0)], [
        (15719202816.0, 8275898880.0), (31438405632.0, 9106639872.0),
        (62876811264.0, 10768121856.0)], 7871725043712.0,
        (10463739904.0, 7983256576.0)),
}


@pytest.mark.parametrize("config", sorted(AT_CELL_SHAPES))
def test_counts_at_the_cells_shapes_are_unchanged(config):
    c = _counts(config)
    p, g, rows, prefill, decode, row, split = AT_CELL_SHAPES[config]
    assert [c.prefill(r, p) for r in rows] == prefill
    assert [c.decode(r, p + g - 2) for r in rows] == decode
    assert c.served_row_flops(p, g) == row
    assert c.decode(rows[1] / 3, p) == pytest.approx(split, rel=1e-15)
