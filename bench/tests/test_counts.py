"""The benchmark's FLOP and byte counts on the published shapes: those of
one token from the configurations' reference module, and their sums over
a request from the harness."""
import json
from pathlib import Path

from harness import counts
from references import dense

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_qwen_parameter_count_is_the_published_3_09b():
    n = dense.param_count(sizes("qwen2.5-3b"))
    assert n == 3_085_938_688
    assert round(n / 1e9, 2) == 3.09


def test_danube_parameter_count():
    # 24 layers, untied 32000 x 2560 embedding and head: 1.83 B
    assert dense.param_count(sizes("h2o-danube-1.8b")) == 1_831_201_280


def test_kv_bytes_per_position():
    assert dense.kv_bytes_per_position(sizes("qwen2.5-3b")) == 36_864
    assert dense.kv_bytes_per_position(sizes("h2o-danube-1.8b")) == 61_440


def test_decode_flops_are_twice_the_matmul_weights_plus_attention():
    c = sizes("qwen2.5-3b")
    mm = 36 * dense.layer_matmul_params(c)
    head = 2 * 2048 * 151936
    assert dense.decode_flops(c, 1) == 2 * mm + head + 36 * 4 * 16 * 128
    assert (dense.decode_flops(c, 101) - dense.decode_flops(c, 1)
            == 36 * 4 * 16 * 128 * 100)


def test_sliding_window_caps_the_keys_a_query_sees():
    c = dict(sizes("h2o-danube-1.8b"), window_pattern=[8])
    assert dense.decode_flops(c, 8) == dense.decode_flops(c, 500)


def test_prefill_counts_causal_pairs_and_one_row_of_logits():
    c = sizes("qwen2.5-3b")
    mm = 2 * 36 * dense.layer_matmul_params(c)
    pairs = 3 * 4 // 2
    assert dense.prefill_flops(c, 3) == (3 * mm + 36 * 4 * 16 * 128 * pairs
                                          + 2 * 2048 * 151936)


def test_request_work_reads_each_earlier_position_once_per_step():
    c = sizes("qwen2.5-3b")
    w = counts.request_work(dense, c, prompt_len=4, n_tokens=3)
    kvb = 36_864
    # token 1 reads 4 positions, token 2 reads 5; each writes one
    assert w["decode_kv_bytes"] == (4 + 1) * kvb + (5 + 1) * kvb


def test_decode_weight_bytes_read_an_untied_embedding_by_rows():
    q, d = sizes("qwen2.5-3b"), sizes("h2o-danube-1.8b")
    assert dense.decode_weight_bytes(q, 40) == 2 * 3_085_938_688
    assert dense.decode_weight_bytes(d, 40) == 2 * (
        1_831_201_280 - 32000 * 2560 + 40 * 2560)


def test_mixed_window_pattern_counts_each_layer_over_its_own_window():
    # qwen's sizes with every other layer windowed at 8: 18 layers see at
    # most 8 keys, 18 see the whole causal prefix
    c = dict(sizes("qwen2.5-3b"), window_pattern=[8, 0])
    mm = 2 * 36 * dense.layer_matmul_params(c)
    head = 2 * 2048 * 151936
    per_key = 4 * 16 * 128
    assert dense.decode_flops(c, 100) == (mm + 18 * per_key * 8
                                          + 18 * per_key * 100 + head)
    # prompt of 10: windowed layers see 1..8, 8, 8 keys; full ones 1..10
    assert dense.prefill_flops(c, 10) == (
        10 * mm + 18 * per_key * (36 + 8 + 8) + 18 * per_key * 55 + head)
    # the step at context 100 reads 8 or 99 earlier positions, writes 1
    per_pos = 2 * 2 * 128 * 2
    assert dense.decode_kv_bytes(c, 100) == (18 * 9 + 18 * 100) * per_pos
    w = counts.request_work(dense, c, prompt_len=8, n_tokens=3)
    assert w["decode_kv_bytes"] == ((18 * 9 + 18 * 9)
                                    + (18 * 9 + 18 * 10)) * per_pos


def test_cache_bytes_of_a_pool_cut_to_each_layer_window():
    c = sizes("qwen2.5-3b")
    assert dense.cache_bytes(c, 32, 512) == 32 * 512 * 36_864
    # [8, 0]: 18 layers keep 8 positions, 18 keep all 512; K and V of
    # 2 heads of 128 in bf16 are 1024 B per layer and position
    mixed = dict(c, window_pattern=[8, 0])
    assert dense.cache_bytes(mixed, 2, 512) == 2 * 1024 * (18 * 8 + 18 * 512)
