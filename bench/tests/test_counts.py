"""The benchmark's FLOP and byte counts on the published shapes."""
import json
from pathlib import Path

from harness import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_qwen_parameter_count_is_the_published_3_09b():
    n = counts.param_count(sizes("qwen2.5-3b"))
    assert n == 3_085_938_688
    assert round(n / 1e9, 2) == 3.09


def test_danube_parameter_count():
    # 24 layers, untied 32000 x 2560 embedding and head: 1.83 B
    assert counts.param_count(sizes("h2o-danube-1.8b")) == 1_831_201_280


def test_kv_bytes_per_position():
    assert counts.kv_bytes_per_position(sizes("qwen2.5-3b")) == 36_864
    assert counts.kv_bytes_per_position(sizes("h2o-danube-1.8b")) == 61_440


def test_decode_flops_are_twice_the_matmul_weights_plus_attention():
    c = sizes("qwen2.5-3b")
    mm = 36 * counts.layer_matmul_params(c)
    head = 2 * 2048 * 151936
    assert counts.decode_flops(c, 1) == 2 * mm + head + 36 * 4 * 16 * 128
    assert (counts.decode_flops(c, 101) - counts.decode_flops(c, 1)
            == 36 * 4 * 16 * 128 * 100)


def test_sliding_window_caps_the_keys_a_query_sees():
    c = dict(sizes("h2o-danube-1.8b"), window_pattern=[8])
    assert counts.decode_flops(c, 8) == counts.decode_flops(c, 500)


def test_prefill_counts_causal_pairs_and_one_row_of_logits():
    c = sizes("qwen2.5-3b")
    mm = 2 * 36 * counts.layer_matmul_params(c)
    pairs = 3 * 4 // 2
    assert counts.prefill_flops(c, 3) == (3 * mm + 36 * 4 * 16 * 128 * pairs
                                          + 2 * 2048 * 151936)


def test_request_work_reads_each_earlier_position_once_per_step():
    c = sizes("qwen2.5-3b")
    w = counts.request_work(c, prompt_len=4, n_tokens=3)
    kvb = 36_864
    # token 1 reads 4 positions, token 2 reads 5; each writes one
    assert w["decode_kv_bytes"] == (4 + 1) * kvb + (5 + 1) * kvb


def test_decode_weight_bytes_read_an_untied_embedding_by_rows():
    q, d = sizes("qwen2.5-3b"), sizes("h2o-danube-1.8b")
    assert counts.decode_weight_bytes(q, 40) == 2 * 3_085_938_688
    assert counts.decode_weight_bytes(d, 40) == 2 * (
        1_831_201_280 - 32000 * 2560 + 40 * 2560)
