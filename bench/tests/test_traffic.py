"""The traffic generator: same work for every seed, a pool that never
overflows, and the bounded set of shapes the warm-up covers."""
import collections
from pathlib import Path

import pytest

from harness import traffic

MIXES = sorted((Path(__file__).resolve().parents[1] / "mixes").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_block_holds_the_same_lengths_in_its_own_order(path):
    mix = traffic.Mix.load(path)
    n = 4 * mix.block
    a = traffic.draw_lengths(mix, n)
    blocks = [a[i:i + mix.block] for i in range(0, n, mix.block)]
    assert len({tuple(b) for b in blocks}) > 1
    for b in blocks:
        assert sorted(p for p, _ in b) == sorted(mix.prompt_block().tolist())
        assert sorted(d for _, d in b) == sorted(mix.decode_block().tolist())
    assert a == traffic.draw_lengths(mix, n)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_arrivals_fill_the_pool_without_overflow(path):
    mix = traffic.Mix.load(path)
    lengths = traffic.draw_lengths(mix, 2000)
    arr = traffic.arrival_steps(mix, [d for _, d in lengths])
    assert arr == sorted(arr)
    assert all(a % mix.max_burst == 0 for a in arr)
    per_step = collections.Counter(arr)
    assert max(per_step.values()) == 1
    done = [a + d for a, (_, d) in zip(arr, lengths)]
    for t in range(0, arr[-1], mix.max_burst):
        live = sum(1 for a, e in zip(arr, done) if a <= t < e)
        assert live <= mix.capacity
    assert all(d % mix.max_burst == 0 for _, d in lengths)
    assert all(p + d <= mix.max_seq for p, d in lengths)


def test_prompt_tokens_depend_on_seed_and_index_only():
    a = traffic.prompt_tokens(5, 3, 16, 1000)
    assert (a == traffic.prompt_tokens(5, 3, 16, 1000)).all()
    assert not (a == traffic.prompt_tokens(6, 3, 16, 1000)).all()
    assert a.shape == (1, 16) and a.min() >= 0 and a.max() < 1000


def test_stream_closes_for_good():
    mix = traffic.Mix.load(MIXES[0])
    s = traffic.Stream(mix, 1, 100, lambda *a: a, close_at=0.0)
    assert not s and s.next_arrival() is None and not s


def test_stream_closes_at_a_whole_block():
    mix = traffic.Mix.load(MIXES[0])
    s = traffic.Stream(mix, 1, 100, lambda *a: a)
    for _ in range(3):
        s.popleft()
    s.close_at = 0.0
    served = 3
    while s:
        s.popleft()
        served += 1
    assert served == mix.block and not s.exhausted
