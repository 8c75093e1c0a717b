"""One general traffic generator for every mix in ``bench/mixes``.

A mix file gives the pool (``capacity``, ``max_seq``, ``max_burst``), the
prompt lengths and the output lengths. Lengths come in blocks: every
block of ``block`` requests holds the same multiset of prompt lengths and of
decode lengths, in an order drawn from the block index alone. So every seed
serves the same sequence of lengths, and does the same work: a window that
closes on the wall clock leaves its last requests draining, and an order
that changed with the seed would change that drain, and with it the
window's tokens per second, by a few percent from seed to seed. The seed
draws the prompts' tokens (and the weights), so the same seed gives the
same stream whatever the speed of the system.

Arrivals are on the scheduler's clock (decode steps). The stream keeps the
pool refilled: at each event (every ``max_burst`` steps) one request
arrives while a slot is free, and none arrives while the pool is full.
Each admitted request asks the program for temporary memory of some
hundred times its cache row, so one admission per event is what a pool
of useful size can hold. Decode lengths are multiples of ``max_burst``,
so every request completes at the end of a burst and every burst has the
same length: the window compiles one burst and one admission per prompt
length (see ``shapes``).
"""
from __future__ import annotations

import dataclasses
import heapq
import json
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    capacity: int
    max_seq: int
    max_burst: int
    prompt_lengths: Tuple[int, ...]
    prompt_counts: Tuple[int, ...]
    decode_min: int
    decode_max: int
    decode_alpha: float
    block: int
    max_requests: int
    check_requests: int
    serve: Dict[str, Any]
    scrub: Optional[Dict[str, Any]]

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        p, o = d["prompt"], d["decode_steps"]
        mix = cls(name=Path(path).stem, capacity=d["capacity"],
                  max_seq=d["max_seq"], max_burst=d["max_burst"],
                  prompt_lengths=tuple(p["lengths"]),
                  prompt_counts=tuple(p["counts"]),
                  decode_min=o["min"], decode_max=o["max"],
                  decode_alpha=o["alpha"], block=d["block"],
                  max_requests=d["max_requests"],
                  check_requests=d["check_requests"],
                  serve=dict(d.get("serve", {})), scrub=d.get("scrub"))
        mix.validate()
        return mix

    def validate(self) -> None:
        q = self.max_burst
        if self.decode_min % q or self.decode_max % q or self.decode_min < q:
            raise ValueError(f"{self.name}: decode lengths must be positive "
                             f"multiples of max_burst {q}")
        if self.block % sum(self.prompt_counts):
            raise ValueError(f"{self.name}: block must be a multiple of the "
                             "prompt counts' sum")
        if max(self.prompt_lengths) + self.decode_max > self.max_seq:
            raise ValueError(f"{self.name}: longest request exceeds max_seq")

    @property
    def max_new_tokens(self) -> int:
        return self.decode_max + 1

    def decode_block(self) -> np.ndarray:
        """The block's decode lengths: quantiles of a bounded Pareto
        (heavy-tailed) on [decode_min, decode_max], rounded to multiples of
        ``max_burst``."""
        lo, hi, a, q = (self.decode_min, self.decode_max, self.decode_alpha,
                        self.max_burst)
        u = (np.arange(self.block) + 0.5) / self.block
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return np.clip(np.round(x / q) * q, lo, hi).astype(np.int64)

    def prompt_block(self) -> np.ndarray:
        reps = self.block // sum(self.prompt_counts)
        return np.repeat(np.asarray(self.prompt_lengths, np.int64),
                         np.asarray(self.prompt_counts) * reps)


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *path])


def draw_lengths(mix: Mix, n: int) -> List[Tuple[int, int]]:
    """(prompt_len, decode_steps) of the first ``n`` requests."""
    out: List[Tuple[int, int]] = []
    dec, pro = mix.decode_block(), mix.prompt_block()
    b = 0
    while len(out) < n:
        r = _rng(0, 1, b)
        out += list(zip(r.permutation(pro).tolist(),
                        r.permutation(dec).tolist()))
        b += 1
    return out[:n]


def arrival_steps(mix: Mix, decode_steps: Sequence[int]) -> List[int]:
    """Arrival step of each request: at every event (a multiple of
    ``max_burst``) one request arrives while a slot is free. A request
    admitted at step ``a`` with ``d`` decode steps frees its slot at
    ``a + d``."""
    busy: List[int] = []   # completion steps of the occupied slots
    out: List[int] = []
    t = 0
    for d in decode_steps:
        while True:
            while busy and busy[0] <= t:
                heapq.heappop(busy)
            if len(busy) < mix.capacity:
                break
            t += mix.max_burst
        out.append(t)
        heapq.heappush(busy, t + d)
        t += mix.max_burst
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> np.ndarray:
    return _rng(seed, 2, index).integers(0, vocab, (1, length),
                                         dtype=np.int32)


class Stream:
    """The arrival source the scheduler drains (``next_arrival``,
    ``popleft``, truthiness). Prompts are made at ``popleft``. Once
    ``close_at`` (a ``time.perf_counter`` value) has passed, the stream
    closes at the next whole block of requests, yields nothing more and
    stays closed, so the scheduler drains the pool and returns. A window
    thus serves a whole number of blocks: a close at any request would let
    the host's speed pick which requests drain last, and with them the
    decode steps (418 or 462 in h2o-danube runs of one seed)."""

    def __init__(self, mix: Mix, seed: int, vocab: int, make_request,
                 close_at: Optional[float] = None):
        n = mix.max_requests
        self.lengths = draw_lengths(mix, n)
        self.arrivals = arrival_steps(mix, [d for _, d in self.lengths])
        self.seed, self.vocab = seed, vocab
        self.make_request = make_request
        self.close_at = close_at
        self.block = mix.block
        self.next = 0
        self.closed = False
        self.exhausted = False

    def _open(self) -> bool:
        if self.closed:
            return False
        if self.next >= len(self.lengths):
            self.closed = self.exhausted = True
        elif (self.close_at is not None and self.next % self.block == 0
              and time.perf_counter() >= self.close_at):
            self.closed = True
        return not self.closed

    def __bool__(self) -> bool:
        return self._open()

    def next_arrival(self) -> Optional[int]:
        return self.arrivals[self.next] if self._open() else None

    def popleft(self):
        i = self.next
        self.next += 1
        p, d = self.lengths[i]
        return self.make_request(i, prompt_tokens(self.seed, i, p, self.vocab),
                                 d + 1, self.arrivals[i])


def shapes(mix: Mix) -> Dict[str, Any]:
    """The compiled shapes a window of this mix uses: one burst of
    ``max_burst`` steps, and one admission of a single request per prompt
    length."""
    return {"burst_steps": mix.max_burst,
            "admission": list(mix.prompt_lengths)}

