"""The traffic generator: the same lengths for every seed, order and token
ids drawn from the seed, and the arrival interval that gives each traffic
file its resident target."""
import json
import sys
from collections import Counter
from dataclasses import dataclass
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import traffic as T  # noqa: E402

FILES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))
BIG = 2 ** 31 + 12345


@dataclass(frozen=True)
class Req:
    rid: int
    prompt: np.ndarray
    gen: int
    arrival: int


def _load(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", FILES)
def test_same_lengths_for_every_seed_order_and_ids_change(name):
    tr = _load(name)
    n = tr["n_lengths"]
    a = T.make_stream(tr, 3, 1000, 3 * n, Req)
    b = T.make_stream(tr, BIG, 1000, 3 * n, Req)
    steady = lambda s: [(s.prompt_len[r], s.gen[r])  # noqa: E731
                        for r in sorted(s.arrival) if r not in s.cohort]
    sa, sb = steady(a), steady(b)
    for blk in range(3):                 # each block of n: the same multiset
        assert Counter(sa[blk * n:(blk + 1) * n]) == \
            Counter(sb[blk * n:(blk + 1) * n])
    assert sa != sb                      # ... in another order
    assert Counter(map(tuple, T.length_pairs(tr).tolist())) == \
        Counter(sa[:n])
    ra = {r.rid: r for r in a.requests}
    rb = {r.rid: r for r in b.requests}
    assert any(not np.array_equal(ra[r].prompt, rb[r].prompt)
               for r in ra if r in rb and len(ra[r].prompt) == len(rb[r].prompt))
    again = T.make_stream(tr, BIG, 1000, 3 * n, Req)
    assert all(np.array_equal(x.prompt, y.prompt) and x.gen == y.gen
               for x, y in zip(b.requests, again.requests))


@pytest.mark.parametrize("name", FILES)
def test_arrival_interval_meets_the_resident_target(name):
    """Arrivals at four fifths of the knee's rate (``knee.py`` finds the
    knee in ticks), and the residents that rate gives, as the file says."""
    tr = _load(name)
    assert abs(T.mean_resident(tr) / tr["resident_target"] - 1) < 0.02
    assert tr["resident_target"] <= 0.8 * tr["slots"]
    assert abs(tr["knee_every_ticks"] / tr["arrival_every_ticks"] - 0.8) \
        < 0.01


@pytest.mark.parametrize("n", [1, 2, 8, 16, 32])
def test_stratified_block_balances_every_prefix(n):
    """Each block is a permutation, and any 2**j arrivals from its start
    hold one pair from each of 2**j equal strata of the prompt lengths."""
    rng = np.random.default_rng(BIG)
    blocks = [T.stratified_block(n, rng) for _ in range(8)]
    for b in blocks:
        assert sorted(b) == list(range(n))
        j = 1
        while j <= n:
            assert sorted(i * j // n for i in b[:j]) == list(range(j))
            j *= 2
    assert n < 4 or len({tuple(b) for b in blocks}) > 1


def test_stratified_block_needs_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        T.stratified_block(12, np.random.default_rng(0))


@pytest.mark.parametrize("name", FILES)
def test_stream_shape(name):
    tr = _load(name)
    s = T.make_stream(tr, 11, 1000, 50, Req)
    assert s.requests[0].rid == T.PACER_RID and len(s.requests[0].prompt) == 1
    # the pacer decodes through every tick before the first steady arrival
    assert s.gen[T.PACER_RID] == s.first_tick + 1
    assert 1 <= len(s.cohort) <= tr["slots"]
    assert all(s.arrival[r] == 0 for r in s.cohort)
    steady = sorted(r for r in s.arrival if r not in s.cohort)
    ticks = [s.arrival[r] for r in steady]
    assert ticks[0] == s.first_tick and ticks == sorted(ticks)
    k = tr["arrival_every_ticks"]
    assert abs((ticks[-1] - ticks[0]) / (len(ticks) - 1) - k) < 0.1
    assert all(s.prompt_len[r] + s.gen[r] <= T.max_len(tr) for r in s.arrival)
    pairs = T.length_pairs(tr)
    assert T.max_len(tr) == pairs[:, 0].max() + pairs[:, 1].max()
    # cohort requests carry their progress in the prompt: lengths stay within
    # what the traffic can hold, and each still has tokens to serve
    assert all(s.gen[r] >= 1 for r in s.cohort)


def test_resident_ticks_counts_chunks_and_decode_steps():
    # 600 tokens = 3 chunks of 256: first token in tick 2, then one a tick
    assert T.resident_ticks(600, 10, 256) == 3 + 10 - 2
    assert T.resident_ticks(10, 1, 256) == 1
