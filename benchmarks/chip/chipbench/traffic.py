"""The one traffic generator: a traffic file of parameters -> a request stream.

A traffic file (``traffic/<name>.json``) fixes the engine settings (slots,
page size, prefill chunk) and the length distributions.  Lengths are fixed
quantiles of those distributions, paired by a fixed rule, so every seed
serves the same set of ``(prompt, output)`` pairs; the seed only permutes
their order and draws the token ids.  The tail of the lengths is therefore
the same in every run.

The scheduler takes arrivals in engine ticks, so the loop is closed at the
tick: one arrival every ``arrival_every_ticks`` ticks.  The stream has three
parts:

* the pacer (rid 0): a one-token prompt that decodes from tick 0 until the
  steady arrivals begin, so every tick of the pre-roll ends in a decode
  step that the stream recorder can count;
* the cohort (arrival tick 0): the requests that would be resident in the
  steady state, each represented by a request whose prompt already holds the
  tokens it would have generated so far.  The window then opens on a full
  batch instead of an empty engine;
* the steady stream: ``n`` requests from ``first_tick`` on, request ``s``
  at tick ``first_tick + floor(s * arrival_every_ticks)`` (the interval may
  be fractional), more than any window can reach.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

PACER_RID = 0
_PAIR_STRIDE = 13


@dataclass(frozen=True)
class Stream:
    """What the harness submits and what it needs to time it."""
    requests: list            # repro.launch.scheduler.Request, arrival-sorted
    prompt_len: Dict[int, int]
    gen: Dict[int, int]
    arrival: Dict[int, int]
    cohort: frozenset         # rids of the pacer and the cohort
    first_tick: int           # tick of the first steady arrival (window opens)


def _quantiles(spec: dict, q: np.ndarray) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if spec["kind"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["kind"] == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['kind']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def length_pairs(traffic: dict) -> np.ndarray:
    """The fixed set of ``(prompt, gen)`` pairs, shape (n, 2): quantile j of
    the prompts with quantile ``(13 j + 5) mod n`` of the outputs."""
    n = traffic["n_lengths"]
    q = (np.arange(n) + 0.5) / n
    prompts = _quantiles(traffic["prompt"], q)
    gens = _quantiles(traffic["output"], q)
    if math.gcd(_PAIR_STRIDE, n) != 1:
        raise ValueError(f"n_lengths {n} must be coprime with {_PAIR_STRIDE}")
    pick = (_PAIR_STRIDE * np.arange(n) + 5) % n
    return np.stack([prompts, gens[pick]], axis=1)


def stratified_block(n: int, rng: np.random.Generator) -> List[int]:
    """One block of the order: the ``n`` pair indices with the bits of each
    position reversed, xor a mask drawn from ``rng``.  Any ``2**j``
    consecutive arrivals of a block hold one pair from each of ``2**j``
    equal strata of the prompt lengths, so a window that ends inside a block
    still holds about the same lengths for every seed."""
    bits = n.bit_length() - 1
    if n != 1 << bits:
        raise ValueError(f"n_lengths {n} must be a power of two")
    mask = int(rng.integers(n))
    return [int(f"{p:0{bits}b}"[::-1], 2) ^ mask if bits else 0
            for p in range(n)]


def max_len(traffic: dict) -> int:
    """The block-table width in tokens: the longest prompt served plus the
    longest output served."""
    pairs = length_pairs(traffic)
    return int(pairs[:, 0].max() + pairs[:, 1].max())


def resident_ticks(prompt: int, gen: int, chunk: int) -> int:
    """Ticks a request holds its slot when admitted at once: one prefill
    chunk per tick, the first token with the last chunk, and the second in
    the decode step of that same tick, then one token per tick."""
    return -(-prompt // chunk) + max(gen, 2) - 2


def mean_resident(traffic: dict) -> float:
    """Requests resident on average (Little's law in ticks, no queueing)."""
    pairs = length_pairs(traffic)
    ticks = [resident_ticks(p, g, traffic["chunk"]) for p, g in pairs]
    return float(np.mean(ticks)) / traffic["arrival_every_ticks"]


def make_stream(traffic: dict, seed: int, vocab: int, n_steady: int,
                request_cls) -> Stream:
    """The request stream of one run, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    pairs = length_pairs(traffic)
    chunk, k = traffic["chunk"], traffic["arrival_every_ticks"]
    n = len(pairs)

    def order(count: int) -> List[int]:
        idx: List[int] = []
        while len(idx) < count:
            idx.extend(stratified_block(n, rng))
        return idx[:count]

    def tokens(m: int) -> np.ndarray:
        return rng.integers(0, vocab, size=m, dtype=np.int32)

    # the cohort: walk back over a virtual past at the same arrival rate and
    # keep what would still be resident now, with its progress folded into
    # its prompt (context length and remaining output as in steady state)
    longest = max(resident_ticks(p, g, chunk) for p, g in pairs)
    past = order(int(longest / k) + 1)
    cohort = []
    for j, i in enumerate(past, start=1):
        p, g = (int(x) for x in pairs[i])
        age = int(j * k)
        if age >= resident_ticks(p, g, chunk):
            continue
        c = -(-p // chunk)
        done = age - c + 2 if age >= c else 0        # tokens generated so far
        cohort.append((p + done, g - done))
    cohort = cohort[:traffic["slots"] - 1]          # one slot is the pacer's
    first_tick = max(-(-p // chunk) for p, _ in cohort) if cohort else 1

    reqs, prompt_len, gen, arrival = [], {}, {}, {}

    def add(rid: int, p: int, g: int, tick: int) -> None:
        reqs.append(request_cls(rid=rid, prompt=tokens(p), gen=g,
                                arrival=tick))
        prompt_len[rid], gen[rid], arrival[rid] = p, g, tick

    add(PACER_RID, 1, first_tick + 1, 0)
    for c, (p, g) in enumerate(cohort, start=1):
        add(c, p, g, 0)
    base = len(cohort) + 1
    for s, i in enumerate(order(n_steady)):
        add(base + s, int(pairs[i][0]), int(pairs[i][1]),
            first_tick + int(s * k))
    return Stream(requests=reqs, prompt_len=prompt_len, gen=gen,
                  arrival=arrival, cohort=frozenset(range(base)),
                  first_tick=first_tick)
