"""What decides ``correct``: the served tokens of a sample of finished
requests against the plain reference.

The sample is drawn from the seed once the window has closed: the finished
request with the most served tokens, the one with the longest context, and
more at random until some hundreds of tokens are covered.  The reference
runs once over each prompt followed by its served tokens; the number
compared is the widest gap by which a served token's reference logit lies
below the reference's best at its position.  Greedy decoding serves the
reference's best token up to rounding, so the gap is rounding alone; a
token altered where it is produced, a missing layer or a lower precision
shows as a wider gap.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

SAMPLE_TOKENS = 300          # served tokens to cover at least
SAMPLE_POSITIONS = 12_000    # prompt + served positions at most


def sample(finished: Sequence[int], prompt_len: Dict[int, int],
           served: Dict[int, int], seed: int) -> List[int]:
    """Finished request ids to compare, drawn from ``seed``."""
    if not finished:
        return []
    by_served = max(finished, key=lambda r: (served[r], prompt_len[r], -r))
    by_context = max(finished,
                     key=lambda r: (prompt_len[r] + served[r], served[r], -r))
    picked = list(dict.fromkeys([by_served, by_context]))
    rest = [r for r in sorted(finished) if r not in picked]
    rng = np.random.default_rng([seed, 7])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    tokens = sum(served[r] for r in picked)
    positions = sum(prompt_len[r] + served[r] for r in picked)
    for r in rest:
        if tokens >= SAMPLE_TOKENS:
            break
        cost = prompt_len[r] + served[r]
        if positions + cost > SAMPLE_POSITIONS:
            continue
        picked.append(r)
        tokens += served[r]
        positions += cost
    return picked


def verdict(gaps: List[np.ndarray], limit):
    """``(correct, failed, widest)``: ``widest`` is the widest gap of any
    sampled served token; correct when tokens were compared and ``widest``
    is within ``limit``; ``failed`` counts the requests with a wider gap.
    With no limit set, nothing is correct."""
    if not gaps:
        return False, 0, float("inf")
    widest = float(max(g.max() for g in gaps))
    if limit is None:
        return False, len(gaps), widest
    failed = sum(1 for g in gaps if g.max() > limit)
    return failed == 0, failed, widest


def _gaps(ref, logits, tokens: np.ndarray) -> np.ndarray:
    """Gaps of ``tokens`` at the first ``len(tokens)`` rows of ``logits``."""
    import jax.numpy as jnp

    padded = np.zeros(logits.shape[0], np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(ref.gap_of(logits, jnp.asarray(padded)))[:len(tokens)]


def token_gaps(ref, model: dict, weight_seed: int,
               seqs: Sequence[Tuple[np.ndarray, np.ndarray]]) -> List[np.ndarray]:
    """Per sampled request: the gap of each served token."""
    logits = ref.logits(model, weight_seed, seqs)
    return [_gaps(ref, lg, served) for lg, (_, served) in zip(logits, seqs)]


def control_token_gaps(ref, model: dict, weight_seed: int,
                       seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                       quant: str = "fp8") -> List[np.ndarray]:
    """The control, in the program's place: per request, the gap of each
    token that the reference at ``quant`` puts first, at the same positions
    of the same prompts and served tokens."""
    full = ref.logits(model, weight_seed, seqs)
    low = ref.logits(model, weight_seed, seqs, quant=quant)
    return [_gaps(ref, f, np.asarray(lo.argmax(axis=1))[:len(served)])
            for f, lo, (_, served) in zip(full, low, seqs)]
