"""The one generator of every traffic mix, driven by the mix's file.

Serving mixes are open loops.  A run of ``seconds`` sends
``round(rate_per_s * seconds)`` requests.  Every seed gets the same
multiset of gaps between arrivals (the exponential distribution's
quantiles, so Poisson-like), of prompt lengths (log-normal quantiles,
each moved to the nearest length of the mix's fixed set) and of output
lengths (log-normal quantiles, clipped), each shuffled by the seed, and
prompt tokens drawn by the seed.  So two seeds do the same work in a
different order.

Training mixes are token logs: documents of log-normal lengths, each
ended by the end-of-document token, packed back to back into rows of
``seq_len + 1`` tokens.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

EOS = 2


@dataclass
class Req:
    due: float          # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(n: int, median: float, sigma: float) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return median * np.exp(sigma * z)


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             rate: float = None) -> List[Req]:
    rate = mix["rate_per_s"] if rate is None else rate
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng(seed)
    gaps = -np.log(1.0 - _quantiles(n)) / rate
    # spread the arrivals over the window: the last one is due before it ends
    gaps *= seconds / (gaps.sum() + gaps.mean())
    due = np.cumsum(rng.permutation(gaps))
    lengths = np.asarray(mix["prompt_lengths"])
    want = _lognormal(n, mix["prompt_median"], mix["prompt_sigma"])
    plen = lengths[np.abs(np.log(want)[:, None] - np.log(lengths)[None]).argmin(1)]
    out = np.clip(np.rint(_lognormal(n, mix["output_median"], mix["output_sigma"])),
                  mix["output_min"], mix["output_max"]).astype(int)
    plen, out = rng.permutation(plen), rng.permutation(out)
    if np.any(plen + out > mix["max_total"]):
        raise ValueError("a prompt and its output exceed the mix's max_total")
    return [Req(float(d), rng.integers(3, vocab, p, dtype=np.int32), int(o))
            for d, p, o in zip(due, plen, out)]


def warmup_prompts(mix: dict, vocab: int) -> List[np.ndarray]:
    """One prompt of each length the mix sends."""
    rng = np.random.default_rng(0)
    return [rng.integers(3, vocab, p, dtype=np.int32) for p in mix["prompt_lengths"]]


def token_rows(mix: dict, seed: int, n_rows: int, seq_len: int, vocab: int) -> np.ndarray:
    """``n_rows`` rows of ``seq_len + 1`` tokens packed from documents."""
    rng = np.random.default_rng(seed)
    need = n_rows * (seq_len + 1)
    docs, have = [], 0
    while have < need:
        ln = int(np.clip(rng.lognormal(np.log(mix["doc_median"]), mix["doc_sigma"]),
                         mix["doc_min"], mix["doc_max"]))
        # token ids: a Zipf-like unigram law over a per-document shuffle
        ids = rng.zipf(mix["zipf_a"], ln) % (vocab - 3) + 3
        docs.append(np.append(ids, EOS))
        have += ln + 1
    flat = np.concatenate(docs)[:need].astype(np.int32)
    return flat.reshape(n_rows, seq_len + 1)
