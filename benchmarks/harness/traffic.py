"""The one traffic generator: a mix is a data file of parameters.

Every seed gets the SAME schedule: the sizes and arrival gaps are the
distribution's own quantiles ((i + 0.5) / n), laid out once in an order
fixed by the mix's `order_seed`.  `--seed` draws the token values (and the
weights), nothing else.  An earlier form rotated the sequence by the seed:
the same work in another order, and yet the 90th percentile of time to
first token differed by 15% between two seeds (1585 and 1836 ms, my chip
runs, PR 23) -- which burst meets an empty engine decides a tail of eleven
requests.  So the order belongs to the mix: two seeds differ as two runs
of one seed do, and a difference between commits is not one between draws.
A mix with another order is another file with another `order_seed`.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float            # open loop: seconds after the window opens
    prompt_tokens: list
    max_new_tokens: int


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """n whole lengths: the quantiles of lognormal(median, sigma), clipped."""
    z = np.array([_NORMAL.inv_cdf(float(u)) for u in _quantile_points(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def uniform_quantiles(n: int, lo: int, hi: int) -> np.ndarray:
    return np.clip(np.rint(lo + (hi - lo) * _quantile_points(n)),
                   lo, hi).astype(int)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process: the exponential's
    quantiles, scaled so that they sum to n / rate exactly."""
    gaps = -np.log1p(-_quantile_points(n))
    return gaps * (n / rate) / gaps.sum()


def lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        return lognormal_quantiles(n, spec["median"], spec["sigma"],
                                   spec["min"], spec["max"])
    if spec["dist"] == "uniform":
        return uniform_quantiles(n, spec["min"], spec["max"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _laid_out(values: np.ndarray, order_seed: int, salt: int) -> np.ndarray:
    return values[np.random.default_rng([order_seed, salt])
                  .permutation(len(values))]


def serve_requests(traffic: dict, seed: int, vocab_size: int,
                   seconds: float) -> list:
    """The requests of one run.  Open loop (`rate_rps`): those due inside
    `seconds`, with their due times.  Closed loop (`clients`): a pool of
    `pool_requests`, dealt to the clients in order, due_s = 0."""
    if "rate_rps" in traffic:
        n = max(1, int(round(traffic["rate_rps"] * seconds)))
    else:
        n = int(traffic["pool_requests"])
    order_seed = int(traffic["order_seed"])
    prompts = _laid_out(lengths(traffic["prompt_tokens"], n), order_seed, 1)
    outputs = _laid_out(lengths(traffic["output_tokens"], n), order_seed, 2)
    if "rate_rps" in traffic:
        gaps = _laid_out(exponential_gaps(n, traffic["rate_rps"]),
                         order_seed, 3)
    else:
        gaps = np.zeros(n)
    # The first request is due as the window opens and each gap follows its
    # request, so the last is due inside the window.
    due = np.cumsum(gaps) - gaps
    rng = np.random.default_rng([seed, 7])
    return [Request(i, float(due[i]),
                    rng.integers(1, vocab_size, size=int(prompts[i])).tolist(),
                    int(outputs[i]))
            for i in range(n)]


def prompt_bucket(n: int, page_size: int, max_len: int) -> int:
    """The engine's prefill bucket for a prompt of n tokens (its rule,
    copied: powers of two from 16, at least a page, at most max_len)."""
    b = 16
    while b < n:
        b *= 2
    return max(min(b, max_len), page_size)


def buckets_of(traffic: dict, page_size: int, max_len: int) -> list:
    spec = traffic["prompt_tokens"]
    lo = prompt_bucket(spec["min"], page_size, max_len)
    hi = prompt_bucket(spec["max"], page_size, max_len)
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


class DocumentPacker:
    """Documents of seeded lengths, joined by `eos_token` and cut into
    rows of seq_len + 1 tokens (inputs and targets overlap by one)."""

    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        self._spec = traffic["document_tokens"]
        self._eos = int(traffic["eos_token"])
        self._seq = int(traffic["seq_len"])
        self._vocab = vocab_size
        # One cycle of document lengths, the same for every seed.
        cycle = lengths(self._spec, int(traffic["documents_per_cycle"]))
        self._cycle = _laid_out(cycle, int(traffic["order_seed"]), 1)
        self._next_doc = 0
        self._rng = np.random.default_rng([seed, 11])
        self._carry = np.zeros(0, np.int32)
        self.documents = 0

    def _document(self) -> np.ndarray:
        n = int(self._cycle[self._next_doc % len(self._cycle)])
        self._next_doc += 1
        self.documents += 1
        doc = self._rng.integers(1, self._vocab, size=n + 1, dtype=np.int32)
        doc[-1] = self._eos
        return doc

    def batch(self, rows: int) -> np.ndarray:
        need = rows * (self._seq + 1)
        parts, have = [self._carry], len(self._carry)
        while have < need:
            doc = self._document()
            parts.append(doc)
            have += len(doc)
        stream = np.concatenate(parts)
        self._carry = stream[need:]
        return stream[:need].reshape(rows, self._seq + 1)
