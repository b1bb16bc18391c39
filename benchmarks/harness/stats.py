"""Arithmetic on spans: percentiles, time per output token, due times."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, as numpy's default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def ttft_ms(span: dict) -> float:
    """Client time to first token, from when the request was DUE (open
    loop: a late generator or a stalled server both count) or sent."""
    return (span["first"] - span["due"]) * 1e3


def tpot_ms(span: dict) -> float | None:
    """(last token - first token) / (tokens - 1) at the client.  The
    engine streams in chunks, so single gaps are 0, 0, ..., chunk time;
    the mean over a request is what repeats."""
    if span["tokens"] < 2:
        return None
    return (span["last"] - span["first"]) * 1e3 / (span["tokens"] - 1)


def tokens_in_window(span: dict, t0: float, t1: float) -> int:
    return sum(1 for t in span["token_times"] if t0 <= t <= t1)
