"""End-to-end statistics over the host's request timelines."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Timeline:
    """One request as the host saw it: when it was due and when each of
    its tokens landed (seconds on the host's clock; the first token is
    the admission's)."""
    due: float
    token_times: List[float]


def ttft_samples(reqs: List[Timeline], w0: float, w1: float) -> np.ndarray:
    """Time to first token of every request due in [w0, w1), in seconds.
    A request whose first token had not landed by w1 counts with its wait
    so far, so a stall raises the tail and never drops out of it."""
    out = []
    for r in reqs:
        if not (w0 <= r.due < w1):
            continue
        first = r.token_times[0] if r.token_times else None
        if first is None or first > w1:
            out.append(w1 - r.due)
        else:
            out.append(first - r.due)
    return np.asarray(out, np.float64)


def tpot_samples(reqs: List[Timeline], w0: float, w1: float) -> np.ndarray:
    """For each request that delivered at least two tokens inside
    [w0, w1]: (last - first) / (tokens - 1) over those tokens, seconds."""
    out = []
    for r in reqs:
        t = [x for x in r.token_times if w0 <= x <= w1]
        if len(t) >= 2:
            out.append((t[-1] - t[0]) / (len(t) - 1))
    return np.asarray(out, np.float64)


def tokens_in(reqs: List[Timeline], w0: float, w1: float) -> int:
    return sum(sum(1 for x in r.token_times if w0 <= x <= w1)
               for r in reqs)


def pct(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(x, q)) if len(x) else None


def end_to_end(reqs: List[Timeline], w0: float, w1: float
               ) -> Dict[str, Optional[float]]:
    """The cell's latency and rate metrics over the window [w0, w1]."""
    ttft = ttft_samples(reqs, w0, w1)
    tpot = tpot_samples(reqs, w0, w1)
    p90 = pct(ttft, 90)
    return {
        "ttft_p90_ms": None if p90 is None else 1e3 * p90,
        "tpot_p50_ms": None if not len(tpot) else 1e3 * pct(tpot, 50),
        "tpot_p90_ms": None if not len(tpot) else 1e3 * pct(tpot, 90),
        "output_tokens_per_s": tokens_in(reqs, w0, w1) / (w1 - w0),
        "n_ttft": len(ttft), "n_tpot": len(tpot),
    }

