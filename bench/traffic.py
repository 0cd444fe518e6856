"""The one traffic generator: reads a mix's parameters from
`bench/traffic/<mix>.json` and turns them, with a seed, into an
open-loop list of requests.

Every seed offers the same work at the same pace.  The sequence of
prompt lengths, output lengths and inter-arrival gaps is drawn once from
the mix's own `layout_seed`; `--seed` only shuffles the three lists
(independently) inside each block of `BLOCK` consecutive requests, and
draws the prompt tokens and the per-request sampling seeds.  So runs on
different seeds differ in order and content, not in how much work
arrives in any few seconds, and their spread is the system's, not the
dice's.

Mix keys:
  rate_per_s      mean arrival rate (fixed; found once by a sweep)
  lead_in_s       load starts this long before the measured window
  arrivals        {"shape": k}: gamma-distributed gaps with shape k
                  (1 = Poisson; 0.25 = coefficient of variation 2)
  prompt_tokens   {"median", "sigma", "min", "max"}: lognormal, clipped
  output_tokens   the same, for the token budget of each request
  sampling        {"temperature", "top_p", "stop_at_eos", "greedy_every"}:
                  every `greedy_every`-th request decodes greedily (the
                  correctness check reads only greedy requests)
  layout_seed     seed of the size and gap multiset
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = 2


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float                  # seconds after load starts
    prompt: np.ndarray          # (P,) int32
    max_new: int
    greedy: bool
    temperature: float
    top_p: float
    seed: int
    stop_at_eos: bool


def load_mix(name: str, root: str = HERE) -> Dict[str, Any]:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        return json.load(f)


def _lengths(rng, spec, n):
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def count(mix: Dict[str, Any], seconds: float, rate: float = None) -> int:
    rate = mix["rate_per_s"] if rate is None else rate
    return int(math.ceil(rate * (mix["lead_in_s"] + seconds)))


def generate(mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
             rate: float = None) -> List[Arrival]:
    """Requests due over the lead-in and the window, in arrival order."""
    rate = mix["rate_per_s"] if rate is None else rate
    n = count(mix, seconds, rate)
    layout = np.random.default_rng(mix["layout_seed"])
    plens = _lengths(layout, mix["prompt_tokens"], n)
    outs = _lengths(layout, mix["output_tokens"], n)
    shape = mix["arrivals"]["shape"]
    gaps = layout.gamma(shape, 1.0 / shape, n)
    gaps *= (n / rate) / gaps.sum()        # offered load is exactly `rate`
    rng = np.random.default_rng(int(seed))

    def shuffle(x):
        return np.concatenate([rng.permutation(x[i:i + BLOCK])
                               for i in range(0, len(x), BLOCK)])
    plens, outs, gaps = shuffle(plens), shuffle(outs), shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    samp = mix["sampling"]
    out = []
    for i in range(n):
        greedy = (samp["temperature"] <= 0
                  or i % samp["greedy_every"] == 0)
        out.append(Arrival(
            rid=i, due=float(due[i]),
            prompt=rng.integers(1, vocab, int(plens[i])).astype(np.int32),
            max_new=int(outs[i]), greedy=greedy,
            temperature=0.0 if greedy else float(samp["temperature"]),
            top_p=1.0 if greedy else float(samp["top_p"]),
            seed=int(rng.integers(0, 2 ** 31 - 1)),
            stop_at_eos=bool(samp["stop_at_eos"])))
    return out
