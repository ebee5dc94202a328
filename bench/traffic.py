"""The one traffic generator: reads a mix file ``bench/traffic/<mix>.json``.

A mix names its arrival process (a module of ``bench/arrivals/``), the
rate, and the distributions of prompt and output lengths.  Lengths, like
gaps, are the distribution's mid-quantiles, shuffled by the seed: every
seed offers the same multiset of sizes and arrivals, in another order, so
the seed changes which request comes when and never how much work there
is.  Prompt token ids are drawn from the seed.

A mix may fix its schedule with ``"schedule_seed": <n>``: the order of
lengths and gaps, and so which request comes when, is then drawn from
``n`` and is the same for every seed, which draws only the token ids.
Where a tail sits near the edge between two kinds of tick, the order
alone decides which side it reads (a bursty mix's share of gaps stretched
by a prefill chunk moves with it), so such a mix fixes its order.

Length distributions (``prompt`` and ``output``):

* ``{"dist": "uniform", "min": a, "max": b}`` -- integers ``a..b``;
* ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
  -- ``m * exp(s * z)``, rounded and clipped to ``a..b``.

An open-loop mix offers ``round(rate_rps * seconds)`` requests, due over
the window: gaps are scaled so that they sum to the window, and the first
request is due when the window opens.  A closed backlog offers a pool of
``pool`` requests, taken in turn (and again from the start if it runs
out); ``first_wave_cut`` scales the output lengths of the first wave, one
factor per slot, so that the slots do not all free at once.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

import numpy as np
from scipy import stats

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Offered:
    """One request as the generator offers it."""

    rid: int
    prompt: list[int]
    max_new_tokens: int        # decode steps: the output is one token more
    due: float | None          # seconds after the window opens; None: closed


@dataclasses.dataclass
class Traffic:
    mix: dict
    closed: bool
    requests: list[Offered]


def load_mix(name: str, data: Path = BENCH) -> dict:
    return json.loads((data / "traffic" / f"{name}.json").read_text())


def arrival_module(mix: dict):
    return importlib.import_module(f"bench.arrivals.{mix['arrival']}")


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as integers."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        vals = lo + np.floor(q * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        vals = np.rint(float(dist["median"])
                       * np.exp(float(dist["sigma"]) * stats.norm.ppf(q)))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def generate(mix: dict, *, seed: int, seconds: float, vocab: int,
             batch: int) -> Traffic:
    rng = np.random.default_rng(seed)
    order = rng if "schedule_seed" not in mix else \
        np.random.default_rng(int(mix["schedule_seed"]))
    mod = arrival_module(mix)
    if mod.CLOSED:
        n = int(mix["pool"])
    else:
        n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
    prompts = order.permutation(quantile_lengths(mix["prompt"], n))
    outputs = order.permutation(quantile_lengths(mix["output"], n))
    if mod.CLOSED:
        due = [None] * n
        cuts = mix.get("first_wave_cut")
        if cuts:
            for i in range(min(batch, n)):
                outputs[i] = max(1, int(outputs[i] * cuts[i % len(cuts)]))
    else:
        gaps = order.permutation(np.asarray(mod.gaps(mix, n), np.float64))
        gaps *= seconds / gaps.sum()
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]).tolist()
    reqs = [Offered(rid=i,
                    prompt=rng.integers(0, vocab, size=int(p)).tolist(),
                    max_new_tokens=max(1, int(o) - 1), due=d)
            for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]
    return Traffic(mix=mix, closed=mod.CLOSED, requests=reqs)


def percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile of an ascending list (``q`` in [0, 1])."""
    if not sorted_vals:
        return float("nan")
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]
