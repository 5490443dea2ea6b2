"""The one traffic generator: builds a cell's requests from a mix's data
file (``bench/traffic/<mix>.json``), the cell's overrides and the seed.

Every seed gets the same *set* of sizes and arrival gaps: each length
is the distribution's value at an evenly spaced quantile ((i + 0.5) / n
for i < n), and a permutation decides which request gets which.  So two
seeds offer the same work.  The permutation is drawn from the mix's
``schedule_seed`` where the mix gives one (then every seed offers the
same schedule, and the seed changes only the token ids), and from the
seed otherwise (then the seed changes the order as well).

A mix file holds:

* ``kind``: ``"open_loop"`` (arrivals spread over the window at
  ``rate_per_s``, exponential gaps) or ``"backlog"`` (``backlog``
  requests all queued at t = 0, each with the window as its deadline);
* optionally ``rate_profile``: ``[[seconds, relative rate], ...]``,
  repeated over the window and scaled so that its mean is 1 (bursts:
  ``[[5, 3], [10, 0]]`` is 5 s at 3x the mean rate, then 10 s of none);
* ``prompt`` and ``output``: a length distribution each,
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  ``{"dist": "uniform", "min", "max"}``, or
  ``{"dist": "mixture", "parts": [{"weight", <distribution>}, ...]}``
  (each part gets its weight's share of the requests);
* optionally ``prefix``: ``{"count", "tokens", "zipf_s"}`` - ``count``
  shared prefixes of ``tokens`` tokens, each request drawing one with
  Zipf weights 1 / rank**s, in front of its own ``prompt`` part;
* optionally ``schedule_seed``: the seed of the order of lengths,
  arrival gaps and prefixes, fixed for every run.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()

# numpy streams of one seed, kept apart so that adding a draw to one
# never shifts another
_ORDER, _TOKENS, _WARM = 1, 2, 3


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it (engine-independent)."""
    rid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    arrival_s: float
    deadline_s: Optional[float]
    prefix_id: int = -1         # which shared prefix, -1 for none


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, stream])


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at evenly spaced quantiles of ``dist``, ascending."""
    if dist["dist"] == "mixture":
        counts = shares(n, [p["weight"] for p in dist["parts"]])
        return np.sort(np.concatenate([quantile_lengths(p, c) for p, c in
                                       zip(dist["parts"], counts)]))
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in q])
        vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        vals = np.rint(vals)
    elif dist["dist"] == "uniform":
        vals = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def shares(n: int, weights) -> np.ndarray:
    """Split n by the weights, rounded by largest remainder so the
    counts sum to n."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    base = np.floor(exact).astype(np.int64)
    short = n - int(base.sum())
    order = np.argsort(-(exact - base), kind="stable")
    base[order[:short]] += 1
    return base


def zipf_counts(n: int, count: int, s: float) -> np.ndarray:
    """Split n requests over ``count`` prefixes by weights 1 / rank**s."""
    return shares(n, 1.0 / np.arange(1, count + 1) ** s)


def warp(t: np.ndarray, profile, seconds: float) -> np.ndarray:
    """Map times of a constant-rate process onto the same count under
    the rate profile: t is where the cumulative arrivals of the profile
    (mean 1, repeated) reach those of the constant rate."""
    if not profile:
        return t
    dur = np.array([p[0] for p in profile], np.float64)
    rel = np.array([p[1] for p in profile], np.float64)
    period = dur.sum()
    rel = rel * period / (dur * rel).sum()            # mean rate 1
    cum = np.concatenate([[0.0], np.cumsum(dur * rel)])
    cycles, rest = np.divmod(t, period)
    # the segment whose cumulative span holds ``rest``; an idle segment
    # spans nothing, so none lands there
    i = np.searchsorted(cum, rest, side="right") - 1
    starts = np.concatenate([[0.0], np.cumsum(dur)])[i]
    return cycles * period + starts + (rest - cum[i]) / rel[i]


def n_requests(mix: dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    if mix["kind"] == "backlog":
        return int(mix["backlog"])
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def arrivals(mix: dict, n: int, seconds: float,
             order: np.random.Generator) -> np.ndarray:
    """Arrival offsets (s) from the window's start, ascending."""
    if mix["kind"] == "backlog":
        return np.zeros(n)
    q = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-q))
    # scale so the n arrivals fill the window at exactly n / seconds per
    # second, the last one inside it
    gaps *= seconds * (1.0 - 0.5 / n) / gaps.sum()
    return warp(np.cumsum(gaps), mix.get("rate_profile"), seconds)


def make(mix: dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    """The window's requests for this mix, seed and window length."""
    n = n_requests(mix, seconds)
    order = rng(int(mix.get("schedule_seed", seed)), _ORDER)
    toks = rng(seed, _TOKENS)
    prompt_len = order.permutation(quantile_lengths(mix["prompt"], n))
    out_len = order.permutation(quantile_lengths(mix["output"], n))
    at = arrivals(mix, n, seconds, order)
    prefixes, which = [], np.full(n, -1)
    if mix.get("prefix"):
        px = mix["prefix"]
        prefixes = [toks.integers(0, vocab, int(px["tokens"]), np.int32)
                    for _ in range(int(px["count"]))]
        counts = zipf_counts(n, int(px["count"]), float(px["zipf_s"]))
        which = order.permutation(np.repeat(np.arange(len(counts)), counts))
    deadline = seconds if mix["kind"] == "backlog" else None
    specs = []
    for i in range(n):
        own = toks.integers(0, vocab, int(prompt_len[i]), np.int32)
        if which[i] >= 0:
            own = np.concatenate([prefixes[which[i]], own])
        specs.append(Spec(rid=i, prompt=own, max_new=int(out_len[i]),
                          arrival_s=float(at[i]), deadline_s=deadline,
                          prefix_id=int(which[i])))
    return specs


def longest(dist: dict) -> int:
    if dist["dist"] == "mixture":
        return max(longest(p) for p in dist["parts"])
    return int(dist["max"])


def prompt_cap(mix: dict) -> int:
    """The longest prompt the mix can produce."""
    px = mix.get("prefix") or {}
    return longest(mix["prompt"]) + int(px.get("tokens", 0))


def warm_prompts(seed: int, lengths, vocab: int) -> List[np.ndarray]:
    """Prompts for warming the programs: their own stream, so they share
    no prefix with the window's traffic."""
    r = rng(seed, _WARM)
    return [r.integers(0, vocab, int(n), np.int32) for n in lengths]
