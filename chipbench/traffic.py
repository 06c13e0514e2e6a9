"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``chipbench/mixes/<traffic>.json``) is data only:

- ``loop``: ``"open"`` (independent users arriving on a wall-clock
  schedule at ``rate_per_s`` with exponential gaps; arrivals start
  ``ramp_s`` before the measured window and go on for ``tail_s`` after
  it) or ``"closed"``
  (``sessions`` clients, each sending its next of ``turns`` requests
  when the previous one completes).
- ``prompt_len`` / ``max_new``: a length distribution each,
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``.
- ``check_requests``: how many finished requests the correctness check
  compares.

Every seed gets the same multiset of lengths and inter-arrival gaps
(stratified quantiles of the stated distributions) in its own order, and
its own token ids: seeds change which request comes when, not how much
work a run holds. In an open loop that holds for the ramp, the window
and the tail each: every seed's window has the same requests.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

LOOPS = ("open", "closed")


@dataclass(frozen=True)
class Item:
    """One request: due time (s, relative to the window's start; closed
    loops use 0 for a session's first turn), prompt ids, output budget,
    session and turn (closed loops; -1 and 0 for open loops)."""
    due_s: float
    prompt: np.ndarray
    max_new: int
    session: int = -1
    turn: int = 0


@dataclass(frozen=True)
class Schedule:
    loop: str
    items: List[Item]
    ramp_s: float = 0.0
    sessions: int = 0


def load_mix(path: Path) -> Dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    return mix


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution: the quantiles at
    (i + 0.5) / n, as whole numbers within [min, max]."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "uniform":
        v = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        v = np.floor(v)
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def _budgets(mix: Dict, prompt_lens: np.ndarray, max_len: int,
             max_new_cap: int, rng) -> np.ndarray:
    out = rng.permutation(quantiles(mix["max_new"], len(prompt_lens)))
    out = np.minimum(np.minimum(out, max_new_cap), max_len - prompt_lens)
    if (out < 1).any():
        raise ValueError("mix leaves a request no room to decode")
    return out


def _open_phase(mix: Dict, rng, rate: float, a: float, b: float,
                vocab: int, max_len: int, max_new_cap: int) -> List[Item]:
    """round(rate * (b - a)) arrivals in [a, b): exponential gaps
    (stratified, in the seed's order) scaled to fill the stretch, and
    stratified lengths in the seed's order. The ramp, the window and the
    tail each get their own, so every seed's window holds the same
    requests."""
    n = int(round(rate * (b - a)))
    if n <= 0:
        return []
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    due = a + (b - a) * (np.cumsum(gaps) - gaps[0]) / gaps.sum()
    plens = np.minimum(rng.permutation(quantiles(mix["prompt_len"], n)),
                       max_len - 1)
    outs = _budgets(mix, plens, max_len, max_new_cap, rng)
    return [Item(float(due[i]),
                 rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
                 int(outs[i]))
            for i in range(n)]


def generate(mix: Dict, seed: int, seconds: float, vocab: int, *,
             max_len: int, max_new_cap: int,
             rate_per_s: float = None) -> Schedule:
    """The schedule of one run. ``rate_per_s`` overrides the mix's rate
    (the knee sweep uses it); nothing else may change the work."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        rate = float(rate_per_s or mix["rate_per_s"])
        ramp, tail = float(mix.get("ramp_s", 0.0)), float(mix.get("tail_s",
                                                                   0.0))
        items = []
        for a, b in ((-ramp, 0.0), (0.0, seconds), (seconds, seconds + tail)):
            items += _open_phase(mix, rng, rate, a, b, vocab, max_len,
                                 max_new_cap)
        return Schedule("open", items, ramp_s=ramp)
    sessions, turns = int(mix["sessions"]), int(mix.get("turns", 1))
    n = sessions * turns
    plens = rng.permutation(quantiles(mix["prompt_len"], n))
    plens = np.minimum(plens, max_len - 1)
    outs = _budgets(mix, plens, max_len, max_new_cap, rng)
    items = [Item(0.0, rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
                  int(outs[i]), session=i % sessions, turn=i // sessions)
             for i in range(n)]
    return Schedule("closed", items, sessions=sessions)
