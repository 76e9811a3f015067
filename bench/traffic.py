"""The one traffic generator: a mix file of parameters -> requests.

A mix (``bench/traffic/<name>.json``) fixes the serving set-up it runs
under (scheme, slots, chunk, page size, faults at prepare) and the load:

* ``"loop": "closed"`` — ``clients`` callers, each sending its next
  request when its last one has finished;
* ``"loop": "open"`` — Poisson arrivals at ``rate_rps``, sent on schedule
  whether or not earlier requests have finished.

Prompt lengths are buckets drawn with the mix's weights; output lengths
come from ``gen``: ``{"dist": "pareto", "min", "max", "alpha"}`` (bounded
Pareto, heavy-tailed) or ``{"dist": "uniform", "min", "max"}``.

The sizes and arrival times are drawn from the mix's own
``schedule_seed``, so every run seed offers the same work at the same
times; the run seed draws the prompt tokens (and, elsewhere, the
weights).  Runs of different seeds then differ only in what the work
computes, not in how much of it there is.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request to send: its prompt length, its output length and,
    in an open loop, its due time in seconds after the window opens."""
    prompt_len: int
    gen: int
    due_s: float = 0.0


def _gens(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    u = rng.random(n)
    if spec["dist"] == "uniform":
        return lo + np.floor(u * (hi - lo + 1)).astype(int)
    if spec["dist"] == "pareto":
        a = float(spec["alpha"])
        # inverse CDF of the Pareto(a) law truncated to [lo, hi]
        x = lo * (1.0 - u * (1.0 - (lo / hi) ** a)) ** (-1.0 / a)
        return np.clip(np.floor(x).astype(int), lo, hi)
    raise ValueError(f"unknown output-length law {spec['dist']!r}")


def _prompts(mix: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    buckets = np.asarray(mix["prompt_buckets"])
    w = np.asarray(mix.get("prompt_weights", [1.0] * len(buckets)), float)
    return rng.choice(buckets, size=n, p=w / w.sum())


def closed_items(mix: dict, per_client: int) -> List[List[Item]]:
    """Each client's sequence of requests, in the order it sends them."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    out = []
    for _ in range(int(mix["clients"])):
        plens = _prompts(mix, rng, per_client)
        gens = _gens(mix["gen"], rng, per_client)
        out.append([Item(int(p), int(g)) for p, g in zip(plens, gens)])
    return out


def open_items(mix: dict, seconds: float, rate_rps: float = 0.0
               ) -> List[Item]:
    """Every request due in the first `seconds`, in due order."""
    rate = rate_rps or float(mix["rate_rps"])
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    n = int(rate * seconds * 2) + 16
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    plens = _prompts(mix, rng, n)
    gens = _gens(mix["gen"], rng, n)
    if due[-1] < seconds:
        raise ValueError("schedule too short for the window")
    return [Item(int(p), int(g), float(t))
            for p, g, t in zip(plens, gens, due) if t < seconds]


def prompt_tokens(seed: int, vocab: int, lengths: List[int]
                  ) -> List[np.ndarray]:
    """The prompts' token ids, drawn from the run seed."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in lengths]
