"""The one traffic generator: reads a mix's data file and makes a cell's
requests from ``--seed``.

Arrivals are an open loop on the cluster clock.  Their times are one fixed
Poisson draw at the mix's rate (from the mix's ``gap_seed``, the same for
every run), so every seed offers the same load at the same moments.  The
seed draws what is asked: one distinct prompt per arrival, from the mix's
prompt families in turn.  (Gaps drawn from the seed were tried first: over
a window of some twenty arrivals the seeds then offered different loads,
and the tails spread by 70 % and more.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due: float            # cluster-clock seconds after the window opens
    family: str           # prompt family the program's generator draws from
    prompt_seed: int      # same (family, prompt_seed) = same prompt


def rate_rps(mix: Dict) -> float:
    a = mix["arrivals"]
    return float(a["rate_share_of_knee"]) * float(a["knee_rps"])


def poisson_gaps(rate: float, n: int, gap_seed: int) -> np.ndarray:
    """``n`` exponential gaps at ``rate`` per second (copied from the
    program's ``workloads.arrivals.Poisson``: the same draw, fixed size)."""
    return np.random.default_rng(gap_seed).exponential(1.0 / rate, size=n)


def schedule(mix: Dict, seed: int, horizon_s: float,
             rate: float = 0.0) -> List[Arrival]:
    """Requests due in ``[0, horizon_s)`` of cluster time."""
    a = mix["arrivals"]
    if a["process"] != "poisson":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    rate = rate or rate_rps(mix)
    n = int(rate * horizon_s + 6 * math.sqrt(rate * horizon_s) + 16)
    due = np.cumsum(poisson_gaps(rate, n, a["gap_seed"]))
    families = list(mix["prompt_families"])
    content = np.random.default_rng([seed, 2])
    return [Arrival(float(t), families[i % len(families)],
                    int(content.integers(1 << 62)))
            for i, t in enumerate(due[due < horizon_s])]


def link_segments(mix: Dict, horizon_s: float) -> List[List[float]]:
    """``[[start_s, bytes_per_s], ...]`` of the modelled link: the mix's
    Gbit/s values in turn, each held for ``segment_s``."""
    link = mix["link"]
    vals = [g * 1e9 / 8 for g in link["gbit_s"]]
    seg = float(link.get("segment_s", 0) or 0)
    if seg <= 0 or len(vals) == 1:
        return [[0.0, vals[0]]]
    n = int(horizon_s // seg) + 2
    return [[i * seg, vals[i % len(vals)]] for i in range(n)]
