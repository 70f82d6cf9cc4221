"""The one traffic generator. A mix is a data file ``traffic/<name>.json``:

- ``"kind": "poisson"``: open loop. ``rate_qps`` queries a second with
  ``sources_per_query`` sources each. A window holds
  ``round(rate_qps * seconds)`` arrivals whose gaps are the exponential
  distribution's quantiles, in an order drawn from the run's seed: every
  seed offers the same set of gaps, as a Poisson process gives them on
  average, in another order.
- ``"kind": "closed"``: ``clients`` callers, each submitting its next query
  when the previous one is delivered.

Sources are drawn from the run's seed, uniformly from the nodes with
out-degree > 0, one query at a time as the run deals them. The warm-up
and the window draw from separate streams of the seed, so the window
serves queries that the warm-up has not seen.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KINDS = ("poisson", "closed")
TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name!r}: kind must be one of {KINDS}")
    return mix


class SourceDeck:
    """Queries of ``sources_per_query`` sources, drawn from the run's seed
    uniformly from the nodes with out-degree > 0 (in the deployment's
    structural ids, then mapped through the run's ``relabel``). ``stream``
    keeps the warm-up's draws apart from the window's."""

    def __init__(self, mix: dict, out_degrees_structural: np.ndarray,
                 relabel: np.ndarray, seed: int, stream: int):
        self.k = int(mix["sources_per_query"])
        eligible = np.flatnonzero(out_degrees_structural > 0)
        self.eligible = relabel[eligible].astype(np.int32)
        self._rng = np.random.default_rng([seed, 3, stream])

    def deal(self) -> np.ndarray:
        return self.eligible[
            self._rng.integers(0, len(self.eligible), self.k)]


def arrival_offsets(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop mix:
    the exponential gaps at the quantiles ``(i + 1/2) / n``, permuted by
    the seed, scaled so that the n-th arrival falls at ``n / (n + 1)`` of
    the window."""
    rate = float(mix["rate_qps"])
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    t = np.cumsum(np.random.default_rng([seed, 1]).permutation(gaps))
    return t * (seconds * n / (n + 1) / t[-1])
