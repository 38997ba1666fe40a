"""The one traffic generator: a mix's data file plus ``--seed`` make the
requests of a run.

Every seed of a mix gets the same schedule: the same prompt and output
lengths, at the same times, with the same requests sampling or greedy,
drawn once from the mix's own ``base_seed``.  The seed draws the token
ids and the sampling seeds.  So two runs with different seeds do the same
work, and the spread between them is the system's, not the generator's:
near the knee, the order in which long prompts meet a burst alone moved
the p90 time to first token by a factor of four between seeds.

Mix keys (all lengths in tokens, times in seconds):

* ``loop``: ``"open"`` (arrivals on a schedule) or ``"closed"``
  (``clients`` callers, each sending its next request when the last one
  finished);
* ``arrivals``: ``{"process": "poisson" | "gamma", "rate_rps": r,
  "cv": c}`` for an open loop — gamma gaps have coefficient of variation
  ``cv`` (bursts), poisson gaps are exponential;
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``sampling``: ``{"temperature", "top_p", "greedy_every"}`` — every
  ``greedy_every``-th request decodes greedily, the rest sample;
* ``pool``: requests drawn for a closed loop (it takes them in order).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of a run, before it is sent."""
    index: int
    at: float                 # scheduled send time after the window opens
    prompt: np.ndarray        # (P,) int32
    max_new_tokens: int
    temperature: float
    top_p: float
    seed: int                 # sampling seed (unused when greedy)

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = np.exp(np.log(spec["median"])
                   + spec["sigma"] * rng.standard_normal(n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["process"] == "poisson":
        return rng.exponential(1.0, n)
    if spec["process"] == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def count(mix: dict, seconds: float) -> int:
    """Requests in a run of ``seconds``: the open loop's rate times the
    window, or a closed loop's pool."""
    if mix["loop"] == "open":
        return max(1, int(round(mix["arrivals"]["rate_rps"] * seconds)))
    return int(mix["pool"])


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate_rps: float | None = None) -> list[Planned]:
    """The run's requests in send order (closed loop: pool order, all at
    ``at = 0``).  ``rate_rps`` overrides the mix's rate (the knee sweep)."""
    if rate_rps is not None:
        mix = dict(mix, arrivals=dict(mix["arrivals"], rate_rps=rate_rps))
    n = count(mix, seconds)
    base = np.random.default_rng(int(mix["base_seed"]) + n)
    prompts = _lengths(mix["prompt"], n, base)
    outputs = _lengths(mix["output"], n, base)
    if mix["loop"] == "open":
        gaps = _gaps(mix["arrivals"], n, base)
        gaps *= seconds / gaps.sum()            # the window holds them all
        at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        at = np.zeros(n)
    rng = np.random.default_rng(int(seed))
    samp = mix.get("sampling", {})
    every = int(samp.get("greedy_every", 0))
    out = []
    for i in range(n):
        greedy = samp.get("temperature", 0) <= 0 or (every and i % every == 0)
        out.append(Planned(
            index=i, at=float(at[i]),
            prompt=rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
            max_new_tokens=int(outputs[i]),
            temperature=0.0 if greedy else float(samp["temperature"]),
            top_p=1.0 if greedy else float(samp.get("top_p", 1.0)),
            seed=int(rng.integers(0, 2 ** 31 - 1))))
    return out


def max_lengths(mix: dict) -> tuple[int, int]:
    """The longest prompt and output the mix can hold (arena sizing)."""
    return int(mix["prompt"]["max"]), int(mix["output"]["max"])
