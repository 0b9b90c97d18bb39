"""The one request generator: a traffic mix file plus a cell file.

Every seed runs the same work. Requests come in sets; a set of ``n`` takes,
for each of its distributions (prompt length, output length, arrival gap,
share already served), exactly the ``n`` quantiles ``(k + 0.5) / n``, and
hands them out in golden-ratio order: the request with index ``i`` takes
the quantile whose rank in the set is that of ``frac((i + 1) * m)``, with
``m`` an irrational of its own for each distribution, so large and small
values are spread through the set and the distributions are not paired
alike. What ``--seed`` draws is the content: the prompt tokens, uniform
over the vocabulary from (seed, request index), so no two prompts share a
prefix, and the weights.

Why the work is not drawn from the seed: at the rates the chip sustains a
window holds tens of requests, and which sizes and gaps a seed draws, or
only the order a seed puts a fixed set in, moves a latency statistic far
more than the system does (PERF.md: a seeded order spreads the median time
to first token by 22-73% between seeds).

Open loop (``"loop": "open"``): a warm set of ``round(rate * warm)``
requests spread over the warm phase, then a window set of
``round(rate * seconds)`` whose gaps, from the mix's distribution, are
scaled to sum to the window: the window offers exactly the cell's rate.
Closed loop (``"loop": "closed"``): sets of ``max_slots`` requests as the
runner needs them. The first set, which set-up prefills, stands for slots
caught mid-answer, as in a loop that has run a while: each of its requests
has a share of its output, from the quantiles of [0, 1), already served,
which it carries as part of its prompt.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# one irrational per distribution, so no two are handed out alike
MULT = {"prompt": (math.sqrt(5) - 1) / 2, "output": math.sqrt(2) - 1,
        "gap": math.sqrt(3) - 1, "served": math.sqrt(7) - 2}


@dataclasses.dataclass
class Request:
    index: int
    prompt_len: int
    output_len: int
    due_s: float | None = None         # open loop: offset from its phase


def seed_seq(seed: int, *words: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed & (2**64 - 1), *words])


def ordered_quantiles(first: int, n: int, kind: str) -> np.ndarray:
    """The ``n`` quantiles of a set whose first request has index
    ``first``, in the set's golden-ratio order."""
    keys = np.modf((first + np.arange(n) + 1) * MULT[kind])[0]
    return (np.argsort(np.argsort(keys)) + 0.5) / n


def _ppf(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        return dist["median"] * np.exp(dist["sigma"] * z)
    if kind == "gamma":                # unit mean; the caller scales it
        from scipy.special import gammaincinv
        k = dist["shape"]
        return gammaincinv(k, u) / k
    raise ValueError(f"unknown distribution {kind!r}")


def _lengths(dist: dict, bounds, u: np.ndarray) -> np.ndarray:
    lo, hi = bounds
    if dist["dist"] == "uniform":
        v = lo + (hi - lo) * u
    else:
        v = np.clip(_ppf(dist, u), lo, hi)
    return np.rint(v).astype(int)


def _bounds(mix: dict, cell: dict, what: str):
    d = mix[what]
    return cell[d.get("bounds_from_cell") or d["clip_from_cell"]]


def request_set(mix: dict, cell: dict, first: int, n: int, *,
                seconds: float | None = None,
                part_served: bool = False) -> list[Request]:
    """Requests ``first .. first + n - 1``: one set. ``seconds``: arrival
    offsets in [0, seconds) whose gaps sum to ``seconds`` (open loop).
    ``part_served``: each has a share of its output served already."""
    q = lambda kind: ordered_quantiles(first, n, kind)   # noqa: E731
    prompt = _lengths(mix["prompt"], _bounds(mix, cell, "prompt"),
                      q("prompt"))
    output = _lengths(mix["output"], _bounds(mix, cell, "output"),
                      q("output"))
    if part_served:
        served = np.floor(q("served") * output).astype(int)
        prompt, output = prompt + served, output - served
    due = [None] * n
    if seconds is not None:
        gaps = _ppf(mix["arrivals"], q("gap"))
        gaps *= seconds / gaps.sum()
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]).tolist()
    return [Request(first + j, int(prompt[j]), int(output[j]), due[j])
            for j in range(n)]


def open_count(rate_per_s: float, seconds: float) -> int:
    return max(1, round(rate_per_s * seconds))


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list:
    rng = np.random.default_rng(seed_seq(seed, 2, index))
    return rng.integers(0, vocab, size=length).tolist()
