"""The one traffic generator: a mix file's parameters -> requests.

A mix (``bench/traffic/<mix>.json``) gives lengths and arrivals:

- ``prompt_tokens`` / ``output_tokens``: a lognormal by ``median`` and
  ``sigma``, clipped to ``[min, max]``;
- ``arrivals``: the kind of arrivals, a module
  ``bench/traffic/arrivals/<kind>.py`` that gives each request's due time
  (``due_s``), the number of requests a run offers (``count``) and whether
  the load is open-loop (``OPEN_LOOP``). A new kind is a new module;
- ``schedule_seed``: fixes the schedule, below;
- ``first_batch``: ``"residual_life"`` gives the first ``slots`` requests
  of a backlog the remaining lengths of requests met mid-flight: the total
  drawn length-biased, a uniform share already generated (and so part of
  the prompt), so that the window starts in steady state.

Every run of a mix serves one schedule. Each length and gap is the
distribution's quantile at (i + 1/2) / n, and the mix's ``schedule_seed``
fixes which lengths pair with which, their order and the order of the
gaps. A run's seed draws only the token ids (and, elsewhere, the weights).
So every seed does the same work, and the spread between runs is the
system's, not the sampler's.
"""

from __future__ import annotations

import dataclasses
import math
import pathlib
from statistics import NormalDist

import numpy as np

from bench import spec


@dataclasses.dataclass(frozen=True)
class Planned:
    rid: int
    due_s: float          # seconds after the clock starts
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(p: np.ndarray, median: float, sigma: float, lo: int,
               hi: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def _lengths(dist: dict, n: int, rng: np.random.Generator,
             bias: bool = False) -> np.ndarray:
    median = dist["median"]
    if bias:  # length-biased lognormal: mu + sigma^2
        median = median * math.exp(dist["sigma"] ** 2)
    out = _lognormal(quantiles(n), median, dist["sigma"], dist["min"],
                     dist["max"])
    return rng.permutation(out)


def arrivals_module(kind: str, bench_dir: pathlib.Path = spec.BENCH_DIR):
    return spec.load_module(bench_dir, "traffic/arrivals", kind)


def plan(mix: dict, *, seed: int, n: int, vocab: int, slots: int = 0,
         rate_per_s: float | None = None, arrivals=None) -> list[Planned]:
    """``n`` requests of ``mix``, in queue order: the mix's schedule with
    token ids from ``seed``. ``arrivals`` is the mix's arrivals module
    (found by its kind under this ``bench/`` when not given)."""
    if arrivals is None:
        arrivals = arrivals_module(mix["arrivals"])
    order = np.random.default_rng(
        np.random.SeedSequence(int(mix["schedule_seed"])))
    prompts = _lengths(mix["prompt_tokens"], n, order)
    outputs = _lengths(mix["output_tokens"], n, order)
    due = np.asarray(arrivals.due_s(mix, n, rate_per_s, order), float)
    if mix.get("first_batch") == "residual_life" and slots:
        k = min(slots, n)
        totals = _lengths(mix["output_tokens"], k, order, bias=True)
        age = order.permutation(quantiles(k))
        done = np.floor(age * totals).astype(int)
        outputs[:k] = totals - done
        prompts[:k] = prompts[:k] + done
    ids = np.random.default_rng(np.random.SeedSequence(int(seed))).integers(
        3, vocab, size=int(prompts.sum()), dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(prompts)])
    return [Planned(rid=i, due_s=float(due[i]),
                    prompt=ids[starts[i]:starts[i + 1]],
                    max_new_tokens=int(outputs[i])) for i in range(n)]
