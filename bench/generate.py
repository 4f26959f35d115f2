"""The benchmark's one traffic generator.

A traffic file (`bench/traffic/<name>.json`) holds only parameters:

    {"arrival": {"kind": "poisson", "rate_rps": 170.0},
     "mix": {"input_logmean": ..., "input_logstd": ..., "input_min": ...,
             "input_max": ..., "output_logmean": ..., ...},
     ...}

`arrival.kind` names a module `bench/arrivals/<kind>.py`. Two ways to
draw a trace from it:

* `sequential` draws one arrival gap, then the input and the output
  length, request by request from one `numpy.random.default_rng(seed)`:
  the draw order of the program's workload scenarios (a copy of
  `repro.workloads.Scenario.generate`, kept here so that a change to the
  program cannot move the yardstick).
* `stratified` gives every seed the same multiset of sizes and gaps,
  taken at evenly spaced quantiles of their distributions, in an order
  drawn from the seed: the same work in every run, so that runs on
  different seeds differ only in arrival order.
"""
from __future__ import annotations

import importlib
import math
from statistics import NormalDist

import numpy as np


def _arrivals(arrival: dict):
    return importlib.import_module(f"bench.arrivals.{arrival['kind']}")


def _clip_int(x: float, lo: int, hi: int) -> int:
    return int(np.clip(x, lo, hi))


def sequential(traffic: dict, duration_s: float, seed: int):
    """(arrival_s, input_tokens, output_tokens) arrays, drawn exactly as
    the program's scenario draws them."""
    mix = traffic["mix"]
    rng = np.random.default_rng(seed)
    next_gap = _arrivals(traffic["arrival"]).next_gap_fn(traffic["arrival"])
    t_arr, n_in, n_out = [], [], []
    t = 0.0
    while True:
        t += next_gap(rng, t)
        if t >= duration_s:
            break
        t_arr.append(t)
        n_in.append(_clip_int(rng.lognormal(mix["input_logmean"],
                                            mix["input_logstd"]),
                              mix["input_min"], mix["input_max"]))
        n_out.append(_clip_int(rng.lognormal(mix["output_logmean"],
                                             mix["output_logstd"]),
                               mix["output_min"], mix["output_max"]))
    return (np.asarray(t_arr, np.float64), np.asarray(n_in, np.int64),
            np.asarray(n_out, np.int64))


def _lognormal_quantiles(logmean: float, logstd: float, n: int):
    nd = NormalDist()
    return np.array([math.exp(logmean + logstd * nd.inv_cdf((k + 0.5) / n))
                     for k in range(n)])


def stratified(traffic: dict, duration_s: float, seed: int) -> list[dict]:
    """`round(rate x duration)` requests due in [0, duration_s): sizes
    and gaps at the quantiles (k + 0.5) / n, each list in its own order
    drawn from the seed; gaps scaled to fill the window. Input lengths
    are clipped, then rounded up to a multiple of `input_round`."""
    arrival, mix = traffic["arrival"], traffic["mix"]
    n = max(1, round(float(arrival["rate_rps"]) * duration_s))
    rng = np.random.default_rng(seed)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(
        [_arrivals(arrival).quantile_gap(arrival, x) for x in u])
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= duration_s / gaps.sum()
    step = int(mix.get("input_round", 1))
    n_in = np.clip(_lognormal_quantiles(mix["input_logmean"],
                                        mix["input_logstd"], n),
                   mix["input_min"], mix["input_max"])
    n_in = (np.ceil(n_in / step) * step).astype(np.int64)
    n_out = np.clip(_lognormal_quantiles(mix["output_logmean"],
                                         mix["output_logstd"], n),
                    mix["output_min"], mix["output_max"]).astype(np.int64)
    n_in = rng.permutation(n_in)
    n_out = rng.permutation(n_out)
    return [{"due_s": float(due[k]), "input_tokens": int(n_in[k]),
             "output_tokens": int(n_out[k])} for k in range(n)]
