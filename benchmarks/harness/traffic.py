"""The one general traffic generator: a mix is a data file of parameters
(benchmarks/traffic/<name>.json), and this turns it and a seed into
requests. No JAX here: the load generator's process imports it too.

Every seed gets the same work in the same order. The (prompt length,
output length) pairs and the gaps between arrivals are drawn from the
mix's own fixed `shape_seed`, for the length of the window; the run's seed
draws the token ids (and, in the harness, the weights). Otherwise the seed
would change how much work a window holds, and runs of different seeds
would differ far more than two runs of one. The order is kept as well: an
open loop's schedule is ONE draw of Poisson arrivals (at four fifths of
the knee which long prompts happen to arrive together makes the tail), and
a closed loop deals the same lengths to the same callers.

    kind serve_open    arrivals at `rate_per_s` with exponential gaps
                       (Poisson), scaled so that they fill the window
                       exactly; each request is due at a fixed time
    kind serve_closed  `clients` callers, each sending its next request
                       when the last completes; `requests_per_client`
                       requests each, walked round and round
    lengths            {"dist": "lognormal", "median", "sigma", "min",
                       "max"} | {"dist": "uniform", "min", "max"} |
                       {"dist": "fixed", "value"}
"""

from __future__ import annotations

import math

import numpy as np


def draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def make_requests(mix: dict, seed: int, vocab: int, seconds: float) -> list:
    """[{"id", "client", "due_s", "prompt": [ids], "max_new"}] in sending
    order. Open loop: `due_s` from the window's start, `client` None.
    Closed loop: `due_s` None; each client sends its own in order."""
    shape = np.random.default_rng([int(mix["shape_seed"]), 11])
    ids = np.random.default_rng([int(seed), 17])
    kind = mix["kind"]
    if kind == "serve_open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        gaps = shape.gamma(1.0, 1.0, n)     # exponential
        gaps *= seconds / gaps.sum()        # the window holds exactly n
        due = np.cumsum(gaps) - gaps[0] * 0.5
        clients = [None] * n
    elif kind == "serve_closed":
        c, per = int(mix["clients"]), int(mix["requests_per_client"])
        n = c * per
        due = [None] * n
        clients = [i % c for i in range(n)]
    else:
        raise ValueError(f"traffic kind {kind!r} is not a serving mix")
    prompts = draw_lengths(shape, mix["prompt_len"], n)
    outs = draw_lengths(shape, mix["max_new_tokens"], n)
    return [{"id": f"r{i}", "client": clients[i],
             "due_s": None if due[i] is None else float(due[i]),
             "prompt": ids.integers(0, vocab, int(prompts[i])).tolist(),
             "max_new": int(outs[i])} for i in range(n)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values (q in 0..100)."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(0, min(len(v) - 1, int(math.ceil(q / 100.0 * len(v))) - 1))
    return float(v[k])
