"""The one general generator of request traffic.  A mix is a data file
(``traffic/<name>.json``, ``"kind": "requests"``) of parameters; a later PR
adds a mix by adding a file.

Every seed gets the SAME multiset of inter-arrival gaps and of
(prompt length, output length, greedy) triples — drawn once from the mix's
own ``mix_seed`` and scaled so the arrivals exactly fill the window — in
another order, with other token ids.  So a seed changes which request
meets which, never how much work the window holds.

Copied arithmetic: ``paddle_tpu/serving/traffic/workload.py``
``TrafficSpec.compile_trace`` (seeded exponential gaps, seeded lengths).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np


class Request(NamedTuple):
    due_s: float
    prompt: list
    max_new_tokens: int
    greedy: bool
    temperature: float
    top_p: float
    seed: int


def _lengths(rng, spec, n):
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "loguniform":
        return np.clip(np.round(np.exp(rng.uniform(
            math.log(lo), math.log(hi), n))), lo, hi).astype(np.int64)
    if spec["dist"] == "fixed":
        return np.full(n, lo, np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _gaps(rng, spec, n, seconds):
    kind = spec["kind"]
    if kind == "poisson":
        gaps = rng.exponential(1.0, n)
    elif kind == "uniform":
        gaps = np.ones(n)
    elif kind == "bursty":      # gamma gaps, cv > 1: burstier than Poisson
        cv = spec["cv"]
        gaps = rng.gamma(1.0 / cv ** 2, cv ** 2, n)
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    # n gaps, then room for one more: the last arrival lies inside the window
    return gaps * (seconds / (gaps.sum() + gaps.mean()))


def _requests(mix, fixed, rng, due, vocab):
    """``len(due)`` requests: lengths and the greedy share from the mix's
    own stream ``fixed``, their order and token ids from the run's ``rng``."""
    n = len(due)
    prompts = _lengths(fixed, mix["prompt_len"], n)
    outputs = _lengths(fixed, mix["output_len"], n)
    greedy = np.arange(n) < int(round(mix["sampling"]["greedy_share"] * n))
    order = rng.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    greedy = greedy[rng.permutation(n)]
    temp, top_p = mix["sampling"]["temperature"], mix["sampling"]["top_p"]
    return [Request(float(due[i]),
                    rng.integers(1, vocab, int(prompts[i])).tolist(),
                    int(outputs[i]), bool(greedy[i]),
                    0.0 if greedy[i] else temp, 1.0 if greedy[i] else top_p,
                    int(rng.integers(0, 2 ** 31 - 1)))
            for i in range(n)]


def generate(mix: dict, seconds: float, seed: int, vocab: int,
             rate_scale: float = 1.0):
    """The requests due in a window of ``seconds``, in order of due time."""
    n = max(1, int(round(mix["arrival"]["rate_qps"] * rate_scale * seconds)))
    fixed = np.random.default_rng(mix["mix_seed"])
    gaps = _gaps(fixed, mix["arrival"], n, seconds)
    rng = np.random.default_rng((int(seed), 0x7AFF1C))
    due = np.cumsum(gaps[rng.permutation(n)])
    return _requests(mix, fixed, rng, due, vocab)


def ramp(mix: dict, seed: int, vocab: int):
    """The burst that set-up offers before the window opens, so that the
    window starts on a full engine (``"ramp": {"burst": n}`` in the mix;
    none without it).  Same rule: every seed the same lengths, shuffled."""
    n = mix.get("ramp", {}).get("burst", 0)
    fixed = np.random.default_rng((mix["mix_seed"], 1))
    rng = np.random.default_rng((int(seed), 0x7AFF1D))
    return _requests(mix, fixed, rng, np.full(n, -1.0), vocab)
