"""Traffic from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) fixes the arrival process and the
length distributions; a cell file (``bench/cells/<cell>.json``) fixes the
rate. A run of ``seconds`` at ``rate_rps`` sends ``n = round(rate *
seconds)`` requests. Their lengths are the log-normal's quantiles at
``(i + 0.5) / n``, and the gaps between their arrivals the exponential's, so
every seed puts the same set of sizes and the same set of gaps on the
system, and the gaps sum to about the window. The seed draws the order of
each, stratified: every run of ``STRATA`` consecutive requests holds one
value from each tenth of the distribution, so that the long prompts are
spread over the window. The seed also draws the prompt tokens (and,
elsewhere, the weights and the check's sample). The arithmetic of the
log-normal (median and sigma, then rounded and clamped) follows
``repro.workload.generator``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

STRATA = 10
ARRIVALS = ("poisson",)  # the arrival processes a mix file may name


@dataclass
class Job:
    """One request as the client sends it."""

    index: int
    prompt: list[int]
    output_tokens: int
    due_s: float = 0.0  # offset from the window's start


@dataclass
class Traffic:
    jobs: list[Job] = field(default_factory=list)


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of a rounded, clamped
    log-normal with the spec's median and sigma, in ascending order."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    vals = [int(round(math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))))
            for i in range(n)]
    return np.clip(np.asarray(vals, np.int64), spec["min"], spec["max"])


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the stratified quantiles of an
    exponential with mean ``1 / rate``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def stratified_order(values: np.ndarray, rng) -> np.ndarray:
    """``values`` (ascending) in an order drawn from ``rng`` in which each
    block of ``STRATA`` consecutive entries takes one entry from each
    stratum."""
    strata = [rng.permutation(s) for s in np.array_split(values, STRATA)]
    out = []
    for j in range(len(strata[0])):
        block = [s[j] for s in strata if j < len(s)]
        out.extend(rng.permutation(block))
    return np.asarray(out, values.dtype)


def make_traffic(mix: dict, cell: dict, seed: int, seconds: float,
                 vocab: int) -> Traffic:
    """The jobs of one run: ``round(rate * seconds)`` arrivals, which span
    the window, the first due as it opens."""
    if mix["arrival"] not in ARRIVALS:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    rng = np.random.default_rng([seed % 2**63, 0x7A11])
    n = max(1, round(cell["rate_rps"] * seconds))
    prompts = stratified_order(lognormal_quantiles(mix["prompt_tokens"], n),
                               rng)
    outputs = stratified_order(lognormal_quantiles(mix["output_tokens"], n),
                               rng)
    due = np.cumsum(stratified_order(exponential_gaps(cell["rate_rps"], n),
                                     rng))
    due -= due[0]
    tokens = rng.integers(0, vocab, size=int(prompts.sum()), dtype=np.int64)
    jobs, at = [], 0
    for i in range(n):
        p = int(prompts[i])
        jobs.append(Job(i, tokens[at:at + p].tolist(), int(outputs[i]),
                        float(due[i])))
        at += p
    return Traffic(jobs)
