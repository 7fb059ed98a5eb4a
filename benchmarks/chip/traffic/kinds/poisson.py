"""Open loop: independent users, Poisson arrivals at the cell's fixed
rate, lognormal prompt and output lengths, no shared prefixes.

Parameters (the mix's file): ``prompt`` and ``output``, each
``{"median", "sigma", "min", "max"}``; ``greedy_share`` and
``temperature`` for the sampled rest.  The cell's file gives
``rate_hz``.  Every seed gets the same sets of gaps and lengths
(``sampling``), in an order the run's seed draws, stratified in blocks
of ``BLOCK`` requests: each block holds one gap, one prompt length and
one output length from each eighth of its set, so the longest prompts
are spread through the window and no seed bunches them.  The seed also
draws the token ids.
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.chip import sampling
from benchmarks.chip.traffic.mix import Mix, Req

#: requests per stratified block
BLOCK = 8


def schedule_size(rate_hz: float, seconds: float) -> int:
    """Requests scheduled: enough that the last is due past the window,
    in whole blocks."""
    n = int(math.ceil(rate_hz * seconds * 1.1)) + 2
    return -(-n // BLOCK) * BLOCK


def make(traffic, *, seed: int, vocab: int, seconds: float,
         cell_params, engine) -> Mix:
    rate = float(cell_params["rate_hz"])
    n = schedule_size(rate, seconds)
    gaps = sampling.stratified(sampling.exponential_set(n, rate), seed, 1,
                               BLOCK)
    due = np.cumsum(gaps)
    p, o = traffic["prompt"], traffic["output"]
    plens = sampling.stratified(sampling.lognormal_set(
        n, p["median"], p["sigma"], p["min"], p["max"]), seed, 2, BLOCK)
    olens = sampling.stratified(sampling.lognormal_set(
        n, o["median"], o["sigma"], o["min"], o["max"]), seed, 3, BLOCK)
    n_greedy = int(round(traffic.get("greedy_share", 1.0) * n))
    greedy = sampling.stratified(np.arange(n) < n_greedy, seed, 4, BLOCK)
    temp = float(traffic.get("temperature", 0.0))

    def request(k: int) -> Req:
        toks = sampling.rng(seed, 5, k).integers(0, vocab, int(plens[k]))
        return Req(prompt=toks.astype(np.int32),
                   max_new_tokens=int(olens[k]),
                   temperature=0.0 if greedy[k] else temp)

    return Mix(loop="open", request=request, due_s=due)
