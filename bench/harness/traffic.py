"""The one traffic generator: it reads a mix's data file
(``bench/traffic/<mix>.json``) and draws the requests.

A mix is a closed backlog of ``n_requests`` requests, every one due at t=0.
Every seed gets the same work. The multiset of prompt lengths, output lengths
and length estimates is drawn once from the mix's ``base_seed``; the run's
``--seed`` only shuffles the order of the requests and picks the request
ids, from which the prompts' token ids follow. So two seeds differ in order
and in every token, but not in how much there is to do: runs with different
seeds spread no wider than runs of one seed.

Lengths are ``{"dist": "normal", "mean", "std", "min", "max"}``: rounded
and clipped to [``min``, ``max``].

The GSM8K-like draw follows the paper's Table III moments as the program's
``data/workload.py`` draws them (copied, not imported, so that a change
there cannot move this yardstick): prompts N(68.43, 25.04) clipped to at
least 1, outputs clip(N(423.508, 340.894), 1, 512), estimates the true
length plus N(0, 40) clipped to [1, 512].
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

#: request ids are ``rid_base + i``; ``rid_base`` is a multiple of this
RID_STRIDE = 100_000
#: rid bases drawn from the seed stay below 2**31 (the engine keeps ids in
#: int32 arrays)
RID_BASES = 20_000


@dataclass
class Draw:
    """One request of a mix: its sizes and its length estimate."""

    rid: int
    prompt: int
    output: int
    estimate: int


def _lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] != "normal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    x = rng.normal(spec["mean"], spec["std"], size=n)
    hi = spec.get("max", np.inf)
    return np.clip(np.round(x), spec.get("min", 1), hi).astype(np.int64)


def draw(mix: dict, seed: int) -> List[Draw]:
    """The requests of one run of ``mix``."""
    if mix["loop"] != "backlog":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = int(mix["n_requests"])
    base = np.random.default_rng(int(mix["base_seed"]))
    prompt = _lengths(mix["prompt"], base, n)
    output = _lengths(mix["output"], base, n)
    hi = mix["output"].get("max", np.inf)
    est = np.clip(
        np.round(output + base.normal(0.0, mix["estimate_noise_std"], size=n)),
        1, hi,
    ).astype(np.int64)
    run = np.random.default_rng(np.random.SeedSequence(abs(int(seed))))
    order = run.permutation(n)
    rid_base = int(run.integers(RID_BASES)) * RID_STRIDE
    return [
        Draw(rid=rid_base + i, prompt=int(prompt[j]), output=int(output[j]),
             estimate=int(est[j]))
        for i, j in enumerate(order)
    ]


def prompt_tokens(rid: int, n: int, vocab: int) -> np.ndarray:
    """The token ids of request ``rid``'s prompt: ids 1..vocab-1 drawn from
    the request id, the rule the served engine documents for its synthetic
    prompts. The reference reads the prompt from here, not from the
    program, so a program that served another prompt fails the check."""
    return np.random.default_rng(rid).integers(1, vocab, size=n).astype(np.int32)
