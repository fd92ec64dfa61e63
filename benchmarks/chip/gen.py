"""Open-loop traffic from a mix file and a seed.

A mix file (``traffic/<name>.json``) lists one or more streams, each with a
tenant, a share of the total rate, a burstiness and lognormal prompt and
output lengths. The generator is the same for every mix.

Every seed gets the same work in another order. The set of prompt lengths,
output lengths and inter-arrival gaps of a stream is fixed by the mix alone
(lognormal quantiles, and gamma gaps from a fixed generator); ``--seed``
only permutes them and draws the prompt tokens. So runs on different seeds
differ in order and token ids, not in how much there is to do, and the set
of prompt lengths (hence of prefill chunk shapes) is the same for all seeds.

Arrivals: gamma-distributed inter-arrival gaps with mean ``1 / rate`` and
squared coefficient of variation ``burstiness`` (BurstGPT's
characterisation; burstiness 1 is a Poisson process). The same idea as the
program's ``serving/traces.py`` (gamma bursts, lognormal lengths), kept
here so that the program cannot change the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

# fixes the multiset of gaps of a stream, never the run seed
SHAPE_SEED = 20240131


@dataclasses.dataclass
class Item:
    rid: str
    tenant: str
    due: float            # seconds from the window's opening (< 0: pre-roll)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    preroll: bool


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a lognormal given by its
    ``median`` (or ``mean``) and ``sigma``, clipped to [min, max]."""
    sigma = float(spec["sigma"])
    if "median" in spec:
        mu = math.log(spec["median"])
    else:
        mu = math.log(spec["mean"]) - sigma ** 2 / 2
    nd = NormalDist()
    q = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q).astype(np.int64), spec["min"], spec["max"])


def gamma_gaps(rate: float, burstiness: float, n: int, salt: int) -> np.ndarray:
    """``n`` inter-arrival gaps, mean exactly ``1 / rate``."""
    rng = np.random.default_rng([SHAPE_SEED, salt, n])
    g = rng.gamma(1.0 / burstiness, burstiness, n)
    return g * (n / rate) / g.sum()


def _items(stream: dict, n: int, due: np.ndarray, tag: str, rng,
           vocab: int) -> List[Item]:
    prompts = rng.permutation(lognormal_set(stream["prompt"], n))
    outs = rng.permutation(lognormal_set(stream["output"], n))
    return [Item(f"{stream['tenant']}/{tag}{i}", stream["tenant"],
                 float(due[i]),
                 rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                 int(outs[i]), tag == "p")
            for i in range(n)]


def generate(mix: dict, seed: int, seconds: float,
             vocab: dict) -> List[Item]:
    """Pre-roll and window requests of ``mix`` for a window of ``seconds``,
    sorted by due time. Pre-roll requests (``mix["preroll"]["requests"]``,
    split by share) are all due before the window (due -1); window requests
    arrive at the stream's rate from 0. ``vocab`` maps tenant -> vocabulary
    size."""
    total = float(mix["rate_rps"])
    items: List[Item] = []
    for k, s in enumerate(mix["streams"]):
        rng = np.random.default_rng([seed, k])
        n_pre = int(round(mix["preroll"]["requests"] * s["share"]))
        if n_pre:
            items += _items(s, n_pre, np.full(n_pre, -1.0), "p", rng,
                            vocab[s["tenant"]])
        r = total * s["share"]
        n = max(int(round(r * seconds)), 1)
        gaps = gamma_gaps(r, s["burstiness"], n, k)
        g = rng.permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
        items += _items(s, n, due, "w", rng, vocab[s["tenant"]])
    items.sort(key=lambda it: it.due)
    return items


def chunk_shapes(items: List[Item], chunk: int) -> List[Tuple[int, int]]:
    """Every (length, start) of the prefill chunks these prompts give: a
    prompt of ``n`` tokens is prefilled in chunks of ``chunk`` tokens from
    0, the last one shorter. The program compiles one chunk program for
    each pair (the start is a constant inside it)."""
    out = set()
    for it in items:
        n = len(it.prompt)
        for start in range(0, n, chunk):
            out.add((min(chunk, n - start), start))
    return sorted(out)
