"""The one general traffic generator: every traffic mix is a data file of
parameters that this module reads.

Every seed gets the same amount of work. For tuning the jobs and sizes are
the mix's own and the seed draws the token rows. For serving the sizes and
the gaps between arrivals are drawn once from the mix's ``shape_seed`` and
the run's seed only shuffles them (and draws the prompts' tokens), so runs
with different seeds serve the same requests in another order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


# ---------------------------------------------------------------------------
# tuning: a task's token rows
# ---------------------------------------------------------------------------

def markov_rows(g: np.random.Generator, probs: np.ndarray, n: int,
                length: int) -> np.ndarray:
    """n rows of a first-order Markov chain over ``len(probs)`` tokens."""
    cum = np.cumsum(probs, axis=1)
    out = np.empty((n, length), np.int32)
    state = g.integers(0, len(probs), size=n)
    out[:, 0] = state
    for t in range(1, length):
        u = g.random(n)
        state = np.minimum((u[:, None] >= cum[state]).sum(axis=1),
                           len(probs) - 1)
        out[:, t] = state
    return out


def tune_rows(traffic: Dict, seed: int) -> Dict[str, np.ndarray]:
    """{"train": [N, S+1], "val": [M, S+1]} int32 rows. The chain's
    transition matrix is drawn from the seed: a fine-tuning task with a
    learnable, low-entropy structure over ``data.tokens`` token ids."""
    data = traffic["data"]
    g = rng(seed, 0)
    k = data["tokens"]
    probs = g.dirichlet(np.full(k, data["concentration"]), size=k)
    S = traffic["seq_len"] + 1
    return {"train": markov_rows(g, probs, traffic["num_train"], S),
            "val": markov_rows(g, probs, traffic["num_val"], S)}


# ---------------------------------------------------------------------------
# serving: an open-loop request schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    due_s: float
    adapter: int
    prompt: np.ndarray
    max_new: int


def _lognormal_ints(g: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * g.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def serve_schedule(traffic: Dict, vocab: int, seed: int,
                   seconds: float) -> List[Request]:
    """``rate_per_s * seconds`` requests due in [0, seconds): Poisson
    arrivals (exponential gaps), lognormal prompt and output lengths,
    adapters by popularity. Shapes from ``shape_seed``; order and tokens
    from ``seed``."""
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    base = rng(traffic["shape_seed"], 1)
    gaps = base.exponential(1.0, n)
    prompts = _lognormal_ints(base, traffic["prompt"], n)
    outputs = _lognormal_ints(base, traffic["output"], n)
    pop = np.asarray(traffic["popularity"], float)
    adapters = np.repeat(np.arange(len(pop)),
                         np.round(pop / pop.sum() * n).astype(int))
    adapters = np.resize(adapters, n)
    g = rng(seed, 2)
    order = g.permutation(n)
    gaps = gaps[g.permutation(n)]
    # the first request is due at 0 and the mean gap is seconds / n
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= seconds / (due[-1] + gaps[-1])
    adapters = adapters[g.permutation(n)]
    out = []
    for i in range(n):
        j = order[i]
        out.append(Request(
            due_s=float(due[i]), adapter=int(adapters[i]),
            prompt=g.integers(0, vocab, size=int(prompts[j])).astype(
                np.int32),
            max_new=int(outputs[j])))
    return out
