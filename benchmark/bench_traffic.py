"""One general traffic generator, driven by a mix's data file.

A mix (``benchmark/traffic/<name>.json``) names a ``kind`` and its
parameters; a later PR adds a mix by adding a file. The one kind a runner of
this benchmark drives is ``closed``: ``callers`` clients, each sending its
next request when the last returns (a pool of workers waiting on replies).

Lengths: a mix gives each length's published ``mean`` with its ``min`` and
``max``; the generator takes ``min`` plus an exponential, the distribution
that assumes nothing beyond that mean. Every seed gets the same *set* of
sizes in another order: the ``pool`` stratified quantiles of both lengths,
paired by a permutation the mix fixes, a pair over ``total_max`` having its
output cut to fit. The seed shuffles the pool anew for every pass through it
and draws the token ids. So two seeds differ in order and in ids, not in the
amount of work. No jax here: the parent process runs this.
"""

from __future__ import annotations

import json
import math
import os
import random
import threading

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def length_quantiles(spec: dict, n: int) -> list:
    """n stratified quantiles of ``min`` + Exp(``mean`` - ``min``), as whole
    lengths of at most ``max``."""
    scale = spec["mean"] - spec["min"]
    return [int(min(spec["max"], round(
        spec["min"] - scale * math.log(1.0 - (i + 0.5) / n))))
        for i in range(n)]


def size_pool(mix: dict) -> list:
    """[(prompt_len, max_new)], the same for every seed."""
    n = mix["pool"]
    prompts = length_quantiles(mix["prompt"], n)
    outputs = length_quantiles(mix["output"], n)
    random.Random(mix.get("pairing_seed", 0)).shuffle(outputs)
    return [(p, min(o, mix["total_max"] - p))
            for p, o in zip(prompts, outputs)]


def token_ids(seed: int, k: int, n: int, vocab: int) -> list:
    """The ids of request k: from the seed, never 0 (the padding id)."""
    return np.random.default_rng([int(seed), int(k)]) \
        .integers(1, vocab, n).tolist()


def _sizes_in_order(mix: dict, seed: int):
    """The pool, shuffled anew for every pass through it."""
    pool = size_pool(mix)
    rng = random.Random(int(seed))
    while True:
        order = list(pool)
        rng.shuffle(order)
        yield from order


class Deck:
    """Closed loop: one shared deck; whichever caller is free draws the next
    request, so the k-th request started is the same for a seed whatever the
    callers' speeds, and any stretch of the run holds the pool's mix."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self._sizes = _sizes_in_order(mix, seed)
        self._seed, self._vocab = seed, vocab
        self._k = 0
        self._lock = threading.Lock()

    def draw(self) -> dict:
        with self._lock:
            k = self._k
            self._k += 1
            p, n = next(self._sizes)
        return {"k": k, "max_new": n,
                "prompt": token_ids(self._seed, k, p, self._vocab)}


def longest_request(mix: dict) -> int:
    """Prompt and output together, at most: the reference pads to it."""
    return max(p + n for p, n in size_pool(mix))
