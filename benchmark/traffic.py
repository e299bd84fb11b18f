"""The one general traffic generator: a cell's JSON file -> a schedule.

A mix is data (``workloads/<cell>.json``); the functions it names are found
here by name, or as ``generate(params, n, rng)`` in ``generators/<name>.py`` (a later PR adds
a generator as a new file, never by editing this one).

Every seed carries the same work in another order. A distribution is
sampled at the fixed quantiles (i + 0.5) / BLOCK; prompt quantile i is paired
with output quantile PAIRING[i] (one fixed permutation, so the two lengths
stay uncorrelated); ``--seed`` permutes each block of BLOCK requests and each
block of BLOCK arrival gaps, and gives the words of every prompt. So two
seeds send the same multiset of sizes and gaps per block, and differ in which
requests meet: measured on the chip (PR 23), that alone moves tokens/s by
3.8 % and a 90th percentile by 6-9 % between seeds, which is the spread the
bounds are set from.
"""

from __future__ import annotations

import importlib.util
import math
import os
import random
from statistics import NormalDist

BLOCK = 32
PAIRING = random.Random(BLOCK).sample(range(BLOCK), BLOCK)
HERE = os.path.dirname(os.path.abspath(__file__))


def _blocks(quantile, n: int, rng: random.Random) -> list:
    """``n`` draws: the BLOCK fixed quantiles, permuted anew per block by
    ``rng`` (``rng`` None: in quantile order, for the caller to permute)."""
    base = [quantile((i + 0.5) / BLOCK) for i in range(BLOCK)]
    out = []
    while len(out) < n:
        block = list(base)
        if rng is not None:
            rng.shuffle(block)
        out += block
    return out[:n]


# -- length generators: (params, n, rng) -> list of int ------------------------

def lognormal_clipped(params: dict, n: int, rng: random.Random) -> list:
    """Lognormal with ``median`` and ``sigma``, clipped to [``lo``, ``hi``]."""
    mu, sigma = math.log(params["median"]), params["sigma"]
    lo, hi = params["lo"], params["hi"]
    norm = NormalDist()

    def q(p):
        return int(min(hi, max(lo, round(math.exp(mu + sigma * norm.inv_cdf(p))))))

    return _blocks(q, n, rng)


def uniform_int(params: dict, n: int, rng: random.Random) -> list:
    """Whole numbers spread evenly over [``lo``, ``hi``]."""
    lo, hi = params["lo"], params["hi"]
    return _blocks(lambda p: int(lo + math.floor(p * (hi - lo + 1))), n, rng)


# -- arrival generators: (params, n, rng) -> due times in seconds, or None ------

def poisson(params: dict, n: int, rng: random.Random) -> list:
    """Open loop: exponential gaps at ``rate_rps`` (stratified, see above);
    the first request is due one gap after the origin."""
    rate = params["rate_rps"]
    gaps = _blocks(lambda p: -math.log(1.0 - p) / rate, n, rng)
    due, t = [], 0.0
    for g in gaps:
        t += g
        due.append(t)
    return due


def closed(params: dict, n: int, rng: random.Random) -> None:
    """Closed loop: ``clients`` callers, each sends its next request when
    the last completes. No due times."""
    return None


def find_generator(name: str):
    fn = globals().get(name)
    if callable(fn) and not name.startswith("_"):
        return fn
    path = os.path.join(HERE, "generators", f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no generator {name!r} in traffic.py or generators/")
    spec = importlib.util.spec_from_file_location(f"benchmark_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def build_schedule(cell: dict, seed: int, seconds: float) -> dict:
    """The requests of one run: prompt and output lengths, and due times
    (open loop, relative to the start of the window; negative inside the
    pre-roll) or a client count (closed loop). Pure function of its
    arguments."""
    rng = random.Random(seed)
    arrivals = dict(cell["arrivals"])
    preroll = float(cell.get("preroll_s", 0.0))
    span = preroll + seconds
    if arrivals["gen"] == "closed":
        # more than any system could complete: the pool is never exhausted
        n = BLOCK * max(4, math.ceil(arrivals["clients"] * span / BLOCK))
    else:
        n = BLOCK * math.ceil(arrivals["rate_rps"] * span * 1.5 / BLOCK + 1)
    due = find_generator(arrivals["gen"])(arrivals, n, rng)
    if due is not None:
        due = [t - preroll for t in due if t < span]
        n = len(due)
    # one block of (prompt, output) pairs, the same for every seed ...
    p_block = find_generator(cell["prompt_tokens"]["gen"])(cell["prompt_tokens"], BLOCK, None)
    o_block = find_generator(cell["output_tokens"]["gen"])(cell["output_tokens"], BLOCK, None)
    pairs = [(p_block[i], o_block[PAIRING[i]]) for i in range(BLOCK)]
    prompt, output = [], []
    while len(prompt) < n:  # ... in an order of the seed's own, block by block
        for p, o in rng.sample(pairs, BLOCK):
            prompt.append(p)
            output.append(o)
    prompt, output = prompt[:n], output[:n]
    return {
        "n": n, "due": due, "clients": arrivals.get("clients"),
        "preroll_s": preroll, "prompt_tokens": prompt, "output_tokens": output,
        "text_seed": rng.getrandbits(32),
    }


def prompt_text(plain_words: list, n_tokens: int, rng: random.Random) -> str:
    """``n_tokens`` seeded random words: one token each, shared with nothing."""
    return " ".join(rng.choices(plain_words, k=n_tokens))
