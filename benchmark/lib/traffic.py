"""The one general generator of request traffic. A traffic mix is a data
file of parameters. Lengths and gaps between arrivals are evenly spaced
quantiles of the mix's distributions. The mix's ``skeleton_seed`` fixes when
each request is due and how many tokens it asks for; ``--seed`` puts the
prompt lengths in another order and draws the tokens. So runs with different
seeds offer the same work at the same moments: a request that takes tens of
seconds is cut by the window's end in every seed alike."""

import math
import random
import statistics

import numpy as np


def _quantiles(n: int):
    return [(i + 0.5) / n for i in range(n)]


def lengths(dist: dict, n: int, avoid=()):
    """``n`` whole lengths at evenly spaced quantiles of ``dist``
    (``lognormal``: ``median``, ``sigma``, clipped to ``min``..``max``;
    ``fixed``: ``value``). With ``distinct`` no two are equal and none is in
    ``avoid`` (a server that compiles for a length it has seen twice then
    compiles nothing in the window): a clash moves to the nearest free
    length inside the bounds."""
    if dist["dist"] == "fixed":
        return [int(dist["value"])] * n
    if dist["dist"] != "lognormal":
        raise ValueError(f"distribution {dist['dist']!r} is not known")
    normal = statistics.NormalDist()
    lo, hi = int(dist["min"]), int(dist["max"])
    raw = [
        min(max(round(dist["median"] * math.exp(
            dist["sigma"] * normal.inv_cdf(u))), lo), hi)
        for u in _quantiles(n)
    ]
    if not dist.get("distinct"):
        return raw
    taken, out = set(avoid), []
    if hi - lo + 1 - len(taken) < n:
        raise ValueError("more distinct lengths asked than the bounds hold")
    for want in raw:
        for step in range(hi - lo + 1):
            cand = [c for c in (want - step, want + step)
                    if lo <= c <= hi and c not in taken]
            if cand:
                taken.add(cand[0])
                out.append(cand[0])
                break
    return out


def arrival_offsets(kind: str, rate: float, n: int, seconds: float, rng):
    """``n`` arrival times in ``[0, seconds)``. ``poisson``: the gaps are the
    evenly spaced quantiles of the exponential distribution, shuffled, and
    scaled so that the arrivals fill the window; ``uniform``: even gaps."""
    if kind == "uniform":
        gaps = [1.0 / rate] * n
    elif kind == "poisson":
        gaps = [-math.log(1.0 - u) / rate for u in _quantiles(n)]
        rng.shuffle(gaps)
    else:
        raise ValueError(f"arrivals {kind!r} are not known")
    scale = seconds * (1.0 - 0.5 / n) / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def open_loop_schedule(traffic: dict, vocab_size: int, seed: int,
                       seconds: float, rate=None, avoid_prompt_lens=()):
    """The requests of one window: ``[{"id", "due", "tokens",
    "max_tokens"}]`` sorted by ``due`` (seconds after the window opens)."""
    rate = float(traffic["rate_rps"] if rate is None else rate)
    n = max(int(round(rate * seconds)), 1)
    rng = random.Random(int(seed))
    skeleton = random.Random(int(traffic.get("skeleton_seed", 0)))
    prompt = lengths(traffic["prompt_len"], n, avoid_prompt_lens)
    output = lengths(traffic["output_len"], n)
    rng.shuffle(prompt)
    skeleton.shuffle(output)
    due = arrival_offsets(traffic["arrivals"], rate, n, seconds, skeleton)
    tokens = np.random.default_rng([int(seed), 0x73657276])
    return [
        {"id": i, "due": due[i], "max_tokens": output[i],
         "tokens": tokens.integers(0, vocab_size, prompt[i]).tolist()}
        for i in range(n)
    ]


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def prefill_buckets(prompt_lens, min_bucket: int, ceiling: int):
    """Widths of the padded prefill programs that prompts of these lengths
    run through: the next power of two, between ``min_bucket`` and
    ``ceiling``; a longer prompt streams in ``ceiling``-wide chunks and a
    tail."""
    widths = set()
    for n in prompt_lens:
        if n > ceiling:
            widths.add(ceiling)
            n = n % ceiling or ceiling
        widths.add(min(max(next_pow2(n), min_bucket), ceiling))
    return sorted(widths)
