"""The one traffic generator: a pure function of (cell file, seed, seconds).

Every seed gets the SAME sizes and the same gaps between arrivals in the
SAME order: sizes are the evenly spaced quantiles of the cell's length
distributions and gaps the quantiles of its arrival process, one fixed
draw (``ORDER_SEED``) orders them, and the seed draws the token ids (and
the weights). The program's scheduler runs batches to completion, so
what a request waits and what a window completes follow the order of
the requests: two seeds have to ask for the same work in the same
order, or the spread between runs is the dice's, not the system's.

Parameters a cell file may give (all data; a new mix is a new file):

``loop``            ``open`` (arrivals on a schedule) or ``closed``
                    (``clients`` callers, each sending its next request
                    when the last one ends)
``rate_per_s``      open loop: mean arrivals per second
``burst_size``      open loop: arrivals come ``burst_size`` at a time,
                    at the same mean rate (1 = Poisson)
``clients``         closed loop: callers
``stagger_ms``      closed loop: caller k sends its first request
                    ``k * stagger_ms`` after the window's start
                    (default 0: together). Callers that start together
                    race for the first batch, which takes the 1 to 4 of
                    them it finds
``deck``            closed loop: requests prepared (more than a window
                    can finish)
``block``           requests to a block (default: all in one). Sizes and
                    gaps are dealt into blocks of like mixes (every
                    k-th quantile to block k), the blocks and the
                    requests inside each in the fixed order. Every
                    stretch of a run then holds the same mix of short
                    and long requests, its end too
``classes``         list of ``{"share", "prompt", "output"}``; a length
                    is ``{"dist": "lognormal", "median", "sigma",
                    "min", "max"}`` or ``{"dist": "uniform", "min",
                    "max"}``
``sharing``         ``{"prefix_pool", "zipf_a", "prefix": <length>}``:
                    each prompt starts with one of ``prefix_pool``
                    shared prefixes, drawn Zipf; absent = no two prompts
                    share anything
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
ORDER_SEED = 20260930  # orders sizes and gaps, the same in every run


@dataclasses.dataclass(frozen=True)
class Req:
    i: int
    t: float | None        # seconds from the window's start; None = closed
    prompt: tuple
    gen_len: int
    cls: int = 0


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, clipped."""
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length dist {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def deal(values, block: int | None, rng) -> np.ndarray:
    """``values`` in ``rng``'s order: dealt into blocks of about
    ``block`` (value k of the sorted list to block k mod n), the blocks
    and each block's inside permuted by ``rng``."""
    values = np.sort(np.asarray(values))
    n_blocks = max(len(values) // block, 1) if block else 1
    out = [rng.permutation(values[b::n_blocks])
           for b in rng.permutation(n_blocks)]
    return np.concatenate(out)


def arrivals(rate: float, burst: int, seconds: float, rng,
             block: int | None = None) -> np.ndarray:
    """Arrival times in [0, seconds): exponential-quantile gaps between
    bursts, ordered by ``rng``, scaled to fill the window."""
    n = max(int(round(rate * seconds)), 1)
    n_bursts = max(-(-n // burst), 1)
    gaps = -np.log1p(-_quantiles(n_bursts)) * burst / rate
    gaps = deal(gaps, block, rng)
    # Half a mean gap of slack at the end keeps the last arrival inside.
    scale = seconds / (gaps.sum() + 0.5 * burst / rate)
    starts = np.cumsum(gaps * scale)
    return np.repeat(starts, burst)[:n]


def _class_counts(classes: list, n: int) -> list:
    shares = np.array([float(c.get("share", 1.0)) for c in classes])
    raw = shares / shares.sum() * n
    counts = np.floor(raw).astype(int)
    for k in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[k] += 1
    return counts.tolist()


def generate(traffic: dict, seed: int, seconds: float,
             vocab_size: int) -> list:
    """The requests of one run, in sending order."""
    tokens_rng = np.random.default_rng(seed)
    rng = np.random.default_rng(ORDER_SEED)
    block = traffic.get("block")
    if traffic["loop"] == "open":
        ts = arrivals(float(traffic["rate_per_s"]),
                      int(traffic.get("burst_size", 1)), seconds, rng, block)
        n = len(ts)
    elif traffic["loop"] == "closed":
        n = int(traffic["deck"])
        ts = [None] * n
    else:
        raise ValueError(f"loop must be open or closed: {traffic['loop']!r}")
    classes = traffic["classes"]
    sizes = []  # (class, prompt_len, gen_len)
    for ci, (c, cnt) in enumerate(zip(classes, _class_counts(classes, n))):
        if cnt == 0:
            continue
        p = deal(lengths(c["prompt"], cnt), block, rng)
        g = deal(lengths(c["output"], cnt), block, rng)
        sizes += [(ci, int(a), int(b)) for a, b in zip(p, g)]
    # Several classes are mixed through one another; one keeps the
    # order its blocks were dealt in.
    order = (rng.permutation(len(sizes)) if len(classes) > 1
             else np.arange(len(sizes)))
    share = traffic.get("sharing") or {}
    pool = int(share.get("prefix_pool", 0))
    prefixes, weights = [], None
    if pool:
        plens = rng.permutation(lengths(share["prefix"], pool))
        prefixes = [tokens_rng.integers(0, vocab_size, size=int(k)).tolist()
                    for k in plens]
        w = 1.0 / np.arange(1, pool + 1) ** float(share.get("zipf_a", 1.0))
        weights = w / w.sum()
    out = []
    for i, k in enumerate(order):
        ci, plen, glen = sizes[k]
        head = prefixes[int(rng.choice(pool, p=weights))] if pool else []
        body = tokens_rng.integers(0, vocab_size, size=plen).tolist()
        out.append(Req(i=i, t=None if ts[i] is None else float(ts[i]),
                       prompt=tuple(head + body), gen_len=glen, cls=ci))
    return out


def histogram(reqs: list) -> dict:
    """Length histogram for an earlier output line."""
    p = sorted(len(r.prompt) for r in reqs)
    g = sorted(r.gen_len for r in reqs)

    def q(v, x):
        return v[min(int(x * len(v)), len(v) - 1)]

    return {"n": len(reqs),
            "prompt_min_p50_p90_max": [p[0], q(p, .5), q(p, .9), p[-1]],
            "output_min_p50_p90_max": [g[0], q(g, .5), q(g, .9), g[-1]],
            "prompt_tokens": sum(p), "output_tokens": sum(g)}
