"""The one general traffic generator. A mix is a data file under
``benchmark/traffic/``; this module turns its parameters and ``--seed``
into inputs, and nothing in it knows a mix by name.

Every seed gets the *same* sizes, gaps and prefix choices — a stratified
sample of each distribution, one value per equal slice of probability, in
an order fixed by the mix's ``order_seed`` — with other token ids. An open
loop's window takes the whole cycle, and the seed starts it at another
point: the same work in another order (a tail such as a 95th percentile
then reads the program and the machine, not the luck of one arrival
pattern). A closed loop's window takes only the part of the pool that the
program gets through, so there every seed starts at the same point: a
start from the seed would give each seed another subset of sizes, with up
to 5% more or fewer prefills for the same output tokens. Two runs of one
seed get the same work in the same order.

Kinds of mix (``"kind"`` in the file):

- ``train_batches``: ``batch`` rows of ``seq`` uniform token ids and as
  many labels, a fresh batch for every step.
- ``open_loop``: arrivals on a schedule (Poisson at
  ``arrivals.rate_per_s``), whatever the system does.
- ``closed_loop``: ``clients`` callers, each sending its next request when
  its last has finished.

A request is ``prefix`` (one of ``prefix.count`` shared token runs, chosen
by a Zipf law; absent = nothing shared) + a unique tail of ``tail_tokens``
+ ``output_tokens`` to generate.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream for each use of a seed of any size."""
    return np.random.default_rng([int(seed), *map(int, stream)])


# ------------------------------------------------------------ distributions
def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """The inverse distribution function of ``dist`` at ``u`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        x = np.floor(lo + u * (hi - lo + 1))          # whole numbers lo..hi
    elif kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(v)) for v in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif kind == "exponential":
        x = -np.log1p(-u) * dist["mean"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist or "max" in dist:
        x = np.clip(x, dist.get("min", -math.inf), dist.get("max", math.inf))
    return x


def stratified(dist: dict, n: int, rng: np.random.Generator,
               start: int = 0) -> np.ndarray:
    """``n`` values, one from the middle of each of ``n`` equal slices of
    probability, in an order drawn from ``rng`` and begun at ``start``."""
    u = (np.arange(n) + 0.5) / n
    return np.roll(rng.permutation(quantile(dist, u)), -start)


def zipf_choices(count: int, exponent: float, n: int,
                 rng: np.random.Generator, start: int = 0) -> np.ndarray:
    """``n`` indices in ``[0, count)`` with P(k) ~ 1/(k+1)**exponent,
    stratified likewise."""
    w = 1.0 / np.arange(1, count + 1) ** exponent
    cdf = np.cumsum(w) / w.sum()
    u = (np.arange(n) + 0.5) / n
    return np.roll(rng.permutation(np.searchsorted(cdf, u, side="left")),
                   -start)


# ---------------------------------------------------------------- training
def train_batch(mix: dict, vocab: int, seed: int, step: int):
    """The batch of step ``step``: (ids, labels), int32 ``[batch, seq]``,
    every row different."""
    rng = rng_for(seed, 1, step)
    shape = (mix["batch"], mix["seq"])
    return (rng.integers(0, vocab, shape, dtype=np.int32),
            rng.integers(0, vocab, shape, dtype=np.int32))


# ----------------------------------------------------------------- serving
@dataclass
class ServeRequest:
    index: int
    due_s: float | None      # open loop: seconds after the window opens
    prompt: np.ndarray       # int32 token ids
    prefix_id: int           # -1: nothing shared
    output_tokens: int


def horizon_count(mix: dict, seconds: float) -> int:
    """How many requests a mix prepares for a window of ``seconds``."""
    if mix["kind"] == "open_loop":
        return max(1, math.ceil(mix["arrivals"]["rate_per_s"] * seconds))
    return int(mix["pool"])


def serve_requests(mix: dict, vocab: int, seed: int, seconds: float,
                   cycle: int = 0) -> list[ServeRequest]:
    """The requests of one run. Open loop: those due inside ``seconds``,
    in due order. Closed loop: a pool that the clients draw from in
    order; clients that come to its end draw from ``cycle`` 1, 2, ..: the
    same sizes in the same order with token ids never sent before, so
    that a faster program meets no prompt twice."""
    n = horizon_count(mix, seconds)
    order = mix["order_seed"]
    # the seed chooses where in the cycle the run begins, where the window
    # takes the whole cycle. A closed loop's window takes as much of the
    # pool as the program is fast (405 of 512 in 51 s), so another start
    # would be another subset of sizes: every seed begins at 0
    start = int(rng_for(seed, 0).integers(0, n)) \
        if mix["kind"] == "open_loop" else 0
    tails = stratified(mix["tail_tokens"], n, rng_for(order, 2),
                       start).astype(int)
    outs = stratified(mix["output_tokens"], n, rng_for(order, 3),
                      start).astype(int)
    prefix = mix.get("prefix")
    if prefix:
        which = zipf_choices(prefix["count"], prefix["zipf_exponent"], n,
                             rng_for(order, 4), start)
        runs = rng_for(seed, 5).integers(
            1, vocab, (prefix["count"], prefix["tokens"]), dtype=np.int32)
    else:
        which = np.full(n, -1)
    if mix["kind"] == "open_loop":
        gaps = stratified({"dist": "exponential",
                           "mean": 1.0 / mix["arrivals"]["rate_per_s"]},
                          n, rng_for(order, 6), start)
        # the first arrival opens the window
        due = np.cumsum(gaps)
        due = due - due[0]
    else:
        due = [None] * n
    tok = rng_for(seed, 7, cycle) if cycle else rng_for(seed, 7)
    out = []
    for i in range(n):
        tail = tok.integers(1, vocab, int(tails[i]), dtype=np.int32)
        prompt = np.concatenate([runs[which[i]], tail]) if prefix else tail
        out.append(ServeRequest(cycle * n + i,
                                None if due[i] is None else float(due[i]),
                                prompt, int(which[i]), int(outs[i])))
    if mix["kind"] == "open_loop":
        out = [r for r in out if r.due_s < seconds]
    return out


def warmup_requests(mix: dict, vocab: int, seed: int) -> list[ServeRequest]:
    """The requests that warm the programs a mix lists: ``warmup`` in the
    file is a list of {"prefix": bool, "tail_tokens": n, "output_tokens":
    n}; the shared run they use is one the window never sends."""
    rng = rng_for(seed, 8)
    prefix = mix.get("prefix")
    run = rng.integers(1, vocab, prefix["tokens"], dtype=np.int32) \
        if prefix else None
    out = []
    for i, w in enumerate(mix["warmup"]):
        tail = rng.integers(1, vocab, w["tail_tokens"], dtype=np.int32)
        prompt = np.concatenate([run, tail]) if w.get("prefix") else tail
        out.append(ServeRequest(i, None, prompt, -2 if w.get("prefix") else -1,
                                w["output_tokens"]))
    return out
