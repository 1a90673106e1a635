"""One general generator for every traffic mix.

A mix is a data file (``bench/traffic/<mix>.json``) of parameters; the cell
file (``bench/cells/<workload>.json``) fixes what belongs to the cell, such
as the open-loop rate.  Two loops:

* ``open``: independent users; requests arrive on a schedule of exponential
  gaps at ``rate_rps`` whether or not earlier ones have finished.  Where the
  cell gives ``token_s``, the pace of a request in steady state, the load
  starts warm: the requests that would be in flight at its start, had the
  same arrivals run for as long as the longest answer lasts, come first,
  each with the tokens it would have streamed appended to its prompt and
  the rest as its answer.
* ``closed``: ``clients`` callers, each sending its next request when the
  previous one has finished; a client runs sessions back to back, and a
  session asks about one document ``asks`` times (each prompt is the
  document plus a question), so later asks find the document in the prefix
  cache.

Lengths are drawn from ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}`` (integers, ends included).
The *sizes* of a run's requests (and, open loop, their arrival times) are
drawn once from a fixed master seed, so every seed offers the same work; the
token ids are drawn from the run's seed (closed loop: the run's seed also
orders the sessions).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

MASTER_SEED = 20251015


@dataclasses.dataclass
class Ask:
    """One request as the client sends it."""
    prompt: List[int]
    max_tokens: int
    due: float = 0.0            # open loop: seconds after the load starts
    warm: bool = False          # open loop: in flight when the load starts
    session: int = -1           # closed loop: session index, ask number
    ask: int = 0


def draw_lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        x = np.rint(x)
    elif spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, n).tolist()


def open_loop(mix: Dict, rate: float, segments: Sequence[float], seed: int,
              vocab: int, token_s: Optional[float] = None) -> List[Ask]:
    """Requests due in ``[0, sum(segments))`` at ``rate`` per second,
    after the warm start's requests (due at 0) where ``token_s`` is given.

    The arrival times and the sizes of the n-th request are the master
    seed's, the same on every run, in the same order; the run's seed draws
    the token ids.  (Reordering the sizes by the seed, even within blocks of
    8 requests, moved the 95th-percentile time to first token by 19% from
    seed to seed, against 0.6% between two runs of one seed.)"""
    horizon = float(sum(segments))
    n = int(math.ceil(rate * horizon * 1.5)) + 64
    master = np.random.default_rng(MASTER_SEED)
    prompts = draw_lengths(mix["prompt"], n, master)
    outputs = draw_lengths(mix["output"], n, master)
    due = np.cumsum(master.exponential(1.0, n)) / rate
    if due[-1] < horizon:
        raise ValueError(f"{n} arrivals do not cover {horizon} s at {rate}/s")
    rng = np.random.default_rng(seed)
    warm = [] if token_s is None else warm_start(mix, rate, token_s, rng,
                                                 vocab)
    return warm + [Ask(_tokens(rng, int(p), vocab), int(o), due=float(t))
                   for p, o, t in zip(prompts, outputs, due) if t < horizon]


def warm_start(mix: Dict, rate: float, token_s: float,
               rng: np.random.Generator, vocab: int) -> List[Ask]:
    """The requests in flight when the load starts, had arrivals at ``rate``
    gone on before it for as long as the longest answer takes at
    ``token_s`` seconds a token: a request that arrived ``age`` seconds
    earlier has streamed ``floor(age / token_s)`` of its tokens, which join
    its prompt; the rest is its answer.  Sizes and ages come from the master
    seed (a stream of their own), token ids from ``rng``."""
    span = int(mix["output"]["max"]) * token_s
    master = np.random.default_rng([MASTER_SEED, 1])
    n = int(math.ceil(rate * span * 1.5)) + 64
    ages = np.cumsum(master.exponential(1.0, n)) / rate
    if ages[-1] < span:
        raise ValueError(f"{n} arrivals do not cover {span} s at {rate}/s")
    prompts = draw_lengths(mix["prompt"], n, master)
    outputs = draw_lengths(mix["output"], n, master)
    asks = []
    for p, o, age in zip(prompts, outputs, ages):
        seen = int(age // token_s)
        if age < span and seen < o:
            asks.append(Ask(_tokens(rng, int(p) + seen, vocab), int(o) - seen,
                            warm=True))
    return asks


def closed_sessions(mix: Dict, seed: int, vocab: int) -> List[List[Ask]]:
    """Sessions, each a list of asks sharing one document, in the order the
    clients take them."""
    n = int(mix["sessions"])
    master = np.random.default_rng(MASTER_SEED)
    docs = draw_lengths(mix["document"], n, master)
    n_asks = draw_lengths(mix["asks"], n, master)
    qs = [draw_lengths(mix["question"], int(k), master) for k in n_asks]
    outs = [draw_lengths(mix["output"], int(k), master) for k in n_asks]
    rng = np.random.default_rng(seed)
    sessions = []
    for si, j in enumerate(rng.permutation(n)):
        doc = _tokens(rng, int(docs[j]), vocab)
        sessions.append([Ask(doc + _tokens(rng, int(q), vocab), int(o),
                             session=si, ask=a)
                         for a, (q, o) in enumerate(zip(qs[j], outs[j]))])
    return sessions


def longest(mix: Dict) -> Optional[int]:
    """Most prompt plus output tokens one request of the mix can hold."""
    if mix["loop"] == "open":
        return int(mix["prompt"]["max"]) + int(mix["output"]["max"])
    return (int(mix["document"]["max"]) + int(mix["question"]["max"])
            + int(mix["output"]["max"]))
