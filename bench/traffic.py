"""The one traffic generator: reads a mix's parameters, draws its requests.

A mix is a data file ``bench/traffic/<mix>.json``.  Every seed gets the
same set of sizes and the same set of gaps between arrivals, in another
order: sizes are the distribution's quantiles at ``(i + 0.5) / n``, gaps
the exponential's.  The order is a low-discrepancy one (``spread``):
any stretch of consecutive requests holds short and long ones alike, so
a window sees about the same work whatever the seed, which shifts the
order and draws the token ids.

Kinds of arrival:

* ``open_loop`` — requests are sent on a schedule at ``rate_rps``,
  whether or not earlier ones have finished, Poisson-like.  Sends start
  ``preroll_s`` before the measured window opens (offsets are relative to
  the window's opening, so pre-roll sends have negative offsets).
* ``backlog`` — offline batch: the harness keeps ``waiting`` requests
  queued at all times; the list is drawn long enough never to run dry.

A ``documents`` group makes each request a question appended to a long
document: ``resident`` documents are prefilled in set-up, and a share
``new_share`` of questions, evenly spread, brings a new document that
replaces the oldest resident one.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    """One request of the mix."""

    t: float | None            # send offset from the window's opening
    prompt: np.ndarray         # int32 token ids
    max_new: int
    temperature: float         # 0 → greedy
    seed: int                  # the request's sampling stream
    doc: int | None = None     # document asked about (documents mixes)

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


@dataclasses.dataclass
class Plan:
    """What a run sends: set-up documents, then the requests in order."""

    docs: list[np.ndarray]
    requests: list[Req]
    mix: dict


def load(path: str | pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole sizes at the distribution's mid-quantiles, clipped."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    elif dist == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        v = np.exp(lo + q * (hi - lo))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


#: irrational steps of the orders of prompt lengths, output lengths,
#: gaps, documents and greedy requests, so that the orders are unrelated
_STEPS = {"prompt": 0.6180339887, "output": 0.4142135624,
          "gaps": 0.7320508076, "docs": 0.2360679775, "greedy": 0.1622776602}


def spread(values: np.ndarray, what: str, rng) -> np.ndarray:
    """``values`` reordered so that request ``i`` takes the one whose rank
    is that of ``frac(u + i·step)``: a Kronecker sequence with a random
    start ``u``, so every stretch of requests samples the whole range."""
    n = len(values)
    keys = (rng.random() + np.arange(n) * _STEPS[what]) % 1.0
    ranks = np.argsort(np.argsort(keys))
    return np.sort(values)[ranks]


def n_requests(mix: dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return max(1, round(mix["rate_rps"] * (mix["preroll_s"] + seconds)))
    if mix["kind"] == "backlog":
        return mix["pool"]
    raise ValueError(f"unknown kind {mix['kind']!r}")


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> Plan:
    """Draw a run's requests.  Same ``seed`` → same requests."""
    rng = np.random.default_rng([seed, 0x7A11C])
    n = n_requests(mix, seconds)
    prompt_lens = spread(quantiles(mix["prompt"], n), "prompt", rng)
    outputs = spread(quantiles(mix["output"], n), "output", rng)
    share = mix["greedy_share"]
    u = rng.random()
    greedy = np.floor(np.arange(1, n + 1) * share + u) > np.floor(
        np.arange(n) * share + u)
    seeds = rng.integers(0, 2 ** 31, size=n)
    if mix["kind"] == "open_loop":
        # n exponential gaps (Poisson arrivals) scaled to fill pre-roll
        # and window exactly, request i sent after the first i of them
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)
        gaps = spread(gaps * (mix["preroll_s"] + seconds) / gaps.sum(),
                      "gaps", rng)
        times = np.cumsum(gaps) - gaps - mix["preroll_s"]
    else:
        times = [None] * n

    docs: list[np.ndarray] = []
    doc_of = [None] * n
    if "documents" in mix:
        dspec = mix["documents"]
        # new documents come evenly spread, at the same places for every
        # seed: with few requests a run, their place would otherwise move
        # the window's work more than anything else the seed changes
        share = dspec["new_share"]
        is_new = np.floor((np.arange(n) + 1) * share) > np.floor(
            np.arange(n) * share)
        n_new = int(is_new.sum())
        lens = spread(quantiles(dspec, dspec["resident"] + n_new), "docs",
                      rng)
        docs = [rng.integers(0, vocab, size=int(m), dtype=np.int32)
                for m in lens]
        resident = list(range(dspec["resident"]))
        nxt = dspec["resident"]
        for i in range(n):
            if is_new[i]:
                resident = resident[1:] + [nxt]
                doc_of[i] = nxt
                nxt += 1
            else:
                doc_of[i] = resident[int(rng.integers(len(resident)))]

    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab, size=int(prompt_lens[i]), dtype=np.int32)
        prompt = tail if doc_of[i] is None else np.concatenate(
            [docs[doc_of[i]], tail])
        reqs.append(Req(t=None if times[i] is None else float(times[i]),
                        prompt=prompt, max_new=int(outputs[i]),
                        temperature=0.0 if greedy[i] else mix["temperature"],
                        seed=int(seeds[i]), doc=doc_of[i]))
    n_setup = mix["documents"]["resident"] if "documents" in mix else 0
    return Plan(docs=docs[:n_setup], requests=reqs, mix=mix)
