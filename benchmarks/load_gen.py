"""Open-loop load generator for the serving engine.

Closed-loop benchmarks (``serving_throughput.py``) always keep the
engine saturated: a finished request is immediately replaced, so they
measure peak throughput but say nothing about latency under realistic
load.  This driver is **open-loop**: request arrival times are drawn
from a seeded Poisson process and injected on schedule whether or not
the engine has kept up — the regime where queueing delay, admission
backpressure and the host/device overlap actually show.

Recorded per arrival rate (into the ``open_loop`` section of
``BENCH_serving.json``):

* TTFT p50/p99 — scheduled arrival → first token (queueing included);
* TPOT p50/p99 — mean inter-token time per request after the first;
* goodput — completed requests per second meeting BOTH SLOs (TTFT and
  TPOT bounds derived from an unloaded calibration run), alongside raw
  throughput.

A ``closed_loop_async`` row is also written: the identical closed-loop
workload through the synchronous engine vs the pipelined engine
(on-device sampling + one-step-ahead dispatch), token-equality checked,
isolating the host-sync removal from everything else.

  PYTHONPATH=src python -m benchmarks.load_gen [--fast]
"""

from __future__ import annotations

import json
import pathlib
import sys
import time
from collections import deque

import jax
import numpy as np

from benchmarks.timing import merge_bench_json, time_rotated
from repro.configs import ARCHS, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.models import build_model
from repro.runtime import (EngineConfig, PagedCacheConfig, PipelinedEngine,
                           ServingEngine)
from repro.runtime.engine import EngineStats

JSON_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_serving.json"

#: SLO bounds as multiples of the unloaded (single-request) latencies:
#: a request is "good" if its TTFT is within 4x the unloaded TTFT and
#: its TPOT within 2x the unloaded per-token time.
SLO_TTFT_X = 4.0
SLO_TPOT_X = 2.0


def build_engine(pipelined: bool, *, impl: str = "rexp", n_slots: int = 4,
                 cache: PagedCacheConfig | None = None):
    # realistic-vocab sampled serving is the regime this PR targets: the
    # sync engine ships (B, 1, V) logits to the host and runs an eager
    # per-row categorical there, both of which scale with vocab
    arch = ARCHS["qwen3-32b"].scaled_down(d_model=128, n_heads=8,
                                          vocab=8192, n_periods=2)
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    policy = (SoftmaxPolicy(impl=impl, precision="uint8")
              if impl != "exact" else SoftmaxPolicy())
    run = RunConfig(dtype="float32", attention_backend="naive",
                    scan_layers=True, softmax_policy=policy)
    cache = cache or PagedCacheConfig(n_pages=64, page_size=8,
                                      max_pages_per_seq=10)
    cls = PipelinedEngine if pipelined else ServingEngine
    return cls(model, params, run,
               EngineConfig(n_slots=n_slots, cache=cache))


def make_workload(rng, n, vocab=8192, max_prompt=24, max_new=16,
                  temperature=0.7):
    return [dict(prompt=rng.integers(0, vocab,
                                     size=int(rng.integers(4, max_prompt + 1))
                                     ).tolist(),
                 max_new_tokens=int(rng.integers(4, max_new + 1)),
                 temperature=temperature,
                 seed=int(rng.integers(0, 2**31)))
            for _ in range(n)]


def run_open_loop(eng, requests, arrivals_s):
    """Inject requests at their scheduled offsets; drive until drained.

    Returns per-request records with scheduled arrival, first-token and
    last-token wall times (first/last stamped by the engine's streaming
    callback, so the pipelined engine's late harvests are charged
    honestly).
    """
    recs = [{"t_arr": None, "t_first": None, "t_last": None, "n": 0}
            for _ in requests]
    pending = deque(zip(arrivals_s, range(len(requests))))
    t0 = time.time()
    while pending or eng.has_work():
        now = time.time() - t0
        while pending and pending[0][0] <= now:
            arr, i = pending.popleft()
            rec = recs[i]
            rec["t_arr"] = t0 + arr  # scheduled, not actual: open loop

            def cb(_tok, rec=rec):
                t = time.time()
                if rec["t_first"] is None:
                    rec["t_first"] = t
                rec["t_last"] = t
                rec["n"] += 1

            eng.add_request(**requests[i], on_token=cb)
        if eng.has_work():
            eng.step()
        elif pending:
            time.sleep(max(0.0, min(0.001, pending[0][0] - now)))
    return recs, time.time() - t0


def _percentiles(xs):
    return {"p50": round(float(np.percentile(xs, 50)), 5),
            "p99": round(float(np.percentile(xs, 99)), 5)}


def summarize(recs, makespan_s, slo_ttft_s, slo_tpot_s):
    ttfts = [r["t_first"] - r["t_arr"] for r in recs]
    tpots = [(r["t_last"] - r["t_first"]) / (r["n"] - 1)
             for r in recs if r["n"] > 1]
    good = sum(1 for r in recs
               if r["t_first"] - r["t_arr"] <= slo_ttft_s
               and (r["n"] < 2 or (r["t_last"] - r["t_first"]) / (r["n"] - 1)
                    <= slo_tpot_s))
    return {
        "n_requests": len(recs),
        "makespan_s": round(makespan_s, 3),
        "ttft_s": _percentiles(ttfts),
        "tpot_s": _percentiles(tpots),
        "throughput_req_s": round(len(recs) / makespan_s, 3),
        "goodput_req_s": round(good / makespan_s, 3),
        "slo_attainment": round(good / len(recs), 3),
    }


def calibrate(eng, rng):
    """Unloaded latencies: one request at a time, best of 3."""
    ttfts, tpots = [], []
    for _ in range(3):
        reqs = make_workload(rng, 1)
        recs, _ = run_open_loop(eng, reqs, [0.0])
        r = recs[0]
        ttfts.append(r["t_first"] - r["t_arr"])
        if r["n"] > 1:
            tpots.append((r["t_last"] - r["t_first"]) / (r["n"] - 1))
    return min(ttfts), min(tpots)


def bench_open_loop(n_requests: int = 24, seed: int = 0) -> dict:
    """Poisson arrivals at ~0.5x / 0.8x / 1.2x the engine's closed-loop
    request rate, through the pipelined engine."""
    rng = np.random.default_rng(seed)
    eng = build_engine(pipelined=True)
    warm = make_workload(rng, 4)
    eng.run(warm)

    ttft0, tpot0 = calibrate(eng, rng)
    slo_ttft_s = SLO_TTFT_X * ttft0
    slo_tpot_s = SLO_TPOT_X * tpot0

    # capacity probe: closed-loop (all arrivals at t=0) request rate
    probe = make_workload(rng, n_requests)
    eng.stats = EngineStats()
    recs, makespan = run_open_loop(eng, probe, [0.0] * len(probe))
    capacity_req_s = len(probe) / makespan

    rates = {}
    for mult in (0.5, 0.8, 1.2):
        lam = capacity_req_s * mult
        requests = make_workload(rng, n_requests)
        arrivals = np.cumsum(rng.exponential(1.0 / lam, size=n_requests))
        eng.stats = EngineStats()
        recs, makespan = run_open_loop(eng, requests, arrivals.tolist())
        rates[f"{mult}x"] = {
            "arrival_rate_req_s": round(lam, 3),
            **summarize(recs, makespan, slo_ttft_s, slo_tpot_s),
            "queue_depth_peak": eng.stats.queue_depth_peak,
            "speculative_wasted": eng.stats.speculative_wasted,
        }
    return {
        "workload": {"n_requests": n_requests, "seed": seed, "n_slots": 4,
                     "policy": "rexp", "vocab": 8192, "temperature": 0.7},
        "backend": jax.default_backend(),
        "arrival_process": "poisson",
        "calibration": {"unloaded_ttft_s": round(ttft0, 4),
                        "unloaded_tpot_s": round(tpot0, 4),
                        "slo_ttft_s": round(slo_ttft_s, 4),
                        "slo_tpot_s": round(slo_tpot_s, 4)},
        "capacity_req_s": round(capacity_req_s, 3),
        "rates": rates,
    }


def bench_closed_loop_async(n_requests: int = 24, seed: int = 0) -> dict:
    """Sync vs pipelined engine on the identical saturated workload:
    the before/after row isolating on-device sampling + overlapped
    dispatch from every other engine feature."""
    rng = np.random.default_rng(seed)
    requests = make_workload(rng, n_requests)
    useful = sum(r["max_new_tokens"] for r in requests)
    warm = [dict(r, max_new_tokens=2) for r in requests[:4]]
    engines = {"sync": build_engine(pipelined=False),
               "pipelined": build_engine(pipelined=True)}
    for eng in engines.values():
        eng.run([dict(r) for r in warm])

    def make_driver(eng):
        def drive(_r):
            eng.stats = EngineStats()
            t0 = time.time()
            rids = [eng.add_request(**r) for r in requests]
            out = eng.run()
            return time.time() - t0, [out[rid].tokens for rid in rids]
        return drive

    def check(_r, payloads):
        for a, b in zip(payloads["sync"], payloads["pipelined"]):
            np.testing.assert_array_equal(a, b)

    best = time_rotated({name: make_driver(eng)
                         for name, eng in engines.items()},
                        after_round=check)
    t_sync, t_pipe = best["sync"][0], best["pipelined"][0]
    return {
        "workload": {"n_requests": n_requests, "seed": seed, "n_slots": 4,
                     "useful_tokens": useful, "policy": "rexp",
                     "vocab": 8192, "temperature": 0.7},
        "backend": jax.default_backend(),
        "sync_s": round(t_sync, 3),
        "sync_tok_s": round(useful / t_sync, 1),
        "pipelined_s": round(t_pipe, 3),
        "pipelined_tok_s": round(useful / t_pipe, 1),
        "speedup": round(t_sync / t_pipe, 3),
        "pipeline_depth": engines["pipelined"].depth,
    }


def main() -> None:
    fast = "--fast" in sys.argv
    n = 12 if fast else 24
    doc = merge_bench_json(JSON_PATH, {
        "closed_loop_async": bench_closed_loop_async(n_requests=n),
        "open_loop": bench_open_loop(n_requests=n),
    })
    print(f"wrote {JSON_PATH}")
    print(json.dumps({"closed_loop_async": doc["closed_loop_async"],
                      "open_loop": doc["open_loop"]}, indent=2))


if __name__ == "__main__":
    main()
