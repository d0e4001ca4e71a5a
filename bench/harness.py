"""One run of one cell: set up, serve the mix for the window, check.

The window drives the program the way its users do: the asyncio
streaming server (``AsyncServingServer``) over the pipelined engine.
Clients stream tokens; each token is stamped as it arrives, and each
request is timed from its scheduled send, so a stall of the server also
delays the requests queued behind it.  Everything a reader or a metric
needs is gathered in one :class:`Readings`.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import math
import pathlib
import shutil
import sys
import time

import jax
import numpy as np

from bench import cells as C
from bench import reference, traffic, work

COUNTERS = ("steps", "decode_tokens", "prompt_tokens", "prefill_steps",
            "prefix_hit_tokens", "preemptions", "prefills",
            "decode_kv_blocks", "decode_kv_blocks_live")


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""

    req: traffic.Req
    due: float = math.inf            # scheduled send (perf_counter)
    sent: float | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    times: list[float] = dataclasses.field(default_factory=list)
    done: bool = False
    error: str | None = None


@dataclasses.dataclass
class Readings:
    """What a run measured; the per-layer readers take their numbers
    from here."""

    cell: C.Cell
    dims: dict
    seconds: float
    t_open: float
    t_close: float
    records: list[Record]
    counters: dict                   # engine counters over the window
    decode_rows: list[tuple]         # (t, kv_len) per slot and decode step
    chunks: list[tuple]              # (t, q_start, n) per prefill chunk
    trace: object = None             # bench.trace.Trace of the window
    peak: dict | None = None         # bench/peaks.json row of the device

    @property
    def arch(self):
        """The configuration's architecture module, which counts the work
        (``bench.work``)."""
        return self.cell.arch

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close


class CompileClock:
    """Seconds and count of XLA compiles, and how many in a window."""

    def __init__(self):
        self.seconds, self.count, self.in_window = 0.0, 0, 0
        self.window = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1
            self.in_window += int(self.window)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def warm_up(eng, mix: dict, vocab: int, chunk: int) -> None:
    """Compile and run every program shape the window uses: a prompt of
    two chunks and a one-chunk prompt, greedy and sampled, so that both
    final-chunk variants and both decode variants (mixed and all-greedy)
    run once."""
    rng = np.random.default_rng(0)
    long_len = min(chunk + 1, mix["max_context"] - 8)
    eng.run([
        dict(prompt=rng.integers(0, vocab, long_len), max_new_tokens=4,
             temperature=0.0),
        dict(prompt=rng.integers(0, vocab, 8), max_new_tokens=2,
             temperature=mix["temperature"], seed=1),
    ])


def observe(eng, rows: list, chunks: list) -> None:
    """Record, at the scheduler boundary, each prefill chunk (its start
    and length) and each slot's decode length as they are dispatched."""
    sched = eng.scheduler
    on_chunk, on_spec = sched.on_prefill_chunk, sched.on_token_speculative

    def chunk(seq, n):
        chunks.append((time.perf_counter(), seq.prefilled, n))
        return on_chunk(seq, n)

    def spec(seq):
        if seq.prefilled == seq.prompt_len and seq.generated:
            rows.append((time.perf_counter(), seq.total_tokens))
        return on_spec(seq)

    sched.on_prefill_chunk, sched.on_token_speculative = chunk, spec


def span(eng, name: str) -> None:
    """Wrap ``eng.step`` in a host span of the profiler's trace."""
    step = eng.step

    def traced():
        with jax.profiler.TraceAnnotation(name):
            return step()

    eng.step = traced


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


async def _client(server, rec: Record, closed) -> None:
    delay = rec.due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec.sent = time.perf_counter()
    req = rec.req
    try:
        stream = await server.submit(req.prompt, req.max_new,
                                     temperature=req.temperature,
                                     seed=req.seed)
        async for tok in stream:
            rec.times.append(time.perf_counter())
            rec.tokens.append(tok)
        rec.done = True
    except Exception as exc:  # noqa: BLE001 — a failed request is counted
        if not closed():
            rec.error = repr(exc)


async def serve_window(server, plan: traffic.Plan, seconds: float,
                       on_open, on_close) -> tuple[list[Record], float, float]:
    """Send the plan, open the window after the pre-roll, close it after
    ``seconds``.  Returns the records and the window's bounds."""
    mix = plan.mix
    await server.start()
    closed = [False]
    is_closed = lambda: closed[0]  # noqa: E731
    t_open = time.perf_counter() + mix["preroll_s"]
    records: list[Record] = []
    tasks = []
    if mix["kind"] == "open_loop":
        for req in plan.requests:
            rec = Record(req, due=t_open + req.t)
            records.append(rec)
            tasks.append(asyncio.create_task(_client(server, rec, is_closed)))
    else:
        outstanding = asyncio.Semaphore(mix["n_slots"] + mix["waiting"])

        async def one(rec):
            try:
                await _client(server, rec, is_closed)
            finally:
                outstanding.release()

        async def feed():
            for req in plan.requests:
                await outstanding.acquire()
                rec = Record(req, due=time.perf_counter())
                records.append(rec)
                tasks.append(asyncio.create_task(one(rec)))
            raise RuntimeError("the backlog mix ran out of requests")

        feeder = asyncio.create_task(feed())
    await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
    on_open()
    await asyncio.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    on_close()
    closed[0] = True
    if mix["kind"] != "open_loop":
        feeder.cancel()
    try:
        await server.shutdown()
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        if mix["kind"] != "open_loop":
            await asyncio.gather(feeder, return_exceptions=True)
    return records, t_open, t_close


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------


def percentile(xs, q: float) -> float | None:
    return float(np.percentile(xs, q)) if len(xs) else None


def end_to_end(r: Readings) -> dict:
    """TTFT of requests due in the window (from the scheduled send; one
    with no first token by the close counts with its age then), the gaps
    between tokens that end in the window, and tokens delivered."""
    ttft, gaps, n_tok = [], [], 0
    for rec in r.records:
        if r.in_window(rec.due):
            first = rec.times[0] if rec.times else math.inf
            ttft.append(min(first, r.t_close) - rec.due)
        for j, t in enumerate(rec.times):
            if r.in_window(t):
                n_tok += 1
                if j:
                    gaps.append(t - rec.times[j - 1])
    return {
        "ttft_p95_s": (percentile(ttft, 95), "s"),
        "itl_p50_ms": (None if not gaps else 1e3 * percentile(gaps, 50),
                       "ms"),
        "itl_p95_ms": (None if not gaps else 1e3 * percentile(gaps, 95),
                       "ms"),
        "output_tok_s": (n_tok / r.seconds, "tokens/s"),
        "_counts": {"ttft_samples": len(ttft), "gaps": len(gaps),
                    "tokens": n_tok},
    }


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------


def sample(r: Readings, seed: int) -> list[Record]:
    """Greedy requests served in the run, drawn from the seed, with the
    one of the longest context in it; finished ones first."""
    pool = [rec for rec in r.records if rec.req.greedy and rec.tokens
            and rec.error is None]
    if not pool:
        return []
    longest = max(pool, key=lambda x: (len(x.req.prompt) + len(x.tokens),
                                       x.done))
    rest = [x for x in pool if x is not longest]
    rng = np.random.default_rng([seed, 0x5A4D])
    order = rng.permutation(len(rest))
    rest = sorted((rest[i] for i in order), key=lambda x: not x.done)
    return [longest] + rest[:r.cell.mix["check"]["requests"] - 1]


def logit_gaps(cell: C.Cell, seed: int, recs: list[Record],
               control: bool = False
               ) -> tuple[list[np.ndarray], list[np.ndarray] | None]:
    """Per request and served token, the gap by which the token's
    reference logit lies below the reference's best at that position.
    With ``control``, also the gap of the token that the fp8 reference
    ranks first at each of those positions (else ``None``)."""
    seqs, rows, served = [], [], []
    for rec in recs:
        prompt = np.asarray(rec.req.prompt, np.int32)
        toks = np.asarray(rec.tokens, np.int32)
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(seqs[-1])))
        served.append(toks)
    if not recs:
        return [], ([] if control else None)
    refs = reference.forward(cell.arch, cell.cfg, seed, seqs, rows)

    def gaps(picks):
        return [ref.max(-1) - ref[np.arange(len(p)), p]
                for ref, p in zip(refs, picks)]

    if not control:
        return gaps(served), None
    low = reference.forward(cell.arch, cell.cfg, seed, seqs, rows,
                            control=True)
    return gaps(served), gaps([c.argmax(-1) for c in low])


def mean_gap(gaps: list[np.ndarray]) -> float | None:
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return float(flat.mean()) if flat.size else None


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}; "
                         "the benchmark runs only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"{len(devs)} device(s), the cell needs {chips}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peak_bytes(chips: int) -> int | None:
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return max(peaks) if all(p is not None for p in peaks) else None


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, rehearse: bool = False, control: bool = False,
        trace_dir: pathlib.Path | None = None,
        mix_override: dict | None = None) -> dict:
    """Run cell ``name`` once; returns the result object."""
    from bench import program

    cell = C.cell(name)
    if rehearse:
        cell = C.rehearsal(cell)
    if mix_override:
        cell = dataclasses.replace(cell, mix={**cell.mix, **mix_override})
    device = device_info(cell.chips, require_tpu=not rehearse)
    peak = None if rehearse else work.peaks(device["kind"])
    cfg, mix = cell.cfg, cell.mix
    dims = cell.arch.dims(cfg)
    clock = CompileClock()
    if not rehearse and program.backends() != ("pallas", "pallas"):
        raise SystemExit(f"paged attention resolves to {program.backends()}, "
                         "not the compiled kernels")

    tree = program.params(cell.arch, cfg, seed)
    eng = program.engine(cell.arch, cfg, mix, tree)
    del tree
    jax.block_until_ready(eng.params)
    t_w = time.perf_counter()
    warm_up(eng, mix, dims["vocab"], cfg["serving"]["prefill_chunk"])
    jax.block_until_ready(eng.pools)
    t_c = time.perf_counter()
    plan = traffic.plan(mix, seed, seconds, dims["vocab"])
    if plan.docs:
        eng.run([dict(prompt=d, max_new_tokens=1) for d in plan.docs])
    jax.block_until_ready(eng.pools)
    t_ready = time.perf_counter()
    log(f"set-up: weights and engine {t_w - t_start:.3f} s, warm-up "
        f"{t_c - t_w:.3f} s, {len(plan.docs)} resident documents "
        f"{t_ready - t_c:.3f} s; {clock.count} compiles, "
        f"{clock.seconds:.3f} s")

    rows, chunks = [], []
    tdir = None
    if trace:
        observe(eng, rows, chunks)
        span(eng, "engine.step")
        tdir = trace_dir / f"{name}-{seed}"
        shutil.rmtree(tdir, ignore_errors=True)
    server = program.server(eng)
    before, after, window_span = {}, {}, []

    def on_open():
        clock.window = True
        before.update({k: getattr(eng.stats, k) for k in COUNTERS})
        before["waiting"] = len(eng.scheduler.waiting)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, not every call
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
            window_span.append(jax.profiler.TraceAnnotation("bench.window"))
            window_span[0].__enter__()

    def on_close():
        after.update({k: getattr(eng.stats, k) for k in COUNTERS})
        after["waiting"] = len(eng.scheduler.waiting)
        clock.window = False
        if trace:
            window_span[0].__exit__(None, None, None)

    records, t_open, t_close = asyncio.run(
        serve_window(server, plan, seconds, on_open, on_close))
    setup_s = t_open - t_start
    if trace:
        jax.profiler.stop_trace()
    mem = None if rehearse else peak_bytes(cell.chips)
    del server, eng
    gc.collect()

    readings = Readings(cell=cell, dims=dims, seconds=seconds, t_open=t_open,
                        t_close=t_close, records=records,
                        counters={k: after[k] - before[k] for k in COUNTERS},
                        decode_rows=rows, chunks=chunks, peak=peak)
    due = [r for r in records if readings.in_window(r.due)]
    attempted = len(due) if mix["kind"] == "open_loop" else len(
        [r for r in records if r.sent is not None and r.sent < t_close])
    failed = sum(r.error is not None for r in records)
    late = [r.sent - r.due for r in due if r.sent is not None]
    log(f"window: {seconds} s; requests attempted {attempted}, completed "
        f"{sum(r.done for r in records if r.sent is not None)}, failed "
        f"{failed}; generator lateness p99 "
        f"{percentile(late, 99) if late else 0.0:.6f} s; compiles in window "
        f"{clock.in_window}; engine counters {readings.counters}")
    log(f"compile: {clock.count} programs, {clock.seconds:.3f} s; peak device "
        f"bytes {mem}; requests waiting for a slot at the window's opening "
        f"{before['waiting']} and close {after['waiting']}")

    # correctness, after the program's state is freed.  The number
    # compared is the mean gap over the served tokens: the widest gap is
    # set by the rarest LUT-bin flip and does not tell the program from
    # the fp8 control (PERF.md, Findings).  With ``control`` the control
    # stands in the program's place, and ``correct`` is its verdict.
    t_ref = time.perf_counter()
    picked = sample(readings, seed)
    gaps, ctrl = logit_gaps(cell, seed, picked, control)
    compared = ctrl if control else gaps
    mean = mean_gap(compared)
    limit = cell.check["logit_gap_mean_limit"]
    checks = {"logit_gap_mean": {"value": mean, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = mean is not None and mean <= limit and failed == 0
    widest = max((float(g.max()) for g in compared if g.size), default=None)
    log(f"check: {len(picked)} greedy requests, "
        f"{sum(g.size for g in gaps)} served tokens, longest context "
        f"{len(picked[0].req.prompt) + len(picked[0].tokens) if picked else 0}"
        f"; widest gap {widest}; reference {time.perf_counter() - t_ref:.3f} s")
    if control:
        log(f"control: the fp8 reference in the program's place; the "
            f"program's own mean gap {mean_gap(gaps)}")

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        from bench import trace as T
        readings.trace = T.load(T.find(str(tdir)))
        tr = readings.trace
        metrics = {}
        for m in cell.per_layer:
            v = C.reader(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = T.busy_s(tr)
        device.update(busy_s=busy, window_s=tr.window_s)
        result["breakdown"] = {"device_ops": T.top_ops(tr),
                               "idle_gaps": T.idle_gaps(tr)}
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        e2e = end_to_end(readings)
        log(f"samples: {e2e['_counts']}")
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] in e2e:
                v, unit = e2e[m["name"]]
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": unit}
    if rehearse:
        metrics = {}
    device["memory_peak_bytes"] = mem
    result.update(metrics=metrics, device=device,
                  waiting=[before["waiting"], after["waiting"]])
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    if control:
        result["control"] = {"program_mean_gap": mean_gap(gaps),
                             "control_mean_gap": mean}
    result["checks"] = checks
    return result
