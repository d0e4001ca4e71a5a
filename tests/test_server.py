"""Asyncio serving front-end: streaming, admission control and
failure propagation over the engine driver thread.

No pytest-asyncio dependency: each test owns its loop via
``asyncio.run`` — the server only requires *a* running loop, not a
particular runner.
"""

import asyncio

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.models import build_model
from repro.runtime import (AsyncServingServer, EngineConfig, PagedCacheConfig,
                           PipelinedEngine, ServerSaturatedError,
                           ServingEngine)

CACHE = PagedCacheConfig(n_pages=40, page_size=8, max_pages_per_seq=8)


@pytest.fixture(scope="module")
def small_lm():
    arch = ARCHS["qwen3-32b"].scaled_down(d_model=64, n_heads=4, vocab=128,
                                          n_periods=2)
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _run_cfg():
    return RunConfig(dtype="float32", attention_backend="naive",
                     scan_layers=True,
                     softmax_policy=SoftmaxPolicy(impl="rexp",
                                                  precision="uint8"))


def _engine(small_lm, cls=PipelinedEngine, **over):
    model, params = small_lm
    cfg = EngineConfig(**{"n_slots": 2, "cache": CACHE, **over})
    return cls(model, params, _run_cfg(), cfg)


def test_server_streams_match_sync_engine(small_lm):
    """Concurrent streamed requests yield, token for token and in
    order, exactly what the synchronous engine produces for the same
    request set — the asyncio facade adds no reordering, duplication
    or loss."""
    rng = np.random.default_rng(0)
    reqs = [dict(prompt=rng.integers(0, 128, size=int(l)).tolist(),
                 max_new_tokens=int(m), temperature=t, seed=i)
            for i, (l, m, t) in enumerate(
                [(9, 8, 0.0), (17, 12, 0.9), (4, 6, 0.0),
                 (24, 10, 1.1), (6, 14, 0.0), (12, 9, 0.7)])]
    ref = ServingEngine(*small_lm, _run_cfg(),
                        EngineConfig(n_slots=2, cache=CACHE)).run(
        [dict(r) for r in reqs])

    async def go():
        async with AsyncServingServer(_engine(small_lm)) as srv:
            streams = [await srv.submit(**r) for r in reqs]

            async def consume(stream):
                toks = [tok async for tok in stream]
                res = await stream.result()
                return toks, res

            return await asyncio.gather(*map(consume, streams))

    outs = asyncio.run(go())
    for i, (toks, res) in enumerate(outs):
        np.testing.assert_array_equal(toks, ref[i].tokens,
                                      err_msg=f"request {i} (streamed)")
        np.testing.assert_array_equal(res.tokens, ref[i].tokens,
                                      err_msg=f"request {i} (result)")
        assert res.finish_reason == ref[i].finish_reason
        assert res.ttft_s is not None


def test_server_backpressure_reject(small_lm):
    """max_queue bounds *waiting* requests: with one slot occupied by a
    long request and one waiting, the next submit is shed with
    ServerSaturatedError; the queued work still completes."""
    async def go():
        eng = _engine(small_lm, n_slots=1)
        async with AsyncServingServer(eng, max_queue=1) as srv:
            prompt = list(range(8))
            long = await srv.submit(prompt, 48)   # takes the only slot
            while srv._n_waiting:                 # wait out its credit
                await asyncio.sleep(0.001)
            queued = await srv.submit(prompt, 4)  # waits (queue now full)
            with pytest.raises(ServerSaturatedError):
                await srv.submit(prompt, 4)
            r_long, r_queued = await asyncio.gather(long.result(),
                                                    queued.result())
            assert len(r_long.tokens) == 48 and len(r_queued.tokens) == 4
            # queue drained: admission works again
            retry = await srv.submit(prompt, 3)
            assert len((await retry.result()).tokens) == 3
    asyncio.run(go())


def test_server_backpressure_wait(small_lm):
    """backpressure='wait' parks submit until a waiting request takes a
    slot, instead of shedding it."""
    async def go():
        eng = _engine(small_lm, n_slots=1)
        async with AsyncServingServer(eng, max_queue=1,
                                      backpressure="wait") as srv:
            prompt = list(range(8))
            long = await srv.submit(prompt, 48)
            while srv._n_waiting:                 # wait out its credit
                await asyncio.sleep(0.001)
            queued = await srv.submit(prompt, 4)
            parked = asyncio.ensure_future(srv.submit(prompt, 5))
            await asyncio.sleep(0)          # let it hit the bound
            assert not parked.done(), "submit must block at the bound"
            stream = await asyncio.wait_for(parked, timeout=30)
            results = await asyncio.gather(long.result(), queued.result(),
                                           stream.result())
            assert [len(r.tokens) for r in results] == [48, 4, 5]
    asyncio.run(go())


def test_server_bad_request_fails_its_stream_only(small_lm):
    """An invalid request (prompt exceeds the cache context) fails its
    own stream with the engine's ValueError — and does not poison the
    server or leak its admission credit."""
    async def go():
        async with AsyncServingServer(_engine(small_lm),
                                      max_queue=2) as srv:
            bad = await srv.submit(list(range(CACHE.max_context + 1)), 4)
            with pytest.raises(ValueError):
                await bad.result()
            with pytest.raises(ValueError):
                async for _ in bad:
                    pass
            ok = await srv.submit(list(range(6)), 5)
            assert len((await ok.result()).tokens) == 5
    asyncio.run(go())


def test_server_shutdown_fails_inflight_streams(small_lm):
    """Shutdown mid-generation: awaiting clients get a RuntimeError
    instead of hanging."""
    async def go():
        srv = AsyncServingServer(_engine(small_lm, n_slots=1))
        await srv.start()
        # one slot, three long requests: the last cannot have finished
        # by the time shutdown lands
        streams = [await srv.submit(list(range(8)), 48) for _ in range(3)]
        waiter = asyncio.ensure_future(streams[-1].result())
        await srv.shutdown()
        with pytest.raises(RuntimeError):
            await asyncio.wait_for(waiter, timeout=10)
    asyncio.run(go())


def test_server_lifecycle_and_arg_validation(small_lm):
    eng = _engine(small_lm)
    with pytest.raises(ValueError, match="backpressure"):
        AsyncServingServer(eng, backpressure="drop")
    with pytest.raises(ValueError, match="max_queue"):
        AsyncServingServer(eng, max_queue=0)

    async def go():
        srv = AsyncServingServer(eng)
        with pytest.raises(RuntimeError, match="not started"):
            await srv.submit([1, 2], 2)
        await srv.start()
        with pytest.raises(RuntimeError, match="already started"):
            await srv.start()
        await srv.shutdown()
        await srv.shutdown()   # idempotent
    asyncio.run(go())


def test_request_handle_done_under_concurrent_completion(small_lm):
    """Satellite: a handle whose request was finished by *another*
    driver (a different handle's self-driving result(), here) reports
    done and returns its result without stepping further — and
    streaming callbacks observed exactly the returned tokens."""
    eng = _engine(small_lm)
    rng = np.random.default_rng(1)
    streamed = []
    h_a = eng.add_request(rng.integers(0, 128, size=6).tolist(), 4,
                          on_token=streamed.append)
    h_b = eng.add_request(rng.integers(0, 128, size=20).tolist(), 12)
    res_b = h_b.result()      # drives the engine; finishes a on the way
    assert h_b.done and len(res_b.tokens) == 12
    assert h_a.done, "a finished while b's result() drove the engine"
    steps_before = eng.stats.steps
    res_a = h_a.result()
    assert eng.stats.steps == steps_before, "done handle must not step"
    assert streamed == list(res_a.tokens) and len(res_a.tokens) == 4


#: the driver thread's host spans (runtime/server.py, runtime/engine.py)
HOST_SPANS = ("server.ingress", "engine.plan", "engine.dispatch_chunk",
              "engine.dispatch_decode", "engine.harvest_wait",
              "engine.resolve")


def test_server_host_spans_in_a_profiler_trace(small_lm, tmp_path):
    """Under ``jax.profiler.trace`` the driver thread records its six
    host phases as spans that never nest in one another, with one
    ``engine.dispatch_decode`` per decode step; the benchmark's trace
    reader loads them."""
    from bench import trace as T

    eng = _engine(small_lm)
    rng = np.random.default_rng(2)
    reqs = [dict(prompt=rng.integers(0, 128, size=int(n)).tolist(),
                 max_new_tokens=int(m), temperature=t)
            for n, m, t in [(9, 5, 0.0), (20, 7, 0.8), (5, 4, 0.0)]]

    async def go():
        async with AsyncServingServer(eng) as srv:
            streams = [await srv.submit(**r) for r in reqs]
            for s in streams:
                await s.result()

    asyncio.run(go())          # compiles every program, outside the trace
    steps = eng.stats.steps
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            asyncio.run(go())
    tr = T.load(T.find(str(tmp_path)))
    spans = sorted((e for e in tr.host if e.name in HOST_SPANS),
                   key=lambda e: e.start)
    assert {e.name for e in spans} == set(HOST_SPANS)
    n_decode = sum(e.name == "engine.dispatch_decode" for e in spans)
    assert n_decode == eng.stats.steps - steps > 0
    for a, b in zip(spans, spans[1:]):
        assert a.end <= b.start, f"{a.name} and {b.name} overlap"
