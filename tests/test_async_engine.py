"""Pipelined engine: on-device sampling fused onto the decode / final
prefill-chunk step, one-step-ahead dispatch with speculative EOS
resolution, and the no-full-logits-on-the-hot-path regression gate.

The contract under test: ``PipelinedEngine`` is *token-identical* to
``ServingEngine`` (and hence to lockstep ``generate()``) on every
workload — altered scheduling, fused sampling and late harvests must
all be unobservable in the output stream.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import assert_compile_count
from repro.configs import ARCHS, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.models import build_model
from repro.runtime import (EngineConfig, PagedCacheConfig, PipelinedEngine,
                           Request, Scheduler, ServingEngine)
from repro.runtime.scheduler import PENDING_TOKEN
from repro.runtime.serve_loop import generate, sample_tokens

CACHE = PagedCacheConfig(n_pages=40, page_size=8, max_pages_per_seq=8)
#: usable pages cannot hold the aggregate working set → forced evictions
TIGHT = PagedCacheConfig(n_pages=10, page_size=8, max_pages_per_seq=8)


def _run_cfg(impl="exact"):
    pol = (SoftmaxPolicy(impl=impl, precision="uint8")
           if impl != "exact" else SoftmaxPolicy())
    return RunConfig(dtype="float32", attention_backend="naive",
                     scan_layers=True, softmax_policy=pol)


@pytest.fixture(scope="module")
def small_lm():
    arch = ARCHS["qwen3-32b"].scaled_down(d_model=64, n_heads=4, vocab=128,
                                          n_periods=2)
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _mixed_requests(rng, n=6, vocab=128, temperatures=(0.0, 0.8)):
    return [dict(prompt=rng.integers(0, vocab,
                                     size=int(rng.integers(2, 30))).tolist(),
                 max_new_tokens=int(rng.integers(2, 24)),
                 temperature=float(rng.choice(temperatures)), seed=i)
            for i in range(n)]


def _pair(model, params, run, cfg):
    return (ServingEngine(model, params, run, cfg),
            PipelinedEngine(model, params, run, cfg))


def _assert_same_outputs(out_sync, out_pipe):
    assert set(out_sync) == set(out_pipe)
    for rid in out_sync:
        np.testing.assert_array_equal(out_sync[rid].tokens,
                                      out_pipe[rid].tokens,
                                      err_msg=f"request {rid}")
        assert out_sync[rid].finish_reason == out_pipe[rid].finish_reason


# ---------------------------------------------------------------------------
# Token identity: pipelined == sync == lockstep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["exact", "rexp", "lut2d"])
def test_pipelined_token_identical_to_sync_and_lockstep(small_lm, impl):
    """Acceptance: one-step-ahead dispatch with fused sampling changes
    nothing observable — greedy requests also match lockstep
    ``generate()`` per request."""
    model, params = small_lm
    run = _run_cfg(impl)
    rng = np.random.default_rng(0)
    reqs = _mixed_requests(rng)
    cfg = EngineConfig(n_slots=3, cache=CACHE)
    sync, pipe = _pair(model, params, run, cfg)
    out_s = sync.run([dict(r) for r in reqs])
    out_p = pipe.run([dict(r) for r in reqs])
    _assert_same_outputs(out_s, out_p)
    for i, r in enumerate(reqs):
        if r["temperature"] > 0.0:
            continue  # lockstep uses a different sampling PRNG chain
        ref = np.asarray(generate(
            model, params, jnp.asarray(r["prompt"], jnp.int32)[None], run,
            max_new_tokens=r["max_new_tokens"],
            max_len=CACHE.max_context))[0]
        np.testing.assert_array_equal(out_p[i].tokens, ref,
                                      err_msg=f"request {i} ({impl})")


def test_pipelined_under_eviction_pressure_no_leaks(small_lm):
    """Speculation + eviction: pages freed by a preemption are only
    reused after the in-flight step that still reads them (the pool
    threading orders it), and replayed requests finish identically."""
    model, params = small_lm
    run = _run_cfg("rexp")
    rng = np.random.default_rng(1)
    reqs = [dict(prompt=rng.integers(0, 128, size=l).tolist(),
                 max_new_tokens=m, temperature=t, seed=i)
            for i, (l, m, t) in enumerate(
                [(20, 30, 0.0), (16, 30, 0.9), (12, 20, 0.0), (8, 16, 1.1)])]
    cfg = EngineConfig(n_slots=3, cache=TIGHT)
    sync, pipe = _pair(model, params, run, cfg)
    out_s = sync.run([dict(r) for r in reqs])
    out_p = pipe.run([dict(r) for r in reqs])
    assert pipe.stats.preemptions > 0
    assert pipe.scheduler.allocator.n_free == TIGHT.usable_pages
    _assert_same_outputs(out_s, out_p)


def test_pipelined_speculative_eos_rollback(small_lm):
    """EOS lands one harvest late: tokens speculated past it must be
    rolled back (counted in stats.speculative_wasted), the finish
    reason must say "eos", and no pages may leak."""
    model, params = small_lm
    run = _run_cfg("exact")
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 128, size=6).tolist()
    probe = ServingEngine(model, params, run,
                          EngineConfig(n_slots=2, cache=CACHE)).run(
        [(prompt, 12)])
    eos = int(probe[0].tokens[4])
    stop_at = int(np.argmax(probe[0].tokens == eos)) + 1
    cfg = EngineConfig(n_slots=2, cache=CACHE)
    sync, pipe = _pair(model, params, run, cfg)
    out_s = sync.run([dict(prompt=prompt, max_new_tokens=12, eos_id=eos)])
    out_p = pipe.run([dict(prompt=prompt, max_new_tokens=12, eos_id=eos)])
    _assert_same_outputs(out_s, out_p)
    assert out_p[0].finish_reason == "eos"
    assert len(out_p[0].tokens) == stop_at
    assert out_p[0].tokens[-1] == eos
    assert pipe.stats.speculative_wasted > 0
    assert pipe.scheduler.allocator.n_free == CACHE.usable_pages
    assert not any(PENDING_TOKEN in r.tokens for r in out_p.values())


def test_pipelined_sampled_reproducible(small_lm):
    """temperature > 0 through the fused on-device sampler is still
    deterministic in (seed, position): two pipelined engines agree, and
    they agree with the sync engine's host-side sampler bit for bit."""
    model, params = small_lm
    run = _run_cfg("lut2d")
    rng = np.random.default_rng(3)
    reqs = [dict(prompt=rng.integers(0, 128, size=l).tolist(),
                 max_new_tokens=m, temperature=0.9, seed=s)
            for l, m, s in [(9, 10, 0), (4, 12, 1), (13, 8, 2)]]
    cfg = EngineConfig(n_slots=2, cache=CACHE, prefill_chunk=4)
    out_a = PipelinedEngine(model, params, run, cfg).run(
        [dict(r) for r in reqs])
    out_b = PipelinedEngine(model, params, run, cfg).run(
        [dict(r) for r in reqs])
    out_s = ServingEngine(model, params, run, cfg).run(
        [dict(r) for r in reqs])
    _assert_same_outputs(out_a, out_b)
    _assert_same_outputs(out_s, out_a)


# ---------------------------------------------------------------------------
# The hot path ships tokens, not logits
# ---------------------------------------------------------------------------


def test_pipelined_never_ships_full_logits(small_lm):
    """Regression gate for the tentpole: everything the pipelined engine
    fetches to the host per step is a token vector — ``(n_slots,)`` for
    decode, ``(1,)`` for a final prefill chunk — never ``(B, 1, V)``
    logits."""
    model, params = small_lm
    run = _run_cfg("exact")
    eng = PipelinedEngine(model, params, run,
                          EngineConfig(n_slots=3, cache=CACHE))
    shapes = set()
    orig = eng._push_inflight

    def spy(toks, entries, kind):
        shapes.add((kind, tuple(toks.shape), toks.dtype))
        orig(toks, entries, kind)

    eng._push_inflight = spy
    rng = np.random.default_rng(4)
    eng.run(_mixed_requests(rng, n=5))
    assert shapes  # both kinds actually dispatched
    assert {k for k, _, _ in shapes} == {"decode", "chunk"}
    for kind, shape, dtype in shapes:
        assert dtype == jnp.int32
        assert shape == ((3,) if kind == "decode" else (1,)), \
            f"{kind} step fetched {shape}, not a token vector"


def test_pipelined_steps_pass_no_logits_contract(small_lm):
    """Static companion to the spy test above: hold the traced jaxprs of
    both fused-sampling steps to the analyzer's logits-escape lint — no
    ``(…, V)``-shaped output may leave the jitted program at all, so the
    invariant binds at trace time, not just on the paths a run happens
    to exercise."""
    from repro.analysis import contracts
    model, params = small_lm
    eng = PipelinedEngine(model, params, _run_cfg("exact"),
                          EngineConfig(n_slots=3, cache=CACHE))
    for step in ("decode-sampled", "final-chunk-sampled"):
        spec = contracts.ContractSpec(
            name=f"async/{step}", topology="single", step=step,
            policy="exact", forbid_logits_output=True,
            min_donated=contracts._pool_leaves(eng))
        res = contracts.check_artifacts(
            spec, *contracts._step_artifacts(eng, step),
            vocab=model.cfg.vocab_size)
        assert res.status == "ok", (step, res.violations)


def test_sample_tokens_bitwise_matches_host_sample(small_lm):
    """The fused device sampler and the sync engine's host-side
    ``_sample`` draw from the same (seed, position) key stream: same
    logits row → same token, greedy and sampled rows alike.  The static
    ``greedy=True`` variant must agree wherever both apply."""
    model, params = small_lm
    eng = ServingEngine(model, params, _run_cfg("exact"),
                        EngineConfig(n_slots=1, cache=CACHE))
    rng = np.random.default_rng(5)
    rows = rng.normal(size=(6, 1, 128)).astype(np.float32)
    seeds = np.array([0, 1, 2, 3, 4, 5], np.int32)
    positions = np.array([0, 1, 7, 0, 3, 11], np.int32)
    temps = np.array([0.0, 0.7, 1.0, 0.0, 1.3, 0.5], np.float32)
    dev = np.asarray(sample_tokens(jnp.asarray(rows), jnp.asarray(seeds),
                                   jnp.asarray(positions),
                                   jnp.asarray(temps)))
    for i in range(len(rows)):
        seq = Scheduler(CACHE, 1).add(Request(
            id=0, prompt=(1,), max_new_tokens=20,
            temperature=float(temps[i]), seed=int(seeds[i])))
        seq.generated = [9] * int(positions[i])
        assert eng._sample(seq, rows[i, 0]) == dev[i], f"row {i}"
    zero_t = jnp.zeros_like(jnp.asarray(temps))
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(jnp.asarray(rows), jnp.asarray(seeds),
                                 jnp.asarray(positions), zero_t,
                                 greedy=True)),
        np.asarray(sample_tokens(jnp.asarray(rows), jnp.asarray(seeds),
                                 jnp.asarray(positions), zero_t)))


# ---------------------------------------------------------------------------
# Streaming, depths, stats, compilation
# ---------------------------------------------------------------------------


def test_pipelined_streaming_exactly_once_under_eviction(small_lm):
    """The on_token callback fires exactly once per emitted token, in
    order, even when evictions replay work and EOS rolls back
    speculation — streamed == final result, no duplicates, no
    placeholders."""
    model, params = small_lm
    run = _run_cfg("exact")
    eng = PipelinedEngine(model, params, run,
                          EngineConfig(n_slots=3, cache=TIGHT))
    rng = np.random.default_rng(6)
    reqs = [dict(prompt=rng.integers(0, 128, size=l).tolist(),
                 max_new_tokens=m)
            for l, m in [(20, 30), (16, 30), (12, 20), (8, 16)]]
    streamed = {i: [] for i in range(len(reqs))}
    rids = [eng.add_request(**r, on_token=streamed[i].append)
            for i, r in enumerate(reqs)]
    out = eng.run()
    assert eng.stats.preemptions > 0
    for i, rid in enumerate(rids):
        assert streamed[i] == list(out[rid].tokens), f"request {i}"
        assert PENDING_TOKEN not in streamed[i]


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_pipelined_depth_is_unobservable(small_lm, depth):
    model, params = small_lm
    run = _run_cfg("exact")
    rng = np.random.default_rng(7)
    reqs = _mixed_requests(rng, n=4)
    sync = ServingEngine(model, params, run,
                         EngineConfig(n_slots=2, cache=CACHE))
    pipe = PipelinedEngine(model, params, run,
                           EngineConfig(n_slots=2, cache=CACHE,
                                        pipeline_depth=depth))
    out_s = sync.run([dict(r) for r in reqs])
    out_p = pipe.run([dict(r) for r in reqs])
    assert pipe.depth == depth
    _assert_same_outputs(out_s, out_p)


def test_pipelined_rejects_zero_depth(small_lm):
    model, params = small_lm
    with pytest.raises(ValueError, match="pipeline_depth"):
        PipelinedEngine(model, params, _run_cfg("exact"),
                        EngineConfig(n_slots=2, cache=CACHE,
                                     pipeline_depth=0))


def test_pipelined_stats_and_handle(small_lm):
    """New EngineStats fields are live, and a RequestHandle on the
    pipelined engine self-drives result() through speculative
    harvests."""
    model, params = small_lm
    run = _run_cfg("exact")
    eng = PipelinedEngine(model, params, run,
                          EngineConfig(n_slots=2, cache=CACHE))
    rng = np.random.default_rng(8)
    h = eng.add_request(rng.integers(0, 128, size=9).tolist(), 6)
    assert not h.done
    res = h.result()          # drives step() across dispatch + harvest
    assert h.done and len(res.tokens) == 6
    assert h.ttft_s is not None and h.ttft_s >= 0.0
    assert 1 <= eng.stats.inflight_peak <= eng.depth
    assert not eng.has_work() and not eng._inflight


@pytest.mark.parametrize("engine_cls", [ServingEngine, PipelinedEngine])
def test_decode_kv_block_counters(small_lm, engine_cls):
    """EngineStats counts the decode kernel's blocks from each decode
    view's lengths: slots × blocks a step, and ceil(kv_len / block keys)
    a slot that holds keys — the share of a known set of lengths, by
    hand; over a run, every step adds the whole grid."""
    model, params = small_lm
    # 40-page tables of 8-key pages: blocks of 10 pages (80 keys), 4 a slot
    cache = PagedCacheConfig(n_pages=64, page_size=8, max_pages_per_seq=40)
    eng = engine_cls(model, params, _run_cfg("exact"),
                     EngineConfig(n_slots=4, cache=cache))
    held = {0: 80, 2: 81, 3: 161}     # tokens incl. the one entering
    running = {slot: types.SimpleNamespace(
        pages=list(range(1, -(-n // 8) + 1)), total_tokens=n, generated=[5])
        for slot, n in held.items()}
    for _ in range(2):
        eng._decode_view(running)
    # kv_lens 80, 1 (the empty slot), 81, 161 → 1 + 1 + 2 + 3 of 4 × 4
    assert eng.stats.decode_kv_blocks == 2 * 16
    assert eng.stats.decode_kv_blocks_live == 2 * 7
    assert (eng.stats.decode_kv_blocks_live
            / eng.stats.decode_kv_blocks) == 7 / 16

    fresh = engine_cls(model, params, _run_cfg("exact"),
                       EngineConfig(n_slots=4, cache=cache))
    fresh.run(_mixed_requests(np.random.default_rng(10), n=4))
    assert fresh.stats.decode_kv_blocks == fresh.stats.steps * 16
    assert fresh.stats.steps <= fresh.stats.decode_kv_blocks_live <= (
        fresh.stats.decode_kv_blocks)


def test_pipelined_no_rejit_across_steps(small_lm):
    """One trace per (step kind, greedy flag): an all-greedy run
    compiles exactly one decode and one chunk program; adding sampled
    requests adds at most one more variant of each."""
    model, params = small_lm
    run = _run_cfg("exact")
    eng = PipelinedEngine(model, params, run,
                          EngineConfig(n_slots=2, cache=CACHE))
    rng = np.random.default_rng(9)
    eng.run(_mixed_requests(rng, n=4, temperatures=(0.0,)))
    assert_compile_count(eng._decode_sampled_fn, 1, "greedy decode")
    assert_compile_count(eng._chunk_sampled_fn, 1, "greedy chunk")
    eng.run(_mixed_requests(rng, n=4, temperatures=(0.7,)))
    assert_compile_count(eng._decode_sampled_fn, 2, "sampled decode")
    assert_compile_count(eng._chunk_sampled_fn, 2, "sampled chunk")
