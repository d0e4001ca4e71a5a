"""Chunked paged prefill: kernel-level masking/alignment, engine
token-identity across prompt-length ⟂ chunk-size alignments under every
softmax policy, and the one-compile-serves-all-lengths guarantee.

The engine acceptance bar is bitwise: chunked prefill must produce the
same first token (and thus the same greedy continuation) as lockstep
``generate()``, whose prefill walks the whole prompt in one pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import assert_compile_count
from repro.configs import ARCHS, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.kernels.lut_attention.ops import (lut_attention,
                                             lut_attention_blocked,
                                             lut_attention_paged_prefill,
                                             lut_attention_prefill_varlen)
from repro.models import build_model
from repro.runtime import EngineConfig, PagedCacheConfig, ServingEngine
from repro.runtime.serve_loop import generate

CACHE = PagedCacheConfig(n_pages=40, page_size=8, max_pages_per_seq=8)
CHUNK = 8

POLICIES = {
    "exact": SoftmaxPolicy(),
    "rexp": SoftmaxPolicy(impl="rexp", precision="uint8"),
    "lut2d": SoftmaxPolicy(impl="lut2d", precision="uint8"),
}


def _qkv(rng, b, h, kvh, lq, lk, d):
    """Integer-valued inputs: block dot products exact in f32, so LUT
    bin indices match across paths (see tests/test_kernels.py)."""
    def gen(s):
        return jnp.asarray(np.round(rng.normal(0, 2, s)).astype(np.float32))
    return gen((b, h, lq, d)), gen((b, kvh, lk, d)), gen((b, kvh, lk, d))


def _run_cfg(impl="exact"):
    return RunConfig(dtype="float32", attention_backend="naive",
                     scan_layers=True, softmax_policy=POLICIES[impl]
                     if impl != "exact" else SoftmaxPolicy())


@pytest.fixture(scope="module")
def small_lm():
    arch = ARCHS["qwen3-32b"].scaled_down(d_model=64, n_heads=4, vocab=128,
                                          n_periods=2)
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ---------------------------------------------------------------------------
# Kernel level: blocked masking + chunk alignment
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["exact", "rexp", "lut2d"])
def test_blocked_structural_padding_masked_causal_no_kv_len(rng, impl):
    """Regression (this used to hinge on reading ``lk`` before its
    reassignment): causal attention, Lk NOT a multiple of k_chunk,
    kv_len=None — the structural K padding must stay invisible.  The
    reference is the same blocked program with the chunk sizes covering
    the whole sequence (no padding), so the comparison isolates the
    masking and not the fused-requant form."""
    pol = POLICIES[impl]
    q, k, v = _qkv(np.random.default_rng(3), 2, 4, 2, 10, 70, 16)
    padded = lut_attention_blocked(q, k, v, pol, causal=True,
                                   q_chunk=4, k_chunk=32)
    ref = lut_attention_blocked(q, k, v, pol, causal=True,
                                q_chunk=16, k_chunk=128)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if impl == "exact":  # the oracle agrees too (same semantics)
        naive = lut_attention(q, k, v, pol, causal=True, backend="naive")
        np.testing.assert_allclose(np.asarray(padded), np.asarray(naive),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["exact", "rexp", "lut2d"])
def test_blocked_per_row_q_start_matches_per_row_scalar_calls(rng, impl):
    """A batched chunk with per-row (q_start, kv_len) must equal each
    row computed alone with scalar cursors — the chunked-prefill batch
    never mixes rows."""
    pol = POLICIES[impl]
    b, c, lk = 3, 6, 64
    q, k, v = _qkv(np.random.default_rng(4), b, 4, 2, c, lk, 16)
    starts = jnp.asarray([0, 13, 37], jnp.int32)
    kv_lens = starts + c
    batched = lut_attention_blocked(q, k, v, pol, causal=True,
                                    kv_len=kv_lens, q_start=starts,
                                    q_chunk=4, k_chunk=32)
    for i in range(b):
        row = lut_attention_blocked(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], pol, causal=True,
            kv_len=jnp.int32(int(kv_lens[i])),
            q_start=jnp.int32(int(starts[i])), q_chunk=4, k_chunk=32)
        np.testing.assert_array_equal(np.asarray(batched)[i],
                                      np.asarray(row)[0])


@pytest.mark.parametrize("impl", ["exact", "rexp", "lut2d"])
def test_prefill_varlen_chunks_reassemble_whole_prompt(rng, impl):
    """Walking a prompt in chunks through the varlen oracle reproduces
    the whole-prompt causal attention row-for-row — the per-chunk
    max-normalization sees exactly the keys the full pass sees."""
    pol = POLICIES[impl]
    b, lq, d = 1, 21, 16
    q, k, v = _qkv(np.random.default_rng(5), b, 4, 2, lq, lq, d)
    # the reference is the lockstep prefill semantics: naive dispatch
    # with a kv_len (the cache path), i.e. per-element σ requant
    whole = lut_attention(q, k, v, pol, causal=True, backend="naive",
                          kv_len=jnp.int32(lq))
    chunk = 8
    rows = []
    for start in range(0, lq, chunk):
        n = min(chunk, lq - start)
        out = lut_attention_prefill_varlen(
            q[:, :, start:start + n], k, v, pol,
            q_start=jnp.asarray([start], jnp.int32),
            kv_lens=jnp.asarray([start + n], jnp.int32))
        rows.append(np.asarray(out))
    np.testing.assert_array_equal(np.concatenate(rows, axis=2),
                                  np.asarray(whole))


def test_paged_prefill_reads_prior_keys_through_block_tables(rng):
    """lut_attention_paged_prefill gathers the pool through an
    arbitrary (permuted) block table and must match attention over the
    logically ordered K/V."""
    pol = POLICIES["rexp"]
    ps, mp, kvh, d = 4, 4, 2, 16
    rng_ = np.random.default_rng(6)
    kv_len, c = 11, 5                      # 6 prior + 5 chunk keys
    q, k_log, v_log = _qkv(rng_, 1, 4, kvh, c, mp * ps, d)
    pages = [3, 1, 4, 2]                   # scrambled physical placement
    pool_k = np.zeros((6, kvh, ps, d), np.float32)
    pool_v = np.zeros((6, kvh, ps, d), np.float32)
    for j, pg in enumerate(pages):
        pool_k[pg] = np.asarray(k_log)[0, :, j * ps:(j + 1) * ps]
        pool_v[pg] = np.asarray(v_log)[0, :, j * ps:(j + 1) * ps]
    out = lut_attention_paged_prefill(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray([pages], jnp.int32),
        q_start=jnp.asarray([kv_len - c], jnp.int32),
        kv_lens=jnp.asarray([kv_len], jnp.int32), policy=pol)
    ref = lut_attention_prefill_varlen(
        q, k_log, v_log, pol, q_start=jnp.asarray([kv_len - c], jnp.int32),
        kv_lens=jnp.asarray([kv_len], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# Engine level: token identity + single compile
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["exact", "rexp", "lut2d"])
def test_engine_chunked_prefill_token_identical_across_alignments(
        small_lm, impl):
    """Acceptance: prompt lengths that are (a) chunk multiples, (b)
    chunk+1, (c) shorter than one chunk all decode token-identically to
    lockstep ``generate()`` under every softmax policy."""
    model, params = small_lm
    run = _run_cfg(impl)
    rng = np.random.default_rng(7)
    plens = [CHUNK, 2 * CHUNK, CHUNK + 1, 2 * CHUNK + 1, CHUNK - 3, 1]
    reqs = [(rng.integers(0, 128, size=pl).tolist(), 6) for pl in plens]
    eng = ServingEngine(model, params, run,
                        EngineConfig(n_slots=3, cache=CACHE,
                                     prefill_chunk=CHUNK))
    out = eng.run(reqs)
    for i, (prompt, m) in enumerate(reqs):
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt, jnp.int32)[None], run,
            max_new_tokens=m, max_len=CACHE.max_context))[0]
        np.testing.assert_array_equal(
            out[i].tokens, ref,
            err_msg=f"prompt_len={plens[i]} chunk={CHUNK} ({impl})")


def test_engine_one_prefill_compile_serves_all_lengths(small_lm):
    """The jit-retrace counter: every prompt-length alignment above runs
    through ONE compiled chunk program (the old path retraced per
    distinct length)."""
    model, params = small_lm
    run = _run_cfg("exact")
    rng = np.random.default_rng(8)
    plens = [1, CHUNK - 3, CHUNK, CHUNK + 1, 2 * CHUNK, 2 * CHUNK + 5]
    eng = ServingEngine(model, params, run,
                        EngineConfig(n_slots=3, cache=CACHE,
                                     prefill_chunk=CHUNK))
    eng.run([(rng.integers(0, 128, size=pl).tolist(), 2) for pl in plens])
    assert_compile_count(eng._chunk_fn, 1, f"prefill chunk over {plens}")
    assert_compile_count(eng._decode_fn, 1, "decode")


def test_engine_prefill_interleaves_with_decode(small_lm):
    """Mixed batching: while a long prompt prefills chunk by chunk, the
    already-running slot keeps producing tokens — a short request that
    joined first finishes BEFORE the long prompt emits its first token
    (the old whole-prompt path stalled it)."""
    model, params = small_lm
    run = _run_cfg("exact")
    rng = np.random.default_rng(9)
    long_prompt = rng.integers(0, 128, size=40).tolist()
    short_prompt = rng.integers(0, 128, size=3).tolist()
    eng = ServingEngine(model, params, run, EngineConfig(
        n_slots=2, prefill_chunk=4,
        cache=PagedCacheConfig(n_pages=40, page_size=8,
                               max_pages_per_seq=8)))
    short = eng.add_request(short_prompt, 4)
    done_at: dict[int, int] = {}
    n_steps = 0
    long_ = eng.add_request(long_prompt, 2)
    while eng.scheduler.has_work():
        n_steps += 1
        for res in eng.step():
            done_at[res.request_id] = n_steps
    assert done_at[short] < done_at[long_], (
        f"short finished at step {done_at[short]}, long at "
        f"{done_at[long_]} — decode stalled behind the long prefill")
    # the long prompt took ceil(40/4) = 10 chunk steps; the short one 1
    assert eng.stats.prefill_steps >= 11
