"""Paged-KV building blocks: allocator alloc/free/OOM, block-table
gather correctness, paged decode vs contiguous decode numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import SoftmaxPolicy
from repro.kernels.lut_attention.ops import (gather_pages, lut_attention,
                                             lut_attention_decode_varlen)
from repro.models import layers as L
from repro.runtime.paged_cache import (NULL_PAGE, OutOfPagesError,
                                       PageAllocator, PagedCacheConfig,
                                       block_table_row)


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_allocator_never_hands_out_null_page():
    a = PageAllocator(8)
    pages = a.alloc(7)
    assert NULL_PAGE not in pages
    assert sorted(pages) == list(range(1, 8))


def test_allocator_oom_is_all_or_nothing():
    a = PageAllocator(8)
    a.alloc(5)
    with pytest.raises(OutOfPagesError):
        a.alloc(3)  # only 2 free
    assert a.n_free == 2  # nothing was taken by the failed alloc
    a.alloc(2)
    assert a.n_free == 0


def test_allocator_free_and_reuse_fifo():
    a = PageAllocator(6)
    first = a.alloc(3)
    a.free(first)
    again = a.alloc(5)
    # FIFO: the pages freed first come back last
    assert again == [4, 5] + first


def test_allocator_double_free_and_foreign_page_raise():
    a = PageAllocator(8)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)  # double free
    with pytest.raises(ValueError):
        a.free([NULL_PAGE])
    with pytest.raises(ValueError):
        PageAllocator(1)  # no room for the null page


def test_cache_config_accounting():
    cfg = PagedCacheConfig(n_pages=10, page_size=16, max_pages_per_seq=4)
    assert cfg.max_context == 64
    assert cfg.usable_pages == 9
    assert cfg.pages_for(1) == 1
    assert cfg.pages_for(16) == 1
    assert cfg.pages_for(17) == 2


def test_block_table_row_pads_with_null():
    row = block_table_row([3, 7], 4)
    assert row.tolist() == [3, 7, NULL_PAGE, NULL_PAGE]
    with pytest.raises(ValueError):
        block_table_row([1, 2, 3], 2)


# ---------------------------------------------------------------------------
# Block-table gather
# ---------------------------------------------------------------------------


def test_gather_pages_reassembles_logical_order(rng):
    n_pages, ps, kvh, dh = 9, 4, 2, 8
    pool = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh))
                       .astype(np.float32))
    # two slots with interleaved, out-of-order physical pages
    bt = jnp.asarray(np.array([[5, 2, 8], [1, 7, NULL_PAGE]], np.int32))
    out = gather_pages(pool, bt)
    assert out.shape == (2, kvh, 3 * ps, dh)
    np_pool = np.asarray(pool)
    for b in range(2):
        for j, pg in enumerate(np.asarray(bt)[b]):
            got = np.asarray(out)[b, :, j * ps:(j + 1) * ps]
            want = np_pool[pg]                     # (KVH, ps, dh)
            np.testing.assert_array_equal(got, want)


def test_paged_decode_matches_contiguous_decode(rng):
    """The gather-from-block-table step must reproduce AttnCache decode
    bit-for-bit when both caches hold the same tokens."""
    b, h, kvh, dh, ps, mp = 3, 4, 2, 16, 4, 4
    max_len = mp * ps
    prompt_lens = np.array([5, 11, 9], np.int32)
    p = L.init_attention(jax.random.PRNGKey(0), h * dh, h, kvh, dh)
    hist = rng.normal(size=(b, max_len, h * dh)).astype(np.float32)
    x_tok = jnp.asarray(rng.normal(size=(b, 1, h * dh)).astype(np.float32))

    for impl in (SoftmaxPolicy(),
                 SoftmaxPolicy(impl="rexp", precision="uint8")):
        # contiguous reference, one sequence at a time (scalar cursor)
        refs = []
        for i in range(b):
            cache = L.AttnCache.zeros(1, kvh, max_len, dh, jnp.float32)
            _, cache = L.apply_attention(
                p, jnp.asarray(hist[i:i + 1, :prompt_lens[i]]), n_heads=h,
                n_kv_heads=kvh, head_dim=dh, policy=impl, cache=cache)
            out, _ = L.apply_attention(
                p, x_tok[i:i + 1], n_heads=h, n_kv_heads=kvh, head_dim=dh,
                policy=impl, cache=cache)
            refs.append(np.asarray(out))

        # paged: same tokens via prefill-into-pages, mixed lengths batched
        paged = L.PagedAttnCache.zeros(2 + b * mp, ps, kvh, dh, b, mp,
                                       jnp.float32)
        k_pages, v_pages = paged.k_pages, paged.v_pages
        bts = np.zeros((b, mp), np.int32)
        for i in range(b):
            pages = [1 + i * mp + j for j in range(mp)]
            bts[i] = pages
            cache = L.AttnCache.zeros(1, kvh, max_len, dh, jnp.float32)
            _, cache = L.apply_attention(
                p, jnp.asarray(hist[i:i + 1, :prompt_lens[i]]), n_heads=h,
                n_kv_heads=kvh, head_dim=dh, policy=impl, cache=cache)
            chunk = lambda a: a[0].reshape(kvh, mp, ps, dh).transpose(
                1, 0, 2, 3)
            k_pages = k_pages.at[jnp.asarray(pages)].set(chunk(cache.k))
            v_pages = v_pages.at[jnp.asarray(pages)].set(chunk(cache.v))
        paged = L.PagedAttnCache(k_pages=k_pages, v_pages=v_pages,
                                 block_tables=jnp.asarray(bts),
                                 lengths=jnp.asarray(prompt_lens))
        out, new_cache = L.apply_attention(
            p, x_tok, n_heads=h, n_kv_heads=kvh, head_dim=dh, policy=impl,
            cache=paged)
        for i in range(b):
            np.testing.assert_array_equal(np.asarray(out)[i], refs[i][0])
        np.testing.assert_array_equal(np.asarray(new_cache.lengths),
                                      prompt_lens + 1)


def test_varlen_decode_matches_scalar_kv_len(rng):
    """Per-row masking degenerates to the lockstep kv_len path when every
    row has the same length."""
    b, h, kvh, lk, dh = 2, 4, 4, 24, 8
    q = jnp.asarray(rng.normal(size=(b, h, 1, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, kvh, lk, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, kvh, lk, dh)).astype(np.float32))
    for impl in (SoftmaxPolicy(),
                 SoftmaxPolicy(impl="rexp", precision="uint8"),
                 SoftmaxPolicy(impl="lut2d", precision="uint8")):
        ref = lut_attention(q, k, v, impl, causal=True, kv_len=jnp.int32(17))
        out = lut_attention_decode_varlen(
            q, k, v, impl, kv_lens=jnp.full((b,), 17, jnp.int32))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_varlen_decode_ignores_junk_past_length(rng):
    """Keys past kv_lens must not influence the output at all."""
    b, h, kvh, lk, dh = 2, 2, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(b, h, 1, dh)).astype(np.float32))
    k = rng.normal(size=(b, kvh, lk, dh)).astype(np.float32)
    v = rng.normal(size=(b, kvh, lk, dh)).astype(np.float32)
    lens = jnp.asarray([5, 12], jnp.int32)
    pol = SoftmaxPolicy(impl="rexp", precision="uint8")
    ref = lut_attention_decode_varlen(q, jnp.asarray(k), jnp.asarray(v),
                                      pol, kv_lens=lens)
    k2, v2 = k.copy(), v.copy()
    k2[0, :, 5:] = 1e6
    v2[0, :, 5:] = -1e6
    k2[1, :, 12:] = np.pi
    out = lut_attention_decode_varlen(q, jnp.asarray(k2), jnp.asarray(v2),
                                      pol, kv_lens=lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
