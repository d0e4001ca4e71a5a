"""Core transformer layers — functional style (init_* / apply_*) over
plain dict pytrees, pjit-friendly (no framework deps).

Attention is the paper's integration point: ``apply_attention`` takes a
:class:`SoftmaxPolicy` and routes the softmax through exact / REXP /
2D-LUT semantics, with three execution backends (naive / blocked-XLA /
Pallas).  Training always runs exact softmax; serving selects the policy
per config.

Conventions:
  * params are dicts of jnp arrays; init fns take an rng key + config.
  * compute dtype is ``cfg.dtype`` (bf16 default); accumulation f32.
  * attention logical shapes: q (B, H, L, Dh); kv (B, KVH, L, Dh).
  * KV caches are pre-allocated (B, KVH, max_len, Dh) with a traced
    ``kv_len`` write cursor (decode appends in-place via dynamic update).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.policies import EXACT, SoftmaxPolicy
from repro.kernels.lut_attention.ops import (lut_attention,
                                             lut_attention_paged_decode,
                                             lut_attention_paged_prefill)

Array = jax.Array
Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers / numerics helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis_size: int | None = None,
               dtype=jnp.float32) -> Array:
    """Truncated-normal fan-in init (LeCun-style)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(key, d: int, with_bias: bool = False) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if with_bias:
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: Array, eps: float) -> Array:
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], eps)
    return rms_norm(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x (..., L, Dh); positions (..., L) int32 → rotated x (same dtype)."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (the paper's integration point)
# ---------------------------------------------------------------------------


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qk_norm: bool = False,
                   with_bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads * head_dim)),
        "wk": dense_init(ks[1], (d_model, n_kv_heads * head_dim)),
        "wv": dense_init(ks[2], (d_model, n_kv_heads * head_dim)),
        "wo": dense_init(ks[3], (n_heads * head_dim, d_model),
                         in_axis_size=n_heads * head_dim),
    }
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
    if with_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
        p["bo"] = jnp.zeros((d_model,), jnp.float32)
    return p


@dataclasses.dataclass
class AttnCache:
    """Pre-allocated KV cache; ``length`` is the traced write cursor."""
    k: Array  # (B, KVH, max_len, Dh)
    v: Array
    length: Array  # scalar int32

    @staticmethod
    def zeros(b: int, kvh: int, max_len: int, dh: int, dtype) -> "AttnCache":
        return AttnCache(
            k=jnp.zeros((b, kvh, max_len, dh), dtype),
            v=jnp.zeros((b, kvh, max_len, dh), dtype),
            length=jnp.zeros((), jnp.int32),
        )


jax.tree_util.register_dataclass(AttnCache, ["k", "v", "length"], [])


@dataclasses.dataclass
class PagedAttnCache:
    """Paged KV storage for continuous-batching decode.

    One physical pool of fixed-size pages is shared by every slot in the
    decode batch; a per-slot block table maps logical page index →
    physical page id, and ``lengths`` carries each slot's own write
    cursor (unlike :class:`AttnCache`, whose single scalar forces the
    whole batch into lockstep).

    Physical page 0 is the reserved **null page**: inactive slots map
    every logical page to it, so their (masked-out, garbage) decode
    writes can proceed unconditionally without touching live pages.

    With ``kv_dtype='int8'`` the pools store int8 and ``k_scales`` /
    ``v_scales`` carry the f32 per-(page, token, KV-head) scales
    (``runtime.paged_cache.scale_pool_shape``); ``None`` (the f32 pool)
    keeps the historical 4-field pytree exactly.
    """

    k_pages: Array       # (n_pages, KVH, page_size, Dh)
    v_pages: Array
    block_tables: Array  # (B, max_pages_per_seq) int32 physical page ids
    lengths: Array       # (B,) int32 — tokens already cached per slot
    k_scales: Array | None = None   # (n_pages, 1, KVH·page_size) f32
    v_scales: Array | None = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @staticmethod
    def zeros(n_pages: int, page_size: int, kvh: int, dh: int, b: int,
              max_pages_per_seq: int, dtype) -> "PagedAttnCache":
        from repro.runtime.paged_cache import pool_shape
        shape = pool_shape(n_pages, page_size, kvh, dh)
        return PagedAttnCache(
            k_pages=jnp.zeros(shape, dtype),
            v_pages=jnp.zeros(shape, dtype),
            block_tables=jnp.zeros((b, max_pages_per_seq), jnp.int32),
            lengths=jnp.zeros((b,), jnp.int32),
        )


jax.tree_util.register_dataclass(
    PagedAttnCache, ["k_pages", "v_pages", "block_tables", "lengths",
                     "k_scales", "v_scales"], [])


@dataclasses.dataclass
class PagedPrefillCache:
    """Chunked-prefill view of the paged pool.

    Same storage contract as :class:`PagedAttnCache`, but the entering
    unit is a fixed-size *chunk* of prompt tokens rather than one decode
    token: ``lengths`` is the per-slot count of tokens already cached
    (the chunk's first absolute position) and ``chunk_lens`` how many of
    the chunk's rows are real prompt tokens — the tail past it is
    structural padding whose K/V writes are routed to the null page and
    whose attention rows are discarded by the caller.  One compiled
    program serves every prompt length: only the two cursors are traced.
    """

    k_pages: Array       # (n_pages, KVH, page_size, Dh)
    v_pages: Array
    block_tables: Array  # (B, max_pages_per_seq) int32
    lengths: Array       # (B,) int32 — tokens cached before this chunk
    chunk_lens: Array    # (B,) int32 — valid tokens entering this chunk
    k_scales: Array | None = None   # (n_pages, 1, KVH·page_size) f32
    v_scales: Array | None = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]


jax.tree_util.register_dataclass(
    PagedPrefillCache,
    ["k_pages", "v_pages", "block_tables", "lengths", "chunk_lens",
     "k_scales", "v_scales"], [])


def _paged_mesh(n_kv_heads: int):
    """Active tensor-parallel mesh + paged-dispatch regime.

    The serving engine sets the active mesh around its jitted steps
    (the same context the lockstep sharded decode reads); both paged
    phases consult it so the attention — and, in the page-sharded
    regime, the K/V scatter — run through the shard_map dispatchers.
    Returns ``(None, None)`` for single-device serving.
    """
    from repro.kernels.lut_attention.ops import paged_mesh_regime
    from repro.runtime import partitioning as PT
    mesh = PT.active_mesh()
    regime = paged_mesh_regime(mesh, n_kv_heads)
    return (mesh, regime) if regime is not None else (None, None)


def _paged_prefill_chunk(p: Params, x: Array, cache: PagedPrefillCache, *,
                         n_heads: int, n_kv_heads: int, head_dim: int,
                         qk_norm: bool, norm_eps: float,
                         rope_theta: float | None, policy: SoftmaxPolicy,
                         paged_backend: str, q_chunk: int, k_chunk: int):
    """One prompt chunk against the paged pool — scatter-then-attend.

    The chunk's K/V go straight into the pool pages at positions
    ``[lengths, lengths + chunk_lens)`` through the block table (no
    contiguous per-request cache is ever materialized), then the chunk's
    queries attend to every prior key *through the same block tables*
    via :func:`lut_attention_paged_prefill` — governed by the same
    ``paged_backend`` knob as paged decode (fused Pallas kernel on TPU;
    dense reference elsewhere), NOT by the lockstep attention backend.
    Padding rows (row index ≥ ``chunk_lens``) write to the null page and
    read garbage that the engine discards; per-chunk max-normalization
    inside the attention is exactly the whole-prompt path's, so the LUT
    tables see the ranges they were calibrated for.

    Prefix-cache contract: with copy-on-write page sharing enabled, the
    engine guarantees every page this chunk *writes* (positions
    ``[lengths, lengths + chunk_lens)``) is privately owned by the
    sequence — shared pages appear only strictly before ``lengths``,
    and a divergence landing mid-way into a shared page was already
    re-pointed at a fresh duplicate on the host side before this runs.
    Reads through the block table are placement-oblivious, so this
    function needs no sharing awareness at all.
    """
    b, c, _ = x.shape
    positions = cache.lengths[:, None] + jnp.arange(c, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, qk_norm,
                           norm_eps, rope_theta, positions)
    ps = cache.page_size
    mp = cache.block_tables.shape[1]
    valid = jnp.arange(c)[None, :] < cache.chunk_lens[:, None]   # (B, C)
    page_idx = jnp.clip(positions // ps, 0, mp - 1)
    offs = positions % ps
    phys = jnp.take_along_axis(cache.block_tables, page_idx, axis=1)
    # padding rows (and anything past the block table) land on the null
    # page, which is garbage by definition — the write needs no branch
    phys = jnp.where(valid & (positions // ps < mp), phys, 0)
    from repro.runtime.paged_cache import write_token_scales, write_tokens
    quantized = cache.k_scales is not None
    if quantized:
        # int8 pool: quantize at scatter time, one scale per (token, KV
        # head) row — appending never requants neighbours, so chunking
        # and placement stay semantically invisible (same values as the
        # lockstep fake-quant, bit for bit)
        from repro.core.quantization import quantize_rows
        k_tok, k_sc = quantize_rows(k.transpose(0, 2, 1, 3))  # (B,C,KVH,·)
        v_tok, v_sc = quantize_rows(v.transpose(0, 2, 1, 3))
    else:
        k_tok = k.transpose(0, 2, 1, 3).astype(cache.k_pages.dtype)
        v_tok = v.transpose(0, 2, 1, 3).astype(cache.v_pages.dtype)
        k_sc = v_sc = None
    mesh, regime = _paged_mesh(n_kv_heads)
    k_scales, v_scales = cache.k_scales, cache.v_scales
    if regime == "pages":
        # page-axis-sharded pool: the write must stay slab-local
        from repro.kernels.lut_attention.sharded_paged import (
            scatter_chunk_sharded)
        k_pages, v_pages, k_scales, v_scales = scatter_chunk_sharded(
            cache.k_pages, cache.v_pages, phys, offs, k_tok, v_tok,
            k_scales=k_scales, v_scales=v_scales, k_sc=k_sc, v_sc=v_sc,
            mesh=mesh)
    else:
        k_pages = write_tokens(cache.k_pages, phys, offs, k_tok)
        v_pages = write_tokens(cache.v_pages, phys, offs, v_tok)
        if quantized:
            k_scales = write_token_scales(k_scales, phys, offs, k_sc)
            v_scales = write_token_scales(v_scales, phys, offs, v_sc)

    out = lut_attention_paged_prefill(
        q, k_pages, v_pages, cache.block_tables,
        q_start=cache.lengths, kv_lens=cache.lengths + cache.chunk_lens,
        policy=policy, backend=paged_backend, q_chunk=q_chunk,
        k_chunk=k_chunk, mesh=mesh, k_scales=k_scales, v_scales=v_scales)
    new_cache = PagedPrefillCache(
        k_pages=k_pages, v_pages=v_pages, block_tables=cache.block_tables,
        lengths=cache.lengths + cache.chunk_lens,
        chunk_lens=cache.chunk_lens, k_scales=k_scales, v_scales=v_scales)
    return out, new_cache


def _paged_decode(p: Params, x: Array, cache: PagedAttnCache, *,
                  n_heads: int, n_kv_heads: int, head_dim: int,
                  qk_norm: bool, norm_eps: float, rope_theta: float | None,
                  policy: SoftmaxPolicy, paged_backend: str = "auto"):
    """Single-token decode against the paged pool — no contiguous gather.

    Appends the token's KV at ``lengths`` (per slot), then attends
    straight off the pool through the per-slot block tables via
    :func:`repro.kernels.lut_attention.ops.lut_attention_paged_decode`
    (fused Pallas kernel on TPU; dense block-table reference elsewhere).
    The numerics per valid key are identical to the contiguous-cache
    decode path either way.

    Prefix-cache contract: the page holding position ``lengths`` is
    always privately owned by the slot writing it — the scheduler never
    maps a *shared* page at a sequence's append frontier (decode always
    appends past the prompt, and copy-on-write already duplicated any
    shared last page during admission) — so the scatter below is safe
    without any refcount checks on the device.
    """
    b, l, _ = x.shape
    positions = cache.lengths[:, None]  # (B, 1) absolute positions
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, qk_norm,
                           norm_eps, rope_theta, positions)
    ps = cache.page_size
    page_idx = cache.lengths // ps
    offs = cache.lengths % ps
    phys = jnp.take_along_axis(cache.block_tables, page_idx[:, None],
                               axis=1)[:, 0]               # (B,)
    from repro.runtime.paged_cache import write_token_scales, write_tokens
    quantized = cache.k_scales is not None
    if quantized:
        from repro.core.quantization import quantize_rows
        k_tok, k_sc = quantize_rows(k[:, :, 0])            # (B, KVH, Dh)
        v_tok, v_sc = quantize_rows(v[:, :, 0])
    else:
        k_tok = k[:, :, 0].astype(cache.k_pages.dtype)     # (B, KVH, Dh)
        v_tok = v[:, :, 0].astype(cache.v_pages.dtype)
        k_sc = v_sc = None
    mesh, regime = _paged_mesh(n_kv_heads)
    k_scales, v_scales = cache.k_scales, cache.v_scales
    if regime == "pages":
        # page-axis-sharded pool: the write must stay slab-local
        from repro.kernels.lut_attention.sharded_paged import (
            scatter_chunk_sharded)
        k_pages, v_pages, k_scales, v_scales = scatter_chunk_sharded(
            cache.k_pages, cache.v_pages, phys[:, None], offs[:, None],
            k_tok[:, None], v_tok[:, None],
            k_scales=k_scales, v_scales=v_scales,
            k_sc=None if k_sc is None else k_sc[:, None],
            v_sc=None if v_sc is None else v_sc[:, None], mesh=mesh)
    else:
        # inactive slots all target the null page; duplicate scatter
        # indices there are harmless (the page is garbage by definition)
        k_pages = write_tokens(cache.k_pages, phys, offs, k_tok)
        v_pages = write_tokens(cache.v_pages, phys, offs, v_tok)
        if quantized:
            k_scales = write_token_scales(k_scales, phys, offs, k_sc)
            v_scales = write_token_scales(v_scales, phys, offs, v_sc)

    out = lut_attention_paged_decode(q, k_pages, v_pages,
                                     cache.block_tables,
                                     kv_lens=cache.lengths + 1,
                                     policy=policy, backend=paged_backend,
                                     mesh=mesh, k_scales=k_scales,
                                     v_scales=v_scales)
    new_cache = PagedAttnCache(k_pages=k_pages, v_pages=v_pages,
                               block_tables=cache.block_tables,
                               lengths=cache.lengths + 1,
                               k_scales=k_scales, v_scales=v_scales)
    return out, new_cache


def _project_qkv(p: Params, x: Array, n_heads: int, n_kv_heads: int,
                 head_dim: int, qk_norm: bool, norm_eps: float,
                 rope_theta: float | None, positions: Array):
    b, l, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, l, n_heads, head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, l, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, l, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    if qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"], norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], norm_eps)
    if rope_theta is not None:
        q = apply_rope(q, positions[:, None, :], rope_theta)
        k = apply_rope(k, positions[:, None, :], rope_theta)
    return q, k, v


def apply_attention(
    p: Params, x: Array, *,
    n_heads: int, n_kv_heads: int, head_dim: int,
    causal: bool = True,
    qk_norm: bool = False,
    norm_eps: float = 1e-5,
    rope_theta: float | None = 10000.0,
    policy: SoftmaxPolicy = EXACT,
    backend: str = "naive",          # 'naive' | 'blocked' | 'pallas'
    cache: AttnCache | None = None,
    positions: Array | None = None,
    collector=None,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    kv_x: Array | None = None,       # cross-attention source (enc-dec)
    precomputed_kv: tuple[Array, Array] | None = None,  # cached cross KV
    unroll: bool = False,            # unroll blocked-attention chunk loops
    paged_backend: str = "auto",     # paged attn (decode + prefill chunks):
                                     # 'auto'|'pallas'|'dense'
    kv_dtype: str = "f32",           # lockstep KV storage emulation:
                                     # 'int8' fake-quants K/V entering the
                                     # contiguous cache with the SAME
                                     # rounding the paged int8 pool uses
                                     # (paged caches carry real scales
                                     # instead and ignore this knob)
) -> tuple[Array, AttnCache | None]:
    """Self- or cross-attention with pluggable softmax semantics.

    Modes:
      * no cache            — full-sequence (train / encoder).
      * cache, L == x.len   — prefill: writes KV at [0, L), attends causal.
      * cache, L == 1       — decode: appends one position, attends to
                              cache[:length+1] (traced kv_len).
    """
    b, l, _ = x.shape
    if isinstance(cache, PagedPrefillCache):
        if kv_x is not None or precomputed_kv is not None:
            raise ValueError("paged KV cache supports self-attention only")
        out, new_cache = _paged_prefill_chunk(
            p, x, cache, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, qk_norm=qk_norm, norm_eps=norm_eps,
            rope_theta=rope_theta, policy=policy,
            paged_backend=paged_backend, q_chunk=q_chunk, k_chunk=k_chunk)
        return _out_projection(p, x, out, b, l), new_cache
    if isinstance(cache, PagedAttnCache):
        if l != 1:
            raise ValueError("paged KV cache decodes one token at a time; "
                             "prompts go through chunked paged prefill "
                             "(PagedPrefillCache)")
        if kv_x is not None or precomputed_kv is not None:
            raise ValueError("paged KV cache supports self-attention only")
        out, new_cache = _paged_decode(
            p, x, cache, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, qk_norm=qk_norm, norm_eps=norm_eps,
            rope_theta=rope_theta, policy=policy,
            paged_backend=paged_backend)
        return _out_projection(p, x, out, b, l), new_cache
    if positions is None:
        base = cache.length if cache is not None else 0
        positions = base + jnp.arange(l, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, l))

    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, qk_norm,
                           norm_eps, rope_theta, positions)
    if cache is None and kv_x is None and precomputed_kv is None:
        # full-sequence self-attention (train/encoder): pin TP layout —
        # heads over 'model', or query-seq when heads don't divide
        from repro.runtime import partitioning as PT
        q, k, v = PT.constrain_attn_activations(q, k, v)
    if precomputed_kv is not None:
        # cross-attention against cached encoder KV (computed once at prefill
        # via cross_attention_kv — decode must not recompute them per token)
        k, v = precomputed_kv
    elif kv_x is not None:
        src_pos = jnp.broadcast_to(
            jnp.arange(kv_x.shape[1], dtype=jnp.int32)[None, :],
            (b, kv_x.shape[1]))
        _, k, v = _project_qkv(p, kv_x, n_heads, n_kv_heads, head_dim,
                               qk_norm, norm_eps, rope_theta, src_pos)

    kv_len = None
    new_cache = None
    if cache is not None:
        if kv_dtype == "int8":
            # lockstep view of the engine's int8 pool: the engine reads
            # the current token's K/V back quantized from the pool, so
            # the cache write (which the attention below reads through)
            # snaps K/V onto the identical int8 grid — shared helper,
            # one rounding convention, token-identical streams
            from repro.core.quantization import fake_quant_rows
            k = fake_quant_rows(k).astype(k.dtype)
            v = fake_quant_rows(v).astype(v.dtype)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), cache.length, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), cache.length, axis=2)
        new_len = cache.length + l
        new_cache = AttnCache(k=k_cache, v=v_cache, length=new_len)
        k, v, kv_len = k_cache, v_cache, new_len

    if collector is not None:
        # Σe^x calibration taps the pre-softmax logits (naive recompute on
        # a slice to bound cost).
        from repro.kernels.lut_attention.ref import _logits
        collector.offer(_logits(q[:1, :1].astype(jnp.float32),
                                k[:1, :1].astype(jnp.float32),
                                head_dim ** -0.5, causal))

    is_cross = kv_x is not None or precomputed_kv is not None
    out = None
    if l == 1 and cache is not None:
        # single-token decode against a length-sharded KV cache: compute
        # per-shard partials + tiny psum instead of letting SPMD
        # all-gather the cache (§Perf iteration 7)
        from repro.runtime import partitioning as PT
        mesh = PT.active_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and n_kv_heads % mesh.shape["model"] != 0
                and k.shape[2] % mesh.shape["model"] == 0
                and policy.impl in ("exact", "rexp")):
            from repro.kernels.lut_attention.sharded_decode import (
                lut_decode_sharded)
            b_axes = PT._batch_axes_for(mesh, q.shape[0])
            out = lut_decode_sharded(q, k, v, policy, kv_len=kv_len,
                                     mesh=mesh, batch_axes=b_axes)
    if out is None:
        out = lut_attention(q, k, v, policy, causal=causal and not is_cross,
                            kv_len=kv_len, backend=backend,
                            q_chunk=q_chunk, k_chunk=k_chunk, unroll=unroll)
    return _out_projection(p, x, out, b, l), new_cache


def _out_projection(p: Params, x: Array, out: Array, b: int, l: int) -> Array:
    """(B, H, L, Dh) attention output → (B, L, D) through wo (+bo)."""
    out = out.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, l, -1)
    out = out @ p["wo"].astype(x.dtype)
    if "bo" in p:
        out = out + p["bo"].astype(x.dtype)
    return out


def cross_attention_kv(p: Params, src: Array, *, n_kv_heads: int,
                       head_dim: int) -> tuple[Array, Array]:
    """Project encoder states into cross-attention KV once (cached)."""
    b, l, _ = src.shape
    k = (src @ p["wk"].astype(src.dtype))
    v = (src @ p["wv"].astype(src.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(src.dtype)
        v = v + p["bv"].astype(src.dtype)
    k = k.reshape(b, l, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, l, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    return k, v


# ---------------------------------------------------------------------------
# Feed-forward (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, gated: bool = True) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d_model, d_ff)),
         "w_down": dense_init(ks[1], (d_ff, d_model), in_axis_size=d_ff)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff))
    return p


def apply_mlp(p: Params, x: Array) -> Array:
    up = x @ p["w_up"].astype(x.dtype)
    if "w_gate" in p:
        gate = x @ p["w_gate"].astype(x.dtype)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return h @ p["w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int) -> Params:
    return {"table": jax.random.normal(key, (vocab, d_model),
                                       jnp.float32) * 0.02}


def apply_embedding(p: Params, tokens: Array, dtype) -> Array:
    return jnp.take(p["table"], tokens, axis=0).astype(dtype)


def init_lm_head(key, d_model: int, vocab: int) -> Params:
    return {"w": dense_init(key, (d_model, vocab))}


def apply_lm_head(p: Params, x: Array) -> Array:
    # logits in f32 for a stable loss/top-k
    return (x.astype(jnp.float32) @ p["w"].astype(jnp.float32))
