"""Public attention-with-LUT-softmax API.

Three execution paths, one semantics:

* ``pallas``  — the fused VMEM-blocked kernels (interpret mode off-TPU).
* ``blocked`` — pure-XLA flash-style scan over K chunks (and lax.map over
  Q chunks).  O(chunk) memory; this is the production serving path the
  multi-pod dry-run lowers, and it supports a *traced* ``kv_len`` for
  decode against a pre-allocated KV cache.
* ``naive``   — materialized logits (the oracle).  Used by small models,
  tests, and the roofline probes (XLA's cost_analysis counts loop bodies
  once, so probes must avoid scans — see EXPERIMENTS.md §Methodology).

The continuous-batching serving engine attends through the two *paged*
dispatchers — :func:`lut_attention_paged_decode` (single-token decode)
and :func:`lut_attention_paged_prefill` (prompt chunks; the chunk's K/V
are already in the pool, prior keys are read through the same block
tables, one compiled program for every prompt length).  Both follow ONE
dispatch matrix (the single source of truth — README and
``kernels/__init__`` restate it, and ``tests/test_paged_prefill_kernel``
asserts the three stay in sync):

    knob (``paged_backend``)   TPU                   CPU / GPU
    ``auto``                   fused Pallas kernel   dense reference
    ``pallas``                 fused Pallas kernel   kernel, interpret mode
    ``dense``                  dense reference       dense reference

    and two ``mesh`` rows (tensor-parallel serving; any knob + a mesh
    whose 'model' axis has tp > 1 — :func:`paged_mesh_regime` picks the
    regime, and the knob's single-device paths are bypassed):

    ``mesh``, KVH % tp == 0    'heads' regime: shard_map, KV-head-sharded
                               pool, local dense compute per head group,
                               no attention collectives
    ``mesh``, KVH % tp != 0    'pages' regime: page-axis-sharded pool,
                               per-slab (m, Σ, σ·V) partials reduced with
                               pmax + integer-Σ psum — only (B, H, 1)
                               partials on the wire, never gathered KV

    and two quantized rows (``kv_dtype=int8``; the engine passes the
    pool's f32 per-token × KV-head scale arrays alongside the pages —
    every path dequantizes under ``dequant_scope``, the LUT integer-Σ
    pipeline itself is untouched):

    ``int8`` + fused kernel    int8 variant of the same 3-pass kernel:
                               scale blocks stream beside their pages,
                               dequant in VMEM (`kernel_spec_int8`)
    ``int8`` + dense / mesh    gathered view dequantized before the
                               dense reference; under a mesh the scales
                               shard with their pages in both regimes

The fused kernels (``paged_decode.py`` / ``paged_prefill.py``) stream
K/V pages straight from the pool through scalar-prefetched block tables
— no contiguous gather; their scalar-prefetch grid spec is
Mosaic/TPU-only, so ``auto`` on GPU serves through the dense reference
until a Mosaic-GPU port lands.  The dense reference
(gather-from-block-table, materialized logits) runs identically
everywhere and is the CI parity oracle.  ``pallas`` is never a silent
stand-in: off-TPU it runs the real kernel under the interpreter.  All
paths share one integer LUT pipeline and produce the same tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import lut_builder
from repro.core.lut_softmax import inv_scale
from repro.core.policies import SoftmaxPolicy
from repro.core import lut_softmax as _core
from repro.kernels.common import ATTN_PRECISION, dequant_scope, kernel_lookup
from repro.kernels.lut_attention import ref as _ref
from repro.kernels.lut_attention.lut_attention import lut_attention_pallas
from repro.kernels.lut_attention.paged_decode import paged_decode_attention
from repro.kernels.lut_attention.paged_prefill import paged_prefill_attention

Array = jax.Array


def _tables_for(policy: SoftmaxPolicy):
    if policy.impl == "rexp":
        return lut_builder.build_rexp_tables(policy.precision,
                                             policy.alpha_len)
    if policy.impl == "lut2d":
        return lut_builder.build_lut2d_tables(policy.precision)
    return None


# ---------------------------------------------------------------------------
# Blocked XLA path (flash-style scans; supports traced kv_len)
# ---------------------------------------------------------------------------


def _chunk_mask(q0: Array | int, k0: Array | int, bq: int, bk: int,
                causal: bool, lq: int, lk_eff: Array | int,
                q_start: Array | int | None = None):
    """Visibility mask for a (q-chunk, k-chunk) tile.

    ``lk_eff`` (valid key count) and ``q_start`` (absolute position of
    query row 0) may be scalars or per-row ``(B,)`` arrays — the chunked
    paged-prefill path masks per slot.  Returns a mask broadcastable
    against the ``(B, KVH, G, bq, bk)`` logits tile: ``(bq, bk)`` gains
    a leading batch axis only when a per-row argument is given.

    When ``q_start`` is None the causal alignment assumes the queries
    are the *last* ``lq`` positions of the valid keys (the lockstep
    decode/prefill convention ``q_start = lk_eff - lq``).
    """
    def _b(x):  # scalar → broadcast as-is; (B,) → (B, 1, 1, 1, 1)
        x = jnp.asarray(x)
        return x.reshape(-1, 1, 1, 1, 1) if x.ndim == 1 else x
    ki = (k0 + jnp.arange(bk))[None, :]          # (1, bk)
    mask = ki < _b(lk_eff)
    if causal:
        if q_start is None:
            q_start = jnp.asarray(lk_eff) - lq
        qi = (q0 + jnp.arange(bq))[:, None] + _b(q_start)
        mask = mask & (ki <= qi)
    return mask


def _grouped_logits(qc: Array, kc: Array, scale: float) -> Array:
    """q (B,KVH,G,bq,D) × k (B,KVH,bk,D) → (B,KVH,G,bq,bk) f32."""
    return jnp.einsum("bngqd,bnkd->bngqk", qc.astype(jnp.float32),
                      kc.astype(jnp.float32),
                      precision=ATTN_PRECISION) * scale


def lut_attention_blocked(
    q: Array, k: Array, v: Array, policy: SoftmaxPolicy, *,
    causal: bool = False,
    scale: float | None = None,
    kv_len: Array | int | None = None,
    q_start: Array | int | None = None,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    unroll: bool = False,
) -> Array:
    """Flash-style LUT attention in pure XLA (fused-requant semantics).

    ``unroll=True`` unrolls the chunk loops (roofline probes: XLA's
    cost_analysis counts a while body once, so the probe program must be
    loop-free to account every tile — EXPERIMENTS.md §Methodology).

    q (B,H,Lq,D); k,v (B,KVH,Lk,D).  ``kv_len`` (traced ok; scalar or
    per-row (B,)) masks the tail of a pre-allocated KV cache.
    ``q_start`` (scalar or (B,)) pins the absolute position of query
    row 0 for the causal mask — chunked paged prefill places a chunk's
    queries *inside* the valid keys rather than at their tail (the
    default ``kv_len − Lq`` alignment).  Never materializes more than a
    (q_chunk × k_chunk) logits tile per (batch, head).
    """
    b, h, lq, d = q.shape
    kvh, lk = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    tables = _tables_for(policy)
    exact = policy.impl == "exact"

    bq = min(q_chunk, lq)
    bk = min(k_chunk, lk)
    # pad to chunk multiples; padded Q rows compute junk that is sliced
    # off at the end.
    lq_orig, lk_orig = lq, lk
    lq_p = -(-lq // bq) * bq
    lk_p = -(-lk // bk) * bk
    if lq_p != lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_p - lq), (0, 0)))
    if lk_p != lk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_p - lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_p - lk), (0, 0)))
    # the valid-key count NEVER includes structural K padding: without a
    # kv_len it is the *pre-pad* Lk (this used to rely on reading ``lk``
    # before its reassignment — now explicit), and a caller kv_len is
    # trusted to be ≤ Lk.
    lk_eff = lk_orig if kv_len is None else kv_len
    lq, lk = lq_p, lk_p
    nq, nk = lq // bq, lk // bk

    qg = q.reshape(b, kvh, g, lq, d)
    # chunk axis leading for lax.scan
    kr = jnp.moveaxis(k.reshape(b, kvh, nk, bk, d), 2, 0)
    vr = jnp.moveaxis(v.reshape(b, kvh, nk, bk, d), 2, 0)

    if exact:
        def one_q_chunk(qi):
            qc = jax.lax.dynamic_slice_in_dim(qg, qi * bq, bq, axis=3)

            def step(carry, xs):
                m, l, acc = carry
                kc, vc, ki = xs
                s = _grouped_logits(qc, kc, scale)
                mask = _chunk_mask(qi * bq, ki * bk, bq, bk, causal,
                                   lq_orig, lk_eff, q_start)
                s = jnp.where(mask, s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]),
                              0.0)
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                l = l * corr + jnp.sum(p, axis=-1)
                acc = (acc * corr[..., None]
                       + jnp.einsum("bngqk,bnkd->bngqd", p,
                                    vc.astype(jnp.float32),
                                    precision=ATTN_PRECISION))
                return (m_new, l, acc), None

            m0 = jnp.full((b, kvh, g, bq), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
            a0 = jnp.zeros((b, kvh, g, bq, d), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                step, (m0, l0, a0), (kr, vr, jnp.arange(nk)),
                unroll=nk if unroll else 1)
            return acc / jnp.maximum(l, 1e-30)[..., None]
    else:
        qmax = tables.precision.qmax
        if policy.impl == "rexp":
            lut_main = jnp.asarray(tables.lut_recip_exp, jnp.int32)
            e_step = 1.0
        else:
            lut_main = jnp.asarray(tables.lut_exp, jnp.int32)
            e_step = tables.exp_step
        n_lut = lut_main.shape[0]
        rnd = jnp.round if policy.index_mode == "round" else jnp.floor

        def e_int_of(s, m_safe):
            finite = jnp.isfinite(s)
            dd = jnp.where(finite, (m_safe[..., None] - s) * inv_scale(e_step),
                           float(n_lut - 1))
            idx = jnp.clip(rnd(dd).astype(jnp.int32), 0, n_lut - 1)
            return jnp.where(finite, kernel_lookup(lut_main, idx, "gather"), 0)

        def one_q_chunk(qi):
            qc = jax.lax.dynamic_slice_in_dim(qg, qi * bq, bq, axis=3)

            def maxstep(m, xs):
                kc, ki = xs
                s = _grouped_logits(qc, kc, scale)
                mask = _chunk_mask(qi * bq, ki * bk, bq, bk, causal,
                                   lq_orig, lk_eff, q_start)
                s = jnp.where(mask, s, -jnp.inf)
                return jnp.maximum(m, jnp.max(s, axis=-1)), None

            m0 = jnp.full((b, kvh, g, bq), -jnp.inf, jnp.float32)
            m, _ = jax.lax.scan(maxstep, m0, (kr, jnp.arange(nk)),
                                unroll=nk if unroll else 1)
            m_safe = jnp.where(jnp.isfinite(m), m, 0.0)

            def accstep(carry, xs):
                ssum, u = carry
                kc, vc, ki = xs
                s = _grouped_logits(qc, kc, scale)
                mask = _chunk_mask(qi * bq, ki * bk, bq, bk, causal,
                                   lq_orig, lk_eff, q_start)
                s = jnp.where(mask, s, -jnp.inf)
                with dequant_scope():  # f32-exact integer Σ accumulator
                    e = e_int_of(s, m_safe).astype(jnp.float32)
                ssum = ssum + jnp.sum(e, axis=-1)
                u = u + jnp.einsum("bngqk,bnkd->bngqd", e,
                                   vc.astype(jnp.float32),
                                   precision=ATTN_PRECISION)
                return (ssum, u), None

            s0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
            u0 = jnp.zeros((b, kvh, g, bq, d), jnp.float32)
            (ssum, u), _ = jax.lax.scan(accstep, (s0, u0),
                                        (kr, vr, jnp.arange(nk)),
                                        unroll=nk if unroll else 1)

            inv = inv_scale(qmax)
            if policy.impl == "rexp":
                lut_a = jnp.asarray(tables.lut_alpha, jnp.int32)
                ja = jnp.clip(rnd(ssum * inv).astype(jnp.int32), 0,
                              lut_a.shape[0] - 1)
                with dequant_scope():  # α/qmax² fused requant exit
                    alpha = kernel_lookup(lut_a, ja, "gather") \
                        .astype(jnp.float32)
                return u * (alpha * inv * inv)[..., None]
            # lut2d fused form: scale U by LUT_σ row value of the mean bin —
            # the faithful per-element σ is only available in naive/pallas
            # paths; blocked lut2d divides by the binned denominator instead.
            lut_sig = tables.lut_sigma
            n_cols = lut_sig.shape[1]
            jj = jnp.clip(rnd(ssum * inv_scale(qmax * tables.scale_sum))
                          .astype(jnp.int32), 1, n_cols).astype(jnp.float32)
            return u * (inv / (jj * tables.scale_sum))[..., None]

    if unroll:
        outs = jnp.stack([one_q_chunk(jnp.int32(i)) for i in range(nq)])
    else:
        outs = jax.lax.map(one_q_chunk, jnp.arange(nq))  # (nq,B,KVH,G,bq,D)
    out = jnp.moveaxis(outs, 0, 3).reshape(b, kvh, g, lq, d)
    return out.reshape(b, h, lq, d)[:, :, :lq_orig]


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def lut_attention(
    q: Array, k: Array, v: Array, policy: SoftmaxPolicy, *,
    causal: bool = False,
    scale: float | None = None,
    kv_len: Array | int | None = None,
    backend: str = "naive",  # 'naive' | 'blocked' | 'pallas'
    fused_requant: bool = True,
    interpret: bool = True,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    unroll: bool = False,
) -> Array:
    """Attention with the policy's softmax.  See module docstring."""
    if backend == "pallas" and policy.impl in ("rexp", "lut2d"):
        assert kv_len is None, "pallas path needs static kv_len"
        tables = _tables_for(policy)
        return lut_attention_pallas(
            q, k, v, tables, method=policy.impl, causal=causal, scale=scale,
            index_mode=policy.index_mode, fused_requant=fused_requant,
            interpret=interpret)
    if backend == "blocked":
        return lut_attention_blocked(q, k, v, policy, causal=causal,
                                     scale=scale, kv_len=kv_len,
                                     q_chunk=q_chunk, k_chunk=k_chunk,
                                     unroll=unroll)
    # naive
    if kv_len is not None:
        ki = jnp.arange(k.shape[2])
        neg = jnp.where(ki < kv_len, 0.0, -jnp.inf).astype(jnp.float32)
        # fold the tail mask through an additive bias on k-side logits:
        return _naive_with_bias(q, k, v, policy, causal, scale, neg,
                                fused_requant, kv_len)
    method = policy.impl if policy.impl in ("rexp", "lut2d", "exact") else "exact"
    tables = _tables_for(policy)
    return _ref.lut_attention_ref(q, k, v, method=method, tables=tables,
                                  scale=scale, causal=causal,
                                  index_mode=policy.index_mode,
                                  fused_requant=fused_requant)


def _policy_softmax(s: Array, policy: SoftmaxPolicy) -> Array:
    """Masked logits (−inf tails) → σ under the policy's semantics.

    Single dispatch point for every dense serving path (lockstep
    kv_len, varlen decode, chunked prefill) — one place to extend when
    a policy is added, so the paths cannot silently diverge.
    """
    if policy.impl == "exact":
        return _core.softmax_exact(s, axis=-1)
    if policy.impl == "rexp":
        return _core.softmax_rexp(s, _tables_for(policy), axis=-1,
                                  index_mode=policy.index_mode)
    if policy.impl == "lut2d":
        return _core.softmax_lut2d(s, _tables_for(policy), axis=-1,
                                   index_mode=policy.index_mode)
    raise ValueError(f"unsupported softmax policy {policy.impl!r}")


def _grouped_pv(p: Array, v: Array) -> Array:
    """σ (B, H, Lq, Lk) × v (B, KVH, Lk, D) → (B, H, Lq, D) without
    materializing a duplicated (B, H, Lk, D) value tensor: the query-head
    axis is reshaped into (KVH, G) groups and contracted against the
    shared KV head directly (GQA reads each value row once)."""
    b, h, lq, lk = p.shape
    kvh = v.shape[1]
    g = h // kvh
    out = jnp.einsum("bngqk,bnkd->bngqd", p.reshape(b, kvh, g, lq, lk),
                     v.astype(jnp.float32), precision=ATTN_PRECISION)
    return out.reshape(b, h, lq, -1)


def lut_attention_decode_varlen(
    q: Array, k: Array, v: Array, policy: SoftmaxPolicy, kv_lens: Array, *,
    scale: float | None = None,
) -> Array:
    """Decode attention with a *per-sequence* valid KV length.

    The continuous-batching serving path: every slot in the decode batch
    sits at its own position, so the tail mask is per-row rather than the
    single traced ``kv_len`` the lockstep path uses.

    q (B, H, Lq, D) single/few-token queries; k, v (B, KVH, Lk, D) — the
    block-table-gathered view of the paged KV pool (logical order, junk
    past ``kv_lens``); kv_lens (B,) int32.  Dense fallback (logits
    materialized) so it runs identically on CPU CI and TPU; semantics
    per key are exactly the lockstep ``kv_len`` path's, which keeps
    continuous-batching output token-identical to ``generate()``.
    """
    b, h, lq, d = q.shape
    kvh, lk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    s = _ref._logits(q, k, scale, causal=False)  # (B, H, Lq, Lk) f32
    ki = jnp.arange(lk)
    valid = ki[None, :] < kv_lens[:, None]       # (B, Lk)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    return _grouped_pv(_policy_softmax(s, policy), v)


def lut_attention_prefill_varlen(
    q: Array, k: Array, v: Array, policy: SoftmaxPolicy, *,
    q_start: Array, kv_lens: Array,
    scale: float | None = None,
) -> Array:
    """Chunked-prefill attention with materialized logits (the oracle).

    One prompt *chunk* attends causally to everything already cached
    plus itself: query row i sits at absolute position ``q_start + i``
    and sees keys ``[0, q_start + i]``; keys at or past ``kv_lens``
    (junk pool content, structural padding) are masked per row.  Both
    ``q_start`` and ``kv_lens`` are (B,) int32 — every slot carries its
    own cursor.

    q (B, H, C, D) chunk queries; k, v (B, KVH, Lk, D) — the
    block-table-gathered logical view of the paged pool.  Masking with
    −inf before the policy softmax keeps the per-key numerics exactly
    those of the whole-prompt naive path, which is what makes chunked
    engine prefill token-identical to lockstep ``generate()``.
    """
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    s = _ref._logits(q, k, scale, causal=False)   # (B, H, C, Lk) f32
    ki = jnp.arange(lk)[None, None, None, :]
    qi = (q_start[:, None] + jnp.arange(lq)[None, :])[:, None, :, None]
    mask = (ki <= qi) & (ki < kv_lens[:, None, None, None])
    s = jnp.where(mask, s, -jnp.inf)
    return _grouped_pv(_policy_softmax(s, policy), v)


def _resolve_paged(backend: str, *, kind: str, dense: str,
                   passthrough: tuple[str, ...]) -> str:
    """The one dispatch matrix in code (see the module docstring): the
    decode/prefill resolvers differ only in the name of their dense
    flavor and which explicit paths they pass through."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else dense
    if backend == "pallas":
        return ("pallas" if jax.default_backend() == "tpu"
                else "pallas_interpret")
    if backend == "dense":
        return dense
    if backend in passthrough:
        return backend
    raise ValueError(f"unknown paged {kind} backend {backend!r}")


def paged_mesh_regime(mesh, n_kv_heads: int) -> str | None:
    """The mesh rows of the dispatch matrix (see the module docstring).

    Returns ``None`` without a tensor-parallel mesh (single-device
    dispatch applies), ``'heads'`` when the GQA KV-head count divides the
    'model' axis (pool sharded on KV heads, attention fully local per
    shard), and ``'pages'`` otherwise (pool sharded on the physical-page
    axis, ``sharded_paged.py`` reduces only ``(B, H, 1)`` partials).
    """
    from repro.runtime.partitioning import mesh_model_tp
    tp = mesh_model_tp(mesh)
    if tp <= 1:
        return None
    return "heads" if n_kv_heads % tp == 0 else "pages"


def resolve_paged_prefill_backend(backend: str = "auto") -> str:
    """Resolve the paged-prefill dispatch knob to an executable path.

    Same matrix as :func:`resolve_paged_backend` (the decode side):

    * ``auto``   → ``pallas`` on TPU, the ``naive`` oracle elsewhere
      (the kernel's scalar-prefetch grid spec is Mosaic/TPU-only — GPU
      serves through the dense reference until a Mosaic-GPU port lands,
      and CPU CI always does);
    * ``pallas`` → the fused kernel; off-TPU it runs in interpret mode
      (``pallas_interpret`` — the CI parity configuration, never a
      silent stand-in);
    * ``dense``  → the gathered ``naive`` oracle, everywhere (alias so
      ``RunConfig.paged_backend`` values flow through unchanged);
    * ``naive`` / ``blocked`` → the explicit dense flavors (materialized
      oracle / blocked-XLA scan over the gathered view).
    """
    return _resolve_paged(backend, kind="prefill", dense="naive",
                          passthrough=("naive", "blocked",
                                       "pallas_interpret"))


def lut_attention_paged_prefill(
    q: Array,               # (B, C, H·D)-projected chunk queries (B, H, C, D)
    k_pages: Array,         # (num_pages, KVH, page_size, D) shared pool
    v_pages: Array,
    block_tables: Array,    # (B, max_pages_per_seq) int32
    q_start: Array,         # (B,) int32 — tokens cached before this chunk
    kv_lens: Array,         # (B,) int32 — valid keys incl. this chunk
    policy: SoftmaxPolicy,
    *,
    scale: float | None = None,
    backend: str = "naive",  # 'auto' | 'pallas' | 'dense'|'naive' | 'blocked'
    q_chunk: int = 512,
    k_chunk: int = 1024,
    mesh=None,
    k_scales: Array | None = None,  # (num_pages, 1, KVH·page_size) f32 —
    v_scales: Array | None = None,  # int8 pool dequant scales (or None)
) -> Array:
    """Prefill-chunk attention reading prior keys through the block
    tables — the chunk's K/V were already scattered into the pool, so
    the pool *is* the only KV **storage** (no contiguous per-request
    cache is ever written).

    A ``mesh`` whose 'model' axis has tp > 1 selects the tensor-parallel
    rows of the matrix instead of ``backend`` (``paged_mesh_regime``;
    the pool must carry the matching sharding — see
    ``runtime/partitioning.paged_pool_pspec``).

    Dispatches per :func:`resolve_paged_prefill_backend` (the module
    docstring's matrix).  On the ``pallas`` path the fused kernel
    (``paged_prefill.py``) streams K/V pages straight from the pool
    through scalar-prefetched block tables — ``gather_pages`` is never
    called there.  The dense flavors assemble a transient block-table
    view per chunk (as the dense paged-*decode* reference does per step)
    and run the materialized oracle (``'naive'`` — bitwise the lockstep
    semantics, the parity configuration) or the blocked LUT path with
    per-row ``kv_len`` / ``q_start``; that per-chunk gather costs
    O(L/C · max_context) reads over a prompt, which is exactly what the
    kernel path removes.  One compiled program serves every prompt
    length: all shapes are fixed by (C, block-table width); only the
    cursors are traced.
    """
    regime = paged_mesh_regime(mesh, k_pages.shape[1])
    if regime is not None:
        from repro.kernels.lut_attention import sharded_paged
        return sharded_paged.paged_attention_sharded(
            q, k_pages, v_pages, block_tables, kv_lens, policy, mesh=mesh,
            regime=regime, q_start=q_start, scale=scale,
            k_scales=k_scales, v_scales=v_scales)
    resolved = resolve_paged_prefill_backend(backend)
    if resolved.startswith("pallas"):
        return paged_prefill_attention(
            q, k_pages, v_pages, block_tables, q_start, kv_lens,
            _tables_for(policy), method=policy.impl, scale=scale,
            index_mode=policy.index_mode,
            interpret=resolved == "pallas_interpret",
            k_scales=k_scales, v_scales=v_scales)
    if k_scales is not None:
        k_seq, v_seq = _gather_dequant(k_pages, v_pages, block_tables,
                                       k_scales, v_scales)
    else:
        k_seq = gather_pages(k_pages, block_tables)
        v_seq = gather_pages(v_pages, block_tables)
    if resolved == "blocked":
        return lut_attention_blocked(q, k_seq, v_seq, policy, causal=True,
                                     scale=scale, kv_len=kv_lens,
                                     q_start=q_start, q_chunk=q_chunk,
                                     k_chunk=k_chunk)
    return lut_attention_prefill_varlen(q, k_seq, v_seq, policy,
                                        q_start=q_start, kv_lens=kv_lens,
                                        scale=scale)


def _naive_with_bias(q, k, v, policy, causal, scale, k_bias, fused_requant,
                     kv_len):
    """Naive path with an additive per-key bias (KV-cache tail masking).

    Causal alignment must use the *valid* length (``kv_len``), not the
    allocated cache length: queries sit at absolute positions
    [kv_len − lq, kv_len), while the cache may be pre-allocated longer.
    """
    b, h, lq, d = q.shape
    kvh, lk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    s = _ref._logits(q, k, scale, causal=False) \
        + k_bias[None, None, None, :]
    if causal:
        qi = jnp.arange(lq)[:, None] + (kv_len - lq)
        ki = jnp.arange(lk)[None, :]
        s = jnp.where((ki <= qi)[None, None], s, -jnp.inf)
    return _grouped_pv(_policy_softmax(s, policy), v)


# ---------------------------------------------------------------------------
# Paged decode (continuous-batching hot loop)
# ---------------------------------------------------------------------------


def gather_pages(pages: Array, block_tables: Array) -> Array:
    """(P, KVH, ps, Dh) pool + (B, mp) table → (B, KVH, mp·ps, Dh) view.

    Logical token order is preserved: page j of a slot covers absolute
    positions [j·ps, (j+1)·ps).  Junk past a slot's length (null-page
    content, partial-page tails) is masked by the caller via ``kv_lens``.
    This materialized view exists ONLY on the dense fallback path — the
    Pallas kernel streams pages straight from the pool.
    """
    b, mp = block_tables.shape
    kvh, ps, dh = pages.shape[1], pages.shape[2], pages.shape[3]
    g = pages[block_tables]                     # (B, mp, KVH, ps, Dh)
    return g.transpose(0, 2, 1, 3, 4).reshape(b, kvh, mp * ps, dh)


def gather_page_scales(scales: Array, block_tables: Array,
                       n_kv_heads: int) -> Array:
    """(P, 1, KVH·ps) scale pool + (B, mp) table → (B, KVH, mp·ps) view.

    Row-aligned with :func:`gather_pages`: scale [b, n, t] dequantizes
    token row t of the gathered int8 K (or V) view.  Dense-path only,
    like the page gather itself.
    """
    b, mp = block_tables.shape
    ps = scales.shape[2] // n_kv_heads
    g = scales[block_tables].reshape(b, mp, n_kv_heads, ps)
    return g.transpose(0, 2, 1, 3).reshape(b, n_kv_heads, mp * ps)


def _gather_dequant(k_pages, v_pages, block_tables, k_scales, v_scales):
    """Dense-path int8 pool → dequantized (B, KVH, mp·ps, Dh) f32 views."""
    from repro.core.quantization import dequantize_rows
    kvh = k_pages.shape[1]
    k_seq = dequantize_rows(gather_pages(k_pages, block_tables),
                            gather_page_scales(k_scales, block_tables, kvh))
    v_seq = dequantize_rows(gather_pages(v_pages, block_tables),
                            gather_page_scales(v_scales, block_tables, kvh))
    return k_seq, v_seq


def resolve_paged_backend(backend: str = "auto") -> str:
    """Resolve the paged-decode dispatch knob to an executable path.

    Same matrix as :func:`resolve_paged_prefill_backend` (the prefill
    side) — the module docstring states it once for both kernels:

    * ``auto``   → ``pallas`` on TPU, ``dense`` elsewhere (the kernel's
      scalar-prefetch grid spec is Mosaic/TPU-only — GPU serves through
      the dense reference until a Mosaic-GPU port lands, and CPU CI
      always does);
    * ``pallas`` → the fused kernel; off-TPU it runs in interpret mode
      (``pallas_interpret`` — the CI parity configuration, never a
      silent stand-in);
    * ``dense``  → gather-from-block-table reference, everywhere.
    """
    return _resolve_paged(backend, kind="decode", dense="dense",
                          passthrough=("dense", "pallas_interpret"))


def lut_attention_paged_decode(
    q: Array,              # (B, H, 1, D) single-token queries
    k_pages: Array,        # (num_pages, KVH, page_size, D) shared pool
    v_pages: Array,
    block_tables: Array,   # (B, max_pages_per_seq) int32
    kv_lens: Array,        # (B,) int32 — valid keys incl. the new token
    policy: SoftmaxPolicy,
    *,
    scale: float | None = None,
    backend: str = "auto",  # 'auto' | 'pallas' | 'dense'
    mesh=None,
    k_scales: Array | None = None,  # (num_pages, 1, KVH·page_size) f32 —
    v_scales: Array | None = None,  # int8 pool dequant scales (or None)
) -> Array:
    """Decode attention straight off the paged KV pool.

    Dispatches per :func:`resolve_paged_backend`: on TPU the fused
    Pallas kernel reads K/V through the per-slot block tables (a block
    of pages per grid step, blocks past ``kv_lens`` skipped — no
    contiguous (B, KVH, Lk, D) gather, no logits tensor); elsewhere the
    dense reference gathers the block-table view and reuses
    :func:`lut_attention_decode_varlen`.  Per-key numerics are
    identical across paths (the parity suite pins this), so serving
    output does not depend on where a slot decodes.

    A ``mesh`` whose 'model' axis has tp > 1 selects the tensor-parallel
    rows of the matrix instead of ``backend`` (``paged_mesh_regime``;
    the pool must carry the matching sharding — see
    ``runtime/partitioning.paged_pool_pspec``).
    """
    regime = paged_mesh_regime(mesh, k_pages.shape[1])
    if regime is not None:
        from repro.kernels.lut_attention import sharded_paged
        return sharded_paged.paged_attention_sharded(
            q, k_pages, v_pages, block_tables, kv_lens, policy, mesh=mesh,
            regime=regime, scale=scale, k_scales=k_scales, v_scales=v_scales)
    resolved = resolve_paged_backend(backend)
    if resolved.startswith("pallas"):
        return paged_decode_attention(
            q, k_pages, v_pages, block_tables, kv_lens, _tables_for(policy),
            method=policy.impl, scale=scale, index_mode=policy.index_mode,
            interpret=resolved == "pallas_interpret",
            k_scales=k_scales, v_scales=v_scales)
    if k_scales is not None:
        k_seq, v_seq = _gather_dequant(k_pages, v_pages, block_tables,
                                       k_scales, v_scales)
    else:
        k_seq = gather_pages(k_pages, block_tables)
        v_seq = gather_pages(v_pages, block_tables)
    return lut_attention_decode_varlen(q, k_seq, v_seq, policy, kv_lens,
                                       scale=scale)
