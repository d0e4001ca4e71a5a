"""Fused paged-decode attention: LUT softmax in-kernel over block tables.

The continuous-batching decode hot loop.  Every slot in the decode batch
holds one single-token query and attends to its own sequence, whose K/V
live scattered across a shared head-major page pool ``(num_pages, KVH,
page_size, Dh)``.  The dense fallback first *gathers* each slot's pages into a
contiguous ``(B, KVH, Lk, D)`` tensor and then materializes full logits
— exactly the memory traffic the paper's LUT approach exists to avoid.
This kernel instead streams pages straight out of the pool:

* the innermost grid axis walks a slot's **block table**; the K/V block
  index maps read the physical page id from a scalar-prefetched table
  (``pltpu.PrefetchScalarGridSpec``), so each grid step DMAs exactly one
  page into VMEM — the contiguous per-slot view never exists;
* a per-slot ``kv_lens`` tail mask (also scalar-prefetched) invalidates
  the partial last page and every null-page placeholder;
* GQA is handled by grouping: queries arrive as ``(B, KVH, G, Dh)`` and
  each (slot, kv-head) grid cell serves all ``G`` query heads of that KV
  head from one page read.

Why multi-pass (same argument as ``lut_attention.py``): the paper's
Algorithms 1/2 normalize by the *global* row max and the *global* Σe —
piecewise-constant tables do not satisfy the online-softmax rescaling
identity, so the classic single-pass flash-decoding trick would change
the numerics.  The page axis is swept three times, with the running
max / Σ accumulated online across page chunks in the output refs (block
index maps are independent of the page axis, so accumulators stay
resident across the sequential innermost grid dimension):

  pass 1   row max    m(b,h)   = max_p max(q·K_pᵀ)              [MXU]
  pass 2   Σ          S(b,h)   = Σ_p Σ(e(s, m))                 [MXU+VPU]
  pass 3   weighted V out(b,h) = Σ_p w(s, m, S) · V_p           [MXU]

where ``e``/``w`` are the policy's semantics — exact ``exp``/softmax, or
the integer LUT pipeline (REXP per-element σ_int requantization, 2D-LUT
σ table read) applied *inside* the kernel via the same binning as
``core.lut_softmax`` (bit-identical integer pipeline; only the final f32
V-contraction accumulates page-chunked instead of row-at-once).

Total traffic per step: the live pages once per pass plus O(B·G·Dh)
accumulators — no O(B·mp·ps·D) gather and no (B, H, Lk) logits tensor in
HBM.  Validated in interpret mode on CPU, and compiled by Mosaic for a
TPU v5e at qwen3-32b widths (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut_builder import Lut2DTables, RexpTables
from repro.core.lut_softmax import inv_scale
from repro.kernels.common import (ATTN_PRECISION, NEG_INF, PAGED_PASSES,
                                  dequant_scope, lut2d_sigma_int, pass_name,
                                  policy_e_terms, policy_kernel_tables,
                                  rexp_sigma)

Array = jax.Array


# ---------------------------------------------------------------------------
# In-kernel helpers
# ---------------------------------------------------------------------------


def _head_scales(sc_ref, page_size):
    """(ps, 1) f32 dequant scales of this grid cell's KV head.

    ``sc_ref`` is the page's ``(1, 1, KVH·ps)`` scale block: every KV
    head's per-token scales on one head-major lane row
    (``runtime.paged_cache.scale_pool_shape``).  The cell's ``ps`` lanes
    are picked and turned into a column by a masked lane sum — exact,
    since each output sums one scale with zeros.
    """
    row = sc_ref[0]                                   # (1, KVH·ps)
    n = row.shape[-1]
    first = pl.program_id(1) * page_size
    lane = jax.lax.broadcasted_iota(jnp.int32, (page_size, n), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (page_size, n), 0)
    return jnp.sum(jnp.where(lane == first + tok, row, 0.0), axis=1,
                   keepdims=True)


def _page_rows(x_ref, sc_ref):
    """Page block (1, 1, ps, Dh) → (ps, Dh) f32 token rows.

    ``sc_ref`` is the page's scale block when the pool is int8
    (per-token × KV-head symmetric quantization) — the int8→f32 upcast
    then happens here, inside ``dequant_scope`` (the sanctioned exit the
    jaxpr lint checks for), and nowhere else in the kernel.
    """
    if sc_ref is None:
        return x_ref[0, 0].astype(jnp.float32)
    scales = _head_scales(sc_ref, x_ref.shape[2])
    with dequant_scope():  # int8 page rows × per-token scales
        return x_ref[0, 0].astype(jnp.float32) * scales


def _page_logits(q_ref, k_ref, kl_ref, scale, page_size, ks_ref=None):
    """(G, ps) f32 logits of this (slot, kv-head, page) cell, tail-masked.

    Key positions are logical: page ``p`` of a slot covers absolute
    positions [p·ps, (p+1)·ps); everything at or past ``kv_lens[b]`` —
    partial-page tails and null-page placeholders — is masked to −inf.
    """
    b = pl.program_id(0)
    p = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)          # (G, Dh)
    k = _page_rows(k_ref, ks_ref)                # (ps, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=ATTN_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(pos < kl_ref[b], s, NEG_INF)


# ---------------------------------------------------------------------------
# Pass 1 — global row max (online across pages)
# ---------------------------------------------------------------------------


def _accum_rowmax(s, m_ref):
    """Fold the (R, ps) logits tile into the (1, 1, R, 1) row-max block.

    Row statistics live in (R, 1) columns throughout (accumulator blocks
    included) so they broadcast over the tile's lanes without a
    sublane↔lane relayout — the form Mosaic lowers.
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    m_ref[0, 0] = jnp.maximum(m_ref[0, 0], jnp.max(s, axis=-1, keepdims=True))


def _pg_rowmax_kernel(bt_ref, kl_ref, q_ref, k_ref, m_ref, *, scale,
                      page_size):
    _accum_rowmax(_page_logits(q_ref, k_ref, kl_ref, scale, page_size),
                  m_ref)


def _pg_rowmax_kernel_int8(bt_ref, kl_ref, q_ref, k_ref, ks_ref, m_ref, *,
                           scale, page_size):
    _accum_rowmax(_page_logits(q_ref, k_ref, kl_ref, scale, page_size,
                               ks_ref=ks_ref), m_ref)


# ---------------------------------------------------------------------------
# Pass 2 — Σ numerators (online across pages)
# ---------------------------------------------------------------------------


def _accum_sum(s, m_ref, lut_ref, s_ref, method, exp_step, index_mode):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = policy_e_terms(s, m, lut_ref[0, :], method, exp_step, index_mode)
    with dequant_scope():  # f32-exact integer Σ accumulator
        s_ref[0, 0] += jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)


def _pg_sum_kernel(bt_ref, kl_ref, q_ref, k_ref, m_ref, lut_ref, s_ref, *,
                   scale, page_size, method, exp_step, index_mode):
    _accum_sum(_page_logits(q_ref, k_ref, kl_ref, scale, page_size),
               m_ref, lut_ref, s_ref, method, exp_step, index_mode)


def _pg_sum_kernel_int8(bt_ref, kl_ref, q_ref, k_ref, ks_ref, m_ref, lut_ref,
                        s_ref, *, scale, page_size, method, exp_step,
                        index_mode):
    _accum_sum(_page_logits(q_ref, k_ref, kl_ref, scale, page_size,
                            ks_ref=ks_ref),
               m_ref, lut_ref, s_ref, method, exp_step, index_mode)


# ---------------------------------------------------------------------------
# Pass 3 — per-element σ · V (faithful requantization, online across pages)
# ---------------------------------------------------------------------------


def _accum_weight(s, v, m_ref, s_ref, lut_main_ref, lut_aux_ref, o_ref,
                  method, qmax, exp_step, scale_ex, scale_sum, index_mode):
    """Accumulate out += σ(s, m, S) @ V_page with the policy's per-element
    weights — REXP re-quantizes σ_int per element (Algorithm 1 line 11),
    2D-LUT reads LUT_σ[i(e), j(S)] (Algorithm 2), exact divides by S."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = policy_e_terms(s, m, lut_main_ref[0, :], method, exp_step,
                       index_mode)
    s_tot = s_ref[0, 0]  # (R, 1) global Σ from pass 2

    if method == "exact":
        w = e / jnp.maximum(s_tot, jnp.finfo(jnp.float32).tiny)
    elif method == "rexp":
        w = rexp_sigma(e, s_tot, lut_aux_ref[0, :], qmax,
                       index_mode) * inv_scale(qmax)
    else:  # lut2d
        sigma_int = lut2d_sigma_int(e, s_tot, lut_aux_ref[...], qmax,
                                    scale_ex, scale_sum, index_mode)
        with dequant_scope():  # σ_int/qmax: the sanctioned exit
            w = sigma_int.astype(jnp.float32) * inv_scale(qmax)

    o_ref[0, 0] += jax.lax.dot_general(
        w.astype(jnp.float32), v, (((1,), (0,)), ((), ())),
        precision=ATTN_PRECISION, preferred_element_type=jnp.float32)


def _pg_weight_kernel(bt_ref, kl_ref, q_ref, k_ref, v_ref, m_ref, s_ref,
                      lut_main_ref, lut_aux_ref, o_ref, *, scale, page_size,
                      method, qmax, exp_step, scale_ex, scale_sum,
                      index_mode):
    _accum_weight(_page_logits(q_ref, k_ref, kl_ref, scale, page_size),
                  _page_rows(v_ref, None), m_ref, s_ref, lut_main_ref,
                  lut_aux_ref, o_ref, method, qmax, exp_step, scale_ex,
                  scale_sum, index_mode)


def _pg_weight_kernel_int8(bt_ref, kl_ref, q_ref, k_ref, ks_ref, v_ref,
                           vs_ref, m_ref, s_ref, lut_main_ref, lut_aux_ref,
                           o_ref, *, scale, page_size, method, qmax, exp_step,
                           scale_ex, scale_sum, index_mode):
    _accum_weight(_page_logits(q_ref, k_ref, kl_ref, scale, page_size,
                               ks_ref=ks_ref),
                  _page_rows(v_ref, vs_ref), m_ref, s_ref, lut_main_ref,
                  lut_aux_ref, o_ref, method, qmax, exp_step, scale_ex,
                  scale_sum, index_mode)


# ---------------------------------------------------------------------------
# Host-side launcher
# ---------------------------------------------------------------------------


# Index maps take the grid point and then the scalar-prefetch refs, block
# table first; ``*_`` absorbs the rest, so the paged-prefill launcher (which
# prefetches one more array) shares these specs.


def _pool_spec(page_size, dh):
    """One physical page of one KV head per grid step — a contiguous
    (ps, Dh) slab of the head-major pool; the page id comes from the
    scalar-prefetched block table — the paged-pool indirection itself."""
    return pl.BlockSpec(
        (1, 1, page_size, dh),
        lambda b, h, p, bt_ref, *_: (bt_ref[b, p], h, 0, 0))


def _scale_spec(row):
    """The int8 pool's per-page scale row (all KV heads, ``row`` =
    KVH·ps lanes) — rides the same scalar-prefetched block-table
    indirection as its page; ``_head_scales`` picks the cell's head."""
    return pl.BlockSpec(
        (1, 1, row),
        lambda b, h, p, bt_ref, *_: (bt_ref[b, p], 0, 0))


def _lut_spec(arr):
    zeros = (0,) * arr.ndim
    return pl.BlockSpec(arr.shape, lambda b, h, p, *_: zeros)


def _row_specs(rows, dh):
    """Query, row-statistic and output blocks of one (slot, kv-head)
    cell holding ``rows`` query rows; resident along the page axis."""
    q_spec = pl.BlockSpec((1, 1, rows, dh), lambda b, h, p, *_: (b, h, 0, 0))
    acc_spec = pl.BlockSpec((1, 1, rows, 1), lambda b, h, p, *_: (b, h, 0, 0))
    return q_spec, acc_spec, q_spec


def kernel_name(quantized: bool) -> str:
    """``KernelSpec.name`` of the f32- or int8-pool variant; its passes
    run as ``pass_name(kernel_name(quantized), p)``."""
    return "paged_decode_int8" if quantized else "paged_decode"


def _grid_specs(g, dh, page_size):
    """The decode dispatch's BlockSpecs — single source for the launcher
    and for ``kernel_spec`` (the static guard's declaration)."""
    q_spec, acc_spec, o_spec = _row_specs(g, dh)
    return q_spec, _pool_spec(page_size, dh), acc_spec, o_spec


def _build_kernel_spec(geom, quantized):
    import numpy as np

    from repro.analysis.kernel_guard import KernelSpec, Operand, PassSpec
    from repro.core.lut_builder import build_lut2d_tables
    from repro.runtime.paged_cache import pool_shape, scale_pool_shape

    b, h, kvh, dh = geom["b"], geom["h"], geom["kvh"], geom["dh"]
    g = h // kvh
    page_size, mp, n_pages = geom["page_size"], geom["mp"], geom["n_pages"]
    grid = (b, kvh, mp)  # page axis innermost (sequential accumulation)
    q_spec, kv_spec, acc_spec, o_spec = _grid_specs(g, dh, page_size)

    bt = np.zeros((b, mp), np.int32)
    bt[:, 1::2] = n_pages - 1  # both domain extremes appear
    kl = np.full((b,), page_size * mp, np.int32)
    prefetch = (bt, kl)

    l2d = build_lut2d_tables("int16")
    lut_main = l2d.lut_exp[None, :]
    # aux slot carries α (rexp, (1,16)) or σ (lut2d); σ (11,60) is worst
    lut_aux = l2d.lut_sigma

    page_dtype = "int8" if quantized else "float32"
    q = Operand("q", (b, kvh, g, dh), q_spec)
    kv = Operand("k_pages", pool_shape(n_pages, page_size, kvh, dh),
                 kv_spec, page_dtype, table_indexed=True,
                 index_domain=(0, n_pages))
    vv = Operand("v_pages", pool_shape(n_pages, page_size, kvh, dh),
                 kv_spec, page_dtype, table_indexed=True,
                 index_domain=(0, n_pages))
    sc_shape = scale_pool_shape(n_pages, page_size, kvh)
    sc = _scale_spec(sc_shape[-1])
    ks = Operand("k_scales", sc_shape, sc,
                 table_indexed=True, index_domain=(0, n_pages))
    vs = Operand("v_scales", sc_shape, sc,
                 table_indexed=True, index_domain=(0, n_pages))
    kk = (kv, ks) if quantized else (kv,)
    vvv = (vv, vs) if quantized else (vv,)
    m = Operand("m", (b, kvh, g, 1), acc_spec)
    s = Operand("s_sum", (b, kvh, g, 1), acc_spec)
    o = Operand("out", (b, kvh, g, dh), o_spec)
    t_main = Operand("lut_main", lut_main.shape, _lut_spec(lut_main), "int32")
    t_aux = Operand("lut_aux", lut_aux.shape, _lut_spec(lut_aux), "int32")

    rowmax, sum_, weight = PAGED_PASSES
    passes = (
        PassSpec(rowmax, grid, (q,) + kk, (m,), scalar_prefetch=prefetch),
        PassSpec(sum_, grid, (q,) + kk + (m, t_main), (s,),
                 scalar_prefetch=prefetch, sigma_acc=True,
                 acc_dtype="float32",
                 notes="integer Σ accumulated f32-exact in the resident ref"),
        PassSpec(weight, grid, (q,) + kk + vvv + (m, s, t_main, t_aux),
                 (o,), scalar_prefetch=prefetch),
    )
    if quantized:
        return KernelSpec(
            name=kernel_name(quantized), module=__name__, kind="pallas",
            passes=passes,
            notes="int8 pool variant: pages stream as int8 with per-token "
                  "f32 scale blocks riding the same block-table "
                  "indirection; dequant in VMEM under dequant_scope")
    return KernelSpec(
        name=kernel_name(quantized), module=__name__, kind="pallas",
        passes=passes,
        notes="streams pages from the pool via scalar-prefetched block "
              "tables; one page DMA per grid step")


def kernel_spec(geom):
    """Static declaration for :mod:`repro.analysis.kernel_guard`.

    Uses the launcher's own ``_grid_specs`` / ``_pool_spec``; the
    scalar-prefetch probe arrays exercise both extremes of the declared
    block-table domain ``[0, n_pages)`` (0 is the null-page placeholder,
    the allocator issues ids in ``[1, n_pages)``), so the in-range check
    is a clamp proof for the pool indirection.  Table operands use the
    worst-case (int16 2D-LUT) shapes.
    """
    return _build_kernel_spec(geom, quantized=False)


def kernel_spec_int8(geom):
    """The int8-pool variant's declaration (``paged_decode_int8``).

    Same grid and accumulators as :func:`kernel_spec`; the K/V page
    operands are int8 and each carries a per-token f32 scale operand
    read through the identical block-table indirection — the guard
    proves the streamed working set shrinks to ~¼ of the f32 pages.
    """
    return _build_kernel_spec(geom, quantized=True)


def paged_decode_attention(
    q: Array,              # (B, H, 1, Dh) single-token queries
    k_pages: Array,        # (num_pages, KVH, page_size, Dh) shared pool
    v_pages: Array,
    block_tables: Array,   # (B, max_pages_per_seq) int32 physical page ids
    kv_lens: Array,        # (B,) int32 — valid keys incl. the new token
    tables: RexpTables | Lut2DTables | None = None,
    *,
    method: str = "exact",          # 'exact' | 'rexp' | 'lut2d'
    scale: float | None = None,
    index_mode: str = "round",
    interpret: bool | None = None,
    k_scales: Array | None = None,  # (num_pages, 1, KVH·page_size) f32
    v_scales: Array | None = None,
) -> Array:
    """Fused paged-decode attention; returns (B, H, 1, Dh) f32.

    ``interpret=None`` resolves per backend: compiled (Mosaic) on TPU,
    interpreter emulation elsewhere — callers never get a silent
    interpreter run on real hardware, and CPU callers never get a
    lowering error.

    ``k_scales``/``v_scales`` (both or neither) select the int8-pool
    variant: the pages are int8, each token row carrying one symmetric
    f32 scale per KV head; the scale blocks ride the same block-table
    indirection and the rows are dequantized in VMEM (``_page_rows``)
    before the identical 3-pass pipeline — halved page traffic, same
    integer LUT semantics.

    Numerics match ``ops.lut_attention_decode_varlen`` on the gathered
    view: identical integer pipeline (bins, e_int, Σ, σ_int); the final
    f32 V-contraction accumulates per page, so outputs agree to f32
    roundoff.  Both dots run at ``ATTN_PRECISION`` (f32), like the
    reference's, so this holds on a TPU too: there the compiled kernel
    may part from the reference only in a row with a logit at a LUT
    bin's rounding edge (``chip_smoke.py`` checks both).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scales is not None
    assert quantized == (v_scales is not None), \
        "int8 pool needs both k_scales and v_scales"
    b, h, lq, dh = q.shape
    assert lq == 1, f"paged decode takes single-token queries, got Lq={lq}"
    num_pages, kvh, page_size, _ = k_pages.shape
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    mp = block_tables.shape[1]
    scale = scale if scale is not None else dh ** -0.5

    qg = q[:, :, 0, :].reshape(b, kvh, g, dh)
    block_tables = block_tables.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)

    q_spec, kv_spec, acc_spec, o_spec = _grid_specs(g, dh, page_size)
    grid = (b, kvh, mp)  # page axis innermost → sequential accumulation

    def spec(in_specs, out_specs):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs)

    (lut_main, lut_aux, exp_step, qmax, scale_ex,
     scale_sum) = policy_kernel_tables(method, tables)

    geom = dict(scale=scale, page_size=page_size)
    sc_spec = _scale_spec(kvh * page_size)
    # the int8 variants interleave each page's scale block right after it
    k_in = [kv_spec, sc_spec] if quantized else [kv_spec]
    k_ops = (k_pages, k_scales) if quantized else (k_pages,)
    v_in = [kv_spec, sc_spec] if quantized else [kv_spec]
    v_ops = (v_pages, v_scales) if quantized else (v_pages,)
    rowmax_k = _pg_rowmax_kernel_int8 if quantized else _pg_rowmax_kernel
    sum_k = _pg_sum_kernel_int8 if quantized else _pg_sum_kernel
    weight_k = _pg_weight_kernel_int8 if quantized else _pg_weight_kernel
    rowmax_name, sum_name, weight_name = (
        pass_name(kernel_name(quantized), p) for p in PAGED_PASSES)

    # Pass 1: global row max, accumulated online over the page chunks.
    m = pl.pallas_call(
        functools.partial(rowmax_k, **geom),
        grid_spec=spec([q_spec] + k_in, acc_spec),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        interpret=interpret,
        name=rowmax_name,
    )(block_tables, kv_lens, qg, *k_ops)

    # Pass 2: global Σ of the policy's numerators.
    s_sum = pl.pallas_call(
        functools.partial(sum_k, method=method, exp_step=exp_step,
                          index_mode=index_mode, **geom),
        grid_spec=spec([q_spec] + k_in + [acc_spec, _lut_spec(lut_main)],
                       acc_spec),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        interpret=interpret,
        name=sum_name,
    )(block_tables, kv_lens, qg, *k_ops, m, lut_main)

    # Pass 3: per-element σ · V, accumulated page by page.
    out = pl.pallas_call(
        functools.partial(weight_k, method=method, qmax=qmax,
                          exp_step=exp_step, scale_ex=scale_ex,
                          scale_sum=scale_sum, index_mode=index_mode,
                          **geom),
        grid_spec=spec([q_spec] + k_in + v_in + [acc_spec, acc_spec,
                        _lut_spec(lut_main), _lut_spec(lut_aux)],
                       o_spec),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), jnp.float32),
        interpret=interpret,
        name=weight_name,
    )(block_tables, kv_lens, qg, *k_ops, *v_ops, m, s_sum, lut_main,
      lut_aux)

    return out.reshape(b, h, 1, dh)
