"""Fused paged-prefill attention: LUT softmax in-kernel over block tables.

The chunked-prefill half of the continuous-batching hot path.  Each slot
carries a ``C``-token prompt *chunk* whose K/V were already scattered
into the shared head-major page pool ``(num_pages, KVH, page_size,
Dh)``; the chunk's queries sit at absolute positions ``[q_start,
q_start + C)`` and attend causally to every key ``< kv_lens`` of their
own sequence.  The
dense fallback first *gathers* each slot's pages into a contiguous
``(B, KVH, Lk, D)`` view — an O(L/C · max_context) read per prompt that
``ops.py`` documented as the last densification on the serving path.
This kernel removes it by streaming pages straight from the pool, the
same way ``paged_decode.py`` does:

* the innermost grid axis walks a slot's **block table**; the K/V block
  index maps read the physical page id from a scalar-prefetched table
  (``pltpu.PrefetchScalarGridSpec``), so each grid step DMAs exactly one
  page into VMEM — the contiguous per-slot view never exists;
* per-slot ``kv_lens`` (valid keys incl. this chunk) and ``q_start``
  (chunk cursor) are also scalar-prefetched: key position ``pos`` is
  visible to chunk row ``i`` iff ``pos < kv_lens[b]`` and
  ``pos ≤ q_start[b] + i`` — exactly the mask of the varlen oracle, so
  partial last pages, null-page placeholders, *and* the causal frontier
  inside the chunk are all handled per element (structural padding rows
  ``i ≥ chunk_lens`` compute defined-but-discarded values, identical to
  the oracle's);
* GQA is handled by grouping: queries arrive as ``(B, KVH, G·C, Dh)``
  and each (slot, kv-head) grid cell serves all ``G`` query heads of
  that KV head, for every chunk row, from one page read — the decode
  kernel's cell with ``G·C`` rows in place of ``G``, so the two share
  their accumulators and block specs.

Why multi-pass (same argument as ``paged_decode.py``): the paper's
Algorithms 1/2 normalize by the *global* row max and the *global* Σe —
piecewise-constant tables do not satisfy the online-softmax rescaling
identity, so the page axis is swept three times with the accumulators
resident across the sequential innermost grid dimension:

  pass 1   row max    m(b,h,i)   = max_p max(q_i·K_pᵀ)           [MXU]
  pass 2   Σ          S(b,h,i)   = Σ_p Σ(e(s, m))                [MXU+VPU]
  pass 3   weighted V out(b,h,i) = Σ_p w(s, m, S) · V_p          [MXU]

``e``/``w`` follow the policy (exact / REXP / 2D-LUT) through the shared
in-kernel helpers (``kernels/common.py``: ``policy_e_terms``,
``rexp_sigma``, ``lut2d_sigma_int``) — bit-identical integer pipeline to
``core.lut_softmax``; only the final f32 V-contraction accumulates
page-chunked instead of row-at-once.

Total traffic per chunk: the live pages once per pass plus O(B·G·C·Dh)
accumulators — no O(B·mp·ps·D) gather and no (B, H, C, Lk) logits tensor
in HBM.  Validated in interpret mode on CPU, and compiled by Mosaic for
a TPU v5e at qwen3-32b widths (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut_builder import Lut2DTables, RexpTables
from repro.kernels.common import (ATTN_PRECISION, NEG_INF, PAGED_PASSES,
                                  pass_name, policy_kernel_tables)
from repro.kernels.lut_attention.paged_decode import (_accum_rowmax,
                                                      _accum_sum,
                                                      _accum_weight,
                                                      _lut_spec, _page_rows,
                                                      _pool_spec, _row_specs,
                                                      _scale_spec)

Array = jax.Array


# ---------------------------------------------------------------------------
# In-kernel helpers
# ---------------------------------------------------------------------------


def _chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale, page_size, chunk,
                  ks_ref=None):
    """(G·C, ps) f32 logits of this (slot, kv-head, page) cell, masked.

    Row ``r = g·C + i`` is query head ``g`` of the group at chunk row
    ``i``.  Key positions are logical: page ``p`` of a slot covers
    absolute positions [p·ps, (p+1)·ps).  A key at ``pos`` is visible to
    chunk row ``i`` (absolute query position ``q_start[b] + i``) iff
    ``pos < kv_lens[b]`` (tail / null-page mask) and
    ``pos ≤ q_start[b] + i`` (causal frontier inside the chunk).
    ``ks_ref`` is the int8 pool's scale block (see
    ``paged_decode._page_rows``).
    """
    b = pl.program_id(0)
    p = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)          # (G·C, Dh)
    k = _page_rows(k_ref, ks_ref)                # (ps, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=ATTN_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qi = qs_ref[b] + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), chunk)
    return jnp.where((pos < kl_ref[b]) & (pos <= qi), s, NEG_INF)


# ---------------------------------------------------------------------------
# The three passes — the decode kernel's accumulators over G·C rows
# ---------------------------------------------------------------------------


def _pf_rowmax_kernel(bt_ref, kl_ref, qs_ref, q_ref, k_ref, m_ref, *, scale,
                      page_size, chunk):
    _accum_rowmax(_chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale,
                                page_size, chunk), m_ref)


def _pf_rowmax_kernel_int8(bt_ref, kl_ref, qs_ref, q_ref, k_ref, ks_ref,
                           m_ref, *, scale, page_size, chunk):
    _accum_rowmax(_chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale,
                                page_size, chunk, ks_ref=ks_ref), m_ref)


def _pf_sum_kernel(bt_ref, kl_ref, qs_ref, q_ref, k_ref, m_ref, lut_ref,
                   s_ref, *, scale, page_size, chunk, method, exp_step,
                   index_mode):
    _accum_sum(_chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale, page_size,
                             chunk),
               m_ref, lut_ref, s_ref, method, exp_step, index_mode)


def _pf_sum_kernel_int8(bt_ref, kl_ref, qs_ref, q_ref, k_ref, ks_ref, m_ref,
                        lut_ref, s_ref, *, scale, page_size, chunk, method,
                        exp_step, index_mode):
    _accum_sum(_chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale, page_size,
                             chunk, ks_ref=ks_ref),
               m_ref, lut_ref, s_ref, method, exp_step, index_mode)


def _pf_weight_kernel(bt_ref, kl_ref, qs_ref, q_ref, k_ref, v_ref, m_ref,
                      s_ref, lut_main_ref, lut_aux_ref, o_ref, *, scale,
                      page_size, chunk, method, qmax, exp_step, scale_ex,
                      scale_sum, index_mode):
    _accum_weight(_chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale,
                                page_size, chunk),
                  _page_rows(v_ref, None), m_ref, s_ref, lut_main_ref,
                  lut_aux_ref, o_ref, method, qmax, exp_step, scale_ex,
                  scale_sum, index_mode)


def _pf_weight_kernel_int8(bt_ref, kl_ref, qs_ref, q_ref, k_ref, ks_ref,
                           v_ref, vs_ref, m_ref, s_ref, lut_main_ref,
                           lut_aux_ref, o_ref, *, scale, page_size, chunk,
                           method, qmax, exp_step, scale_ex, scale_sum,
                           index_mode):
    _accum_weight(_chunk_logits(q_ref, k_ref, kl_ref, qs_ref, scale,
                                page_size, chunk, ks_ref=ks_ref),
                  _page_rows(v_ref, vs_ref), m_ref, s_ref, lut_main_ref,
                  lut_aux_ref, o_ref, method, qmax, exp_step, scale_ex,
                  scale_sum, index_mode)


# ---------------------------------------------------------------------------
# Host-side launcher
# ---------------------------------------------------------------------------


def kernel_name(quantized: bool) -> str:
    """``KernelSpec.name`` of the f32- or int8-pool variant; its passes
    run as ``pass_name(kernel_name(quantized), p)``."""
    return "paged_prefill_int8" if quantized else "paged_prefill"


def _grid_specs(g, c, dh, page_size):
    """The prefill dispatch's BlockSpecs — single source for the launcher
    and for ``kernel_spec`` (the static guard's declaration).  A cell
    holds the G·C query rows of its KV head's group."""
    q_spec, acc_spec, o_spec = _row_specs(g * c, dh)
    return q_spec, _pool_spec(page_size, dh), acc_spec, o_spec


def _build_kernel_spec(geom, quantized):
    import numpy as np

    from repro.analysis.kernel_guard import KernelSpec, Operand, PassSpec
    from repro.core.lut_builder import build_lut2d_tables
    from repro.runtime.paged_cache import pool_shape, scale_pool_shape

    b, h, kvh, dh = geom["b"], geom["h"], geom["kvh"], geom["dh"]
    g = h // kvh
    c = geom["chunk"]
    page_size, mp, n_pages = geom["page_size"], geom["mp"], geom["n_pages"]
    grid = (b, kvh, mp)  # page axis innermost (sequential accumulation)
    q_spec, kv_spec, acc_spec, o_spec = _grid_specs(g, c, dh, page_size)

    bt = np.zeros((b, mp), np.int32)
    bt[:, 1::2] = n_pages - 1  # both domain extremes appear
    kl = np.full((b,), page_size * mp, np.int32)
    qs = np.arange(b, dtype=np.int32) * c  # chunk cursors incl. 0
    prefetch = (bt, kl, qs)

    l2d = build_lut2d_tables("int16")
    lut_main = l2d.lut_exp[None, :]
    # aux slot carries α (rexp, (1,16)) or σ (lut2d); σ (11,60) is worst
    lut_aux = l2d.lut_sigma

    page_dtype = "int8" if quantized else "float32"
    q = Operand("q", (b, kvh, g * c, dh), q_spec)
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
    m = Operand("m", (b, kvh, g * c, 1), acc_spec)
    s = Operand("s_sum", (b, kvh, g * c, 1), acc_spec)
    o = Operand("out", (b, kvh, g * c, dh), o_spec)
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
            notes="int8 pool variant of the chunked prefill: pages stream "
                  "as int8 with per-token f32 scale blocks; dequant in "
                  "VMEM under dequant_scope")
    return KernelSpec(
        name=kernel_name(quantized), module=__name__, kind="pallas",
        passes=passes,
        notes="chunked prefill streaming pages from the pool; causal "
              "frontier handled per element via prefetched q_start")


def kernel_spec(geom):
    """Static declaration for :mod:`repro.analysis.kernel_guard`.

    Uses the launcher's own ``_grid_specs`` / ``_pool_spec``; the probe
    block table exercises both extremes of the declared domain
    ``[0, n_pages)``, ``q_start`` spans 0 and a mid-prompt cursor.
    Table operands use the worst-case (int16 2D-LUT) shapes.
    """
    return _build_kernel_spec(geom, quantized=False)


def kernel_spec_int8(geom):
    """The int8-pool variant's declaration (``paged_prefill_int8``).

    Same grid and accumulators as :func:`kernel_spec`; the K/V page
    operands are int8 and each carries a per-token f32 scale operand
    read through the identical block-table indirection."""
    return _build_kernel_spec(geom, quantized=True)


def paged_prefill_attention(
    q: Array,              # (B, H, C, Dh) chunk queries
    k_pages: Array,        # (num_pages, KVH, page_size, Dh) shared pool
    v_pages: Array,
    block_tables: Array,   # (B, max_pages_per_seq) int32 physical page ids
    q_start: Array,        # (B,) int32 — tokens cached before this chunk
    kv_lens: Array,        # (B,) int32 — valid keys incl. this chunk
    tables: RexpTables | Lut2DTables | None = None,
    *,
    method: str = "exact",          # 'exact' | 'rexp' | 'lut2d'
    scale: float | None = None,
    index_mode: str = "round",
    interpret: bool | None = None,
    k_scales: Array | None = None,  # (num_pages, 1, KVH·page_size) f32
    v_scales: Array | None = None,
) -> Array:
    """Fused paged-prefill attention; returns (B, H, C, Dh) f32.

    ``interpret=None`` resolves per backend: compiled (Mosaic) on TPU,
    interpreter emulation elsewhere — callers never get a silent
    interpreter run on real hardware, and CPU callers never get a
    lowering error.

    ``k_scales``/``v_scales`` (both or neither) select the int8-pool
    variant — same contract as ``paged_decode_attention``: int8 pages
    with per-token × KV-head f32 scales, dequantized in VMEM before the
    identical 3-pass pipeline.

    Numerics match ``ops.lut_attention_prefill_varlen`` on the gathered
    view: identical integer pipeline (bins, e_int, Σ, σ_int); the final
    f32 V-contraction accumulates per page, so outputs agree to f32
    roundoff.  Both dots run at ``ATTN_PRECISION`` (f32), like the
    reference's, so this holds on a TPU too: there the compiled kernel
    may part from the reference only in a row with a logit at a LUT
    bin's rounding edge (``chip_smoke.py`` checks both).  Rows past a
    chunk's valid length carry the same defined-but-garbage values as
    the oracle's (the engine discards them).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scales is not None
    assert quantized == (v_scales is not None), \
        "int8 pool needs both k_scales and v_scales"
    b, h, c, dh = q.shape
    num_pages, kvh, page_size, _ = k_pages.shape
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    mp = block_tables.shape[1]
    scale = scale if scale is not None else dh ** -0.5

    qg = q.reshape(b, kvh, g * c, dh)  # H is KVH-major: rows g·C + i
    block_tables = block_tables.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    q_start = jnp.asarray(q_start, jnp.int32)

    q_spec, kv_spec, acc_spec, o_spec = _grid_specs(g, c, dh, page_size)
    grid = (b, kvh, mp)  # page axis innermost → sequential accumulation

    def spec(in_specs, out_specs):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid,
            in_specs=in_specs, out_specs=out_specs)

    (lut_main, lut_aux, exp_step, qmax, scale_ex,
     scale_sum) = policy_kernel_tables(method, tables)

    geom = dict(scale=scale, page_size=page_size, chunk=c)
    sc_spec = _scale_spec(kvh * page_size)
    # the int8 variants interleave each page's scale block right after it
    k_in = [kv_spec, sc_spec] if quantized else [kv_spec]
    k_ops = (k_pages, k_scales) if quantized else (k_pages,)
    v_in = [kv_spec, sc_spec] if quantized else [kv_spec]
    v_ops = (v_pages, v_scales) if quantized else (v_pages,)
    rowmax_k = _pf_rowmax_kernel_int8 if quantized else _pf_rowmax_kernel
    sum_k = _pf_sum_kernel_int8 if quantized else _pf_sum_kernel
    weight_k = _pf_weight_kernel_int8 if quantized else _pf_weight_kernel
    rowmax_name, sum_name, weight_name = (
        pass_name(kernel_name(quantized), p) for p in PAGED_PASSES)

    # Pass 1: global row max, accumulated online over the page chunks.
    m = pl.pallas_call(
        functools.partial(rowmax_k, **geom),
        grid_spec=spec([q_spec] + k_in, acc_spec),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g * c, 1), jnp.float32),
        interpret=interpret,
        name=rowmax_name,
    )(block_tables, kv_lens, q_start, qg, *k_ops)

    # Pass 2: global Σ of the policy's numerators.
    s_sum = pl.pallas_call(
        functools.partial(sum_k, method=method, exp_step=exp_step,
                          index_mode=index_mode, **geom),
        grid_spec=spec([q_spec] + k_in + [acc_spec, _lut_spec(lut_main)],
                       acc_spec),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g * c, 1), jnp.float32),
        interpret=interpret,
        name=sum_name,
    )(block_tables, kv_lens, q_start, qg, *k_ops, m, lut_main)

    # Pass 3: per-element σ · V, accumulated page by page.
    out = pl.pallas_call(
        functools.partial(weight_k, method=method, qmax=qmax,
                          exp_step=exp_step, scale_ex=scale_ex,
                          scale_sum=scale_sum, index_mode=index_mode,
                          **geom),
        grid_spec=spec([q_spec] + k_in + v_in + [acc_spec, acc_spec,
                        _lut_spec(lut_main), _lut_spec(lut_aux)],
                       o_spec),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g * c, dh), jnp.float32),
        interpret=interpret,
        name=weight_name,
    )(block_tables, kv_lens, q_start, qg, *k_ops, *v_ops, m, s_sum,
      lut_main, lut_aux)

    return out.reshape(b, h, c, dh)
