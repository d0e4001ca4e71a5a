"""Fused paged-decode attention: LUT softmax in-kernel over block tables.

The continuous-batching decode hot loop.  Every slot in the decode batch
holds one single-token query and attends to its own sequence, whose K/V
live scattered across a shared head-major page pool ``(num_pages, KVH,
page_size, Dh)``.  The dense fallback first *gathers* each slot's pages into a
contiguous ``(B, KVH, Lk, D)`` tensor and then materializes full logits
— exactly the memory traffic the paper's LUT approach exists to avoid.
This kernel instead streams pages straight out of the pool:

* the innermost grid axis walks a slot's **block table** in blocks of
  ``P`` pages (:func:`decode_block_pages`: the largest divisor of the
  table's width whose block holds at most 128 keys, one lane row of
  logits).  The pool stays in HBM (``memory_space=pltpu.HBM``); a grid step
  copies its ``P`` pages, whose ids it reads from the scalar-prefetched
  table (``pltpu.PrefetchScalarGridSpec``), into a double-buffered VMEM
  scratch — the contiguous per-slot view never exists;
* the copies are software-pipelined over the whole grid: while one block
  computes, the pages of the next *live* block are already in flight,
  across slot and head boundaries too;
* a block whose first key lies at or past the slot's ``kv_lens`` (also
  scalar-prefetched) issues no copy and runs no compute; inside a live
  block a per-key tail mask invalidates the partial last page and every
  null-page placeholder;
* GQA is handled by grouping: queries arrive as ``(B, KVH, G, Dh)`` and
  each (slot, kv-head) grid cell serves all ``G`` query heads of that KV
  head from one read of each block.

Why multi-pass (same argument as ``lut_attention.py``): the paper's
Algorithms 1/2 normalize by the *global* row max and the *global* Σe —
piecewise-constant tables do not satisfy the online-softmax rescaling
identity, so the classic single-pass flash-decoding trick would change
the numerics.  The block axis is swept three times, with the running
max / Σ accumulated online across blocks in the output refs (their block
index maps are independent of the block axis, so accumulators stay
resident across the sequential innermost grid dimension):

  pass 1   row max    m(b,h)   = max_i max(q·K_iᵀ)              [MXU]
  pass 2   Σ          S(b,h)   = Σ_i Σ(e(s, m))                 [MXU+VPU]
  pass 3   weighted V out(b,h) = Σ_i w(s, m, S) · V_i           [MXU]

where ``e``/``w`` are the policy's semantics — exact ``exp``/softmax, or
the integer LUT pipeline (REXP per-element σ_int requantization, 2D-LUT
σ table read) applied *inside* the kernel via the same binning as
``core.lut_softmax`` (bit-identical integer pipeline; only the final f32
V-contraction accumulates block-chunked instead of row-at-once).

Total traffic per step: the live blocks once per pass plus O(B·G·Dh)
accumulators — no O(B·mp·ps·D) gather and no (B, H, Lk) logits tensor in
HBM.  Validated in interpret mode on CPU, and compiled by Mosaic for a
TPU v5e at qwen3-32b widths (``tests/test_tpu_compile.py``).

The per-page helpers below (``_page_rows``, ``_pool_spec``,
``_scale_spec``) and the accumulators serve the paged-prefill kernel,
which sweeps one page per grid step through BlockSpecs.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut_builder import Lut2DTables, RexpTables
from repro.core.lut_softmax import inv_scale
from repro.kernels.common import (ATTN_PRECISION, MXU_ALIGN, NEG_INF,
                                  PAGED_PASSES, PageCopy, dequant_scope,
                                  lut2d_sigma_int, pass_name, policy_e_terms,
                                  policy_kernel_tables, rexp_sigma)

Array = jax.Array


def decode_block_pages(page_size: int, mp: int) -> int:
    """Pages per grid step of the decode sweep over ``mp``-page block
    tables: the largest divisor of ``mp`` whose block holds at most
    ``MXU_ALIGN`` (128) keys, so a block's logits fill one lane row."""
    cap = max(1, MXU_ALIGN // page_size)
    return max(p for p in range(1, min(cap, mp) + 1) if mp % p == 0)


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


def _block_rows(pages_buf, slot, scales_ref, h):
    """The block in VMEM buffer ``slot`` as (P·ps, Dh) f32 token rows.

    ``scales_ref`` holds the block's ``(1, 1, P, 1, KVH·ps)`` scale rows
    when the pool is int8 (None on a float pool): each row carries every
    KV head's per-token scales, and the ``ps`` lanes of KV head ``h`` in
    each are picked into a column by a masked lane sum, as
    :func:`_head_scales` does for one page.  The int8→f32 upcast happens
    under ``dequant_scope``.
    """
    x = pages_buf[slot]                               # (P, ps, Dh)
    n, ps, dh = x.shape
    if scales_ref is None:
        return x.astype(jnp.float32).reshape(n * ps, dh)
    rows = scales_ref[0, 0]                           # (P, 1, KVH·ps)
    shape = (n, ps, rows.shape[-1])
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    tok = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    cols = jnp.sum(jnp.where(lane == h * ps + tok, rows, 0.0), axis=2,
                   keepdims=True)                     # (P, ps, 1)
    with dequant_scope():  # int8 page rows × per-token scales
        return (x.astype(jnp.float32) * cols).reshape(n * ps, dh)


def _block_logits(q_ref, k, kl_ref, scale, b, i):
    """(G, P·ps) f32 logits of query block ``q_ref`` against the ``k``
    rows of block ``i`` of slot ``b``, tail-masked.

    Key positions are logical: block ``i`` of a slot covers absolute
    positions [i·P·ps, (i+1)·P·ps); everything at or past ``kv_lens[b]``
    — partial-page tails and null-page placeholders — is masked to −inf.
    """
    q = q_ref[0, 0].astype(jnp.float32)          # (G, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=ATTN_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    pos = i * k.shape[0] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(pos < kl_ref[b], s, NEG_INF)


# ---------------------------------------------------------------------------
# The block sweep: page DMAs, double-buffered across grid steps
# ---------------------------------------------------------------------------


class _Stream(NamedTuple):
    """One pool operand's block stream: its HBM pages, their
    double-buffered VMEM scratch with one DMA semaphore per buffer, and
    (int8 pool, else None) the block's scale rows."""

    pages: Any
    pages_buf: Any
    sem: Any
    scales: Any

    def each_copy(self, act, copy, slot, coords, prefetch):
        """``act`` on each page DMA of the block at grid point ``coords``
        into buffer ``slot``."""
        def body(j, carry):
            act(pltpu.make_async_copy(copy.source(self.pages, *coords, j,
                                                  *prefetch),
                                      self.pages_buf.at[slot, j],
                                      self.sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, copy.count, body, 0)

    def rows(self, slot, h):
        return _block_rows(self.pages_buf, slot, self.scales, h)


def _next_live_block(kl_ref, cell, grid, block_keys):
    """Grid point of the first live block after ``cell`` = (b, h, i) in
    sweep order over ``grid``: the next block of this cell, else block 0
    of the next KV head, else of the next slot holding a key.  Slot
    ``n_slots`` when none is left.
    """
    b, h, i = cell
    n_slots, kvh, n_blocks = grid
    zero = jnp.int32(0)

    def next_slot():
        def keyless(c):
            return (c < n_slots) & (kl_ref[jnp.minimum(c, n_slots - 1)] <= 0)
        return jax.lax.while_loop(keyless, lambda c: c + 1, b + 1), zero, zero

    def next_head():
        return jax.lax.cond(h + 1 < kvh, lambda: (b, h + 1, zero), next_slot)

    return jax.lax.cond(
        (i + 1 < n_blocks) & ((i + 1) * block_keys < kl_ref[b]),
        lambda: (b, h, i + 1), next_head)


def _start(copy):
    copy.start()


def _wait(copy):
    copy.wait()


def _sweep(bt_ref, kl_ref, state_ref, streams, copy, acc_ref, acc_init,
           fold):
    """One grid step of a pass: ``acc_ref`` filled with ``acc_init`` at
    block 0 of every (slot, head) cell, then, if the block is live,
    ``fold(slot, (b, h, i))`` once its pages are in VMEM buffer ``slot``.

    Before it waits for its own copies, a live step starts those of the
    next live block into the other buffer, so each block's DMA hides
    behind the compute of the one before.  ``state_ref`` (SMEM) carries
    from step to step the buffer of the next block and whether its
    copies are in flight; only the sweep's first live block starts its
    own.
    """
    # grid reads stay out of the branches (the interpreter lowers them
    # only at the kernel's top level)
    b, h, i = cell = tuple(pl.program_id(a) for a in range(3))
    grid = tuple(pl.num_programs(a) for a in range(3))
    block_keys = copy.count * copy.block_shape[-2]
    prefetch = (bt_ref, kl_ref)

    @pl.when((b == 0) & (h == 0) & (i == 0))
    def _reset():
        state_ref[0] = 0    # buffer of the next live block
        state_ref[1] = 0    # 1 once its copies are in flight

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, acc_init)

    @pl.when(i * block_keys < kl_ref[b])
    def _live():
        slot = state_ref[0]

        @pl.when(state_ref[1] == 0)
        def _first():
            for st in streams:
                st.each_copy(_start, copy, slot, cell, prefetch)

        nb, nh, ni = _next_live_block(kl_ref, cell, grid, block_keys)
        has_next = nb < grid[0]

        @pl.when(has_next)
        def _prefetch():
            for st in streams:
                st.each_copy(_start, copy, 1 - slot, (nb, nh, ni), prefetch)

        state_ref[0] = 1 - slot
        state_ref[1] = has_next.astype(jnp.int32)
        for st in streams:
            st.each_copy(_wait, copy, slot, cell, prefetch)
        fold(slot, cell)


# ---------------------------------------------------------------------------
# Pass 1 — global row max (online across blocks)
# ---------------------------------------------------------------------------


def _fold_rowmax(s, m_ref):
    m_ref[0, 0] = jnp.maximum(m_ref[0, 0], jnp.max(s, axis=-1, keepdims=True))


def _accum_rowmax(s, m_ref):
    """Fold the (R, C) logits tile into the (1, 1, R, 1) row-max block.

    Row statistics live in (R, 1) columns throughout (accumulator blocks
    included) so they broadcast over the tile's lanes without a
    sublane↔lane relayout — the form Mosaic lowers.
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    _fold_rowmax(s, m_ref)


def _dc_rowmax_kernel(bt_ref, kl_ref, q_ref, k_ref, ks_ref, m_ref, kb_ref,
                      k_sem, state_ref, *, scale, copy):
    k = _Stream(k_ref, kb_ref, k_sem, ks_ref)

    def fold(slot, cell):
        b, h, i = cell
        _fold_rowmax(_block_logits(q_ref, k.rows(slot, h), kl_ref, scale, b,
                                   i), m_ref)

    _sweep(bt_ref, kl_ref, state_ref, (k,), copy, m_ref, NEG_INF, fold)


# ---------------------------------------------------------------------------
# Pass 2 — Σ numerators (online across blocks)
# ---------------------------------------------------------------------------


def _fold_sum(s, m_ref, lut_ref, s_ref, method, exp_step, index_mode):
    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = policy_e_terms(s, m, lut_ref[0, :], method, exp_step, index_mode)
    with dequant_scope():  # f32-exact integer Σ accumulator
        s_ref[0, 0] += jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)


def _accum_sum(s, m_ref, lut_ref, s_ref, method, exp_step, index_mode):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    _fold_sum(s, m_ref, lut_ref, s_ref, method, exp_step, index_mode)


def _dc_sum_kernel(bt_ref, kl_ref, q_ref, k_ref, ks_ref, m_ref, lut_ref,
                   s_ref, kb_ref, k_sem, state_ref, *, scale, copy, method,
                   exp_step, index_mode):
    k = _Stream(k_ref, kb_ref, k_sem, ks_ref)

    def fold(slot, cell):
        b, h, i = cell
        _fold_sum(_block_logits(q_ref, k.rows(slot, h), kl_ref, scale, b, i),
                  m_ref, lut_ref, s_ref, method, exp_step, index_mode)

    _sweep(bt_ref, kl_ref, state_ref, (k,), copy, s_ref, 0.0, fold)


# ---------------------------------------------------------------------------
# Pass 3 — per-element σ · V (faithful requantization, online across blocks)
# ---------------------------------------------------------------------------


def _fold_weight(s, v, m_ref, s_ref, lut_main_ref, lut_aux_ref, o_ref,
                 method, qmax, exp_step, scale_ex, scale_sum, index_mode):
    """out += σ(s, m, S) @ V_block with the policy's per-element weights
    — REXP re-quantizes σ_int per element (Algorithm 1 line 11), 2D-LUT
    reads LUT_σ[i(e), j(S)] (Algorithm 2), exact divides by S."""
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


def _accum_weight(s, v, m_ref, s_ref, lut_main_ref, lut_aux_ref, o_ref,
                  method, qmax, exp_step, scale_ex, scale_sum, index_mode):
    """Accumulate out += σ(s, m, S) @ V_page (see :func:`_fold_weight`),
    zeroing the output block at the first page."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _fold_weight(s, v, m_ref, s_ref, lut_main_ref, lut_aux_ref, o_ref,
                 method, qmax, exp_step, scale_ex, scale_sum, index_mode)


def _dc_weight_kernel(bt_ref, kl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                      m_ref, s_ref, lut_main_ref, lut_aux_ref, o_ref, kb_ref,
                      k_sem, vb_ref, v_sem, state_ref, *, scale, copy, method,
                      qmax, exp_step, scale_ex, scale_sum, index_mode):
    k = _Stream(k_ref, kb_ref, k_sem, ks_ref)
    v = _Stream(v_ref, vb_ref, v_sem, vs_ref)

    def fold(slot, cell):
        b, h, i = cell
        _fold_weight(_block_logits(q_ref, k.rows(slot, h), kl_ref, scale, b,
                                   i),
                     v.rows(slot, h), m_ref, s_ref, lut_main_ref, lut_aux_ref,
                     o_ref, method, qmax, exp_step, scale_ex, scale_sum,
                     index_mode)

    _sweep(bt_ref, kl_ref, state_ref, (k, v), copy, o_ref, 0.0, fold)


# ---------------------------------------------------------------------------
# Host-side launcher
# ---------------------------------------------------------------------------


# Index maps take the grid point and then the scalar-prefetch refs, block
# table first; ``*_`` absorbs the rest, so the paged-prefill launcher (which
# prefetches one more array) shares these specs.


def _pool_spec(page_size, dh):
    """The paged-prefill sweep's pool block: one physical page of one KV
    head per grid step — a contiguous (ps, Dh) slab of the head-major
    pool; the page id comes from the scalar-prefetched block table — the
    paged-pool indirection itself."""
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


def _block_copy(page_size, dh, pages):
    """The decode sweep's page DMAs — single source for the kernel and
    for ``kernel_spec``: the ``j``-th of a block's ``pages`` pages of one
    KV head, a contiguous (ps, Dh) slab of the head-major pool."""
    def page(b, h, i, j, bt_ref, *_):
        return bt_ref[b, i * pages + j], h, 0, 0

    return PageCopy((1, 1, page_size, dh), pages, page)


def _block_scales(scales, block_tables, pages):
    """The int8 pool's scale rows by block: ``(B, n_blocks, P, 1,
    KVH·ps)``, gathered through the block tables before the kernel.  A
    row is a fraction of a lane tile wherever KVH·ps < 128, which Mosaic
    copies only as a whole BlockSpec block, never as a DMA slice."""
    b, mp = block_tables.shape
    return scales[block_tables].reshape(b, mp // pages, pages,
                                        *scales.shape[1:])


def _block_scale_spec(pages, row):
    """Block ``i`` of slot ``b`` in :func:`_block_scales`' array — every
    KV head's scale rows of its ``pages`` pages (``row`` = KVH·ps)."""
    return pl.BlockSpec((1, 1, pages, 1, row),
                        lambda b, h, i, *_: (b, i, 0, 0, 0))


def _stream_scratch(name, page_dtype, pages, page_size, dh):
    """One block stream's scratch as (name, ``scratch_shapes`` entry)
    pairs — single source for the launcher and for ``kernel_spec``: the
    double-buffered pages and one DMA semaphore per buffer."""
    return ((f"{name}_buf", pltpu.VMEM((2, pages, page_size, dh), page_dtype)),
            (f"{name}_sems", pltpu.SemaphoreType.DMA((2,))))


#: the sweep's carried state (SMEM): next block's buffer, copies in flight
_SWEEP_STATE = ("sweep_state", pltpu.SMEM((2,), jnp.int32))


def _build_kernel_spec(geom, quantized):
    import numpy as np

    from repro.analysis.kernel_guard import (KernelSpec, Operand, PassSpec,
                                             Scratch)
    from repro.core.lut_builder import build_lut2d_tables
    from repro.runtime.paged_cache import pool_shape, scale_pool_shape

    b, h, kvh, dh = geom["b"], geom["h"], geom["kvh"], geom["dh"]
    g = h // kvh
    page_size, mp, n_pages = geom["page_size"], geom["mp"], geom["n_pages"]
    pages = decode_block_pages(page_size, mp)
    grid = (b, kvh, mp // pages)  # block axis innermost (sequential)
    q_spec, acc_spec, o_spec = _row_specs(g, dh)
    row = scale_pool_shape(n_pages, page_size, kvh)[-1]
    page_copy = _block_copy(page_size, dh, pages)
    sc_spec = _block_scale_spec(pages, row)

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
                 page_copy, page_dtype, table_indexed=True,
                 index_domain=(0, n_pages))
    vv = Operand("v_pages", pool_shape(n_pages, page_size, kvh, dh),
                 page_copy, page_dtype, table_indexed=True,
                 index_domain=(0, n_pages))
    sc_shape = (b, mp // pages, pages, 1, row)
    ks = Operand("k_scales", sc_shape, sc_spec)
    vs = Operand("v_scales", sc_shape, sc_spec)
    kk = (kv, ks) if quantized else (kv,)
    vvv = (vv, vs) if quantized else (vv,)
    m = Operand("m", (b, kvh, g, 1), acc_spec)
    s = Operand("s_sum", (b, kvh, g, 1), acc_spec)
    o = Operand("out", (b, kvh, g, dh), o_spec)
    t_main = Operand("lut_main", lut_main.shape, _lut_spec(lut_main), "int32")
    t_aux = Operand("lut_aux", lut_aux.shape, _lut_spec(lut_aux), "int32")

    def scratch(*names):
        entries = [e for n in names for e in _stream_scratch(
            n, jnp.dtype(page_dtype), pages, page_size, dh)] + [_SWEEP_STATE]
        return tuple(Scratch(n, ref) for n, ref in entries)

    rowmax, sum_, weight = PAGED_PASSES
    passes = (
        PassSpec(rowmax, grid, (q,) + kk, (m,), scalar_prefetch=prefetch,
                 scratch=scratch("k")),
        PassSpec(sum_, grid, (q,) + kk + (m, t_main), (s,),
                 scalar_prefetch=prefetch, sigma_acc=True,
                 acc_dtype="float32", scratch=scratch("k"),
                 notes="integer Σ accumulated f32-exact in the resident ref"),
        PassSpec(weight, grid, (q,) + kk + vvv + (m, s, t_main, t_aux),
                 (o,), scalar_prefetch=prefetch, scratch=scratch("k", "v")),
    )
    if quantized:
        return KernelSpec(
            name=kernel_name(quantized), module=__name__, kind="pallas",
            passes=passes,
            notes="int8 pool variant: int8 pages copied as in the f32 "
                  "kernel, their per-token f32 scale rows gathered by "
                  "block before the kernel and streamed as a BlockSpec; "
                  "dequant in VMEM under dequant_scope")
    return KernelSpec(
        name=kernel_name(quantized), module=__name__, kind="pallas",
        passes=passes,
        notes="copies blocks of pages from the pool through "
              "scalar-prefetched block tables into double-buffered VMEM "
              "scratch; blocks past kv_lens are skipped")


def kernel_spec(geom):
    """Static declaration for :mod:`repro.analysis.kernel_guard`.

    Uses the launcher's own ``_row_specs`` / ``_block_copy`` /
    ``_stream_scratch``; the scalar-prefetch probe arrays exercise both
    extremes of the declared block-table domain ``[0, n_pages)`` (0 is
    the null-page placeholder, the allocator issues ids in ``[1,
    n_pages)``), so the in-range check is a clamp proof for the pool
    indirection.  Table operands use the worst-case (int16 2D-LUT)
    shapes.
    """
    return _build_kernel_spec(geom, quantized=False)


def kernel_spec_int8(geom):
    """The int8-pool variant's declaration (``paged_decode_int8``).

    Same grid and accumulators as :func:`kernel_spec`; the K/V page
    operands are int8 and each carries a per-token f32 scale operand,
    its rows gathered by block (``_block_scales``) — the guard proves the
    streamed working set shrinks to ~¼ of the f32 pages.
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
    f32 scale per KV head; the scale rows are copied with their pages
    and the rows are dequantized in VMEM (``_block_rows``) before the
    identical 3-pass pipeline — halved page traffic, same integer LUT
    semantics.

    Numerics match ``ops.lut_attention_decode_varlen`` on the gathered
    view: identical integer pipeline (bins, e_int, Σ, σ_int); the final
    f32 V-contraction accumulates per block of
    :func:`decode_block_pages` pages, so outputs agree to f32 roundoff.
    Both dots run at ``ATTN_PRECISION`` (f32), like the reference's, so
    this holds on a TPU too: there the compiled kernel may part from the
    reference only in a row with a logit at a LUT bin's rounding edge
    (``chip_smoke.py`` checks both).
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

    pages = decode_block_pages(page_size, mp)
    grid = (b, kvh, mp // pages)  # block axis innermost → sequential
    q_spec, acc_spec, o_spec = _row_specs(g, dh)
    copy = _block_copy(page_size, dh, pages)

    def stream_scratch(name):
        return [ref for _, ref in _stream_scratch(name, k_pages.dtype, pages,
                                                  page_size, dh)]

    def spec(in_specs, out_specs, streams):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[r for n in streams for r in stream_scratch(n)]
            + [_SWEEP_STATE[1]])

    # the sweep carries its copies from step to step: every axis in order
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3)

    (lut_main, lut_aux, exp_step, qmax, scale_ex,
     scale_sum) = policy_kernel_tables(method, tables)

    geom = dict(scale=scale, copy=copy)
    # the pool stays in HBM (``pl.ANY`` would let Mosaic put a small one
    # in VMEM); each pool operand sits beside its scale rows by block
    # (None on a float pool)
    pool_in = [pl.BlockSpec(memory_space=pltpu.HBM),
               _block_scale_spec(pages, kvh * page_size) if quantized
               else None]
    if quantized:
        k_scales, v_scales = (_block_scales(x, block_tables, pages)
                              for x in (k_scales, v_scales))
    k_ops = (k_pages, k_scales)
    v_ops = (v_pages, v_scales)
    rowmax_name, sum_name, weight_name = (
        pass_name(kernel_name(quantized), p) for p in PAGED_PASSES)

    # Pass 1: global row max, accumulated online over the blocks.
    m = pl.pallas_call(
        functools.partial(_dc_rowmax_kernel, **geom),
        grid_spec=spec([q_spec] + pool_in, acc_spec, ("k",)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name=rowmax_name,
    )(block_tables, kv_lens, qg, *k_ops)

    # Pass 2: global Σ of the policy's numerators.
    s_sum = pl.pallas_call(
        functools.partial(_dc_sum_kernel, method=method, exp_step=exp_step,
                          index_mode=index_mode, **geom),
        grid_spec=spec([q_spec] + pool_in + [acc_spec, _lut_spec(lut_main)],
                       acc_spec, ("k",)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name=sum_name,
    )(block_tables, kv_lens, qg, *k_ops, m, lut_main)

    # Pass 3: per-element σ · V, accumulated block by block.
    out = pl.pallas_call(
        functools.partial(_dc_weight_kernel, method=method, qmax=qmax,
                          exp_step=exp_step, scale_ex=scale_ex,
                          scale_sum=scale_sum, index_mode=index_mode,
                          **geom),
        grid_spec=spec([q_spec] + pool_in + pool_in
                       + [acc_spec, acc_spec, _lut_spec(lut_main),
                          _lut_spec(lut_aux)], o_spec, ("k", "v")),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name=weight_name,
    )(block_tables, kv_lens, qg, *k_ops, *v_ops, m, s_sum, lut_main,
      lut_aux)

    return out.reshape(b, h, 1, dh)
