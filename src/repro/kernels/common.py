"""Shared Pallas kernel utilities: VMEM budgeting, padding, in-kernel LUT reads.

TPU-native LUT lookup
---------------------
The paper's tables are tiny (≤ 1.5 KB) but TPUs have no cheap per-lane
arbitrary gather.  Three lowerings, chosen per table size:

* ``select`` — unrolled select-chain ``Σ_l (idx == l) · lut[l]``: L fused
  VPU select-madds per element.  For the REXP tables (L ≤ 13) and the
  α/σ tables this is essentially free and needs no gather support at all
  (this is the piecewise-constant LUT re-expressed as predication — the
  TPU-native analogue of the paper's MSB wiring).
* ``gather`` — ``jnp.take``; for XLA paths and interpret mode only:
  Mosaic lowers no 1-D gather, so the LUT-attention kernels always
  read through ``select``.
* one-hot × LUT on the MXU is numerically identical to ``select`` and is
  what ``select`` amortizes into when XLA vectorizes the chain; see
  DESIGN.md §2 for the napkin math.

All three produce bit-identical int32 results (property-tested).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = float("-inf")

#: Trace-time tags for the paper's integer-Σ LUT datapath.  ``jax.named_scope``
#: pushes these onto every equation's ``source_info.name_stack`` at zero
#: runtime cost; :mod:`repro.analysis.jaxpr_lint` treats integer outputs of
#: LUT_INT_TAG-scoped equations as taint roots and only accepts int→float
#: ``convert_element_type`` on tainted values inside a LUT_DEQUANT_TAG scope —
#: so any *new* silent upcast of the integer pipeline fails the contracts.
LUT_INT_TAG = "lut_int_sigma"
LUT_DEQUANT_TAG = "lut_dequant"


def lut_int_scope():
    """Scope whose integer results are LUT-datapath taint roots."""
    return jax.named_scope(LUT_INT_TAG)


def dequant_scope():
    """Scope sanctioning an intentional int→float dequant/accumulate."""
    return jax.named_scope(LUT_DEQUANT_TAG)


#: the paged kernels' passes, in launch order: the ``PassSpec`` names of
#: their guard declarations and, via :func:`pass_name`, the ``name=`` of
#: each ``pallas_call`` (the op name a profiler trace shows)
PAGED_PASSES = ("rowmax", "sum", "weight")


def pass_name(kernel: str, pass_: str) -> str:
    """Device name of one pass: ``<KernelSpec.name>_<PassSpec.name>``,
    e.g. ``paged_decode_int8_rowmax``."""
    return f"{kernel}_{pass_}"


@dataclasses.dataclass(frozen=True)
class PageCopy:
    """The DMAs a kernel issues itself from an HBM operand
    (``memory_space=pltpu.HBM``): ``count`` blocks of ``block_shape`` per
    grid step, the ``j``-th at block index ``index_map(*grid_point, j,
    *scalar_prefetch)``.  The block's last two dims are whole, so a copy
    reads ``src.at[index[:-2]]``.  The kernel issues its copies from this
    declaration, and ``analysis.kernel_guard`` proves their indices in
    range from the same one."""

    block_shape: tuple
    count: int
    index_map: Callable

    def source(self, src, *coords):
        """The ref slice the copy at ``coords`` (grid point, ``j``,
        scalar-prefetch refs) reads."""
        return src.at[tuple(self.index_map(*coords)[:-2])]


#: per-kernel VMEM working-set budget (bytes): the scoped VMEM limit the
#: kernels compile under.  They pass no ``vmem_limit_bytes``, so Mosaic
#: applies the compiler's default, 16 MiB on TPU v5e — an ahead-of-time
#: v5e compile refuses a pass needing more ("Scoped allocation with size
#: 16.01M and limit 16.00M exceeded"), although the core has 128 MiB.
VMEM_BUDGET = 16 * 1024 * 1024

#: fraction of :data:`VMEM_BUDGET` the static kernel guard keeps free:
#: ``analysis.kernel_guard`` asserts every kernel pass's derived working
#: set (streamed operands double-buffered) stays under
#: ``VMEM_BUDGET * (1 - VMEM_GUARD_HEADROOM)`` at every dispatch
#: geometry, leaving room for Mosaic spills and semaphore state.
VMEM_GUARD_HEADROOM = 0.25

#: precision of every attention product (the QK logits and σ·V) in the
#: LUT-attention kernels and their dense references.  At the default
#: precision a TPU rounds f32 operands to one bf16 pass, and the LUT
#: policies' discrete bins turn that rounding into different table
#: entries (one bin is a whole step of the softmax numerator).  HIGHEST
#: keeps the f32 semantics the CPU suites check — on the CPU it changes
#: nothing, on a TPU the MXU runs the f32 contraction in several passes.
ATTN_PRECISION = jax.lax.Precision.HIGHEST

MXU_ALIGN = 128  # MXU systolic dims; block shapes are multiples of this
SUBLANE = 8


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_axis_to(x: Array, axis: int, size: int, value: float) -> Array:
    """Pad ``axis`` of ``x`` up to ``size`` with ``value``."""
    cur = x.shape[axis]
    if cur == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - cur)
    return jnp.pad(x, pads, constant_values=value)


def select_lookup(lut: Array, idx: Array) -> Array:
    """Unrolled select-chain LUT read (TPU-native; no gather primitive).

    ``lut`` is a small 1-D int32 table (compile-time length); ``idx`` is an
    int32 array of clamped indices.  Emits ``len(lut)`` vector selects.
    """
    n = lut.shape[0]
    with lut_int_scope():
        acc = jnp.zeros(idx.shape, dtype=jnp.int32)
        for l in range(n):
            acc = jnp.where(idx == l, lut[l], acc)
        return acc


def kernel_lookup(lut: Array, idx: Array, impl: str) -> Array:
    """In-kernel LUT read dispatch ('select' | 'gather')."""
    if impl == "select":
        return select_lookup(lut, idx)
    if impl == "gather":
        with lut_int_scope():
            return jnp.take(lut, idx, axis=0)
    raise ValueError(f"unknown in-kernel lookup impl {impl!r}")


def policy_e_terms(s: Array, m_col: Array, lut_main: Array, method: str,
                   exp_step: float, index_mode: str,
                   lookup: str = "select") -> Array:
    """Per-element numerators given the global row max ``m_col`` (R, 1),
    shared by the paged-decode and paged-prefill pass-2/3 kernels.

    ``s`` (R, C) are tail-masked f32 logits;
    exact  → f32 ``exp(s − m)``;
    rexp   → int  ``LUT_1/e[bin(m − s)]``;
    lut2d  → int  ``LUT_exp[bin((m − s)/step)]``.
    Masked (−inf) logits yield hard zeros, never the terminal LUT entry.
    """
    finite = jnp.isfinite(s)
    if method == "exact":
        return jnp.where(finite, jnp.exp(s - m_col), 0.0)
    n = lut_main.shape[0]
    d = m_col - s
    if method == "lut2d":
        from repro.core.lut_softmax import inv_scale
        d = d * inv_scale(exp_step)
    d = jnp.where(finite, d, float(n - 1))
    rnd = jnp.round if index_mode == "round" else jnp.floor
    idx = jnp.clip(rnd(d).astype(jnp.int32), 0, n - 1)
    return jnp.where(finite, kernel_lookup(lut_main, idx, lookup), 0)


def policy_kernel_tables(method: str, tables):
    """Device-ready LUT operands for the paged kernels' pallas_call chain.

    Returns ``(lut_main, lut_aux, exp_step, qmax, scale_ex, scale_sum)``
    — the main table is shipped ``(1, N)`` so a single BlockSpec shape
    covers every policy; ``exact`` flows 1-entry placeholders through the
    same signature so the three passes share one code path.
    """
    from repro.core.lut_builder import Lut2DTables, RexpTables
    if method == "rexp":
        assert isinstance(tables, RexpTables)
        lut_main = jnp.asarray(tables.lut_recip_exp, jnp.int32)[None, :]
        lut_aux = jnp.asarray(tables.lut_alpha, jnp.int32)[None, :]
        return lut_main, lut_aux, 1.0, tables.precision.qmax, 0.0, 0.0
    if method == "lut2d":
        assert isinstance(tables, Lut2DTables)
        lut_main = jnp.asarray(tables.lut_exp, jnp.int32)[None, :]
        lut_aux = jnp.asarray(tables.lut_sigma, jnp.int32)
        return (lut_main, lut_aux, tables.exp_step, tables.precision.qmax,
                tables.scale_ex, tables.scale_sum)
    if method == "exact":
        return (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32),
                1.0, 1, 0.0, 0.0)
    raise ValueError(f"unsupported paged-kernel method {method!r}")


def rexp_sigma(e_int: Array, s_col: Array, lut_alpha: Array, qmax: int,
               index_mode: str, lookup: str = "select") -> Array:
    """Faithful Algorithm 1 per-element σ_int (pre-dequant), shared by the
    blocked-attention and paged-decode pass-3 kernels.

    ``e_int`` (R, C) integer numerators of a tile; ``s_col`` (R, 1) the
    *global* integer Σ of each row (f32-exact); returns f32 σ_int values
    ``round(e·α/qmax)`` ≤ qmax — callers dequantize by 1/qmax.
    """
    from repro.core.lut_softmax import inv_scale
    inv = inv_scale(qmax)
    n_a = lut_alpha.shape[0]
    rnd = jnp.round if index_mode == "round" else jnp.floor
    ja = jnp.clip(rnd(s_col * inv).astype(jnp.int32), 0, n_a - 1)
    alpha = kernel_lookup(lut_alpha, ja, lookup)  # (R, 1)
    with dequant_scope():  # e·α requantizes by 1/qmax: the sanctioned exit
        prod = (e_int * alpha).astype(jnp.float32)
    return jnp.round(prod * inv)


def lut2d_sigma_int(e_int: Array, s_col: Array, lut_sigma: Array, qmax: int,
                    scale_ex: float, scale_sum: float, index_mode: str) -> Array:
    """Algorithm 2 per-element σ_int via the 2-D table, shared by the
    blocked-attention and paged-decode pass-3 kernels.

    ``s_col`` (R, 1) is the global Σ of each row.  Gather-free: the
    column is selected per row, then the row per element, through
    unrolled predication (the TPU-native analogue of the paper's MSB
    wiring).  Returns int32 σ_int ≤ qmax.
    """
    from repro.core.lut_softmax import inv_scale
    n_rows, n_cols = lut_sigma.shape
    rnd = jnp.round if index_mode == "round" else jnp.floor
    with dequant_scope():  # MSB addressing, not a value escape
        e_f32 = e_int.astype(jnp.float32)
    i_idx = jnp.clip(rnd(e_f32 * inv_scale(qmax * scale_ex))
                     .astype(jnp.int32), 0, n_rows - 1)
    j_idx = jnp.clip(rnd(s_col * inv_scale(qmax * scale_sum))
                     .astype(jnp.int32), 1, n_cols) - 1  # (R, 1)
    with lut_int_scope():
        sel_col = jnp.zeros((e_int.shape[0], n_rows), dtype=jnp.int32)
        for j in range(n_cols):
            sel_col = jnp.where(j_idx == j, lut_sigma[:, j][None, :],
                                sel_col)
        sigma_int = jnp.zeros(e_int.shape, dtype=jnp.int32)
        for i in range(n_rows):
            sigma_int = jnp.where(i_idx == i, sel_col[:, i:i + 1],
                                  sigma_int)
        return sigma_int


def pick_block_rows(n_cols: int, target_bytes: int = 4 * 1024 * 1024,
                    max_rows: int = 1024) -> int:
    """Row-block size so a (rows, n_cols) f32 tile fits ``target_bytes``."""
    rows = max(SUBLANE, target_bytes // max(n_cols * 4, 1))
    rows = min(int(rows), max_rows)
    return max(SUBLANE, rows // SUBLANE * SUBLANE)
