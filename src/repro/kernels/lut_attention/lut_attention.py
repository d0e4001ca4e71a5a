"""Fused LUT-attention Pallas kernels (the paper's technique inside flash-
style blocked attention).

Why multi-pass: the paper's Algorithms 1/2 normalize by the *global* row
max and the *global* Σe (piecewise-constant tables do not satisfy the
online-softmax rescaling identity `e^{x-m_new} = e^{x-m_old}·e^{m_old-m_new}`
exactly, so the classic single-pass flash trick would change the numerics).
We therefore sweep the K blocks:

  pass 1   row max        m(q)    = max_k (q·kᵀ)                    [MXU]
  pass 2   LUT numerators S(q)    = Σ_k LUT[bin(m − s)]             [MXU+VPU]
  pass 3   weighted V     out(q)  = Σ_k σ_int(s, S) · v             [MXU]

``fused_requant=True`` merges passes 2 and 3 (accumulate U = Σ e_int·v and
S together; apply α to U in the epilogue).  That saves one full QKᵀ sweep
(per-token FLOPs 4·L·D → 3·L·D) at the cost of skipping the per-element
w-bit σ re-quantization — the *beyond-paper* serving configuration, and
one of the §Perf hillclimb levers.  Both variants never materialize the
L×L matrix in HBM.

Everything is VMEM-blocked: q (BQ,D), k/v (BK,D), logits tile (BQ,BK),
LUTs ≤ 1.5 KB replicated per grid step.  Accumulators live in the output
refs (block index maps are independent of the K grid dimension, so the
blocks stay resident across the sequential innermost grid axis).

GQA is handled in the index maps (query head h reads KV head h // group).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lut_builder import Lut2DTables, RexpTables
from repro.core.lut_softmax import inv_scale
from repro.kernels.common import (ATTN_PRECISION, lut2d_sigma_int,
                                  pad_axis_to, rexp_sigma, round_up,
                                  select_lookup)

Array = jax.Array

NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# In-kernel helpers
# ---------------------------------------------------------------------------


def _block_logits(q_ref, k_ref, scale, causal, lq, lk, lk_valid, bq, bk):
    """(BQ, BK) f32 logits tile with causal/padding masking applied."""
    q = q_ref[0, 0].astype(jnp.float32)  # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=ATTN_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    ki = kb * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = ki < lk_valid  # mask padded KV positions
    if causal:
        qi = (qb * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
              + (lk_valid - lq))  # right-aligned queries
        mask = mask & (ki <= qi)
    return jnp.where(mask, s, NEG_INF)


def _rexp_e_int(s, m, lut_re, index_mode):
    """REXP numerators for a logits tile given the (global) row max.

    Masked (-inf) logits — causal or KV padding — yield hard zeros, never
    the terminal LUT entry (non-zero in some published table lengths).
    """
    n = lut_re.shape[0]
    finite = jnp.isfinite(s)
    d = jnp.where(finite, m[:, None] - s, float(n - 1))
    rnd = jnp.round if index_mode == "round" else jnp.floor
    idx = jnp.clip(rnd(d).astype(jnp.int32), 0, n - 1)
    return jnp.where(finite, select_lookup(lut_re, idx), 0)


def _lut2d_e_int(s, m, lut_e, exp_step, index_mode):
    """2D-LUT numerators for a logits tile given the (global) row max."""
    n = lut_e.shape[0]
    finite = jnp.isfinite(s)
    d = jnp.where(finite, (m[:, None] - s) * inv_scale(exp_step),
                  float(n - 1))
    rnd = jnp.round if index_mode == "round" else jnp.floor
    idx = jnp.clip(rnd(d).astype(jnp.int32), 0, n - 1)
    return jnp.where(finite, select_lookup(lut_e, idx), 0)


# ---------------------------------------------------------------------------
# Pass 1 — row max
# ---------------------------------------------------------------------------


def _rowmax_kernel(q_ref, k_ref, m_ref, *, scale, causal, lq, lk, lk_valid,
                   bq, bk):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    s = _block_logits(q_ref, k_ref, scale, causal, lq, lk, lk_valid, bq, bk)
    m_ref[0, 0] = jnp.maximum(m_ref[0, 0], jnp.max(s, axis=-1))


# ---------------------------------------------------------------------------
# Pass 2 — Σ e_int   (and pass 2' — fused Σ e_int & U = Σ e_int·v)
# ---------------------------------------------------------------------------


def _sum_kernel(q_ref, k_ref, m_ref, lut_ref, s_ref, *, scale, causal,
                lq, lk, lk_valid, bq, bk, method, exp_step, index_mode):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = _block_logits(q_ref, k_ref, scale, causal, lq, lk, lk_valid, bq, bk)
    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    lut = lut_ref[0, :]
    if method == "rexp":
        e_int = _rexp_e_int(s, m, lut, index_mode)
    else:
        e_int = _lut2d_e_int(s, m, lut, exp_step, index_mode)
    s_ref[0, 0] += jnp.sum(e_int.astype(jnp.float32), axis=-1)


def _fused_sum_av_kernel(q_ref, k_ref, v_ref, m_ref, lut_re_ref, lut_a_ref,
                         s_ref, o_ref, *, scale, causal, lq, lk, lk_valid,
                         bq, bk, qmax, index_mode):
    """REXP fused variant: accumulate S and U = Σ e_int·v; epilogue applies
    α·inv² to U (beyond-paper — skips per-element σ requantization)."""
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    s = _block_logits(q_ref, k_ref, scale, causal, lq, lk, lk_valid, bq, bk)
    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e_int = _rexp_e_int(s, m, lut_re_ref[0, :], index_mode)
    e_f = e_int.astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s_ref[0, 0] += jnp.sum(e_f, axis=-1)
    o_ref[0, 0] += jax.lax.dot_general(e_f, v, (((1,), (0,)), ((), ())),
                                       precision=ATTN_PRECISION,
                                       preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _epilogue():
        inv = inv_scale(qmax)
        n_a = lut_a_ref.shape[1]
        rnd = jnp.round if index_mode == "round" else jnp.floor
        ja = jnp.clip(rnd(s_ref[0, 0] * inv).astype(jnp.int32), 0, n_a - 1)
        alpha = select_lookup(lut_a_ref[0, :], ja)
        o_ref[0, 0] *= (alpha.astype(jnp.float32) * inv * inv)[:, None]


# ---------------------------------------------------------------------------
# Pass 3 — faithful σ_int · V
# ---------------------------------------------------------------------------


def _rexp_av_kernel(q_ref, k_ref, v_ref, m_ref, s_ref, lut_re_ref, lut_a_ref,
                    o_ref, *, scale, causal, lq, lk, lk_valid, bq, bk, qmax,
                    index_mode):
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = _block_logits(q_ref, k_ref, scale, causal, lq, lk, lk_valid, bq, bk)
    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e_int = _rexp_e_int(s, m, lut_re_ref[0, :], index_mode)

    # Faithful Algorithm 1: per-element w-bit σ requantization, THEN ·v.
    sigma_int = rexp_sigma(e_int, s_ref[0, 0][:, None], lut_a_ref[0, :],
                           qmax, index_mode)
    v = v_ref[0, 0].astype(jnp.float32)
    o_ref[0, 0] += jax.lax.dot_general(sigma_int, v, (((1,), (0,)), ((), ())),
                                       precision=ATTN_PRECISION,
                                       preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _dequant():
        o_ref[0, 0] *= inv_scale(qmax)


def _lut2d_av_kernel(q_ref, k_ref, v_ref, m_ref, s_ref, lut_e_ref, lut_s_ref,
                     o_ref, *, scale, causal, lq, lk, lk_valid, bq, bk, qmax,
                     exp_step, scale_ex, scale_sum, index_mode):
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = _block_logits(q_ref, k_ref, scale, causal, lq, lk, lk_valid, bq, bk)
    m = m_ref[0, 0]
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e_int = _lut2d_e_int(s, m, lut_e_ref[0, :], exp_step, index_mode)

    sigma_int = lut2d_sigma_int(e_int, s_ref[0, 0][:, None], lut_s_ref[...],
                                qmax, scale_ex, scale_sum, index_mode)

    v = v_ref[0, 0].astype(jnp.float32)
    o_ref[0, 0] += jax.lax.dot_general(
        sigma_int.astype(jnp.float32), v, (((1,), (0,)), ((), ())),
        precision=ATTN_PRECISION, preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _dequant():
        o_ref[0, 0] *= inv_scale(qmax)


# ---------------------------------------------------------------------------
# Host-side launcher
# ---------------------------------------------------------------------------


def _specs(b, h, kvh, lq, lk, d, bq, bk):
    g = h // kvh
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    k_spec = pl.BlockSpec((1, 1, bk, d),
                          lambda bi, hi, qi, ki: (bi, hi // g, ki, 0))
    v_spec = k_spec
    m_spec = pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, ki: (bi, hi, qi))
    o_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    return q_spec, k_spec, v_spec, m_spec, o_spec


def _lut_spec(arr):
    nd = arr.ndim
    return pl.BlockSpec(arr.shape, lambda bi, hi, qi, ki, _nd=nd: (0,) * _nd)


def kernel_spec(geom):
    """Static declaration for :mod:`repro.analysis.kernel_guard`.

    Built from the SAME ``_specs`` / ``_lut_spec`` helpers the launcher
    dispatches with, at the launcher's own block-size policy, so the
    guard analyzes the real grid and index maps.  Table operands use the
    worst-case shapes (int16 — the largest shipped tables) so the VMEM
    accounting upper-bounds every policy.
    """
    from repro.analysis.kernel_guard import KernelSpec, Operand, PassSpec
    from repro.core.lut_builder import build_lut2d_tables, build_rexp_tables

    b, h, kvh, d = geom["b"], geom["h"], geom["kvh"], geom["dh"]
    lq, lk = geom["lq"], geom["lk"]
    bq = min(256, round_up(lq, 8))
    bk = min(256, round_up(lk, 128))
    lq_p, lk_p = round_up(lq, bq), round_up(lk, bk)
    grid = (b, h, lq_p // bq, lk_p // bk)  # K axis innermost (sequential)
    q_spec, k_spec, v_spec, m_spec, o_spec = _specs(b, h, kvh, lq_p, lk_p,
                                                    d, bq, bk)

    rexp = build_rexp_tables("int16")
    l2d = build_lut2d_tables("int16")
    lut_re = rexp.lut_recip_exp[None, :]
    lut_a = rexp.lut_alpha[None, :]
    lut_e = l2d.lut_exp[None, :]
    lut_sig = l2d.lut_sigma

    q = Operand("q", (b, h, lq_p, d), q_spec)
    k = Operand("k", (b, kvh, lk_p, d), k_spec)
    v = Operand("v", (b, kvh, lk_p, d), v_spec)
    m = Operand("m", (b, h, lq_p), m_spec)
    s = Operand("s_sum", (b, h, lq_p), m_spec)
    o = Operand("out", (b, h, lq_p, d), o_spec)
    t_re = Operand("lut_recip_exp", lut_re.shape, _lut_spec(lut_re), "int32")
    t_a = Operand("lut_alpha", lut_a.shape, _lut_spec(lut_a), "int32")
    t_e = Operand("lut_exp", lut_e.shape, _lut_spec(lut_e), "int32")
    t_s = Operand("lut_sigma", lut_sig.shape, _lut_spec(lut_sig), "int32")

    passes = (
        PassSpec("rowmax", grid, (q, k), (m,)),
        PassSpec("sum", grid, (q, k, m, t_e), (s,),
                 sigma_acc=True, acc_dtype="float32",
                 notes="integer Σ accumulated f32-exact in the resident ref"),
        PassSpec("fused_sum_av", grid, (q, k, v, m, t_re, t_a), (s, o),
                 sigma_acc=True, acc_dtype="float32",
                 notes="REXP fused-requant variant (S and U together)"),
        PassSpec("rexp_av", grid, (q, k, v, m, s, t_re, t_a), (o,)),
        PassSpec("lut2d_av", grid, (q, k, v, m, s, t_e, t_s), (o,)),
    )
    return KernelSpec(
        name="lut_attention", module=__name__, kind="pallas", passes=passes,
        notes="dense blocked multi-pass; accumulators resident across the "
              "sequential K axis")


def lut_attention_pallas(
    q: Array, k: Array, v: Array,
    tables: RexpTables | Lut2DTables,
    *,
    method: str = "rexp",            # 'rexp' | 'lut2d'
    causal: bool = False,
    scale: float | None = None,
    index_mode: str = "round",
    fused_requant: bool = False,      # REXP only: 2-pass beyond-paper variant
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = True,
) -> Array:
    """Fused LUT attention.  q (B,H,Lq,D); k,v (B,KVH,Lk,D).  Returns f32."""
    b, h, lq, d_model = q.shape
    _, kvh, lk, _ = k.shape
    assert h % kvh == 0, (h, kvh)
    scale = scale if scale is not None else d_model ** -0.5
    qmax = tables.precision.qmax

    bq = min(block_q, round_up(lq, 8))
    bk = min(block_k, round_up(lk, 128))
    lq_p, lk_p = round_up(lq, bq), round_up(lk, bk)
    qp = pad_axis_to(q, 2, lq_p, 0.0)
    kp = pad_axis_to(k, 2, lk_p, 0.0)
    vp = pad_axis_to(v, 2, lk_p, 0.0)

    grid = (b, h, lq_p // bq, lk_p // bk)
    q_spec, k_spec, v_spec, m_spec, o_spec = _specs(b, h, kvh, lq_p, lk_p,
                                                    d_model, bq, bk)
    # NB: causal right-alignment must use the TRUE lq/lk, not padded sizes.
    geom = dict(scale=scale, causal=causal, lq=lq, lk=lk_p, lk_valid=lk,
                bq=bq, bk=bk)

    if method == "rexp":
        assert isinstance(tables, RexpTables)
        lut_main = jnp.asarray(tables.lut_recip_exp, jnp.int32)[None, :]
        lut_a = jnp.asarray(tables.lut_alpha, jnp.int32)[None, :]
        lut_sig = None
        exp_step = 1.0
    else:
        assert isinstance(tables, Lut2DTables)
        lut_main = jnp.asarray(tables.lut_exp, jnp.int32)[None, :]
        lut_a = None
        lut_sig = jnp.asarray(tables.lut_sigma, jnp.int32)
        exp_step = tables.exp_step

    # Pass 1: row max.
    m = pl.pallas_call(
        functools.partial(_rowmax_kernel, **geom),
        grid=grid,
        in_specs=[q_spec, k_spec],
        out_specs=m_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lq_p), jnp.float32),
        interpret=interpret,
    )(qp, kp)

    if method == "rexp" and fused_requant:
        s_sum, out = pl.pallas_call(
            functools.partial(_fused_sum_av_kernel, qmax=qmax,
                              index_mode=index_mode, **geom),
            grid=grid,
            in_specs=[q_spec, k_spec, v_spec, m_spec, _lut_spec(lut_main),
                      _lut_spec(lut_a)],
            out_specs=(m_spec, o_spec),
            out_shape=(jax.ShapeDtypeStruct((b, h, lq_p), jnp.float32),
                       jax.ShapeDtypeStruct((b, h, lq_p, d_model),
                                            jnp.float32)),
            interpret=interpret,
        )(qp, kp, vp, m, lut_main, lut_a)
        return out[:, :, :lq]

    # Pass 2: Σ e_int.
    s_sum = pl.pallas_call(
        functools.partial(_sum_kernel, method=method, exp_step=exp_step,
                          index_mode=index_mode, **geom),
        grid=grid,
        in_specs=[q_spec, k_spec, m_spec, _lut_spec(lut_main)],
        out_specs=m_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lq_p), jnp.float32),
        interpret=interpret,
    )(qp, kp, m, lut_main)

    # Pass 3: σ_int · V (faithful per-element requantization).
    if method == "rexp":
        out = pl.pallas_call(
            functools.partial(_rexp_av_kernel, qmax=qmax,
                              index_mode=index_mode, **geom),
            grid=grid,
            in_specs=[q_spec, k_spec, v_spec, m_spec, m_spec,
                      _lut_spec(lut_main), _lut_spec(lut_a)],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, lq_p, d_model), jnp.float32),
            interpret=interpret,
        )(qp, kp, vp, m, s_sum, lut_main, lut_a)
    else:
        out = pl.pallas_call(
            functools.partial(_lut2d_av_kernel, qmax=qmax, exp_step=exp_step,
                              scale_ex=tables.scale_ex,
                              scale_sum=tables.scale_sum,
                              index_mode=index_mode, **geom),
            grid=grid,
            in_specs=[q_spec, k_spec, v_spec, m_spec, m_spec,
                      _lut_spec(lut_main), _lut_spec(lut_sig)],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, lq_p, d_model), jnp.float32),
            interpret=interpret,
        )(qp, kp, vp, m, s_sum, lut_main, lut_sig)
    return out[:, :, :lq]
