"""Pure-jnp oracle for the fused LUT-attention kernels.

Semantics = naive attention with the core LUT softmax in the middle:

    logits = (q @ kᵀ) · scale  (+ causal mask)
    σ      = softmax_<method>(logits)        # repro.core semantics
    out    = σ @ v

both products at ``kernels.common.ATTN_PRECISION`` (f32 on any backend).

The kernels block the K dimension, so the final f32 contraction
accumulates in a different order than the naive oracle — tests use
``assert_allclose`` with a tight tolerance for ``out`` but require the
*integer* pipeline (row max bins, e_int, S, σ_int) to match bit-exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lut_builder import Lut2DTables, RexpTables
from repro.core import lut_softmax as _core
from repro.kernels.common import ATTN_PRECISION

Array = jax.Array


def _logits(q: Array, k: Array, scale: float, causal: bool) -> Array:
    """(B, H, Lq, D) × (B, KVH, Lk, D) → (B, H, Lq, Lk) with GQA head map."""
    b, h, lq, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    kx = jnp.repeat(k, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kx.astype(jnp.float32), precision=ATTN_PRECISION) * scale
    if causal:
        lk = k.shape[2]
        qi = jnp.arange(lq)[:, None] + (lk - lq)  # right-aligned queries
        ki = jnp.arange(lk)[None, :]
        s = jnp.where(ki <= qi, s, -jnp.inf)
    return s


def lut_attention_ref(
    q: Array, k: Array, v: Array, *,
    method: str,  # 'rexp' | 'lut2d' | 'exact'
    tables: RexpTables | Lut2DTables | None = None,
    scale: float | None = None,
    causal: bool = False,
    index_mode: str = "round",
    fused_requant: bool = False,
) -> Array:
    """Naive-attention oracle.  ``fused_requant`` mirrors the 2-pass kernel
    (α applied to the Σe·v accumulator instead of per-element σ requant —
    the beyond-paper fused variant; see DESIGN.md)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = _logits(q, k, scale, causal)
    kvh = k.shape[1]
    g = q.shape[1] // kvh
    vx = jnp.repeat(v, g, axis=1).astype(jnp.float32)

    if method == "exact":
        p = _core.softmax_exact(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vx,
                          precision=ATTN_PRECISION)
    if method == "rexp":
        assert isinstance(tables, RexpTables)
        if not fused_requant:
            p = _core.softmax_rexp(s, tables, axis=-1, index_mode=index_mode)
            return jnp.einsum("bhqk,bhkd->bhqd", p, vx,
                              precision=ATTN_PRECISION)
        qmax = tables.precision.qmax
        inv = _core.inv_scale(qmax)
        e_int = _core.rexp_exp_int(s, tables, axis=-1, index_mode=index_mode)
        ssum = jnp.sum(e_int.astype(jnp.float32), axis=-1, keepdims=True)
        ja = _core.rexp_alpha_index(ssum, tables, index_mode)
        alpha = jnp.take(jnp.asarray(tables.lut_alpha, jnp.int32), ja, axis=0)
        u = jnp.einsum("bhqk,bhkd->bhqd", e_int.astype(jnp.float32), vx,
                       precision=ATTN_PRECISION)
        return u * (alpha.astype(jnp.float32) * inv * inv)
    if method == "lut2d":
        assert isinstance(tables, Lut2DTables)
        p = _core.softmax_lut2d(s, tables, axis=-1, index_mode=index_mode)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vx,
                          precision=ATTN_PRECISION)
    raise ValueError(f"unknown method {method!r}")
