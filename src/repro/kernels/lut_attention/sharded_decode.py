"""Distributed flash-decode over a length-sharded KV cache.

When GQA KV heads don't divide the TP axis, the cache length dim carries
'model' (runtime/partitioning.cache_pspec).  XLA's SPMD resolves the
decode attention by ALL-GATHERING the full K and V per layer per step
(measured: 2×1 GiB/layer f32 for qwen3 decode_32k — the entire decode
collective term).  This shard_map computes the paper's REXP semantics
locally per length shard and reduces only the (B,H,1) partials:

    round 1:  m = pmax(local row max)
    round 2:  S = psum(Σ local e_int),  U = psum(Σ local e_int · v)
    epilogue: out = U · α(S) · inv²          (fused-requant REXP)

Wire bytes per layer drop from 2·KV-shard-gather (~GiB) to ~B·H·D floats
(§Perf iteration 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.core import lut_builder
from repro.kernels.common import ATTN_PRECISION, dequant_scope, kernel_lookup
from repro.core.lut_softmax import inv_scale
from repro.core.policies import SoftmaxPolicy

Array = jax.Array


def kernel_spec(geom):
    """Static declaration for :mod:`repro.analysis.kernel_guard`.

    Declares the length-sharded decode's cross-device reductions — the
    whole point of this kernel is that ONLY (B, H, 1)-shaped partials
    cross the mesh (vs the ~GiB per-layer KV all-gather it replaces), so
    the guard pins the wire footprint to the partial budget.
    """
    from repro.analysis.kernel_guard import KernelSpec, Reduction

    b, h, dh = geom["b"], geom["h"], geom["dh"]
    reductions = (
        Reduction("pmax", (b, h, 1)),       # global row max
        Reduction("psum", (b, h, 1)),       # global integer Σ (f32-exact)
        Reduction("psum", (b, h, 1, dh)),   # U = Σ local e_int · v
    )
    return KernelSpec(
        name="sharded_decode", module=__name__, kind="shard_map",
        reductions=reductions,
        wire_budget=2 * b * h * 1 * (dh + 2) * 4,
        notes="length-sharded contiguous cache; fused-requant REXP "
              "epilogue applies α(S)·inv² to the psum'd U")


def lut_decode_sharded(
    q: Array, k: Array, v: Array, policy: SoftmaxPolicy, *,
    kv_len: Array, mesh: Mesh, batch_axes, seq_axis: str = "model",
    scale: float | None = None,
) -> Array:
    """q (B,H,1,D) · cache k/v (B,KVH,L,D) L-sharded on ``seq_axis``."""
    b, h, lq, d = q.shape
    kvh, l_total = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    tp = mesh.shape[seq_axis]
    l_shard = l_total // tp
    exact = policy.impl == "exact"
    if not exact:
        tables = lut_builder.build_rexp_tables(policy.precision,
                                               policy.alpha_len)
        lut_re = jnp.asarray(tables.lut_recip_exp, jnp.int32)
        lut_a = jnp.asarray(tables.lut_alpha, jnp.int32)
        qmax = tables.precision.qmax
        rnd = jnp.round if policy.index_mode == "round" else jnp.floor

    def body(q_, k_, v_, kv_len_):
        idx = jax.lax.axis_index(seq_axis)
        ki = idx * l_shard + jnp.arange(l_shard)
        valid = (ki < kv_len_)[None, None, None, :]           # (1,1,1,l)
        qg = q_.reshape(q_.shape[0], kvh, g, lq, d).astype(jnp.float32)
        s = jnp.einsum("bngqd,bnkd->bngqk", qg, k_.astype(jnp.float32),
                       precision=ATTN_PRECISION) * scale
        s = jnp.where(valid[:, :, None], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)
        m = jax.lax.pmax(m_loc, seq_axis)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)

        if exact:
            p = jnp.where(jnp.isfinite(s),
                          jnp.exp(s - m_safe[..., None]), 0.0)
            l_loc = jnp.sum(p, axis=-1)
            u_loc = jnp.einsum("bngqk,bnkd->bngqd", p,
                               v_.astype(jnp.float32),
                               precision=ATTN_PRECISION)
            lsum = jax.lax.psum(l_loc, seq_axis)
            u = jax.lax.psum(u_loc, seq_axis)
            out = u / jnp.maximum(lsum, 1e-30)[..., None]
        else:
            n = lut_re.shape[0]
            finite = jnp.isfinite(s)
            dd = jnp.where(finite, m_safe[..., None] - s, float(n - 1))
            bins = jnp.clip(rnd(dd).astype(jnp.int32), 0, n - 1)
            e = jnp.where(finite, kernel_lookup(lut_re, bins, "gather"), 0)
            with dequant_scope():  # f32-exact integer Σ accumulator
                e = e.astype(jnp.float32)
            s_loc = jnp.sum(e, axis=-1)
            u_loc = jnp.einsum("bngqk,bnkd->bngqd", e,
                               v_.astype(jnp.float32),
                               precision=ATTN_PRECISION)
            ssum = jax.lax.psum(s_loc, seq_axis)
            u = jax.lax.psum(u_loc, seq_axis)
            inv = inv_scale(qmax)
            ja = jnp.clip(rnd(ssum * inv).astype(jnp.int32), 0,
                          lut_a.shape[0] - 1)
            with dequant_scope():  # α/qmax² fused requant: the sanctioned exit
                alpha = kernel_lookup(lut_a, ja, "gather").astype(jnp.float32)
            out = u * (alpha * inv * inv)[..., None]
        return out.reshape(q_.shape[0], h, lq, d)

    bspec = batch_axes if batch_axes else None
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, None, seq_axis, None),
                  P(bspec, None, seq_axis, None),
                  P()),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, k, v, kv_len)
