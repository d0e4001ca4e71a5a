"""Tensor-parallel paged attention: shard_map dispatchers over 'model'.

The continuous-batching engine's page pool is sharded across a device
mesh and BOTH serving phases (single-token decode and prompt chunks)
attend through these dispatchers.  Two regimes, picked by
``ops.paged_mesh_regime`` from the GQA KV-head count:

* ``'heads'`` (KVH % tp == 0) — the pool is sharded on the KV-head axis
  ``P(None, 'model', None, None)``.  Each device runs the *unmodified*
  dense block-table reference on its own head group (query heads are
  KVH-major, so a contiguous H/tp slice aligns exactly with a KVH/tp
  slice of the pool): zero collectives inside attention, and the per-head
  output is bitwise the single-device reference's.

* ``'pages'`` (KVH does not divide tp) — heads cannot shard, so the
  POOL'S PAGE AXIS absorbs 'model': each device owns a slab of
  ``n_pages/tp`` physical pages and computes the paper's LUT softmax over
  only the keys resident in its slab (``sharded_decode.py`` proved this
  split for the contiguous lockstep cache; this is its paged analogue).
  The reduction exchanges only ``(B, H, Lq)``-shaped partials:

      round 1:  m = pmax(local row max)
      round 2:  S = psum(Σ local e_int)        (integer-exact in f32)
      epilogue: σ_i computed locally from (e_i, S) with the FAITHFUL
                per-element requant — bitwise ``ops._policy_softmax`` —
      round 3:  out = psum(Σ local σ_i · v_i)  ((B, H, Lq, D))

  so wire bytes per layer are ~B·H·D floats instead of a full-KV
  all-gather (``tests/test_engine_tp.py`` pins this on the compiled
  HLO via ``launch/hlo_analysis.py``).  For REXP / 2D-LUT the e/σ
  integer pipeline depends only on the *global* max and the
  integer-exact Σ, so every σ_i is bit-identical to the dense path and
  only the final f32 V-contraction reassociates across shards (the same
  roundoff-level caveat the Pallas kernels carry); for ``exact`` the Σ
  psum itself reassociates f32 partial sums, so σ too can differ at ulp
  level — token identity with the single-device engine holds at the
  argmax, pinned empirically by the engine tests, not bit-for-bit in σ.

Masked (−inf) positions — pool junk past ``kv_lens``, pages owned by
another device, null-page columns — produce hard-zero σ in every policy
(LUT_1/e terminal entry handling and LUT_σ row 0 are zero), so a key
contributes on exactly the one device that owns its page.

Scatter: in the 'pages' regime the K/V token writes must also stay
slab-local — :func:`scatter_chunk_sharded` clips non-local physical page
ids out of range and drops them (``mode='drop'``), so each device writes
only the pages it owns.  In the 'heads' regime the plain
``paged_cache.write_tokens`` in ``models/layers.py`` is already local
(the scattered axes are unsharded; the head-major scale row splits into
whole per-head lane groups).

Local compute is the dense reference on all backends — a
Pallas-kernel-inside-shard_map TPU path is future work; the ``backend``
knob is bypassed when a mesh is given (the dispatch matrix in ``ops.py``
documents the mesh rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core.lut_softmax import inv_scale
from repro.core.policies import SoftmaxPolicy

Array = jax.Array


def _tp(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


# ---------------------------------------------------------------------------
# Slab-local page-id clamps ('pages' regime)
#
# Module-level so ``kernel_spec`` can hand them to the static guard,
# which probes them numerically at the slab boundaries.
# ---------------------------------------------------------------------------


def _gather_page_ids(bt: Array, lo: int, slab: int):
    """(local mask, slab-local rows) for reading a device's page slab.

    Non-local pages clamp to row 0 — a real, in-slab row whose keys are
    −inf-masked by the ``local`` mask, so the read is safe and the value
    never contributes.
    """
    local = (bt >= lo) & (bt < lo + slab)
    return local, jnp.where(local, bt - lo, 0)


def _scatter_page_ids(ph: Array, lo: int, slab: int) -> Array:
    """Slab-local rows for writing into a device's page slab.

    Non-local pages map to ``slab`` — one past the end — so
    ``.at[...].set(mode='drop')`` discards them; a clipped foreign write
    can never collide with a real local one.
    """
    local = (ph >= lo) & (ph < lo + slab)
    return jnp.where(local, ph - lo, slab)


# ---------------------------------------------------------------------------
# Faithful per-element σ from (global max, global Σ) — bitwise the
# ``ops._policy_softmax`` pipeline, split so the two reductions can psum
# ---------------------------------------------------------------------------


def _e_terms(s: Array, m: Array, policy: SoftmaxPolicy, ktabs) -> Array:
    """Numerators of the policy softmax given the *global* row max.

    ``s`` (..., Lk) −inf-masked f32 logits; ``m`` (..., 1) the global
    (pmax-reduced) row max; ``ktabs`` the
    :func:`repro.kernels.common.policy_kernel_tables` tuple.  Thin
    reshape over :func:`repro.kernels.common.policy_e_terms` — the SAME
    helpers the paged kernels' pass 2/3 run, so a table-format or
    bin/clip fix there propagates here; it matches ``rexp_exp_int`` /
    ``lut2d_exp_int`` / ``softmax_exact`` bit-for-bit (safe-max
    handling, bin arithmetic, hard zeros for masked logits).
    """
    from repro.kernels.common import policy_e_terms
    lut_main, _, exp_step, _, _, _ = ktabs
    lk = s.shape[-1]
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = policy_e_terms(s.reshape(-1, lk), m_safe.reshape(-1, 1), lut_main[0],
                       policy.impl, exp_step, policy.index_mode, "gather")
    return e.reshape(s.shape)


def _sigma_from_terms(e: Array, s_sum: Array, policy: SoftmaxPolicy,
                      ktabs) -> Array:
    """Per-element σ from numerators + global Σ (keepdims, psum-reduced).

    The epilogue of ``softmax_exact`` / ``softmax_rexp`` /
    ``softmax_lut2d`` with the row reductions already done, shared with
    the paged kernels' pass 3 via
    :func:`repro.kernels.common.rexp_sigma` /
    :func:`~repro.kernels.common.lut2d_sigma_int` — constants, rounding
    and lookups are identical, so σ is bit-identical to the dense
    path's for the integer policies (their Σ is f32-exact under any
    summation order); for ``exact`` the psum'd Σ may reassociate,
    leaving σ identical only to ulp level.
    """
    from repro.kernels.common import dequant_scope, lut2d_sigma_int, rexp_sigma
    if policy.impl == "exact":
        return e / jnp.maximum(s_sum, jnp.finfo(jnp.float32).tiny)
    _, lut_aux, _, qmax, scale_ex, scale_sum = ktabs
    inv = inv_scale(qmax)
    lk = e.shape[-1]
    e2, s_col = e.reshape(-1, lk), s_sum.reshape(-1, 1)
    if policy.impl == "rexp":
        sigma_int = rexp_sigma(e2, s_col, lut_aux[0], qmax,
                               policy.index_mode, "gather")
    else:  # lut2d
        with dequant_scope():  # σ_int/qmax: the sanctioned exit
            sigma_int = lut2d_sigma_int(e2, s_col, lut_aux, qmax, scale_ex,
                                        scale_sum,
                                        policy.index_mode).astype(jnp.float32)
    return sigma_int.reshape(e.shape) * inv


# ---------------------------------------------------------------------------
# The shard_map bodies
# ---------------------------------------------------------------------------


def _partials_body(policy: SoftmaxPolicy, tables, scale: float, causal: bool,
                   slab: int, axis: str, quantized: bool = False):
    """'pages'-regime body: local (m, Σ, σ·V) partials + tiny reductions.

    Runs per device on the local page slab ``[idx·slab, (idx+1)·slab)``;
    positions whose page lives elsewhere are −inf-masked, so each valid
    key is claimed by exactly one device.  ``quantized`` appends the
    slab's f32 scale arrays to the signature (they shard with their
    pages) and dequantizes the local views before the identical partials
    pipeline.
    """
    from repro.kernels.common import dequant_scope, policy_kernel_tables
    from repro.kernels.lut_attention import ops as _ops
    from repro.kernels.lut_attention import ref as _ref

    ktabs = policy_kernel_tables(policy.impl, tables)

    def body(q, k_slab, v_slab, bt, q_start, kv_lens, *scales):
        lo = jax.lax.axis_index(axis) * slab
        local, lbt = _gather_page_ids(bt, lo, slab)    # (B, mp)
        if quantized:
            ks_slab, vs_slab = scales
            k_view, v_view = _ops._gather_dequant(k_slab, v_slab, lbt,
                                                  ks_slab, vs_slab)
        else:
            k_view = _ops.gather_pages(k_slab, lbt)    # (B, KVH, mp·ps, D)
            v_view = _ops.gather_pages(v_slab, lbt)
        lq, ps = q.shape[2], k_slab.shape[2]
        lk = k_view.shape[2]
        s = _ref._logits(q, k_view, scale, causal=False)  # (B, H, Lq, Lk)
        pos = jnp.arange(lk)
        valid = jnp.repeat(local, ps, axis=1) \
            & (pos[None, :] < kv_lens[:, None])        # (B, Lk)
        mask = valid[:, None, None, :]
        if causal:
            qi = q_start[:, None] + jnp.arange(lq)[None, :]   # (B, Lq)
            mask = mask & (pos[None, None, None, :]
                           <= qi[:, None, :, None])
        s = jnp.where(mask, s, -jnp.inf)
        m = jax.lax.pmax(jnp.max(s, axis=-1, keepdims=True), axis)
        e = _e_terms(s, m, policy, ktabs)
        with dequant_scope():  # f32-exact integer Σ accumulator
            local_sum = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
        s_sum = jax.lax.psum(local_sum, axis)
        sigma = _sigma_from_terms(e, s_sum, policy, ktabs)
        return jax.lax.psum(_ops._grouped_pv(sigma, v_view), axis)

    return body


def paged_attention_sharded(
    q: Array,               # (B, H, Lq, D); Lq == 1 for decode
    k_pages: Array,         # (P, KVH, ps, D) — sharded per regime
    v_pages: Array,
    block_tables: Array,    # (B, mp) int32
    kv_lens: Array,         # (B,) int32
    policy: SoftmaxPolicy,
    *,
    mesh: Mesh,
    regime: str,            # 'heads' | 'pages' (ops.paged_mesh_regime)
    q_start: Array | None = None,  # (B,) int32 — prefill chunks only
    scale: float | None = None,
    axis: str = "model",
    k_scales: Array | None = None,  # (P, 1, KVH·ps) f32 — int8 pool only
    v_scales: Array | None = None,
) -> Array:
    """Tensor-parallel paged attention for both serving phases.

    ``q_start=None`` is the decode shape (one query at ``kv_lens − 1``,
    no causal mask needed); a ``q_start`` array selects the chunked
    prefill semantics of ``lut_attention_prefill_varlen``.  Output is
    replicated across the mesh so the surrounding (replicated) layer
    compute stays bitwise the single-device program.

    ``k_scales``/``v_scales`` (both or neither) select the int8 pool:
    the scale arrays shard exactly with their pages in BOTH regimes
    (KV-head axis in 'heads', page axis in 'pages' —
    ``partitioning.paged_pool_pspec(..., scales=True)``), and each
    device dequantizes only its local view.
    """
    from repro.kernels.lut_attention import ops as _ops

    tp = _tp(mesh, axis)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    causal = q_start is not None
    qs = q_start if causal else jnp.zeros_like(kv_lens)
    tables = _ops._tables_for(policy)
    quantized = k_scales is not None
    assert quantized == (v_scales is not None), \
        "int8 pool needs both k_scales and v_scales"
    sc_args = (k_scales, v_scales) if quantized else ()

    if regime == "heads":
        if q.shape[1] % tp or k_pages.shape[1] % tp:
            raise ValueError(
                f"'heads' regime needs H ({q.shape[1]}) and KVH "
                f"({k_pages.shape[1]}) divisible by tp={tp}")

        def body(q_, k_, v_, bt_, qs_, kl_, *sc_):
            if quantized:
                k_seq, v_seq = _ops._gather_dequant(k_, v_, bt_, *sc_)
            else:
                k_seq = _ops.gather_pages(k_, bt_)
                v_seq = _ops.gather_pages(v_, bt_)
            if causal:
                return _ops.lut_attention_prefill_varlen(
                    q_, k_seq, v_seq, policy, q_start=qs_, kv_lens=kl_,
                    scale=scale)
            return _ops.lut_attention_decode_varlen(
                q_, k_seq, v_seq, policy, kl_, scale=scale)

        sc_specs = 2 * (P(None, None, axis),) if quantized else ()
        out = shard_map(
            body, mesh=mesh,
            in_specs=(P(None, axis, None, None),
                      P(None, axis, None, None),
                      P(None, axis, None, None),
                      P(None, None), P(None), P(None)) + sc_specs,
            out_specs=P(None, axis, None, None),
            check_vma=False,
        )(q, k_pages, v_pages, block_tables, qs, kv_lens, *sc_args)
        # replicate the head-sharded output: B·H·D floats on the wire,
        # and everything downstream computes replicated (bitwise the
        # single-device program)
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P()))

    if regime != "pages":
        raise ValueError(f"unknown sharded paged regime {regime!r}")
    if k_pages.shape[0] % tp:
        raise ValueError(
            f"'pages' regime needs n_pages ({k_pages.shape[0]}) divisible "
            f"by tp={tp} — size the pool with pool_shape(..., tp=tp)")
    slab = k_pages.shape[0] // tp
    body = _partials_body(policy, tables, scale, causal, slab, axis,
                          quantized=quantized)
    sc_specs = 2 * (P(axis, None, None),) if quantized else ()
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis, None, None, None), P(axis, None, None, None),
                  P(None, None), P(None), P(None)) + sc_specs,
        out_specs=P(),
        check_vma=False,
    )(q, k_pages, v_pages, block_tables, qs, kv_lens, *sc_args)


def kernel_spec(geom):
    """Static declaration for :mod:`repro.analysis.kernel_guard`.

    A shard_map kernel has no BlockSpecs; it declares instead the
    'pages'-regime cross-device reductions (checked against the
    (B, H, Lq)-partial wire budget — never KV-sized) and the slab-local
    page-id clamps, which the guard probes numerically at the slab
    boundaries of the first and last shard.
    """
    from repro.analysis.kernel_guard import ClampProbe, KernelSpec, Reduction

    b, h, dh = geom["b"], geom["h"], geom["dh"]
    c = geom["chunk"]  # worst-case Lq (prefill chunk; decode is Lq=1)
    n_pages, tp = geom["n_pages"], geom["tp"]
    slab = n_pages // tp

    reductions = (
        Reduction("pmax", (b, h, c, 1)),        # global row max
        Reduction("psum", (b, h, c, 1)),        # global integer Σ (f32-exact)
        Reduction("psum", (b, h, c, dh)),       # Σ local σ·V
    )
    clamps = tuple(
        ClampProbe(f"{name}@shard{idx}", fn=fn, lo=idx * slab, slab=slab,
                   n_pages=n_pages, mode=mode)
        for idx in (0, tp - 1)
        for name, fn, mode in (
            ("gather_page_ids",
             lambda ids, lo, s: _gather_page_ids(ids, lo, s)[1], "mask"),
            ("scatter_page_ids", _scatter_page_ids, "drop"),
        ))
    return KernelSpec(
        name="sharded_paged", module=__name__, kind="shard_map",
        reductions=reductions, clamps=clamps,
        wire_budget=2 * b * h * c * (dh + 2) * 4,
        notes="'pages' regime: page-axis-sharded pool, (B, H, Lq) partial "
              "reductions; 'heads' regime runs collective-free")


# ---------------------------------------------------------------------------
# Slab-local K/V scatter ('pages' regime)
# ---------------------------------------------------------------------------


def scatter_chunk_sharded(
    k_pages: Array, v_pages: Array,   # (P, KVH, ps, D), page-axis sharded
    phys: Array, offs: Array,         # (B, C) int32 physical page / offset
    k_tok: Array, v_tok: Array,       # (B, C, KVH, D)
    k_scales: Array | None = None,    # (P, 1, KVH·ps) f32 scale pools,
    v_scales: Array | None = None,    # page-axis sharded (int8 pool only)
    k_sc: Array | None = None,        # (B, C, KVH) f32 entering-token scales
    v_sc: Array | None = None,
    *,
    mesh: Mesh,
    axis: str = "model",
) -> tuple[Array, Array, Array | None, Array | None]:
    """Write entering K/V tokens into a page-axis-sharded pool.

    Each device keeps only the writes that land in its own slab —
    non-local physical pages are clipped out of range and dropped
    (``mode='drop'``), so no cross-device traffic and no risk of a
    clipped foreign write colliding with a real local one.  Decode calls
    this with C == 1; prefill with C == chunk.

    For an int8 pool the per-token scales are scattered through the SAME
    clipped page ids inside the SAME shard_map body, so a page and its
    scale block can never land on different devices (the COW copy relies
    on page+scale moving atomically).  Returns
    ``(k_pages, v_pages, k_scales, v_scales)`` — the scale slots are
    ``None`` for an f32 pool.
    """
    slab = k_pages.shape[0] // _tp(mesh, axis)
    quantized = k_scales is not None

    from repro.runtime.paged_cache import write_token_scales, write_tokens

    def body(kp, vp, ph, of, kt, vt, *sc):
        lo = jax.lax.axis_index(axis) * slab
        lph = _scatter_page_ids(ph, lo, slab)  # out of range → dropped
        kp = write_tokens(kp, lph, of, kt, mode="drop")
        vp = write_tokens(vp, lph, of, vt, mode="drop")
        if not quantized:
            return kp, vp
        ksp, vsp, ks, vs = sc
        ksp = write_token_scales(ksp, lph, of, ks, mode="drop")
        vsp = write_token_scales(vsp, lph, of, vs, mode="drop")
        return kp, vp, ksp, vsp

    pool_spec = P(axis, None, None, None)
    scale_pool_spec = P(axis, None, None)
    in_specs = (pool_spec, pool_spec, P(None, None), P(None, None),
                P(None, None, None, None), P(None, None, None, None))
    args = (k_pages, v_pages, phys, offs, k_tok, v_tok)
    out_specs = (pool_spec, pool_spec)
    if quantized:
        in_specs += (scale_pool_spec, scale_pool_spec,
                     P(None, None, None), P(None, None, None))
        args += (k_scales, v_scales, k_sc, v_sc)
        out_specs += (scale_pool_spec, scale_pool_spec)
    out = shard_map(body, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)(*args)
    if quantized:
        return out
    return out + (None, None)
