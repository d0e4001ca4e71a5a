"""Partitioning rules: param/batch/cache PartitionSpecs for the meshes.

Strategy (MaxText-style logical axes, resolved directly to specs here):

  * **DP/FSDP** — batch over ('pod', 'data'); every weight matrix is
    additionally sharded over 'data' on its d_model-ish dimension
    (ZeRO-3: optimizer state inherits the same spec, XLA inserts the
    all-gathers before use and reduce-scatters after the backward).
  * **TP**     — heads / ffn-hidden / vocab dimensions over 'model'.
  * **EP**     — MoE expert dimension over 'model' when the expert count
    divides the axis; otherwise experts fall back to intra-expert TP
    (granite's 40 experts on a 16-way axis).
  * **SP**     — KV-cache length over 'data' for batch=1 long-context
    decode (flash-decode with sharded KV; XLA merges the partial
    max/sum terms).

Every rule is divisibility-guarded: an axis is dropped (replicated)
whenever the dimension does not divide the mesh axis size, so every
(arch × shape × mesh) cell lowers without manual exceptions — e.g.
whisper's 51865 vocab simply replicates where qwen3's 151936 shards.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# ---------------------------------------------------------------------------
# Active-mesh context (used by in-model sharding constraints)
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Mesh | None = None


def set_active_mesh(mesh: Mesh | None) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Mesh | None:
    return _ACTIVE_MESH


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain(x, *spec):
    """with_sharding_constraint against the active mesh (no-op without one)."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def constrain_batch_major(x):
    """Shard the leading (batch) dim over ('pod','data') if divisible."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    axes = batch_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    if axes and x.shape[0] % size == 0:
        return constrain(x, axes, *([None] * (x.ndim - 1)))
    return x


def _batch_axes_for(mesh, b: int):
    axes = batch_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and b % size == 0:
        return axes
    if "data" in mesh.axis_names and b % mesh.shape["data"] == 0:
        return ("data",)
    return None


def constrain_logits(logits):
    """(B, S, V) LM-head output: batch over data axes, vocab over 'model'
    when divisible.  Pins the head contraction to weight-gathering instead
    of a full-logits all-reduce (§Perf iteration 2: a 123B train step was
    moving 12+ GiB/chip of f32 logits through all-reduce without this)."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return logits
    b_axes = _batch_axes_for(mesh, logits.shape[0])
    v_axis = ("model" if "model" in mesh.axis_names
              and logits.shape[-1] % mesh.shape["model"] == 0 else None)
    return constrain(logits, b_axes, None, v_axis)


def constrain_attn_activations(q, k, v):
    """(B, H|KVH, L, Dh) attention tensors: heads over 'model' when
    divisible; otherwise QUERY-SEQUENCE over 'model' (context parallel) —
    avoids the degenerate fractional-head resharding XLA falls into when
    H % tp != 0 (§Perf iteration 3)."""
    mesh = _ACTIVE_MESH
    if mesh is None or "model" not in mesh.axis_names:
        return q, k, v
    tp = mesh.shape["model"]
    b_axes = _batch_axes_for(mesh, q.shape[0])

    def heads_spec(x):
        if x.shape[1] % tp == 0:
            return constrain(x, b_axes, "model", None, None)
        return x

    if q.shape[1] % tp == 0 and k.shape[1] % tp == 0:
        return heads_spec(q), heads_spec(k), heads_spec(v)
    if q.shape[2] % tp == 0 and q.shape[2] >= tp:
        q = constrain(q, b_axes, None, "model", None)
        k = constrain(k, b_axes, None, None, None)
        v = constrain(v, b_axes, None, None, None)
    return q, k, v


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# (name suffix, trailing ndim) → spec tuple over the trailing dims.
# 'F' = fsdp axis ('data'), 'M' = tensor axis ('model').
_RULES: list[tuple[str, int, tuple]] = [
    ("embed/table", 2, ("M", "F")),
    ("head/w", 2, ("F", "M")),
    # attention
    ("wq", 2, ("F", "M")),
    ("wk", 2, ("F", "M")),
    ("wv", 2, ("F", "M")),
    ("wo", 2, ("M", "F")),
    # dense mlp (2-D) — gate/up column-parallel, down row-parallel
    ("w_gate", 2, ("F", "M")),
    ("w_up", 2, ("F", "M")),
    ("w_down", 2, ("M", "F")),
    # moe experts (3-D): EP on expert dim (divisibility-guarded; falls
    # back to intra-expert TP below via the guard dropping 'M')
    ("w_gate", 3, ("M", "F", "EPTP")),
    ("w_up", 3, ("M", "F", "EPTP")),
    ("w_down", 3, ("M", "EPTP", "F")),
    ("router", 2, ("F", None)),
    # mamba
    ("in_proj", 2, ("F", "M")),
    ("conv_w", 2, (None, "M")),
    ("x_proj", 2, ("M", None)),
    ("dt_proj", 2, (None, "M")),
    ("a_log", 2, ("M", None)),
    ("out_proj", 2, ("M", "F")),
    # xlstm
    ("up_proj", 2, ("F", "M")),
    ("down_proj", 2, ("M", "F")),
    ("w_igate", 2, ("F", None)),
    ("w_fgate", 2, ("F", None)),
    ("w_in", 2, ("F", "M")),
    ("r_z", 3, (None, None, None)),
    ("r_i", 3, (None, None, None)),
    ("r_f", 3, (None, None, None)),
    ("r_o", 3, (None, None, None)),
]


def _resolve(sym, dim: int, mesh: Mesh, used: set[str],
             ep_possible: bool, fsdp: bool = True) -> str | None:
    if sym is None:
        return None
    if sym == "F" and not fsdp:
        return None
    if sym == "EPTP":
        # third slot of expert weights: use 'model' here only when the
        # expert dim could NOT take it (TP fallback)
        sym = "M" if not ep_possible else None
        if sym is None:
            return None
    axis = {"F": "data", "M": "model"}[sym]
    if axis not in mesh.axis_names or axis in used:
        return None
    if dim % mesh.shape[axis] != 0:
        return None
    used.add(axis)
    return axis


def param_pspec(path: str, shape: tuple[int, ...], mesh: Mesh,
                fsdp: bool = True) -> P:
    """PartitionSpec for one parameter leaf (period-stacked aware).

    ``fsdp=False`` drops the 'data' ('F') axis from weights: serving wants
    TP-only parameters — ZeRO sharding would re-all-gather the weights on
    EVERY decode step (§Perf iteration 6).
    """
    ndim = len(shape)
    for suffix, rule_nd, spec in _RULES:
        if not path.endswith(suffix) and f"/{suffix}/" not in path + "/":
            continue
        stacked = 0
        if ndim == rule_nd + 1:
            stacked = 1  # leading period axis
        elif ndim != rule_nd:
            continue
        dims = shape[stacked:]
        # EP feasibility: expert dim (slot 0 of 3-D rules) divides 'model'
        ep_possible = (rule_nd == 3 and spec[0] == "M"
                       and "model" in mesh.axis_names
                       and dims[0] % mesh.shape["model"] == 0)
        used: set[str] = set()
        out = []
        for sym, dim in zip(spec, dims):
            out.append(_resolve(sym, dim, mesh, used, ep_possible, fsdp))
        return P(*([None] * stacked), *out)
    # fallback: replicate 0/1-D; fsdp+tp for ≥2-D matmuls
    if ndim >= 2:
        used = set()
        tail = [_resolve("F", shape[-2], mesh, used, False, fsdp),
                _resolve("M", shape[-1], mesh, used, False, fsdp)]
        return P(*([None] * (ndim - 2)), *tail)
    return P()


def path_str(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def make_param_shardings(params_shape: PyTree, mesh: Mesh,
                         fsdp: bool = True) -> PyTree:
    """NamedShardings for a param (or ShapeDtypeStruct) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_pspec(path_str(path), tuple(leaf.shape), mesh,
                              fsdp)),
        params_shape)


# ---------------------------------------------------------------------------
# Batch / cache shardings
# ---------------------------------------------------------------------------


def batch_pspec(mesh: Mesh, batch_size: int) -> P:
    axes = batch_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and batch_size % size == 0 and batch_size >= size:
        return P(axes)
    # partial: try 'data' only
    if "data" in mesh.axis_names and batch_size % mesh.shape["data"] == 0 \
            and batch_size >= mesh.shape["data"]:
        return P("data")
    return P()


def tokens_sharding(mesh: Mesh, batch_size: int) -> NamedSharding:
    return NamedSharding(mesh, P(*batch_pspec(mesh, batch_size), None))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated NamedSharding — every device holds the whole
    array.  The placement for everything the sharded serving step reads
    uniformly: block tables, cache cursors, entering tokens, and the
    scalar page ids of a copy-on-write duplication (the pool they index
    is what's sharded, per :func:`paged_pool_pspec`)."""
    return NamedSharding(mesh, P())


def serving_param_shardings(params: PyTree, mesh: Mesh,
                            shard_params: bool) -> PyTree:
    """Weight placement of the serving engine on a mesh: TP-sharded
    (:func:`make_param_shardings` without FSDP) or replicated."""
    if shard_params:
        return make_param_shardings(params, mesh, fsdp=False)
    return jax.tree_util.tree_map(lambda _: replicated_sharding(mesh),
                                  params)


def mesh_model_tp(mesh: Mesh | None) -> int:
    """Tensor-parallel degree of a mesh: its 'model' axis size.

    1 without a mesh or without that axis — the single guard shared by
    every TP consumer (pool specs, the paged dispatch regime, the
    engine's scheduler interleave, pool-shape padding), so the axis
    convention cannot drift between them.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def paged_pool_pspec(mesh: Mesh | None, n_kv_heads: int,
                     scales: bool = False) -> P:
    """Spec for one layer's page-major KV pool (n_pages, KVH, ps, Dh).

    KV heads take 'model' when divisible (the 'heads' regime of the
    tensor-parallel paged dispatch — attention is fully local per
    shard); otherwise the PHYSICAL-PAGE axis absorbs 'model' (the
    'pages' regime: each device owns a slab of pages and the shard_map
    dispatcher in ``kernels/lut_attention/sharded_paged.py`` reduces
    only ``(B, H, 1)`` partials).  Mirrors ``cache_pspec``'s
    heads-else-length fallback for the contiguous lockstep cache.

    ``scales=True`` gives the spec of the int8 pool's f32 scale leaf
    ``(n_pages, 1, KVH·ps)``, so scales always shard exactly with the
    pages they describe (the 'pages' regime keeps page+scale co-resident
    per slab; the 'heads' regime splits both on KVH — the scale row is
    head-major, so an even split of its lanes is an even split of the
    heads).
    """
    tp = mesh_model_tp(mesh)
    if tp <= 1:
        return P()
    if n_kv_heads % tp == 0:
        return P(None, None, "model") if scales \
            else P(None, "model", None, None)
    return P("model", None, None) if scales else P("model", None, None, None)


def paged_pool_sharding(mesh: Mesh, n_kv_heads: int,
                        stacked: bool = True,
                        scales: bool = False) -> NamedSharding:
    """NamedSharding for a (periods-stacked) paged pool leaf."""
    spec = paged_pool_pspec(mesh, n_kv_heads, scales=scales)
    if stacked:
        spec = P(None, *spec)
    return NamedSharding(mesh, spec)


def cache_pspec(mesh: Mesh, batch_size: int, n_kv_heads: int,
                shard_kv_seq: bool = False) -> P:
    """Spec for (B, KVH, L, Dh) KV-cache arrays.

    Heads take 'model' when divisible; otherwise the LENGTH dim absorbs
    'model' (flash-decode over length-sharded KV — XLA merges the partial
    max/sum).  With ``shard_kv_seq`` (long-context, batch=1) the length
    dim additionally takes 'data' (SP).
    """
    bspec = batch_pspec(mesh, batch_size)
    b_axes = bspec[0] if len(bspec) else None
    kv_axis = ("model" if "model" in mesh.axis_names
               and n_kv_heads % mesh.shape["model"] == 0 else None)
    seq_axes: list[str] = []
    if kv_axis is None and "model" in mesh.axis_names:
        seq_axes.append("model")
    if shard_kv_seq and "data" in mesh.axis_names \
            and "data" not in (b_axes or ()):
        seq_axes.append("data")
    seq_axis = tuple(seq_axes) if seq_axes else None
    return P(b_axes, kv_axis, seq_axis, None)


def make_cache_shardings(cache_shape: PyTree, mesh: Mesh, batch_size: int,
                         n_kv_heads: int, shard_kv_seq: bool,
                         stacked: bool = True) -> PyTree:
    """Shardings for a serving-state pytree.

    ``stacked=True`` for the decoder-LM caches (period-leading axis on
    every leaf); False for the enc-dec per-layer lists.
    """
    kv = cache_pspec(mesh, batch_size, n_kv_heads, shard_kv_seq)
    bspec = batch_pspec(mesh, batch_size)
    b_axes = bspec[0] if len(bspec) else None
    msize = mesh.shape.get("model", 1)
    off = 1 if stacked else 0
    pre = (None,) * off

    def leaf_spec(path, leaf):
        nd = leaf.ndim
        name = path_str(path).rsplit("/", 1)[-1]
        if nd == 4 + off and name in ("k", "v"):  # (…,B,KVH,L,Dh) attn KV
            return P(*pre, *kv)
        if nd >= 2 + off:
            # SSM/recurrent states (…,B,X,…): TP the channel dim X
            # (mamba d_inner, mlstm/slstm heads) when divisible.
            x_axis = ("model" if "model" in mesh.axis_names
                      and leaf.shape[off + 1] % msize == 0 else None)
            return P(*pre, b_axes, x_axis, *([None] * (nd - off - 2)))
        if nd == 2 + off:
            return P(*pre, b_axes)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, leaf_spec(path, leaf)),
        cache_shape)
