"""The dense decoder: every layer is GQA attention over all earlier keys
(RoPE, optional q/k RMS norm) and a SwiGLU MLP.

A configuration holds one chip's share of a tensor-parallel deployment:
the query and KV heads and the vocabulary rows the chip keeps, and
``intermediate_size / tensor_parallel`` MLP columns.  The program's side
of this architecture is ``dense_program.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import (Q_BLOCK, _fp8, _mm, _rms, _rope, softmax_exact,
                             softmax_rexp)

KIND = "dense"


def dims(cfg: dict) -> dict:
    """The widths one chip runs: published widths, held head counts and
    vocabulary rows, and the chip's share of the MLP columns."""
    tp = cfg["tensor_parallel"]
    if cfg["intermediate_size"] % tp:
        raise ValueError("intermediate_size does not split over "
                         f"tensor_parallel={tp}")
    return {
        "hidden": cfg["hidden_size"],
        "layers": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "ffn": cfg["intermediate_size"] // tp,
        "vocab": cfg["vocab_size"],
        "qk_norm": bool(cfg["qk_norm"]),
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
    }


def layer_kinds(cfg: dict) -> list[str]:
    return [KIND] * cfg["num_hidden_layers"]


def leaves(cfg: dict) -> dict:
    """``top`` leaves and the one kind's layer leaves: name -> (shape,
    init std or ``("const", value)``)."""
    d = dims(cfg)
    D, H, KV, Dh, F, V = (d["hidden"], d["heads"], d["kv_heads"],
                          d["head_dim"], d["ffn"], d["vocab"])
    init = cfg["assumed"]["init"]
    qk_gain = init["qk_gain"]
    attn_gain, mlp_gain = init["attn_gain"], init["mlp_gain"]
    layer = {
        "attn_norm": ((D,), ("const", 1.0)),
        "wq": ((D, H * Dh), (1.0 if d["qk_norm"] else qk_gain) / math.sqrt(D)),
        "wk": ((D, KV * Dh), (1.0 if d["qk_norm"] else qk_gain) / math.sqrt(D)),
        "wv": ((D, KV * Dh), 1.0 / math.sqrt(D)),
        "wo": ((H * Dh, D), attn_gain / math.sqrt(H * Dh)),
        "mlp_norm": ((D,), ("const", 1.0)),
        "w_gate": ((D, F), 1.0 / math.sqrt(D)),
        "w_up": ((D, F), 1.0 / math.sqrt(D)),
        "w_down": ((F, D), mlp_gain / math.sqrt(F)),
    }
    if d["qk_norm"]:
        layer["q_norm"] = ((Dh,), ("const", qk_gain))
        layer["k_norm"] = ((Dh,), ("const", qk_gain))
    top = {
        "embed": ((V, D), init["embed_std"]),
        "final_norm": ((D,), ("const", 1.0)),
        "head": ((D, V), 1.0 / math.sqrt(D)),
    }
    return {"top": top, "layers": {KIND: layer}}


@functools.partial(jax.jit,
                   static_argnames=("kind", "dims", "softmax", "control"))
def reference_layer(kind, x, w, dims, softmax, control):
    """One decoder layer over a whole (T, D) sequence, causal."""
    d = dict(dims)
    T = x.shape[0]
    H, KV, Dh, eps = d["heads"], d["kv_heads"], d["head_dim"], d["norm_eps"]
    G = H // KV
    pos = jnp.arange(T)
    h = _rms(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], control).reshape(T, H, Dh).transpose(1, 0, 2)
    k = _mm(h, w["wk"], control).reshape(T, KV, Dh).transpose(1, 0, 2)
    v = _mm(h, w["wv"], control).reshape(T, KV, Dh).transpose(1, 0, 2)
    if d["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q = _rope(q, pos, d["rope_theta"]) * Dh ** -0.5
    k = _rope(k, pos, d["rope_theta"])
    qg = q.reshape(KV, G, T, Dh)

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, 2)
        s = jnp.einsum("ngqd,nkd->ngqk", rows, k, precision="highest") \
            if not control else jnp.einsum(
                "ngqd,nkd->ngqk", _fp8(rows), _fp8(k), precision="highest")
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        if softmax is None:
            p = softmax_exact(s)
        else:
            lut_e, lut_a, qmax = softmax
            p = softmax_rexp(s, lut_e, lut_a, qmax)
        if control:
            return jnp.einsum("ngqk,nkd->ngqd", _fp8(p), _fp8(v),
                              precision="highest")
        return jnp.einsum("ngqk,nkd->ngqd", p, v, precision="highest")

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))   # (nb, KV, G, Qb, Dh)
    out = out.transpose(1, 2, 0, 3, 4).reshape(H, T, Dh)
    out = out.transpose(1, 0, 2).reshape(T, H * Dh)
    x = x + _mm(out, w["wo"], control)
    h = _rms(x, w["mlp_norm"], eps)
    g = _mm(h, w["w_gate"], control)
    u = _mm(h, w["w_up"], control)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], control)


def decode_attention(kv_len: int, dims: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one slot's decode attention over every layer."""
    f, b = work.decode_row(kv_len, dims)
    return f * dims["layers"], b * dims["layers"]


def prefill_attention(q_start: int, n: int, dims: dict
                      ) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk's attention over every layer."""
    f, b = work.prefill_chunk(q_start, n, dims)
    return f * dims["layers"], b * dims["layers"]


def matmul_flops_per_token(dims: dict) -> float:
    """Weight-matmul FLOPs of one token through every layer."""
    D, H, KV, Dh, F = (dims["hidden"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ffn"])
    per_layer = D * (H + 2 * KV) * Dh + H * Dh * D + 3 * D * F
    return 2.0 * per_layer * dims["layers"]


def head_flops(dims: dict) -> float:
    return 2.0 * dims["hidden"] * dims["vocab"]


def rehearse(cfg: dict) -> dict:
    """The widths of a CPU rehearsal."""
    cfg.update(hidden_size=64, head_dim=16,
               intermediate_size=64 * cfg["tensor_parallel"])
    return cfg
