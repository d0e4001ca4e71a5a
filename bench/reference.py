"""Plain float32 forward pass of a configuration's chip share.

This is the yardstick behind ``correct``.  It imports nothing of the
program: the weights are made again from the seed (``bench.weights``), the
softmax follows the paper's Algorithm 1 (REXP) from its definition, and
everything runs as plain ``jax.numpy`` at ``highest`` matmul precision,
one layer at a time over a whole sequence, so that it fits beside nothing
else on the chip.

``forward`` returns the logits at chosen positions of token sequences.
With ``control=True`` every matmul takes its operands rounded to fp8
(e4m3, per-tensor scale): the precision step below the bfloat16 that the
configurations state, used to show that the comparison can fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

#: sequence lengths are padded up to a multiple of this (one compile each)
BUCKET = 1024
#: query rows per attention block
Q_BLOCK = 512


def rexp_tables(w_bits: int, alpha_len: int) -> tuple[np.ndarray, np.ndarray]:
    """Algorithm 1's tables: ``LUT_1/e[i] = round(e^-i · qmax)`` up to its
    first zero, and ``LUT_α = [qmax, round(qmax/j) for j in 1..x_s-1, 0]``."""
    qmax = (1 << w_bits) - 1
    x_q = math.ceil(math.log(qmax))
    recip = []
    for i in range(x_q + 2):
        recip.append(int(np.rint(math.exp(-i) * qmax)))
        if recip[-1] == 0:
            break
    alpha = [qmax] + [int(np.rint(qmax / j)) for j in range(1, alpha_len)]
    alpha[-1] = 0
    return np.asarray(recip, np.int32), np.asarray(alpha, np.int32)


def softmax_rexp(s, lut_e, lut_a, qmax):
    """REXP over the last axis of f32 logits ``s``; −inf entries are masked
    and give exact zeros."""
    finite = jnp.isfinite(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    n = len(lut_e)
    d = jnp.where(finite, m - s, float(n - 1))
    idx = jnp.clip(jnp.round(d).astype(jnp.int32), 0, n - 1)
    e = jnp.zeros(s.shape, jnp.int32)
    for i, v in enumerate(lut_e):         # a table read as selects
        e = jnp.where(idx == i, v, e)
    e = jnp.where(finite, e, 0)
    total = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
    inv = jnp.float32(1.0 / qmax)
    ja = jnp.clip(jnp.round(total * inv).astype(jnp.int32), 0,
                  len(lut_a) - 1)
    alpha = jnp.asarray(lut_a, jnp.int32)[ja]
    sigma = jnp.round((e * alpha).astype(jnp.float32) * inv)
    return sigma * inv


def _fp8(x):
    """Round to fp8 e4m3 under a per-tensor scale, back to f32."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(a, b, control):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "softmax", "control"))
def _layer(x, w, dims, softmax, control):
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


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, rows, norm, head, eps, control):
    return _mm(_rms(x[rows], norm, eps), head, control)


def softmax_exact(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(jnp.isfinite(s), jnp.exp(s - jnp.where(jnp.isfinite(m), m,
                                                         0.0)), 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def softmax_of(cfg: dict):
    s = cfg["serving"]
    if s["softmax"] == "exact":
        return None
    if s["softmax"] != "rexp":
        raise ValueError(f"reference implements exact and rexp, not "
                         f"{s['softmax']!r}")
    w_bits = {"uint8": 8, "int16": 15, "uint4": 4, "uint2": 2}[
        s["lut_precision"]]
    lut_e, lut_a = rexp_tables(w_bits, s["alpha_len"])
    return (tuple(int(v) for v in lut_e), tuple(int(v) for v in lut_a),
            (1 << w_bits) - 1)


def forward(cfg: dict, seed: int, seqs: list[np.ndarray],
            rows: list[np.ndarray], control: bool = False) -> list[np.ndarray]:
    """f32 logits ``(len(rows[i]), vocab)`` at positions ``rows[i]`` of each
    token sequence ``seqs[i]``.  Weights are made from ``seed`` one layer
    at a time, and each layer is applied to every sequence before the
    next is made."""
    dims = W.model_dims(cfg)
    top = W.make_top(cfg, seed)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    xs = []
    for tokens in seqs:
        toks = np.zeros(-(-len(tokens) // BUCKET) * BUCKET, np.int32)
        toks[:len(tokens)] = tokens
        xs.append(f32(top["embed"][jnp.asarray(toks)]))
    dkey = tuple(sorted(dims.items()))
    sm = softmax_of(cfg)
    with jax.default_matmul_precision("highest"):
        for layer in range(dims["layers"]):
            w = jax.tree_util.tree_map(f32, W.make_layer(cfg, seed, layer))
            xs = [_layer(x, w, dkey, sm, control) for x in xs]
        norm, head = f32(top["final_norm"]), f32(top["head"])
        return [np.asarray(_head(x, jnp.asarray(r, jnp.int32), norm, head,
                                 dims["norm_eps"], control))
                for x, r in zip(xs, rows)]
