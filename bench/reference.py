"""Plain float32 forward pass of a configuration's chip share.

This is the yardstick behind ``correct``.  It imports nothing of the
program: the weights are made again from the seed (``bench.weights``), the
softmax follows the paper's Algorithm 1 (REXP) from its definition, and
everything runs as plain ``jax.numpy`` at ``highest`` matmul precision,
one layer at a time over a whole sequence, so that it fits beside nothing
else on the chip.  Each kind of layer is its architecture module's
``reference_layer`` (``bench/arch/<name>.py``), made of the helpers here.

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


def forward(arch, cfg: dict, seed: int, seqs: list[np.ndarray],
            rows: list[np.ndarray], control: bool = False) -> list[np.ndarray]:
    """f32 logits ``(len(rows[i]), vocab)`` at positions ``rows[i]`` of each
    token sequence ``seqs[i]``.  Weights are made from ``seed`` one layer
    at a time, and each layer is applied, by the architecture module
    ``arch``'s ``reference_layer`` for its kind, to every sequence before
    the next is made."""
    dims = arch.dims(cfg)
    top = W.make_top(arch, cfg, seed)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    xs = []
    for tokens in seqs:
        toks = np.zeros(-(-len(tokens) // BUCKET) * BUCKET, np.int32)
        toks[:len(tokens)] = tokens
        xs.append(f32(top["embed"][jnp.asarray(toks)]))
    dkey = tuple(sorted(dims.items()))
    sm = softmax_of(cfg)
    with jax.default_matmul_precision("highest"):
        for layer, kind in enumerate(arch.layer_kinds(cfg)):
            w = jax.tree_util.tree_map(
                f32, W.make_layer(arch, cfg, seed, layer))
            xs = [arch.reference_layer(kind, x, w, dkey, sm, control)
                  for x in xs]
        norm, head = f32(top["final_norm"]), f32(top["head"])
        return [np.asarray(_head(x, jnp.asarray(r, jnp.int32), norm, head,
                                 dims["norm_eps"], control))
                for x, r in zip(xs, rows)]
