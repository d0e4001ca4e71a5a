"""Seeded weights of a configuration, made on the device in the dtype served.

The benchmark owns the weights: the harness hands them to the program and
the reference makes the same values again, layer by layer, from the seed
alone.  Every matrix is drawn from integer random bits (an Irwin-Hall sum
of four bytes, close to a normal), so the values depend on the seed, the
leaf and the layer only, and not on how a compiler fuses the arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: standard deviation of the sum of four uniform bytes
_IRWIN_HALL_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (64 bits kept)."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(cfg: dict) -> dict:
    """``layer`` and ``top`` leaves: name -> (shape, init std or constant).

    A std is a float; a constant vector is ``("const", value)``.  Widths are
    the configuration's as run (``model_dims``)."""
    d = model_dims(cfg)
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
    return {"layer": layer, "top": top}


def model_dims(cfg: dict) -> dict:
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


def _matrix(key, shape, std, dtype):
    bits = jax.random.bits(key, shape, jnp.uint32)
    total = sum(((bits >> s) & 0xFF).astype(jnp.int32) for s in (0, 8, 16, 24))
    centred = (total - 510).astype(jnp.float32)
    return (centred * jnp.float32(std / _IRWIN_HALL_STD)).astype(dtype)


def _leaf(key, shape, init, dtype):
    if isinstance(init, tuple):
        return jnp.full(shape, init[1], jnp.float32)
    return _matrix(key, shape, init, dtype)


def _leaf_key(base, name: str):
    return jax.random.fold_in(base, sum(ord(c) * 31 ** i
                                        for i, c in enumerate(name)) % 2 ** 31)


def layer_leaf(base, name, layer, shape, init, dtype):
    return _leaf(jax.random.fold_in(_leaf_key(base, name), layer), shape,
                 init, dtype)


def make_all(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf, layers stacked on a leading axis, in one jitted call."""
    spec = shapes(cfg)
    n_layers = cfg["num_hidden_layers"]

    def build(base):
        out = {"top": {n: _leaf(_leaf_key(base, n), s, i, dtype)
                       for n, (s, i) in spec["top"].items()},
               "layer": {}}
        for n, (s, i) in spec["layer"].items():
            out["layer"][n] = jax.vmap(
                lambda l, n=n, s=s, i=i: layer_leaf(base, n, l, s, i, dtype)
            )(jnp.arange(n_layers))
        return out

    return jax.jit(build)(seed_key(seed))


def make_layer(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16) -> dict:
    """One layer's leaves, bitwise those of :func:`make_all`."""
    spec = shapes(cfg)["layer"]
    fn = _layer_fn(tuple((n, s, i) for n, (s, i) in spec.items()),
                   jnp.dtype(dtype).name)
    return fn(seed_key(seed), jnp.int32(layer))


def make_top(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    spec = shapes(cfg)["top"]
    base = seed_key(seed)
    return jax.jit(lambda b: {n: _leaf(_leaf_key(b, n), s, i, dtype)
                              for n, (s, i) in spec.items()})(base)


@functools.lru_cache(maxsize=8)
def _layer_fn(spec, dtype: str):
    """One jitted maker per layer spec, so that every layer reuses it."""
    return jax.jit(lambda base, l: {
        n: layer_leaf(base, n, l, s, i, dtype) for n, s, i in spec})
