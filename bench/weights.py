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


def make_all(arch, cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf in one jitted call: ``top``, and under ``layers`` each
    layer kind's leaves, stacked on a leading axis over that kind's
    layers in order.  ``arch`` is the configuration's architecture module
    (``bench.cells.arch``)."""
    spec = arch.leaves(cfg)
    kinds = arch.layer_kinds(cfg)

    def build(base):
        out = {"top": {n: _leaf(_leaf_key(base, n), s, i, dtype)
                       for n, (s, i) in spec["top"].items()},
               "layers": {}}
        for kind, leaves in spec["layers"].items():
            idx = jnp.asarray([l for l, k in enumerate(kinds) if k == kind],
                              jnp.int32)
            out["layers"][kind] = {n: jax.vmap(
                lambda l, n=n, s=s, i=i: layer_leaf(base, n, l, s, i, dtype)
            )(idx) for n, (s, i) in leaves.items()}
        return out

    return jax.jit(build)(seed_key(seed))


def make_layer(arch, cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16
               ) -> dict:
    """One layer's leaves, bitwise those of :func:`make_all`."""
    kind = arch.layer_kinds(cfg)[layer]
    spec = arch.leaves(cfg)["layers"][kind]
    fn = _layer_fn(tuple((n, s, i) for n, (s, i) in spec.items()),
                   jnp.dtype(dtype).name)
    return fn(seed_key(seed), jnp.int32(layer))


def make_top(arch, cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    spec = arch.leaves(cfg)["top"]
    base = seed_key(seed)
    return jax.jit(lambda b: {n: _leaf(_leaf_key(b, n), s, i, dtype)
                              for n, (s, i) in spec.items()})(base)


@functools.lru_cache(maxsize=8)
def _layer_fn(spec, dtype: str):
    """One jitted maker per layer spec, so that every layer reuses it."""
    return jax.jit(lambda base, l: {
        n: layer_leaf(base, n, l, s, i, dtype) for n, s, i in spec})
