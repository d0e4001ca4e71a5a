"""The reference makes the program's weights again, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells as C
from bench import weights as W


def arch_cfg():
    cell = C.rehearsal(C.cell("qwen3-32b-tp8.chat-short"))
    return cell.arch, cell.cfg


def test_layer_by_layer_equals_all_at_once():
    arch, c = arch_cfg()
    seed = 2 ** 33 + 17
    everything = W.make_all(arch, c, seed)
    kinds = arch.layer_kinds(c)
    for layer, kind in enumerate(kinds):
        one = W.make_layer(arch, c, seed, layer)
        at = kinds[:layer].count(kind)
        for name, leaf in one.items():
            assert np.array_equal(
                np.asarray(everything["layers"][kind][name][at]),
                np.asarray(leaf)), name
    top = W.make_top(arch, c, seed)
    for name, leaf in top.items():
        assert np.array_equal(np.asarray(everything["top"][name]),
                              np.asarray(leaf))


def test_seeds_differ_in_their_high_bits():
    arch, c = arch_cfg()
    a = W.make_top(arch, c, 5)["head"]
    b = W.make_top(arch, c, 5 + 2 ** 32)["head"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_matrices_have_the_stated_spread():
    arch, c = arch_cfg()
    w = W.make_layer(arch, c, 1, 0)
    d = arch.dims(c)
    got = float(jnp.std(w["w_up"].astype(jnp.float32)))
    assert abs(got * np.sqrt(d["hidden"]) - 1) < 0.05
    assert w["w_up"].dtype == jnp.bfloat16
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a: bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), w))
