"""Partitioning rules: valid specs for every arch, divisibility guards,
cache specs (single-process, abstract — no devices needed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax.sharding import AbstractMesh
from repro.configs import ARCHS, RunConfig
from repro.models import build_model
from repro.runtime import partitioning as PT


def _mesh_abstract(shape=(2, 16, 16), axes=("pod", "data", "model")):
    # AbstractMesh builds specs without devices
    return AbstractMesh(shape, axes)


MESH = _mesh_abstract()


def _check_spec_valid(path, shape, spec):
    assert len(spec) <= len(shape), (path, shape, spec)
    used = [a for s in spec if s for a in (s if isinstance(s, tuple) else (s,))]
    assert len(used) == len(set(used)), f"axis reused: {path} {spec}"
    for dim, s in zip(shape, spec):
        if s is None:
            continue
        axes = s if isinstance(s, tuple) else (s,)
        total = 1
        for a in axes:
            total *= MESH.shape[a]
        assert dim % total == 0, (path, shape, spec)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_specs_valid_all_archs(name):
    model = build_model(ARCHS[name])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    sharded_bytes = total_bytes = 0
    for path, leaf in flat:
        spec = PT.param_pspec(PT.path_str(path), tuple(leaf.shape), MESH)
        _check_spec_valid(PT.path_str(path), leaf.shape, spec)
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        total_bytes += nbytes
        if any(s is not None for s in spec):
            sharded_bytes += nbytes
    # the overwhelming majority of parameter BYTES must be sharded
    # (1-D biases/norms replicate; they are noise by weight)
    assert sharded_bytes > 0.95 * total_bytes, (
        f"{name}: {sharded_bytes/total_bytes:.3f} of bytes sharded")


def test_big_matrices_get_both_axes():
    spec = PT.param_pspec("periods/0/mixer/wq", (64, 12288, 12288), MESH)
    assert spec == P(None, "data", "model")
    spec = PT.param_pspec("periods/0/mixer/wo", (64, 12288, 12288), MESH)
    assert spec == P(None, "model", "data")


def test_divisibility_guard_drops_axis():
    # whisper vocab 51865 is not divisible by 16 → replicated dim
    spec = PT.param_pspec("head/w", (768, 51865), MESH)
    assert spec[1] is None
    # granite experts: 40 % 16 != 0 → EP infeasible → TP inside expert
    spec = PT.param_pspec("periods/0/ffn/w_up", (32, 40, 1536, 512), MESH)
    assert spec == P(None, None, "data", "model")
    # deepseek experts: 64 % 16 == 0 → EP on the expert dim
    spec = PT.param_pspec("periods/0/ffn/w_up", (28, 64, 2048, 1408), MESH)
    assert spec == P(None, "model", "data", None)


def test_batch_pspec():
    assert PT.batch_pspec(MESH, 256) == P(("pod", "data"))
    assert PT.batch_pspec(MESH, 1) == P()
    # 16 divides data(16) but not pod*data(32)
    assert PT.batch_pspec(MESH, 16) == P("data")


def test_cache_pspec_head_vs_length_sharding():
    # kv heads divide 'model' → heads take it
    assert PT.cache_pspec(MESH, 128, 16) == P(("pod", "data"), "model",
                                              None, None)
    # kv=8 doesn't divide 16 → LENGTH absorbs 'model'
    assert PT.cache_pspec(MESH, 128, 8) == P(("pod", "data"), None,
                                             ("model",), None)
    # long-context batch=1: SP adds 'data' on length
    spec = PT.cache_pspec(MESH, 1, 8, shard_kv_seq=True)
    assert spec == P(None, None, ("model", "data"), None)


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(np.asarray(PT.constrain_batch_major(x)),
                                  np.asarray(x))
