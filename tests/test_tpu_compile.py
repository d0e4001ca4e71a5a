"""Ahead-of-time TPU v5e compiles of the served path at qwen3-32b widths.

CPU CI runs the Pallas kernels in interpret mode, which accepts block
shapes, in-kernel gathers and VMEM footprints that Mosaic refuses on the
chip.  These tests compile for a described — not attached — v5e:

* both paged kernels through their dispatchers, as the engine calls
  them (decode and prefill; exact, rexp/uint8 and lut2d/uint8; f32 and
  int8 pools), and the decode kernel's blocked sweep at the benchmark
  cells' geometries (bf16 and int8 pools);
* the model's whole prefill-chunk and decode-step programs at
  qwen3-32b's published widths cut to two layers, checking that they
  fit the chip's 16 GB of HBM and name their kernel's three passes as
  a profiler trace shows them;
* that ``VMEM_BUDGET`` is the scoped VMEM limit the kernels compile
  under.

On a CPU host ``jax.default_backend()`` says "cpu", which would send the
dispatchers to their interpret-mode path; each test steers it to "tpu"
for its own duration.  The topology is described inside a module-scoped
fixture, never at import: only one process at a time may load the TPU
compiler library, and the test workers all import this file.  All such
compiles live in this one file so that one worker holds the library.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.configs import RunConfig, get_arch
from repro.core.policies import SoftmaxPolicy
from repro.kernels.common import PAGED_PASSES, VMEM_BUDGET, pass_name
from repro.kernels.lut_attention.ops import (lut_attention_paged_decode,
                                             lut_attention_paged_prefill)
from repro.kernels.lut_attention.paged_decode import (decode_block_pages,
                                                      kernel_name)
from repro.models import build_model
from repro.runtime.paged_cache import pool_shape, scale_pool_shape

#: qwen3-32b attention widths (configs/qwen3_32b.py), the serving page
#: size, and a small serving batch: 4 slots × 16 pages (256 tokens),
#: 16-token prefill chunks.
H, KVH, DH, PS = 64, 8, 128, 16
B, MP, N_PAGES, CHUNK = 4, 16, 512, 16

#: one v5e chip's HBM (Google Cloud, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9

POLICIES = {
    "exact": SoftmaxPolicy(),
    "rexp-uint8": SoftmaxPolicy(impl="rexp", precision="uint8"),
    "lut2d-uint8": SoftmaxPolicy(impl="lut2d", precision="uint8"),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(one_chip, monkeypatch):
    """Compile as the chip would: dispatchers resolve to the compiled
    kernels, and the persistent cache (which cannot read back a
    described-device compile) stays out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield one_chip
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def _pool_args(dev, quantized):
    pages = _sds(dev, pool_shape(N_PAGES, PS, KVH, DH),
                 jnp.int8 if quantized else jnp.float32)
    sc = _sds(dev, scale_pool_shape(N_PAGES, PS, KVH), jnp.float32)
    return pages, (dict(k_scales=sc, v_scales=sc) if quantized else {})


def _count_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_paged_decode_compiles_for_v5e(for_tpu, policy, quantized):
    dev = for_tpu
    pages, scales = _pool_args(dev, quantized)
    pol = POLICIES[policy]

    def step(q, kp, vp, bt, kl, **sc):
        return lut_attention_paged_decode(q, kp, vp, bt, kl, pol,
                                          backend="pallas", **sc)

    compiled = jax.jit(step).lower(
        _sds(dev, (B, H, 1, DH), jnp.float32), pages, pages,
        _sds(dev, (B, MP), jnp.int32), _sds(dev, (B,), jnp.int32),
        **scales).compile()
    assert _count_kernels(compiled) == 3  # row max, Σ, σ·V


#: the benchmark cells' decode dispatch (bench/configs): G query heads on
#: one KV head, n_slots slots, mp pages per sequence, the pool's pages
CELL_DECODE = {
    "qwen3-32b-tp8": dict(g=8, slots=32, mp=64, n_pages=6144),
    "internlm2-20b-tp8": dict(g=6, slots=64, mp=160, n_pages=12288),
}


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_DECODE))
def test_blocked_paged_decode_compiles_at_cell_geometries(for_tpu, cell,
                                                          pool):
    """The decode kernel's blocked sweep (8 pages, 128 keys a grid step,
    in both cells) compiles for v5e at the cells' geometries, and its
    three passes are ops under their own names."""
    dev, c = for_tpu, CELL_DECODE[cell]
    assert decode_block_pages(PS, c["mp"]) == 8
    quantized = pool == "int8"
    pages = _sds(dev, pool_shape(c["n_pages"], PS, 1, DH),
                 jnp.int8 if quantized else jnp.bfloat16)
    sc = _sds(dev, scale_pool_shape(c["n_pages"], PS, 1), jnp.float32)
    scales = dict(k_scales=sc, v_scales=sc) if quantized else {}
    pol = POLICIES["rexp-uint8"]

    def step(q, kp, vp, bt, kl, **sc):
        return lut_attention_paged_decode(q, kp, vp, bt, kl, pol,
                                          backend="pallas", **sc)

    text = jax.jit(step).lower(
        _sds(dev, (c["slots"], c["g"], 1, DH), jnp.float32), pages, pages,
        _sds(dev, (c["slots"], c["mp"]), jnp.int32),
        _sds(dev, (c["slots"],), jnp.int32), **scales).compile().as_text()
    kernel = kernel_name(quantized)
    for p in PAGED_PASSES:
        name = pass_name(kernel, p)
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call",
                         text), name


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_paged_prefill_compiles_for_v5e(for_tpu, policy, quantized):
    dev = for_tpu
    pages, scales = _pool_args(dev, quantized)
    pol = POLICIES[policy]

    def step(q, kp, vp, bt, qs, kl, **sc):
        return lut_attention_paged_prefill(q, kp, vp, bt, qs, kl, pol,
                                           backend="pallas", **sc)

    compiled = jax.jit(step).lower(
        _sds(dev, (B, H, CHUNK, DH), jnp.float32), pages, pages,
        _sds(dev, (B, MP), jnp.int32), _sds(dev, (B,), jnp.int32),
        _sds(dev, (B,), jnp.int32), **scales).compile()
    assert _count_kernels(compiled) == 3


#: compiled step programs, by (phase, kv_dtype): each compiles once
_STEPS = {}


def _step_program(dev, phase, kv_dtype):
    """The engine's step program at qwen3-32b widths (2 layers, f32
    weights, the launcher's dtype), compiled for the described chip."""
    if (phase, kv_dtype) in _STEPS:
        return _STEPS[phase, kv_dtype]
    model = build_model(get_arch("qwen3-32b").with_layers(2))
    run = RunConfig(dtype="float32", attention_backend="naive",
                    softmax_policy=POLICIES["rexp-uint8"],
                    paged_backend="pallas", kv_dtype=kv_dtype)
    put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(dev, x.shape, x.dtype), tree)
    params = put(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = put(jax.eval_shape(
        lambda: model.init_paged_pools(N_PAGES, PS, run)))
    bt, lens = _sds(dev, (B, MP), jnp.int32), _sds(dev, (B,), jnp.int32)
    if phase == "decode":
        lowered = jax.jit(model.decode_step_paged, static_argnums=5).lower(
            params, _sds(dev, (B, 1), jnp.int32), pools, bt, lens, run)
    else:
        lowered = jax.jit(model.prefill_chunk_paged, static_argnums=6).lower(
            params, _sds(dev, (1, CHUNK), jnp.int32), pools,
            _sds(dev, (1, MP), jnp.int32), _sds(dev, (1,), jnp.int32),
            _sds(dev, (1,), jnp.int32), run)
    _STEPS[phase, kv_dtype] = lowered.compile()
    return _STEPS[phase, kv_dtype]


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("phase", ["prefill_chunk", "decode"])
def test_serving_step_fits_one_v5e(for_tpu, phase, kv_dtype):
    """The fused kernels are in the step program and weights + pool +
    temporaries fit one chip's HBM."""
    compiled = _step_program(for_tpu, phase, kv_dtype)
    assert _count_kernels(compiled) == 3  # one layer body, scanned
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("phase", ["prefill_chunk", "decode"])
def test_serving_step_names_its_kernel_passes(for_tpu, phase, kv_dtype):
    """Each of the kernel's three passes is an instruction of the
    compiled step program under the pass's own name, the head of the
    label a TPU profiler trace gives the op (``%paged_decode_sum.6 =
    ...``)."""
    text = _step_program(for_tpu, phase, kv_dtype).as_text()
    kernel = ("paged_decode" if phase == "decode" else "paged_prefill") + (
        "_int8" if kv_dtype == "int8" else "")
    for p in PAGED_PASSES:
        name = pass_name(kernel, p)
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call",
                         text), name


@pytest.mark.parametrize("rows,fits", [(7 * 1024, True), (9 * 1024, False)],
                         ids=["under", "over"])
def test_vmem_budget_is_the_scoped_limit(for_tpu, rows, fits):
    """``VMEM_BUDGET`` is the scoped VMEM limit the kernels compile
    under (they pass no ``vmem_limit_bytes``): a copy kernel whose
    double-buffered in + out blocks need 14 MiB compiles, one needing
    18 MiB is refused."""
    assert VMEM_BUDGET == 16 * 1024 * 1024

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def step(x):
        spec = pl.BlockSpec((rows, 128), lambda i: (i, 0))
        return pl.pallas_call(
            copy, grid=(2,), in_specs=[spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    lowered = jax.jit(step).lower(
        _sds(for_tpu, (2 * rows, 128), jnp.float32))
    if fits:
        lowered.compile()
    else:
        with pytest.raises(Exception, match="(?i)vmem|scoped"):
            lowered.compile()
