"""Cell builder + shape registry invariants (abstract — no devices)."""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from repro.configs import ARCHS, SHAPES, get_arch, shape_applicable
from repro.configs.base import RunConfig
from repro.core.policies import EXACT


def test_shape_registry():
    assert SHAPES["train_4k"].kind == "train"
    assert SHAPES["prefill_32k"].seq_len == 32_768
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524_288
    assert SHAPES["long_500k"].global_batch == 1


def test_cell_grid_is_40():
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    assert len(cells) == 40
    runnable = [c for c in cells
                if shape_applicable(ARCHS[c[0]], SHAPES[c[1]])[0]]
    # long_500k runs only for jamba + xlstm → 40 − 8 skips
    assert len(runnable) == 32
    skipped = {c[0] for c in cells if c not in runnable}
    assert skipped == {a for a in ARCHS if not ARCHS[a].sub_quadratic}


def test_long_context_gating():
    ok, _ = shape_applicable(ARCHS["jamba-v0.1-52b"], SHAPES["long_500k"])
    assert ok
    ok, reason = shape_applicable(ARCHS["mistral-large-123b"],
                                  SHAPES["long_500k"])
    assert not ok and "sub-quadratic" in reason


def test_make_run_probe_vs_real():
    from repro.launch.cells import make_run
    arch = get_arch("qwen3-32b")
    real = make_run(arch, SHAPES["train_4k"])
    probe = make_run(arch, SHAPES["train_4k"], probe=True)
    assert real.scan_layers and not probe.scan_layers
    assert probe.microbatch == 1
    assert real.softmax_policy is EXACT  # training is always exact
    serve = make_run(arch, SHAPES["prefill_32k"])
    assert serve.softmax_policy.impl == "rexp"  # the paper's serving path
    assert serve.attention_backend == "blocked"
    long = make_run(get_arch("jamba-v0.1-52b"), SHAPES["long_500k"])
    assert long.shard_kv_seq


def test_arch_sources_recorded():
    for arch in ARCHS.values():
        assert arch.source, arch.name


def test_every_arch_has_exact_assigned_dims():
    spec = {
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
    }
    for name, (nl, dm, nh, kvh, dff, v) in spec.items():
        a = ARCHS[name]
        assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.d_ff,
                a.vocab_size) == (nl, dm, nh, kvh, dff, v), name
    # MoE specs per assignment line
    assert (ARCHS["jamba-v0.1-52b"].moe.n_experts,
            ARCHS["jamba-v0.1-52b"].moe.top_k) == (16, 2)
    assert (ARCHS["deepseek-moe-16b"].moe.n_experts,
            ARCHS["deepseek-moe-16b"].moe.top_k,
            ARCHS["deepseek-moe-16b"].moe.n_shared) == (64, 6, 2)
    assert (ARCHS["granite-moe-3b-a800m"].moe.n_experts,
            ARCHS["granite-moe-3b-a800m"].moe.top_k) == (40, 8)


def test_decode_state_struct_abstract():
    """Serving-state structs are ShapeDtypeStructs (no allocation)."""
    from repro.models import build_model
    run = RunConfig(dtype="bfloat16")
    model = build_model(get_arch("qwen3-32b"))
    st = model.decode_state_struct(128, 32768, run)
    leaves = jax.tree_util.tree_leaves(st)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    kv_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in leaves if l.ndim == 5)
    # qwen3 decode_32k KV cache: 64L × 2 × 8kvh × 32768 × 128dh × 2B = 2 TiB
    assert abs(kv_bytes - 2 * 64 * 8 * 32768 * 128 * 2 * 128) / kv_bytes < .01

    enc = build_model(get_arch("whisper-small"))
    st = enc.decode_state_struct(4, 64, run)
    caches, cross = st
    assert len(caches) == 12 and len(cross) == 12


def test_mesh_factories():
    from repro.launch.mesh import make_production_mesh
    # AbstractMesh mirrors the factory shapes without touching devices
    m1 = AbstractMesh((16, 16), ("data", "model"))
    m2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert m1.size == 256 and m2.size == 512
