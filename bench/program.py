"""The system under test, built from a configuration file.

This and the architectures' program halves (``bench/arch/<name>_program.py``)
are the modules of the benchmark that import the program: the model code,
the pipelined engine and the asyncio streaming server.  The weights come
from ``bench.weights`` and are handed over in the program's own parameter
tree, which the architecture's program half lays out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import cells as C
from bench import weights as W
from repro.configs.base import ArchConfig, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.kernels.lut_attention import ops
from repro.models import build_model
from repro.runtime.engine import EngineConfig, PipelinedEngine
from repro.runtime.paged_cache import PagedCacheConfig
from repro.runtime.server import AsyncServingServer


def arch_config(arch, cfg: dict) -> ArchConfig:
    return C.arch_program(arch).arch_config(cfg, arch.dims(cfg))


def params(arch, cfg: dict, seed: int) -> dict:
    """The seeded weights (one jitted call) in the program's tree, checked
    against the shapes of the program's own initializer."""
    tree = C.arch_program(arch).tree(W.make_all(arch, cfg, seed))
    if cfg["serving"]["weights"] == "float32":
        tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
    model = build_model(arch_config(arch, cfg))
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    got = jax.tree_util.tree_map(lambda a: a.shape, tree)
    if jax.tree_util.tree_map(lambda a: a.shape, want) != got:
        raise ValueError("weights do not match the program's parameter tree")
    return tree


def run_config(cfg: dict) -> RunConfig:
    s = cfg["serving"]
    policy = SoftmaxPolicy(impl=s["softmax"], precision=s["lut_precision"],
                           alpha_len=s["alpha_len"])
    # the program's 'f32' pool means "in the model's dtype"
    kv = "int8" if s["kv_pool"] == "int8" else "f32"
    if s["weights"] not in ("bfloat16", "float32") or (
            kv == "f32" and s["kv_pool"] != s["weights"]):
        raise ValueError("the program serves its weights' dtype or int8 in "
                         f"the pool, not {s['weights']}/{s['kv_pool']}")
    return RunConfig(dtype=s["weights"], softmax_policy=policy, kv_dtype=kv)


def engine(arch, cfg: dict, mix: dict, tree: dict) -> PipelinedEngine:
    s = cfg["serving"]
    ps = PagedCacheConfig().page_size
    cache = PagedCacheConfig(n_pages=s["pool_pages"], page_size=ps,
                             max_pages_per_seq=math.ceil(
                                 mix["max_context"] / ps))
    econf = EngineConfig(n_slots=mix["n_slots"], cache=cache,
                         prefill_chunk=s["prefill_chunk"],
                         prefix_cache=s["prefix_cache"])
    return PipelinedEngine(build_model(arch_config(arch, cfg)), tree,
                           run_config(cfg), econf)


def server(eng: PipelinedEngine) -> AsyncServingServer:
    return AsyncServingServer(eng)


def backends() -> tuple[str, str]:
    """Which paged attention paths the engine resolves to here."""
    return ops.resolve_paged_backend("auto"), \
        ops.resolve_paged_prefill_backend("auto")

