"""Tensor-parallel paged serving: sharded engine ≡ single-device engine.

Each multi-device test runs in a subprocess with
``--xla_force_host_platform_device_count=4`` (the repo's dry-run
isolation rule — the main pytest process keeps its single real device),
driving BOTH sharded regimes of the paged dispatch on a 4-way mesh:

* 'heads' (KVH % tp == 0): pool sharded on KV heads, attention fully
  local per shard;
* 'pages' (KVH does not divide tp): pool sharded on the physical-page
  axis, per-slab (m, Σ, σ·V) partials reduced with pmax + integer-Σ
  psum.

The acceptance gates: token identity with the single-device engine for
exact/REXP/2D-LUT, and a compiled-HLO regression (via
``launch/hlo_analysis.py``) that decode exchanges only (B, H, 1)-shaped
partials — never gathered KV.  Host-side mesh plumbing (regime
resolver, slab-interleaved page allocation, padded pool shapes) is
tested in-process, no devices needed.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = r"""
import sys
sys.path.insert(0, {tests_dir!r})
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import ARCHS, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.models import build_model
from repro.runtime import EngineConfig, PagedCacheConfig, ServingEngine
from repro.launch.mesh import make_serving_mesh

mesh = make_serving_mesh(4)
CACHE = PagedCacheConfig(n_pages=30, page_size=8, max_pages_per_seq=8)

def run_cfg(impl, kv_dtype='f32'):
    pol = (SoftmaxPolicy(impl=impl, precision='uint8')
           if impl != 'exact' else SoftmaxPolicy())
    return RunConfig(dtype='float32', attention_backend='naive',
                     scan_layers=True, softmax_policy=pol,
                     kv_dtype=kv_dtype)

def small_model(kvh, heads=4):
    arch = ARCHS['qwen3-32b'].scaled_down(d_model=64, n_heads=heads,
                                          n_kv_heads=kvh, vocab=128,
                                          n_periods=2)
    model = build_model(arch)
    return arch, model, model.init(jax.random.PRNGKey(0))
"""


def run_py(code: str, devices: int = 4, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = _PRELUDE.format(
        tests_dir=os.path.join(REPO, "tests")) + code
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# Host-side mesh plumbing (no devices needed)
# ---------------------------------------------------------------------------


def test_mesh_regime_resolver():
    """The mesh rows of the dispatch matrix, on abstract meshes."""
    from jax.sharding import AbstractMesh
    from repro.kernels.lut_attention.ops import paged_mesh_regime
    tp4 = AbstractMesh((1, 4), ("data", "model"))
    tp1 = AbstractMesh((1, 1), ("data", "model"))
    no_model = AbstractMesh((4,), ("data",))
    assert paged_mesh_regime(None, 4) is None
    assert paged_mesh_regime(tp1, 4) is None          # tp == 1: single-device
    assert paged_mesh_regime(no_model, 4) is None
    assert paged_mesh_regime(tp4, 4) == "heads"
    assert paged_mesh_regime(tp4, 8) == "heads"
    assert paged_mesh_regime(tp4, 1) == "pages"
    assert paged_mesh_regime(tp4, 3) == "pages"


def test_pool_pspec_heads_else_pages():
    """paged_pool_pspec mirrors cache_pspec's heads-else-length fallback:
    KV heads over 'model' when divisible, else the page axis."""
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    from repro.runtime.partitioning import paged_pool_pspec
    tp4 = AbstractMesh((1, 4), ("data", "model"))
    assert paged_pool_pspec(None, 4) == P()
    assert paged_pool_pspec(tp4, 4) == P(None, "model", None, None)
    assert paged_pool_pspec(tp4, 3) == P("model", None, None, None)


def test_pool_shape_pads_page_axis_to_slabs():
    from repro.runtime.paged_cache import padded_n_pages, pool_shape
    assert padded_n_pages(30, 4) == 32 and padded_n_pages(32, 4) == 32
    assert pool_shape(30, 8, 2, 16, tp=4) == (32, 2, 8, 16)
    assert pool_shape(30, 8, 2, 16) == (30, 2, 8, 16)
    with pytest.raises(ValueError):
        padded_n_pages(8, 0)


def test_allocator_interleaves_across_slabs():
    """Mesh-aware allocation: with tp set, consecutive allocations
    round-robin over the device slabs (pages-regime load balance), stay
    deterministic, still hand out every usable page exactly once — and
    the balance survives free/alloc churn, because a freed page returns
    to its owning slab's FIFO rather than one global list."""
    from repro.runtime.paged_cache import PageAllocator
    # 16 pages, tp=4 → slabs of 4: [0..3][4..7][8..11][12..15]
    alloc = PageAllocator(16, tp=4)
    first = alloc.alloc(4)
    assert sorted(p // 4 for p in first) == [0, 1, 2, 3], \
        f"first 4 pages {first} do not cover all 4 slabs"
    rest = alloc.alloc(alloc.n_free)
    assert sorted(first + rest) == list(range(1, 16))  # full coverage
    assert PageAllocator(16, tp=4).alloc(4) == first   # deterministic
    # churn: free an unbalanced set (all of slab 1 + a few strays), then
    # re-allocate — the next 4 pages must again cover 4 distinct slabs
    churn = PageAllocator(16, tp=4)
    held = churn.alloc(15)
    churn.free([p for p in held if p // 4 == 1] + [3, 9, 14])
    again = churn.alloc(4)
    assert len({p // 4 for p in again}) == 4, \
        f"post-churn allocation {again} collapsed onto fewer slabs"
    # tp=1 keeps the historical plain-FIFO order
    assert PageAllocator(8).alloc(7) == list(range(1, 8))


# ---------------------------------------------------------------------------
# Sharded engine ≡ single-device engine (forced 4-device CPU mesh)
# ---------------------------------------------------------------------------

_ENGINE_IDENTITY = r"""
kvh = {kvh}
arch, model, params = small_model(kvh, heads={heads})
rng = np.random.default_rng(3)
reqs = [(rng.integers(0, 128, size=int(l)).tolist(), int(m))
        for l, m in [(9, 7), (21, 6), (4, 8), (14, 5)]]
for impl in ['exact', 'rexp', 'lut2d']:
    run = run_cfg(impl)
    cfg = EngineConfig(n_slots=3, cache=CACHE, prefill_chunk=5)
    ref = ServingEngine(model, params, run, cfg).run(list(reqs))
    tpe = ServingEngine(model, params, run,
                        dataclasses.replace(cfg, mesh=mesh))
    out = tpe.run(list(reqs))
    assert tpe.tp == 4
    for i in range(len(reqs)):
        np.testing.assert_array_equal(
            out[i].tokens, ref[i].tokens,
            err_msg=f'{{impl}} request {{i}} (kvh={kvh})')
print('TP-IDENTITY-OK')
"""


def test_tp_engine_token_identical_heads_regime():
    """Acceptance: KVH = tp = 4 — KV-head-sharded pool, every policy
    token-identical to the single-device engine (bitwise attention per
    head shard + replicated surrounding compute)."""
    assert "TP-IDENTITY-OK" in run_py(_ENGINE_IDENTITY.format(kvh=4,
                                                             heads=4))


def test_tp_engine_token_identical_heads_regime_gqa():
    """Acceptance: the heads regime with a real GQA group (H=8, KVH=4,
    g=2 on tp=4) — exercises the contiguous-H-slice ↔ KVH-slice
    alignment that g=1 satisfies trivially (query head h must attend
    its own kv head h // g on every shard)."""
    assert "TP-IDENTITY-OK" in run_py(_ENGINE_IDENTITY.format(kvh=4,
                                                             heads=8))


def test_tp_engine_token_identical_pages_regime():
    """Acceptance: KVH = 1 on a 4-way axis — page-slab partial
    reduction (the sharded_decode.py fallback, paged), every policy
    token-identical to the single-device engine."""
    assert "TP-IDENTITY-OK" in run_py(_ENGINE_IDENTITY.format(kvh=1,
                                                             heads=4))


def test_tp_engine_int8_token_identical_both_regimes():
    """Acceptance: the quantized pool on a 4-way mesh — scale leaves
    sharded with their pages (KV-head axis in 'heads', page axis in
    'pages'), scattered atomically with them — token-identical to the
    single-device int8 engine in both regimes."""
    out = run_py(r"""
for kvh in (4, 1):                      # heads regime, then pages
    arch, model, params = small_model(kvh)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, 128, size=int(l)).tolist(), int(m))
            for l, m in [(9, 7), (21, 6), (4, 8), (14, 5)]]
    for impl in ['exact', 'rexp']:
        run = run_cfg(impl, kv_dtype='int8')
        cfg = EngineConfig(n_slots=3, cache=CACHE, prefill_chunk=5)
        ref = ServingEngine(model, params, run, cfg).run(list(reqs))
        tpe = ServingEngine(model, params, run,
                            dataclasses.replace(cfg, mesh=mesh))
        assert tpe.tp == 4
        assert tpe.pools[0]['k_pages'].dtype == jnp.int8
        assert tpe.pools[0]['k_scales'].dtype == jnp.float32
        out = tpe.run(list(reqs))
        for i in range(len(reqs)):
            np.testing.assert_array_equal(
                out[i].tokens, ref[i].tokens,
                err_msg=f'{impl} request {i} (kvh={kvh})')
print('TP-INT8-OK')
""")
    assert "TP-INT8-OK" in out


def test_tp_engine_int8_prefix_cow_pages_regime():
    """Acceptance: COW prefix sharing on the sharded *quantized* pool
    (pages regime — the copy's src/dst pages generally live on
    different device slabs): page AND scale move in one step, so every
    request stays token-identical to the single-device int8 no-sharing
    engine.  A scale left on the old slab would corrupt every token
    decoded off the copied page."""
    out = run_py(r"""
arch, model, params = small_model(1)    # kvh=1 → pages regime
run = run_cfg('lut2d', kv_dtype='int8')
ps = CACHE.page_size
rng = np.random.default_rng(11)
pre = rng.integers(0, 128, size=2 * ps).tolist()
reqs = [(pre + rng.integers(0, 128, size=t).tolist(), int(m))
        for t, m in [(5, 6), (0, 7), (ps, 5), (0, 6), (3, 8)]]

def drive(eng):
    out = {}
    for p, m in reqs:
        eng.add_request(p, m)
        for res in eng.step():
            out[res.request_id] = res
    while eng.scheduler.has_work():
        for res in eng.step():
            out[res.request_id] = res
    return out

ref = drive(ServingEngine(model, params, run,
                          EngineConfig(n_slots=3, cache=CACHE)))
tpe = ServingEngine(model, params, run,
                    EngineConfig(n_slots=3, cache=CACHE, mesh=mesh,
                                 prefix_cache=True))
out = drive(tpe)
assert tpe.stats.cow_copies > 0, 'duplicate prompts never forced a COW'
assert tpe.stats.pages_shared > 0
for i in range(len(reqs)):
    np.testing.assert_array_equal(out[i].tokens, ref[i].tokens,
                                  err_msg=f'request {i}')
print('TP-INT8-COW-OK')
""")
    assert "TP-INT8-COW-OK" in out


_PIPELINED_IDENTITY = r"""
from repro.runtime import PipelinedEngine
kvh = {kvh}
arch, model, params = small_model(kvh, heads={heads})
rng = np.random.default_rng(4)
reqs = [dict(prompt=rng.integers(0, 128, size=int(l)).tolist(),
             max_new_tokens=int(m), temperature=float(t), seed=i)
        for i, (l, m, t) in enumerate(
            [(9, 7, 0.0), (21, 6, 0.9), (4, 8, 0.0), (14, 5, 1.1)])]
for impl in ['exact', 'lut2d']:
    run = run_cfg(impl)
    cfg = EngineConfig(n_slots=3, cache=CACHE, prefill_chunk=5)
    ref = ServingEngine(model, params, run, cfg).run(
        [dict(r) for r in reqs])
    pipe = PipelinedEngine(model, params, run,
                           dataclasses.replace(cfg, mesh=mesh))
    out = pipe.run([dict(r) for r in reqs])
    assert pipe.tp == 4
    for i in range(len(reqs)):
        np.testing.assert_array_equal(
            out[i].tokens, ref[i].tokens,
            err_msg=f'{{impl}} request {{i}} (kvh={kvh})')
        assert out[i].finish_reason == ref[i].finish_reason
print('TP-PIPELINED-OK')
"""


def test_tp_pipelined_engine_token_identical_heads_regime():
    """Acceptance: the pipelined engine on a 4-way mesh (KV-head-
    sharded pool) — fused on-device sampling over replicated logits,
    the device-resident token buffer, and speculative harvests are all
    token-identical to the single-device *sync* engine, greedy and
    sampled requests alike."""
    assert "TP-PIPELINED-OK" in run_py(
        _PIPELINED_IDENTITY.format(kvh=4, heads=4))


def test_tp_pipelined_engine_token_identical_pages_regime():
    """Acceptance: same, on the pages regime (KVH = 1, page-slab
    partial reductions under the fused sampled step)."""
    assert "TP-PIPELINED-OK" in run_py(
        _PIPELINED_IDENTITY.format(kvh=1, heads=4))


def test_tp_engine_evictions_and_staggered_arrivals():
    """The sharded engine composes with the scheduler: staggered
    arrivals + a pool small enough to force eviction/replay still
    decode token-identically to the single-device engine."""
    out = run_py(r"""
kvh = 1  # pages regime — the harder reduction path
arch, model, params = small_model(kvh)
tiny = PagedCacheConfig(n_pages=10, page_size=8, max_pages_per_seq=8)
run = run_cfg('rexp')
rng = np.random.default_rng(1)
reqs = [(rng.integers(0, 128, size=l).tolist(), m)
        for l, m in [(20, 30), (16, 30), (12, 20), (8, 16)]]

def drive(eng):
    out = {}
    for step, (p, m) in enumerate(reqs):
        eng.add_request(p, m)          # arrival staggered by one step
        for res in eng.step():
            out[res.request_id] = res
    while eng.scheduler.has_work():
        for res in eng.step():
            out[res.request_id] = res
    return out

ref = drive(ServingEngine(model, params, run,
                          EngineConfig(n_slots=3, cache=tiny)))
tpe = ServingEngine(model, params, run,
                    EngineConfig(n_slots=3, cache=tiny, mesh=mesh))
out = drive(tpe)
assert tpe.stats.preemptions > 0, 'pool never pressured'
assert tpe.scheduler.allocator.n_free == tiny.usable_pages
for i in range(len(reqs)):
    np.testing.assert_array_equal(out[i].tokens, ref[i].tokens,
                                  err_msg=f'request {i}')
print('TP-EVICT-OK')
""")
    assert "TP-EVICT-OK" in out


_PREFIX_SHARING = r"""
kvh = {kvh}
arch, model, params = small_model(kvh)
run = run_cfg('lut2d')
ps = CACHE.page_size
rng = np.random.default_rng(11)
pre = rng.integers(0, 128, size=2 * ps).tolist()
# tail 0 → an exact duplicate of the preamble-only prompt: the
# copy-on-write case (and on the pages regime the COW copy's src/dst
# pages generally live on different device slabs)
reqs = [(pre + rng.integers(0, 128, size=t).tolist(), int(m))
        for t, m in [(5, 6), (0, 7), (ps, 5), (0, 6), (3, 8)]]

def drive(eng):
    out = {{}}
    for p, m in reqs:
        eng.add_request(p, m)          # staggered: warm trie for later reqs
        for res in eng.step():
            out[res.request_id] = res
    while eng.scheduler.has_work():
        for res in eng.step():
            out[res.request_id] = res
    return out

ref = drive(ServingEngine(model, params, run,
                          EngineConfig(n_slots=3, cache=CACHE)))
tpe = ServingEngine(model, params, run,
                    EngineConfig(n_slots=3, cache=CACHE, mesh=mesh,
                                 prefix_cache=True))
out = drive(tpe)
assert tpe.tp == 4
assert tpe.stats.pages_shared > 0, 'schedule never shared a page'
assert tpe.stats.cow_copies > 0, 'duplicate prompts never forced a COW'
assert tpe.stats.prefix_hit_tokens > 0
for i in range(len(reqs)):
    np.testing.assert_array_equal(out[i].tokens, ref[i].tokens,
                                  err_msg=f'request {{i}} (kvh={kvh})')
print('TP-PREFIX-OK')
"""


def test_tp_engine_prefix_cache_token_identical_pages_regime():
    """Acceptance: prefix sharing + COW on a 4-way page-sharded pool
    (KVH=1) — shared block-table rows point across device slabs and the
    COW device copy moves a page between slabs, yet every request stays
    token-identical to the single-device no-sharing engine."""
    assert "TP-PREFIX-OK" in run_py(_PREFIX_SHARING.format(kvh=1))


def test_tp_engine_prefix_cache_token_identical_heads_regime():
    """Acceptance: prefix sharing + COW with the pool sharded on KV
    heads (KVH = tp = 4) — the copy touches every head shard of the
    page — token-identical to the single-device no-sharing engine."""
    assert "TP-PREFIX-OK" in run_py(_PREFIX_SHARING.format(kvh=4))


# ---------------------------------------------------------------------------
# HLO regression: decode exchanges only (B, H, 1)-shaped partials
# ---------------------------------------------------------------------------


def test_tp_decode_hlo_exchanges_only_partials():
    """Compile the sharded engine's decode step and hold it to the
    analyzer's collective-budget predicate (``repro.analysis``): no
    full-KV all-gather in either regime — the 'pages' regime moves only
    the (B, H, 1) max/Σ partials plus the (B, H, 1, D) output psum, the
    'heads' regime only the replicated (B, H, 1, D) output.  Same
    budgets as the original PR 5 parse_collectives version; the
    predicate's per-op cap is inclusive, hence the ``- 1``."""
    out = run_py(r"""
from repro.runtime.paged_cache import decode_view, view_arrays
from repro.analysis import (collective_budget_violations,
                            collectives_summary, donation_violations)

run = run_cfg('rexp')
for kvh, regime in [(1, 'pages'), (4, 'heads')]:
    arch, model, params = small_model(kvh)
    eng = ServingEngine(model, params, run,
                        EngineConfig(n_slots=3, cache=CACHE, mesh=mesh))
    view = view_arrays(decode_view({}, eng.n_slots, CACHE), mesh)
    with eng._mesh_ctx():
        compiled = eng._decode_fn.lower(eng.params, view.tokens, eng.pools,
                                        view.block_tables,
                                        view.lengths).compile()
    text = compiled.as_text()
    pool_bytes = (CACHE.n_pages * CACHE.page_size * kvh
                  * arch.resolved_head_dim * 4)
    b, h, d = eng.n_slots, arch.n_heads, arch.resolved_head_dim
    # (B,H,1) partials (m, Σ) + (B,H,1,D) output, f32, 2x margin
    bad = collective_budget_violations(
        text,
        max_tensor_bytes=2 * b * h * (d + 2) * 4,
        max_op_tensor_bytes={'all-gather': pool_bytes // 4 - 1},
        require=('all-reduce',) if regime == 'pages' else ())
    assert not bad, f'{regime}: ' + '; '.join(bad)
    # the pool pytree must still be donated in both regimes
    assert not donation_violations(text, 2), regime
    print(regime, collectives_summary(text)['total'])
print('TP-HLO-OK')
""")
    assert "TP-HLO-OK" in out


# ---------------------------------------------------------------------------
# Permutation invariance of the sharded dispatch (shared strategies)
# ---------------------------------------------------------------------------


def test_tp_dispatch_permutation_invariance():
    """Relabelling physical pages must not change the sharded dispatch
    output: bit-for-bit in the 'heads' regime (pages never change
    devices), and to kernel-suite tolerance in the 'pages' regime —
    there a relabelling migrates keys between slabs, so the integer
    pipeline (bins, e_int, Σ, σ_int) stays identical but the final f32
    σ·V contraction reassociates across the psum."""
    out = run_py(r"""
import strategies
from repro.kernels.lut_attention.ops import (lut_attention_paged_decode,
                                             paged_mesh_regime)

POLICIES = strategies.make_policies()

def problem(rng, b, kvh, g, kv_lens, ps=4, mp=5, dh=16):
    h = kvh * g
    n_pages = -(-(1 + b * mp) // 4) * 4   # slab-divisible (tp=4)
    q = jnp.asarray(rng.normal(size=(b, h, 1, dh)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32))
    bt = np.zeros((b, mp), np.int32)
    for i, kl in enumerate(kv_lens):
        n_owned = -(-int(kl) // ps)
        bt[i, :n_owned] = np.arange(1 + i * mp, 1 + i * mp + n_owned)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(np.asarray(kv_lens, np.int32))

for kvh, g in [(4, 1), (4, 2), (1, 4)]:  # heads (MHA + GQA) and pages
    regime = paged_mesh_regime(mesh, kvh)
    for seed, impl, kv_lens in strategies.FALLBACK_PERMUTATION_CASES:
        rng = np.random.default_rng(seed)
        pol = POLICIES[impl]
        q, kp, vp, bt, kls = problem(rng, len(kv_lens), kvh, g, kv_lens)
        base = lut_attention_paged_decode(q, kp, vp, bt, kls, pol, mesh=mesh)
        kp2, vp2, bt2 = strategies.permute_paged_problem(rng, kp, vp, bt)
        out = lut_attention_paged_decode(q, kp2, vp2, bt2, kls, pol, mesh=mesh)
        if regime == 'heads':
            np.testing.assert_array_equal(np.asarray(out), np.asarray(base),
                                          err_msg=f'{regime}/{impl}/{seed}')
        else:
            np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                       rtol=2e-6, atol=2e-6,
                                       err_msg=f'{regime}/{impl}/{seed}')
print('TP-PERMUTE-OK')
""")
    assert "TP-PERMUTE-OK" in out
