"""Parity suite for the fused Pallas paged-decode kernel.

The kernel (``kernels/lut_attention/paged_decode.py``, run in interpret
mode on CPU) must reproduce ``lut_attention_decode_varlen`` on the
gathered block-table view across every softmax policy, GQA ratio, and
ragged ``kv_lens`` shape the serving engine can produce.  The integer
LUT pipeline is bit-identical by construction; the final f32
V-contraction accumulates page-chunked instead of row-at-once, so the
comparisons pin a roundoff-level tolerance (2e-6, ~16 ulp at the output
scale) rather than bit equality — the same convention the blocked/pallas
full-attention kernels use against their naive oracle.

The kernel sweeps a block table in blocks of ``decode_block_pages``
pages and skips blocks past a slot's ``kv_lens``; the blocked-sweep
cases pin its geometries (P > 1, P = 1, a table width 128 / page_size
does not divide) and the lengths at a block's edges.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import strategies
from repro.kernels.lut_attention.ops import (_tables_for, gather_pages,
                                             lut_attention_decode_varlen,
                                             lut_attention_paged_decode,
                                             resolve_paged_backend)
from repro.kernels.lut_attention.paged_decode import (decode_block_pages,
                                                      paged_decode_attention)

POLICIES = strategies.make_policies()

TOL = dict(rtol=2e-6, atol=2e-6)


def _paged_problem(rng, *, b=3, kvh=2, g=2, dh=16, ps=4, mp=5,
                   kv_lens=(20, 17, 9), shuffle=True):
    """Random pool + block tables; slot i owns ceil(kv_lens[i]/ps) pages."""
    h = kvh * g
    n_pages = 1 + b * mp  # null page + every slot fully allocated
    q = jnp.asarray(rng.normal(size=(b, h, 1, dh)).astype(np.float32))
    k_pages = jnp.asarray(
        rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32))
    v_pages = jnp.asarray(
        rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32))
    phys = np.arange(1, n_pages)
    if shuffle:
        phys = rng.permutation(phys)
    bt = np.zeros((b, mp), np.int32)
    for i, kl in enumerate(kv_lens):
        n_owned = -(-int(kl) // ps)
        bt[i, :n_owned] = phys[i * mp:i * mp + n_owned]
    return q, k_pages, v_pages, jnp.asarray(bt), jnp.asarray(
        np.asarray(kv_lens, np.int32))


def _dense_ref(q, k_pages, v_pages, bt, kv_lens, policy):
    return lut_attention_decode_varlen(q, gather_pages(k_pages, bt),
                                       gather_pages(v_pages, bt), policy,
                                       kv_lens)


@pytest.mark.parametrize("impl", sorted(POLICIES))
@pytest.mark.parametrize("g", [1, 4])
def test_kernel_matches_dense_across_policies_and_gqa(rng, impl, g):
    """Acceptance: interpret-mode kernel ≡ dense reference for every
    policy × GQA ratio on ragged lengths (page-aligned, partial-page,
    near-empty)."""
    pol = POLICIES[impl]
    q, kp, vp, bt, kls = _paged_problem(rng, g=g, kv_lens=(20, 17, 2))
    out = paged_decode_attention(q, kp, vp, bt, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    ref = _dense_ref(q, kp, vp, bt, kls, pol)
    assert out.shape == ref.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kv_lens", [
    (16, 16, 16),   # every slot exactly on a page boundary
    (1, 1, 1),      # single-token sequences (first decode after 0-cache)
    (4, 20, 1),     # boundary + full + single mixed
    (19, 3, 7),     # partial last pages everywhere
])
def test_kernel_ragged_lengths_edges(rng, kv_lens):
    pol = POLICIES["rexp"]
    q, kp, vp, bt, kls = _paged_problem(rng, kv_lens=kv_lens)
    out = paged_decode_attention(q, kp, vp, bt, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    ref = _dense_ref(q, kp, vp, bt, kls, pol)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_kernel_ignores_junk_pages(rng):
    """Pages outside a slot's block table — including the null page —
    must not influence its output: poison them and compare.  Blocks past
    a slot's ``kv_lens`` point at NaN pages: the kernel never reads them,
    so the output stays bitwise equal."""
    pol = POLICIES["lut2d"]
    ps, mp = 16, 24                  # 3 blocks of 8 pages (128 keys)
    q, kp, vp, bt, kls = _paged_problem(rng, ps=ps, mp=mp,
                                        kv_lens=(9, 140, 5))
    ref = paged_decode_attention(q, kp, vp, bt, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    owned = set()
    bt_np = np.asarray(bt).copy()
    for i, kl in enumerate(np.asarray(kls)):
        owned.update(bt_np[i, :-(-int(kl) // ps)])
    junk = [p for p in range(kp.shape[0]) if p not in owned]
    kp2 = kp.at[jnp.asarray(junk)].set(1e6)
    vp2 = vp.at[jnp.asarray(junk)].set(-1e6)
    # every page of a dead block becomes a NaN page of its own
    block = decode_block_pages(ps, mp)
    n_pages = kp.shape[0]
    dead = [(i, c) for i, kl in enumerate(np.asarray(kls))
            for c in range(-(-int(kl) // (block * ps)) * block, mp)]
    assert dead
    for n, (i, c) in enumerate(dead):
        bt_np[i, c] = n_pages + n
    nan = jnp.full((len(dead),) + kp.shape[1:], jnp.nan, kp.dtype)
    out = paged_decode_attention(
        q, jnp.concatenate([kp2, nan]), jnp.concatenate([vp2, nan]),
        jnp.asarray(bt_np), kls, _tables_for(pol), method=pol.impl,
        index_mode=pol.index_mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# The blocked sweep: geometries from the shapes, block edges, dead blocks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("page_size,mp,pages", [
    (16, 64, 8),     # qwen3-32b-tp8 cell: 8 pages, 128 keys a block
    (16, 160, 8),    # internlm2-20b-tp8 cell
    (16, 12, 6),     # 128 / page_size does not divide the table width
    (16, 13, 1),     # a prime width: one page a block
    (4, 5, 5),       # the whole table in one block
    (256, 4, 1),     # a page wider than a lane row
])
def test_block_pages_come_from_the_shapes(page_size, mp, pages):
    assert decode_block_pages(page_size, mp) == pages


#: (page_size, mp) of the blocked-sweep cases: 3 blocks of 8 pages;
#: 2 blocks of 6 (128 / 16 does not divide 12); 13 blocks of one page
SWEEPS = {"p8": (16, 24), "p6": (16, 12), "p1": (16, 13)}


def _edge_lens(ps, mp):
    """Lengths at a block's edges, and 1 (what an empty slot sends)."""
    keys = decode_block_pages(ps, mp) * ps
    return (keys - 1, keys, keys + 1, 1)


@pytest.mark.parametrize("sweep,g", [("p8", 8), ("p8", 6), ("p6", 6),
                                     ("p1", 8)])
@pytest.mark.parametrize("impl", sorted(POLICIES))
def test_blocked_sweep_matches_dense_at_block_edges(rng, impl, sweep, g):
    """Every policy, the cells' GQA ratios (G = 8 and 6 on one KV head)
    and each block geometry: lengths one short of a block, exactly a
    block, one key into the next, and a single key all match the dense
    reference."""
    ps, mp = SWEEPS[sweep]
    pol = POLICIES[impl]
    q, kp, vp, bt, kls = _paged_problem(rng, b=4, kvh=1, g=g, ps=ps, mp=mp,
                                        kv_lens=_edge_lens(ps, mp))
    out = paged_decode_attention(q, kp, vp, bt, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    ref = _dense_ref(q, kp, vp, bt, kls, pol)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("impl", sorted(POLICIES))
def test_blocked_sweep_int8_pool_matches_dense(rng, impl):
    """The int8 pool on the blocked sweep (P = 8, G = 6): its scale rows
    travel by block beside the pages."""
    from repro.core.quantization import quantize_rows
    ps, mp = SWEEPS["p8"]
    pol = POLICIES[impl]
    q, kp, vp, bt, kls = _paged_problem(rng, b=4, kvh=1, g=6, ps=ps, mp=mp,
                                        kv_lens=_edge_lens(ps, mp))
    pools = []
    for x in (kp, vp):
        xq, sc = quantize_rows(x)          # (n, KVH, ps, Dh), (n, KVH, ps)
        pools.append((xq, sc.reshape(x.shape[0], 1, -1)))
    (kq, ks), (vq, vs) = pools
    kw = dict(k_scales=ks, v_scales=vs)
    pal = lut_attention_paged_decode(q, kq, vq, bt, kls, pol,
                                     backend="pallas", **kw)
    den = lut_attention_paged_decode(q, kq, vq, bt, kls, pol,
                                     backend="dense", **kw)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(den), **TOL)


def test_blocked_sweep_skips_keyless_slots(rng):
    """Slots without a key (first, consecutive, last) run no block: their
    outputs stay the accumulators' init, zero, and the sweep's copies
    hop over them to the next slot that holds a key."""
    pol = POLICIES["rexp"]
    ps, mp = SWEEPS["p8"]
    lens = (0, 129, 0, 0, 5, 0)
    q, kp, vp, bt, kls = _paged_problem(rng, b=len(lens), kvh=1, g=8,
                                        ps=ps, mp=mp, kv_lens=lens)
    out = np.asarray(paged_decode_attention(
        q, kp, vp, bt, kls, _tables_for(pol), method=pol.impl,
        index_mode=pol.index_mode))
    keyed = np.asarray(lens) > 0
    assert not out[~keyed].any()
    ref = _dense_ref(q[keyed], kp, vp, bt[keyed], kls[keyed], pol)
    np.testing.assert_allclose(out[keyed], np.asarray(ref), **TOL)


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_blocked_sweep_copies_are_race_free(rng, quantized):
    """Under the interpreter that simulates the TPU's DMAs and semaphores
    (a copy lands at its wait), with its happens-before race detector
    on, the sweep's copies — the next live block's in flight while a
    block computes, across keyless slots — race with no read, and the
    output is bitwise the plain interpreter's."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from repro.core.quantization import quantize_rows
    pol = POLICIES["rexp"]
    ps, mp = SWEEPS["p8"]
    q, kp, vp, bt, kls = _paged_problem(rng, b=4, kvh=1, g=8, ps=ps, mp=mp,
                                        kv_lens=(0, 129, 0, 300))
    kw = {}
    if quantized:
        (kp, ks), (vp, vs) = [
            (xq, sc.reshape(x.shape[0], 1, -1))
            for x in (kp, vp) for xq, sc in [quantize_rows(x)]]
        kw = dict(k_scales=ks, v_scales=vs)
    run = functools.partial(paged_decode_attention, q, kp, vp, bt, kls,
                            _tables_for(pol), method=pol.impl,
                            index_mode=pol.index_mode, **kw)
    pltpu.reset_tpu_interpret_mode_state()
    out = run(interpret=pltpu.InterpretParams(detect_races=True))
    assert interpret_pallas_call.races is not None
    assert not interpret_pallas_call.races.races_found
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(run(interpret=True)))


def test_dispatcher_auto_resolves_dense_on_cpu():
    assert jax.default_backend() == "cpu"  # the CI environment
    assert resolve_paged_backend("auto") == "dense"
    assert resolve_paged_backend("pallas") == "pallas_interpret"
    assert resolve_paged_backend("dense") == "dense"
    with pytest.raises(ValueError):
        resolve_paged_backend("mosaic")


def test_dispatcher_backends_agree(rng):
    """The public dispatch entry point: forced-pallas (interpret) and
    forced-dense agree for every policy."""
    for impl, pol in POLICIES.items():
        q, kp, vp, bt, kls = _paged_problem(rng, kv_lens=(11, 8, 3))
        pal = lut_attention_paged_decode(q, kp, vp, bt, kls, pol,
                                         backend="pallas")
        den = lut_attention_paged_decode(q, kp, vp, bt, kls, pol,
                                         backend="dense")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(den),
                                   err_msg=impl, **TOL)


def test_kernel_under_jit(rng):
    """The engine jits the decode step; the pallas_call chain must trace."""
    pol = POLICIES["rexp"]
    q, kp, vp, bt, kls = _paged_problem(rng, kv_lens=(6, 12, 4))
    fn = jax.jit(lambda *a: lut_attention_paged_decode(
        *a, pol, backend="pallas"))
    out = fn(q, kp, vp, bt, kls)
    ref = _dense_ref(q, kp, vp, bt, kls, pol)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


# ---------------------------------------------------------------------------
# Property: block-table permutation invariance (shared machinery in
# tests/strategies.py — hypothesis when available, fixed seeds otherwise)
# ---------------------------------------------------------------------------


@strategies.permutation_property()
def test_block_table_permutation_invariance(seed, impl, kv_lens):
    """Physical page placement is an implementation detail: relabelling
    the pool pages (and the block tables with them) must not change the
    kernel output at all — the paged indirection is exact."""
    rng = np.random.default_rng(seed)
    pol = POLICIES[impl]
    q, kp, vp, bt, kls = _paged_problem(rng, b=len(kv_lens),
                                        kv_lens=tuple(kv_lens),
                                        shuffle=False)
    base = paged_decode_attention(q, kp, vp, bt, kls, _tables_for(pol),
                                  method=pol.impl,
                                  index_mode=pol.index_mode)
    kp2, vp2, bt2 = strategies.permute_paged_problem(rng, kp, vp, bt)
    out = paged_decode_attention(q, kp2, vp2, bt2, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
