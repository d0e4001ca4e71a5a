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
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import strategies
from repro.kernels.lut_attention.ops import (_tables_for, gather_pages,
                                             lut_attention_decode_varlen,
                                             lut_attention_paged_decode,
                                             resolve_paged_backend)
from repro.kernels.lut_attention.paged_decode import paged_decode_attention

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
    must not influence its output: poison them and compare."""
    pol = POLICIES["lut2d"]
    q, kp, vp, bt, kls = _paged_problem(rng, kv_lens=(9, 13, 5))
    ref = paged_decode_attention(q, kp, vp, bt, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    owned = set()
    bt_np = np.asarray(bt)
    for i, kl in enumerate(np.asarray(kls)):
        owned.update(bt_np[i, :-(-int(kl) // kp.shape[2])])
    junk = [p for p in range(kp.shape[0]) if p not in owned]
    kp2 = kp.at[jnp.asarray(junk)].set(1e6)
    vp2 = vp.at[jnp.asarray(junk)].set(-1e6)
    # also poison the masked tail of each slot's LAST page
    out = paged_decode_attention(q, kp2, vp2, bt, kls, _tables_for(pol),
                                 method=pol.impl, index_mode=pol.index_mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


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
