"""Parity suite for the fused Pallas paged-prefill kernel.

The kernel (``kernels/lut_attention/paged_prefill.py``, run in interpret
mode on CPU) must reproduce ``lut_attention_prefill_varlen`` on the
gathered block-table view across every softmax policy, GQA ratio, and
ragged ``q_start``/``kv_lens`` cursor shape the serving engine can
produce — including partial last chunks (prompt length not a multiple of
the chunk size).  The integer LUT pipeline is bit-identical by
construction; the final f32 V-contraction accumulates page-chunked
instead of row-at-once, so the comparisons pin a roundoff-level
tolerance (2e-6) rather than bit equality — the same convention the
paged-decode suite uses against its oracle.

This file also holds the dispatcher regression tests for the silent
``backend='pallas'`` fallback bug: the dispatcher must route ``pallas``
to the real kernel (no ``gather_pages`` anywhere on that path) and the
documented dispatch matrix must match what the resolvers actually do.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import strategies
from repro.kernels.lut_attention.ops import (_tables_for, gather_pages,
                                             lut_attention,
                                             lut_attention_paged_prefill,
                                             lut_attention_prefill_varlen,
                                             resolve_paged_backend,
                                             resolve_paged_prefill_backend)
from repro.kernels.lut_attention.paged_prefill import paged_prefill_attention

POLICIES = strategies.make_policies()

TOL = dict(rtol=2e-6, atol=2e-6)


def _paged_problem(rng, *, b=3, kvh=2, g=2, dh=16, ps=4, mp=5, c=6,
                   kv_lens=(17, 9, 6), chunk_lens=None, shuffle=True):
    """Random pool + block tables + chunk queries.

    Slot i has ``kv_lens[i]`` valid keys (its chunk included) and its
    chunk carries ``chunk_lens[i]`` real rows (default: full chunks),
    so ``q_start = kv_lens − chunk_lens``.  Slot i owns
    ceil(kv_lens[i]/ps) pages at shuffled physical ids.
    """
    if chunk_lens is None:
        chunk_lens = (c,) * len(kv_lens)
    h = kvh * g
    n_pages = 1 + b * mp  # null page + every slot fully allocated
    q = jnp.asarray(rng.normal(size=(b, h, c, dh)).astype(np.float32))
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
    kls = np.asarray(kv_lens, np.int32)
    qs = kls - np.asarray(chunk_lens, np.int32)
    assert (qs >= 0).all()
    return (q, k_pages, v_pages, jnp.asarray(bt), jnp.asarray(qs),
            jnp.asarray(kls))


def _oracle(q, k_pages, v_pages, bt, q_start, kv_lens, policy):
    return lut_attention_prefill_varlen(
        q, gather_pages(k_pages, bt), gather_pages(v_pages, bt), policy,
        q_start=q_start, kv_lens=kv_lens)


@pytest.mark.parametrize("impl", sorted(POLICIES))
@pytest.mark.parametrize("g", [1, 4])
def test_kernel_matches_oracle_across_policies_and_gqa(rng, impl, g):
    """Acceptance: interpret-mode kernel ≡ gathered varlen oracle for
    every policy × GQA ratio on ragged cursors (page-aligned, partial
    page, chunk-covers-whole-prompt)."""
    pol = POLICIES[impl]
    q, kp, vp, bt, qs, kls = _paged_problem(rng, g=g, kv_lens=(17, 9, 6),
                                            chunk_lens=(6, 6, 6))
    out = paged_prefill_attention(q, kp, vp, bt, qs, kls, _tables_for(pol),
                                  method=pol.impl,
                                  index_mode=pol.index_mode)
    ref = _oracle(q, kp, vp, bt, qs, kls, pol)
    assert out.shape == ref.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kv_lens,chunk_lens", [
    ((16, 16, 16), (6, 6, 6)),   # every slot exactly on a page boundary
    ((6, 6, 6), (6, 6, 6)),      # q_start = 0: the prompt's FIRST chunk
    ((6, 20, 7), (6, 6, 1)),     # first-chunk + deep-cursor + 1-row mixed
    ((19, 9, 3), (3, 5, 2)),     # partial chunks (Lq % C != 0 tails)
])
def test_kernel_ragged_cursor_edges(rng, kv_lens, chunk_lens):
    pol = POLICIES["rexp"]
    q, kp, vp, bt, qs, kls = _paged_problem(rng, kv_lens=kv_lens,
                                            chunk_lens=chunk_lens)
    out = paged_prefill_attention(q, kp, vp, bt, qs, kls, _tables_for(pol),
                                  method=pol.impl,
                                  index_mode=pol.index_mode)
    ref = _oracle(q, kp, vp, bt, qs, kls, pol)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("impl", sorted(POLICIES))
def test_chunk_walk_reassembles_whole_prompt(rng, impl):
    """Walking a prompt through the kernel chunk by chunk (last chunk
    partial: Lq % C != 0) reproduces the whole-prompt causal attention
    row-for-row — same guarantee the varlen-oracle suite pins, now with
    no gather anywhere."""
    pol = POLICIES[impl]
    lq, c, ps, kvh, dh = 21, 8, 4, 2, 16
    mp = -(-lq // ps)
    rng_ = np.random.default_rng(11)

    def gen(s):  # integer-valued: dots exact in f32, LUT bins match
        return np.round(rng_.normal(0, 2, s)).astype(np.float32)

    q_all = jnp.asarray(gen((1, 4, lq, dh)))
    k_log = gen((1, kvh, mp * ps, dh))
    v_log = gen((1, kvh, mp * ps, dh))
    pages = list(1 + rng_.permutation(mp))       # scrambled placement
    pool_k = np.zeros((1 + mp, kvh, ps, dh), np.float32)
    pool_v = np.zeros((1 + mp, kvh, ps, dh), np.float32)
    for j, pg in enumerate(pages):
        pool_k[pg] = k_log[0, :, j * ps:(j + 1) * ps]
        pool_v[pg] = v_log[0, :, j * ps:(j + 1) * ps]
    bt = jnp.asarray([pages], jnp.int32)
    whole = lut_attention(q_all, jnp.asarray(k_log), jnp.asarray(v_log),
                          pol, causal=True, backend="naive",
                          kv_len=jnp.int32(lq))
    rows = []
    for start in range(0, lq, c):
        n = min(c, lq - start)
        qc = jnp.pad(q_all[:, :, start:start + n], (
            (0, 0), (0, 0), (0, c - n), (0, 0)))  # fixed chunk shape
        out = paged_prefill_attention(
            qc, jnp.asarray(pool_k), jnp.asarray(pool_v), bt,
            jnp.asarray([start], jnp.int32),
            jnp.asarray([start + n], jnp.int32), _tables_for(pol),
            method=pol.impl, index_mode=pol.index_mode)
        rows.append(np.asarray(out)[:, :, :n])   # drop padding rows
    np.testing.assert_allclose(np.concatenate(rows, axis=2),
                               np.asarray(whole), **TOL)


def test_kernel_ignores_junk_pages(rng):
    """Pages outside a slot's block table — including the null page —
    must not influence its output: poison them and compare."""
    pol = POLICIES["lut2d"]
    q, kp, vp, bt, qs, kls = _paged_problem(rng, kv_lens=(9, 13, 5),
                                            chunk_lens=(5, 6, 5))
    ref = paged_prefill_attention(q, kp, vp, bt, qs, kls, _tables_for(pol),
                                  method=pol.impl,
                                  index_mode=pol.index_mode)
    owned = set()
    bt_np = np.asarray(bt)
    for i, kl in enumerate(np.asarray(kls)):
        owned.update(bt_np[i, :-(-int(kl) // kp.shape[2])])
    junk = [p for p in range(kp.shape[0]) if p not in owned]
    kp2 = kp.at[jnp.asarray(junk)].set(1e6)
    vp2 = vp.at[jnp.asarray(junk)].set(-1e6)
    out = paged_prefill_attention(q, kp2, vp2, bt, qs, kls,
                                  _tables_for(pol), method=pol.impl,
                                  index_mode=pol.index_mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_under_jit_one_compile(rng):
    """The engine jits the chunk step and feeds it every (q_start,
    kv_lens) cursor value a prompt walk produces: the pallas_call chain
    must trace AND one compile must serve all cursor values."""
    pol = POLICIES["rexp"]
    q, kp, vp, bt, qs, kls = _paged_problem(rng, kv_lens=(11, 8, 6),
                                            chunk_lens=(6, 4, 6))

    @jax.jit
    def fn(q, kp, vp, bt, qs, kls):
        return lut_attention_paged_prefill(q, kp, vp, bt, qs, kls, pol,
                                           backend="pallas")

    out = fn(q, kp, vp, bt, qs, kls)
    ref = _oracle(q, kp, vp, bt, qs, kls, pol)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    # different traced cursors, same shapes → no retrace
    fn(q, kp, vp, bt, qs - 2, kls - 2)
    fn(q, kp, vp, bt, jnp.zeros_like(qs), jnp.full_like(kls, 6))
    from repro.analysis import assert_compile_count
    assert_compile_count(fn, 1, "paged prefill kernel")


# ---------------------------------------------------------------------------
# Dispatcher regression: 'pallas' is the real kernel, never a stand-in
# ---------------------------------------------------------------------------


def test_dispatcher_resolution_on_cpu():
    """Regression for the silent fallback: ``backend='pallas'`` used to
    run the blocked-XLA path on every platform.  The resolver must send
    it to the kernel (interpret off-TPU), exactly like paged decode."""
    assert jax.default_backend() == "cpu"  # the CI environment
    assert resolve_paged_prefill_backend("auto") == "naive"
    assert resolve_paged_prefill_backend("pallas") == "pallas_interpret"
    assert resolve_paged_prefill_backend("dense") == "naive"
    assert resolve_paged_prefill_backend("naive") == "naive"
    assert resolve_paged_prefill_backend("blocked") == "blocked"
    with pytest.raises(ValueError):
        resolve_paged_prefill_backend("mosaic")


def test_dispatcher_pallas_path_never_gathers(rng, monkeypatch):
    """The whole point of the kernel: no ``gather_pages`` (no contiguous
    block-table view) anywhere on the ``backend='pallas'`` prefill path.
    Poison the gather and drive the dispatcher through it."""
    import repro.kernels.lut_attention.ops as ops_mod

    def _boom(*a, **k):
        raise AssertionError("gather_pages called on the pallas "
                             "paged-prefill path")

    monkeypatch.setattr(ops_mod, "gather_pages", _boom)
    pol = POLICIES["rexp"]
    q, kp, vp, bt, qs, kls = _paged_problem(rng, kv_lens=(9, 7, 6),
                                            chunk_lens=(5, 3, 6))
    out = lut_attention_paged_prefill(q, kp, vp, bt, qs, kls, pol,
                                      backend="pallas")  # must not gather
    assert out.shape == q.shape
    with pytest.raises(AssertionError, match="gather_pages"):
        lut_attention_paged_prefill(q, kp, vp, bt, qs, kls, pol,
                                    backend="naive")  # dense path gathers


@pytest.mark.parametrize("impl", sorted(POLICIES))
def test_dispatcher_backends_agree(rng, impl):
    """The public dispatch entry point: forced-pallas (interpret), the
    dense flavors and auto all agree for every policy.  The ``blocked``
    flavor carries the *fused-requant* LUT semantics (binned denominator
    instead of per-element σ — a documented, coarser approximation of
    the faithful pipeline with its own parity tests in
    ``test_chunked_prefill.py``), so it is only compared for ``exact``,
    whose semantics is shared by all five paths."""
    pol = POLICIES[impl]
    q, kp, vp, bt, qs, kls = _paged_problem(rng, kv_lens=(11, 8, 3),
                                            chunk_lens=(6, 5, 3))
    pal = lut_attention_paged_prefill(q, kp, vp, bt, qs, kls, pol,
                                      backend="pallas")
    others = ["naive", "dense", "auto"] + (["blocked"] if impl == "exact"
                                           else [])
    for other in others:
        ref = lut_attention_paged_prefill(q, kp, vp, bt, qs, kls, pol,
                                          backend=other)
        tol = dict(rtol=2e-5, atol=2e-5) if other == "blocked" else TOL
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   err_msg=f"{impl}:{other}", **tol)


# ---------------------------------------------------------------------------
# Docs-as-spec: ONE dispatch matrix, asserted against the resolvers
# ---------------------------------------------------------------------------


def test_dispatch_matrix_docs_match_resolvers():
    """README, kernels/__init__ and ops.py must state one dispatch
    matrix covering BOTH paged kernels, and the matrix must be what the
    resolvers actually implement (on this CPU host: auto→dense flavors,
    pallas→interpret)."""
    import pathlib

    import repro.kernels as K
    import repro.kernels.lut_attention.ops as ops_mod

    # resolvers implement the documented matrix (CPU column)
    assert resolve_paged_backend("auto") == "dense"
    assert resolve_paged_prefill_backend("auto") == "naive"  # dense flavor
    assert resolve_paged_backend("pallas") == "pallas_interpret"
    assert resolve_paged_prefill_backend("pallas") == "pallas_interpret"
    assert resolve_paged_backend("dense") == "dense"
    assert resolve_paged_prefill_backend("dense") == "naive"

    # ... and the two mesh rows: heads when the GQA KV-head count
    # divides the axis, pages otherwise (the full resolver unit test —
    # no-mesh, tp=1, missing axis — lives in test_engine_tp.py)
    from jax.sharding import AbstractMesh
    from repro.kernels.lut_attention.ops import paged_mesh_regime
    tp4 = AbstractMesh((1, 4), ("data", "model"))
    assert paged_mesh_regime(tp4, 4) == "heads"
    assert paged_mesh_regime(tp4, 3) == "pages"

    def flat(text):  # whitespace-normalized: phrases survive line wraps
        return " ".join(text.split())

    # ops.py carries the canonical matrix, one row per knob — including
    # the two mesh rows (heads / pages regimes, (B, H, 1) partials)
    ops_doc = flat(ops_mod.__doc__)
    for needle in ("``auto``", "``pallas``", "``dense``",
                   "interpret mode", "Mosaic/TPU-only",
                   "``mesh``, KVH % tp == 0", "``mesh``, KVH % tp != 0",
                   "'heads' regime", "'pages' regime", "(B, H, 1)"):
        assert needle in ops_doc, f"ops.py docstring lost {needle!r}"
    assert "paged_prefill" in ops_doc and "paged_decode" in ops_doc

    # kernels/__init__ restates it for both kernels, no TPU/GPU drift:
    # GPU is dense-fallback (not "TPU/GPU runs the kernel"), and the
    # mesh rows say what actually shards (heads vs pages, no KV gather)
    pkg_doc = flat(K.__doc__)
    assert "paged_prefill.py" in pkg_doc and "paged_decode.py" in pkg_doc
    assert "GPU falls back to dense" in pkg_doc
    assert "interpret mode off-TPU" in pkg_doc
    assert "'heads' regime" in pkg_doc and "'pages' regime" in pkg_doc
    assert "never gathered KV" in pkg_doc

    # README's serving section shows the same matrix for both kernels
    readme = flat((pathlib.Path(__file__).resolve().parent.parent
                   / "README.md").read_text())
    assert "| `auto` |" in readme and "| `pallas` |" in readme \
        and "| `dense` |" in readme, "README lost the dispatch matrix"
    assert "decode + prefill" in readme
    assert "interpret" in readme
    assert "| any knob + `mesh` (tp > 1), KVH % tp == 0 |" in readme \
        and "| any knob + `mesh` (tp > 1), KVH % tp != 0 |" in readme, \
        "README lost the mesh rows of the dispatch matrix"
    assert "`heads` regime" in readme and "`pages` regime" in readme

    # ... and the quantized rows: int8 pages keep the same matrix shape
    # (fused kernels stream scales + dequant in VMEM; dense paths
    # dequantize the gathered view; mesh shards scales with pages)
    for needle in ("``int8`` + fused kernel", "``int8`` + dense / mesh",
                   "kernel_spec_int8"):
        assert needle in ops_doc, f"ops.py docstring lost {needle!r}"
    assert "kv_dtype=int8" in pkg_doc
    assert "scales shard with their pages" in pkg_doc
    assert "| any knob + `--kv-dtype int8` |" in readme \
        and "| `--kv-dtype int8` + `mesh` (tp > 1) |" in readme, \
        "README lost the quantized rows of the dispatch matrix"
    assert "Quantized KV pool (`--kv-dtype int8`)" in readme


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
    chunk_lens = tuple(min(int(kl), 6) for kl in kv_lens)
    q, kp, vp, bt, qs, kls = _paged_problem(rng, b=len(kv_lens),
                                            kv_lens=tuple(kv_lens),
                                            chunk_lens=chunk_lens,
                                            shuffle=False)
    base = paged_prefill_attention(q, kp, vp, bt, qs, kls,
                                   _tables_for(pol), method=pol.impl,
                                   index_mode=pol.index_mode)
    kp2, vp2, bt2 = strategies.permute_paged_problem(rng, kp, vp, bt)
    out = paged_prefill_attention(q, kp2, vp2, bt2, qs, kls,
                                  _tables_for(pol), method=pol.impl,
                                  index_mode=pol.index_mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
