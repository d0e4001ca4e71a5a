"""Static kernel guard: VMEM accounting, grid coverage, overflow proof,
LUT census, clamp probes, and the ANALYSIS_kernels.json ratchet.

The boundary tests pin the derived integer-Σ bounds at exactly max_lk
(pass) and max_lk + 1 (fail), and the negative tests prove a widened
BlockSpec / raised context / shrunk budget flips the contract — the CI
failure modes the guard exists for.
"""

import dataclasses
import itertools
import math

import numpy as np
import pytest

from repro.analysis import kernel_guard as kg
from repro.core import lut_builder
from repro.core.precision import (PRECISIONS, SIGMA_ACC_LIMIT,
                                  sigma_acc_max_lk)

TEST_GEOM = kg.GEOMETRIES["test"]


@pytest.fixture(scope="module")
def fresh_report():
    """One full guard run shared by the report-level tests."""
    return kg.check_kernels()


# ---------------------------------------------------------------------------
# (b) Integer-Σ overflow proof
# ---------------------------------------------------------------------------


def test_max_lk_bounds_pinned():
    # SIGMA_ACC_LIMIT is the f32-exact limit (kernels accumulate Σ in f32,
    # which binds before int32 would)
    assert SIGMA_ACC_LIMIT == 1 << 24
    expected = {"int16": 512, "uint8": 65793, "uint4": 1118481,
                "uint2": 5592405}
    for name, bound in expected.items():
        assert PRECISIONS[name].max_lk == bound
        assert sigma_acc_max_lk(PRECISIONS[name].qmax) == bound


@pytest.mark.parametrize("precision", ["int16", "uint8", "uint4", "uint2"])
def test_policy_ledger_boundary_exact_max_lk(precision):
    bound = PRECISIONS[precision].max_lk
    # a context of exactly max_lk passes for this precision...
    led = kg.policy_ledger(SIGMA_ACC_LIMIT, {"probe": bound})
    for method in ("rexp", "lut2d"):
        p = led[f"{method}/{precision}"]
        assert p["max_lk"] == bound and p["margin"] == 0
        assert not [v for v in p["violations"] if "overflow" in v]
    # ...and max_lk + 1 fails with the bound in the message
    led = kg.policy_ledger(SIGMA_ACC_LIMIT, {"probe": bound + 1})
    for method in ("rexp", "lut2d"):
        bad = led[f"{method}/{precision}"]["violations"]
        assert any("overflow bound" in v and str(bound) in v for v in bad)


@pytest.mark.parametrize("builder", [lut_builder.build_rexp_tables,
                                     lut_builder.build_lut2d_tables])
def test_table_builders_mirror_overflow_bound(builder):
    bound = PRECISIONS["uint8"].max_lk
    tables = builder("uint8", max_context=bound)  # boundary: accepted
    assert tables.max_lk == bound
    assert f"max_lk={bound}" in repr(tables)
    with pytest.raises(ValueError, match="overflow bound"):
        builder("uint8", max_context=bound + 1)


def test_engine_rejects_overflowing_context(small_lm_guard):
    from repro.configs import RunConfig
    from repro.core.policies import SoftmaxPolicy
    from repro.runtime import EngineConfig, PagedCacheConfig, ServingEngine
    model, params = small_lm_guard
    run = RunConfig(dtype="float32", attention_backend="naive",
                    scan_layers=True,
                    softmax_policy=SoftmaxPolicy(impl="rexp",
                                                 precision="int16"))
    # int16 bound is 512; 80 pages × 8 = 640 keys max per row
    cache = PagedCacheConfig(n_pages=100, page_size=8, max_pages_per_seq=80)
    with pytest.raises(ValueError, match="overflow bound max_lk=512"):
        ServingEngine(model, params, run,
                      EngineConfig(n_slots=2, cache=cache))
    # the same geometry with a narrower table precision is fine
    run_ok = RunConfig(dtype="float32", attention_backend="naive",
                       scan_layers=True,
                       softmax_policy=SoftmaxPolicy(impl="rexp",
                                                    precision="uint8"))
    ServingEngine(model, params, run_ok,
                  EngineConfig(n_slots=2, cache=cache))


@pytest.fixture(scope="module")
def small_lm_guard():
    import jax
    from repro.configs import ARCHS
    from repro.models import build_model
    arch = ARCHS["qwen3-32b"].scaled_down(d_model=64, n_heads=4, vocab=128,
                                          n_periods=2)
    model = build_model(arch)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# (d) LUT byte census
# ---------------------------------------------------------------------------


def test_lut_census_pinned_to_paper_budget():
    led = kg.policy_ledger(SIGMA_ACC_LIMIT, {"probe": 128})
    # the paper's "~700 Bytes" headline bundle: uint8 2D-LUT
    assert led["lut2d/uint8"]["lut_bytes"] == 761
    assert led["rexp/uint8"]["lut_bytes"] == 24
    for p in led.values():
        assert p["lut_bytes"] <= lut_builder.LUT_BYTE_BUDGET


def test_table_census_shape():
    c = lut_builder.table_census(lut_builder.build_rexp_tables("uint4"))
    assert c["precision"] == "uint4" and c["qmax"] == 15
    assert c["lut_bytes"] == sum(c["tables"].values())
    assert c["max_lk"] == PRECISIONS["uint4"].max_lk


# ---------------------------------------------------------------------------
# (a) VMEM working sets + the widened-BlockSpec negative test
# ---------------------------------------------------------------------------


def test_registry_clean_at_all_geometries(fresh_report):
    report = fresh_report
    assert report["n_violations"] == 0
    assert set(report["kernels"]) == {"lut_attention", "paged_decode",
                                      "paged_decode_int8", "paged_prefill",
                                      "paged_prefill_int8", "sharded_decode",
                                      "sharded_paged"}
    for entry in report["kernels"].values():
        assert set(entry["geometries"]) == set(kg.GEOMETRIES)


def test_streamed_operands_double_buffered():
    # need a geometry whose K axis spans several blocks — at "test" scale
    # the whole K fits one block and nothing streams
    spec = kg.kernel_registry(kg.GEOMETRIES["qwen3-32b-8k"])["lut_attention"]
    rowmax = next(p for p in spec.passes if p.name == "rowmax")
    q, k = rowmax.inputs
    assert rowmax.grid[-1] > 1
    # k streams along the innermost K axis (double-buffered); q is resident
    ws = kg.pass_working_set(rowmax)
    assert ws["k"] == 2 * kg._block_bytes(k)
    assert ws["q"] == kg._block_bytes(q)


def test_widened_blockspec_flips_vmem_contract(monkeypatch):
    """A kernel edit that widens a block changes the declaration and the
    guard's verdict automatically — the acceptance-criteria negative."""
    from jax.experimental import pallas as pl
    from repro.kernels.lut_attention import lut_attention as la

    geom = kg.GEOMETRIES["qwen3-32b-8k"]
    assert not kg.check_kernel(la.kernel_spec(geom))[0]

    orig = la._specs

    def widened(b, h, kvh, lq, lk, d, bq, bk):
        q_spec, _, v_spec, m_spec, o_spec = orig(b, h, kvh, lq, lk, d,
                                                 bq, bk)
        k_spec = pl.BlockSpec((b, kvh, lk, d),  # whole K resident at once
                              lambda bi, hi, qi, ki: (0, 0, 0, 0))
        return q_spec, k_spec, v_spec, m_spec, o_spec

    monkeypatch.setattr(la, "_specs", widened)
    violations, _ = kg.check_kernel(la.kernel_spec(geom))
    assert any("VMEM working set" in v and "exceeds budget" in v
               for v in violations)


def test_shrunk_budget_flips_vmem_contract():
    spec = kg.kernel_registry(TEST_GEOM)["paged_decode"]
    ok, _ = kg.check_kernel(spec)
    assert not ok
    bad, _ = kg.check_kernel(spec, limit=1024)  # budget shrunk under it
    assert any("VMEM working set" in v for v in bad)


# ---------------------------------------------------------------------------
# Quantized (int8) kernel declarations
# ---------------------------------------------------------------------------


def _streamed_bytes(ps):
    """Double-buffered bytes of the operands that stream along the
    innermost (page) axis, and the VMEM scratch a kernel copies its pages
    into — the pool traffic the quantized pools halve."""
    return sum(2 * kg._block_bytes(op) for op in ps.inputs
               if kg._varies_innermost(op, ps)) + sum(
                   kg.scratch_bytes(ps).values())


@pytest.mark.parametrize("base", ["paged_decode", "paged_prefill"])
@pytest.mark.parametrize("gname", sorted(kg.GEOMETRIES))
def test_int8_streamed_vmem_at_most_055x(base, gname):
    """int8 pages + f32 scales stream ≤ 0.55× the f32 pages' bytes.

    Per page the ratio is (ps·dh·1 + s·4) / (ps·dh·4), with s = ps
    scales (prefill: the head's) or KVH·ps (decode: every head's row) —
    ≤ 0.32 for the shipped head dims and 0.5 at the test geometry's
    dh 16, asserted at the looser 0.55 criterion so a future
    scale-granularity change has headroom without losing the headline.
    """
    reg = kg.kernel_registry(kg.GEOMETRIES[gname])
    f32 = {p.name: p for p in reg[base].passes}
    for ps in reg[base + "_int8"].passes:
        ref = _streamed_bytes(f32[ps.name])
        quant = _streamed_bytes(ps)
        assert ref > 0
        assert quant <= 0.55 * ref, (base, gname, ps.name, quant, ref)


@pytest.mark.parametrize("base", ["paged_decode", "paged_prefill"])
def test_int8_clean_and_scale_less_spec_flips_contract(base):
    """The shipped int8 spec passes the guard; the same spec with its
    scale operands stripped flips the quantized-pairing contract."""
    spec = kg.kernel_registry(TEST_GEOM)[base + "_int8"]
    violations, info = kg.check_kernel(spec)
    assert not violations
    assert all(any(op.dtype == "int8" for op in ps.inputs)
               for ps in spec.passes)
    stripped = dataclasses.replace(spec, passes=tuple(
        dataclasses.replace(ps, inputs=tuple(
            op for op in ps.inputs if "scale" not in op.name))
        for ps in spec.passes))
    v, _ = kg.check_kernel(stripped)
    assert any("no float32 scale operand" in x for x in v)


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("gname", sorted(kg.GEOMETRIES))
def test_paged_decode_declares_its_scratch_and_it_fits(gname, quantized):
    """The decode kernel copies its blocks itself: each pass declares a
    double-buffered (2, P, ps, Dh) page scratch per stream (K; K and V
    in σ·V) with two DMA semaphores, and the sweep's SMEM state.  The
    pool operands count no VMEM of their own, the scratch counts in
    full, and the whole fits the budget at every geometry."""
    from repro.kernels.lut_attention.paged_decode import decode_block_pages
    geom = kg.GEOMETRIES[gname]
    name = "paged_decode_int8" if quantized else "paged_decode"
    spec = kg.kernel_registry(geom)[name]
    pages = decode_block_pages(geom["page_size"], geom["mp"])
    buf = (2, pages, geom["page_size"], geom["dh"])
    page_bytes = 1 if quantized else 4
    for ps in spec.passes:
        streams = ("k", "v") if ps.name == "weight" else ("k",)
        shapes = {sc.name: (tuple(sc.ref.shape), str(sc.ref.memory_space))
                  for sc in ps.scratch}
        want = {"sweep_state": ((2,), "smem")}
        for st in streams:
            want[f"{st}_buf"] = (buf, "vmem")
            want[f"{st}_sems"] = ((2,), "semaphore_mem")
        assert shapes == want, (ps.name, shapes)
        ws = kg.pass_working_set(ps)
        assert not {"k_pages", "v_pages"} & set(ws)
        for st in streams:
            assert ws[f"{st}_buf"] == math.prod(buf) * page_bytes
        assert ws["total"] <= kg.vmem_limit()
    assert not kg.check_kernel(spec)[0]


def test_paged_decode_copy_probe_covers_the_page_domain():
    """The decode kernel's own page copies are range-checked over every
    grid point and every page of a block; the probe table reaches both
    ends of the page-id domain, and a copy that reads one page past it
    flips the contract."""
    geom = kg.GEOMETRIES["serve-default"]
    spec = kg.kernel_registry(geom)["paged_decode"]
    weight = next(p for p in spec.passes if p.name == "weight")
    k = next(op for op in weight.inputs if op.name == "k_pages")
    ids = {kg._eval_index(k, weight, (*pt, j))[0]
           for pt in itertools.product(*map(range, weight.grid))
           for j in range(k.spec.count)}
    assert ids == {0, geom["n_pages"] - 1}
    assert not kg._input_range_violations(spec.name, weight)
    off = dataclasses.replace(k.spec, index_map=lambda *a: (
        k.spec.index_map(*a)[0] + 1, *k.spec.index_map(*a)[1:]))
    bad = dataclasses.replace(weight, inputs=tuple(
        dataclasses.replace(op, spec=off) if op is k else op
        for op in weight.inputs))
    v = kg._input_range_violations(spec.name, bad)
    assert any("'k_pages'" in x and "outside grid" in x for x in v)


# ---------------------------------------------------------------------------
# (c) Grid coverage + clamp probes
# ---------------------------------------------------------------------------


def _toy_pass(index_map):
    from jax.experimental import pallas as pl
    out = kg.Operand("o", (4, 8), pl.BlockSpec((1, 8), index_map))
    return kg.PassSpec("toy", (4, 2), (), (out,))


def test_coverage_rejects_innermost_varying_output():
    v = kg._coverage_violations("toy", _toy_pass(lambda i, k: (i + k, 0)))
    assert any("varies along the innermost" in x for x in v)


def test_coverage_rejects_double_writes_and_gaps():
    v = kg._coverage_violations("toy", _toy_pass(lambda i, k: (0, 0)))
    assert any("more than once" in x for x in v)
    assert any("covers only" in x for x in v)


def test_coverage_accepts_bijective_resident_output():
    assert not kg._coverage_violations("toy", _toy_pass(lambda i, k: (i, 0)))


def test_clamp_probe_catches_unclamped_ids():
    bad = kg.ClampProbe("identity", fn=lambda ids, lo, slab: ids,
                        lo=8, slab=8, n_pages=32, mode="mask")
    v = kg._clamp_violations("toy", bad)
    assert any("outside the slab" in x for x in v)
    good = kg.ClampProbe(
        "clamped", lo=8, slab=8, n_pages=32, mode="mask",
        fn=lambda ids, lo, slab: np.where((ids >= lo) & (ids < lo + slab),
                                          ids - lo, 0))
    assert not kg._clamp_violations("toy", good)


def test_sharded_paged_clamps_and_wire_budget():
    spec = kg.kernel_registry(TEST_GEOM)["sharded_paged"]
    violations, info = kg.check_kernel(spec)
    assert not violations
    assert info["wire_bytes"] <= spec.wire_budget
    # a KV-sized reduction (the thing the kernel exists to avoid) trips it
    g = TEST_GEOM
    kv_sized = dataclasses.replace(
        spec, reductions=spec.reductions + (kg.Reduction(
            "psum", (g["n_pages"], g["page_size"], g["kvh"], g["dh"])),))
    v, _ = kg.check_kernel(kv_sized)
    assert any("KV-sized" in x for x in v)


# ---------------------------------------------------------------------------
# Ratchet + contracts integration
# ---------------------------------------------------------------------------


def _mini_report(**over):
    rep = {
        "vmem_budget": 100, "lut_byte_budget": 1536,
        "sigma_acc_limit": SIGMA_ACC_LIMIT,
        "max_contexts": {"engine-default": 128},
        "policies": {"rexp/uint8": {"max_lk": 65793, "lut_bytes": 24,
                                    "violations": []}},
        "kernels": {"paged_decode": {"status": "ok", "vmem_bytes": 50,
                                     "violations": [],
                                     "geometries": {"test": {}}}},
    }
    rep.update(over)
    return rep


def test_ratchet_clean_on_identical_reports():
    assert not kg.ratchet_violations(_mini_report(), _mini_report())


def test_ratchet_flags_regressions():
    base = _mini_report()
    cases = {
        "vmem_budget shrank": _mini_report(vmem_budget=10),
        "overflow bound regressed": _mini_report(policies={
            "rexp/uint8": {"max_lk": 512, "lut_bytes": 24,
                           "violations": []}}),
        "LUT census grew": _mini_report(policies={
            "rexp/uint8": {"max_lk": 65793, "lut_bytes": 999,
                           "violations": []}}),
        "went ok -> violation": _mini_report(kernels={
            "paged_decode": {"status": "violation", "vmem_bytes": 50,
                             "violations": ["x"], "geometries": {"test": {}}}}),
        "VMEM working set grew": _mini_report(kernels={
            "paged_decode": {"status": "ok", "vmem_bytes": 80,
                             "violations": [], "geometries": {"test": {}}}}),
        "policy 'rexp/uint8' disappeared": _mini_report(policies={}),
        "kernel 'paged_decode' disappeared": _mini_report(kernels={}),
        "max_context[engine-default] grew": _mini_report(
            max_contexts={"engine-default": 4096}),
    }
    for needle, fresh in cases.items():
        probs = kg.ratchet_violations(base, fresh)
        assert any(needle in p for p in probs), (needle, probs)


def test_committed_report_matches_fresh_guard(fresh_report):
    """ANALYSIS_kernels.json is in sync with the code (the CI invariant)."""
    import pathlib
    committed = kg.load_report(str(
        pathlib.Path(__file__).resolve().parents[1] / kg.REPORT_NAME))
    fresh = fresh_report
    assert not kg.ratchet_violations(committed, fresh)
    assert fresh["n_violations"] == 0
    for name, p in committed["policies"].items():
        assert fresh["policies"][name]["max_lk"] == p["max_lk"]
        assert fresh["policies"][name]["lut_bytes"] == p["lut_bytes"]


def test_kernel_contracts_wrap_guard_verdicts(fresh_report):
    from repro.analysis import contracts
    results = contracts.kernel_contracts(fresh_report)
    names = {r.spec.name for r in results}
    assert "kernel/paged_decode" in names
    assert "kernel/policy/lut2d/uint8" in names
    assert "kernel/sigma-acc-limit" in names
    assert all(r.status == "ok" for r in results)
    assert all(r.spec.topology == "kernel" for r in results)


def test_acc_limit_consistency_check():
    """A kernel switching its Σ accumulator dtype trips the global check."""
    reg = kg.kernel_registry(TEST_GEOM)
    assert kg.declared_acc_limit([reg]) == SIGMA_ACC_LIMIT
    # declare an int32 accumulator: limit widens, the report flags the
    # disagreement with the constant the committed bounds derive from
    la = reg["lut_attention"]
    widened = dataclasses.replace(la, passes=tuple(
        dataclasses.replace(p, acc_dtype="int32") if p.sigma_acc else p
        for p in la.passes))
    lim = kg.declared_acc_limit([{**reg, "lut_attention": widened}])
    assert lim == SIGMA_ACC_LIMIT  # min() over ALL kernels still f32-bound
    only = {"lut_attention": widened}
    assert kg.declared_acc_limit([only]) == (1 << 31) - 1
