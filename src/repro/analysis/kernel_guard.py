"""Static VMEM / grid / overflow analysis of the LUT-attention kernels.

PR 8 made the *jitted step* statically checkable; this module does the
same for the layer the paper actually lives in — the Pallas kernels and
their LUTs.  Every kernel module in ``kernels/lut_attention/`` exports a
``kernel_spec(geom)`` declaration built from the SAME BlockSpec helpers
its launcher uses (``_specs`` / ``_grid_specs`` / ``_pool_spec`` /
``_lut_spec``), so the guard analyzes the real grids and index maps; a
kernel edit that widens a block or reroutes an index map changes the
declaration automatically.  From those declarations the guard derives:

(a) **VMEM working sets** — per pass, block bytes of every operand,
    double-buffered when the index map varies along the innermost
    (sequential) grid axis, single-copy when resident (accumulators,
    LUTs, the q block), plus the VMEM scratch a pass declares — an
    operand whose blocks the kernel copies itself (a ``PageCopy`` from
    HBM) holds no VMEM of its own, its double buffer is that scratch;
    checked against ``kernels/common.py``'s
    ``VMEM_BUDGET`` with ``VMEM_GUARD_HEADROOM`` at every declared
    dispatch geometry.

(b) **Integer-Σ overflow proof** — the Σ of the paper's integer
    numerators is accumulated in f32 (declared per pass via
    ``sigma_acc`` / ``acc_dtype``), exact only below 2^24; with table
    ceiling ``qmax`` the Σ after Lk keys is ≤ ``qmax · Lk``, so the
    derived bound is ``max_lk = acc_limit // qmax`` per policy —
    asserted ≥ every shipped serving config's ``max_context``.

(c) **Grid / index-map coverage** — enumerating the declared grid, every
    output block is written exactly once (index invariant along the
    accumulation axis, bijective over the outer axes, full coverage),
    and block-table-driven input indices (a BlockSpec's, or each of a
    ``PageCopy``'s copies of a grid step) stay inside the pool for the
    whole declared table domain; the shard_map kernels' page-id clamp
    helpers are probed at slab boundaries (mask / drop semantics).

(d) **LUT byte census** — per policy, entry counts × the paper's entry
    bytes (Tables 5 / 8 accounting), ratcheted against the ≤ 1.5 KB
    budget (``lut_builder.LUT_BYTE_BUDGET``); the uint8 2D-LUT bundle is
    the paper's "~700 Bytes" headline.

``python -m repro.analysis --check-kernels`` writes the committed
``ANALYSIS_kernels.json``; :func:`ratchet_violations` enforces that
bounds may only improve and budgets may not regress, and
``contracts.kernel_contracts`` folds the verdicts into the contract
report so the static-analysis CI job fails before a TPU ever runs a
regressed kernel.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
from typing import Callable, Mapping

import numpy as np

from repro.core import lut_builder
from repro.core.precision import SIGMA_ACC_LIMIT, F32_EXACT_LIMIT, INT32_LIMIT
from repro.kernels.common import (VMEM_BUDGET, VMEM_GUARD_HEADROOM, PageCopy,
                                  cdiv)

REPORT_VERSION = 1
REPORT_NAME = "ANALYSIS_kernels.json"

_DTYPE_BYTES = {"float32": 4, "int32": 4, "bfloat16": 2, "int8": 1}

#: accumulator dtype → largest exactly-representable integer
ACC_LIMITS = {"float32": F32_EXACT_LIMIT, "int32": INT32_LIMIT}

#: the (method, precision) grid of shipped softmax policies
POLICIES = tuple((m, p) for m in ("rexp", "lut2d")
                 for p in ("int16", "uint8", "uint4", "uint2"))


# ---------------------------------------------------------------------------
# The declaration data model (kernel modules build these in kernel_spec())
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Operand:
    """One pallas_call operand: logical array + its real BlockSpec."""

    name: str
    shape: tuple              # logical (padded) array shape
    spec: object              # pl.BlockSpec — .block_shape / .index_map
    dtype: str = "float32"
    table_indexed: bool = False   # index map reads a scalar-prefetched table
    index_domain: tuple | None = None  # declared valid table-entry range
    #                                  # (lo, hi) — hi exclusive
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class Scratch:
    """One ``scratch_shapes`` entry of a pass (``pltpu.VMEM`` / ``SMEM``
    / ``SemaphoreType``): only VMEM scratch counts toward the working
    set, at its declared shape (a double buffer declares its 2)."""

    name: str
    ref: object               # the launcher's scratch entry: .shape/.dtype


@dataclasses.dataclass(frozen=True)
class PassSpec:
    """One pallas_call of a multi-pass kernel."""

    name: str
    grid: tuple
    inputs: tuple
    outputs: tuple
    scalar_prefetch: tuple = ()   # synthetic np arrays fed to index maps
    sigma_acc: bool = False       # accumulates the integer Σ
    acc_dtype: str = "float32"    # accumulator dtype (output refs)
    scratch: tuple = ()           # Scratch entries, in launch order
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class Reduction:
    """A cross-device partial exchanged by a shard_map kernel."""

    op: str                   # 'pmax' | 'psum'
    shape: tuple
    dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class ClampProbe:
    """A page-id clamp helper of a shard_map kernel, probed numerically.

    ``fn(ids, lo, slab)`` maps physical page ids to slab-local rows.
    ``mode='mask'``: every output must land in ``[0, slab)`` (non-local
    reads hit a real row but are −inf-masked).  ``mode='drop'``:
    non-local ids must map to exactly ``slab`` (one past the end — the
    ``.at[...].set(mode='drop')`` discard row), local ones to
    ``[0, slab)``.
    """

    name: str
    fn: Callable
    lo: int
    slab: int
    n_pages: int
    mode: str                 # 'mask' | 'drop'
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One kernel module's static declaration."""

    name: str
    module: str
    kind: str                 # 'pallas' | 'shard_map'
    passes: tuple = ()        # pallas only
    reductions: tuple = ()    # shard_map cross-device partials
    clamps: tuple = ()        # shard_map page-id clamp probes
    wire_budget: int | None = None   # bytes cap on Σ reduction tensors
    notes: str = ""


# ---------------------------------------------------------------------------
# Dispatch geometries (the configurations of the one documented matrix)
# ---------------------------------------------------------------------------

#: Every geometry the guard certifies.  ``test`` is the suite /
#: contracts scale, ``serve-default`` the serve.py CLI defaults,
#: ``qwen3-32b-8k`` a production-shaped dispatch (128-head-dim GQA, 16k
#: pool pages are irrelevant to block sizes — the pool is HBM; blocks
#: stay page-sized).
GEOMETRIES: dict[str, dict] = {
    "test": dict(b=3, h=4, kvh=4, dh=16, lq=16, lk=64,
                 page_size=8, mp=8, n_pages=30, chunk=16, tp=4),
    "serve-default": dict(b=4, h=4, kvh=4, dh=64, lq=32, lk=256,
                          page_size=16, mp=16, n_pages=256, chunk=16, tp=4),
    "qwen3-32b-8k": dict(b=8, h=64, kvh=8, dh=128, lq=512, lk=8192,
                         page_size=16, mp=512, n_pages=4096, chunk=64, tp=4),
}


def kernel_registry(geom: Mapping) -> dict[str, KernelSpec]:
    """All kernel modules' declarations at one dispatch geometry.

    Modules exporting ``kernel_spec_int8`` (the paged kernels' quantized
    variants — int8 pages + f32 scale blocks) contribute that
    declaration too, so the guard proves the halved streamed VMEM and
    the scale-operand pairing on the same grids as the f32 kernels.
    """
    from repro.kernels.lut_attention import (lut_attention, paged_decode,
                                             paged_prefill, sharded_decode,
                                             sharded_paged)
    mods = (lut_attention, paged_decode, paged_prefill, sharded_decode,
            sharded_paged)
    specs = [m.kernel_spec(geom) for m in mods]
    specs += [m.kernel_spec_int8(geom) for m in mods
              if hasattr(m, "kernel_spec_int8")]
    return {s.name: s for s in specs}


# ---------------------------------------------------------------------------
# (a) VMEM working sets
# ---------------------------------------------------------------------------


def _block_bytes(op: Operand) -> int:
    return math.prod(op.spec.block_shape) * _DTYPE_BYTES[op.dtype]


def _copied(op: Operand) -> bool:
    """The kernel copies this operand's blocks itself (a ``PageCopy``):
    it stays in HBM, and its VMEM is the pass's declared scratch."""
    return isinstance(op.spec, PageCopy)


def _eval_index(op: Operand, ps: PassSpec, coords) -> tuple:
    out = op.spec.index_map(*coords, *ps.scalar_prefetch)
    return tuple(int(x) for x in out)


def _varies_innermost(op: Operand, ps: PassSpec) -> bool:
    """Does the block index change along the innermost (sequential) axis?"""
    if _copied(op):
        return False
    outer = (0,) * (len(ps.grid) - 1)
    idxs = {_eval_index(op, ps, (*outer, k)) for k in range(ps.grid[-1])}
    return len(idxs) > 1


def scratch_bytes(ps: PassSpec) -> dict[str, int]:
    """VMEM bytes of each VMEM scratch entry of a pass."""
    return {sc.name: math.prod(sc.ref.shape) * np.dtype(sc.ref.dtype).itemsize
            for sc in ps.scratch if str(sc.ref.memory_space) == "vmem"}


def pass_working_set(ps: PassSpec) -> dict:
    """Derived VMEM bytes of one pass: streamed operands double-buffered,
    resident ones (accumulators, LUTs, blocks constant along the
    sequential axis) single-copy, operands the kernel copies itself
    none (they stay in HBM), plus the declared VMEM scratch."""
    per: dict[str, int] = {}
    for op in (*ps.inputs, *ps.outputs):
        if _copied(op):
            continue
        mult = 2 if _varies_innermost(op, ps) else 1
        # the same operand may appear under one name in several roles
        # (m as input and output); count the larger footprint once
        per[op.name] = max(per.get(op.name, 0), _block_bytes(op) * mult)
    per.update(scratch_bytes(ps))
    per["total"] = sum(v for k, v in per.items() if k != "total")
    return per


def vmem_limit(budget: int = VMEM_BUDGET,
               headroom: float = VMEM_GUARD_HEADROOM) -> int:
    return int(budget * (1.0 - headroom))


# ---------------------------------------------------------------------------
# (c) Grid / index-map coverage
# ---------------------------------------------------------------------------


def _block_counts(op: Operand) -> tuple:
    return tuple(cdiv(s, b) for s, b in zip(op.shape, op.spec.block_shape))


def _coverage_violations(kname: str, ps: PassSpec) -> list[str]:
    """Every output block written exactly once, accumulated sequentially."""
    out: list[str] = []
    outer_dims, n_inner = ps.grid[:-1], ps.grid[-1]
    for op in ps.outputs:
        blocks = _block_counts(op)
        seen: dict[tuple, int] = {}
        bad = False
        for outer in itertools.product(*map(range, outer_dims)):
            idxs = {_eval_index(op, ps, (*outer, k)) for k in range(n_inner)}
            if len(idxs) != 1:
                out.append(
                    f"{kname}/{ps.name}: output {op.name!r} block index "
                    f"varies along the innermost (accumulation) axis at "
                    f"outer={outer} — the accumulator is not resident")
                bad = True
                break
            idx = next(iter(idxs))
            if len(idx) != len(blocks) or any(
                    not 0 <= c < nb for c, nb in zip(idx, blocks)):
                out.append(f"{kname}/{ps.name}: output {op.name!r} block "
                           f"index {idx} outside grid {blocks}")
                bad = True
                break
            seen[idx] = seen.get(idx, 0) + 1
        if bad:
            continue
        total = math.prod(blocks)
        multi = sorted(i for i, c in seen.items() if c > 1)
        if multi:
            out.append(f"{kname}/{ps.name}: output {op.name!r} block(s) "
                       f"written more than once: {multi[:3]}")
        if len(seen) != total:
            out.append(f"{kname}/{ps.name}: output {op.name!r} covers only "
                       f"{len(seen)}/{total} blocks")
    return out


def _input_range_violations(kname: str, ps: PassSpec) -> list[str]:
    """Every input block index in range over the whole grid — with the
    scalar-prefetched probe tables exercising the declared domain
    extremes, this is the block-table clamp proof for the paged pools."""
    out: list[str] = []
    for op in ps.inputs:
        blocks = _block_counts(op)
        if op.table_indexed and op.index_domain is None:
            out.append(f"{kname}/{ps.name}: input {op.name!r} is "
                       f"table-indexed but declares no index_domain")
            continue
        # a kernel's own copies: every one of the step's ``count`` blocks
        copies = (range(op.spec.count),) if _copied(op) else ()
        for coords in itertools.product(*map(range, ps.grid), *copies):
            idx = _eval_index(op, ps, coords)
            if len(idx) != len(blocks) or any(
                    not 0 <= c < nb for c, nb in zip(idx, blocks)):
                out.append(f"{kname}/{ps.name}: input {op.name!r} block "
                           f"index {idx} outside grid {blocks} at "
                           f"grid point {coords}")
                break
    return out


def _quant_scale_violations(kname: str, ps: PassSpec) -> list[str]:
    """Every int8 input operand must stream a float32 scale beside it.

    The quantized pools are useless without their per-token scales: a
    pass that declares an int8 ``<x>_pages`` operand but no float32
    ``<x>_scales`` operand would dequantize garbage (or skip dequant
    entirely).  Pairing is by name — the convention the kernels and the
    pool contract (``paged_cache.pool_leaf_specs``) share.
    """
    out: list[str] = []
    scales = {op.name for op in ps.inputs
              if op.dtype == "float32" and "scale" in op.name}
    for op in ps.inputs:
        if op.dtype != "int8":
            continue
        want = op.name.split("_")[0] + "_scales"
        if want not in scales:
            out.append(
                f"{kname}/{ps.name}: int8 operand {op.name!r} has no "
                f"float32 scale operand {want!r} — quantized pages must "
                f"stream their per-token scales through the same pass")
    return out


def _clamp_violations(kname: str, probe: ClampProbe) -> list[str]:
    """Numerically probe a shard_map page-id clamp at slab boundaries."""
    lo, slab, n = probe.lo, probe.slab, probe.n_pages
    ids = sorted({i for i in (0, lo - 1, lo, lo + slab // 2, lo + slab - 1,
                              lo + slab, n - 1) if 0 <= i < n})
    got = np.asarray(probe.fn(np.asarray(ids, np.int32), lo, slab))
    out: list[str] = []
    for i, g in zip(ids, got.tolist()):
        local = lo <= i < lo + slab
        if local and not 0 <= g < slab:
            out.append(f"{kname}/{probe.name}: local page {i} maps to "
                       f"row {g} outside the slab [0, {slab})")
        elif not local:
            if probe.mode == "drop" and g != slab:
                out.append(f"{kname}/{probe.name}: non-local page {i} maps "
                           f"to row {g}, want the drop row {slab}")
            if probe.mode == "mask" and not 0 <= g < slab:
                out.append(f"{kname}/{probe.name}: non-local page {i} maps "
                           f"to row {g} outside [0, {slab}) — masked reads "
                           f"must still hit a real row")
    return out


# ---------------------------------------------------------------------------
# (b) Integer-Σ overflow proof  +  (d) LUT byte census
# ---------------------------------------------------------------------------


def declared_acc_limit(registries) -> int:
    """The binding Σ-accumulator limit over every declared sigma pass.

    Scans the registries' ``sigma_acc`` passes; the limit is the
    narrowest accumulator any kernel uses.  Asserted equal to
    ``core.precision.SIGMA_ACC_LIMIT`` — if a kernel edit changes a Σ
    accumulator dtype, this recomputes and the per-policy bounds move
    (ratcheted).
    """
    limits = [ACC_LIMITS[ps.acc_dtype]
              for reg in registries for ks in reg.values()
              for ps in ks.passes if ps.sigma_acc]
    return min(limits) if limits else SIGMA_ACC_LIMIT


def shipped_max_contexts() -> dict[str, int]:
    """Every serving configuration's max keys-per-row, by source."""
    from repro.runtime.paged_cache import PagedCacheConfig
    from repro.analysis import contracts
    return {
        "engine-default": PagedCacheConfig().max_context,
        "contracts-suite": PagedCacheConfig(**contracts._CACHE).max_context,
        # benchmarks/serving_throughput.py + load_gen.py pool geometry
        "bench-serving": 10 * 8,
    }


def policy_ledger(acc_limit: int,
                  max_contexts: Mapping[str, int] | None = None) -> dict:
    """Per-policy LUT census + derived max-Lk overflow bound + verdicts."""
    ctxs = dict(max_contexts if max_contexts is not None
                else shipped_max_contexts())
    need = max(ctxs.values())
    ledger: dict[str, dict] = {}
    for method, prec in POLICIES:
        tables = (lut_builder.build_rexp_tables(prec) if method == "rexp"
                  else lut_builder.build_lut2d_tables(prec))
        census = lut_builder.table_census(tables)
        max_lk = acc_limit // census["qmax"]
        violations: list[str] = []
        if census["lut_bytes"] > lut_builder.LUT_BYTE_BUDGET:
            violations.append(
                f"{method}/{prec}: LUT census {census['lut_bytes']} B "
                f"exceeds the paper budget {lut_builder.LUT_BYTE_BUDGET} B")
        if max_lk < need:
            violations.append(
                f"{method}/{prec}: integer-Σ overflow bound max_lk="
                f"{max_lk} is below a shipped max_context "
                f"({ {k: v for k, v in ctxs.items() if v > max_lk} })")
        ledger[f"{method}/{prec}"] = {
            **census,
            "method": method,
            "max_lk": max_lk,
            "margin": max_lk - need,
            "violations": violations,
        }
    return ledger


# ---------------------------------------------------------------------------
# Per-kernel checks + the report
# ---------------------------------------------------------------------------


def check_kernel(ks: KernelSpec, limit: int | None = None) -> tuple[list, dict]:
    """(violations, info) of one kernel declaration."""
    limit = vmem_limit() if limit is None else limit
    violations: list[str] = []
    info: dict = {"kind": ks.kind}
    if ks.kind == "pallas":
        passes: dict[str, int] = {}
        for ps in ks.passes:
            ws = pass_working_set(ps)
            passes[ps.name] = ws["total"]
            if ws["total"] > limit:
                violations.append(
                    f"{ks.name}/{ps.name}: VMEM working set {ws['total']} B "
                    f"exceeds budget {limit} B "
                    f"(= VMEM_BUDGET × (1 − headroom))")
            violations += _coverage_violations(ks.name, ps)
            violations += _input_range_violations(ks.name, ps)
            violations += _quant_scale_violations(ks.name, ps)
        info["vmem_bytes"] = max(passes.values()) if passes else 0
        info["passes"] = passes
    elif ks.kind == "shard_map":
        wire = sum(math.prod(r.shape) * _DTYPE_BYTES[r.dtype]
                   for r in ks.reductions)
        info["wire_bytes"] = wire
        info["reductions"] = [f"{r.op}{list(r.shape)}" for r in ks.reductions]
        if ks.wire_budget is not None and wire > ks.wire_budget:
            violations.append(
                f"{ks.name}: reduction partials {wire} B exceed the "
                f"(B, H, Lq) wire budget {ks.wire_budget} B — a KV-sized "
                f"tensor is crossing the mesh")
        for probe in ks.clamps:
            violations += _clamp_violations(ks.name, probe)
        info["clamps"] = [p.name for p in ks.clamps]
    else:
        violations.append(f"{ks.name}: unknown kernel kind {ks.kind!r}")
    return violations, info


def check_kernels(geometries: Mapping[str, Mapping] | None = None) -> dict:
    """Run the full guard; returns the ``ANALYSIS_kernels.json`` report."""
    geoms = dict(geometries if geometries is not None else GEOMETRIES)
    limit = vmem_limit()
    registries = {name: kernel_registry(g) for name, g in geoms.items()}
    acc_limit = declared_acc_limit(registries.values())
    violations_total: list[str] = []
    if acc_limit != SIGMA_ACC_LIMIT:
        violations_total.append(
            f"declared Σ-accumulator limit {acc_limit} disagrees with "
            f"core.precision.SIGMA_ACC_LIMIT={SIGMA_ACC_LIMIT} — a kernel "
            f"changed its accumulator dtype; update the constant and the "
            f"committed bounds deliberately")

    ctxs = shipped_max_contexts()
    policies = policy_ledger(acc_limit, ctxs)

    kernels: dict[str, dict] = {}
    for gname, reg in registries.items():
        for kname, ks in reg.items():
            entry = kernels.setdefault(
                kname, {"kind": ks.kind, "geometries": {}, "violations": []})
            v, info = check_kernel(ks, limit)
            entry["geometries"][gname] = info
            entry["violations"] += [f"[{gname}] {x}" for x in v]
    for entry in kernels.values():
        entry["status"] = "ok" if not entry["violations"] else "violation"
        if entry["kind"] == "pallas":
            entry["vmem_bytes"] = max(
                g.get("vmem_bytes", 0) for g in entry["geometries"].values())

    n_viol = (len(violations_total)
              + sum(len(p["violations"]) for p in policies.values())
              + sum(len(k["violations"]) for k in kernels.values()))
    return {
        "version": REPORT_VERSION,
        "sigma_acc_limit": acc_limit,
        "vmem_budget": VMEM_BUDGET,
        "vmem_headroom": VMEM_GUARD_HEADROOM,
        "vmem_limit": limit,
        "lut_byte_budget": lut_builder.LUT_BYTE_BUDGET,
        "max_contexts": ctxs,
        "policies": policies,
        "kernels": kernels,
        "violations": violations_total,
        "n_violations": n_viol,
    }


# ---------------------------------------------------------------------------
# Ratchet + (de)serialization
# ---------------------------------------------------------------------------


def ratchet_violations(committed: dict, fresh: dict) -> list[str]:
    """Regressions of ``fresh`` against the committed kernels report.

    Bounds may only improve, budgets may not regress: policies and
    kernels may not disappear, per-policy ``max_lk`` may not decrease
    and ``lut_bytes`` may not grow, per-kernel VMEM working sets may not
    grow, ok may not become violation, and the VMEM/LUT budgets may not
    shrink out from under the committed guarantees.
    """
    out: list[str] = []
    for field in ("vmem_budget", "lut_byte_budget", "sigma_acc_limit"):
        if fresh.get(field, 0) < committed.get(field, 0):
            out.append(f"kernel-ratchet: {field} shrank "
                       f"{committed[field]} -> {fresh[field]}")
    old_ctx = committed.get("max_contexts", {})
    for name, ctx in fresh.get("max_contexts", {}).items():
        if name in old_ctx and ctx > old_ctx[name]:
            # growing a shipped context is fine only while every policy
            # still clears it — surfaced via the policy violations; note
            # the change so --update is deliberate
            out.append(f"kernel-ratchet: max_context[{name}] grew "
                       f"{old_ctx[name]} -> {ctx}; re-record with --update "
                       f"after checking the per-policy margins")
    for name, old in committed.get("policies", {}).items():
        new = fresh.get("policies", {}).get(name)
        if new is None:
            out.append(f"kernel-ratchet: policy {name!r} disappeared")
            continue
        if new["max_lk"] < old["max_lk"]:
            out.append(f"kernel-ratchet: {name} overflow bound regressed "
                       f"max_lk {old['max_lk']} -> {new['max_lk']}")
        if new["lut_bytes"] > old["lut_bytes"]:
            out.append(f"kernel-ratchet: {name} LUT census grew "
                       f"{old['lut_bytes']} -> {new['lut_bytes']} B")
        if len(new["violations"]) > len(old["violations"]):
            out.append(f"kernel-ratchet: {name} regressed to "
                       f"{new['violations']}")
    for name, old in committed.get("kernels", {}).items():
        new = fresh.get("kernels", {}).get(name)
        if new is None:
            out.append(f"kernel-ratchet: kernel {name!r} disappeared")
            continue
        if old.get("status") == "ok" and new.get("status") != "ok":
            out.append(f"kernel-ratchet: kernel {name} went ok -> "
                       f"violation: {new['violations'][:3]}")
        if new.get("vmem_bytes", 0) > old.get("vmem_bytes", 0):
            out.append(f"kernel-ratchet: kernel {name} VMEM working set "
                       f"grew {old['vmem_bytes']} -> {new['vmem_bytes']} B")
        for gname in old.get("geometries", {}):
            if gname not in new.get("geometries", {}):
                out.append(f"kernel-ratchet: kernel {name} geometry "
                           f"{gname!r} disappeared")
    return out


def load_report(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def dump_report(report: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
