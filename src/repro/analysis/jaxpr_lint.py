"""Closed-jaxpr lint for the serving engine's traced step programs.

Three checks, all structural (no execution):

* :func:`lut_upcast_violations` — taint analysis over the paper's
  integer-Σ LUT datapath.  Equations traced inside the
  ``kernels.common.lut_int_scope()`` named scope mark their integer
  outputs as taint roots (the LUT reads and σ_int select chains);
  taint propagates forward through every equation, into and out of
  sub-jaxprs (pjit / scan / while / cond), to a fixed point (scan
  carries feed back).  An int→float ``convert_element_type`` on a
  tainted value is a violation unless it was traced inside the
  ``dequant_scope()`` — the annotated, sanctioned exits (the f32-exact
  Σ accumulator, the e·α/qmax requant, σ_int/qmax).  This is how "the
  integer datapath is never silently upcast" becomes checkable on the
  artifact instead of by numeric spot tests.

* :func:`host_callback_eqns` — host callbacks (pure/io/debug callback,
  infeed/outfeed) anywhere in a jitted step: a serving hot path must
  never bounce through Python per token.

* :func:`logits_escapes` — outputs shaped ``(…, V)`` with rank ≥ 2
  escaping a jitted step: the pipelined engine's steps must return
  token vectors, never full logits (PR 7's gate, now static).

``named_scope`` tags live on ``eqn.source_info.name_stack`` — trace-time
metadata only, so tagging changes no numerics and no compile cache keys.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import jax
import jax.numpy as jnp

from repro.kernels.common import LUT_DEQUANT_TAG, LUT_INT_TAG

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

#: primitives that cross the host boundary
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
})


def _open(jx) -> Jaxpr:
    return jx.jaxpr if isinstance(jx, ClosedJaxpr) else jx


def eqn_scopes(eqn) -> str:
    """The equation's named-scope stack as a '/'-joined string."""
    return str(eqn.source_info.name_stack)


def _sub_jaxprs(eqn) -> list[tuple[Jaxpr, list | None, list | None]]:
    """Inner jaxprs of ``eqn`` with their positional outer var slices.

    Returns ``(inner, outer_invars, outer_outvars)`` triples; a ``None``
    slice means no reliable positional correspondence (the inner jaxpr
    is then analyzed standalone, rooted only by its own tags — the
    pallas_call case, where invars are refs).
    """
    p = eqn.params
    prim = eqn.primitive.name
    out: list[tuple[Jaxpr, list | None, list | None]] = []
    if prim == "while":
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body = _open(p["body_jaxpr"])
        cond = _open(p["cond_jaxpr"])
        carry = list(eqn.invars[cn + bn:])
        out.append((body, list(eqn.invars[cn:cn + bn]) + carry,
                    list(eqn.outvars)))
        out.append((cond, list(eqn.invars[:cn]) + carry, None))
        return out
    if prim == "cond":
        for br in p["branches"]:
            out.append((_open(br), list(eqn.invars[1:]), list(eqn.outvars)))
        return out
    if prim == "pallas_call":
        jx = p.get("jaxpr")
        if jx is not None:
            out.append((_open(jx), None, None))
        return out
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        jx = p.get(key)
        if jx is None:
            continue
        inner = _open(jx)
        n_in_ok = len(inner.invars) == len(eqn.invars)
        n_out_ok = len(inner.outvars) == len(eqn.outvars)
        out.append((inner, list(eqn.invars) if n_in_ok else None,
                    list(eqn.outvars) if n_out_ok else None))
    return out


def iter_eqns(jx) -> Iterator:
    """Yield every equation, recursing into sub-jaxprs."""
    for eqn in _open(jx).eqns:
        yield eqn
        for inner, _, _ in _sub_jaxprs(eqn):
            yield from iter_eqns(inner)


@dataclasses.dataclass(frozen=True)
class UpcastViolation:
    primitive: str
    src_dtype: str
    dst_dtype: str
    shape: tuple
    name_stack: str
    kind: str = "lut"         # 'lut' (tainted Σ path) | 'int8' (KV pool)

    def __str__(self) -> str:
        return (f"{self.kind}-upcast: {self.primitive} "
                f"{self.src_dtype}{self.shape} "
                f"-> {self.dst_dtype} outside dequant scope "
                f"(scopes: {self.name_stack or '<root>'})")


def _is_var(v) -> bool:
    return not isinstance(v, Literal)


def lut_upcast_violations(jx) -> list[UpcastViolation]:
    """Untagged int→float converts reachable from the LUT integer roots."""
    tainted: set[Any] = set()
    found: dict[int, UpcastViolation] = {}

    def is_t(v) -> bool:
        return _is_var(v) and v in tainted

    def mark(v) -> bool:
        if not _is_var(v) or v in tainted:
            return False
        tainted.add(v)
        return True

    def link(src_vars, dst_vars) -> bool:
        ch = False
        for s, d in zip(src_vars, dst_vars):
            if is_t(s):
                ch |= mark(d)
        return ch

    def walk(inner: Jaxpr) -> bool:
        ch = False
        for eqn in inner.eqns:
            scopes = eqn_scopes(eqn)
            prim = eqn.primitive.name
            in_tainted = any(is_t(v) for v in eqn.invars)
            if prim == "convert_element_type" and in_tainted:
                src = eqn.invars[0].aval
                dst = eqn.outvars[0].aval
                if (jnp.issubdtype(src.dtype, jnp.integer)
                        and jnp.issubdtype(dst.dtype, jnp.floating)):
                    # taint stops at every int→float exit — sanctioned
                    # ones silently, unsanctioned ones with a finding
                    if LUT_DEQUANT_TAG not in scopes and id(eqn) not in found:
                        found[id(eqn)] = UpcastViolation(
                            primitive=prim, src_dtype=str(src.dtype),
                            dst_dtype=str(dst.dtype),
                            shape=tuple(src.shape), name_stack=scopes)
                        ch = True
                    continue
            subs = _sub_jaxprs(eqn)
            for sub, outer_in, outer_out in subs:
                if outer_in is not None:
                    ch |= link(outer_in, sub.invars)
                ch |= walk(sub)
                if outer_out is not None:
                    ch |= link(sub.outvars, outer_out)
            if LUT_INT_TAG in scopes:
                for v in eqn.outvars:
                    aval = getattr(v, "aval", None)
                    if (aval is not None and hasattr(aval, "dtype")
                            and jnp.issubdtype(aval.dtype, jnp.integer)):
                        ch |= mark(v)
            if in_tainted and not subs:
                for v in eqn.outvars:
                    ch |= mark(v)
        return ch

    top = _open(jx)
    while walk(top):
        pass
    return list(found.values())


def int8_upcast_violations(jx) -> list[UpcastViolation]:
    """Untagged int8→float converts anywhere in the program.

    The quantized KV pool stores pages as int8; the only sanctioned
    int8→float exits are the per-page dequants inside the kernels'
    ``dequant_scope()``.  Unlike :func:`lut_upcast_violations` this is
    not a taint analysis — *every* int8 source is a root, because int8
    exists in the step program only as quantized KV storage.  The src
    dtype is matched exactly (int8, not uint8/int16) so the LUT table
    reads and σ_int accumulators stay out of scope.
    """
    found = []
    for eqn in iter_eqns(jx):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        dst = eqn.outvars[0].aval
        if (str(src.dtype) == "int8"
                and jnp.issubdtype(dst.dtype, jnp.floating)):
            scopes = eqn_scopes(eqn)
            if LUT_DEQUANT_TAG not in scopes:
                found.append(UpcastViolation(
                    primitive=eqn.primitive.name, src_dtype=str(src.dtype),
                    dst_dtype=str(dst.dtype), shape=tuple(src.shape),
                    name_stack=scopes, kind="int8"))
    return found


def host_callback_eqns(jx) -> list[str]:
    """Host-callback equations anywhere in the jaxpr (recursively)."""
    out = []
    for eqn in iter_eqns(jx):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES:
            out.append(f"host-callback: {name} "
                       f"(scopes: {eqn_scopes(eqn) or '<root>'})")
    return out


def logits_escapes(jx, vocab: int) -> list[str]:
    """Top-level outputs shaped ``(…, vocab)`` with rank ≥ 2."""
    out = []
    for i, aval in enumerate(getattr(jx, "out_avals", None)
                             or [v.aval for v in _open(jx).outvars]):
        shape = tuple(getattr(aval, "shape", ()))
        if len(shape) >= 2 and shape[-1] == vocab:
            out.append(f"logits-escape: output {i} has shape {shape} "
                       f"(vocab={vocab}) — steps must return token "
                       f"vectors, not logits")
    return out


def trace_step(fn, *args, static_argnums=()) -> ClosedJaxpr:
    """Closed jaxpr of a (possibly jitted) step function."""
    return jax.make_jaxpr(fn, static_argnums=static_argnums)(*args)
