"""Share of the paged decode kernel's device time that its two K-only
passes take: the in-window device time of ``paged_decode(_int8)_rowmax``
and ``_sum`` over that of all three passes (with ``_weight``, the σ·V
pass that also reads V).  About 2/3 where a grid step costs the same in
every pass, about 1/2 where the bytes read set the time.  None where the
passes carry no name of their own."""

from bench import trace as T


def _own(passes: str) -> str:
    # the op's own name, at the head of its label (``%paged_decode_sum.6
    # = ...``); a pass named as another op's operand does not count
    return rf"^%?paged_decode(_int8)?_({passes})(\.\d+)?( |$)"


def read(r):
    if r.trace is None:
        return None
    k, _ = T.seconds(r.trace.ops, _own("rowmax|sum"), r.trace)
    s, n = T.seconds(r.trace.ops, _own("rowmax|sum|weight"), r.trace)
    return k / s if n and s else None
