"""Faults planted in the program's timed path, to show that ``correct``
catches them.

``planted(name)`` patches the program for as long as it is open; an
engine built inside it traces the broken step.  ``bench/tests/
test_faults.py`` plants each in a CPU rehearsal, and ``bench/limits.py``
on the chip at a cell's own size.
"""

from __future__ import annotations

import contextlib

FAULTS = ("stale_kv", "altered_token")


@contextlib.contextmanager
def planted(name: str | None):
    """``stale_kv``: a decode step returns the K/V pool it was given, so
    no decoded token's K/V is kept.  ``altered_token``: every sampled
    token is the next id of the vocabulary, where it is produced."""
    if name is None:
        yield
        return
    if name == "stale_kv":
        from repro.models import transformer as mod
        attr = "decode_step_paged"
        step = mod.decode_step_paged

        def broken(params, token, pools, *args, **kw):
            logits, _ = step(params, token, pools, *args, **kw)
            return logits, pools
    elif name == "altered_token":
        from repro.runtime import engine as mod
        attr = "sample_tokens"
        step = mod.sample_tokens

        def broken(logits, *args, **kw):
            return (step(logits, *args, **kw) + 1) % logits.shape[-1]
    else:
        raise ValueError(f"no fault {name!r}; have {FAULTS}")
    setattr(mod, attr, broken)
    try:
        yield
    finally:
        setattr(mod, attr, step)
