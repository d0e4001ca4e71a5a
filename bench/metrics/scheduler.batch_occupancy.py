"""Decode batch occupancy over the window: tokens decoded over decode
steps times slots (``EngineStats.decode_tokens / (steps * n_slots)``)."""


def read(r):
    steps = r.counters["steps"]
    if not steps:
        return None
    return r.counters["decode_tokens"] / (steps * r.cell.mix["n_slots"])
