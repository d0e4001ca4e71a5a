"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""

from bench import trace as T


def read(r):
    tr = r.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 1.0 - T.busy_s(tr) / tr.window_s
