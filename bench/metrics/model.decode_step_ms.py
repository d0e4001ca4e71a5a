"""Mean device time of one decode-step program (ms): the summed device
time of the window's decode programs over their count."""

from bench import trace as T

#: the decode step program, as the trace names it
PROGRAM = r"jit_decode_sampled_fn"


def read(r):
    if r.trace is None:
        return None
    s, n = T.seconds(r.trace.modules, PROGRAM, r.trace)
    return 1e3 * s / n if n else None
