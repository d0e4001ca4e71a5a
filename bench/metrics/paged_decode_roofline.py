"""Roofline share of the paged decode kernel (%): the least time the
chip needs for the decode attention dispatched in the window (the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth,
counted from the lengths served, ``bench.work``) over the summed device
time of the kernel's three passes."""

from bench import trace as T
from bench import work

#: The kernel's passes carry no name of their own in the trace (the
#: pallas_calls have no ``name=``): they are the Pallas custom calls that
#: run inside the decode-step program, which holds no other.
KERNEL = r'custom_call_target="tpu_custom_call"'
PROGRAM = r"jit_decode_sampled_fn"


def read(r):
    if r.trace is None or r.peak is None:
        return None
    s, _ = T.seconds_within(r.trace.ops, KERNEL, r.trace.modules, PROGRAM,
                            r.trace)
    flops, nbytes, rows = work.window_decode(r)
    if not s or not rows:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, r.peak) / s
