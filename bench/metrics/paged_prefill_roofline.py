"""Roofline share of the paged prefill kernel (%): the least time the
chip needs for the chunk attention dispatched in the window (FLOPs and
bytes of each chunk's rows over their causal keys, ``bench.work``) over
the summed device time of the kernel's three passes."""

from bench import trace as T
from bench import work

#: the Pallas custom calls inside the prefill-chunk programs (final chunk
#: with sampling, or not): the kernel's three passes, unnamed in the trace
KERNEL = r'custom_call_target="tpu_custom_call"'
PROGRAM = r"jit_chunk(_sampled)?_fn"


def read(r):
    if r.trace is None or r.peak is None:
        return None
    s, _ = T.seconds_within(r.trace.ops, KERNEL, r.trace.modules, PROGRAM,
                            r.trace)
    flops, nbytes, chunks, _ = work.window_prefill(r)
    if not s or not chunks:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, r.peak) / s
