"""Device time per decode-step program (ms) of its copies and slices:
inside ``jit_decode_sampled_fn``, the ``copy`` ops XLA inserts (each
layer's ``wq`` slice re-laid out for its matmul) and the dynamic slices
and updates with which the layer scan takes each layer's weights and
pool out of the stacked arrays and writes the pool back.  The ops are
known by their opcodes, at the head of the HLO text a TPU trace labels
them with.  None where the trace holds no decode program."""

from bench import trace as T

PROGRAM = r"jit_decode_sampled_fn"
#: the op's own name, at the head of its label: ``%copy.38 = ...``,
#: ``%bitcast_dynamic-update-slice_fusion.5 = ...``
OPS = r"^%?(copy|[\w-]*dynamic-(update-)?slice[\w-]*)(\.\d+)?( |$)"


def read(r):
    tr = r.trace
    if tr is None:
        return None
    _, n = T.seconds(tr.modules, PROGRAM, tr)
    if not n:
        return None
    s, _ = T.seconds_within(tr.ops, OPS, tr.modules, PROGRAM, tr)
    return 1e3 * s / n
