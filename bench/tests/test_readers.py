"""The readers of the program's own spans and device names, on
hand-made traces: host time per decode step, the decode kernel's K-pass
share, and the decode program's copies and slices.  A TPU trace labels a device op by its HLO text alone
(``%name.N = shape opcode(operands), ...``).  The readers of spans and
pass names read nothing (None) from a trace without the names, as the
program before them records."""

import types

import pytest

from bench import cells as C
from bench import trace as T

CUSTOM = 'custom_call_target="tpu_custom_call"'


def ev(name, s, e, label=None):
    return T.Event(name, s, e, label or name)


def op(name, s, e, rest="f32[8] fusion(f32[8] %p.1)"):
    """A device op as a TPU trace labels it: its HLO text."""
    text = f"%{name} = {rest}"
    return T.Event(text, s, e, text)


def read(metric, tr):
    return C.reader(metric)(types.SimpleNamespace(trace=tr))


def host_trace():
    host = [ev(T.WINDOW_SPAN, 0, 1000),
            ev("engine.step", 90, 900),            # the harness's own span
            ev("server.ingress", 100, 102),
            ev("engine.plan", 102, 107),
            ev("engine.dispatch_chunk", 107, 115),
            ev("engine.dispatch_decode", 115, 125),
            ev("engine.harvest_wait", 125, 600),   # the wait: left out
            ev("engine.resolve", 600, 605),
            ev("engine.plan", 605, 607),
            ev("engine.dispatch_decode", 607, 617),
            ev("engine.resolve", 990, 1010),       # clipped at the close
            ev("np.asarray_jax.Array_", 130, 590)]
    return T.Trace(0, 1000, [], [], host, 1)


def test_host_step_ms_is_host_self_time_per_decode_dispatch():
    got = read("engine.host_step_ms", host_trace())
    # 2 + 5 + 8 + 10 + 5 + 2 + 10 + 10 (clipped) ns over two decode steps
    assert got == pytest.approx(1e3 * 52e-9 / 2)


def test_host_step_ms_reads_nothing_without_the_spans():
    tr = host_trace()
    tr.host = [e for e in tr.host if not e.name.startswith(
        ("engine.dispatch", "engine.plan", "engine.resolve", "server."))]
    assert read("engine.host_step_ms", tr) is None


def kernel_trace(kernel="paged_decode", named=True):
    def k(p):
        return f"{kernel}_{p}" if named else "custom-call"

    ops = [
        op(k("rowmax") + ".6", 0, 30, f"f32[8] custom-call(), {CUSTOM}"),
        op(k("sum") + ".6", 30, 70,
           f"f32[8] custom-call(f32[8] %{k('rowmax')}.6), {CUSTOM}"),
        # the σ·V pass names the first two as its operands
        op(k("weight") + ".6", 70, 120,
           f"f32[8] custom-call(f32[8] %{k('rowmax')}.6, "
           f"f32[8] %{k('sum')}.6), {CUSTOM}"),
        # a prefill pass, and an op that reads a pass's output
        op("paged_prefill_sum.4", 200, 260, f"f32[8] custom-call(), {CUSTOM}"),
        op("convert_element_type.3", 120, 130,
           f"bf16[8] convert(f32[8] %{k('weight')}.6)"),
    ]
    return T.Trace(0, 1000, ops, [], [], 1)


@pytest.mark.parametrize("kernel", ["paged_decode", "paged_decode_int8"])
def test_k_pass_share_counts_each_pass_by_its_own_name(kernel):
    got = read("paged_decode_k_pass_share", kernel_trace(kernel))
    assert got == pytest.approx((30 + 40) / (30 + 40 + 50))


def test_k_pass_share_reads_nothing_from_unnamed_passes():
    assert read("paged_decode_k_pass_share", kernel_trace(named=False)) \
        is None


def program_trace(named=True):
    rowmax = "paged_decode_rowmax.6" if named else "custom-call.2"
    mods = [ev("jit_decode_sampled_fn(7)", 0, 100),
            ev("jit_chunk_sampled_fn(8)", 100, 200),
            ev("jit_decode_sampled_fn(7)", 200, 300)]
    ops = [
        op("while.1", 0, 100, "(s32[]) while((s32[]) %tuple.1)"),
        op("fusion.1", 0, 10),                               # a matmul
        op("constant_dynamic-slice_fusion.3", 10, 12,
           "f32[1,8] fusion(f32[64,8] %get-tuple-element.4)"),
        op("copy.38", 12, 24, "bf16[1,8]{1,0} copy(bf16[1,8]{0,1} "
           "%constant_dynamic-slice_fusion.3)"),
        op(rowmax, 24, 40, f"f32[8] custom-call(bf16[8] %copy.38), {CUSTOM}"),
        op("dynamic-slice_bitcast_fusion.5", 40, 48,
           "bf16[16,8] fusion(bf16[64,16,8] %get-tuple-element.7)"),
        op("bitcast_dynamic-update-slice_fusion.5", 48, 53,
           "bf16[64,16,8] fusion(bf16[64,16,8] %get-tuple-element.8)"),
        op("bitcast_add_fusion.2", 53, 80,
           "bf16[8] fusion(bf16[64,8,8] %get-tuple-element.9)"),
        op("copy-start.7", 80, 81, "(s32[8]) copy-start(s32[8] %c.1)"),
        # a chunk program's copy: not a decode program's
        op("copy.10", 100, 190, "bf16[8] copy(bf16[8] %p.2)"),
        op("copy.11", 200, 230, "bf16[8] copy(bf16[8] %p.3)"),
    ]
    return T.Trace(0, 1000, ops, mods, [], 1)


@pytest.mark.parametrize("named", [True, False], ids=["named", "unnamed"])
def test_decode_copy_slice_ms_is_copies_and_slices_per_decode_program(
        named):
    # the kernel's pass names play no part: the same with or without them
    got = read("model.decode_copy_slice_ms", program_trace(named))
    assert got == pytest.approx(1e3 * (2 + 12 + 8 + 5 + 30) * 1e-9 / 2)


def test_decode_copy_slice_ms_reads_nothing_without_a_decode_program():
    tr = program_trace()
    tr.modules = [m for m in tr.modules if "decode" not in m.name]
    assert read("model.decode_copy_slice_ms", tr) is None
