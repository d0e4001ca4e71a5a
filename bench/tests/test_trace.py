"""The trace reduction: busy union, op sums, idle gaps and their labels,
on a small trace recorded here and on hand-made events."""

import jax
import jax.numpy as jnp
import pytest

from bench import trace as T


def ev(name, s, e, label=None):
    return T.Event(name, s, e, label or name)


def hand_trace():
    ops = [ev("_pg_rowmax_kernel.1", 100, 200),
           ev("_pg_sum_kernel.2", 150, 300),        # overlaps the first
           ev("fusion.7", 400, 450),
           ev("_pg_weight_kernel", 900, 1000),
           ev("_pf_rowmax_kernel", 990, 1200)]     # runs past the window
    host = [ev("engine.step", 280, 950), ev("harvest", 460, 880),
            ev(T.WINDOW_SPAN, 0, 1100)]
    mods = [ev("jit_decode_sampled_fn(3)", 100, 450),
            ev("jit_chunk_sampled_fn(4)", 900, 1200)]
    return T.Trace(0, 1100, ops, mods, host, 1)


def test_busy_is_the_union_inside_the_window():
    tr = hand_trace()
    assert T.union(tr.ops, tr.t0, tr.t1) == [(100, 300), (400, 450),
                                               (900, 1100)]
    assert T.busy_s(tr) == pytest.approx((200 + 50 + 200) / 1e9)


def test_kernel_seconds_by_name_table():
    tr = hand_trace()
    s, n = T.seconds(tr.ops, r"_pg_(rowmax|sum|weight)_kernel", tr)
    assert n == 3 and s == pytest.approx((100 + 150 + 100) / 1e9)
    s, n = T.seconds(tr.ops, r"_pf_(rowmax|sum|weight)_kernel", tr)
    assert n == 1 and s == pytest.approx(110 / 1e9)   # clipped at t1
    s, n = T.seconds(tr.modules, r"decode_sampled_fn", tr)
    assert n == 1 and s == pytest.approx(350 / 1e9)


def test_top_ops_merge_numbered_instances():
    tr = T.Trace(0, 100, [ev("fusion.1", 0, 10), ev("fusion.2", 20, 40),
                          ev("copy", 50, 55)], [], [], 1)
    assert T.top_ops(tr) == [["fusion", 30 / 1e9], ["copy", 5 / 1e9]]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = hand_trace()
    gaps = T.idle_gaps(tr)
    # gaps: [0,100), [300,400), [450,900)
    assert gaps[0] == ["harvest", 450 / 1e9]
    assert gaps[1] == ["idle", 100 / 1e9]
    assert gaps[2] == ["engine.step", 100 / 1e9]


def test_reads_a_recorded_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("engine.step"):
            jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(T.find(str(tmp_path)))
    assert tr.window_s > 0
    assert any(e.name == "engine.step" for e in tr.host)
    # the CPU has no device plane: nothing is read as device time
    assert tr.n_devices == 0 and tr.ops == []
    # the whole window is idle, and named by what the host was doing
    assert T.idle_gaps(tr)[0][0] != "idle"
