"""Reduction of a JAX profiler trace to device busy time, op times and
idle gaps labelled by what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  Device operations are the
events of a device plane's ``XLA Ops`` line, programs those of its
``XLA Modules`` line, and host spans every event of the host plane.  The
measured window is the host span named ``WINDOW_SPAN``, which the
harness opens when the window opens and closes when it closes.

Run as a script on a trace to print what it holds: planes, lines and
the most frequent names with their stats.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
import sys
from collections import Counter, defaultdict

WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"[.:]\d+$")


@dataclasses.dataclass
class Event:
    name: str
    start: int   # ns
    end: int     # ns
    label: str   # name and its string stats, searched by op tables


@dataclasses.dataclass
class Trace:
    t0: int
    t1: int
    ops: list[Event]        # device operations of the first device plane
    modules: list[Event]    # device programs
    host: list[Event]
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


def find(directory: str) -> str:
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        extra = []
        for k, v in e.stats:
            if isinstance(v, str) and k in ("long_name", "tf_op", "hlo_op",
                                            "kernel_details", "name",
                                            "hlo_module", "program_name"):
                extra.append(v)
        start = int(e.start_ns)
        out.append(Event(e.name, start, start + int(e.duration_ns),
                         " ".join([e.name] + extra)))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes if p.name.startswith("/device:")
               and "TPU" in p.name.upper() or p.name.startswith("/device:TPU")]
    devices = sorted(devices, key=lambda p: p.name)
    ops: list[Event] = []
    modules: list[Event] = []
    if devices:
        for line in devices[0].lines:
            if line.name == "XLA Ops":
                ops = _events(line)
            elif line.name == "XLA Modules":
                modules = _events(line)
    host: list[Event] = []
    for p in pd.planes:
        if p.name.startswith("/host"):
            for line in p.lines:
                host.extend(_events(line))
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if spans:
        t0, t1 = spans[0].start, spans[0].end
    elif ops:
        t0, t1 = min(e.start for e in ops), max(e.end for e in ops)
    else:
        t0 = t1 = 0
    clip = lambda es: [e for e in es if e.end > t0 and e.start < t1]  # noqa
    return Trace(t0, t1, sorted(clip(ops), key=lambda e: e.start),
                 sorted(clip(modules), key=lambda e: e.start), clip(host),
                 len(devices))


def union(events: list[Event], t0: int, t1: int) -> list[tuple[int, int]]:
    """Merged busy intervals of ``events`` inside [t0, t1)."""
    merged: list[list[int]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, t0), min(e.end, t1)
        if t <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_s(tr: Trace) -> float:
    return sum(t - s for s, t in union(tr.ops, tr.t0, tr.t1)) / 1e9


def seconds(events: list[Event], pattern: str, tr: Trace) -> tuple[float, int]:
    """Summed in-window seconds and count of events whose label matches."""
    rx = re.compile(pattern)
    total, n = 0, 0
    for e in events:
        if rx.search(e.label):
            total += min(e.end, tr.t1) - max(e.start, tr.t0)
            n += 1
    return total / 1e9, n


def seconds_within(ops: list[Event], op_pattern: str,
                   modules: list[Event], module_pattern: str,
                   tr: Trace) -> tuple[float, int]:
    """Like :func:`seconds`, over the ops that start inside a program
    whose label matches ``module_pattern`` (a kernel is known by the
    program it runs in where its own name says nothing)."""
    spans = sorted((m.start, m.end) for m in modules
                   if re.search(module_pattern, m.label))
    starts = [s for s, _ in spans]
    rx = re.compile(op_pattern)
    total, n = 0, 0
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < spans[i][1] and rx.search(e.label):
            total += min(e.end, tr.t1) - max(e.start, tr.t0)
            n += 1
    return total / 1e9, n


def base_name(name: str) -> str:
    """An op's name without its number: ``%fusion.12 = ...`` → ``fusion``;
    a Pallas kernel is a ``tpu_custom_call``."""
    if 'custom_call_target="tpu_custom_call"' in name:
        return "tpu_custom_call"
    head = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


#: ops that only hold others (the scan over layers is a ``while``)
CONTAINERS = ("while", "conditional", "call")


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    by = defaultdict(int)
    for e in tr.ops:
        name = base_name(e.name)
        if name not in CONTAINERS:
            by[name] += min(e.end, tr.t1) - max(e.start, tr.t0)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in top]


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps, each named by the shortest host span
    that covers its middle (what the host was doing), else ``idle``."""
    busy = union(tr.ops, tr.t0, tr.t1)
    gaps, last = [], tr.t0
    for s, t in busy:
        if s > last:
            gaps.append((last, s))
        last = t
    if tr.t1 > last:
        gaps.append((last, tr.t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, t in gaps:
        mid = (s + t) // 2
        cover = [e for e in tr.host if e.start <= mid < e.end
                 and e.name != WINDOW_SPAN]
        name = (min(cover, key=lambda e: e.end - e.start).name
                if cover else "idle")
        out.append([name, (t - s) / 1e9])
    return out


def describe(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for p in pd.planes:
        lines = {}
        for line in p.lines:
            names = Counter(e.name for e in line.events)
            sample = {}
            for e in line.events:
                if e.name in dict(names.most_common(12)) and e.name not in sample:
                    sample[e.name] = {k: str(v)[:160] for k, v in e.stats}
            lines[line.name] = {"n": sum(names.values()),
                                "top": names.most_common(40),
                                "stats": sample}
        out[p.name] = lines
    return out


if __name__ == "__main__":
    json.dump(describe(find(sys.argv[1]) if not sys.argv[1].endswith(".pb")
                       else sys.argv[1]), sys.stdout, indent=1)
