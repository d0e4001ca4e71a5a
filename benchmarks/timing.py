"""Shared timing drivers for the serving benchmarks.

Every serving scenario times several *drivers* (engines/schedulers fed
the same workload) and must defend against the same two biases:

* host-side drift — whichever driver runs last inherits a warmer (or
  noisier) machine, so the order is rotated every round;
* one-off hiccups — a single pass can eat a GC pause or a page fault,
  so each driver keeps its best (min) time over N rounds.

``serving_throughput.py`` grew three copy-pasted variants of this loop;
they now all go through :func:`time_rotated`, as does the open-loop
load generator's closed-loop comparison row.
"""

from __future__ import annotations

import json
import pathlib
from typing import Any, Callable

#: a driver takes the round index (scenarios that regenerate their
#: workload per round key off it) and returns (seconds, payload)
Driver = Callable[[int], tuple[float, Any]]


def time_rotated(drivers: dict[str, Driver], *, rounds: int = 3,
                 after_round: Callable[[int, dict[str, Any]], None] | None
                 = None) -> dict[str, tuple[float, Any]]:
    """Best-of-``rounds`` with per-round order rotation.

    Runs every driver once per round, rotating which goes first, and
    keeps each driver's minimum time together with the payload from
    that best pass.  ``after_round(round_idx, payloads)`` sees every
    driver's payload from the round just finished — the hook the
    scenarios use to assert the drivers produced identical tokens
    (cheap insurance that the comparison stays apples-to-apples).

    Returns ``{name: (best_seconds, payload_at_best)}``.
    """
    if not drivers:
        raise ValueError("no drivers to time")
    if rounds < 1:
        raise ValueError(f"rounds {rounds} < 1")
    best: dict[str, tuple[float, Any]] = {
        name: (float("inf"), None) for name in drivers}
    order = list(drivers)
    for r in range(rounds):
        k = r % len(order)
        payloads: dict[str, Any] = {}
        for name in order[k:] + order[:k]:
            dt, payload = drivers[name](r)
            payloads[name] = payload
            if dt < best[name][0]:
                best[name] = (dt, payload)
        if after_round is not None:
            after_round(r, payloads)
    return best


def timings_device() -> str:
    """The device a benchmark's timings come from, as JAX reports it;
    a CPU host is labelled as such, never as a device measurement."""
    import jax

    d = jax.devices()[0]
    label = f"{d.platform}: {d.device_kind}"
    if d.platform == "cpu":
        label += (" — CPU-host timings (XLA CPU, Pallas kernels in "
                  "interpret mode), not a TPU measurement")
    return label


def merge_bench_json(path: pathlib.Path, updates: dict) -> dict:
    """Merge top-level keys into a benchmark JSON artifact.

    The serving benchmarks accrete sections (throughput sweep,
    long-prompt TTFT, shared-prefix, open-loop load) written by
    different entry points; each writer replaces only its own keys so
    running one benchmark no longer discards the others' records.
    ``timings_device`` names the device of the latest writer.
    """
    doc: dict = {}
    if path.exists():
        doc = json.loads(path.read_text())
    doc.pop("timings_device", None)
    doc = {"timings_device": timings_device(), **doc, **updates}
    path.write_text(json.dumps(doc, indent=2) + "\n")
    return doc
