"""Cells, configurations, mixes and metric readers, found by name.

``BENCHMARK.json`` names every cell.  A cell's configuration is the file
its ``configs`` entry gives, its traffic mix is ``bench/traffic/<mix>.json``,
its correctness limit ``bench/checks/<cell>.json``, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.  Adding a cell is adding
files and one ``workloads`` entry.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    check: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _load(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = _load(root / conf["file"])
    mix = _load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    check = _load(root / "bench" / "checks" / f"{name}.json")
    return Cell(name=name, chips=w["chips"], cfg=cfg, mix=mix, check=check,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(readings) -> float | None`` of a per-layer metric."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


#: a CPU rehearsal divides every length by this
REHEARSAL_SHRINK = 32
#: and runs this many layers
REHEARSAL_LAYERS = 2


def rehearsal(c: Cell) -> Cell:
    """The same cell at a tiny width and length, for the CPU: every path
    and control flow of the chip run, none of its sizes."""
    cfg = json.loads(json.dumps(c.cfg))
    tp = cfg["tensor_parallel"]
    # the cell's own init with the same total branch weight over fewer
    # layers: the layers' branch outputs add in quadrature, so each
    # layer's gains grow by sqrt(layers in the cell / layers here)
    init = cfg["assumed"]["init"]
    grow = math.sqrt(c.cfg["num_hidden_layers"] / REHEARSAL_LAYERS)
    init.update(attn_gain=init["attn_gain"] * grow,
                mlp_gain=init["mlp_gain"] * grow)
    cfg.update(hidden_size=64, head_dim=16, intermediate_size=64 * tp,
               vocab_size=512, num_hidden_layers=REHEARSAL_LAYERS)
    s = cfg["serving"]
    # float32 here, so that a sound rehearsal matches the reference to
    # roundoff; at this size the bfloat16 program's rounding and the fp8
    # control overlap, and the chip runs cover bfloat16 at the cell's size
    s.update(pool_pages=1024, weights="float32", kv_pool="float32",
             prefill_chunk=max(4, s["prefill_chunk"] // REHEARSAL_SHRINK))
    mix = json.loads(json.dumps(c.mix))

    def shrink(d, by):
        for k in ("median", "min", "max"):
            if k in d:
                d[k] = max(1, math.ceil(d[k] / by))

    for k in ("prompt", "documents"):
        if k in mix:
            shrink(mix[k], REHEARSAL_SHRINK)
    # answers shrink less: a fault in decoding shows over many steps
    shrink(mix["output"], REHEARSAL_SHRINK // 4)
    longest = (mix["prompt"]["max"] + mix["output"]["max"]
               + mix.get("documents", {}).get("max", 0))
    mix["max_context"] = math.ceil(longest / 16) * 16
    mix["n_slots"] = min(mix["n_slots"], 4)
    mix["preroll_s"] = min(mix.get("preroll_s", 0), 1.0)
    if "rate_rps" in mix:
        mix["rate_rps"] = max(mix["rate_rps"], 4.0)
    if "waiting" in mix:
        mix["waiting"] = 4
        mix["pool"] = 48
    mix["check"]["requests"] = min(mix["check"]["requests"], 4)
    # the limit of the rehearsal's own size, set from its own readings
    check = {**c.check, **c.check["rehearsal"]}
    return dataclasses.replace(c, cfg=cfg, mix=mix, check=check)
