"""Cells, configurations, architectures, mixes and metric readers, found
by name.

``BENCHMARK.json`` names every cell.  A cell's configuration is the file
its ``configs`` entry gives, and its ``bench_arch`` key names the
architecture module ``bench/arch/<bench_arch>.py`` (see :func:`arch`).
Its traffic mix is ``bench/traffic/<mix>.json``, its correctness limit
``bench/checks/<cell>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, of a new architecture too,
is adding files and one ``workloads`` entry.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import pathlib
import types

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    arch: types.ModuleType
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
    return Cell(name=name, chips=w["chips"], cfg=cfg,
                arch=arch(cfg["bench_arch"], root), mix=mix, check=check,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def _module(path: pathlib.Path, prefix: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(readings) -> float | None`` of a per-layer metric."""
    return _module(root / "bench" / "metrics" / f"{metric}.py",
                   "bench_metric_").read


@functools.cache
def _arch_module(path: pathlib.Path) -> types.ModuleType:
    # once per file, so that its jitted reference layers compile once
    return _module(path, "bench_arch_")


def arch(name: str, root: pathlib.Path = ROOT) -> types.ModuleType:
    """The architecture module ``bench/arch/<name>.py``.  It imports
    nothing of the program, and gives, for a configuration ``cfg``:

    - ``dims(cfg)``: the widths one chip runs, with at least ``vocab``
      and ``norm_eps``;
    - ``layer_kinds(cfg)``: one kind name per layer, in order;
    - ``leaves(cfg)``: ``{"top": {...}, "layers": {kind: {...}}}``, each
      leaf ``name: (shape, init std or ("const", value))``; ``top`` holds
      ``embed``, ``final_norm`` and ``head``;
    - ``reference_layer(kind, x, w, dims, softmax, control)``: one layer
      of that kind over a whole (T, D) float32 sequence, ``dims`` as
      sorted items (``bench.reference``);
    - ``decode_attention(kv_len, dims)``, ``prefill_attention(q_start, n,
      dims)``: (FLOPs, bytes) of the attention over every layer, and
      ``matmul_flops_per_token(dims)``, ``head_flops(dims)``
      (``bench.work``);
    - ``rehearse(cfg)``: ``cfg`` with its own widths cut for a CPU
      rehearsal (:func:`rehearsal`).

    Its program half, ``bench/arch/<name>_program.py`` (:func:`arch_program`),
    gives ``arch_config(cfg, dims)`` and ``tree(weights)``
    (``bench.program``)."""
    path = root / "bench" / "arch" / f"{name}.py"
    if name.endswith("_program") or not path.is_file():
        have = sorted(p.stem for p in path.parent.glob("*.py")
                      if not p.stem.endswith("_program"))
        raise KeyError(f"no architecture module {name!r} in bench/arch; "
                       f"have {have}")
    return _arch_module(path)


def arch_program(mod: types.ModuleType) -> types.ModuleType:
    """The program half of architecture module ``mod``."""
    path = pathlib.Path(mod.__file__)
    return _arch_module(path.with_name(f"{path.stem}_program.py"))


#: a CPU rehearsal divides every length by this
REHEARSAL_SHRINK = 32
#: and runs this many layers
REHEARSAL_LAYERS = 2


def rehearsal(c: Cell) -> Cell:
    """The same cell at a tiny width and length, for the CPU: every path
    and control flow of the chip run, none of its sizes."""
    cfg = json.loads(json.dumps(c.cfg))
    cfg.update(vocab_size=512, num_hidden_layers=REHEARSAL_LAYERS)
    cfg = c.arch.rehearse(cfg)
    # the cell's own init with the same total branch weight over fewer
    # layers: the layers' branch outputs add in quadrature, so each
    # layer's gains grow by sqrt(layers in the cell / layers here)
    init = cfg["assumed"]["init"]
    grow = math.sqrt(c.cfg["num_hidden_layers"] / cfg["num_hidden_layers"])
    init.update(attn_gain=init["attn_gain"] * grow,
                mlp_gain=init["mlp_gain"] * grow)
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
