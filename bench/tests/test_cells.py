"""Cells are found by name, a new one is added by files alone, and the
benchmark refuses to run where it cannot measure."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells as C

ROOT = C.ROOT


def run_py(root, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *args], cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)


def copy_tree(tmp_path, with_program=True):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    if with_program:
        (root / "src").symlink_to(ROOT / "src")
    return root


def test_every_cell_resolves():
    bench = C.benchmark()
    for w in bench["workloads"]:
        cell = C.cell(w["name"])
        assert cell.check["logit_gap_mean_limit"] > 0
        assert C.rehearsal(cell).check["logit_gap_mean_limit"] > 0
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(C.reader(m["name"]))


def test_a_cell_is_added_by_files_alone(tmp_path):
    root = copy_tree(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    # a new mix, its check and a new per-layer metric, as files
    mix = json.loads((root / "bench/traffic/chat-short.json").read_text())
    mix["rate_rps"] *= 2
    (root / "bench/traffic/chat-double.json").write_text(json.dumps(mix))
    (root / "bench/checks/qwen3-32b-tp8.chat-double.json").write_text(
        json.dumps({"logit_gap_mean_limit": 0.012,
                    "rehearsal": {"logit_gap_mean_limit": 0.001}}))
    (root / "bench/metrics/server.requests_done.py").write_text(
        "def read(r):\n    return float(sum(x.done for x in r.records))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen3-32b-tp8.chat-double",
                               "config": "qwen3-32b-tp8",
                               "traffic": "chat-double", "chips": 1,
                               "why": "twice the rate"})
    bench["per_layer"].append({"name": "server.requests_done", "unit": "n",
                               "better": "higher", "source": "host_clock",
                               "layer": "server (runtime/server.py)",
                               "moves": "output_tok_s",
                               "workloads": ["qwen3-32b-tp8.chat-double"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for p, data in before.items():
        assert p.read_bytes() == data      # no file that was there changed
    cell = C.cell("qwen3-32b-tp8.chat-double", root=root)
    assert cell.mix["rate_rps"] == 2 * C.cell(
        "qwen3-32b-tp8.chat-short").mix["rate_rps"]
    assert [m["name"] for m in cell.per_layer][-1] == "server.requests_done"
    out = run_py(root, "--workload", "qwen3-32b-tp8.chat-double", "--seed",
                 "3", "--seconds", "2", "--trace", "1", "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert list(res)[-1] == "checks"


def test_no_tpu_no_result(tmp_path):
    out = run_py(ROOT, "--workload", "qwen3-32b-tp8.chat-short", "--seed",
                 "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_give_no_result(tmp_path):
    root = copy_tree(tmp_path, with_program=False)
    out = run_py(root, "--workload", "qwen3-32b-tp8.chat-short", "--seed",
                 "1", "--seconds", "1", "--trace", "0", "--rehearse")
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_unknown_workload_fails():
    with pytest.raises(KeyError):
        C.cell("no-such-cell")
