"""The harness's data files and its refusals, on the CPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench.harness import Cell
from chipbench.readout import METRICS_DIR

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_its_files(name):
    cell = Cell.load(name)
    cfg, shape, opt = cell.program_configs()
    assert cfg.vocab_size == cell.model["vocab_size"]
    assert shape.global_batch == cell.workers * cell.traffic["batch_per_worker"]
    assert opt.H == cell.H and opt.flat == cell.traffic["flat"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


def test_a_cell_without_limits_is_never_correct():
    from chipbench import check
    numbers = dict.fromkeys(check.NUMBERS, 0.0)
    assert not check.verdict(numbers, None)
    assert check.verdict(numbers, dict.fromkeys(check.NUMBERS, 1.0))
    assert not check.verdict({**numbers, "loss": float("nan")},
                             dict.fromkeys(check.NUMBERS, 1.0))


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert (METRICS_DIR / f"{m['name']}.py").exists(), m["name"]
        assert m["moves"] in e2e
        for w in m.get("workloads", CELLS):
            assert w in CELLS


def test_configs_list_what_they_cut():
    for c in BENCH["configs"]:
        m = json.loads((ROOT / c["file"]).read_text())
        full = json.loads((ROOT / "chipbench/configs/biglstm.json")
                          .read_text())
        changed = sorted(k for k in full if k not in ("name", "reduced",
                                                      "deployment")
                         and full[k] != m.get(k))
        assert changed == sorted(c["reduced"]), (c["name"], changed)


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == "", p.stdout
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
