"""The harness's data files and its refusals, on the CPU."""
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

from chipbench.harness import Cell
from chipbench.models import lstm
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


def cut_problems(root: Path) -> list:
    """Where the benchmark at ``root`` breaks the cut rule, which holds for
    any family: the keys of each configuration file's ``reduced`` object
    (none where it has none) are its ``BENCHMARK.json`` entry's
    ``reduced``; and where the benchmark holds an uncut configuration
    (``reduced: []``) of the same family and source, the keys in which
    the file differs from it, ``name``, ``reduced`` and ``deployment``
    aside, are that ``reduced`` too."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    files = {c["name"]: json.loads((root / c["file"]).read_text())
             for c in bench["configs"]}
    problems = []
    for c in bench["configs"]:
        m, listed = files[c["name"]], sorted(c["reduced"])
        own = sorted(m.get("reduced") or {})
        if own != listed:
            problems.append((c["name"], "file lists", own))
        for u in bench["configs"]:
            full = files[u["name"]]
            if u["reduced"] or u["source"] != c["source"] \
                    or full.get("family") != m.get("family"):
                continue
            changed = sorted(k for k in full.keys() | m.keys()
                             if k not in ("name", "reduced", "deployment")
                             and full.get(k) != m.get(k))
            if changed != listed:
                problems.append((c["name"], f"differs from {u['name']} in",
                                 changed))
    return problems


def test_configs_list_what_they_cut():
    assert cut_problems(ROOT) == []


def _copy_data(dst: Path) -> Path:
    """The benchmark's data files (no code) under ``dst``."""
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(ROOT / "chipbench" / sub, dst / "chipbench" / sub)
    return dst


def _edit_json(path: Path, edit) -> None:
    obj = json.loads(path.read_text())
    edit(obj)
    path.write_text(json.dumps(obj, indent=1))


def test_a_file_whose_reduced_disagrees_fails_the_cut_rule(tmp_path):
    root = _copy_data(tmp_path)
    _edit_json(root / "chipbench/configs/biglstm-vocab8.json",
               lambda m: m.pop("reduced"))
    assert cut_problems(root) == [("biglstm-vocab8", "file lists", [])]


def test_a_model_key_the_program_lacks_is_refused():
    cell = Cell.load(CELLS[0])
    cell.model["experts_on_chip"] = 8
    with pytest.raises(ValueError, match="experts_on_chip") as err:
        cell.program_configs()
    assert cell.model_file in str(err.value)


def test_a_family_without_small_is_named(tmp_path, monkeypatch):
    from test_check import make_root
    src = _copy_data(tmp_path / "src")
    stub = types.ModuleType("chipbench.models.nosmall")
    stub.SMALL_LIMITS = {"loss": 1.0}
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    (src / "chipbench/configs/nosmall.json").write_text(
        json.dumps({"name": "nosmall", "family": "nosmall"}))
    _edit_json(src / "BENCHMARK.json", lambda b: b["configs"].append(
        {"name": "nosmall", "source": "https://arxiv.org/abs/1911.09030",
         "file": "chipbench/configs/nosmall.json", "reduced": [],
         "why": "a family module without small"}))
    with pytest.raises(AttributeError,
                       match=r"chipbench\.models\.nosmall does not define "
                             r"small\b"):
        make_root(tmp_path / "dst", src)


WHOLE_STEP = ("batch_build_ms", "idle_share", "local_step_ms", "mfu")


def test_a_new_configuration_and_cell_need_only_new_files(tmp_path):
    """The probe: a configuration and a one-chip cell added to a copy of
    the benchmark as new files and appended entries alone load, pass the
    cut rule and run correct on the CPU."""
    from test_check import make_root, run_cell
    src = _copy_data(tmp_path / "src")
    before = {p: p.read_bytes() for p in (src / "chipbench").rglob("*")
              if p.is_file()}
    name, cell = "probe-vocab8", "probe-vocab8.h4-int8-flat"
    configs = src / "chipbench" / "configs"
    m = json.loads((ROOT / "chipbench/configs/biglstm-vocab8.json")
                   .read_text())
    (configs / f"{name}.json").write_text(json.dumps({**m, "name": name}))
    shutil.copy(ROOT / "chipbench/limits/biglstm-vocab8.h4-int8-flat.json",
                src / "chipbench" / "limits" / f"{cell}.json")

    def append(bench):
        entry = next(c for c in bench["configs"]
                     if c["name"] == "biglstm-vocab8")
        bench["configs"].append(
            {**entry, "name": name, "file": f"chipbench/configs/{name}.json",
             "source": "https://arxiv.org/abs/1911.09030"})
        bench["workloads"].append(
            {"name": cell, "config": name, "traffic": "h4-int8-flat",
             "chips": 1, "why": "the probe: a configuration and cell added "
                                "with new files only"})
        for metric in bench["per_layer"]:
            if metric["name"] in WHOLE_STEP:
                metric["workloads"].append(cell)
    _edit_json(src / "BENCHMARK.json", append)
    for p, data in before.items():
        assert p.read_bytes() == data, p

    assert cut_problems(src) == []
    dst = make_root(tmp_path / "dst", src)
    loaded = Cell.load(cell, root=dst)
    assert loaded.model["name"] == name
    assert loaded.model["vocab_size"] == lstm.small(m)["vocab_size"]
    assert loaded.limits == lstm.SMALL_LIMITS
    assert {x["name"] for x in loaded.per_layer} == set(WHOLE_STEP)
    cfg, _, _ = loaded.program_configs()
    assert cfg.name == name
    res = run_cell(dst, cell, 2**31 + 11)
    assert res["correct"], res["checks"]


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
