"""The correctness check, driven end to end on the CPU at a reduced size.

Each cell's run goes through ``run.measure`` with the harness's look for a
chip skipped, at the cell's own job (layout, wire, H, workers) but reduced
widths, batch and sequence. A sound run comes out correct; the control
(calibration's reference in float8, put in the program's place) and each
planted fault of ``check.FAULTS`` that the cell can have come out not
correct. The one-chip cells are those of ``BENCHMARK.json``; the
four-worker cell runs in a subprocess with four virtual CPU devices.

Each configuration is shrunk by its family's ``small`` and each cell
judged by its family's ``SMALL_LIMITS`` (``chipbench/models/``), which say
how those limits were set.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench.reference import family_module

ROOT = Path(__file__).resolve().parents[2]
ONE_CHIP = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"] if w["chips"] == 1]
FOUR_CHIP = "biglstm.4x1-h4-fp32"


def make_root(dst: Path, src: Path = ROOT) -> Path:
    """A copy of the benchmark at ``src`` with every configuration and job
    shrunk: each configuration by its family's ``small``, each cell's
    limits its family's ``SMALL_LIMITS``."""
    (dst / "chipbench" / "limits").mkdir(parents=True)
    shutil.copytree(src / "chipbench" / "traffic",
                    dst / "chipbench" / "traffic")
    bench = json.loads((src / "BENCHMARK.json").read_text())
    # the four-worker job (traffic/4x1-h4-fp32.json) is tested here too,
    # though its cell waits for the chip runs that would prove it
    if FOUR_CHIP not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append({"name": FOUR_CHIP, "config": "biglstm",
                                   "traffic": "4x1-h4-fp32", "chips": 4,
                                   "why": "4 workers x 1 chip"})
    family = {}
    for c in bench["configs"]:
        m = json.loads((src / c["file"]).read_text())
        fam = family_module(m["family"])
        missing = [n for n in ("small", "SMALL_LIMITS") if not hasattr(fam, n)]
        if missing:
            raise AttributeError(f"family module {fam.__name__} does not "
                                 f"define {', '.join(missing)} (see "
                                 "chipbench/models/__init__.py)")
        m.update(fam.small(m))
        family[c["name"]] = fam
        p = dst / c["file"]
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(m))
    for w in bench["workloads"]:
        (dst / "chipbench" / "limits" / f"{w['name']}.json").write_text(
            json.dumps(family[w["config"]].SMALL_LIMITS))
    for p in (dst / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        t.update(batch_per_worker=8, seq_len=16 if t["seq_len"] > 20 else 5)
        p.write_text(json.dumps(t))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run_cell(root: Path, name: str, seed: int, fault=None,
             control=False) -> dict:
    """A run's result; with ``control``, calibration's comparison of the
    float8 control with the reference, judged by the cell's limits."""
    import jax

    from chipbench import calibrate, check, run
    from chipbench.harness import Cell
    cell = Cell.load(name, root=root)
    devices = jax.devices()[:cell.chips]
    if control:
        _, batches = calibrate.program(cell, seed)
        ref = calibrate.reference(cell, devices, seed, batches)
        numbers = check.compare(
            calibrate.control(cell, devices, seed, batches), ref)
        return {"correct": check.verdict(numbers, cell.limits),
                "checks": check.report(numbers, cell.limits)}
    return run.measure(cell, seed, 0.5, False, jax.devices(),
                       time.perf_counter(), fault=fault)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(root, name):
    res = run_cell(root, name, 2**31 + 7)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"])[-1] == "window_compiles"


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_fault_is_not_correct(root, name, fault):
    res = run_cell(root, name, 11, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(root, name):
    res = run_cell(root, name, 11, control=True)
    assert not res["correct"], res["checks"]


FOUR = """
import json, sys
from pathlib import Path
sys.path[:0] = [{src!r}, {root!r}]
sys.path.insert(0, {tests!r})
from test_check import run_cell
out = {{}}
for what in ("sound", "no_exchange", "half_batch", "control"):
    res = run_cell(Path({bench!r}), {name!r}, 5,
                   fault=what if what in ("no_exchange", "half_batch") else None,
                   control=what == "control")
    out[what] = res["correct"]
print(json.dumps(out))
"""


def test_four_workers_sound_and_faults(root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = FOUR.format(src=str(ROOT / "src"), root=str(ROOT),
                       tests=str(Path(__file__).parent), bench=str(root),
                       name=FOUR_CHIP)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False,
                   "half_batch": False, "control": False}, out
