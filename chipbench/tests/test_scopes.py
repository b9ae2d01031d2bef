"""The per-layer readers that sum device time by the program's named
scopes (``chipbench/scopes.py``), and the readers the benchmark already
had, on recorded v5e traces."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import scopes
from chipbench import trace as tr
from chipbench.readout import Readout, reader

DATA = Path(__file__).resolve().parents[1] / "testdata"
ROOT = Path(__file__).resolve().parents[2]
NEW = ("head_ms", "scan_ms", "update_ms", "sync_round_ms")


def _json(path):
    return json.loads(Path(path).read_text())


def _load(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{name}.xplane.pb.gz").read_bytes()))
    return tr.load(str(path))


@pytest.fixture(scope="module")
def flat_int8_h4(tmp_path_factory):
    """The trace that the benchmark's first readers were checked on, with
    the window the harness would have marked (as in test_trace.py)."""
    t = _load(tmp_path_factory, "flat_int8_h4")
    progs = t.programs[0]
    t.host.append(tr.Event(tr.HOST_PREFIX + "window", progs[0].start_ns - 1,
                           progs[-1].end_ns + 1))
    meta = _json(DATA / "flat_int8_h4.json")
    kinds = meta["kinds"]
    return Readout(t, kinds, [64 * 128] * len(kinds), [1.0] * len(kinds),
                   [70.0] * len(kinds),
                   _json(ROOT / "chipbench/configs/biglstm-vocab8.json"),
                   _json(ROOT / "chipbench/traffic/h4-int8-flat.json"), 1,
                   meta["device_kind"])


# what each reader the benchmark had read on this trace before the
# program named its scopes and the scope readers came
BEFORE = {
    "batch_build_ms": 1.0,
    "step_interval_ms_p95": 70.0,
    "idle_share": 5.946753068358701,
    "local_step_ms": 67.839596,
    "mfu": 20.71062053799076,
    "sync_extra_ms": 44.354811,
    "mfu.sync_step": 15.490515944649632,
    "update_roofline": 54.00147057538685,
    "ef_encode_roofline": 57.1343247778446,
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_readers_the_benchmark_had_read_as_before(flat_int8_h4, name):
    assert reader(name)(flat_int8_h4) == BEFORE[name]


@pytest.mark.parametrize("name", NEW)
def test_scope_readers_are_silent_without_a_map(flat_int8_h4, name):
    # the CPU compiles nothing for a chip trace: the readout has no map
    assert reader(name)(flat_int8_h4) is None
    assert flat_int8_h4.op_scopes is None


def test_scope_map_of_a_compiled_text():
    text = "\n".join([
        "HloModule jit_local_step",
        "ENTRY %main.2 (x.1: f32[4]) -> f32[4] {",
        '  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  ROOT %fusion.3 = (f32[4]{0}, f32[]) fusion(%x.1), kind=kLoop, '
        'metadata={op_name="jit(local_step)/vmap(jvp(head))/mul"}',
        "}"])
    from repro.trace.scopes import op_scopes
    assert scopes.scope_map(op_scopes(text), text) == {
        "x.1": [None, "f32[4]{0}"], "fusion.3": ["head", "(f32[4]{0},"]}


def test_program_scopes_compiles_the_cells_programs_small():
    """The cell's two programs, built and compiled as the harness's job
    builds them, at a few rows and lanes on the CPU (the kernels in
    interpret mode)."""
    model = _json(ROOT / "chipbench/configs/biglstm-vocab8.json")
    model.update(vocab_size=256, d_model=64, lstm_proj=32)
    traffic = _json(ROOT / "chipbench/traffic/h4-int8-flat.json")
    traffic.update(batch_per_worker=4, seq_len=8)
    maps = scopes.program_scopes(model, traffic, 1)
    assert set(maps) == {"local", "sync"}
    for kind, m in maps.items():
        found = {scope for scope, _ in m.values()}
        assert {"embed", "lstmp", "head", "xent", "adaalter_update"} <= found
        assert ("ef_encode" in found) == (kind == "sync")
        assert all(rtype for _, rtype in m.values())


# -- a trace recorded with the program's names on the device ------------ #
SCOPED = "flat_int8_h4_scoped"


@pytest.fixture(scope="module")
def scoped_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{SCOPED}.xplane.pb.gz").read_bytes()))
    return str(path)


@pytest.fixture(scope="module")
def scoped_meta():
    return _json(DATA / f"{SCOPED}.json")


def _scoped_readout(path, meta, op_scopes):
    r = Readout(tr.load(path), meta["kinds"], meta["tokens"],
                meta["batch_ms"], meta["interval_ms"],
                _json(ROOT / "chipbench/configs/biglstm-vocab8.json"),
                _json(ROOT / "chipbench/traffic/h4-int8-flat.json"), 1,
                meta["device_kind"])
    r.op_scopes = op_scopes
    return r


@pytest.fixture(scope="module")
def scoped(scoped_path, scoped_meta):
    return _scoped_readout(scoped_path, scoped_meta,
                           _json(DATA / f"{SCOPED}.scopes.json"))


def test_the_scoped_trace_names_its_programs(scoped, scoped_meta):
    names = [e.name.split("(")[0] for _, e in scoped.programs[0]]
    assert names == [f"jit_{k}_step" for k in scoped_meta["kinds"]]


def test_scope_readers_on_the_scoped_chip_trace(scoped, scoped_meta):
    got = {name: reader(name)(scoped) for name in NEW}
    assert got == pytest.approx(scoped_meta["readers"], rel=1e-12)


def test_the_layers_cover_most_of_the_local_step(scoped):
    local = reader("local_step_ms")(scoped)
    parts = [reader(n)(scoped) for n in ("head_ms", "scan_ms", "update_ms")]
    parts.append(scopes.median_ms(scoped, "local", {"embed"}))
    assert sum(parts) >= 0.8 * local


def test_scope_readers_are_silent_when_the_map_is_withheld(scoped_path,
                                                           scoped_meta):
    r = _scoped_readout(scoped_path, scoped_meta, None)
    assert [reader(name)(r) for name in NEW] == [None] * len(NEW)


def test_scope_readers_are_silent_below_the_covered_share(scoped_path,
                                                          scoped_meta):
    """Withholding the instruction that takes the most time of each
    program (more than 1% of it) leaves too little of the op time found."""
    maps = _json(DATA / f"{SCOPED}.scopes.json")
    r = _scoped_readout(scoped_path, scoped_meta, maps)
    from chipbench.readout import op_name, top_level
    ops = top_level(r.trace.ops_in(0, r.lo, r.hi))
    for kind in maps:
        first = next(e for k, e in r.programs[0] if k == kind)
        inside = [e for e in ops if tr.within(e, first.start_ns,
                                              first.end_ns + 1)]
        big = max(inside, key=lambda e: e.dur_ns)
        assert big.dur_ns > 0.01 * sum(e.dur_ns for e in inside)
        del maps[kind][op_name(big)]
    assert [reader(name)(r) for name in NEW] == [None] * len(NEW)


def test_a_map_of_another_executable_reads_nothing(tmp_path_factory):
    """The scoped trace's map over the older trace of the same job at
    another batch: instruction names recur, result types do not."""
    t = _load(tmp_path_factory, "flat_int8_h4")
    progs = t.programs[0]
    t.host.append(tr.Event(tr.HOST_PREFIX + "window", progs[0].start_ns - 1,
                           progs[-1].end_ns + 1))
    kinds = _json(DATA / "flat_int8_h4.json")["kinds"]
    r = Readout(t, kinds, [64 * 128] * len(kinds), [1.0] * len(kinds),
                [70.0] * len(kinds),
                _json(ROOT / "chipbench/configs/biglstm-vocab8.json"),
                _json(ROOT / "chipbench/traffic/h4-int8-flat.json"), 1,
                "TPU v5 lite")
    r.op_scopes = _json(DATA / f"{SCOPED}.scopes.json")
    assert [reader(name)(r) for name in NEW] == [None] * len(NEW)


def _train_spans(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                out.extend((e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                            {k: v for k, v in e.stats})
                           for e in line.events
                           if e.name.startswith("train."))
    return sorted(out, key=lambda x: x[1])


def test_train_steps_on_the_chip_trace(scoped, scoped_path, scoped_meta):
    # a traced step's span ends in the window: the harness opens and
    # closes the window inside a step's dispatch
    steps = [(a, b, st) for n, a, b, st in _train_spans(scoped_path)
             if n == "train.step" and scoped.lo < b <= scoped.hi]
    assert [st["kind"] for _, _, st in steps] == scoped_meta["kinds"]
    nums = [st["step_num"] for _, _, st in steps]
    assert nums == list(range(nums[0], nums[0] + len(nums)))


def test_idle_gaps_lie_under_the_loops_spans(scoped, scoped_path):
    """The idle stretches of the window are host work that `train.*`
    child spans name, the batch build most of it."""
    kids = [(n, a, b) for n, a, b, _ in _train_spans(scoped_path)
            if n != "train.step"]
    busy = tr.clip(scoped.trace.ops[0], scoped.lo, scoped.hi)
    idle, named, build = 0, 0, 0
    for s, t in tr.gaps(busy, scoped.lo, scoped.hi):
        idle += t - s
        named += tr.covered_ns(tr.clip(
            [tr.Event(n, a, b) for n, a, b in kids], s, t))
        build += tr.covered_ns(tr.clip(
            [tr.Event(n, a, b) for n, a, b in kids
             if n == "train.batch_build"], s, t))
    assert named >= 0.95 * idle and build >= 0.5 * idle
