"""The reduction from a profiler trace to metrics, on a trace recorded on
a v5e chip (``testdata/flat_int8_h4.json`` says how) and on hand-made
intervals."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import trace as tr
from chipbench.readout import Readout, op_base, reader, top_level

DATA = Path(__file__).resolve().parents[1] / "testdata"
ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def meta():
    return json.loads((DATA / "flat_int8_h4.json").read_text())


@pytest.fixture(scope="module")
def small(tmp_path_factory, meta):
    """The chip trace, with the window the harness would have marked:
    from the first step program's start to the last one's end."""
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "flat_int8_h4.xplane.pb.gz").read_bytes()))
    t = tr.load(str(path))
    progs = t.programs[0]
    t.host.append(tr.Event(tr.HOST_PREFIX + "window", progs[0].start_ns - 1,
                           progs[-1].end_ns + 1))
    return t


def test_intervals_merge_and_gaps():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.covered_ns([(0, 2), (1, 3), (10, 11)]) == 4
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tr.clip([tr.Event("a", 0, 10)], 2, 5) == [(2, 5)]


def test_top_level_drops_ops_nested_in_a_loop():
    loop = tr.Event("%while.1 = ...", 0, 100)
    body = [tr.Event("%fusion.2 = ...", 10, 20),
            tr.Event("%fusion.3 = ...", 30, 40)]
    after = tr.Event("%copy.4 = ...", 100, 120)
    assert top_level([loop, *body, after]) == [loop, after]


def test_chip_trace_has_one_device_its_programs_and_the_window(small, meta):
    assert small.devices == [0]
    lo, hi = small.window()
    progs = small.programs_in(0, lo, hi)
    assert len(progs) == len(meta["kinds"])
    # the sync step is the one program that runs longer
    names = [p.name for p, k in zip(progs, meta["kinds"]) if k == "sync"]
    assert len(set(names)) == 1
    ops = small.ops_in(0, lo, hi)
    for e in ops:
        assert any(p.start_ns <= e.start_ns and e.end_ns <= p.end_ns
                   for p in progs), e.name
    busy = small.busy_ns(0, lo, hi)
    assert sum(p.dur_ns for p in progs) * 0.9 <= busy <= hi - lo


def test_kernels_are_found_by_their_own_names(small, meta):
    lo, hi = small.window()
    found = {}
    for e in small.ops_in(0, lo, hi):
        found[op_base(e)] = found.get(op_base(e), 0) + 1
    for name, n in meta["kernels"].items():
        assert found.get(name) == n, (name, found.get(name))


def test_readout_of_the_chip_trace(small, meta):
    model = json.loads((ROOT / "chipbench/configs/biglstm-vocab8.json")
                       .read_text())
    traffic = json.loads((ROOT / "chipbench/traffic/h4-int8-flat.json")
                         .read_text())
    kinds = meta["kinds"]
    tokens = [64 * 128] * len(kinds)
    r = Readout(small, kinds, tokens, [1.0] * len(kinds),
                [70.0] * len(kinds), model, traffic, 1, meta["device_kind"])
    assert len(r.spans_ms("local")) == 9 and len(r.spans_ms("sync")) == 3
    assert 0 < r.busy_s() <= r.window_s
    assert 0 <= reader("idle_share")(r) < 100
    local = reader("local_step_ms")(r)
    assert 60 < local < 80                      # 67.8 ms read by hand
    assert 35 < reader("sync_extra_ms")(r) < 55  # 112.2 - 67.8
    assert 0 < reader("mfu")(r) < 100
    assert 0 < reader("mfu.sync_step")(r) < 100
    # flat_fused_update: 12 launches, 58.9 ms; 2.170 GB each at 819 GB/s
    upd = reader("update_roofline")(r)
    assert upd == pytest.approx(100 * 2.16998e9 / 819e9 / 4.9064e-3,
                                rel=1e-3)
    assert 0 < reader("ef_encode_roofline")(r) < 100
    assert reader("batch_build_ms")(r) == 1.0
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["idle_gaps"])
    top = {k.split(" ")[0].split(".")[0] for k, _ in b["device_ops"]}
    assert "while" in top                       # the LSTMP scan's loops


def test_a_trace_without_the_window_is_refused(small):
    bare = tr.Trace(small.ops, small.programs, [])
    with pytest.raises(ValueError):
        bare.window()
