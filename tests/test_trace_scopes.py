"""The program's names on the device trace and the train loop's spans on
the profiler's clock.

The step programs compile as ``jit_local_step`` and ``jit_sync_step``,
their instructions carry the named scopes that ``repro.trace.scopes``
maps (per-leaf and flat, Pallas kernels in interpret mode), and a
``train_loop`` run under ``jax.profiler.trace`` writes one ``train.step``
per step with its children in order. The programs compile for two
workers on virtual CPU devices, in a subprocess (the device count is
fixed before the backend starts), so that the mean over workers is work
XLA cannot drop.
"""
import glob
import json
import os
import subprocess
import sys

import pytest

from repro.trace import TraceRecorder
from repro.trace.events import HostSpans
from repro.trace.scopes import op_scopes, scope_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE = r"""
import json
import re
import jax
from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs, train_batch_specs
from repro.trace.scopes import op_scopes, scope_of


def round_paths(text):
    # the scopes each instruction of the round (EF encode, worker mean)
    # lies in, outermost first
    out = set()
    for op in re.findall(r'op_name="([^"]*)"', text):
        if scope_of(op) in ("ef_encode", "worker_mean"):
            out.add("/".join(s for s in map(scope_of, op.split("/")) if s))
    return sorted(out)

cfg = reduced(get_arch("biglstm"), vocab=256)
shape = ShapeConfig(name="t", seq_len=16, global_batch=8, kind="train")
out = {}
for layout in ("per_leaf", "flat"):
    opt = OptimizerConfig.from_sync(
        SyncConfig(compression="int8"), name="local_adaalter", lr=0.5, H=4,
        warmup_steps=100, use_pallas=True, flat=layout == "flat")
    mesh = worker_mesh()
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer=opt.name)
        pr = build_train_programs(cfg, shape, opt, mesh, plan)
        state = jax.eval_shape(pr.init_fn, jax.random.PRNGKey(0))
        batch = train_batch_specs(cfg, shape, pr.n_workers)
        for kind, fn in (("local", pr.local_step), ("sync", pr.sync_step)):
            text = fn.lower(*state, batch).compile().as_text()
            out[f"{layout}.{kind}"] = {
                "module": text.split(",", 1)[0].split()[-1],
                "workers": pr.n_workers,
                "scopes": sorted({s for s in op_scopes(text).values() if s}),
                "round": round_paths(text)}
print("SCOPES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled():
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", COMPILE], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("SCOPES ")]
    assert line, proc.stdout + "\n" + proc.stderr
    return json.loads(line[-1][len("SCOPES "):])


MODEL_AND_UPDATE = {"embed", "lstmp", "head", "xent", "adaalter_update"}
ROUND = {"sync", "ef_encode", "worker_mean"}


@pytest.mark.parametrize("layout", ["per_leaf", "flat"])
@pytest.mark.parametrize("kind", ["local", "sync"])
def test_step_programs_are_named(compiled, layout, kind):
    prog = compiled[f"{layout}.{kind}"]
    assert prog["module"] == f"jit_{kind}_step"
    assert prog["workers"] == 2


@pytest.mark.parametrize("layout", ["per_leaf", "flat"])
def test_scopes_of_the_local_program(compiled, layout):
    found = set(compiled[f"{layout}.local"]["scopes"])
    assert MODEL_AND_UPDATE <= found
    assert not found & ROUND


@pytest.mark.parametrize("layout", ["per_leaf", "flat"])
def test_scopes_of_the_sync_program(compiled, layout):
    prog = compiled[f"{layout}.sync"]
    assert MODEL_AND_UPDATE | {"ef_encode", "worker_mean"} <= set(
        prog["scopes"])
    # the per-leaf round is all encode and mean; both lie in the round
    assert prog["round"] == ["sync/ef_encode", "sync/worker_mean"]
    if layout == "flat":                # the plane's own round bookkeeping
        assert "sync" in prog["scopes"]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(local_step)/vmap(jvp(head))/dot_general", "head"),
    ("jit(local_step)/vmap(transpose(jvp(lstmp)))/while/body/add", "lstmp"),
    ("jit(sync_step)/sync/ef_encode/pallas_call", "ef_encode"),
    ("jit(sync_step)/sync/worker_mean/reduce_sum", "worker_mean"),
    ("jit(sync_step)/sync/mul", "sync"),
    ("jit(local_step)/vmap(jvp(_worker))/mul", None),
    ("jit(head)/mul", None),          # a program's name is not a scope
    ("", None),
])
def test_scope_of_takes_the_innermost_scope(op_name, scope):
    assert scope_of(op_name) == scope


def test_op_scopes_reads_every_instruction():
    text = "\n".join([
        "HloModule jit_local_step, is_scheduled=true",
        "%fused_computation.1 (param_0: f32[4]) -> f32[4] {",
        '  %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name='
        '"jit(local_step)/vmap(jvp(head))/mul"}',
        "}",
        "ENTRY %main.2 (x.1: f32[4]) -> f32[4] {",
        '  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  ROOT %fusion.3 = f32[4]{0} fusion(%x.1), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name='
        '"jit(local_step)/vmap(transpose(jvp(xent)))/mul"}',
        "  %copy.4 = f32[4]{0} copy(%x.1)",
        # a layout copy XLA added takes the scope of what it copies
        "  %copy.5 = f32[4]{0:T(128)} copy(%fusion.3), sharding={replicated}",
        "  %copy-start.6 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%copy.5)",
        "  %copy-done.7 = f32[4]{0} copy-done(%copy-start.6)",
        "  %broadcast.8 = f32[4]{0} broadcast(%constant.9), dimensions={}",
        "}"])
    assert op_scopes(text) == {
        "mul.1": "head", "x.1": None, "fusion.3": "xent", "copy.4": None,
        "copy.5": "xent", "copy-start.6": "xent", "copy-done.7": "xent",
        "broadcast.8": None}


def test_host_spans_feed_the_recorder():
    rec = TraceRecorder()
    spans = HostSpans(rec)
    with spans.step(3, kind="local"):
        with spans("dispatch"):
            pass
        with spans("log", record="eval", step=3, loss=1.5):
            pass
        with spans("observe"):
            pass
    t0, dur = spans.since("dispatch")
    assert t0 == spans.last["dispatch"][0] and dur >= spans.last["observe"][1]
    (ev,) = rec.spans
    assert (ev.name, ev.step, ev.args) == ("eval", 3, {"loss": 1.5})
    assert (ev.t0, ev.dur) == spans.last["log"]


def _host_events(logdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("train."):
                    out.append((e.name[len("train."):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def test_train_loop_spans_land_in_a_profiler_trace(tmp_path):
    import jax

    from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
    from repro.launch.train import train_loop

    cfg = reduced(get_arch("biglstm"), vocab=128)
    shape = ShapeConfig(name="t", seq_len=8, global_batch=4, kind="train")
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, H=4,
                          warmup_steps=10)
    steps = 6
    with jax.profiler.trace(str(tmp_path / "prof")):
        res = train_loop(cfg, shape, opt, steps=steps, verbose=True,
                         log_every=2, checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=3)
    events = _host_events(str(tmp_path / "prof"))
    top = [e for e in events if e[0] == "step"]
    assert [e[3]["step_num"] for e in top] == list(range(steps))
    assert [e[3]["kind"] for e in top] == [
        "sync" if s in res.sync_steps else "local" for s in range(steps)]
    assert res.sync_steps == [3]
    for step, (_, lo, hi, _) in enumerate(top):
        children = [e[0] for e in events
                    if e[0] != "step" and lo <= e[1] and e[2] <= hi]
        want = ["batch_build", "transfer", "dispatch", "loss_read",
                "observe"]
        if step % 2 == 0 or step == steps - 1:
            want.append("log")
        if (step + 1) % 3 == 0:
            want.append("ckpt")
        assert children == want, (step, children)
    # nothing of the loop's runs outside its steps
    assert all(any(lo <= e[1] and e[2] <= hi for _, lo, hi, _ in top)
               for e in events)
