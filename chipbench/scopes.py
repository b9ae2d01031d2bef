"""Device time by the program's named scopes (``repro.trace.scopes``).

The trace names each device op by its HLO instruction and carries no op
metadata, so which layer an op belongs to comes from the compiled step
programs: built for the cell as ``train_loop`` builds them, lowered for
the cell's shapes and compiled (a load from the checkout's persistent
cache, which the run has filled), then read by
``repro.trace.scopes.op_scopes``. The map is kept on the ``Readout`` as
``op_scopes``: ``{kind: {instruction: [scope or None, result type]}}``,
so the readers of one run compile once. An op counts as found only where
its instruction's result type in the trace is the compiled one's too, so
a map from another executable finds too little and reads nothing. A
program that names no scopes, or a backend other than the TPU, gives no
map, and the readers that need it stay silent.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional

from chipbench import trace as tr
from chipbench.readout import op_name, top_level

#: the least share of a program's top-level op time whose instructions
#: the map must hold: a map from another executable reads nothing
COVERED = 0.99
KINDS = ("local", "sync")
# an instruction's name and result type, in the compiled text and in the
# name of a trace's device op
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (\S+)", re.M)


def op_scopes(r) -> Optional[Dict[str, Dict[str, list]]]:
    """The cell's ``{kind: op_scopes}``, compiled once per readout."""
    if "op_scopes" not in vars(r):
        import jax
        r.op_scopes = (program_scopes(r.model, r.traffic, r.chips)
                       if jax.default_backend() == "tpu" else None)
    return r.op_scopes


def program_scopes(model: dict, traffic: dict, chips: int
                   ) -> Optional[Dict[str, Dict[str, list]]]:
    """Compiles the cell's two step programs and maps their instructions
    to scopes; None where the program names no scopes."""
    try:
        from repro.trace.scopes import op_scopes as scopes_of
    except ImportError:
        return None
    import jax

    from chipbench.harness import Cell
    from repro.launch.mesh import resolve_plan, worker_mesh
    from repro.launch.steps import build_train_programs, train_batch_specs

    cell = Cell(name="scopes", chips=chips, model=model, traffic=traffic,
                limits=None, end_to_end=[], per_layer=[])
    cfg, shape, opt = cell.program_configs()
    mesh = worker_mesh(devices=jax.devices()[:chips])
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer=opt.name)
        pr = build_train_programs(cfg, shape, opt, mesh, plan)
        state = jax.eval_shape(pr.init_fn, jax.random.PRNGKey(0))
        batch = train_batch_specs(cfg, shape,
                                  pr.n_workers if pr.is_local else 0)
        texts = {kind: fn.lower(*state, batch).compile().as_text()
                 for kind, fn in zip(KINDS, (pr.local_step, pr.sync_step))}
    return {kind: scope_map(scopes_of(text), text)
            for kind, text in texts.items()}


def scope_map(scopes: Dict[str, Optional[str]], text: str
              ) -> Dict[str, list]:
    """``{instruction: [scope, result type]}`` of one compiled text."""
    types = dict(_HEAD.findall(text))
    return {name: [scope, types.get(name)] for name, scope in scopes.items()}


def scope_ms(r, kind: str, names) -> Optional[List[float]]:
    """For each run of the ``kind`` program on each chip the cell uses,
    the ms of its top-level ops whose instruction lies in one of the
    scopes ``names``. None without a map, where the map holds no such
    instruction, or where it finds under :data:`COVERED` of the op time."""
    maps = op_scopes(r)
    m = (maps or {}).get(kind)
    if not m or not set(names) & {scope for scope, _ in m.values()}:
        return None
    runs, total, found = [], 0, 0
    for d in r.devices:
        ops = top_level(r.trace.ops_in(d, r.lo, r.hi))
        for k, prog in r.programs[d]:
            if k != kind:
                continue
            ns = 0
            for e in ops:
                if not tr.within(e, prog.start_ns, prog.end_ns + 1):
                    continue
                total += e.dur_ns
                head = _HEAD.match(e.name)
                scope, rtype = m.get(op_name(e), (None, None))
                if head is not None and rtype == head.group(2):
                    found += e.dur_ns
                    if scope in names:
                        ns += e.dur_ns
            runs.append(ns / 1e6)
    if not runs or found < COVERED * total:
        return None
    return runs


def median_ms(r, kind: str, names) -> Optional[float]:
    runs = scope_ms(r, kind, names)
    return statistics.median(runs) if runs else None
