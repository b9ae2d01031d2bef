"""One run of one cell: Local AdaAlter training through ``train_loop``.

The harness drives ``repro.launch.train.train_loop``, the function that
``python -m repro.launch.train`` runs, and does not copy its loop. From
here it wraps two names that ``repro/launch/train.py`` resolves:

* ``build_train_programs``: every call of the returned ``local_step`` and
  ``sync_step`` is timestamped on entry, and the tokens it trains are
  counted from the shape of the batch it receives;
* ``make_train_batch``: timed, for the data layer.

Steps 1..H (the first local step and the first sync step, with what lies
between) are the warm-up: they compile, and they are the steps the
float32 reference follows (``check.py``). The window opens at the entry of
step H+1 and closes at the first step entry after ``seconds`` have passed;
that call is not made, which ends the job.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

# names train_loop resolves from repro.launch.train's namespace
WRAPPED = ("build_train_programs", "make_train_batch", "train_loop")


class StopWindow(Exception):
    """Raised at the first step entry after the window's time is up."""


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict
    traffic: dict
    limits: Optional[dict]
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        bench = _read_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        model = _read_json(root / configs[w["config"]]["file"])
        traffic = _read_json(root / "chipbench" / "traffic"
                             / f"{w['traffic']}.json")
        lim_path = root / "chipbench" / "limits" / f"{name}.json"
        limits = _read_json(lim_path) if lim_path.exists() else None

        def applies(m):
            return name in m.get("workloads", [name])

        return cls(name=name, chips=int(w["chips"]), model=model,
                   traffic=traffic, limits=limits,
                   end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                   per_layer=[m for m in bench["per_layer"] if applies(m)])

    @property
    def workers(self) -> int:
        return int(self.traffic["workers"])

    @property
    def H(self) -> int:
        return int(self.traffic["H"])

    def program_configs(self):
        """(ModelConfig, ShapeConfig, OptimizerConfig) of the system."""
        from repro.configs.base import (ModelConfig, OptimizerConfig,
                                        ShapeConfig, SyncConfig)
        fields = {f.name for f in dataclasses.fields(ModelConfig)}
        cfg = ModelConfig(**{k: v for k, v in self.model.items()
                             if k in fields})
        t = self.traffic
        shape = ShapeConfig(name=self.name, seq_len=int(t["seq_len"]),
                            global_batch=int(t["batch_per_worker"])
                            * self.workers, kind="train")
        wire = t["wire"]
        opt = OptimizerConfig.from_sync(
            SyncConfig(policy=t["sync_policy"],
                       compression="" if wire == "fp32" else wire),
            name=t["optimizer"], lr=float(t["lr"]), H=self.H,
            warmup_steps=int(t["warmup_steps"]), eps=float(t["eps"]),
            b0=float(t["b0"]), use_pallas=bool(t["use_pallas"]),
            flat=bool(t["flat"]))
        return cfg, shape, opt

    def job(self):
        from chipbench.reference import Job
        t = self.traffic
        return Job(model=self.model, workers=self.workers, H=self.H,
                   lr=float(t["lr"]), warmup=int(t["warmup_steps"]),
                   eps=float(t["eps"]), b0=float(t["b0"]), wire=t["wire"])


@dataclasses.dataclass
class Call:
    t: float          # host clock at entry
    kind: str         # 'local' | 'sync'
    tokens: int


class Session:
    """Runs ``train_loop`` for one cell and records what the window needs.

    ``fault`` plants one of the faults the correctness check must catch
    (``check.FAULTS``); the benchmark's own runs never set it."""

    def __init__(self, cell: Cell, seed: int, seconds: float, *,
                 trace_dir: Optional[str] = None, fault: Optional[str] = None,
                 warm_only: bool = False):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.fault = fault
        self.warm_only = warm_only
        self.calls: List[Call] = []
        self.batch_s: List[float] = []
        self.batches: List[Dict[str, np.ndarray]] = []   # steps 1..H
        self.losses: list = []                           # device scalars
        self.readings: Dict[str, np.ndarray] = {}
        self.leaves: List[str] = []
        self.t_built: Optional[float] = None   # the step programs are built
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.trace_calls: Optional[range] = None
        self._ann = None
        self._profiling = False
        self._reader = None

    # -- the loop's names ------------------------------------------------ #
    def run(self) -> None:
        import jax
        import repro.launch.train as T
        from repro.launch.mesh import worker_mesh

        missing = [n for n in WRAPPED if not hasattr(T, n)]
        if missing:
            raise RuntimeError(f"repro.launch.train no longer has {missing}; "
                               "the benchmark cannot drive train_loop")
        orig_build, orig_batch = T.build_train_programs, T.make_train_batch
        T.build_train_programs = self._build(orig_build)
        T.make_train_batch = self._batch(orig_batch)
        cfg, shape, opt = self.cell.program_configs()
        mesh = worker_mesh(devices=jax.devices()[:self.cell.chips])
        try:
            T.train_loop(cfg, shape, opt, steps=2**62, seed=self.seed,
                         mesh=mesh, verbose=False,
                         non_iid=bool(self.cell.traffic["non_iid"]))
        except StopWindow:
            pass
        finally:
            T.build_train_programs, T.make_train_batch = orig_build, orig_batch
            self._end_trace()
        if self.t_close is None:
            raise RuntimeError("train_loop returned before the window closed")

    def _build(self, orig):
        def build(cfg, shape, opt_cfg, mesh, plan):
            pr = orig(cfg, shape, opt_cfg, mesh, plan)
            local = pr.local_step
            sync = local if self.fault == "no_exchange" else pr.sync_step
            from chipbench.check import ProgramReader
            self._reader = ProgramReader(pr, self.cell.model, self.seed)
            self.t_built = time.perf_counter()
            return dataclasses.replace(
                pr, local_step=self._step("local", local),
                sync_step=self._step("sync", sync))
        return build

    def _batch(self, orig):
        def make(*args, **kwargs):
            if self.trace_dir and self._ann is not None:
                import jax
                with jax.profiler.TraceAnnotation("chipbench.batch_build"):
                    t = time.perf_counter()
                    b = orig(*args, **kwargs)
            else:
                t = time.perf_counter()
                b = orig(*args, **kwargs)
            self.batch_s.append(time.perf_counter() - t)
            if len(self.batches) < self.cell.H:
                self.batches.append({k: np.array(v) for k, v in b.items()})
            return b
        return make

    def _step(self, kind: str, fn):
        H = self.cell.H

        def step(params, opt_state, batch):
            t = time.perf_counter()
            i = len(self.calls)
            if i == H:
                self.t_open = t
            if self.t_open is not None and self._should_close(t, i):
                self.t_close = t
                raise StopWindow
            self._trace_at(i)
            self.calls.append(Call(t, kind, int(np.prod(
                batch["tokens"].shape))))
            if i == 0:
                self._settle(params)
                self.readings["init"] = self._reader.change(params, opt_state)
            if self.fault == "half_batch":
                batch = _halve(batch)
            if self.fault == "frozen":
                out = (params, opt_state, _copy_and_call(
                    fn, params, opt_state, batch)[2])
            elif self._ann is not None:
                import jax
                with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                    out = fn(params, opt_state, batch)
            else:
                out = fn(params, opt_state, batch)
            self.losses.append(out[2]["loss"])
            if i < H:
                self._capture(i + 1, out)
            return out
        return step

    def _should_close(self, t: float, i: int) -> bool:
        if self.warm_only:
            return True
        if self.trace_dir and (self.trace_calls is None
                               or i < self.trace_calls.stop):
            return False
        return t - self.t_open >= self.seconds

    # -- correctness readings of the warm-up steps ------------------------ #
    @staticmethod
    def _settle(tree) -> None:
        """Waits for the step that made ``tree``: a reading dispatched while
        the step still runs would need memory that its buffers hold."""
        import jax
        jax.block_until_ready(tree)

    def _capture(self, t: int, out) -> None:
        params, state, _ = out
        self._settle((params, state))
        H = self.cell.H
        if t == 1:
            self.readings["grad"] = self._reader.state_grad(params, state)
        if t == H - 1:
            self.readings["update"] = self._reader.change(params, state)
        if t == H:
            self.readings["sync"] = self._reader.change(params, state)
            self.leaves = self._reader.leaves

    # -- the traced sub-window ------------------------------------------- #
    TRACE_ROUNDS = 3
    TRACE_SKIP_ROUNDS = 2

    def _trace_at(self, i: int) -> None:
        """Trace TRACE_ROUNDS whole sync rounds, starting at a round
        boundary TRACE_SKIP_ROUNDS rounds into the window. The profiler
        starts one step ahead of the traced steps, so that the device is
        already traced when their first program runs."""
        if not self.trace_dir:
            return
        import jax
        H = self.cell.H
        first = H * (1 + self.TRACE_SKIP_ROUNDS)
        if self.trace_calls is None and i >= first - 1 and (i + 1) % H == 0:
            self.trace_calls = range(i + 1, i + 1 + H * self.TRACE_ROUNDS)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._profiling = True
        elif self.trace_calls is not None and i == self.trace_calls.start:
            self._ann = jax.profiler.TraceAnnotation("chipbench.window")
            self._ann.__enter__()
        elif self.trace_calls is not None and i == self.trace_calls.stop \
                and self._ann is not None:
            self._end_trace()

    def _end_trace(self) -> None:
        import jax
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._profiling:
            self._profiling = False
            jax.profiler.stop_trace()

    # -- results of the window ------------------------------------------- #
    def window_calls(self) -> List[Call]:
        return self.calls[self.cell.H:]

    def intervals_s(self) -> np.ndarray:
        w = self.window_calls()
        ts = [c.t for c in w] + [self.t_close]
        return np.diff(np.asarray(ts))

    def program_losses(self) -> np.ndarray:
        import jax.numpy as jnp
        return np.asarray(jnp.stack(self.losses), dtype=np.float64)


def _copy_and_call(fn, params, opt_state, batch):
    """The fault 'a step that returns its state unchanged': the step runs on
    copies (it donates its inputs), and its new state is dropped."""
    import jax
    import jax.numpy as jnp
    cp = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
    return fn(cp(params), cp(opt_state), batch)


def _halve(batch):
    """The fault 'half the batch left out': every worker's second half of
    rows replaced by its first half, so the mean runs over half."""
    import jax.numpy as jnp

    def one(x):
        n = x.shape[-2]
        h = x[..., : n // 2, :]
        return jnp.concatenate([h, h], axis=-2) if n % 2 == 0 else x
    return {k: one(v) for k, v in batch.items()}
